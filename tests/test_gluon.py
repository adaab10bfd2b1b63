"""Gluon tests — mirrors reference tests/python/unittest/test_gluon*.py."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, autograd
from mxnet_tpu.gluon import nn


def test_dense_forward():
    layer = nn.Dense(4, in_units=3)
    layer.initialize(mx.initializer.One())
    x = nd.ones((2, 3))
    out = layer(x)
    np.testing.assert_allclose(out.asnumpy(), 3.0)


def test_deferred_init():
    layer = nn.Dense(5)
    layer.initialize()
    out = layer(nd.ones((2, 7)))
    assert out.shape == (2, 5)
    assert layer.weight.shape == (5, 7)


def test_sequential_and_collect_params():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu"), nn.Dense(2))
    net.initialize()
    out = net(nd.ones((4, 3)))
    assert out.shape == (4, 2)
    params = net.collect_params()
    assert len(list(params.keys())) == 4


def test_conv_block():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(8, 3, padding=1, activation="relu"),
                nn.MaxPool2D(),
                nn.Flatten(),
                nn.Dense(4))
    net.initialize(mx.initializer.Xavier())
    out = net(nd.ones((2, 3, 8, 8)))
    assert out.shape == (2, 4)


def test_batchnorm_layer():
    bn = nn.BatchNorm()
    bn.initialize()
    x = nd.array(np.random.randn(4, 3, 5, 5).astype("float32"))
    with autograd.record():
        out = bn(x)
    assert out.shape == x.shape
    assert abs(bn.running_mean.data().asnumpy()).sum() > 0


def test_gluon_trainer_convergence():
    np.random.seed(0)
    mx.random.seed(0)
    X = np.random.randn(128, 10).astype("float32")
    w = np.random.randn(10, 3).astype("float32")
    y = (X @ w).argmax(1).astype("float32")
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"), nn.Dense(3))
    net.initialize(mx.initializer.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.5, "momentum": 0.9})
    data, label = nd.array(X), nd.array(y)
    for _ in range(40):
        with autograd.record():
            loss = loss_fn(net(data), label)
        loss.backward()
        trainer.step(128)
    acc = (net(data).asnumpy().argmax(1) == y).mean()
    assert acc > 0.95, acc


def test_save_load_params(tmp_path):
    fname = str(tmp_path / "p.npz")
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8), nn.Dense(2))
    net.initialize(mx.initializer.Xavier())
    x = nd.ones((1, 4))
    ref = net(x).asnumpy()
    net.save_params(fname)
    net2 = nn.HybridSequential()
    with net2.name_scope():
        net2.add(nn.Dense(8), nn.Dense(2))
    net2.load_params(fname)
    np.testing.assert_allclose(net2(x).asnumpy(), ref, rtol=1e-6)


def test_hybridize_matches_imperative():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(mx.initializer.Xavier())
    x = nd.array(np.random.randn(8, 6).astype("float32"))
    ref = net(x).asnumpy()
    net.hybridize()
    out = net(x).asnumpy()
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_losses():
    pred = nd.array([[1.0, 2.0], [3.0, 4.0]])
    label = nd.array([0.0, 1.0])
    l = gluon.loss.SoftmaxCrossEntropyLoss()(pred, label)
    assert l.shape == (2,)
    expect = -np.log(np.exp([1.0, 4.0]) /
                     np.exp([[1, 2], [3, 4]]).sum(1))
    np.testing.assert_allclose(l.asnumpy(), expect, rtol=1e-5)
    l2 = gluon.loss.L2Loss()(nd.array([1.0, 2.0]), nd.array([0.0, 0.0]))
    np.testing.assert_allclose(l2.asnumpy(), [0.5, 2.0])
    l1 = gluon.loss.L1Loss()(nd.array([1.0, -2.0]), nd.array([0.0, 0.0]))
    np.testing.assert_allclose(l1.asnumpy(), [1.0, 2.0])


def test_lstm_cell_shapes():
    cell = gluon.rnn.LSTMCell(16)
    cell.initialize()
    x = nd.ones((4, 8))
    states = cell.begin_state(4)
    out, new_states = cell(x, states)
    assert out.shape == (4, 16)
    assert cell.i2h_weight.shape == (64, 8)
    assert len(new_states) == 2


def test_gru_cell():
    cell = gluon.rnn.GRUCell(8)
    cell.initialize()
    out, states = cell(nd.ones((2, 4)), cell.begin_state(2))
    assert out.shape == (2, 8)
    assert cell.i2h_weight.shape == (24, 4)


def test_rnn_unroll_and_layer():
    cell = gluon.rnn.LSTMCell(8)
    cell.initialize()
    seq = [nd.ones((2, 4)) for _ in range(5)]
    outs, states = cell.unroll(5, seq)
    assert len(outs) == 5 and outs[0].shape == (2, 8)
    lstm = gluon.rnn.LSTM(8, num_layers=2)
    lstm.initialize()
    out = lstm(nd.ones((5, 2, 4)))
    assert out.shape == (5, 2, 8)


def test_bidirectional_cell():
    bi = gluon.rnn.BidirectionalCell(gluon.rnn.LSTMCell(4),
                                     gluon.rnn.LSTMCell(4))
    bi.initialize()
    outs, states = bi.unroll(3, [nd.ones((2, 5))] * 3)
    assert outs[0].shape == (2, 8)  # concat of both directions


def test_lstm_learns_dependency():
    np.random.seed(1)
    mx.random.seed(1)
    T, N, C = 6, 64, 4
    seq = np.random.randn(T, N, C).astype("float32")
    lab = (seq.sum(axis=(0, 2)) > 0).astype("float32")

    class Head(gluon.Block):
        def __init__(self):
            super().__init__()
            self.lstm = gluon.rnn.LSTM(16)
            self.out = nn.Dense(2)

        def forward(self, x):
            h = self.lstm(x)
            return self.out(h[-1])

    head = Head()
    head.initialize(mx.initializer.Xavier())
    tr = gluon.Trainer(head.collect_params(), "adam",
                       {"learning_rate": 0.02})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    sq, lb = nd.array(seq), nd.array(lab)
    for _ in range(40):
        with autograd.record():
            loss = loss_fn(head(sq), lb)
        loss.backward()
        tr.step(N)
    acc = (head(sq).asnumpy().argmax(1) == lab).mean()
    assert acc > 0.9, acc


def test_dataset_dataloader():
    X = np.arange(40).reshape(10, 4).astype("float32")
    y = np.arange(10).astype("float32")
    ds = gluon.data.ArrayDataset(X, y)
    assert len(ds) == 10
    loader = gluon.data.DataLoader(ds, batch_size=3, shuffle=False)
    batches = list(loader)
    assert len(batches) == 4
    data, label = batches[0]
    assert data.shape == (3, 4) and label.shape == (3,)
    loader2 = gluon.data.DataLoader(ds, batch_size=3, last_batch="discard")
    assert len(list(loader2)) == 3


def test_dataloader_prefetch_close_joins_worker():
    """Abandoning a prefetching DataLoader mid-epoch must not leak its
    staging thread (the PR 2/9 teardown contract — mxlint MX006
    regression): close() stops and joins the worker with a timeout."""
    X = np.arange(40).reshape(10, 4).astype("float32")
    y = np.arange(10).astype("float32")
    loader = gluon.data.DataLoader(gluon.data.ArrayDataset(X, y),
                                   batch_size=2, prefetch=2)
    it = iter(loader)
    next(it)  # worker running, queue filling
    thread = it._thread
    assert thread.is_alive()
    it.close(timeout=5)
    assert not thread.is_alive()
    with pytest.raises(StopIteration):
        next(it)


def test_dataloader_prefetch_full_epoch_after_close_of_other_iter():
    """close() on one epoch's iterator leaves the loader reusable."""
    X = np.arange(40).reshape(10, 4).astype("float32")
    y = np.arange(10).astype("float32")
    loader = gluon.data.DataLoader(gluon.data.ArrayDataset(X, y),
                                   batch_size=2, prefetch=2)
    first = iter(loader)
    next(first)
    first.close()
    assert len(list(loader)) == 5


def test_split_and_load():
    arr = nd.array(np.arange(12).reshape(6, 2).astype("float32"))
    parts = gluon.utils.split_data(arr, 3)
    assert len(parts) == 3 and parts[0].shape == (2, 2)
    loaded = gluon.utils.split_and_load(arr, [mx.cpu()])
    assert loaded[0].shape == (6, 2)


def test_clip_global_norm():
    arrays = [nd.ones((2, 2)) * 3, nd.ones((3,)) * 4]
    norm = gluon.utils.clip_global_norm(arrays, 1.0)
    total = sum(float((a * a).sum().asscalar()) for a in arrays)
    assert abs(total - 1.0) < 1e-4


def test_gluon_transformer_block_trains():
    """Gluon face of the transformer family (nn.MultiHeadAttention /
    nn.TransformerBlock) trains a tiny LM with Trainer."""
    from mxnet_tpu import autograd

    rs = np.random.RandomState(0)
    toks = rs.randint(0, 16, (128, 8)).astype("float32")
    labels = ((3 * toks + 1) % 16).astype("int64")

    net = nn.Sequential()
    net.add(nn.Embedding(16, 16))
    net.add(nn.TransformerBlock(16, 2))
    net.add(nn.Dense(16, flatten=False))
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.02})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    first = last = None
    for epoch in range(3):  # epoch totals 7.99, 2.00, 0.20: the bar is half
        total = 0.0
        for i in range(0, 128, 32):
            x = mx.nd.array(toks[i:i + 32])
            y = mx.nd.array(labels[i:i + 32].reshape(-1))
            with autograd.record():
                out = net(x).reshape((-1, 16))
                loss = loss_fn(out, y)
            loss.backward()
            trainer.step(32)
            total += float(loss.asnumpy().mean())
        if first is None:
            first = total
        last = total
    assert last < first * 0.5, (first, last)


def test_gluon_mha_matches_symbolic_op():
    rs = np.random.RandomState(1)
    x = rs.randn(2, 5, 8).astype("float32")
    layer = nn.MultiHeadAttention(num_heads=2)
    layer.initialize(mx.init.Xavier())
    out = layer(mx.nd.array(x))
    ref = mx.nd.MultiHeadAttention(
        mx.nd.array(x), layer.in_weight.data(), layer.in_bias.data(),
        layer.out_weight.data(), layer.out_bias.data(),
        num_heads=2).asnumpy()
    np.testing.assert_allclose(out.asnumpy(), ref, rtol=1e-5, atol=1e-6)


def test_symbol_block_from_checkpoint(tmp_path):
    """SymbolBlock wraps a symbolic checkpoint as a Gluon layer and is
    trainable through the tape."""
    # train + save a symbolic net
    rs = np.random.RandomState(0)
    X = rs.rand(64, 8).astype("float32")
    W = rs.rand(8, 3).astype("float32")
    y = (X @ W).argmax(1).astype("float32")
    data = mx.sym.Variable("data")
    net_sym = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(
            mx.sym.Activation(mx.sym.FullyConnected(
                data, num_hidden=16, name="fc1"), act_type="relu"),
            num_hidden=3, name="fc2"), name="softmax")
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod = mx.mod.Module(net_sym, context=mx.cpu())
    mod.fit(it, num_epoch=3, initializer=mx.init.Xavier(),
            optimizer_params={"learning_rate": 0.5})
    prefix = str(tmp_path / "sb")
    mod.save_checkpoint(prefix, 3)

    # import WITHOUT the loss head: take the fc2 output
    feat_sym = net_sym.get_internals()["fc2_output"] \
        if hasattr(net_sym, "get_internals") else None
    if feat_sym is None:
        feat_sym = net_sym
    block = gluon.SymbolBlock.imports(prefix + "-symbol.json", "data",
                                      prefix + "-0003.params")
    out = block(mx.nd.array(X[:8]))
    # matches the module's forward
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(X[:8])],
                                label=[mx.nd.zeros((8,))]),
                is_train=False)
    np.testing.assert_allclose(out.asnumpy(),
                               mod.get_outputs()[0].asnumpy(),
                               rtol=1e-4, atol=1e-5)

    # training THROUGH a zero-fed loss head must refuse (wrong grads)
    with pytest.raises(mx.MXNetError, match="label"):
        with autograd.record():
            block(mx.nd.array(X[:8]))

    # headless import (reference style: get_internals) trains on the tape
    head = mx.sym.load(prefix + "-symbol.json")
    feat = head.get_internals()["fc2_output"]
    fblock = gluon.SymbolBlock(feat, mx.sym.Variable("data"))
    loaded = mx.nd.load(prefix + "-0003.params")
    for k, v in loaded.items():
        name = k.split(":", 1)[1]
        if name in fblock.params:
            fblock.params[name].set_data(v)
    with autograd.record():
        o = fblock(mx.nd.array(X[:8]))
        loss = nd.sum(o * o)
    loss.backward()
    g = fblock.params["fc1_weight"].grad()
    assert np.abs(g.asnumpy()).sum() > 0

    # non-Variable inputs are rejected with a clear error
    with pytest.raises(mx.MXNetError, match="Variables"):
        gluon.SymbolBlock(feat, head.get_internals()["fc1_output"])


def test_random_sampler_replayable_across_instances():
    from mxnet_tpu.gluon.data import RandomSampler

    # same seed => same epoch orders; global np.random traffic between
    # draws must not perturb the stream
    a, b = RandomSampler(32, seed=5), RandomSampler(32, seed=5)
    first = list(a)
    np.random.seed(0)
    assert first == list(b)
    assert sorted(first) == list(range(32))
    assert list(a) != first  # epochs reshuffle
