"""Symbolic RNN toolkit + BucketingModule (reference tests:
``tests/python/unittest/test_rnn.py``, ``tests/python/train/test_bucketing.py``)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops.rnn_ops import rnn_param_size


def test_rnn_cell_unroll_shapes():
    cell = mx.rnn.RNNCell(10, prefix="rnn_")
    outputs, states = cell.unroll(3, inputs=mx.sym.Variable("data"),
                                  layout="NTC", merge_outputs=True)
    ex = outputs.simple_bind(mx.cpu(), data=(2, 3, 7))
    ex.forward(is_train=False)
    assert ex.outputs[0].shape == (2, 3, 10)
    names = set(outputs.list_arguments())
    assert {"rnn_i2h_weight", "rnn_i2h_bias",
            "rnn_h2h_weight", "rnn_h2h_bias"} <= names


def test_lstm_gru_cell_unroll_match_numpy():
    """Unrolled symbolic LSTM/GRU match an explicit numpy recurrence."""
    def sigmoid(x):
        return 1 / (1 + np.exp(-x))

    T, N, I, H = 4, 2, 3, 5
    rs = np.random.RandomState(1)
    x = rs.randn(N, T, I).astype("float32")

    for mode in ("lstm", "gru"):
        cell = mx.rnn.LSTMCell(H, prefix="l_") if mode == "lstm" else \
            mx.rnn.GRUCell(H, prefix="l_")
        outputs, _ = cell.unroll(T, inputs=mx.sym.Variable("data"),
                                 merge_outputs=True)
        ex = outputs.simple_bind(mx.cpu(), data=(N, T, I))
        params = {}
        for name, arr in ex.arg_dict.items():
            if name != "data":
                params[name] = rs.uniform(-0.4, 0.4,
                                          arr.shape).astype("float32")
                arr[:] = params[name]
        ex.arg_dict["data"][:] = x
        ex.forward(is_train=False)
        out = ex.outputs[0].asnumpy()

        wi, bi = params["l_i2h_weight"], params["l_i2h_bias"]
        wh, bh = params["l_h2h_weight"], params["l_h2h_bias"]
        h = np.zeros((N, H), "float64")
        c = np.zeros((N, H), "float64")
        ref = np.zeros((N, T, H), "float64")
        for t in range(T):
            pre_x = x[:, t] @ wi.T + bi
            pre_h = h @ wh.T + bh
            if mode == "lstm":
                i, f, g, o = np.split(pre_x + pre_h, 4, axis=1)
                c = sigmoid(f) * c + sigmoid(i) * np.tanh(g)
                h = sigmoid(o) * np.tanh(c)
            else:
                rx, zx, nx = np.split(pre_x, 3, axis=1)
                rh, zh, nh = np.split(pre_h, 3, axis=1)
                r = sigmoid(rx + rh)
                z = sigmoid(zx + zh)
                h = (1 - z) * np.tanh(nx + r * nh) + z * h
            ref[:, t] = h
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_fused_cell_matches_unfused_stack():
    """FusedRNNCell.unroll == unfuse()'d stack with weights moved via
    unpack_weights (the reference's fused<->unfused contract)."""
    T, N, I, H, L = 5, 3, 4, 6, 2
    rs = np.random.RandomState(2)
    x = rs.randn(N, T, I).astype("float32")

    fused = mx.rnn.FusedRNNCell(H, num_layers=L, mode="lstm",
                                prefix="lstm_")
    f_out, _ = fused.unroll(T, inputs=mx.sym.Variable("data"),
                            merge_outputs=True)
    psize = rnn_param_size(I, H, L, "lstm")
    f_ex = f_out.simple_bind(mx.cpu(), data=(N, T, I))
    blob = rs.uniform(-0.3, 0.3, (psize,)).astype("float32")
    f_ex.arg_dict["lstm_parameters"][:] = blob
    f_ex.arg_dict["data"][:] = x
    f_ex.forward(is_train=False)
    fused_out = f_ex.outputs[0].asnumpy()

    stack = fused.unfuse()
    s_out, _ = stack.unroll(T, inputs=mx.sym.Variable("data"),
                            merge_outputs=True)
    s_ex = s_out.simple_bind(mx.cpu(), data=(N, T, I))
    unpacked = fused.unpack_weights(
        {"lstm_parameters": mx.nd.array(blob)})
    for name, arr in s_ex.arg_dict.items():
        if name == "data":
            arr[:] = x
        else:
            assert name in unpacked, "missing unpacked weight %s" % name
            arr[:] = unpacked[name].asnumpy()
    s_ex.forward(is_train=False)
    np.testing.assert_allclose(s_ex.outputs[0].asnumpy(), fused_out,
                               rtol=1e-4, atol=1e-4)

    # pack_weights inverts unpack_weights
    repacked = fused.pack_weights(unpacked)
    np.testing.assert_allclose(repacked["lstm_parameters"].asnumpy(), blob,
                               rtol=1e-6)


def test_bidirectional_cell_unroll():
    cell = mx.rnn.BidirectionalCell(mx.rnn.LSTMCell(4, prefix="f_"),
                                    mx.rnn.LSTMCell(4, prefix="b_"))
    outputs, states = cell.unroll(3, inputs=mx.sym.Variable("data"),
                                  merge_outputs=True)
    ex = outputs.simple_bind(mx.cpu(), data=(2, 3, 5))
    ex.forward(is_train=False)
    assert ex.outputs[0].shape == (2, 3, 8)


def test_residual_and_dropout_cells():
    stack = mx.rnn.SequentialRNNCell()
    stack.add(mx.rnn.ResidualCell(mx.rnn.RNNCell(6, prefix="r1_")))
    stack.add(mx.rnn.DropoutCell(0.3, prefix="d_"))
    outputs, _ = stack.unroll(4, inputs=mx.sym.Variable("data"),
                              merge_outputs=True)
    ex = outputs.simple_bind(mx.cpu(), data=(2, 4, 6))
    ex.forward(is_train=False)
    assert ex.outputs[0].shape == (2, 4, 6)


def test_bucket_sentence_iter():
    rs = np.random.RandomState(0)
    sentences = [list(rs.randint(1, 20, rs.randint(2, 12)))
                 for _ in range(200)]
    it = mx.rnn.BucketSentenceIter(sentences, batch_size=8,
                                   buckets=[4, 8, 12], invalid_label=0)
    keys = set()
    for batch in it:
        t = batch.bucket_key
        keys.add(t)
        assert batch.data[0].shape == (8, t)
        assert batch.label[0].shape == (8, t)
        # label is data shifted by one
        d = batch.data[0].asnumpy()
        lbl = batch.label[0].asnumpy()
        np.testing.assert_array_equal(d[:, 1:], lbl[:, :-1])
    assert len(keys) >= 2


def _bucketing_model(vocab=16, hidden=16, embed=8):
    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        label = mx.sym.Variable("softmax_label")
        emb = mx.sym.Embedding(data, input_dim=vocab, output_dim=embed,
                               name="embed")
        cell = mx.rnn.LSTMCell(hidden, prefix="lstm_")
        outputs, _ = cell.unroll(seq_len, inputs=emb, merge_outputs=True)
        pred = mx.sym.Reshape(outputs, shape=(-1, hidden))
        pred = mx.sym.FullyConnected(pred, num_hidden=vocab, name="pred")
        label_flat = mx.sym.Reshape(label, shape=(-1,))
        out = mx.sym.SoftmaxOutput(pred, label=label_flat, name="softmax",
                                   normalization="batch")
        return out, ("data",), ("softmax_label",)

    return sym_gen


def test_bucketing_module_trains_and_shares_params():
    """The reference test_bucketing.py criterion: a bucketed LSTM LM
    converges on synthetic data with >=2 bucket shapes compiled, params
    shared across buckets."""
    rs = np.random.RandomState(4)
    # learnable synthetic language: token k is followed by (k+1) % 8
    sentences = []
    for _ in range(120):
        ln = rs.choice([5, 9])
        start = rs.randint(0, 8)
        sentences.append([(start + i) % 8 + 1 for i in range(ln)])
    it = mx.rnn.BucketSentenceIter(sentences, batch_size=10,
                                   buckets=[5, 9], invalid_label=0)
    mod = mx.mod.BucketingModule(_bucketing_model(),
                                 default_bucket_key=9,
                                 context=mx.cpu())
    # 4 epochs, 48 steps: perplexity is 1.93 after 3 and the bar is 2.5
    # (15 epochs took 106 s: the eager adam update compiles anew for
    # every parameter at every step, ROADMAP D2 (d))
    mod.fit(it, num_epoch=4, optimizer="adam",
            initializer=mx.init.Xavier(),
            eval_metric=mx.metric.Perplexity(ignore_label=None),
            optimizer_params={"learning_rate": 0.02})
    assert len(mod._buckets) == 2  # both bucket programs compiled

    # params are shared objects between bucket executors
    b5 = mod._buckets[5]._exec.arg_dict
    b9 = mod._buckets[9]._exec.arg_dict
    for name in ("lstm_i2h_weight", "embed_weight", "pred_weight"):
        assert b5[name] is b9[name]

    m = mx.metric.Perplexity(ignore_label=None)
    score = dict(mod.score(it, m))
    assert score["perplexity"] < 2.5, score


def test_fused_cell_trains_in_module():
    """FusedRNNCell graph trains through Module.fit (the cudnn_lstm
    path of the reference's train tier)."""
    rs = np.random.RandomState(5)
    T, I = 6, 5
    X = rs.randn(80, T, I).astype("float32")
    y = (X.sum(axis=(1, 2)) > 0).astype("float32")
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    data = mx.sym.Variable("data")
    cell = mx.rnn.FusedRNNCell(12, num_layers=1, mode="gru", prefix="g_")
    outputs, _ = cell.unroll(T, inputs=data, merge_outputs=True)
    last = mx.sym.SequenceLast(mx.sym.SwapAxis(outputs, dim1=0, dim2=1))
    fc = mx.sym.FullyConnected(last, num_hidden=2)
    net = mx.sym.SoftmaxOutput(fc, label=mx.sym.Variable("softmax_label"),
                               normalization="batch")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=8, optimizer="adam",
            initializer=mx.init.Xavier(),
            optimizer_params={"learning_rate": 0.01})
    acc = dict(mod.score(it, mx.metric.Accuracy()))["accuracy"]
    assert acc > 0.85, acc


def test_rnn_checkpoint_roundtrip(tmp_path):
    cell = mx.rnn.FusedRNNCell(6, num_layers=1, mode="lstm", prefix="l_")
    outputs, _ = cell.unroll(3, inputs=mx.sym.Variable("data"),
                             merge_outputs=True)
    ex = outputs.simple_bind(mx.cpu(), data=(2, 3, 4))
    rs = np.random.RandomState(0)
    blob = rs.randn(rnn_param_size(4, 6, 1, "lstm")).astype("float32")
    arg_params = {"l_parameters": mx.nd.array(blob)}
    prefix = str(tmp_path / "rnnck")
    mx.rnn.save_rnn_checkpoint(cell, prefix, 3, outputs, arg_params, {})
    sym, arg, aux = mx.rnn.load_rnn_checkpoint(cell, prefix, 3)
    np.testing.assert_allclose(arg["l_parameters"].asnumpy(), blob,
                               rtol=1e-6)


def test_unfused_cell_tnc_layout():
    """TNC-merged input: states must take batch from axis 1 (review
    regression: _state_zeros used T as batch)."""
    cell = mx.rnn.LSTMCell(4, prefix="l_")
    outputs, _ = cell.unroll(5, inputs=mx.sym.Variable("data"),
                             layout="TNC", merge_outputs=True)
    ex = outputs.simple_bind(mx.cpu(), data=(5, 2, 3))  # T=5, N=2
    ex.forward(is_train=False)
    assert ex.outputs[0].shape == (5, 2, 4)


def test_lstm_cell_graph_json_roundtrip_and_init():
    """Symbol JSON round-trip keeps the serialized LSTMBias init usable
    (review regression: decoded list crashed initializer.create)."""
    cell = mx.rnn.LSTMCell(4, prefix="l_")
    outputs, _ = cell.unroll(3, inputs=mx.sym.Variable("data"),
                             merge_outputs=True)
    sym2 = mx.sym.load_json(outputs.tojson())
    mod = mx.mod.Module(sym2, label_names=[], context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, 3, 5))], label_shapes=None,
             for_training=False)
    mod.init_params(initializer=mx.init.Xavier())
    bias = mod._exec.arg_dict["l_i2h_bias"].asnumpy()
    np.testing.assert_allclose(bias[4:8], 1.0)  # forget-gate block
    np.testing.assert_allclose(bias[:4], 0.0)


def test_bucket_sentence_iter_empty_bucket():
    sentences = [[1, 2, 3, 4, 5, 6]] * 10
    it = mx.rnn.BucketSentenceIter(sentences, batch_size=2,
                                   buckets=[2, 8], invalid_label=0)
    seen = [b.bucket_key for b in it]
    assert set(seen) == {8}


def test_bucketing_module_force_rebind_clears_buckets():
    mod = mx.mod.BucketingModule(_bucketing_model(), default_bucket_key=9,
                                 context=mx.cpu())
    shapes = [mx.io.DataDesc("data", (4, 9), "float32", layout="NT")]
    lshapes = [mx.io.DataDesc("softmax_label", (4, 9), "float32",
                              layout="NT")]
    mod.bind(shapes, lshapes)
    mod.init_params(initializer=mx.init.Xavier())
    mod.switch_bucket(5, [mx.io.DataDesc("data", (4, 5), "float32", "NT")],
                      [mx.io.DataDesc("softmax_label", (4, 5), "float32",
                                      "NT")])
    assert len(mod._buckets) == 2
    mod.bind(shapes, lshapes, force_rebind=True)
    assert len(mod._buckets) == 1 and not mod.params_initialized


def test_bucket_sentence_iter_shuffle_replayable():
    rs = np.random.RandomState(0)
    sentences = [list(rs.randint(1, 20, rs.randint(2, 12)))
                 for _ in range(200)]
    make = lambda: mx.rnn.BucketSentenceIter(
        sentences, batch_size=8, buckets=[4, 8, 12], invalid_label=0,
        seed=7)
    a, b = make(), make()
    # identical (seed, reset count) => identical shuffle, regardless of
    # any interleaved global-RNG traffic
    np.random.seed(123)
    np.testing.assert_array_equal(next(a).data[0].asnumpy(),
                                  next(b).data[0].asnumpy())
    assert a.idx == b.idx
