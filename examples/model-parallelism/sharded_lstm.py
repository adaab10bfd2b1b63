#!/usr/bin/env python
"""Model parallelism, TPU-native (the re-design of
``example/model-parallel-lstm/lstm.py:65-129``).

The reference places each LSTM layer on a different GPU with
``group2ctx``/``AttrScope`` and pays a cross-device copy per boundary.
On TPU the same capability is expressed as *sharding*, not placement:
``param_sharding='tp'`` annotates weight shardings over the mesh's model
axis and XLA inserts the collectives over ICI.  Run on CPU with 8 virtual
devices to see the shardings:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/model-parallelism/sharded_lstm.py
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx


def build_lm(args):
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    embed = mx.sym.Embedding(data, input_dim=args.vocab,
                             output_dim=args.num_hidden, name="embed")
    # the whole stack is ONE fused lax.scan RNN op (reference FusedRNNCell
    # -> cuDNN; src/operator/rnn-inl.h)
    cell = mx.rnn.FusedRNNCell(args.num_hidden, num_layers=args.num_layers,
                               mode="lstm", prefix="lstm_")
    outputs, _ = cell.unroll(args.seq_len, inputs=embed, layout="NTC",
                             merge_outputs=True)
    pred = mx.sym.Reshape(outputs, shape=(-1, args.num_hidden))
    # "fc0" matches the tp rule table: column-parallel over 'model'
    pred = mx.sym.FullyConnected(pred, num_hidden=args.vocab, name="fc0")
    label_f = mx.sym.Reshape(label, shape=(-1,))
    return mx.sym.SoftmaxOutput(pred, label_f, name="softmax",
                                normalization="batch")


def main(args):
    import jax

    rs = np.random.RandomState(0)
    seqs = rs.randint(0, args.vocab,
                      (args.num_examples, args.seq_len)).astype("float32")
    nxt = np.roll(seqs, -1, axis=1)
    it = mx.io.NDArrayIter(seqs, nxt, args.batch_size, shuffle=True,
                           label_name="softmax_label")

    n_dev = len(jax.devices())
    model_axis = 2 if n_dev % 2 == 0 and n_dev > 1 else 1
    print("devices=%d -> mesh data=%d model=%d"
          % (n_dev, n_dev // model_axis, model_axis))

    from mxnet_tpu.parallel import create_mesh, mesh_scope
    import contextlib

    scope = contextlib.nullcontext()
    if model_axis > 1:
        # a hybrid data x model mesh: the 'model' axis carries the tensor-
        # parallel shards (reference group2ctx placed layers on devices;
        # here XLA lays collectives over the mesh axes)
        mesh = create_mesh({"data": n_dev // model_axis,
                            "model": model_axis})
        scope = mesh_scope(mesh)

    mod = mx.mod.Module(build_lm(args), context=mx.current_context())
    with scope:
        mod.fit(it, num_epoch=args.num_epochs,
                eval_metric=mx.metric.Perplexity(ignore_label=None),
                kvstore="dist_tpu_sync" if n_dev > 1 else "local",
                optimizer="adam",
                optimizer_params={"learning_rate": args.lr},
                initializer=mx.init.Xavier(),
                param_sharding="tp" if model_axis > 1 else None,
                batch_end_callback=mx.callback.Speedometer(
                    args.batch_size, 20))
    if model_axis > 1:
        specs = getattr(mod._fused, "_in_pshard", None)
        if specs is not None:
            print("parameter shardings:", specs)
    return mod


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--num-epochs", type=int, default=3)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--seq-len", type=int, default=16)
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--num-hidden", type=int, default=128)
    p.add_argument("--num-layers", type=int, default=2)
    p.add_argument("--num-examples", type=int, default=2048)
    main(p.parse_args())
