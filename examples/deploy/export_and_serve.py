#!/usr/bin/env python
"""Deployment walkthrough (reference ``amalgamation/`` +
``c_predict_api``): train → checkpoint → AOT bundle → serve four ways.

    python examples/deploy/export_and_serve.py

1. ``Predictor`` — forward-only serving from checkpoint files.
2. ``Predictor.export`` → one ``.mxtpu`` artifact (serialized
   multi-platform StableHLO + params); ``ExportedPredictor`` serves it
   with only ``jax.export`` + numpy.
3. The C ABI (``include/mxnet_tpu/c_predict_api.h``) — see
   ``tests/test_deploy_tools.py::test_c_predict_api`` for a full C
   client; this script prints the compile line.
4. The continuous-batching generation queue — an LM checkpoint restored
   into ``serve.InferenceSession`` (bucketed AOT prefill + paged-KV
   decode) and driven by ``serve.Scheduler`` over an arrival trace.
   See ``docs/serving.md`` and ``bench_serve.py``.
"""
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx


def main():
    rs = np.random.RandomState(0)
    X = rs.rand(256, 16).astype("float32")
    W = rs.rand(16, 4).astype("float32")
    y = (X @ W).argmax(1).astype("float32")

    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(
            mx.sym.Activation(mx.sym.FullyConnected(
                mx.sym.Variable("data"), num_hidden=32, name="fc1"),
                act_type="relu"),
            num_hidden=4, name="fc2"), name="softmax")
    it = mx.io.NDArrayIter(X, y, batch_size=64, shuffle=True)
    mod = mx.mod.Module(net, context=mx.current_context())
    mod.fit(it, num_epoch=30, initializer=mx.init.Xavier(),
            optimizer_params={"learning_rate": 0.5})

    workdir = tempfile.mkdtemp(prefix="mxtpu_deploy_")
    prefix = os.path.join(workdir, "model")
    mod.save_checkpoint(prefix, 30)

    # 1. serve from checkpoint files
    pred = mx.Predictor.load(prefix, 30, {"data": (8, 16)})
    pred.set_input("data", X[:8])
    ref = pred.forward()[0].asnumpy()
    print("predictor output", ref.shape, "acc on sample:",
          (ref.argmax(1) == y[:8]).mean())

    # 2. one-file AOT bundle
    bundle = prefix + ".mxtpu"
    pred.export(bundle)
    print("bundle:", bundle, os.path.getsize(bundle), "bytes")
    served = mx.Predictor.load_exported(bundle)
    out = served.forward(data=X[:8])[0]
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)
    print("ExportedPredictor matches:", out.shape)

    # 3. the C ABI build line (full client in tests/test_deploy_tools.py)
    print("\nC serving: build the ABI once with\n"
          "  python -c \"from mxnet_tpu import _native; "
          "_native._load('c_predict_api')\"\n"
          "then link clients against mxnet_tpu/_build/c_predict_api.so "
          "with -I include/ and run with MXNET_TPU_HOME set.")

    # 4. continuous-batching generation queue over a paged KV cache
    from mxnet_tpu import checkpoint as ckpt
    from mxnet_tpu import serve

    lm_cfg = serve.ModelConfig(vocab_size=96, num_layers=2, d_model=32,
                               num_heads=2, max_len=64)
    lm_params = serve.init_params(lm_cfg, seed=0)  # stands in for a run
    ckpt.CheckpointManager(workdir, prefix="lm",
                           save_optimizer_states=False).save(
        epoch=1, arg_params=lm_params)

    # every executable (one prefill per bucket + one decode step) is
    # AOT-compiled here; steady-state serving never traces
    sess = serve.InferenceSession.from_checkpoint(
        workdir, prefix="lm", num_heads=lm_cfg.num_heads,
        config=serve.ServeConfig(slots=4, page_size=8, buckets=(8, 16),
                                 max_new=12))
    rs = np.random.RandomState(1)
    requests = [
        serve.Request(rid=i,
                      prompt=rs.randint(1, 95, size=plen).tolist(),
                      max_new=12, arrival_s=0.004 * i)
        for i, plen in enumerate((5, 9, 13, 6, 11, 7))]
    done, makespan = serve.Scheduler(sess, policy="continuous") \
        .run(requests)
    stats = serve.summarize(done, makespan)
    print("\ncontinuous batching: %d requests, %d tokens, "
          "%.0f tok/s, ttft p99 %.1f ms"
          % (stats["completed"], stats["total_tokens"],
             stats["tokens_per_sec"], stats["ttft_p99_s"] * 1e3))
    print("executables:", sorted(sess.executables),
          "fallbacks:", sess.fallback_count())


if __name__ == "__main__":
    main()
