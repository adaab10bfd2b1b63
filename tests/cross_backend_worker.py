"""Worker for the TPU-vs-CPU consistency tier (run WITHOUT the conftest
CPU pin, so the default platform — the real TPU where one is attached —
is one of the compared backends).  Prints one line per case: ``name maxdiff``.

The reference validates every GPU kernel against the CPU kernel this way
(``tests/python/gpu/test_operator_gpu.py`` + ``check_consistency``); here
the XLA TPU lowering is validated against the XLA CPU lowering.
"""
import sys

sys.path.insert(0, ".")

import numpy as np  # noqa: E402


def _setup_or_skip(discovery_timeout=90):
    """Shared preamble: validate the LOWERING, not the matmul precision
    default (TPU matmuls default to bf16 passes — a precision policy,
    not a kernel property); skip when no accelerator is present.

    Backend discovery runs on a bounded side thread: a TPU that
    another process holds can keep ``jax.devices()`` waiting far past
    any caller budget, so answer SKIP after ``discovery_timeout``
    rather than letting the parent test burn its whole timeout."""
    import os
    import threading

    import jax

    jax.config.update("jax_default_matmul_precision", "highest")
    found = []
    t = threading.Thread(target=lambda: found.append(jax.devices()),
                         daemon=True)
    t.start()
    t.join(discovery_timeout)
    if not found:
        print("SKIP no accelerator")
        sys.stdout.flush()
        os._exit(0)  # discovery thread is wedged; a clean exit would join it
    dev = found[0][0]
    kind = getattr(dev, "device_kind", "cpu")
    if "TPU" not in kind.upper() and dev.platform == "cpu":
        print("SKIP no accelerator")
        return False
    return True


def main():
    import mxnet_tpu as mx

    if not _setup_or_skip():
        return

    rs = np.random.RandomState(0)

    def run(name, sym, shapes, rtol=2e-2, atol=2e-3):
        inputs = {n: rs.normal(size=s).astype("float32")
                  for n, s in shapes.items()}
        outs = {}
        for ctx in (mx.cpu(), mx.tpu()):
            ex = sym.simple_bind(ctx, grad_req="write", **shapes)
            for n, v in inputs.items():
                ex.arg_dict[n][:] = mx.nd.array(v, ctx=ctx)
            ex.forward(is_train=True)
            ex.backward(out_grads=[mx.nd.ones(ex.outputs[0].shape,
                                              ctx=ctx)])
            outs[ctx.device_type] = (
                ex.outputs[0].asnumpy(),
                {n: g.asnumpy() for n, g in ex.grad_dict.items()
                 if g is not None})
        (o_cpu, g_cpu), (o_tpu, g_tpu) = outs["cpu"], outs["tpu"]
        diff = float(np.max(np.abs(o_cpu - o_tpu)))
        np.testing.assert_allclose(o_tpu, o_cpu, rtol=rtol, atol=atol,
                                   err_msg=name)
        for n in g_cpu:
            np.testing.assert_allclose(
                g_tpu[n], g_cpu[n], rtol=rtol, atol=5e-3,
                err_msg="%s grad %s" % (name, n))
        print("OK %s maxdiff=%.2e" % (name, diff))

    d = mx.sym.Variable("data")
    run("FullyConnected",
        mx.sym.FullyConnected(d, num_hidden=8, name="fc"),
        {"data": (4, 16)})
    run("Convolution+BN+relu",
        mx.sym.Activation(mx.sym.BatchNorm(
            mx.sym.Convolution(d, kernel=(3, 3), pad=(1, 1),
                               num_filter=8, name="cv"),
            fix_gamma=False, name="bn"), act_type="relu"),
        {"data": (2, 3, 8, 8)})
    run("Pooling", mx.sym.Pooling(d, kernel=(2, 2), stride=(2, 2),
                                  pool_type="max"),
        {"data": (2, 3, 8, 8)})
    run("softmax+dot",
        mx.sym.softmax(mx.sym.dot(d, mx.sym.Variable("w"))),
        {"data": (4, 8), "w": (8, 8)})
    run("MultiHeadAttention",
        mx.sym.MultiHeadAttention(d, num_heads=2, name="mha"),
        {"data": (2, 8, 16), "mha_in_weight": (48, 16),
         "mha_in_bias": (48,), "mha_out_weight": (16, 16),
         "mha_out_bias": (16,)})
    run("RNN-lstm",
        mx.sym.RNN(d, mx.sym.Variable("p"), mx.sym.Variable("s0"),
                   mx.sym.Variable("c0"), state_size=8, num_layers=1,
                   mode="lstm", name="rnn"),
        {"data": (5, 2, 4),
         "p": (4 * ((4 + 8) * 8 + 2 * 8),),
         "s0": (1, 2, 8), "c0": (1, 2, 8)})
    run("LayerNorm+gelu",
        mx.sym.Activation(mx.sym.LayerNorm(d, name="ln"),
                          act_type="gelu"),
        {"data": (4, 16), "ln_gamma": (16,), "ln_beta": (16,)})
    print("ALL_OK")




def sweep():
    """Registry-generated consistency sweep (VERDICT r3 task 6): drive
    every op with a forward case from the test_op_sweep spec table on
    BOTH backends and compare outputs — the reference imports the whole
    CPU op suite into the GPU tier the same way
    (``tests/python/gpu/test_operator_gpu.py:23``)."""
    import importlib.util
    import os

    import mxnet_tpu as mx
    from mxnet_tpu.ndarray import imperative_invoke
    from mxnet_tpu.ops import registry

    if not _setup_or_skip():
        return

    spec_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "test_op_sweep.py")
    spec = importlib.util.spec_from_file_location("op_sweep_specs",
                                                  spec_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    # one case per OpDef (aliases share), skipping ops whose outputs are
    # legitimately backend-divergent or host-bound:
    #  - rng consumers (fresh key per invoke)
    #  - host-callback ops (they compute on the host on either backend)
    seen_defs = {}
    for name in sorted(mod.SPECS):
        if not registry.exists(name):
            continue
        op = registry.get(name)
        if id(op) not in seen_defs:
            seen_defs[id(op)] = name
    skipped, failed, ran = [], [], 0
    for _, name in sorted(seen_defs.items(), key=lambda kv: kv[1]):
        op = registry.get(name)
        if op.needs_rng or name in ("Custom", "_CustomFunction",
                                    "_Native", "_NDArray"):
            skipped.append(name)
            continue
        inputs, attrs = mod.SPECS[name]
        inputs = [x() if callable(x) else x for x in inputs]
        outs = {}
        try:
            import jax

            for ctx in (mx.cpu(), mx.tpu()):
                arrs = [mx.nd.array(x, ctx=ctx) for x in inputs]
                # default_device pins zero-input ops (creation ops),
                # whose computations nothing else commits to a backend
                with jax.default_device(ctx.jax_device):
                    res = imperative_invoke(name, arrs, dict(attrs))
                outs[ctx.device_type] = [o.asnumpy() for o in res]
        except Exception as exc:  # noqa: BLE001 - report, don't die
            failed.append("%s: %s" % (name, str(exc)[:120]))
            continue
        maxdiff = 0.0
        ok = True
        for o_cpu, o_tpu in zip(outs["cpu"], outs["tpu"]):
            a = np.asarray(o_cpu, "float64")
            b = np.asarray(o_tpu, "float64")
            if a.shape != b.shape:
                ok = False
                failed.append("%s: shape %s vs %s" % (name, a.shape,
                                                      b.shape))
                break
            if a.size:
                maxdiff = max(maxdiff, float(np.max(np.abs(a - b))))
            if not np.allclose(b, a, rtol=2e-2, atol=2e-3,
                               equal_nan=True):
                ok = False
                failed.append("%s: maxdiff %.3e" % (name, maxdiff))
                break
        if ok:
            ran += 1
            print("SWEEP %s maxdiff=%.2e" % (name, maxdiff))
    # alias names answer through the same OpDef; count the full
    # registered-name coverage of the defs that actually RAN
    skipped_set = set(skipped)
    failed_names = {f.split(":", 1)[0] for f in failed}
    covered_defs = {id(registry.get(n)) for _, n in seen_defs.items()
                    if n not in skipped_set and n not in failed_names}
    covered_names = [n for n in registry.list_ops()
                     if id(registry.get(n)) in covered_defs]
    print("SWEEP_DONE ran=%d skipped=%d failed=%d names_covered=%d" %
          (ran, len(skipped), len(failed), len(covered_names)))
    for f in failed:
        print("SWEEP_FAIL %s" % f)
    if not failed:
        print("SWEEP_ALL_OK")


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "sweep":
        sweep()
    else:
        main()
