"""PR 21 repairs: nothing on the main path may hide the device.

A ``tpu`` context is a TPU or an error; the compile cache can be placed
from outside; a compiled executable's runtime error is not retried down
another path; a hung bench is a failure; and ``chip_smoke.py`` cannot
pass without the chip.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(argv, env=None, timeout=240):
    full = dict(os.environ)
    full["JAX_PLATFORMS"] = "cpu"
    full.update(env or {})
    return subprocess.run([sys.executable] + argv, cwd=REPO, env=full,
                          capture_output=True, text=True, timeout=timeout)


# -- contexts -------------------------------------------------------------

@pytest.mark.parametrize("make,device_id", [
    (mx.tpu, 0), (mx.tpu, 3), (mx.gpu, 0), (mx.gpu, 1)],
    ids=["tpu0", "tpu3", "gpu0", "gpu1"])
def test_tpu_context_raises_without_a_tpu(make, device_id):
    """On this CPU-only process every tpu/gpu context is out of range:
    resolving it raises the typed error and names what jax found."""
    ctx = make(device_id)
    assert str(ctx) == "tpu(%d)" % device_id  # naming one is still free
    with pytest.raises(MXNetError) as err:
        ctx.jax_device
    msg = str(err.value)
    assert "0 TPU device(s)" in msg and "cpu" in msg


def test_cpu_context_wraps_and_default_is_cpu():
    n = len(jax.local_devices(backend="cpu"))
    assert mx.cpu(n + 1).jax_device == mx.cpu(1).jax_device
    assert mx.current_context().device_type == "cpu"


# -- attention dispatch ---------------------------------------------------

@pytest.mark.parametrize("shape,dtype,window,fits", [
    ((8, 16, 1024, 128), "bfloat16", 0, True),
    ((8, 16, 1024, 64), "float32", 0, True),
    ((8, 16, 1024, 128), "bfloat16", 256, False),   # no window mask
    ((8, 16, 1000, 128), "bfloat16", 0, False),     # T not a block multiple
    ((8, 16, 1024, 48), "bfloat16", 0, False),      # head dim
    ((128, 1024, 128), "bfloat16", 0, False),       # rank
    ((8, 16, 1024, 128), "int8", 0, False),
], ids=["bench", "d64-f32", "window", "ragged-T", "d48", "rank3", "int8"])
def test_pallas_eligibility_is_decided_from_the_call(shape, dtype, window,
                                                     fits):
    from mxnet_tpu.ops import attention

    q = jax.ShapeDtypeStruct(shape, dtype)
    assert attention.pallas_eligible(q, q, q, window) is fits


# (q shape, kv length, dtype): every eligible row of the table above, the
# benchmark's training shape, and the rule's edges: one block an axis, an
# axis that 256 and 512 do not divide, the widest head, unequal lengths
BLOCK_SIZE_CASES = [
    ((8, 16, 1024, 128), 1024, "bfloat16"),
    ((8, 16, 1024, 64), 1024, "float32"),
    ((4, 16, 2048, 128), 2048, "bfloat16"),
    ((2, 4, 128, 128), 128, "bfloat16"),
    ((2, 4, 1152, 128), 1152, "bfloat16"),
    ((1, 2, 2048, 256), 2048, "float32"),
    ((2, 4, 256, 64), 1152, "float32"),
    ((1, 2, 4096, 128), 4096, "bfloat16"),
]


@pytest.mark.parametrize("shape,kv_len,dtype", BLOCK_SIZE_CASES, ids=[
    "bench", "d64-f32", "cell", "T128", "T1152", "d256-f32", "q256-k1152",
    "T4096"])
def test_pallas_block_sizes_are_a_function_of_the_call(shape, kv_len, dtype,
                                                       monkeypatch):
    from mxnet_tpu.ops import attention

    q = jax.ShapeDtypeStruct(shape, dtype)
    k = jax.ShapeDtypeStruct(shape[:2] + (kv_len, shape[3]), dtype)
    assert attention.pallas_eligible(q, k, k)
    sizes = attention.pallas_block_sizes(q, k)
    assert sizes.has_backward_blocks and sizes.block_b == 1
    q_blocks = [sizes.block_q, sizes.block_q_major_dkv, sizes.block_q_dkv,
                sizes.block_q_dq]
    k_blocks = [sizes.block_k_major, sizes.block_k, sizes.block_k_major_dkv,
                sizes.block_k_dkv, sizes.block_k_major_dq, sizes.block_k_dq]
    for axis, blocks in ((shape[2], q_blocks), (kv_len, k_blocks)):
        for block in blocks:
            assert block % 128 == 0 and axis % block == 0, (axis, block)
        if axis == 128:
            assert set(blocks) == {128}
    # minor divides major (BlockSizes checked it too, or raised)
    assert sizes.block_k_major % sizes.block_k == 0
    assert sizes.block_q_major_dkv % sizes.block_q_dkv == 0
    assert sizes.block_k_major_dkv % sizes.block_k_dkv == 0
    assert sizes.block_k_major_dq % sizes.block_k_dq == 0
    # the avals alone decide: the lax kernel's knob is not read
    monkeypatch.setenv("MXNET_ATTN_BLOCK", "64")
    assert attention.pallas_block_sizes(q, k) == sizes
    # and they are worth having: no axis a block divides is left at the
    # library's 128 where a larger block divides it
    if shape[2] % 256 == 0:
        assert min(q_blocks) > 128
    if kv_len % 256 == 0:
        assert min(k_blocks) > 128


def test_rtc_interpret_is_asked_for_by_name():
    from mxnet_tpu.rtc import PallasKernel

    with pytest.raises(MXNetError):
        PallasKernel(lambda x, o: None, [((8, 128), "float32")],
                     interpret="auto")
    assert PallasKernel(lambda x, o: None,
                        [((8, 128), "float32")])._interpret is False


# -- compiled executables: only a drifted signature changes path ----------

def _fake_session(compiled):
    from mxnet_tpu.compile_cache import RecompileGuard, signature_of
    from mxnet_tpu.serve.session import InferenceSession, _Executable

    args = ({"w": jnp.ones((3,))}, jnp.zeros((2, 3)))
    sess = InferenceSession.__new__(InferenceSession)
    sess._exes = {"decode": _Executable(
        "decode", compiled, lambda p, x: x + 1, RecompileGuard("t.decode"),
        args[0], signature_of(args), {})}
    return sess, args


def test_dispatch_reraises_a_device_error():
    def boom(*_):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    sess, args = _fake_session(boom)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        sess._dispatch("decode", args)
    assert sess.fallback_count() == 0


def test_dispatch_falls_back_on_signature_drift_only():
    sess, args = _fake_session(lambda p, x: x)
    assert sess._dispatch("decode", args) is args[1]
    assert sess.fallback_count() == 0
    out = sess._dispatch("decode", (args[0], jnp.zeros((4, 3))))  # drifted
    assert out.shape == (4, 3) and float(out[0, 0]) == 1.0
    assert sess.fallback_count() == 1


@pytest.mark.parametrize("exc,falls_back", [
    (RuntimeError("INTERNAL: device fault"), False),
    (ValueError("sharding mismatch"), True)], ids=["runtime", "refusal"])
def test_train_step_aot_error_policy(exc, falls_back):
    """The AOT step's own refusal of the live arguments (ValueError /
    TypeError, raised before anything runs) re-specializes through the
    lazy jit, loudly; a runtime error of the step raises."""
    from mxnet_tpu.fused import TrainStep

    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=4, name="fc"), name="softmax")
    step = TrainStep(net, optimizer="sgd")
    shapes = {"data": (8, 6), "softmax_label": (8,)}
    params, aux, states = step.init_state(shapes)
    step.compile(shapes)
    batch = {"data": jnp.ones((8, 6)), "softmax_label": jnp.zeros((8,))}

    def refuse(*_):
        raise exc

    step._aot = refuse
    call = lambda: step(params, aux, states, batch, jax.random.PRNGKey(0))
    if falls_back:
        assert len(call()) == 4 and step._aot is None
    else:
        with pytest.raises(RuntimeError, match="device fault"):
            call()
        assert step._aot is refuse


@pytest.mark.parametrize("compute_dtype,remat", [
    (None, True), ("bfloat16", False)], ids=["fp32", "bf16"])
def test_zero3_regather_policy_is_fp32_only(compute_dtype, remat):
    """On four v5e chips bf16 ZeRO-3 under the jax.checkpoint re-gather
    policy hardly learned (fp32 with it, and bf16 without it, tracked one
    chip exactly; the CPU cannot see the difference) — so the policy is
    traced under fp32 compute only."""
    from mxnet_tpu.fused import TrainStep

    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=32, name="fc"), name="softmax")
    step = TrainStep(net, optimizer="sgd", plan="data=4,zero=3",
                     compute_dtype=compute_dtype)
    assert step.zero3
    shapes = {"data": (8, 16), "softmax_label": (8,)}
    args = step._abstract_inputs(shapes)
    step._jit_step = step._build_zero_jit(args[0], args[2])
    jaxpr = str(jax.make_jaxpr(step._jit_step)(*args))
    assert ("remat" in jaxpr) is remat  # jax.checkpoint traces as remat2


def test_module_fused_build_error_raises(monkeypatch):
    """An unexpected exception building the fused step is a fault, not a
    reason to train through the split path."""
    from mxnet_tpu import fused

    def broken(*a, **k):
        raise RuntimeError("lowering failed")

    monkeypatch.setattr(fused, "TrainStep", broken)
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=4, name="fc"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 6))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    with pytest.raises(RuntimeError, match="lowering failed"):
        mod.init_optimizer()


def test_waitall_waits_for_ordinary_results():
    """effects_barrier alone does not await an async-dispatched result;
    after waitall every live array is ready."""
    x = jnp.ones((256, 256))
    y = jax.jit(lambda a: a @ a)(x)
    mx.nd.waitall()
    assert y.is_ready()
    np.testing.assert_allclose(np.asarray(y)[0, 0], 256.0)


# -- compile cache placement ----------------------------------------------

_HANDED = r"""
import json, os, sys
import jax
updates = []
_update = jax.config.update
jax.config.update = lambda k, v: (updates.append(k), _update(k, v))[1]
from mxnet_tpu import compile_cache
assert compile_cache.ensure_initialized()
jax.jit(lambda a: a * 2 + 1)(jax.numpy.ones((64,))).block_until_ready()
st = compile_cache.cache_stats()
print(json.dumps({"updates": updates, "dir": st["dir"],
                  "entries": st["entries"],
                  "swept": compile_cache.sweep_cache()}))
"""


def test_cache_dir_handed_in_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax's own reading stands — no
    directory written into jax.config, nothing swept (even far over the
    byte cap), and cache_stats() reports that directory."""
    handed = tmp_path / "handed"
    handed.mkdir()
    (handed / "someone-elses-entry").write_bytes(b"x" * 4096)
    res = _run(["-c", _HANDED], env={
        "JAX_COMPILATION_CACHE_DIR": str(handed),
        "MXNET_COMPILE_CACHE_DIR": str(tmp_path / "ignored"),
        "MXNET_COMPILE_CACHE_MAX_BYTES": "1",
        "MXNET_COMPILE_CACHE_MIN_COMPILE_S": "0"})
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert "jax_compilation_cache_dir" not in out["updates"]
    assert out["dir"] == str(handed)
    assert out["entries"] >= 2          # ours landed beside theirs
    assert out["swept"] == [0, 0]       # an implicit sweep is a no-op
    assert (handed / "someone-elses-entry").exists()  # also after atexit
    assert not (tmp_path / "ignored").exists()


def test_cache_dir_default_is_fixed_inside_the_checkout(monkeypatch):
    """Unset: one fixed path derived from the package's location — not
    from $HOME, a temporary name, a pid or the time."""
    from mxnet_tpu import compile_cache

    assert compile_cache.DEFAULT_CACHE_DIR == os.path.join(
        REPO, ".cache", "xla")
    seen = {}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("MXNET_COMPILE_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "_state",
                        dict(compile_cache._state, initialized=False))
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.__setitem__(k, v))
    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        lambda fn: None)
    monkeypatch.setattr(compile_cache.atexit, "register", lambda fn: fn)
    monkeypatch.setattr(compile_cache, "sweep_cache", lambda *a: (0, 0))
    assert compile_cache.ensure_initialized()
    assert seen["jax_compilation_cache_dir"] == os.path.join(
        REPO, ".cache", "xla")
    assert compile_cache.cache_stats()["dir"] == seen[
        "jax_compilation_cache_dir"]


# -- bench scripts and the smoke ------------------------------------------

def test_bench_watchdog_exit_is_a_failure():
    res = _run(["-c", "import time, bench_util; "
                "bench_util.arm_watchdog({'metric': 'm'}, 0.2); "
                "time.sleep(20)"], timeout=30)
    import bench_util

    assert res.returncode == bench_util.WATCHDOG_EXIT_CODE != 0
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert line["partial"] is True and line["watchdog_timeout_sec"] == 0.2


def test_bench_scripts_refuse_to_measure_a_cpu():
    """No chip, no number: the shared gate names the devices it found,
    and a device kind without a recorded peak is an error (bench.py and
    bench_transformer.py both pass through this gate before they build
    anything; only bench_transformer.py --small sets rehearsal)."""
    import bench_util

    with pytest.raises(SystemExit, match="found none.*cpu"):
        bench_util.require_tpu()
    assert bench_util.require_tpu(rehearsal=True).platform == "cpu"
    with pytest.raises(KeyError, match="no bf16 peak"):
        bench_util.peak_flops(jax.devices()[0])


@pytest.mark.parametrize("argv,runs_phases", [
    (["--tiny"], True), ([], False)], ids=["tiny", "full"])
def test_chip_smoke_cannot_pass_without_the_chip(argv, runs_phases):
    """--tiny on the CPU runs every phase and then fails naming the
    device; without --tiny it fails at once.  Neither prints ok."""
    res = _run(["chip_smoke.py"] + argv)
    assert res.returncode != 0, res.stdout[-2000:]
    out = res.stdout
    assert '"ok"' not in out
    last = out.strip().splitlines()[-1]
    assert last.startswith("FAILED") and "x cpu (cpu)" in last
    assert ("all phases passed" in out) is runs_phases, \
        out[-3000:] + res.stderr[-3000:]
    if runs_phases:
        assert "== train:" in out and "== serve:" in out
        assert "REHEARSAL" in out.splitlines()[0]
