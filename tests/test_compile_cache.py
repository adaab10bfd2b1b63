"""Compile-time subsystem: persistent cache, AOT warmup, recompile
guardrails (mxnet_tpu/compile_cache.py, docs/compilation.md)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile_cache
from mxnet_tpu.base import RecompileStorm
from mxnet_tpu.compile_cache import (RecompileGuard, diff_signatures,
                                     signature_of, track_lru)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp(name, feat=16, hidden=8, classes=4):
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=hidden,
                                name="%s_fc1" % name)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=classes,
                                name="%s_fc2" % name)
    return mx.sym.SoftmaxOutput(net, name=name)


# ---------------------------------------------------------------------------
# signatures
# ---------------------------------------------------------------------------

def test_signature_identity_and_weak_types():
    import jax.numpy as jnp

    a = {"w": jnp.zeros((3, 4), "float32")}
    assert signature_of(a) == signature_of(
        {"w": jnp.ones((3, 4), "float32")})  # values don't matter
    assert signature_of(a) != signature_of(
        {"w": jnp.zeros((3, 5), "float32")})  # shapes do
    assert signature_of(a) != signature_of(
        {"w": jnp.zeros((3, 4), "bfloat16")})  # dtypes do
    # python scalars are named as the weak-type leak they are
    sig = dict(signature_of((0.5,)))
    assert list(sig.values()) == [("py_float", "weak")]


def test_signature_matches_shape_dtype_struct():
    import jax
    import jax.numpy as jnp

    conc = signature_of({"w": jnp.zeros((2, 3), "float32")})
    abst = signature_of({"w": jax.ShapeDtypeStruct((2, 3),
                                                   jnp.dtype("float32"))})
    assert conc == abst


def test_diff_signatures_names_changed_leaves():
    import jax.numpy as jnp

    old = signature_of({"data": jnp.zeros((32, 8), "float32")})
    new = signature_of({"data": jnp.zeros((27, 8), "float32")})
    lines = diff_signatures(old, new)
    assert len(lines) == 1
    assert "(32, 8)" in lines[0] and "(27, 8)" in lines[0]


# ---------------------------------------------------------------------------
# recompile guard
# ---------------------------------------------------------------------------

def _sigs(n):
    import jax.numpy as jnp

    return [signature_of({"x": jnp.zeros((i + 1, 4), "float32")})
            for i in range(n)]


def test_guard_counts_traces_and_calls():
    g = RecompileGuard("t")
    s1, s2 = _sigs(2)
    assert g.observe(s1) is True
    assert g.observe(s1) is False          # same signature: no trace
    assert g.observe(s2) is True
    assert g.observe(s1, force=True) is True   # rebuild after eviction
    assert (g.calls, g.traces, g.signatures) == (4, 3, 2)


def test_guard_warns_past_threshold(monkeypatch, caplog):
    import logging

    monkeypatch.setenv("MXNET_RECOMPILE_WARN", "2")
    g = RecompileGuard("warned")
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu"):
        for s in _sigs(2):
            g.observe(s)
        assert not caplog.records          # at the threshold: quiet
        g.observe(_sigs(3)[-1])
    assert any("warned" in r.message and "3 distinct" in r.message
               for r in caplog.records)


def test_guard_raises_recompile_storm(monkeypatch):
    monkeypatch.setenv("MXNET_RECOMPILE_WARN", "2")
    monkeypatch.setenv("MXNET_RECOMPILE_ERROR", "1")
    g = RecompileGuard("stormy")
    sigs = _sigs(3)
    g.observe(sigs[0])
    g.observe(sigs[1])
    with pytest.raises(RecompileStorm) as err:
        g.observe(sigs[2])
    assert err.value.name == "stormy"
    assert err.value.signatures == 3
    assert err.value.diff  # leaf-level shape diff present
    assert isinstance(err.value, mx.MXNetError)


def test_registry_reuses_guard_by_name():
    reg = compile_cache.RecompileRegistry()
    assert reg.guard("a") is reg.guard("a")
    reg.guard("a").observe(_sigs(1)[0])
    assert reg.report()["a"]["traces"] == 1


def test_track_lru_counts_cache_misses():
    import functools

    @track_lru("test._lru_fn")
    @functools.lru_cache(maxsize=2)
    def fn(x):
        return x * 2

    before = compile_cache.registry.guard("test._lru_fn").traces
    fn(1); fn(1); fn(2)          # 2 misses, 1 hit
    fn(3); fn(1)                 # miss, then 1 evicted -> rebuild miss
    g = compile_cache.registry.guard("test._lru_fn")
    assert g.traces - before == 4


# ---------------------------------------------------------------------------
# CachedOp LRU bound
# ---------------------------------------------------------------------------

def test_cached_op_lru_bound(monkeypatch):
    monkeypatch.setenv("MXNET_CACHED_OP_CACHE_SIZE", "2")
    data = mx.sym.Variable("data")
    sym = mx.sym.FullyConnected(data, num_hidden=3, name="coplru_fc")
    op = mx.nd.CachedOp(sym)
    w = mx.nd.zeros((3, 4))
    b = mx.nd.zeros((3,))
    for n in (1, 2, 3):
        op(mx.nd.ones((n, 4)), w, b)
    assert len(op._jit_cache) == 2          # oldest evicted
    g = op._recompile_guard
    assert (g.traces, g.signatures) == (3, 3)
    # the evicted signature re-traces on next use (force-counted)
    op(mx.nd.ones((1, 4)), w, b)
    assert g.traces == 4 and g.signatures == 3
    # a cached signature does not
    op(mx.nd.ones((3, 4)), w, b)
    assert g.traces == 4


# ---------------------------------------------------------------------------
# AOT warmup
# ---------------------------------------------------------------------------

def test_trainstep_aot_matches_lazy():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.fused import TrainStep

    sym = _mlp("aoteq")
    shapes = {"data": (8, 16), "aoteq_label": (8,)}
    kw = dict(optimizer="sgd",
              optimizer_params={"learning_rate": 0.1},
              data_names=("data",), label_names=("aoteq_label",))
    rng = jax.random.PRNGKey(3)
    batch = {"data": jnp.linspace(0, 1, 8 * 16).reshape(8, 16)
             .astype("float32"),
             "aoteq_label": jnp.zeros((8,), "float32")}

    aot = TrainStep(sym, **kw)
    stats = aot.compile(shapes)
    assert stats["duration_s"] > 0
    assert aot.compile_stats is stats
    assert aot._aot is not None
    p1 = aot.init_state(shapes)
    out_aot = aot(*p1, batch, rng)
    assert aot._aot is not None             # fast path survived dispatch

    lazy = TrainStep(sym, **kw)
    p2 = lazy.init_state(shapes)
    out_lazy = lazy(*p2, batch, rng)

    for n in out_aot[0]:
        np.testing.assert_allclose(np.asarray(out_aot[0][n]),
                                   np.asarray(out_lazy[0][n]),
                                   rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out_aot[3][0]),
                               np.asarray(out_lazy[3][0]),
                               rtol=2e-5, atol=1e-6)


def test_trainstep_aot_seeds_guard_single_trace():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.fused import TrainStep

    sym = _mlp("aotseed")
    shapes = {"data": (4, 16), "aotseed_label": (4,)}
    step = TrainStep(sym, data_names=("data",),
                     label_names=("aotseed_label",))
    step.compile(shapes)
    state = step.init_state(shapes)
    batch = {"data": jnp.ones((4, 16), "float32"),
             "aotseed_label": jnp.zeros((4,), "float32")}
    state = step(*state[:3], batch, jax.random.PRNGKey(0))
    step(*state[:3], batch, jax.random.PRNGKey(1))
    g = compile_cache.registry.guard("TrainStep(aotseed)")
    assert (g.traces, g.signatures, g.calls) == (1, 1, 3)


def test_module_prepare_compiled():
    sym = _mlp("prepc")
    mod = mx.mod.Module(sym, context=mx.cpu(),
                        label_names=("prepc_label",))
    mod.bind(data_shapes=[("data", (8, 16))],
             label_shapes=[("prepc_label", (8,))])
    mod.init_params()
    mod.init_optimizer()
    stats = mod.prepare_compiled()
    assert stats is not None and stats["duration_s"] > 0
    assert mod._fused.compile_stats == stats
    # recorded as a profiler compile event
    from mxnet_tpu import profiler

    assert any(e["name"] == "TrainStep(prepc)"
               for e in profiler.compile_events())


def test_fit_static_shapes_traces_exactly_once():
    """The tier-1 shape-hygiene guard: a static-shape fit must compile
    the fused step exactly once — a second trace is a shape/weak-type
    leak in the training loop."""
    sym = _mlp("fit1t")
    X = np.random.RandomState(0).rand(64, 16).astype("float32")
    y = (np.arange(64) % 4).astype("float32")
    it = mx.io.NDArrayIter(X, y, batch_size=16, label_name="fit1t_label")
    mod = mx.mod.Module(sym, context=mx.cpu(),
                        label_names=("fit1t_label",))
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05})
    g = compile_cache.registry.guard("TrainStep(fit1t)")
    assert g.traces == 1, \
        "Module.fit retraced TrainStep %d times on a static-shape " \
        "iterator — a shape/weak-type leak crept into the loop" % g.traces
    assert g.calls >= 8  # 4 batches/epoch x 2 epochs, plus the AOT seed


# ---------------------------------------------------------------------------
# persistent cache
# ---------------------------------------------------------------------------

def test_sweep_cache_evicts_oldest(tmp_path):
    for i, age in enumerate([100, 50, 10]):  # older -> smaller mtime
        p = tmp_path / ("entry%d" % i)
        p.write_bytes(b"x" * 100)
        os.utime(p, (1000 - age, 1000 - age))
    entries, nbytes = compile_cache.sweep_cache(str(tmp_path),
                                               max_bytes=250)
    assert (entries, nbytes) == (2, 200)
    assert not (tmp_path / "entry0").exists()   # oldest went first
    assert (tmp_path / "entry2").exists()


def test_cache_stats_shape():
    stats = compile_cache.cache_stats()
    for key in ("enabled", "dir", "hits", "misses", "requests",
                "entries", "bytes", "max_bytes", "evictions",
                "evicted_bytes"):
        assert key in stats


_ROUNDTRIP = r"""
import json, sys, time
import mxnet_tpu as mx
from mxnet_tpu import compile_cache
from mxnet_tpu.fused import TrainStep

net = mx.sym.Variable("data")
net = mx.sym.FullyConnected(net, num_hidden=32, name="rt_fc1")
net = mx.sym.Activation(net, act_type="tanh")
net = mx.sym.FullyConnected(net, num_hidden=8, name="rt_fc2")
sym = mx.sym.SoftmaxOutput(net, name="rt")
step = TrainStep(sym, data_names=("data",), label_names=("rt_label",))
stats = step.compile({"data": (16, 24), "rt_label": (16,)})
print(json.dumps({"compile_s": stats["duration_s"],
                  "cache": compile_cache.cache_stats()}))
"""


def test_persistent_cache_roundtrip_across_processes(tmp_path):
    """Second process compiling the same program must be served from the
    persistent cache: hits > 0 and a (much) smaller compile_s."""
    env = dict(os.environ)
    # this test places its own cache: JAX's variable would outrank it
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(JAX_PLATFORMS="cpu",
               MXNET_COMPILE_CACHE_DIR=str(tmp_path / "xla"),
               MXNET_COMPILE_CACHE_MIN_COMPILE_S="0")
    runs = []
    for _ in range(2):
        proc = subprocess.run([sys.executable, "-c", _ROUNDTRIP],
                              cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        runs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    first, second = runs
    assert first["cache"]["hits"] == 0
    assert first["cache"]["entries"] > 0, \
        "first process persisted nothing: %s" % (first["cache"],)
    assert second["cache"]["hits"] > 0, \
        "second process compiled from scratch: %s" % (second["cache"],)
    assert second["cache"]["misses"] == 0
    assert second["compile_s"] < first["compile_s"]


def test_cache_opt_out_via_empty_dir(tmp_path):
    env = dict(os.environ)
    # this test places its own cache: JAX's variable would outrank it
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(JAX_PLATFORMS="cpu", MXNET_COMPILE_CACHE_DIR="")
    proc = subprocess.run(
        [sys.executable, "-c",
         "from mxnet_tpu import compile_cache\n"
         "assert compile_cache.ensure_initialized() is False\n"
         "s = compile_cache.cache_stats()\n"
         "assert s['enabled'] is False and s['dir'] is None\n"
         "print('ok')"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "ok" in proc.stdout


# ---------------------------------------------------------------------------
# artifact + tooling + bench budget
# ---------------------------------------------------------------------------

def test_write_artifact_and_report_tool(tmp_path):
    path = compile_cache.write_artifact(str(tmp_path / "report.json"))
    payload = json.load(open(path))
    assert payload["kind"] == compile_cache.ARTIFACT_KIND
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "compile_report.py"),
         path],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "COMPILE REPORT" in proc.stdout
    assert "persistent cache" in proc.stdout


def test_bench_budget_emits_partial_json(tmp_path):
    """A budget-expired bench run must still print one parseable JSON
    line, not die at the harness timeout with no output."""
    env = dict(os.environ)
    # this test places its own cache: JAX's variable would outrank it
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    # 6 s: after the imports (``import jax`` alone has read 3.8 s here, and
    # the emitter reads compile stats only once the package is imported),
    # well before the run's end (16 s)
    env.update(JAX_PLATFORMS="cpu",
               MXNET_COMPILE_CACHE_DIR=str(tmp_path / "xla"),
               MXNET_BENCH_BUDGET_S="6")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench_fit.py"), "16",
         "--epochs", "3", "--skip-nopipe"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    result = json.loads(line)
    assert result.get("partial") is True
    assert result.get("budget_s") == 6.0
    assert "compile_s" in result
    assert "compile_cache" in result
