"""ZeRO-style sharded optimizer update (``parallel/zero.py`` + the
fused step's ``zero=`` branch): layout/eligibility units, the
checkpoint interchange descriptors, end-to-end training equivalence
against the replicated update (tests/closeness.py: two executables,
fp32 with a power-of-two lr), composition with the multi-step scan + dynamic loss scaling +
global-norm clipping, the 1/N state-memory claim, AOT compilation,
the bounded-dispatch fault site, and the elastic-checkpoint resume
matrix (same mesh, zero=off, and a different device count)."""
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import create_mesh, mesh_scope, zero

from closeness import (LIMIT_SPACINGS, assert_close_across_executables,
                       spacings_apart)

HERE = os.path.dirname(os.path.abspath(__file__))


def _devices(n):
    import jax

    if len(jax.devices()) < n:
        pytest.skip("needs %d devices" % n)
    return jax.devices()[:n]


# -- units -----------------------------------------------------------------

def test_zero_mode_parsing(monkeypatch):
    assert zero.zero_mode("on") == "on"
    assert zero.zero_mode("off") == "off"
    assert zero.zero_mode("auto") == "auto"
    assert zero.zero_mode("1") == "on"
    assert zero.zero_mode("FALSE") == "off"
    assert zero.zero_mode("3") == "3"
    assert zero.zero_mode("zero3") == "3"
    assert zero.zero_mode("z3") == "3"
    monkeypatch.setenv("MXNET_ZERO", "on")
    assert zero.zero_mode() == "on"
    assert zero.zero_mode("off") == "off"  # explicit wins over env
    monkeypatch.setenv("MXNET_ZERO", "3")
    assert zero.zero_mode() == "3"
    with pytest.raises(MXNetError, match="auto|on|off"):
        zero.zero_mode("sideways")


def test_zero_axis_eligibility():
    mesh = create_mesh({"data": 8}, devices=_devices(8))
    assert zero.zero_axis(mesh, "data", mode="auto") == "data"
    assert zero.zero_axis(mesh, "data", mode="off") is None
    assert zero.zero_axis(None, "data", mode="on") is None
    assert zero.zero_axis(mesh, "model", mode="on") is None
    one = create_mesh({"data": 1}, devices=_devices(1))
    assert zero.zero_axis(one, "data", mode="on") is None
    # sharded-param styles carry their own state layout
    assert zero.zero_axis(mesh, "data", param_sharding="fsdp",
                          mode="on") is None
    assert zero.zero_axis(mesh, "data", param_sharding="replicated",
                          mode="on") == "data"
    # forced on + ineligible reports through the step's warner
    seen = []
    zero.zero_axis(None, "data", mode="on",
                   warn=lambda k, m: seen.append((k, m)))
    assert seen and "MXNET_ZERO=on" in seen[0][1]
    # auto declines silently
    seen = []
    zero.zero_axis(None, "data", mode="auto",
                   warn=lambda k, m: seen.append((k, m)))
    assert not seen


def test_layout_tiling(monkeypatch):
    monkeypatch.setenv("MXNET_ZERO_MIN_PARAM_BYTES", "64")
    params = {
        "big": np.zeros((10, 3), "float32"),     # 120 B, 30 % 8 != 0
        "even": np.zeros((16,), "float32"),      # 64 B, exact tiling
        "tiny": np.zeros((4,), "float32"),       # 16 B < min -> replicated
        "frozen": np.zeros((64,), "float32"),
    }
    lay = zero.layout(params, 8, frozen=frozenset(["frozen"]))
    assert lay["big"].sharded and lay["big"].logical == 30 \
        and lay["big"].padded == 32
    assert lay["even"].sharded and lay["even"].padded == 16
    assert not lay["tiny"].sharded
    assert not lay["frozen"].sharded
    assert lay["big"].shape == (10, 3)
    # gather volume counts only the sharded padded tiles
    assert zero.update_gather_bytes(lay) == (32 + 16) * 4
    # single device shards nothing
    assert not any(e.sharded for e in zero.layout(params, 1).values())


def test_state_structure_roundtrip():
    tree = (None, (np.arange(3), None, np.arange(2)), np.arange(4))
    desc = zero.state_structure(tree)
    leaves = zero.state_leaves(tree)
    assert len(leaves) == 3
    rebuilt = zero.state_unflatten(desc, leaves)
    assert rebuilt[0] is None and rebuilt[1][1] is None
    np.testing.assert_array_equal(rebuilt[1][0], np.arange(3))
    np.testing.assert_array_equal(rebuilt[2], np.arange(4))


def test_shard_unshard_state_roundtrip():
    mesh = create_mesh({"data": 8}, devices=_devices(8))
    ent = zero.layout({"w": np.zeros((5, 3), "float32")}, 8,
                      min_bytes=0)["w"]
    canon = (np.arange(15, dtype="float32").reshape(5, 3),
             np.float32(0.5))  # weight-shaped moment + scalar schedule
    sharded = zero.shard_state(canon, ent, mesh, "data")
    leaves = zero.state_leaves(sharded)
    assert tuple(leaves[0].shape) == (ent.padded,)   # flat 1/N layout
    back = zero.unshard_state(sharded, ent)
    np.testing.assert_array_equal(back[0], canon[0])
    assert float(back[1]) == 0.5


def test_put_places_host_array():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = create_mesh({"data": 8}, devices=_devices(8))
    shard = NamedSharding(mesh, PartitionSpec("data"))
    host = np.arange(16, dtype="float32")
    arr = zero.put(host, shard)
    assert arr.sharding == shard
    np.testing.assert_array_equal(np.asarray(arr), host)
    assert zero.put(arr, shard) is arr       # already placed: no-op
    assert zero.put(host, None) is host


# -- training equivalence --------------------------------------------------

def _mlp_sym():
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax",
                                normalization="batch")


def _train(monkeypatch, zero_mode, optimizer="sgd", overlap_env="off",
           steps=3, steps_per_call=1, scaled=False, clip=None,
           batch=16, feat=8, stale_tile=False):
    """TrainStep on an 8-way DP mesh; returns (params, last outs, step).

    Power-of-two lr/rescale so zero on/off differ by nothing but the
    order of their sums (XLA reassociates the lr*rescale constant chain
    identically).  ``stale_tile`` plants the smallest realistic ZeRO
    fault before the last step: device 0's 1/N tile of every optimizer
    state still holds the step before's values."""
    import jax

    from mxnet_tpu.fused import TrainStep
    from mxnet_tpu.health import DynamicLossScaler, StepHealth

    monkeypatch.setenv("MXNET_ZERO_MIN_PARAM_BYTES", "0")
    monkeypatch.setenv("MXNET_GRAD_OVERLAP", overlap_env)
    # force several gather buckets under zero=3 so the bucketed
    # schedule (not one monolithic gather) is what's under test
    monkeypatch.setenv("MXNET_ZERO_GATHER_BUCKET_MB", "0.0001")
    if overlap_env == "on":
        monkeypatch.setenv("MXNET_GRAD_BUCKET_MB", "0.0001")
    mesh = create_mesh({"data": 8}, devices=_devices(8))
    opt_params = {"learning_rate": 0.125, "rescale_grad": 1.0 / batch}
    if clip is not None:
        opt_params["clip_global_norm"] = clip
    kw = {}
    if scaled:
        kw["health"] = StepHealth(
            scaler=DynamicLossScaler(init_scale=256.0))
    step = TrainStep(_mlp_sym(), optimizer=optimizer,
                     optimizer_params=opt_params, mesh=mesh,
                     batch_sharding_axis="data",
                     steps_per_call=steps_per_call, zero=zero_mode, **kw)
    if zero_mode in ("on", "3"):
        assert step.zero_axis == "data"
        assert step.zero3 == (zero_mode == "3")
    else:
        assert step.zero_axis is None
    shapes = {"data": (batch, feat), "softmax_label": (batch,)}
    params, aux, states = step.init_state(shapes)
    rs = np.random.RandomState(42)
    rng = jax.random.PRNGKey(7)
    out = None
    for i in range(steps):
        if stale_tile and i == steps - 1:
            states = jax.tree.map(
                lambda new, old: new.at[:new.shape[0] // 8].set(
                    old[:new.shape[0] // 8]), states, before)
        before = jax.tree.map(np.asarray, states)  # the step donates
        if steps_per_call > 1:
            bd = {"data": rs.randn(steps_per_call, batch, feat)
                  .astype("float32"),
                  "softmax_label": rs.randint(
                      0, 4, (steps_per_call, batch)).astype("float32")}
        else:
            bd = {"data": rs.randn(batch, feat).astype("float32"),
                  "softmax_label": rs.randint(0, 4, (batch,))
                  .astype("float32")}
        params, aux, states, out = step(params, aux, states, bd, rng)
    # zero=3 params live as flat 1/N tiles; unpack to canonical host
    # arrays so every mode compares like with like (identity otherwise)
    return ({k: np.asarray(v)
             for k, v in step.unpack_params(params).items()},
            np.asarray(out[0]), step, states)


@pytest.mark.parametrize("optimizer,overlap_env", [
    ("sgd", "on"),    # psum -> psum_scatter inside the bucketed DDP path
    ("adam", "off"),  # GSPMD constraint form, stateful optimizer
])
def test_zero_matches_replicated_bit_exact(monkeypatch, optimizer,
                                           overlap_env):
    """The acceptance equivalence: 3 fp32 steps with the sharded update
    produce the replicated update's parameters — two executables whose
    sums run in different orders: sgd reads 0 spacings apart, adam at
    most 4 (``fc2_bias``; jax 0.9.0), the limit is
    tests/closeness.py's."""
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)  # no declines
        p_on, o_on, _, _ = _train(monkeypatch, "on", optimizer=optimizer,
                                  overlap_env=overlap_env)
    p_off, o_off, _, _ = _train(monkeypatch, "off", optimizer=optimizer,
                                overlap_env=overlap_env)
    assert set(p_on) == set(p_off)
    for k in p_on:
        assert_close_across_executables(p_on[k], p_off[k], err_msg=k)
    assert_close_across_executables(o_on, o_off)


def test_zero_comparison_sees_planted_fault(monkeypatch):
    """The control of the comparison above and of its ZeRO-3 twin: one
    device's tile of the Adam moments a step stale reads tens of
    thousands of spacings on the parameters that tile updates."""
    p_bad, _, _, _ = _train(monkeypatch, "on", optimizer="adam",
                            stale_tile=True)
    p_off, _, _, _ = _train(monkeypatch, "off", optimizer="adam")
    assert max(spacings_apart(p_bad[k], p_off[k])
               for k in p_off) > 30 * LIMIT_SPACINGS


def test_zero_composes_scan_clip_and_loss_scale(monkeypatch):
    """Sharded update inside the K-step scan with global-norm clipping
    (per-shard partial norms + one scalar psum) and the dynamic loss
    scaler — the full composition, compared under tolerance."""
    p_on, o_on, s_on, _ = _train(monkeypatch, "on", optimizer="adam",
                                 steps=2, steps_per_call=2, scaled=True,
                                 clip=1.0)
    p_off, o_off, s_off, _ = _train(monkeypatch, "off", optimizer="adam",
                                    steps=2, steps_per_call=2,
                                    scaled=True, clip=1.0)
    for k in p_on:
        np.testing.assert_allclose(p_on[k], p_off[k],
                                   rtol=2e-6, atol=2e-7, err_msg=k)
    np.testing.assert_allclose(o_on, o_off, rtol=2e-6, atol=2e-7)
    assert s_on.loss_scale == s_off.loss_scale


def test_zero_state_bytes_one_over_n(monkeypatch):
    """The memory claim: per-replica optimizer-state bytes under the
    sharded update are <= full/N plus padding slack, and the report
    exposes the per-step all-gather volume."""
    _, _, step_off, st_off = _train(monkeypatch, "off", optimizer="adam",
                                    steps=1)
    _, _, step_on, st_on = _train(monkeypatch, "on", optimizer="adam",
                                  steps=1)
    full = zero.state_bytes_per_replica(st_off)
    shard = zero.state_bytes_per_replica(st_on)
    # slack: each padded tile may round one element per leaf per device
    slack = sum(8 * 4 * 2 for _ in st_on)
    assert shard <= full / 8 + slack, (shard, full)
    rep = step_on.memory_report(None, st_on)
    assert rep["zero"] is True
    assert rep["opt_state_bytes"] == shard
    rep_off = step_off.memory_report(None, st_off)
    assert rep_off["zero"] is False


def test_zero_aot_compile(monkeypatch):
    """AOT ``compile()`` with the sharded update: the executable is
    built with the zero state layout and serves the call."""
    import jax

    from mxnet_tpu.fused import TrainStep

    monkeypatch.setenv("MXNET_ZERO_MIN_PARAM_BYTES", "0")
    mesh = create_mesh({"data": 8}, devices=_devices(8))
    step = TrainStep(_mlp_sym(), optimizer="adam",
                     optimizer_params={"learning_rate": 0.125},
                     mesh=mesh, zero="on")
    shapes = {"data": (16, 8), "softmax_label": (16,)}
    step.compile(shapes)
    assert step._aot is not None
    params, aux, states = step.init_state(shapes)
    rs = np.random.RandomState(0)
    bd = {"data": rs.randn(16, 8).astype("float32"),
          "softmax_label": rs.randint(0, 4, (16,)).astype("float32")}
    params, aux, states, _ = step(params, aux, states, bd,
                                  jax.random.PRNGKey(0))
    assert step._aot is not None  # served without falling back
    rep = step.memory_report(params, states)
    assert rep["update_gather_bytes"] > 0


def test_decline_warner_scoped_per_step(monkeypatch):
    """Regression: decline warnings fire once per TrainStep, not once
    per process — a second ineligible step must still report."""
    from mxnet_tpu.fused import TrainStep

    for _ in range(2):
        with pytest.warns(RuntimeWarning, match="MXNET_ZERO=on"):
            TrainStep(_mlp_sym(), optimizer="sgd",
                      optimizer_params={"learning_rate": 0.125},
                      zero="on")


# -- ZeRO-3: parameters sharded at rest ------------------------------------

@pytest.mark.parametrize("optimizer,overlap_env", [
    ("sgd", "on"),    # DDP path: grads arrive reduce-scattered as tiles
    ("adam", "off"),  # GSPMD constraint form, stateful optimizer
])
def test_zero3_matches_replicated_bit_exact(monkeypatch, optimizer,
                                            overlap_env):
    """The ZeRO-3 acceptance equivalence: 3 fp32 steps with params at
    rest as flat 1/N tiles (bucketed in-step gathers, backward
    re-gather via remat) produce the replicated update's parameters
    (two executables; 0 spacings apart read on jax 0.9.0)."""
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)  # no declines
        p3, o3, _, _ = _train(monkeypatch, "3", optimizer=optimizer,
                              overlap_env=overlap_env)
    p_off, o_off, _, _ = _train(monkeypatch, "off", optimizer=optimizer,
                                overlap_env=overlap_env)
    assert set(p3) == set(p_off)
    for k in p3:
        assert_close_across_executables(p3[k], p_off[k], err_msg=k)
    assert_close_across_executables(o3, o_off)


def test_zero3_composes_scan_clip_and_loss_scale(monkeypatch):
    """ZeRO-3 inside the K-step scan with global-norm clipping and the
    dynamic loss scaler — the full composition."""
    p3, o3, s3, _ = _train(monkeypatch, "3", optimizer="adam",
                           steps=2, steps_per_call=2, scaled=True,
                           clip=1.0)
    p_off, o_off, s_off, _ = _train(monkeypatch, "off", optimizer="adam",
                                    steps=2, steps_per_call=2,
                                    scaled=True, clip=1.0)
    for k in p3:
        np.testing.assert_allclose(p3[k], p_off[k],
                                   rtol=2e-6, atol=2e-7, err_msg=k)
    np.testing.assert_allclose(o3, o_off, rtol=2e-6, atol=2e-7)
    assert s3.loss_scale == s_off.loss_scale


def test_zero3_params_bytes_at_rest(monkeypatch):
    """The ZeRO-3 memory claim, measured two ways: the labeled
    ``memory_report`` columns say one replica holds <= full/N + padding
    slack of the params at rest (and no trailing update gather), and
    the compiled executable's own ``memory_analysis`` argument bytes
    shrink by at least half the replicated param footprint."""
    import jax

    from mxnet_tpu.fused import TrainStep

    monkeypatch.setenv("MXNET_ZERO_MIN_PARAM_BYTES", "0")
    monkeypatch.setenv("MXNET_ZERO_GATHER_BUCKET_MB", "0.0001")
    mesh = create_mesh({"data": 8}, devices=_devices(8))
    shapes = {"data": (16, 8), "softmax_label": (16,)}
    reports, aot_args = {}, {}
    for mode in ("off", "3"):
        step = TrainStep(_mlp_sym(), optimizer="adam",
                         optimizer_params={"learning_rate": 0.125},
                         mesh=mesh, zero=mode)
        step.compile(shapes)
        params, aux, states = step.init_state(shapes)
        reports[mode] = step.memory_report(params, states)
        aot_args[mode] = reports[mode].get("aot_argument_bytes")
    full = reports["off"]["params_bytes_per_replica"]
    at_rest = reports["3"]["params_bytes_per_replica"]
    lay = zero.layout({"fc1_weight": np.zeros((16, 8), "float32"),
                       "fc1_bias": np.zeros((16,), "float32"),
                       "fc2_weight": np.zeros((4, 16), "float32"),
                       "fc2_bias": np.zeros((4,), "float32")}, 8,
                      min_bytes=0)
    slack = sum(8 * e.dtype.itemsize for e in lay.values())
    assert at_rest <= full / 8 + slack, (at_rest, full)
    rep3 = reports["3"]
    assert rep3["zero3"] is True
    assert rep3["update_gather_bytes"] == 0      # no trailing gather
    assert rep3["gather_bytes_per_step"] == 2 * zero.update_gather_bytes(
        lay)                                     # fwd gathers + re-gather
    assert rep3["total_state_bytes_per_replica"] == (
        rep3["opt_state_bytes"] + at_rest)
    # the executable-level watermark: at-rest args are 1/N, so the AOT
    # argument footprint must drop by at least half the param bytes
    if aot_args["off"] and aot_args["3"]:
        assert aot_args["3"] <= aot_args["off"] - full // 2, aot_args


def test_zero3_aot_compile(monkeypatch):
    """AOT ``compile()`` under ZeRO-3: the executable is built against
    the flat at-rest param avals and serves the live call."""
    import jax

    from mxnet_tpu.fused import TrainStep

    monkeypatch.setenv("MXNET_ZERO_MIN_PARAM_BYTES", "0")
    monkeypatch.setenv("MXNET_ZERO_GATHER_BUCKET_MB", "0.0001")
    mesh = create_mesh({"data": 8}, devices=_devices(8))
    step = TrainStep(_mlp_sym(), optimizer="adam",
                     optimizer_params={"learning_rate": 0.125},
                     mesh=mesh, zero="3")
    shapes = {"data": (16, 8), "softmax_label": (16,)}
    step.compile(shapes)
    assert step._aot is not None
    params, aux, states = step.init_state(shapes)
    lay = step.zero_layout(params)
    for n, ent in lay.items():
        if ent.sharded:
            assert tuple(params[n].shape) == (ent.padded,), n
    rs = np.random.RandomState(0)
    bd = {"data": rs.randn(16, 8).astype("float32"),
          "softmax_label": rs.randint(0, 4, (16,)).astype("float32")}
    params, aux, states, _ = step(params, aux, states, bd,
                                  jax.random.PRNGKey(0))
    assert step._aot is not None  # served without falling back
    # round trip back to canonical shapes is exact
    canon = step.unpack_params(params)
    for n, ent in lay.items():
        assert tuple(canon[n].shape) == ent.shape, n


@pytest.mark.chaos
def test_zero3_gather_fault_bounds_dispatch(monkeypatch):
    """Arming ``zero_gather`` puts the ZeRO-3 step (bucket all-gathers
    included) under the kvstore wall-clock bound: a delay past
    ``MXNET_KV_TIMEOUT_S`` surfaces the bounded-collective error naming
    the knob and the gather instead of hanging."""
    import jax

    from mxnet_tpu.fused import TrainStep
    from mxnet_tpu.testing import faults

    monkeypatch.setenv("MXNET_ZERO_MIN_PARAM_BYTES", "0")
    monkeypatch.setenv("MXNET_ZERO_GATHER_BUCKET_MB", "0.0001")
    monkeypatch.setenv("MXNET_KV_TIMEOUT_S", "1")
    monkeypatch.setenv("MXNET_FAULT_INJECT", "zero_gather:delay:seconds=5")
    faults.reset()
    try:
        mesh = create_mesh({"data": 8}, devices=_devices(8))
        step = TrainStep(_mlp_sym(), optimizer="sgd",
                         optimizer_params={"learning_rate": 0.125},
                         mesh=mesh, zero="3")
        shapes = {"data": (16, 8), "softmax_label": (16,)}
        params, aux, states = step.init_state(shapes)
        rs = np.random.RandomState(0)
        bd = {"data": rs.randn(16, 8).astype("float32"),
              "softmax_label": rs.randint(0, 4, (16,))
              .astype("float32")}
        with pytest.raises(MXNetError) as exc:
            step(params, aux, states, bd, jax.random.PRNGKey(0))
        msg = str(exc.value)
        assert "MXNET_KV_TIMEOUT_S" in msg
        assert "all-gather" in msg
    finally:
        monkeypatch.delenv("MXNET_FAULT_INJECT")
        faults.reset()


# -- fault site ------------------------------------------------------------

@pytest.mark.chaos
def test_zero_update_fault_bounds_dispatch(monkeypatch):
    """Arming ``zero_update`` puts the sharded dispatch under the
    kvstore wall-clock bound even single-process: a delay past
    ``MXNET_KV_TIMEOUT_S`` surfaces the bounded-collective error naming
    the knob instead of hanging."""
    import jax

    from mxnet_tpu.fused import TrainStep
    from mxnet_tpu.testing import faults

    monkeypatch.setenv("MXNET_ZERO_MIN_PARAM_BYTES", "0")
    monkeypatch.setenv("MXNET_KV_TIMEOUT_S", "1")
    monkeypatch.setenv("MXNET_FAULT_INJECT", "zero_update:delay:seconds=5")
    faults.reset()
    try:
        mesh = create_mesh({"data": 8}, devices=_devices(8))
        step = TrainStep(_mlp_sym(), optimizer="sgd",
                         optimizer_params={"learning_rate": 0.125},
                         mesh=mesh, zero="on")
        shapes = {"data": (16, 8), "softmax_label": (16,)}
        params, aux, states = step.init_state(shapes)
        rs = np.random.RandomState(0)
        bd = {"data": rs.randn(16, 8).astype("float32"),
              "softmax_label": rs.randint(0, 4, (16,))
              .astype("float32")}
        with pytest.raises(MXNetError) as exc:
            step(params, aux, states, bd, jax.random.PRNGKey(0))
        msg = str(exc.value)
        assert "MXNET_KV_TIMEOUT_S" in msg
        assert "ZeRO sharded update" in msg
    finally:
        monkeypatch.delenv("MXNET_FAULT_INJECT")
        faults.reset()


# -- elastic checkpoint resume matrix (single process) ---------------------

def _fit(tmp, num_epoch, zero_mode, ndev, mgr=None, resume=None):
    """Module.fit on a dist-sync kvstore + DP mesh (the fused path)."""
    import jax

    from mxnet_tpu import checkpoint as ckpt

    rs = np.random.RandomState(0)
    X = rs.randn(64, 8).astype("float32")
    w = rs.randn(8, 3).astype("float32")
    y = (X @ w).argmax(axis=1).astype("float32")
    # batch 16 keeps per-device batch >= 2 on the 8-way mesh: at
    # per-device batch 1 CPU XLA fuses the degenerate rank-1 local
    # grads differently in the zero=3 (gathered-param) backward than in
    # the replicated one, giving rounding-level (~1e-7) divergence
    it = mx.io.NDArrayIter(X, y, batch_size=16, shuffle=True, seed=42)
    np.random.seed(7)
    mx.random.seed(7)
    mod = mx.mod.Module(_mlp_resume_sym(), context=mx.cpu())
    mesh = create_mesh({"data": ndev}, devices=_devices(ndev))
    with mesh_scope(mesh):
        mod.fit(it, num_epoch=num_epoch, optimizer="adam",
                optimizer_params={"learning_rate": 0.125},
                kvstore="dist_tpu_sync", checkpoint=mgr,
                zero=zero_mode, resume_from=resume)
    return {n: a.asnumpy() for n, a in mod.get_params()[0].items()}


def _mlp_resume_sym():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=3, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


@pytest.mark.parametrize("szero,rzero,rdev", [
    ("on", "on", 8),   # same topology, same executable: the same bits
    ("on", "off", 8),  # sharded save seeds the replicated update
    ("on", "on", 4),   # different N re-tiles; order differs
    ("3", "3", 8),     # ZeRO-3 save -> ZeRO-3 continuation
    ("3", "off", 8),   # ZeRO-3 save seeds the replicated update
    ("3", "on", 4),    # ZeRO-3 save, stage-1 resume on fewer devs
])
def test_zero_ckpt_resume_matrix(monkeypatch, tmp_path, szero, rzero,
                                 rdev):
    """A zero=on or zero=3 save (sharded Adam moments — and under
    ZeRO-3 the at-rest param tiles — through the v2 piece windows)
    resumes into the same mesh bit for bit (the same executable over
    the same state), into zero=off as closely as two executables agree
    (unsharded seeding), and into a different device count within
    reduction-order tolerance — all matching the straight 3-epoch
    run."""
    from mxnet_tpu import checkpoint as ckpt

    monkeypatch.setenv("MXNET_ZERO_MIN_PARAM_BYTES", "0")
    monkeypatch.setenv("MXNET_ZERO_GATHER_BUCKET_MB", "0.0001")
    _devices(8)
    straight = _fit(tmp_path, 3, szero, 8)
    d = str(tmp_path / "ck")
    mgr = ckpt.CheckpointManager(d, prefix="m")
    _fit(tmp_path, 1, szero, 8, mgr=mgr)
    # the save really carried sharded state, not the legacy blob
    state = ckpt.CheckpointManager(d, prefix="m").load()
    assert state.opt_states is not None
    assert state.states_path is None
    resumed = _fit(tmp_path, 3, rzero, rdev,
                   resume=ckpt.CheckpointManager(d, prefix="m"))
    for k in straight:
        if (szero, 8) == (rzero, rdev):
            np.testing.assert_array_equal(straight[k], resumed[k],
                                          err_msg=k)
        elif rdev == 8:
            assert_close_across_executables(straight[k], resumed[k],
                                            err_msg=k)
        else:
            np.testing.assert_allclose(straight[k], resumed[k],
                                       rtol=1e-5, atol=1e-6, err_msg=k)


# -- multi-process round-trip (slow) ---------------------------------------

def _free_coordinator():
    import socket

    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    return "127.0.0.1:%d" % port


def _worker_env():
    env = {**os.environ}
    for k in ("XLA_FLAGS", "MXNET_FAULT_INJECT", "MXNET_NUM_WORKERS",
              "MXNET_ZERO", "MXNET_ZERO_MIN_PARAM_BYTES",
              "MXNET_ZERO_GATHER_BUCKET_MB"):
        env.pop(k, None)
    return env


def _run_one(mode, workdir):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "zero_worker.py"), mode,
         workdir], env=_worker_env(), capture_output=True, text=True,
        timeout=240)
    assert proc.returncode == 0, "worker failed:\n%s\n%s" % (
        proc.stdout, proc.stderr)


def _run_pod(mode, workdir):
    coordinator = _free_coordinator()
    procs = [subprocess.Popen(
        [sys.executable, os.path.join(HERE, "zero_worker.py"), mode,
         workdir, coordinator, "2", str(rank)], env=_worker_env(),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for rank in range(2)]
    outs = [p.communicate(timeout=240) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, "rank failed:\n%s\n%s" % (out, err)


def _assert_states_match(oracle, path):
    a = np.load(oracle)
    b = np.load(path)
    assert set(a.files) == set(b.files), (a.files, b.files)
    for k in a.files:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


@pytest.mark.slow
def test_zero_state_roundtrips_across_process_topologies(tmp_path):
    """Acceptance criterion: ZeRO optimizer state saved by an N-replica
    run restores bit-exactly on M replicas — 2 processes -> 1 and
    1 -> 2 — including ``num_update`` and both Adam moments
    (``tests/zero_worker.py``; identical data/seeds on both topologies,
    so the single-process canonical dump is the oracle for both)."""
    one = str(tmp_path / "one")
    os.makedirs(one)
    _run_one("train", one)                      # writes the oracle too
    oracle = os.path.join(one, "canonical_rank0.npz")
    # 1-proc save -> 2-proc pod load: every rank reassembles the
    # canonical moments
    _run_pod("dump", one)
    for rank in range(2):
        _assert_states_match(
            oracle, os.path.join(one, "loaded_rank%d.npz" % rank))

    # 2-proc pod save (each rank writes only its 1/N windows) -> 1-proc
    # load matches the same oracle bit for bit
    two = str(tmp_path / "two")
    os.makedirs(two)
    _run_pod("train", two)
    _run_one("dump", two)
    _assert_states_match(oracle, os.path.join(two, "loaded_rank0.npz"))


@pytest.mark.slow
def test_zero3_params_roundtrip_across_process_topologies(tmp_path):
    """ZeRO-3 acceptance: a 2-process save in which each rank writes
    only its at-rest 1/N param tile windows (no rank ever holds the
    full params) restores on 1 process — optimizer moments AND the
    canonical params — bit-exact against the single-process oracle,
    and the 1-proc save loads back on a 2-proc pod the same way."""
    one = str(tmp_path / "one")
    os.makedirs(one)
    _run_one("train3", one)                     # writes both oracles
    states_oracle = os.path.join(one, "canonical_rank0.npz")
    params_oracle = os.path.join(one, "canonical3_rank0.npz")
    # 1-proc tile save -> 2-proc pod load
    _run_pod("dump3", one)
    for rank in range(2):
        _assert_states_match(
            states_oracle, os.path.join(one, "loaded_rank%d.npz" % rank))
        _assert_states_match(
            params_oracle, os.path.join(one, "loaded3_rank%d.npz" % rank))

    # 2-proc pod tile save (each rank only its windows) -> 1-proc load,
    # restored unsharded: the zero=3 -> zero=off interchange
    two = str(tmp_path / "two")
    os.makedirs(two)
    _run_pod("train3", two)
    _run_one("dump3", two)
    _assert_states_match(states_oracle,
                         os.path.join(two, "loaded_rank0.npz"))
    _assert_states_match(params_oracle,
                         os.path.join(two, "loaded3_rank0.npz"))
