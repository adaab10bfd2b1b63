"""Bucketed compute/collective gradient overlap
(``parallel/overlap.py`` + the fused train step's DDP branch): bucket
partitioning, eligibility gating, LIBTPU flag arming, the direct
``ddp_value_and_grad`` contract, and end-to-end training equivalence
against the GSPMD reduction — including composition with the health
guard, dynamic loss scaling, and the multi-step scan."""
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.parallel import create_mesh, overlap


def _devices(n):
    import jax

    if len(jax.devices()) < n:
        pytest.skip("needs %d devices" % n)
    return jax.devices()[:n]


def test_bucket_partition():
    sizes = {"a": 100, "b": 100, "c": 300, "d": 50}
    order = ["d", "c", "b", "a"]
    assert overlap.bucket_partition(order, sizes, 200) == \
        [["d"], ["c"], ["b", "a"]]
    # oversized tensors still get their own collective
    assert overlap.bucket_partition(order, sizes, 10) == \
        [["d"], ["c"], ["b"], ["a"]]
    # 0 = one collective per parameter
    assert overlap.bucket_partition(order, sizes, 0) == \
        [[k] for k in order]
    assert overlap.bucket_partition(order, sizes, 10**9) == [order]
    assert overlap.bucket_partition([], {}, 100) == []


def test_ddp_axis_eligibility(monkeypatch):
    mesh = create_mesh({"data": 8}, devices=_devices(8))
    assert overlap.ddp_axis(mesh, "data") == "data"
    assert overlap.ddp_axis(None, "data") is None
    assert overlap.ddp_axis(mesh, "model") is None
    # sharded-param styles keep the GSPMD reduce-scatter path
    assert overlap.ddp_axis(mesh, "data", param_sharding="fsdp") is None
    assert overlap.ddp_axis(mesh, "data",
                            param_sharding="replicated") == "data"
    seq = create_mesh({"seq": 4}, devices=_devices(4))
    assert overlap.ddp_axis(seq, "data") is None
    one = create_mesh({"data": 1}, devices=_devices(1))
    assert overlap.ddp_axis(one, "data") is None
    monkeypatch.setenv("MXNET_GRAD_OVERLAP", "off")
    assert overlap.ddp_axis(mesh, "data") is None


def test_arm_latency_hiding_uses_libtpu_args(monkeypatch):
    """The scheduler flags must ride LIBTPU_INIT_ARGS, never XLA_FLAGS:
    CPU/GPU jaxlib builds abort on unknown --xla_tpu_* in XLA_FLAGS."""
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "--preexisting=1")
    monkeypatch.setenv("XLA_FLAGS", "")
    monkeypatch.setenv("MXNET_XLA_LHS", "1")
    # a late call (this process has a backend by now) must not claim to
    # have armed anything, nor touch the environment
    monkeypatch.setattr(overlap, "_backend_initialized", lambda: True)
    assert not overlap.arm_latency_hiding()
    import os

    assert os.environ["LIBTPU_INIT_ARGS"] == "--preexisting=1"
    monkeypatch.setattr(overlap, "_backend_initialized", lambda: False)
    assert overlap.arm_latency_hiding()

    armed = os.environ["LIBTPU_INIT_ARGS"]
    assert "--preexisting=1" in armed
    assert "--xla_tpu_enable_latency_hiding_scheduler=true" in armed
    assert os.environ["XLA_FLAGS"] == ""
    # idempotent, and a later call sees flags that were there in time
    assert overlap.arm_latency_hiding()
    assert os.environ["LIBTPU_INIT_ARGS"] == armed
    monkeypatch.setattr(overlap, "_backend_initialized", lambda: True)
    assert overlap.arm_latency_hiding()
    monkeypatch.setenv("MXNET_XLA_LHS", "0")
    assert not overlap.arm_latency_hiding()


def test_ddp_value_and_grad_matches_global(monkeypatch):
    import jax
    import jax.numpy as jnp

    mesh = create_mesh({"data": 8}, devices=_devices(8))

    def loss_fn(p, b, r):
        out = jnp.tanh(b["x"] @ p["w"] + p["b"])
        loss = jnp.sum((out - b["y"]) ** 2)
        return loss, ((out,), {"stat": jnp.mean(out)})

    rs = np.random.RandomState(0)
    params = {"w": jnp.asarray(rs.randn(6, 3), "float32"),
              "b": jnp.asarray(rs.randn(3), "float32")}
    batch = {"x": jnp.asarray(rs.randn(16, 6), "float32"),
             "y": jnp.asarray(rs.randn(16, 3), "float32")}
    rng = jax.random.PRNGKey(0)
    res = overlap.ddp_value_and_grad(
        loss_fn, params, batch, rng, mesh, "data",
        order=("b", "w"), bucket_bytes=0)
    assert res is not None
    (loss, ((out,), aux)), grads = res
    (g_loss, ((g_out,), g_aux)), g_grads = jax.value_and_grad(
        lambda p: loss_fn(p, batch, rng), has_aux=True)(params)
    np.testing.assert_allclose(float(loss), float(g_loss), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g_out),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(aux["stat"]), float(g_aux["stat"]),
                               rtol=1e-5)
    for k in grads:
        np.testing.assert_allclose(np.asarray(grads[k]),
                                   np.asarray(g_grads[k]),
                                   rtol=1e-5, atol=1e-6)


def test_ddp_declines_non_batch_output():
    """An output leaf without the batch on its leading dim (scalar
    heads, reductions) has no inferable global stitching — the DDP path
    must decline (warn once, return None) so the caller falls back to
    the GSPMD reduction instead of returning wrong outputs."""
    import jax
    import jax.numpy as jnp

    mesh = create_mesh({"data": 8}, devices=_devices(8))

    def loss_fn(p, b, r):
        loss = jnp.sum(b["x"] * p["w"])
        return loss, ((loss,), {})  # scalar out leaf

    params = {"w": jnp.ones((4,), "float32")}
    batch = {"x": jnp.ones((16, 4), "float32")}
    overlap._warned.discard("outs")
    with pytest.warns(RuntimeWarning, match="declined"):
        res = overlap.ddp_value_and_grad(
            loss_fn, params, batch, jax.random.PRNGKey(0), mesh, "data")
    assert res is None


def _mlp_sym(hidden=16, classes=4, bn=False):
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=hidden, name="fc1")
    if bn:
        net = mx.sym.BatchNorm(net, name="bn1", axis=1)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
    # normalization="batch" is the sharp edge: its gradient scale
    # depends on the batch size the op sees, which under shard_map is
    # the LOCAL shard — the DDP context must widen it back to global
    return mx.sym.SoftmaxOutput(net, name="softmax",
                                normalization="batch")


def _train(monkeypatch, overlap_env, steps=3, steps_per_call=1,
           scaled=False, bn=False, feat=8, batch=16):
    """Run TrainStep on a pure-DP mesh and return final params/outs."""
    import jax

    from mxnet_tpu.fused import TrainStep
    from mxnet_tpu.health import DynamicLossScaler, StepHealth

    monkeypatch.setenv("MXNET_GRAD_OVERLAP", overlap_env)
    if overlap_env != "off":
        # tiny buckets force many collectives — stresses the bucketed
        # schedule, not just the single-psum degenerate case
        monkeypatch.setenv("MXNET_GRAD_BUCKET_MB", "0.0001")
    mesh = create_mesh({"data": 8}, devices=_devices(8))
    kw = {}
    if scaled:
        kw["health"] = StepHealth(
            scaler=DynamicLossScaler(init_scale=256.0))
    step = TrainStep(_mlp_sym(bn=bn), optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1,
                                       "rescale_grad": 1.0 / batch},
                     mesh=mesh, batch_sharding_axis="data",
                     steps_per_call=steps_per_call, **kw)
    if overlap_env == "on":
        assert step.grad_overlap_axis == "data"
    shapes = {"data": (batch, feat), "softmax_label": (batch,)}
    params, aux, states = step.init_state(shapes)
    rs = np.random.RandomState(42)
    rng = jax.random.PRNGKey(7)
    out = None
    for i in range(steps):
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
    # fold aux (BN moving stats) in with the params: the sync-BN test
    # checks the moving stats match the GSPMD global-batch ones too
    merged = {k: np.asarray(v) for k, v in params.items()}
    merged.update({k: np.asarray(v) for k, v in aux.items()})
    return merged, np.asarray(out[0])


def test_overlap_training_matches_gspmd(monkeypatch):
    """The load-bearing equivalence: identical params and outputs after
    several steps with the explicit bucketed reduction vs the GSPMD
    path, on the same mesh with the same data."""
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)  # no declines
        p_on, o_on = _train(monkeypatch, "on")
    p_off, o_off = _train(monkeypatch, "off")
    assert set(p_on) == set(p_off)
    for k in p_on:
        np.testing.assert_allclose(p_on[k], p_off[k],
                                   rtol=1e-5, atol=1e-6, err_msg=k)
    np.testing.assert_allclose(o_on, o_off, rtol=1e-5, atol=1e-6)


def test_overlap_syncbn_matches_gspmd(monkeypatch):
    """BatchNorm under the DDP path must normalize by the GLOBAL
    batch's statistics (sync-BN via the trace context's pmean), exactly
    like GSPMD's global-batch reduction — params, outputs, and the
    moving aux stats all agree."""
    p_on, o_on = _train(monkeypatch, "on", bn=True)
    p_off, o_off = _train(monkeypatch, "off", bn=True)
    for k in p_on:
        np.testing.assert_allclose(p_on[k], p_off[k],
                                   rtol=1e-5, atol=1e-6, err_msg=k)
    np.testing.assert_allclose(o_on, o_off, rtol=1e-5, atol=1e-6)


def test_overlap_composes_with_scan_and_loss_scale(monkeypatch):
    """Bucketed reduction inside the K-step scan body with the dynamic
    loss scaler riding the cotangent — the full PR 3/PR 5 composition."""
    p_on, _ = _train(monkeypatch, "on", steps=2, steps_per_call=2,
                     scaled=True)
    p_off, _ = _train(monkeypatch, "off", steps=2, steps_per_call=2,
                      scaled=True)
    for k in p_on:
        np.testing.assert_allclose(p_on[k], p_off[k],
                                   rtol=1e-5, atol=1e-6, err_msg=k)
