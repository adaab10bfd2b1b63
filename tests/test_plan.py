"""Unified parallel plan (``parallel/plan.py`` + the fused step's
``plan=`` branch): ParallelPlan construction/identity units, the
composed tp x zero3 training equivalence (bit-exact against the same
plan with the sharded update off, tolerance against the single-device
oracle), composition with the multi-step scan + dynamic loss scaling +
global-norm clipping, the per-replica memory claim, the group-scoped
collective roster (``tools/fusion_audit.expect_plan``), Module/env
threading, the plan-elastic checkpoint resume matrix, and the decline
diagnostics that point users at the plan."""
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import ParallelPlan, create_mesh, mesh_scope, zero

from closeness import assert_close_across_executables

HERE = os.path.dirname(os.path.abspath(__file__))
TOOLS = os.path.join(os.path.dirname(HERE), "tools")


def _devices(n):
    import jax

    if len(jax.devices()) < n:
        pytest.skip("needs %d devices" % n)
    return jax.devices()[:n]


# -- units -----------------------------------------------------------------

def test_plan_parse_roundtrip():
    p = ParallelPlan.parse("data=4, model=2, zero=3")
    assert p == ParallelPlan(data=4, model=2, zero="3")
    assert ParallelPlan.parse(p) is p
    # zero aliases follow MXNET_ZERO's grammar
    assert ParallelPlan(zero="1").zero == "on"
    assert ParallelPlan(zero="0").zero == "off"
    # describe() is the checkpoint-manifest identity: stable keys,
    # pipe extras only when the pipe axis exists
    d = p.describe()
    assert d == {"data": 4, "model": 2, "pipe": 1, "seq": 1, "zero": "3"}
    pp = ParallelPlan.parse("pipe=2,schedule=gpipe,microbatches=4")
    assert pp.describe()["schedule"] == "gpipe"
    assert pp.describe()["n_microbatches"] == 4


def test_plan_parse_errors():
    with pytest.raises(MXNetError, match="key=value"):
        ParallelPlan.parse("data:4")
    with pytest.raises(MXNetError, match="unknown plan key"):
        ParallelPlan.parse("dta=4")
    with pytest.raises(MXNetError, match="integer"):
        ParallelPlan.parse("data=4,model=two")
    with pytest.raises(MXNetError, match="microbatches"):
        ParallelPlan.parse("pipe=2,microbatches=many")
    with pytest.raises(MXNetError, match="zero"):
        ParallelPlan(zero="sideways")
    with pytest.raises(MXNetError, match="schedule"):
        ParallelPlan(schedule="interleaved")
    with pytest.raises(MXNetError, match="model"):
        ParallelPlan(model=0)
    with pytest.raises(MXNetError, match="data"):
        ParallelPlan(data=-2)


def test_plan_axes_and_fingerprint():
    p = ParallelPlan(data=2, model=2, zero="3")
    # size-1 axes drop out of the mesh; data always stays
    assert p.axes() == {"data": 2, "model": 2}
    assert ParallelPlan(data=4).axes() == {"data": 4}
    assert p.fingerprint() == "data2-model2-z3"
    assert ParallelPlan(data=4).fingerprint() == "data4"
    # the -1 wildcard resolves through the mesh
    wild = ParallelPlan(zero="on")
    mesh = create_mesh({"data": 8}, devices=_devices(8))
    assert wild.fingerprint(mesh) == "data8-zon"


def test_plan_mesh_slices_devices():
    _devices(8)
    p = ParallelPlan(data=2, model=2)
    mesh = p.mesh()
    # a 4-way plan on an 8-device host uses exactly 4: the plan means
    # the SAME topology on any host big enough (elastic restores)
    assert dict(mesh.shape) == {"data": 2, "model": 2}
    p.validate_mesh(mesh)
    with pytest.raises(MXNetError, match="mesh axis"):
        ParallelPlan(data=4, model=2).validate_mesh(mesh)
    # the data wildcard matches any size
    ParallelPlan(data=-1, model=2).validate_mesh(mesh)


def test_plan_param_spec():
    p = ParallelPlan(data=4, model=2)
    # Megatron MLP pairing on canonical (out, in) FC weights
    assert p.param_spec("fc1_weight", (16, 8)) == (None, "model")
    assert p.param_spec("fc2_weight", (4, 16)) == ("model",)
    assert p.param_spec("fc1_bias", (16,)) == ()
    # transformer rules ride on top
    assert p.param_spec("l0_attn_in_weight", (48, 16)) == ("model",)
    assert p.param_spec("l0_attn_out_weight", (16, 16)) == (None, "model")
    # divisibility fallback: a dim the model size does not divide
    # replicates instead of erroring
    assert p.param_spec("fc1_weight", (16, 9)) == ()
    # pure-DP and ring-seq plans place nothing on the model axis
    assert ParallelPlan(data=8).param_spec("fc1_weight", (16, 8)) == ()
    assert ParallelPlan(data=2, model=2, seq=2).param_spec(
        "fc1_weight", (16, 8)) == ()


def test_plan_autotune_topology_key():
    from mxnet_tpu import autotune

    _devices(4)
    p = ParallelPlan(data=2, model=2, zero="3")
    mesh = p.mesh()
    assert autotune.train_key_topology(mesh, p) == "plan:data2-model2-z3"
    # plan knobs must not leak onto pure-mesh runs of the same symbol
    assert autotune.train_key_topology(mesh, None) != \
        autotune.train_key_topology(mesh, p)
    assert autotune.TRAIN_KNOB_ENV["gather_bucket_mb"] == \
        "MXNET_ZERO_GATHER_BUCKET_MB"


# -- composed training equivalence -----------------------------------------

def _mlp_sym():
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax",
                                normalization="batch")


def _train_plan(monkeypatch, zero_mode, optimizer="sgd", steps=3,
                steps_per_call=1, scaled=False, clip=None, batch=16,
                feat=8, data=4, model=2):
    """TrainStep under the composed plan (tp x zero over a data*model
    mesh); returns (params, last outs, step, states).  Power-of-two
    lr/rescale so zero on/off under the SAME plan is bit-exact in
    fp32 — the TP reduction order is identical, only the weight-update
    tiling differs."""
    import jax

    from mxnet_tpu.fused import TrainStep
    from mxnet_tpu.health import DynamicLossScaler, StepHealth

    _devices(data * model)
    monkeypatch.setenv("MXNET_ZERO_MIN_PARAM_BYTES", "0")
    monkeypatch.setenv("MXNET_ZERO_GATHER_BUCKET_MB", "0.0001")
    monkeypatch.setenv("MXNET_GRAD_OVERLAP", "off")
    opt_params = {"learning_rate": 0.125, "rescale_grad": 1.0 / batch}
    if clip is not None:
        opt_params["clip_global_norm"] = clip
    kw = {}
    if scaled:
        kw["health"] = StepHealth(
            scaler=DynamicLossScaler(init_scale=256.0))
    step = TrainStep(_mlp_sym(), optimizer=optimizer,
                     optimizer_params=opt_params,
                     steps_per_call=steps_per_call,
                     plan=ParallelPlan(data=data, model=model,
                                       zero=zero_mode), **kw)
    assert step.plan is not None
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
    for _ in range(steps):
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
    return ({k: np.asarray(v)
             for k, v in step.unpack_params(params).items()},
            np.asarray(out[0]), step, states)


@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
def test_plan_zero3_matches_zero_off_bit_exact(monkeypatch, optimizer):
    """The acceptance equivalence: tp(2) x zero3 over the composed plan
    produces bit-identical parameters to the same plan with the sharded
    update off — the group-local tiling must not change the math."""
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)  # no declines
        p3, o3, _, _ = _train_plan(monkeypatch, "3", optimizer=optimizer)
    poff, ooff, _, _ = _train_plan(monkeypatch, "off",
                                   optimizer=optimizer)
    assert set(p3) == set(poff)
    for k in p3:  # two executables: tests/closeness.py
        assert_close_across_executables(p3[k], poff[k], err_msg=k)
    assert_close_across_executables(o3, ooff)


def test_plan_matches_single_device_oracle(monkeypatch):
    """The composed program against the no-parallelism oracle: same
    data, same seeds, one device — equal within reduction-order
    tolerance (TP splits the contraction, DP splits the batch sum)."""
    import jax

    from mxnet_tpu.fused import TrainStep

    p3, o3, _, _ = _train_plan(monkeypatch, "3", optimizer="adam")
    step = TrainStep(_mlp_sym(), optimizer="adam",
                     optimizer_params={"learning_rate": 0.125,
                                       "rescale_grad": 1.0 / 16})
    shapes = {"data": (16, 8), "softmax_label": (16,)}
    params, aux, states = step.init_state(shapes)
    rs = np.random.RandomState(42)
    rng = jax.random.PRNGKey(7)
    for _ in range(3):
        bd = {"data": rs.randn(16, 8).astype("float32"),
              "softmax_label": rs.randint(0, 4, (16,))
              .astype("float32")}
        params, aux, states, out = step(params, aux, states, bd, rng)
    for k in p3:
        np.testing.assert_allclose(p3[k], np.asarray(params[k]),
                                   rtol=2e-4, atol=2e-5, err_msg=k)
    np.testing.assert_allclose(o3, np.asarray(out[0]),
                               rtol=2e-4, atol=2e-5)


def test_plan_zero3_composes_scan_clip_and_loss_scale(monkeypatch):
    """tp x zero3 inside the K-step scan with global-norm clipping and
    the dynamic loss scaler — the full composition stays one program."""
    p3, o3, s3, _ = _train_plan(monkeypatch, "3", optimizer="adam",
                                steps=2, steps_per_call=2, scaled=True,
                                clip=1.0)
    poff, ooff, soff, _ = _train_plan(monkeypatch, "off",
                                      optimizer="adam", steps=2,
                                      steps_per_call=2, scaled=True,
                                      clip=1.0)
    for k in p3:
        np.testing.assert_allclose(p3[k], poff[k],
                                   rtol=2e-6, atol=2e-7, err_msg=k)
    np.testing.assert_allclose(o3, ooff, rtol=2e-6, atol=2e-7)
    assert s3.loss_scale == soff.loss_scale


def test_plan_zero3_memory_claim(monkeypatch):
    """The acceptance memory claim: under tp(2) x zero3 one replica
    holds well under 1/4 of the replicated param+state footprint (the
    plan shards params over model AND tiles the remainder over data)."""
    from mxnet_tpu.fused import TrainStep

    _, _, step3, _ = _train_plan(monkeypatch, "3", optimizer="adam",
                                 steps=1)
    shapes = {"data": (16, 8), "softmax_label": (16,)}
    p3, _, st3 = step3.init_state(shapes)
    rep3 = step3.memory_report(p3, st3)
    # fully replicated baseline: no plan, no mesh
    base = TrainStep(_mlp_sym(), optimizer="adam",
                     optimizer_params={"learning_rate": 0.125})
    pb, _, sb = base.init_state(shapes)
    repb = base.memory_report(pb, sb)
    assert rep3["zero3"] is True
    full = repb["params_bytes_per_replica"] + repb["opt_state_bytes"]
    mine = rep3["params_bytes_per_replica"] + rep3["opt_state_bytes"]
    assert rep3["params_bytes_per_replica"] * 4 < \
        repb["params_bytes_per_replica"], (rep3, repb)
    assert mine * 4 < full, (mine, full)
    assert rep3["gather_bytes_per_step"] > 0
    assert rep3["update_gather_bytes"] == 0      # no trailing gather


def test_plan_zero3_aot_and_group_scoped_roster(monkeypatch):
    """AOT ``compile()`` under the composed plan serves the live call,
    and the optimized HLO's collective roster is GROUP-SCOPED: ZeRO
    traffic in per-model-group replica groups, TP reductions in
    per-data-group ones, no global monolithic collective — checked by
    the same ``expect_plan`` gate ``tools/fusion_audit --expect-plan``
    runs on dump artifacts."""
    import jax

    from mxnet_tpu.fused import TrainStep

    sys.path.insert(0, TOOLS)
    try:
        import fusion_audit
    finally:
        sys.path.remove(TOOLS)
    _devices(8)
    monkeypatch.setenv("MXNET_ZERO_MIN_PARAM_BYTES", "0")
    monkeypatch.setenv("MXNET_ZERO_GATHER_BUCKET_MB", "0.0001")
    plan = ParallelPlan(data=4, model=2, zero="3")
    step = TrainStep(_mlp_sym(), optimizer="adam",
                     optimizer_params={"learning_rate": 0.125},
                     plan=plan)
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
    payload = fusion_audit.parse_hlo(step._aot.as_text())
    payload["plan"] = dict(plan.describe())
    payload["plan"]["data"] = 4
    lay = step.zero_layout(params)
    payload["zero_sharded_bytes"] = sum(
        e.padded * e.dtype.itemsize for e in lay.values() if e.sharded)
    assert fusion_audit.expect_plan(payload, "test_plan")
    sized = [c for c in payload["collectives"] if c.get("groups")]
    # the data-axis ZeRO traffic runs in 2 model groups of 4 ...
    assert any(c["groups"] == 2 and c["group_size"] == 4 for c in sized)
    # ... and the Megatron reduction in 4 data groups of 2
    assert any(fusion_audit._collective_kind(c["op"]) == "all-reduce"
               and c["groups"] == 4 and c["group_size"] == 2
               for c in sized)


# -- guards ---------------------------------------------------------------

def test_trainstep_plan_guards(monkeypatch):
    from mxnet_tpu.fused import TrainStep

    _devices(4)
    with pytest.raises(MXNetError, match="PipelineTrainStep"):
        TrainStep(_mlp_sym(), optimizer="sgd",
                  optimizer_params={"learning_rate": 0.125},
                  plan=ParallelPlan(data=2, pipe=2))
    with pytest.raises(MXNetError, match="param_sharding"):
        TrainStep(_mlp_sym(), optimizer="sgd",
                  optimizer_params={"learning_rate": 0.125},
                  param_sharding="tp",
                  plan=ParallelPlan(data=2, model=2))
    # an externally scoped mesh must carry the plan's axes
    mesh = create_mesh({"data": 4}, devices=_devices(4))
    with pytest.raises(MXNetError, match="mesh axis"):
        TrainStep(_mlp_sym(), optimizer="sgd",
                  optimizer_params={"learning_rate": 0.125},
                  mesh=mesh, plan=ParallelPlan(data=2, model=2))


def test_zero_decline_names_blocking_param():
    """Satellite diagnostics: a forced zero request over an explicit
    tp/fsdp layout names the specific blocking parameter and its spec,
    and points at the ParallelPlan composition instead of the old
    generic sentence."""
    mesh = create_mesh({"data": 4, "model": 2}, devices=_devices(8))
    seen = []
    got = zero.zero_axis(mesh, "data", param_sharding="tp", mode="on",
                         warn=lambda k, m: seen.append((k, m)),
                         param_names=("fc1_weight", "fc1_bias",
                                      "fc2_weight"))
    assert got is None
    assert seen and seen[0][0] == "zero-params"
    msg = seen[0][1]
    assert "fc1_weight" in msg or "fc2_weight" in msg
    assert "PartitionSpec" in msg
    assert "ParallelPlan" in msg


def test_zero_trivial_tp_layout_is_pure_dp():
    """A tp style whose every spec resolves trivially (no model axis on
    the mesh) is pure DP: the sharded update runs, nothing warns."""
    mesh = create_mesh({"data": 8}, devices=_devices(8))
    seen = []
    got = zero.zero_axis(mesh, "data", param_sharding="tp", mode="on",
                         warn=lambda k, m: seen.append((k, m)),
                         param_names=("fc1_weight", "fc2_weight"))
    assert got == "data"
    assert not seen


# -- Module / env threading ------------------------------------------------

def _mlp_resume_sym():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=3, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _fit_plan(num_epoch, plan, mgr=None, resume=None, batch=16):
    """Module.fit under a composed plan (no kvstore: a plan declares
    its own topology and GSPMD owns every collective)."""
    rs = np.random.RandomState(0)
    X = rs.randn(64, 8).astype("float32")
    w = rs.randn(8, 3).astype("float32")
    y = (X @ w).argmax(axis=1).astype("float32")
    it = mx.io.NDArrayIter(X, y, batch_size=batch, shuffle=True, seed=42)
    np.random.seed(7)
    mx.random.seed(7)
    mod = mx.mod.Module(_mlp_resume_sym(), context=mx.cpu())
    mod.fit(it, num_epoch=num_epoch, optimizer="adam",
            optimizer_params={"learning_rate": 0.125},
            checkpoint=mgr, plan=plan, resume_from=resume)
    if plan is not None and ParallelPlan.parse(plan).zero == "3":
        # the plan's zero mode must survive the Module path (it once
        # degraded to the MXNET_ZERO default)
        assert mod._fused is not None and mod._fused.zero3
    return {n: a.asnumpy() for n, a in mod.get_params()[0].items()}


def test_module_plan_object_string_env_identical(monkeypatch):
    """The three plan surfaces — object, spec string, MXNET_PLAN env —
    build the same program: bit-identical parameters."""
    monkeypatch.setenv("MXNET_ZERO_MIN_PARAM_BYTES", "0")
    monkeypatch.setenv("MXNET_ZERO_GATHER_BUCKET_MB", "0.0001")
    _devices(8)
    p_obj = _fit_plan(2, ParallelPlan(data=4, model=2, zero="3"))
    p_str = _fit_plan(2, "data=4,model=2,zero=3")
    monkeypatch.setenv("MXNET_PLAN", "data=4,model=2,zero=3")
    p_env = _fit_plan(2, None)
    monkeypatch.delenv("MXNET_PLAN")
    for k in p_obj:
        np.testing.assert_array_equal(p_obj[k], p_str[k], err_msg=k)
        np.testing.assert_array_equal(p_obj[k], p_env[k], err_msg=k)


def test_module_plan_batch_indivisible_raises(monkeypatch):
    """Under a plan an indivisible batch is an error, not a silent
    fall-back to replicated training (the plan was explicit intent)."""
    _devices(8)
    with pytest.raises(MXNetError, match="not divisible"):
        _fit_plan(1, ParallelPlan(data=8), batch=12)


# -- plan-elastic checkpoint restore ---------------------------------------

@pytest.mark.parametrize("rplan,exact", [
    ("data=4,model=2,zero=3", True),   # same plan: bit-exact
    ("data=4,zero=3", False),          # re-tiled onto pure ZeRO-3
    (None, False),                     # unsharded single-device resume
])
def test_plan_ckpt_resume_matrix(monkeypatch, tmp_path, rplan, exact):
    """A tp(2) x zero3 save (group-local shard-major tiles through the
    v2 piece windows, plan identity in the manifest) resumes into the
    same plan bit-exactly and into a different topology — pure ZeRO-3
    or fully unsharded — within reduction-order tolerance, all
    matching the straight run on the resume topology."""
    from mxnet_tpu import checkpoint as ckpt

    monkeypatch.setenv("MXNET_ZERO_MIN_PARAM_BYTES", "0")
    monkeypatch.setenv("MXNET_ZERO_GATHER_BUCKET_MB", "0.0001")
    _devices(8)
    splan = "data=4,model=2,zero=3"
    straight = _fit_plan(3, splan)
    d = str(tmp_path / "ck")
    mgr = ckpt.CheckpointManager(d, prefix="m")
    _fit_plan(1, splan, mgr=mgr)
    state = ckpt.CheckpointManager(d, prefix="m").load()
    # the manifest carries the plan identity and the sharded state
    assert state.manifest.get("plan") == {"data": 4, "model": 2,
                                          "pipe": 1, "seq": 1,
                                          "zero": "3"}
    assert state.opt_states is not None
    assert state.states_path is None
    resumed = _fit_plan(3, rplan,
                        resume=ckpt.CheckpointManager(d, prefix="m"))
    for k in straight:
        if exact:
            np.testing.assert_array_equal(straight[k], resumed[k],
                                          err_msg=k)
        else:
            np.testing.assert_allclose(straight[k], resumed[k],
                                       rtol=1e-4, atol=1e-5, err_msg=k)


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
              "MXNET_ZERO", "MXNET_PLAN", "MXNET_ZERO_MIN_PARAM_BYTES",
              "MXNET_ZERO_GATHER_BUCKET_MB"):
        env.pop(k, None)
    return env


def _run_one(mode, workdir):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "plan_worker.py"), mode,
         workdir], env=_worker_env(), capture_output=True, text=True,
        timeout=240)
    assert proc.returncode == 0, "worker failed:\n%s\n%s" % (
        proc.stdout, proc.stderr)


def _run_pod(mode, workdir):
    coordinator = _free_coordinator()
    procs = [subprocess.Popen(
        [sys.executable, os.path.join(HERE, "plan_worker.py"), mode,
         workdir, coordinator, "2", str(rank)], env=_worker_env(),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for rank in range(2)]
    outs = [p.communicate(timeout=240) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, "rank failed:\n%s\n%s" % (out, err)


def _assert_npz_match(oracle, path):
    a = np.load(oracle)
    b = np.load(path)
    assert set(a.files) == set(b.files), (a.files, b.files)
    for k in a.files:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


@pytest.mark.slow
def test_plan_roundtrips_across_process_topologies(tmp_path):
    """Acceptance: a tp(2) x zero3 plan save where each of 2 processes
    writes only the group-local tile windows it owns (no rank ever
    materializes a full TP-sharded parameter — asserted inside the
    worker) restores bit-exactly on 1 process, and the 1-process save
    loads back on the 2-process pod (``tests/plan_worker.py``)."""
    one = str(tmp_path / "one")
    os.makedirs(one)
    _run_one("train", one)                      # writes the oracles too
    states_oracle = os.path.join(one, "canonical_rank0.npz")
    params_oracle = os.path.join(one, "canonical3_rank0.npz")
    # 1-proc tile save -> 2-proc pod load
    _run_pod("dump", one)
    for rank in range(2):
        _assert_npz_match(
            states_oracle, os.path.join(one, "loaded_rank%d.npz" % rank))
        _assert_npz_match(
            params_oracle, os.path.join(one, "loaded3_rank%d.npz" % rank))

    # 2-proc pod tile save -> 1-proc load matches the same oracles
    two = str(tmp_path / "two")
    os.makedirs(two)
    _run_pod("train", two)
    _run_one("dump", two)
    _assert_npz_match(states_oracle,
                      os.path.join(two, "loaded_rank0.npz"))
    _assert_npz_match(params_oracle,
                      os.path.join(two, "loaded3_rank0.npz"))
