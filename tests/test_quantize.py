"""Weight-only quantization (mxnet_tpu/quantize.py): round-trip error
bounds per storage dtype, cross-process bit-stability, the ZeRO-3
flat-tile interchange (topology-independent codes, gather-path
dequantization, quantized elastic checkpoint restore), and quantized
serving sessions held to the per-precision reference
(tests/closeness.py).

Also the fp8 TRAINING surface that module grew: delayed-scaling
helpers (amax history, realized scales, the fp8_trace site registry),
the custom-VJP fp8 matmul route through TrainStep (history rides the
hstate like the dynamic loss scaler; MXNET_FP8 / MXNET_FP8_LAYERS
gating), and int8/e4m3 quantized KV-cache pages in serving
(per-precision oracle, spec-decode and prefix-cache composition).
"""
import hashlib
import os
import subprocess
import sys

import numpy as np
import pytest

from mxnet_tpu import quantize, serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import create_mesh, zero
from mxnet_tpu.serve import model as serve_model

from closeness import LIMIT_SPACINGS
from serve_util import worst_gap_vs_reference

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = serve.ModelConfig(vocab_size=61, num_layers=2, d_model=32,
                        num_heads=2, max_len=64)
PAGE = 8


def _devices(n):
    import jax

    if len(jax.devices()) < n:
        pytest.skip("needs %d devices" % n)
    return jax.devices()[:n]


@pytest.fixture(scope="module")
def params():
    return serve_model.init_params(CFG, seed=3)


def _sconf(**kw):
    base = dict(slots=3, page_size=PAGE, buckets=(8, 16), max_new=8,
                exact=True)
    base.update(kw)
    return serve.ServeConfig(**base)


def _unwrap(v):
    v = getattr(v, "_data", v)
    if hasattr(v, "asnumpy"):
        v = v.asnumpy()
    return np.asarray(v)


# ---------------------------------------------------------------------------
# mode parsing + eligibility
# ---------------------------------------------------------------------------

def test_quant_mode_parsing():
    for raw in ("", "off", "none", "0", "fp32", None, False):
        assert quantize.quant_mode(raw) == ""
    for raw in ("int8", "I8", " Int8 "):
        assert quantize.quant_mode(raw) == "int8"
    for raw in ("fp8", "e4m3", "float8_e4m3fn", "F8"):
        assert quantize.quant_mode(raw) == "fp8"
    with pytest.raises(MXNetError):
        quantize.quant_mode("int4")


def test_eligibility():
    f32 = np.float32
    assert quantize.eligible((32, 32), f32)          # 4096 B matrix
    assert not quantize.eligible((1024,), f32)       # vector, any size
    assert not quantize.eligible((8, 8), f32)        # 256 B < floor
    assert not quantize.eligible((64, 64), np.int32)  # not floating
    assert quantize.eligible((8, 8), f32, min_bytes=0)


def test_quantize_params_passthrough_and_at_rest_bytes():
    tree = {
        "w": np.random.RandomState(0).randn(64, 64).astype(np.float32),
        "bias": np.zeros(64, np.float32),     # 1-D: stays raw
        "tiny": np.ones((4, 4), np.float32),  # under the byte floor
    }
    qtree = quantize.quantize_params(tree, "int8")
    assert quantize.is_quantized(qtree["w"])
    assert not quantize.is_quantized(qtree["bias"])
    assert not quantize.is_quantized(qtree["tiny"])
    # idempotent: re-quantizing a quantized tree is a no-op
    again = quantize.quantize_params(qtree, "int8")
    assert again["w"] is qtree["w"]
    # the eligible matrix dominates, so the tree shrinks close to 4x
    # (codes 1 B/elem + 64 fp32 scales + the raw small tensors)
    ratio = (quantize.at_rest_bytes(tree)
             / quantize.at_rest_bytes(qtree))
    assert ratio > 3.5
    # dequantize_params resolves records and passes the rest through
    full = quantize.dequantize_params(qtree)
    assert full["bias"] is qtree["bias"]
    assert full["w"].shape == (64, 64)


# ---------------------------------------------------------------------------
# round-trip error bounds per dtype
# ---------------------------------------------------------------------------

def test_int8_roundtrip_error_bound():
    rs = np.random.RandomState(7)
    # per-channel magnitudes spanning 4 orders so a per-tensor scale
    # would blow the bound on the small rows
    x = (rs.randn(32, 48).astype(np.float32)
         * np.logspace(-2, 2, 32).astype(np.float32)[:, None])
    q, scale = quantize.quantize_array(x, "int8")
    assert q.dtype == np.int8
    assert scale.shape == (32, 1)
    dq = quantize.dequantize_array(q, scale)
    # symmetric rounding: at most half a quantization step per channel
    err = np.abs(x - dq)
    assert np.all(err <= 0.5 * scale + 1e-7), float(np.max(err / scale))


def test_fp8_roundtrip_error_bound():
    rs = np.random.RandomState(8)
    x = (rs.randn(32, 48).astype(np.float32)
         * np.logspace(-2, 2, 32).astype(np.float32)[:, None])
    q, scale = quantize.quantize_array(x, "fp8")
    assert q.dtype == quantize.quant_dtype("fp8")
    dq = quantize.dequantize_array(q, scale)
    # e4m3: 3 mantissa bits -> half-ulp relative error 2^-4 for normal
    # values, plus the subnormal floor (min subnormal 2^-9) times scale
    err = np.abs(x - dq)
    assert np.all(err <= np.abs(x) * 2.0 ** -4 + scale * 2.0 ** -9)


def test_zero_channel_is_safe():
    x = np.zeros((32, 64), np.float32)
    x[1] = np.linspace(-3, 3, 64)
    q, scale = quantize.quantize_array(x, "int8")
    assert float(scale[0, 0]) == 1.0  # all-zero channel: unit scale
    dq = quantize.dequantize_array(q, scale)
    np.testing.assert_array_equal(dq[0], np.zeros(64, np.float32))
    assert np.isfinite(dq).all()


def test_vector_uses_per_tensor_scale():
    x = np.linspace(-2, 2, 512).astype(np.float32)
    q, scale = quantize.quantize_array(x, "int8")
    assert np.ndim(scale) == 0
    err = np.abs(x - quantize.dequantize_array(q, scale))
    assert np.all(err <= 0.5 * float(scale) + 1e-7)


# ---------------------------------------------------------------------------
# cross-process bit-stability (the determinism contract)
# ---------------------------------------------------------------------------

_STABILITY_SNIPPET = """
import hashlib, sys

import numpy as np

from mxnet_tpu import quantize

x = (np.random.RandomState(123).randn(48, 96).astype(np.float32)
     * np.logspace(-3, 3, 48).astype(np.float32)[:, None])
q, s = quantize.quantize_array(x, sys.argv[1])
h = hashlib.sha256()
h.update(np.asarray(q).tobytes())
h.update(np.asarray(s, np.float32).tobytes())
print(h.hexdigest())
"""


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_codes_bit_stable_across_processes(mode):
    """quantize_array is numpy float32 arithmetic — a fresh process
    must produce byte-identical codes AND scales (what makes quantized
    checkpoint tiles and the serving oracle deterministic)."""
    x = (np.random.RandomState(123).randn(48, 96).astype(np.float32)
         * np.logspace(-3, 3, 48).astype(np.float32)[:, None])
    q, s = quantize.quantize_array(x, mode)
    h = hashlib.sha256()
    h.update(np.asarray(q).tobytes())
    h.update(np.asarray(s, np.float32).tobytes())
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    proc = subprocess.run(
        [sys.executable, "-c", _STABILITY_SNIPPET, mode], env=env,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == h.hexdigest()


# ---------------------------------------------------------------------------
# ZeRO-3 flat-tile interchange
# ---------------------------------------------------------------------------

def _eligible_names(params, lay):
    return [n for n, e in lay.items()
            if e.sharded and quantize.eligible(e.shape, e.dtype)]


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_flat_tile_codes_topology_independent(params, mode):
    """The tile quantizer is a pure function of the CANONICAL shape:
    an 8-way and a 4-way layout produce identical codes at the logical
    positions and identical scales — and both match the canonical
    quantizer — so quantization commutes with the ZeRO tiling."""
    import jax.numpy as jnp

    lay8 = zero.layout(params, 8, min_bytes=0)
    lay4 = zero.layout(params, 4, min_bytes=0)
    names = _eligible_names(params, lay8)
    assert names, "model has no quantizable weights"
    for name in names:
        w = np.asarray(params[name])
        e8, e4 = lay8[name], lay4[name]
        q8, s8 = quantize.quantize_flat_leaf(
            zero.flat_pad(jnp.asarray(w), e8), e8, mode)
        q4, s4 = quantize.quantize_flat_leaf(
            zero.flat_pad(jnp.asarray(w), e4), e4, mode)
        np.testing.assert_array_equal(np.asarray(s8), np.asarray(s4),
                                      err_msg=name)
        np.testing.assert_array_equal(
            np.asarray(q8)[:e8.logical], np.asarray(q4)[:e4.logical],
            err_msg=name)
        # canonical (numpy) quantizer agreement: scales always; codes
        # for int8 only — jnp.round and np.rint are both
        # round-half-to-even over identical f32 quotients, but XLA's
        # f32->e4m3 convert can round one ulp away from ml_dtypes' on
        # ties, so fp8 code equality holds within each implementation
        # (the topology check above), not across them
        qc, sc = quantize.quantize_array(w, mode)
        np.testing.assert_array_equal(np.asarray(s8),
                                      sc.reshape(-1), err_msg=name)
        if mode == "int8":
            np.testing.assert_array_equal(np.asarray(q8)[:e8.logical],
                                          qc.reshape(-1), err_msg=name)


def test_gather_bucket_dequantizes_after_collective(params):
    """A jitted gather of quantized 1/N tiles over an 8-device mesh
    returns full-precision params bit-identical to the host oracle
    (codes -> fp32 expansion), and the byte accounting reflects the
    1-byte collective payload."""
    import jax
    import jax.numpy as jnp

    mesh = create_mesh({"data": 8}, devices=_devices(8))
    lay = zero.layout(params, 8, min_bytes=0)
    names = _eligible_names(params, lay)[:3]
    entries = [lay[n] for n in names]
    tiles, scales = [], []
    for n, e in zip(names, entries):
        q, s = quantize.quantize_flat_leaf(
            zero.flat_pad(jnp.asarray(np.asarray(params[n])), e), e,
            "int8")
        tiles.append(zero.put(q, zero._axis_sharding(mesh, "data")))
        scales.append(s)

    def gather(flats):
        return zero.gather_bucket(flats, entries, mesh, "data",
                                  scales=scales)

    fulls = jax.jit(gather)(tuple(tiles))
    for n, full in zip(names, fulls):
        qc, sc = quantize.quantize_array(np.asarray(params[n]), "int8")
        np.testing.assert_array_equal(
            np.asarray(full), quantize.dequantize_array(qc, sc),
            err_msg=n)
    # gathers move 1-byte codes: ~4x fewer bytes than the fp32 path
    full_bytes = zero.zero3_gather_bytes(lay)
    quant_bytes = zero.zero3_gather_bytes(lay, "int8")
    assert full_bytes / quant_bytes >= 3.5


def test_quantized_tile_save_restores_on_any_topology(params, tmp_path):
    """Elastic-restore matrix row for quantized checkpoints: an 8-way
    quantized tile save and a 4-way quantized tile save both restore —
    unsharded — to the SAME full-precision values (the host dequant
    oracle), and an unquantized save still restores the original
    weights bit-exactly."""
    import jax.numpy as jnp

    from mxnet_tpu import checkpoint as ckpt

    host = {n: np.asarray(v) for n, v in params.items()}

    def save_tiles(ndev, directory, mode):
        mesh = create_mesh({"data": ndev}, devices=_devices(ndev))
        lay = zero.layout(host, ndev, min_bytes=0)
        packed = zero.pack_params(
            {n: jnp.asarray(v) for n, v in host.items()}, lay, mesh,
            "data")
        desc = zero.export_params(packed, lay)
        if mode:
            desc = quantize.quantize_export(desc, mode)
        mgr = ckpt.CheckpointManager(str(directory), prefix="q")
        mgr.save(epoch=1, arg_params={}, zero_params=desc)

    def restore(directory):
        state = ckpt.CheckpointManager(str(directory), prefix="q").load()
        return {n: _unwrap(v) for n, v in state.arg_params.items()}

    oracle = {}
    for n, w in host.items():
        if quantize.eligible(w.shape, w.dtype):
            q, s = quantize.quantize_array(w, "int8")
            oracle[n] = quantize.dequantize_array(q, s)
        else:
            oracle[n] = w

    for ndev in (8, 4):
        d = tmp_path / ("w%d" % ndev)
        save_tiles(ndev, d, "int8")
        restored = restore(d)
        assert set(restored) == set(host)
        for n in host:
            assert restored[n].dtype == np.float32
            np.testing.assert_array_equal(restored[n], oracle[n],
                                          err_msg="%dway:%s"
                                          % (ndev, n))

    d = tmp_path / "raw8"
    save_tiles(8, d, "")
    restored = restore(d)
    for n in host:
        np.testing.assert_array_equal(restored[n], host[n], err_msg=n)


# ---------------------------------------------------------------------------
# quantized serving sessions
# ---------------------------------------------------------------------------

def _probe(seed):
    return [np.random.RandomState(seed).randint(
        1, CFG.vocab_size, size=6).tolist()]


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_quantized_session_bitexact_per_precision(params, mode,
                                                  monkeypatch):
    """The serving oracle survives quantization: paged decode over the
    quantized tree matches the jitted full-context reference over the
    SAME quantized tree as closely as two executables can (sound: at
    most 4 spacings over 12 seeds, jax 0.9.0), the executable count
    stays frozen under MXNET_RECOMPILE_ERROR=1, and the guard prefix
    carries the quant tag so precisions never alias."""
    monkeypatch.setenv("MXNET_RECOMPILE_ERROR", "1")
    sess = serve.InferenceSession(params, num_heads=CFG.num_heads,
                                  config=_sconf(quant=mode))
    assert sorted(sess.executables) == ["decode", "prefill_16",
                                        "prefill_8"]
    assert "-q%s" % mode in sess._guard_prefix
    assert quantize.is_quantized(sess.params["blk0_ffn1_weight"])

    assert worst_gap_vs_reference(sess, _probe(5), steps=5,
                                  max_new=6) <= LIMIT_SPACINGS
    assert len(sess.executables) == len(sess.config.buckets) + 1

    # at-rest accounting: the quantized tree really is ~4x smaller on
    # its eligible weights.  This tiny test model (d32, V61) carries
    # proportionally more unquantized bias/LayerNorm bytes, so the
    # whole-tree bar is 3.0 here; the >=3.5 acceptance bar is asserted
    # in bench_serve.py on the bench model (measured 3.67x)
    shrink = (quantize.at_rest_bytes(
        quantize.dequantize_params(sess.params))
        / sess.params_bytes_at_rest())
    assert shrink >= 3.0


def test_quant_config_from_env(monkeypatch):
    monkeypatch.setenv("MXNET_SERVE_QUANT", "i8")
    assert serve.ServeConfig.from_env().quant == "int8"
    monkeypatch.setenv("MXNET_SERVE_QUANT", "off")
    assert serve.ServeConfig.from_env().quant == ""
    with pytest.raises(MXNetError):
        serve.ServeConfig(quant="int4")


def test_spec_decoding_composes_with_quant(params):
    """Speculation over a quantized target still cannot change any
    stream: quant+spec emits tokens identical to quant-only decode
    (the verify/decode bit-exactness holds per precision)."""
    rs = np.random.RandomState(14)
    reqs = lambda: [serve.Request(  # noqa: E731
        rid=i, prompt=rs.randint(1, CFG.vocab_size, size=4 + i).tolist(),
        max_new=8, arrival_s=0.0, eos_id=-1) for i in range(3)]
    rs = np.random.RandomState(14)
    plain = serve.InferenceSession(params, num_heads=CFG.num_heads,
                                   config=_sconf(quant="int8"))
    plain_out = {r.rid: list(r.tokens) for r in
                 serve.Scheduler(plain, policy="continuous")
                 .run(reqs())[0]}
    rs = np.random.RandomState(14)
    spec = serve.InferenceSession(
        params, num_heads=CFG.num_heads,
        config=_sconf(quant="int8", spec_k=3,
                      draft="layers:%d" % CFG.num_layers))
    spec_out = {r.rid: list(r.tokens) for r in
                serve.Scheduler(spec, policy="continuous")
                .run(reqs())[0]}
    assert spec_out == plain_out
    rep = spec.spec_report()
    assert rep["acceptance_rate"] == 1.0  # identity draft: all accepted


# ---------------------------------------------------------------------------
# fp8 training helpers: mode parsing, layer gating, delayed scaling
# ---------------------------------------------------------------------------

def test_fp8_mode_parsing_and_enabled(monkeypatch):
    for raw, want in (("", "off"), ("off", "off"), ("0", "off"),
                      ("no", "off"), ("on", "on"), ("1", "on"),
                      ("TRUE", "on"), ("auto", "auto")):
        monkeypatch.setenv("MXNET_FP8", raw)
        assert quantize.fp8_mode() == want
    monkeypatch.delenv("MXNET_FP8")
    assert quantize.fp8_mode() == "off" and not quantize.fp8_enabled()
    monkeypatch.setenv("MXNET_FP8", "on")
    assert quantize.fp8_enabled()
    monkeypatch.setenv("MXNET_FP8", "e4m3")
    with pytest.raises(MXNetError):
        quantize.fp8_mode()


def test_fp8_layer_allowed(monkeypatch):
    monkeypatch.delenv("MXNET_FP8_LAYERS", raising=False)
    assert quantize.fp8_layer_allowed("blk0_attn")
    assert quantize.fp8_layer_allowed(None)  # unnamed site, no spec
    monkeypatch.setenv("MXNET_FP8_LAYERS", "blk, lm_head")
    assert quantize.fp8_layer_allowed("blk1_ffn2")  # prefix match
    assert quantize.fp8_layer_allowed("lm_head")    # exact match
    assert not quantize.fp8_layer_allowed("embed")
    assert not quantize.fp8_layer_allowed(None)  # unnamed, spec set


def test_fp8_delayed_scaling_history():
    hist = quantize.fp8_hist_init(2)
    assert hist.shape == (2, 2, quantize.FP8_AMAX_HISTORY)
    # empty history realizes unit scales: the safe first-step default
    np.testing.assert_array_equal(
        np.asarray(quantize.fp8_realize_scales(hist)),
        np.ones((2, 2), np.float32))
    new = np.array([[quantize.FP8_MAX, 2 * quantize.FP8_MAX],
                    [7.0, 0.0]], np.float32)
    hist = quantize.fp8_update_hist(hist, new)
    s = np.asarray(quantize.fp8_realize_scales(hist))
    assert s[0, 0] == pytest.approx(1.0)  # amax == FP8_MAX: unit scale
    assert s[0, 1] == pytest.approx(2.0)  # 2x over range: scale doubles
    assert s[1, 0] == pytest.approx(7.0 / quantize.FP8_MAX)
    assert s[1, 1] == 1.0                 # operand never saw data
    # the window really is a window: the spike falls out after HISTORY
    for _ in range(quantize.FP8_AMAX_HISTORY):
        hist = quantize.fp8_update_hist(hist,
                                        np.zeros((2, 2), np.float32))
    np.testing.assert_array_equal(
        np.asarray(quantize.fp8_realize_scales(hist)),
        np.ones((2, 2), np.float32))


def test_fp8_apply_dot_trace_contract():
    import jax.numpy as jnp

    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(4, 8), jnp.float32)
    w = jnp.asarray(rs.randn(8, 5), jnp.float32)
    # outside a trace the route declines and callers keep their path
    assert not quantize.fp8_tracing()
    assert quantize.fp8_apply_dot(x, w, label="fc") is None
    with quantize.fp8_trace() as tr:
        assert quantize.fp8_tracing()
        out = quantize.fp8_apply_dot(x, w, label="fc", w_dim=0)
        assert out is not None and out.shape == (4, 5)
        # shape-ineligible operands decline inside the trace too
        assert quantize.fp8_apply_dot(
            x, jnp.zeros((3, 3), jnp.float32), w_dim=0) is None
        assert tr.names == ["fc"] and len(tr.amax) == 1
        assert tr.amax[0].shape == (2,)
    assert not quantize.fp8_tracing()
    # discovery scales are 1.0: output == the e4m3 fake-cast matmul
    e4m3 = quantize.quant_dtype("fp8")
    want = (np.asarray(x.astype(e4m3).astype(jnp.float32))
            @ np.asarray(w.astype(e4m3).astype(jnp.float32)))
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6,
                               atol=1e-6)


def test_fp8_apply_dot_respects_layer_optout(monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setenv("MXNET_FP8_LAYERS", "fc1")
    x = jnp.ones((2, 4), jnp.float32)
    w = jnp.ones((4, 3), jnp.float32)
    with quantize.fp8_trace() as tr:
        assert quantize.fp8_apply_dot(x, w, label="fc2",
                                      w_dim=0) is None
        assert quantize.fp8_apply_dot(x, w, label="fc1",
                                      w_dim=0) is not None
    assert tr.names == ["fc1"]  # opted-out sites never claim a slot


def test_fp8_dot_grads_flow_scales_inert():
    import jax
    import jax.numpy as jnp

    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(4, 8), jnp.float32)
    w = jnp.asarray(rs.randn(8, 5), jnp.float32)

    def loss(x, w):
        with quantize.fp8_trace():
            return jnp.sum(quantize.fp8_apply_dot(x, w, label="fc",
                                                   w_dim=0) ** 2)

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    assert gx.shape == x.shape and gw.shape == w.shape
    assert np.isfinite(np.asarray(gx)).all()
    assert np.isfinite(np.asarray(gw)).all()
    # close to the full-precision analytic grads (e4m3 operands, e5m2
    # cotangent: a few mantissa bits of rounding, nothing structural)
    ref_gx, ref_gw = jax.grad(lambda x, w: jnp.sum((x @ w) ** 2),
                              argnums=(0, 1))(x, w)
    for got, ref in ((gx, ref_gx), (gw, ref_gw)):
        err = np.max(np.abs(np.asarray(got) - np.asarray(ref)))
        assert err <= 0.35 * float(np.max(np.abs(np.asarray(ref))))


# ---------------------------------------------------------------------------
# fp8 training through TrainStep: history rides hstate like the scaler
# ---------------------------------------------------------------------------

def _fp8_train_step(**kw):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.fused import TrainStep

    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=5, name="fc2")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    kw.setdefault("optimizer_params", {"learning_rate": 0.1})
    step = TrainStep(sym, optimizer="sgd", **kw)
    params, aux, states = step.init_state(
        {"data": (16, 8), "softmax_label": (16,)})
    rng = jax.random.PRNGKey(0)
    X = np.asarray(jax.random.normal(rng, (16, 8), "float32"))
    batch = {"data": X,
             "softmax_label": np.tile(np.arange(5.0, dtype="float32"),
                                      4)[:16]}
    return step, params, aux, states, batch, rng


def _run_params(step, params, aux, states, batch, rng, n=5):
    import jax

    for _ in range(n):
        params, aux, states, _ = step(params, aux, states, batch, rng)
    return jax.tree.map(lambda v: np.asarray(jax.device_get(v)), params)


def test_fp8_off_keeps_legacy_hstate_free_path(monkeypatch):
    """MXNET_FP8=off is the clean path: no carried hstate (the jit
    signature an fp8-free build compiles), and the trajectory is
    deterministic."""
    monkeypatch.setenv("MXNET_FP8", "off")
    step, params, aux, states, batch, rng = _fp8_train_step()
    assert not step._fp8 and not step._use_hstate
    ref = _run_params(step, params, aux, states, batch, rng)
    assert step._hstate is None  # nothing carried
    step2, params2, aux2, states2, batch2, rng2 = _fp8_train_step()
    again = _run_params(step2, params2, aux2, states2, batch2, rng2)
    for k in ref:
        np.testing.assert_array_equal(ref[k], again[k], err_msg=k)


def _np_fp8_mlp(p0, x, y, n, lr=0.1):
    """The delayed-scaling fp8 recipe of ``_fp8_train_step``'s net in
    plain numpy, independent of the program: e4m3 fake-casts of both
    operands at max(history) / FP8_MAX (1.0 while the history is
    empty), e5m2 fake-cast of the cotangent at its own amax, SGD."""
    import ml_dtypes

    def fake(v, scale, qmax, dtype):
        scale = np.float32(scale)
        return (np.clip(v / scale, -qmax, qmax).astype(dtype)
                .astype(np.float32) * scale)

    def fwd(v, w, scales):
        vq = fake(v, scales[0], quantize.FP8_MAX, ml_dtypes.float8_e4m3fn)
        wq = fake(w, scales[1], quantize.FP8_MAX, ml_dtypes.float8_e4m3fn)
        return vq @ wq.T, (vq, wq)

    def bwd(saved, g):
        vq, wq = saved
        amax = np.abs(g).max()
        g = fake(g, amax / quantize.FP8_E5M2_MAX if amax > 0 else 1.0,
                 quantize.FP8_E5M2_MAX, ml_dtypes.float8_e5m2)
        return g @ wq, g.T @ vq

    p = {k: v.copy() for k, v in p0.items()}
    hist = np.zeros((2, 2, quantize.FP8_AMAX_HISTORY), np.float32)
    for _ in range(n):
        hmax = hist.max(-1)
        scales = np.where(hmax > 0, hmax / quantize.FP8_MAX, 1.0)
        h, saved1 = fwd(x, p["fc1_weight"], scales[0])
        h = h + p["fc1_bias"]
        act = np.maximum(h, 0)
        z, saved2 = fwd(act, p["fc2_weight"], scales[1])
        z = z + p["fc2_bias"]
        e = np.exp(z - z.max(1, keepdims=True))
        g = e / e.sum(1, keepdims=True)
        g[np.arange(len(y)), y.astype(int)] -= 1
        amax = [[np.abs(x).max(), np.abs(p["fc1_weight"]).max()],
                [np.abs(act).max(), np.abs(p["fc2_weight"]).max()]]
        dact, dw2 = bwd(saved2, g)
        dh = dact * (h > 0)
        _, dw1 = bwd(saved1, dh)
        for k, d in (("fc1_weight", dw1), ("fc1_bias", dh.sum(0)),
                     ("fc2_weight", dw2), ("fc2_bias", g.sum(0))):
            p[k] = (p[k] - lr * d).astype(np.float32)
        hist = np.concatenate(
            [np.asarray(amax, np.float32)[..., None], hist[..., :-1]], -1)
    return p


def test_fp8_on_trains_and_rolls_amax_history(monkeypatch):
    """MXNET_FP8=on: both FC matmuls claim fp8 sites, the (sites, 2,
    HISTORY) amax history advances every step, and the trajectory is
    the fp8 recipe's own: it equals an independent numpy emulation of
    the recipe (``_np_fp8_mlp``) to float32 rounding, and lands as near
    the full-precision trajectory as that recipe does."""
    monkeypatch.setenv("MXNET_FP8", "off")
    step, params, aux, states, batch, rng = _fp8_train_step()
    ref = _run_params(step, params, aux, states, batch, rng)

    monkeypatch.setenv("MXNET_FP8", "on")
    fstep, params, aux, states, batch, rng = _fp8_train_step()
    assert fstep._fp8 and fstep._use_hstate
    p0 = {k: np.asarray(v).copy() for k, v in params.items()}  # donated
    got = _run_params(fstep, params, aux, states, batch, rng)
    assert fstep._fp8_sites == 2  # fc1 + fc2
    hist = np.asarray(fstep._hstate["fp8_hist"])
    assert hist.shape == (2, 2, quantize.FP8_AMAX_HISTORY)
    assert (hist[:, :, :5] > 0).all()  # 5 steps: 5 fresh amax columns
    assert (hist[:, :, 5:] == 0).all()  # older slots still virgin
    want = _np_fp8_mlp(p0, batch["data"], batch["softmax_label"], n=5)
    for k in ref:
        assert np.isfinite(got[k]).all(), k
        # reading: 1.2e-7 here, at most 3.6e-7 over six seeds
        np.testing.assert_allclose(got[k], want[k], rtol=0, atol=1e-5,
                                   err_msg=k)
        # The recipe's own distance from float32 after five steps of
        # lr 0.1 that move the weights by 0.79: fc1_weight 0.184 here
        # (0.077-0.415 over six seeds, the emulation reading the same
        # to 1e-6), so 0.184 is the arithmetic's level under jax 0.9.0's
        # random stream, not a fault of the fp8 path.  Limit: 3x.
        drift = np.max(np.abs(got[k] - ref[k]))
        assert drift <= 0.6, (k, drift)
    assert not np.array_equal(got["fc1_weight"], p0["fc1_weight"])


def test_fp8_layers_filters_sites(monkeypatch):
    monkeypatch.setenv("MXNET_FP8", "on")
    monkeypatch.setenv("MXNET_FP8_LAYERS", "fc1")
    step, params, aux, states, batch, rng = _fp8_train_step()
    got = _run_params(step, params, aux, states, batch, rng, n=2)
    assert step._fp8_sites == 1  # fc2 opted out, never claims a slot
    assert np.asarray(step._hstate["fp8_hist"]).shape == \
        (1, 2, quantize.FP8_AMAX_HISTORY)
    for k, v in got.items():
        assert np.isfinite(v).all(), k


def test_fp8_composes_with_scaler_and_scan(monkeypatch):
    """fp8 history and the dynamic loss scaler share the one carried
    hstate, and both survive the steps_per_call=K lax.scan: one call
    advances the history K slots and the scale still grows."""
    from mxnet_tpu.health import DynamicLossScaler, StepHealth

    monkeypatch.setenv("MXNET_FP8", "on")
    scaler = DynamicLossScaler(init_scale=8.0, growth=2.0,
                               growth_interval=3, max_scale=64.0)
    step, params, aux, states, batch, rng = _fp8_train_step(
        health=StepHealth(scaler=scaler), steps_per_call=3)
    kbatch = {k: np.stack([v] * 3) for k, v in batch.items()}
    params, aux, states, _ = step(params, aux, states, kbatch, rng)
    assert sorted(step._hstate) == ["fp8_hist", "good_steps",
                                    "loss_scale"]
    hist = np.asarray(step._hstate["fp8_hist"])
    assert (hist[:, :, :3] > 0).all()  # K=3 inner steps, 3 slots
    assert (hist[:, :, 3:] == 0).all()
    assert step.loss_scale == 16.0  # 3 clean steps == one growth
    for v in np.asarray(hist).ravel():
        assert np.isfinite(v)


# ---------------------------------------------------------------------------
# quantized KV-cache pages: per-row codecs + serving composition
# ---------------------------------------------------------------------------

def test_kv_quantize_rows_roundtrip():
    import jax.numpy as jnp

    rs = np.random.RandomState(9)
    x = (rs.randn(5, 2, 4).astype(np.float32)
         * np.logspace(-2, 2, 5).astype(np.float32)[:, None, None])
    x[0] = 0.0  # all-zero row: unit scale, exact zeros back
    q, scale = quantize.kv_quantize_rows(jnp.asarray(x), "int8")
    scale = np.asarray(scale)
    assert q.dtype == jnp.int8 and scale.shape == (5,)
    assert scale[0] == 1.0
    dq = np.asarray(quantize.kv_dequantize(q, jnp.asarray(scale)))
    np.testing.assert_array_equal(dq[0], np.zeros((2, 4), np.float32))
    # symmetric rounding: at most half a step per row
    assert np.all(np.abs(x - dq) <= 0.5 * scale[:, None, None] + 1e-7)

    qf, sf = quantize.kv_quantize_rows(jnp.asarray(x), "fp8")
    assert qf.dtype == quantize.quant_dtype("fp8")
    dqf = np.asarray(quantize.kv_dequantize(qf, sf))
    sf = np.asarray(sf)
    assert np.all(np.abs(x - dqf) <= np.abs(x) * 2.0 ** -4
                  + sf[:, None, None] * 2.0 ** -9)
    with pytest.raises(MXNetError):
        quantize.kv_quantize_rows(jnp.asarray(x), "")


def test_kv_quant_page_bytes_capacity_multiplier():
    from mxnet_tpu.serve.kv_cache import PagedKVCache

    f32 = PagedKVCache.page_bytes(CFG.num_layers, CFG.num_heads,
                                  CFG.d_model // CFG.num_heads, PAGE)
    for mode in ("int8", "fp8"):
        q = PagedKVCache.page_bytes(CFG.num_layers, CFG.num_heads,
                                    CFG.d_model // CFG.num_heads, PAGE,
                                    kv_quant=mode)
        # 1-byte codes + f32 per-row scales: >3x more tokens per byte
        assert f32 / q >= 3.0


def test_kv_quant_config_from_env(monkeypatch):
    monkeypatch.setenv("MXNET_SERVE_KV_QUANT", "e4m3")
    assert serve.ServeConfig.from_env().kv_quant == "fp8"
    monkeypatch.delenv("MXNET_SERVE_KV_QUANT")
    assert serve.ServeConfig.from_env().kv_quant == ""
    with pytest.raises(MXNetError):
        serve.ServeConfig(kv_quant="int4")


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_kv_quant_session_bitexact_per_precision(params, mode,
                                                 monkeypatch):
    """Quantized KV pages keep the serving oracle: paged decode over
    int8/e4m3 pages matches the jitted full-context reference running
    the SAME per-row fake quantization as closely as two executables
    can (sound: at most 4 spacings over 12 seeds), the executable count
    stays frozen under MXNET_RECOMPILE_ERROR=1, and the guard prefix
    carries the kv tag so precisions never alias an f32 session's
    executables."""
    monkeypatch.setenv("MXNET_RECOMPILE_ERROR", "1")
    sess = serve.InferenceSession(params, num_heads=CFG.num_heads,
                                  config=_sconf(kv_quant=mode))
    assert "-kv%s" % mode in sess._guard_prefix
    assert worst_gap_vs_reference(sess, _probe(6), steps=5,
                                  max_new=6) <= LIMIT_SPACINGS
    assert len(sess.executables) == len(sess.config.buckets) + 1


def _wrong_channel_scale(sess, slots):
    """Weight quantization's smallest fault: two output channels of one
    weight swap their scales."""
    rec = sess.params["blk0_ffn1_weight"]
    scale = np.array(rec["s"])
    scale[[0, 1]] = scale[[1, 0]]
    sess.params = dict(sess.params,
                       blk0_ffn1_weight={"q": rec["q"], "s": scale})


def _wrong_row_scale(sess, slots):
    """KV quantization's smallest fault: two token rows of one page swap
    their key scales."""
    import jax.numpy as jnp

    cache = sess.cache
    page = int(cache._tables[slots[0], 0])
    scale = np.array(cache.pools["k_scale"])
    scale[:, page, [0, 1]] = scale[:, page, [1, 0]]
    cache.pools["k_scale"] = jnp.asarray(scale)


@pytest.mark.parametrize("kw,plant", [
    (dict(quant="int8"), _wrong_channel_scale),
    (dict(kv_quant="int8"), _wrong_row_scale),
    (dict(kv_quant="fp8"), _wrong_row_scale)],
    ids=["quant-int8", "kv-int8", "kv-fp8"])
def test_quantized_session_comparison_sees_planted_fault(params, kw,
                                                         plant):
    """The control of the two comparisons above, where the limit is 32:
    swapped channel scales read 88 773 spacings (74 862-273 549 over 5
    seeds, int8 and fp8), a KV scale of the wrong row 6 374 (int8) and
    13 651 (fp8; 1 501-13 651 over 5 seeds).  The reference keeps the
    sound weights."""
    sess = serve.InferenceSession(params, num_heads=CFG.num_heads,
                                  config=_sconf(**kw))
    assert worst_gap_vs_reference(
        sess, _probe(6), steps=5, max_new=6, plant=plant,
        ref_params=sess.params) > 30 * LIMIT_SPACINGS


def test_spec_decoding_composes_with_kv_quant(params):
    """Speculation over quantized KV pages cannot change any stream:
    the verify step reads the same codes the serial decode writes, so
    kv_quant+spec emits tokens identical to kv_quant-only decode."""
    def reqs():
        rs = np.random.RandomState(15)
        return [serve.Request(
            rid=i, prompt=rs.randint(1, CFG.vocab_size,
                                     size=4 + i).tolist(),
            max_new=8, arrival_s=0.0, eos_id=-1) for i in range(3)]

    plain = serve.InferenceSession(params, num_heads=CFG.num_heads,
                                   config=_sconf(kv_quant="int8"))
    plain_out = {r.rid: list(r.tokens) for r in
                 serve.Scheduler(plain, policy="continuous")
                 .run(reqs())[0]}
    spec = serve.InferenceSession(
        params, num_heads=CFG.num_heads,
        config=_sconf(kv_quant="int8", spec_k=3,
                      draft="layers:%d" % CFG.num_layers))
    spec_out = {r.rid: list(r.tokens) for r in
                serve.Scheduler(spec, policy="continuous")
                .run(reqs())[0]}
    assert spec_out == plain_out
    assert spec.spec_report()["acceptance_rate"] == 1.0


def test_prefix_hit_bitexact_on_quantized_pages(params):
    """A prefix hit that maps an already-quantized page prefills only
    the suffix, and both streams stay on the per-precision reference —
    the mapped codes and scale rows ARE the cold-miss ones."""
    sess = serve.InferenceSession(
        params, num_heads=CFG.num_heads,
        config=_sconf(kv_quant="int8", prefix_pages=-1))
    shared = [5, 9, 2, 11, 3, 7, 8, 4]  # one full page
    p_cold = shared + [1, 6]
    p_hit = shared + [2, 9, 14]

    def mapped(sess, slots):
        assert sess.cache.cached_len(slots[0]) == 0
        assert sess.cache.cached_len(slots[1]) == PAGE  # not recomputed

    assert worst_gap_vs_reference(sess, [p_cold, p_hit], steps=3,
                                  max_new=4, plant=mapped) <= LIMIT_SPACINGS
