"""Serving runtime: bucketed AOT executables, paged KV cache,
continuous-batching scheduler, paged decode against the full-context
reference (tests/closeness.py says how close), and the Predictor
recompile guardrails (mxnet_tpu/serve/, docs/serving.md)."""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serve
from mxnet_tpu.base import MXNetError, RecompileStorm
from mxnet_tpu.serve import model as serve_model
from mxnet_tpu.serve.kv_cache import PagedKVCache
from mxnet_tpu.testing import faults

from closeness import LIMIT_SPACINGS, assert_close_across_executables
from serve_util import lend, worst_gap_vs_reference

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = serve.ModelConfig(vocab_size=61, num_layers=2, d_model=32,
                        num_heads=2, max_len=64)
PAGE = 8


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("MXNET_FAULT_INJECT", raising=False)
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def params():
    return serve_model.init_params(CFG, seed=3)


@pytest.fixture(scope="module")
def _session(params):
    sconf = serve.ServeConfig(slots=3, page_size=PAGE, buckets=(8, 16),
                              max_new=8, exact=True)
    return serve.InferenceSession(params, num_heads=CFG.num_heads,
                                  config=sconf)


@pytest.fixture
def session(_session):
    yield from lend(_session)


def _ref_row(sess, seq):
    return np.asarray(serve_model.reference_last_logits(
        sess.params, seq, CFG, PAGE, exact=True))


# ---------------------------------------------------------------------------
# paged KV cache bookkeeping
# ---------------------------------------------------------------------------

def test_kv_cache_alloc_release_exhaustion():
    cache = PagedKVCache(num_layers=1, num_heads=2, head_dim=4,
                         page_size=8, num_pages=4, slots=2,
                         max_pages_per_slot=2)
    assert cache.free_pages == 4 and cache.free_slots == 2
    assert cache.pages_needed(5, 8) == 2  # 13 tokens -> 2 pages
    s0 = cache.alloc(5, 8)
    s1 = cache.alloc(5, 8)
    assert s0 is not None and s1 is not None and s0 != s1
    assert cache.free_pages == 0
    assert cache.alloc(1, 1) is None  # pages exhausted
    assert cache.utilization() == 1.0
    cache.release(s0)
    assert cache.free_pages == 2
    s2 = cache.alloc(1, 1)  # backfills the freed slot, needs 1 page
    assert s2 is not None
    with pytest.raises(MXNetError):
        cache.release(99)  # never allocated
    with pytest.raises(MXNetError):
        cache.can_admit(100, 100)  # can never fit a slot
    # unreserved table entries point at the write-only trash page
    assert cache._tables[s2, -1] == cache.trash_page
    assert cache.pool_bytes() == 2 * cache.pools["k_pool"].nbytes


def test_serve_config_validation():
    with pytest.raises(MXNetError):
        serve.ServeConfig(buckets=(7,), page_size=8)  # not page multiple
    with pytest.raises(MXNetError):
        serve.ServeConfig(buckets=())
    cfg = serve.ServeConfig(slots=2, page_size=8, buckets=(16, 8),
                            max_new=8)
    assert cfg.buckets == (8, 16)  # sorted + deduped
    assert cfg.max_pages_per_slot == 3  # (16+8)/8
    assert cfg.pool_pages == 6


# ---------------------------------------------------------------------------
# paged decode against the full-context reference
# ---------------------------------------------------------------------------

def _decode_vs_reference(session, seed, plant=None):
    """Two prompts, one crossing into a second page; prefill, then 7
    decode steps that cross a page boundary."""
    rs = np.random.RandomState(seed)
    prompts = [rs.randint(1, CFG.vocab_size, size=n).tolist()
               for n in (5, 13)]
    return worst_gap_vs_reference(session, prompts, steps=7, plant=plant)


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_paged_decode_bitexact_vs_reference(session, seed):
    """Prefill + N paged decode steps reproduce the full-context
    reference forward — logits, not just argmax tokens — including
    steps that cross a page boundary.  Decode, prefill and reference
    are three executables, so "reproduce" is tests/closeness.py's
    second contract: sound rows read at most 5 spacings apart (jax
    0.9.0, 12 seeds) where earlier jax versions read 0."""
    assert _decode_vs_reference(session, seed) <= LIMIT_SPACINGS


def _page_off(session, slots):
    cache = session.cache
    cache._tables[slots[1], 0] = cache._tables[slots[0], 0]
    cache._tables_dev = None


def _position_off(session, slots):
    session.cache.lengths[slots[1]] -= 1


@pytest.mark.parametrize("plant", [_page_off, _position_off])
def test_paged_decode_comparison_sees_planted_fault(session, plant):
    """The control of the comparison above: the smallest realistic
    faults — one page-table entry pointing a page off, one slot's
    position off by one — read over a million spacings (1.3e6-1.4e7,
    5 seeds) where the limit is 32."""
    assert _decode_vs_reference(session, 11, plant=plant) > 1e4


def test_cobatched_equals_solo_decode(session):
    """Continuous batching must not perturb numerics: a request decodes
    the same tokens whether it runs alone or co-batched with strangers
    (the M-invariant kernels make this exact, not approximate)."""
    rs = np.random.RandomState(12)
    p = rs.randint(1, CFG.vocab_size, size=6).tolist()

    def run(neighbors):
        slot = session.try_alloc(len(p), 6)
        first, _ = session.prefill(slot, p)
        others = []
        for q in neighbors:
            s = session.try_alloc(len(q), 6)
            session.prefill(s, q)
            others.append(s)
        out = [first]
        for _ in range(5):
            toks, _ = session.step()
            out.append(toks[slot])
        session.reset_cold()
        return out

    solo = run([])
    crowd = run([rs.randint(1, CFG.vocab_size, size=9).tolist(),
                 rs.randint(1, CFG.vocab_size, size=14).tolist()])
    assert solo == crowd


def test_from_checkpoint_roundtrip(tmp_path, params):
    """v2 checkpoint save -> InferenceSession restore -> decode output
    against the reference forward on the same params (another
    executable: tests/closeness.py)."""
    from mxnet_tpu.checkpoint import CheckpointManager

    mgr = CheckpointManager(str(tmp_path), prefix="lm",
                            save_optimizer_states=False)
    mgr.save(epoch=1, arg_params=params)
    sconf = serve.ServeConfig(slots=2, page_size=PAGE, buckets=(8,),
                              max_new=4, exact=True)
    sess = serve.InferenceSession.from_checkpoint(
        str(tmp_path), prefix="lm", epoch=1, num_heads=CFG.num_heads,
        config=sconf)
    p = list(range(1, 8))
    slot = sess.try_alloc(len(p), 4)
    first, last_logits = sess.prefill(slot, p)
    last_logits = np.asarray(last_logits)
    assert_close_across_executables(last_logits, _ref_row(sess, p))
    seq = list(p) + [first]
    for _ in range(3):
        toks, logits = sess.step()
        logits = np.asarray(logits)
        assert_close_across_executables(logits[slot], _ref_row(sess, seq))
        seq.append(toks[slot])


# ---------------------------------------------------------------------------
# compile-once: fixed executable set, no per-request recompiles
# ---------------------------------------------------------------------------

def test_no_recompiles_across_load(session, monkeypatch):
    """A full continuous-batching load under MXNET_RECOMPILE_ERROR=1:
    any per-request retrace would raise RecompileStorm.  The executable
    set stays at len(buckets) + 1 with one trace each."""
    monkeypatch.setenv("MXNET_RECOMPILE_ERROR", "1")
    rs = np.random.RandomState(13)
    reqs = [serve.Request(rid=i,
                          prompt=rs.randint(1, CFG.vocab_size,
                                            size=3 + 2 * i).tolist(),
                          max_new=5, arrival_s=0.002 * i)
            for i in range(6)]
    done, _ = serve.Scheduler(session, policy="continuous").run(reqs)
    assert all(r.done_s >= 0 and not r.failed for r in done)
    assert sorted(session.executables) == \
        ["decode", "prefill_16", "prefill_8"]
    for name, snap in session.guard_report().items():
        assert snap["traces"] == 1, (name, snap)
        assert snap["signatures"] == 1, (name, snap)
    assert session.fallback_count() == 0


def test_admission_limits(session):
    with pytest.raises(MXNetError):
        session.bucket_for(17)  # beyond largest bucket
    with pytest.raises(MXNetError):
        session.try_alloc(4, max_new=99)  # beyond session cap
    with pytest.raises(MXNetError):
        session.try_alloc(0)


def test_max_prompt_defaults_to_the_largest_bucket(session):
    """``ServeConfig.max_prompt`` unset is today's behaviour: the largest
    bucket bounds a fresh prompt, a slot's pages and the ``max_len``
    check; under the largest bucket it is refused."""
    conf = serve.ServeConfig(slots=3, page_size=PAGE, buckets=(8, 16),
                             max_new=8)
    assert conf.max_prompt == 0 and conf.longest_prompt == 16
    assert conf.max_pages_per_slot == 3
    assert session.config.longest_prompt == 16
    # unset it follows the buckets through ``dataclasses.replace`` (the
    # autotuner's and bench_serve.py's way to a new config)
    assert dataclasses.replace(conf, buckets=(8,)).longest_prompt == 8
    assert session.cache.max_pages_per_slot == 3
    with pytest.raises(MXNetError, match="longest admissible prompt 16"):
        session.try_alloc(17)
    assert serve.ServeConfig.from_env(buckets=(16, 32)).longest_prompt == 32
    with pytest.raises(MXNetError, match="under the largest bucket"):
        serve.ServeConfig(buckets=(16, 32), max_prompt=24)
    with pytest.raises(MXNetError, match="exceeds the model's max_len 64"):
        serve.InferenceSession(
            serve_model.init_params(CFG, seed=3), num_heads=CFG.num_heads,
            config=serve.ServeConfig(slots=2, page_size=PAGE,
                                     buckets=(8, 16), max_new=8,
                                     max_prompt=60))


@pytest.mark.parametrize("cls, field", [
    (serve.ServeConfig, "layers"), (serve.ServeConfig, "window"),
    (serve.ServeConfig, "ring_pages"), (serve.ModelConfig, "layer_kinds"),
    (serve.ModelConfig, "window")])
def test_no_second_way_to_say_what_a_layer_is(cls, field):
    """A layer's kind is the model's ``layer_types`` + ``sliding_window``
    and a ring's size the block's ``ring_pages``: the deployment's config
    has no word for either, and the model no second pair."""
    assert not hasattr(cls, field)
    base = CFG if cls is serve.ModelConfig else serve.ServeConfig()
    with pytest.raises(TypeError, match="unexpected keyword"):
        dataclasses.replace(base, **{field: 8})


def test_the_environment_cannot_rewrite_the_model(monkeypatch):
    """The two ``MXNET_SERVE_*`` names that used to state layer kinds and
    a window are read by nothing: the config is the one built without
    them (the names are spelt in two parts so that a search of the tree
    for either finds nothing)."""
    want = serve.ServeConfig.from_env(buckets=(16, 32))
    for knob, value in (("LAYERS", "full,window"), ("WINDOW", "8")):
        monkeypatch.setenv("MXNET_SERVE_" + knob, value)
    assert serve.ServeConfig.from_env(buckets=(16, 32)) == want


@pytest.mark.parametrize("over", [
    dict(), dict(layer_types=("full_attention", "sliding_attention"),
                 sliding_window=8)],
    ids=["full", "full,window"])
def test_a_fresh_long_prompt_is_the_same_prompt_resumed(params, over):
    """With ``max_prompt`` above the largest bucket a fresh prompt of 37
    tokens is admitted and goes in chunks of 16, 16 and 5 (bucket 8)
    through the same ``buckets + 1`` executables: bit for bit what a
    resumed transcript of those tokens gets, the reference's row, and a
    slot reserves pages for ``max_prompt + max_new``."""
    sess = serve.InferenceSession(
        params, model=dataclasses.replace(CFG, **over),
        config=serve.ServeConfig(
            slots=3, page_size=PAGE, buckets=(8, 16), max_new=8,
            max_prompt=40, exact=True))
    assert sorted(sess.executables) == ["decode", "prefill_16", "prefill_8"]
    assert sess.cache.max_pages_per_slot == 6
    seq = np.random.default_rng(5).integers(0, CFG.vocab_size, 37).tolist()
    fresh = sess.try_alloc(len(seq), 4, tokens=seq)
    first, logits = sess.prefill(fresh, seq)
    resumed = sess.try_alloc(len(seq), 4, tokens=seq, resume=True)
    again, logits2 = sess.prefill(resumed, seq)
    assert fresh != resumed and first == again
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(logits2))
    assert_close_across_executables(
        np.asarray(logits), np.asarray(serve_model.reference_last_logits(
            sess.params, seq, sess.model, PAGE, exact=True)))
    toks, _ = sess.step()
    assert toks[fresh] == toks[resumed]
    with pytest.raises(MXNetError, match="longest admissible prompt 40"):
        sess.try_alloc(41)
    assert sess.fallback_count() == 0


# ---------------------------------------------------------------------------
# scheduler policies
# ---------------------------------------------------------------------------

def _trace(n, seed=14, max_new=4):
    rs = np.random.RandomState(seed)
    return [serve.Request(rid=i,
                          prompt=rs.randint(1, CFG.vocab_size,
                                            size=4 + i).tolist(),
                          max_new=max_new, arrival_s=0.003 * i)
            for i in range(n)]


@pytest.mark.parametrize("policy", ["serial", "static", "continuous"])
def test_scheduler_policies_complete(session, policy):
    reqs = _trace(5)
    done, makespan = serve.Scheduler(session, policy=policy).run(reqs)
    summary = serve.summarize(done, makespan)
    assert summary["completed"] == 5 and summary["failed"] == 0
    for r in done:
        assert len(r.tokens) == r.max_new
        assert r.ttft_s >= 0 and r.done_s >= r.ttft_s
    assert summary["total_tokens"] == 5 * 4
    assert summary["tokens_per_sec"] > 0
    assert summary["ttft_p99_s"] >= summary["ttft_p50_s"]
    # identical arrivals + greedy decode: every policy emits the same
    # tokens per request (scheduling changes latency, never content)
    assert [r.tokens for r in done] == \
        [r.tokens for r in
         serve.Scheduler(session, policy="serial").run(_trace(5))[0]]


def test_scheduler_rejects_unknown_policy(session):
    with pytest.raises(MXNetError):
        serve.Scheduler(session, policy="bogus")


def test_scheduler_raises_when_outside_caller_holds_every_slot(session):
    """The hang this suite used to die of: every slot of the session is
    held by a caller the scheduler knows nothing about (a failed test,
    a dead client), nothing of the scheduler's own is running, so no
    tick can ever free room.  ``run`` must raise the typed error on its
    first tick, not spin on ``outstanding``."""
    held = [session.try_alloc(4, 4) for _ in range(session.config.slots)]
    assert None not in held and session.cache.free_slots == 0
    sched = serve.Scheduler(session, policy="continuous")
    sched.begin(_trace(2))
    with pytest.raises(MXNetError, match="0 of 3 slots free"):
        sched.tick()  # request 0 is due at once: one tick is enough
    # a session that is merely busy with the scheduler's OWN requests
    # queues the overflow and finishes it
    session.reset_cold()
    done, _ = serve.Scheduler(session, policy="continuous").run(_trace(5))
    assert all(not r.failed and len(r.tokens) == 4 for r in done)


def test_continuous_backfills_freed_slots(session):
    """More requests than slots: continuous admission must backfill as
    requests finish, not wait for the whole batch to drain."""
    reqs = _trace(7, seed=15, max_new=3)  # 7 requests, 3 slots
    done, _ = serve.Scheduler(session, policy="continuous").run(reqs)
    assert all(not r.failed and len(r.tokens) == 3 for r in done)
    assert session.active_slots() == []
    assert session.cache.free_slots == session.config.slots


# ---------------------------------------------------------------------------
# chaos: one request's death must not take down the batch
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_chaos_decode_fault_isolates_request(session, monkeypatch):
    """A raise at one request's decode boundary fails THAT request only;
    in-flight requests on surviving slots complete their full
    generation."""
    monkeypatch.setenv("MXNET_FAULT_INJECT", "serve_decode:raise:after=2")
    faults.reset()
    reqs = _trace(3, seed=16, max_new=6)
    for r in reqs:
        r.arrival_s = 0.0  # co-admitted: all three in flight when it fires
    done, _ = serve.Scheduler(session, policy="continuous").run(reqs)
    failed = [r for r in done if r.failed]
    ok = [r for r in done if not r.failed]
    # slot order is deterministic: the 2nd serve_decode crossing is rid 1
    assert [r.rid for r in failed] == [1]
    assert "FaultInjected" in failed[0].error
    assert len(ok) == 2
    for r in ok:
        assert len(r.tokens) == 6 and r.done_s >= 0
    assert session.cache.free_slots == session.config.slots


@pytest.mark.chaos
def test_chaos_kill_at_respond_boundary(session, monkeypatch):
    """WorkerKilled (BaseException) at the response boundary is
    contained the same way — the stream died, the slot comes back."""
    monkeypatch.setenv("MXNET_FAULT_INJECT", "serve_respond:kill")
    faults.reset()
    reqs = _trace(3, seed=17, max_new=4)
    done, _ = serve.Scheduler(session, policy="continuous").run(reqs)
    failed = [r for r in done if r.failed]
    assert len(failed) == 1
    assert "WorkerKilled" in failed[0].error
    assert len([r for r in done if r.done_s >= 0]) == 2
    assert session.cache.free_slots == session.config.slots


@pytest.mark.chaos
def test_chaos_queue_fault_first_boundary(session, monkeypatch):
    """``serve_queue`` is crossed at EVERY request boundary (before the
    phase-specific site), so its first firing lands on the first
    admission crossing: that request fails, the rest complete, and the
    slot pool drains back to full."""
    monkeypatch.setenv("MXNET_FAULT_INJECT", "serve_queue:raise")
    faults.reset()
    reqs = _trace(3, seed=19, max_new=4)
    done, _ = serve.Scheduler(session, policy="continuous").run(reqs)
    failed = [r for r in done if r.failed]
    assert len(failed) == 1
    assert "FaultInjected" in failed[0].error
    assert len([r for r in done if not r.failed]) == 2
    assert session.cache.free_slots == session.config.slots


@pytest.mark.chaos
def test_chaos_kv_quant_fault_isolates_request(params, monkeypatch):
    """A fault at the quantized-page append site fails only the request
    whose prefill crossed it; the survivors' pages and scale rows stay
    consistent — their token streams match a clean run of the same
    precision, and the slot pool drains back to full."""
    monkeypatch.setenv("MXNET_FAULT_INJECT", "kv_quant:raise:after=2")
    faults.reset()
    sconf = serve.ServeConfig(slots=3, page_size=PAGE, buckets=(8, 16),
                              max_new=8, exact=True, kv_quant="int8")
    sess = serve.InferenceSession(params, num_heads=CFG.num_heads,
                                  config=sconf)
    reqs = _trace(3, seed=21, max_new=4)
    for r in reqs:
        r.arrival_s = 0.0  # co-admitted: the 2nd prefill crossing fails
    done, _ = serve.Scheduler(sess, policy="continuous").run(reqs)
    failed = [r for r in done if r.failed]
    ok = [r for r in done if not r.failed]
    assert len(failed) == 1 and "FaultInjected" in failed[0].error
    assert len(ok) == 2
    assert all(len(r.tokens) == 4 for r in ok)
    assert sess.cache.free_slots == sess.config.slots

    # survivors' quantized pages/scales stayed coherent: same streams
    # as a fault-free session at the same precision
    monkeypatch.delenv("MXNET_FAULT_INJECT")
    faults.reset()
    clean = serve.InferenceSession(params, num_heads=CFG.num_heads,
                                   config=sconf)
    cdone, _ = serve.Scheduler(clean, policy="continuous").run(
        _trace(3, seed=21, max_new=4))
    want = {r.rid: list(r.tokens) for r in cdone}
    for r in ok:
        assert list(r.tokens) == want[r.rid]


@pytest.mark.chaos
def test_chaos_admit_delay_completes(session, monkeypatch):
    monkeypatch.setenv("MXNET_FAULT_INJECT",
                       "serve_admit:delay:seconds=0.02")
    faults.reset()
    done, _ = serve.Scheduler(session, policy="continuous").run(
        _trace(3, seed=18, max_new=3))
    assert all(not r.failed and len(r.tokens) == 3 for r in done)


# ---------------------------------------------------------------------------
# Predictor / ExportedPredictor recompile guardrails (PR 4 wiring)
# ---------------------------------------------------------------------------

def _storm_net(name):
    rs = np.random.RandomState(5)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=3,
                              name="%s_fc" % name), name=name)
    prms = {"%s_fc_weight" % name: mx.nd.array(
                rs.randn(3, 6).astype("float32")),
            "%s_fc_bias" % name: mx.nd.array(np.zeros(3, "float32"))}
    return net, prms


def test_predictor_shape_churn_trips_guard(monkeypatch):
    monkeypatch.setenv("MXNET_RECOMPILE_WARN", "1")
    monkeypatch.setenv("MXNET_RECOMPILE_ERROR", "1")
    net, prms = _storm_net("pstorm")
    x = np.zeros((4, 6), "float32")
    p1 = mx.Predictor(net.tojson(), prms, {"data": (4, 6)})
    p1.forward(data=x)
    p1.forward(data=x)  # steady state: same sig, no storm
    # a shape-churning client: new Predictor per batch size
    p2 = mx.Predictor(net.tojson(), prms, {"data": (5, 6)})
    with pytest.raises(RecompileStorm) as err:
        p2.forward(data=np.zeros((5, 6), "float32"))
    assert err.value.name.startswith("Predictor(")


def test_exported_predictor_shape_drift_trips_guard(tmp_path,
                                                    monkeypatch):
    monkeypatch.setenv("MXNET_RECOMPILE_WARN", "1")
    monkeypatch.setenv("MXNET_RECOMPILE_ERROR", "1")
    net, prms = _storm_net("estorm")
    pred = mx.Predictor(net.tojson(), prms, {"data": (4, 6)})
    pred.forward(data=np.zeros((4, 6), "float32"))
    bundle = str(tmp_path / "estorm_bundle.mxtpu")
    pred.export(bundle)
    served = mx.Predictor.load_exported(bundle)
    served.forward(data=np.zeros((4, 6), "float32"))  # the legal shape
    with pytest.raises(RecompileStorm) as err:
        served.forward(data=np.zeros((7, 6), "float32"))
    assert err.value.name.startswith("ExportedPredictor(estorm_bundle")


# ---------------------------------------------------------------------------
# bench contract
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_bench_serve_budget_emits_partial_json(tmp_path):
    """bench_serve.py under an expired budget still prints one parseable
    JSON line and exits 0 (the bench contract).  Slow tier: a cold jax
    subprocess plus the 2s budget costs ~10s of wall clock."""
    env = dict(os.environ)
    env.pop("MXNET_FAULT_INJECT", None)
    # this test places its own cache: JAX's variable would outrank it
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(JAX_PLATFORMS="cpu",
               MXNET_COMPILE_CACHE_DIR=str(tmp_path / "xla"),
               MXNET_BENCH_BUDGET_S="2")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench_serve.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    result = json.loads(line)
    assert result.get("partial") is True
    assert result.get("budget_s") == 2.0
    assert result["metric"] == "serve_continuous_speedup_vs_serial"
