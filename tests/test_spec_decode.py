"""Speculative decoding: K-token exact verify, draft proposers, cache
rollback, variable-advance scheduling (mxnet_tpu/serve/, ISSUE 12).

The load-bearing claim is *greedy acceptance by the target alone*: every
committed token is the verify executable's own argmax, so speculation
can never commit a token the target did not choose, only change how
many target dispatches a stream takes.  verify_step is built from the
same M-invariant ops as decode_step; the two are still two executables,
and their logits agree to tests/closeness.py's limit (0 spacings read
on jax 0.9.0 for this pair), so a stream differs from the plain one only
where the target's top two logits are closer than that.
"""
import numpy as np
import pytest

from mxnet_tpu import serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serve import model as serve_model
from mxnet_tpu.serve.kv_cache import PagedKVCache, kv_pool_shape
from mxnet_tpu.testing import faults

from closeness import LIMIT_SPACINGS, spacings_apart
from serve_util import lend

CFG = serve.ModelConfig(vocab_size=61, num_layers=2, d_model=32,
                        num_heads=2, max_len=64)
PAGE = 8
SPEC_K = 3


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("MXNET_FAULT_INJECT", raising=False)
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def params():
    return serve_model.init_params(CFG, seed=3)


def _sconf(**kw):
    base = dict(slots=3, page_size=PAGE, buckets=(8, 16), max_new=8,
                exact=True)
    base.update(kw)
    return serve.ServeConfig(**base)


@pytest.fixture(scope="module")
def _plain_session(params):
    return serve.InferenceSession(params, num_heads=CFG.num_heads,
                                  config=_sconf())


@pytest.fixture
def plain_session(_plain_session):
    yield from lend(_plain_session)


@pytest.fixture(scope="module")
def _spec_session(params):
    """Identity draft (layers:<full depth>): the draft IS the target, so
    every window is fully accepted — the deterministic rig for
    acceptance/advance bookkeeping.  (Draft and verify are two
    executables: only a top-two tie closer than a few spacings could
    reject a proposal, and none of this file's traces holds one.)"""
    return serve.InferenceSession(
        params, num_heads=CFG.num_heads,
        config=_sconf(spec_k=SPEC_K, draft="layers:%d" % CFG.num_layers))


@pytest.fixture
def spec_session(_spec_session):
    yield from lend(_spec_session)


def _trace(n, seed=14, max_new=8, eos=-1):
    rs = np.random.RandomState(seed)
    return [serve.Request(rid=i,
                          prompt=rs.randint(1, CFG.vocab_size,
                                            size=4 + i).tolist(),
                          max_new=max_new, arrival_s=0.0, eos_id=eos)
            for i in range(n)]


def _run(sess, reqs):
    done, _ = serve.Scheduler(sess, policy="continuous").run(reqs)
    return {r.rid: list(r.tokens) for r in done}


def _delta(before, after):
    d = {k: after[k] - before[k] for k in
         ("verify_steps", "slot_steps", "proposed", "accepted",
          "committed")}
    d["acceptance_rate"] = (d["accepted"] / float(d["proposed"])
                            if d["proposed"] else 0.0)
    d["tokens_per_verify_step"] = (d["committed"] / float(d["slot_steps"])
                                   if d["slot_steps"] else 0.0)
    return d


# ---------------------------------------------------------------------------
# PagedKVCache.truncate + the speculative table pad
# ---------------------------------------------------------------------------

def test_truncate_rolls_back_lengths_only():
    cache = PagedKVCache(num_layers=1, num_heads=2, head_dim=4,
                         page_size=8, num_pages=4, slots=2,
                         max_pages_per_slot=2)
    slot = cache.alloc(5, 8)
    cache.lengths[slot] = 9
    pages_before = cache.free_pages
    cache.truncate(slot, 3)
    assert cache.lengths[slot] == 6
    # rollback never returns pages: the reservation is worst-case at
    # admission, so the freed rows stay owned (and get overwritten)
    assert cache.free_pages == pages_before
    cache.truncate(slot, 6)
    assert cache.lengths[slot] == 0
    cache.release(slot)
    assert cache.free_pages == 4 and cache.free_slots == 2


def test_truncate_rejects_bad_args():
    cache = PagedKVCache(num_layers=1, num_heads=2, head_dim=4,
                         page_size=8, num_pages=4, slots=2,
                         max_pages_per_slot=2)
    with pytest.raises(MXNetError):
        cache.truncate(0, 1)  # unallocated slot
    slot = cache.alloc(5, 3)
    cache.lengths[slot] = 5
    with pytest.raises(MXNetError):
        cache.truncate(slot, -1)
    with pytest.raises(MXNetError):
        cache.truncate(slot, 6)  # past zero
    assert cache.lengths[slot] == 5  # failed truncates left it alone


def test_truncate_preserves_device_table_cache():
    """The upload cache invalidates ONLY on alloc/release; truncate
    mutates lengths, not tables, so the cached device array must
    survive it."""
    cache = PagedKVCache(num_layers=1, num_heads=2, head_dim=4,
                         page_size=8, num_pages=4, slots=2,
                         max_pages_per_slot=2)
    slot = cache.alloc(5, 8)
    dev = cache.device_tables()
    cache.lengths[slot] = 4
    cache.truncate(slot, 2)
    assert cache.device_tables() is dev  # no re-upload
    cache.release(slot)
    assert cache._tables_dev is None  # release still invalidates


def test_table_pad_columns_are_trash():
    cache = PagedKVCache(num_layers=1, num_heads=2, head_dim=4,
                         page_size=8, num_pages=4, slots=2,
                         max_pages_per_slot=2, table_pad=1)
    assert cache.table_width == 3
    slot = cache.alloc(9, 7)  # needs exactly max_pages_per_slot
    # the pad column stays trash even for a fully-reserved slot: a
    # clipped overflow write can never alias a real page
    assert cache._tables[slot, 2] == cache.trash_page
    assert cache._tables[slot, 0] != cache.trash_page
    with pytest.raises(MXNetError):
        PagedKVCache(num_layers=1, num_heads=2, head_dim=4, page_size=8,
                     num_pages=4, slots=2, max_pages_per_slot=2,
                     table_pad=-1)


def test_spec_pad_pages_config():
    assert _sconf(spec_k=0).spec_pad_pages == 0
    assert _sconf(spec_k=3).spec_pad_pages == 1  # ceil(3/8)
    assert _sconf(spec_k=8).spec_pad_pages == 1
    assert _sconf(spec_k=9).spec_pad_pages == 2
    assert _sconf(spec_k=3).spec_window == 4


# ---------------------------------------------------------------------------
# verify_step exactness: one W-row verify == W serial decode steps
# ---------------------------------------------------------------------------

def _verify_vs_serial_decode(pool_dtype, position_off=0, layout="folded"):
    """Gaps, in spacings, between one batched verify and the serial
    decode trajectory fed the same tokens: (logits, k pool, v pool),
    plus whether verify's greedy row is its own logits' argmax.
    ``position_off`` plants the fault: verify is told a history one
    row longer than the pools hold.  ``layout``: the pools as the cache
    lays out heads of 8 (``"folded"`` into the last axis), or with the
    heads on an axis of their own; the step functions take either."""
    import jax
    import jax.numpy as jnp

    cfg = serve.ModelConfig(vocab_size=37, num_layers=2, d_model=16,
                            num_heads=2, max_len=32)
    params = serve_model.init_params(cfg, seed=7)
    page, w, slots, pages = 4, SPEC_K + 1, 2, 8
    dtype = jnp.dtype(pool_dtype)
    pool_shape = (cfg.num_layers, pages + 1, page, cfg.num_heads,
                  cfg.head_dim)
    if layout == "folded":
        pool_shape = kv_pool_shape(*pool_shape)
        assert len(pool_shape) == 4
    tables = jnp.asarray([[0, 1, 2, pages], [3, 4, 5, pages]], jnp.int32)

    def on_pools(step):
        def run(p, t, l, kp, vp):
            toks, logits, pools, _ = step(
                p, t, l, tables, {"k_pool": kp, "v_pool": vp}, {}, cfg,
                page, exact=True)
            return toks, logits, pools["k_pool"], pools["v_pool"]
        return jax.jit(run)

    decode = on_pools(serve_model.decode_step)
    verify = on_pools(serve_model.verify_step)

    rs = np.random.RandomState(11)
    k_pool = jnp.zeros(pool_shape, dtype)
    v_pool = jnp.zeros(pool_shape, dtype)
    # build unequal histories serially (slot 0: 5 rows, slot 1: 3 rows)
    hist_len = np.asarray([5, 3], np.int32)
    for j in range(int(hist_len.max())):
        toks = jnp.asarray(rs.randint(1, cfg.vocab_size, slots), jnp.int32)
        lens = jnp.asarray(np.minimum(j, hist_len), jnp.int32)
        _, _, k_pool, v_pool = decode(params, toks, lens, k_pool, v_pool)

    window = rs.randint(1, cfg.vocab_size, (slots, w)).astype(np.int32)

    # serial trajectory: W decode steps, one row at a time
    sk, sv = k_pool, v_pool
    serial_logits = []
    for j in range(w):
        lens = jnp.asarray(hist_len + j, jnp.int32)
        _, logits, sk, sv = decode(params, jnp.asarray(window[:, j]),
                                   lens, sk, sv)
        serial_logits.append(np.asarray(logits))
    serial_logits = np.stack(serial_logits, axis=1)  # (S, W, V)

    greedy, batched_logits, bk, bv = verify(
        params, jnp.asarray(window), jnp.asarray(hist_len + position_off),
        k_pool, v_pool)
    batched_logits = np.asarray(batched_logits)
    own_argmax = np.array_equal(
        np.asarray(greedy), batched_logits.argmax(axis=-1).astype(np.int32))
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    return (spacings_apart(batched_logits, serial_logits),
            spacings_apart(f32(bk), f32(sk), pool_dtype),
            spacings_apart(f32(bv), f32(sv), pool_dtype), own_argmax)


@pytest.mark.parametrize("layout", ["folded", "heads"])
@pytest.mark.parametrize("pool_dtype", ["float32", "bfloat16"])
def test_verify_bitexact_vs_serial_decode(pool_dtype, layout):
    """The kernel-level contract under both pool precisions and both
    layouts of the pools at rest: logits AND
    the written KV pools from one batched verify match the serial decode
    trajectory fed the same tokens (two executables: 0 spacings read on
    jax 0.9.0, the limit is tests/closeness.py's), and the greedy row is
    the verify logits' own argmax, exactly."""
    logits, k_gap, v_gap, own_argmax = _verify_vs_serial_decode(
        pool_dtype, layout=layout)
    assert max(logits, k_gap, v_gap) <= LIMIT_SPACINGS
    assert own_argmax


def test_verify_comparison_sees_planted_fault():
    """The control: a history length off by one reads 1.7e7 spacings on
    the logits and 1.3e7 on the K pool (its rows land a position off)."""
    logits, k_gap, _, own_argmax = _verify_vs_serial_decode(
        "float32", position_off=1)
    assert logits > 30 * LIMIT_SPACINGS and k_gap > 30 * LIMIT_SPACINGS
    assert own_argmax


# ---------------------------------------------------------------------------
# acceptance bookkeeping: all, none, EOS inside the window
# ---------------------------------------------------------------------------

def test_accept_all_with_identity_draft(plain_session, spec_session):
    ref = _run(plain_session, _trace(4, seed=21))
    before = spec_session.spec_report()
    got = _run(spec_session, _trace(4, seed=21))
    assert got == ref  # the same streams
    d = _delta(before, spec_session.spec_report())
    # identity draft: every proposal with a chance to commit is accepted
    assert d["acceptance_rate"] == 1.0
    assert d["tokens_per_verify_step"] > 2.0
    # spec_step commits everything after each request's prefill token
    assert d["committed"] == sum(len(v) - 1 for v in ref.values())


def test_accept_zero_never_matching_draft(params, plain_session,
                                          monkeypatch):
    """A draft that is always wrong degrades to one committed token per
    step — decode-step semantics, same bit-identical output."""
    ref = _run(plain_session, _trace(3, seed=22))
    bad = max(set(range(CFG.vocab_size))
              - set(t for v in ref.values() for t in v))
    sess = serve.InferenceSession(params, num_heads=CFG.num_heads,
                                  config=_sconf(spec_k=SPEC_K,
                                                draft="ngram"))
    monkeypatch.setattr(sess, "_ngram_propose",
                        lambda slot, k, max_n=3: [bad] * k)
    got = _run(sess, _trace(3, seed=22))
    rep = sess.spec_report()
    assert got == ref
    assert rep["acceptance_rate"] == 0.0
    assert rep["tokens_per_verify_step"] == 1.0
    assert rep["committed"] == sum(len(v) - 1 for v in ref.values())


def test_eos_inside_speculated_window(plain_session, spec_session):
    """EOS landing mid-window: the committed tail past it is dropped and
    the request stops exactly where non-speculative decode stops."""
    base = _run(plain_session, _trace(1, seed=23))[0]
    eos = base[2]  # third emitted token: inside the first K+1 window
    ref = _run(plain_session, _trace(1, seed=23, eos=eos))
    got = _run(spec_session, _trace(1, seed=23, eos=eos))
    assert got == ref
    assert got[0][-1] == eos and len(got[0]) == 3
    assert len(got[0]) < _sconf().max_new
    assert spec_session.cache.free_slots == spec_session.config.slots


def test_max_new_respected_with_full_windows(spec_session):
    """max_new not a multiple of the window: the final partial window
    must commit exactly the remainder, never overrunning the page
    reservation."""
    got = _run(spec_session, _trace(3, seed=24, max_new=6))
    assert all(len(v) == 6 for v in got.values())
    assert spec_session.cache.free_pages == spec_session.cache.num_pages
    assert (spec_session.draft_cache.free_pages
            == spec_session.draft_cache.num_pages)


# ---------------------------------------------------------------------------
# session plumbing: executables frozen, drafts resolve, stats report
# ---------------------------------------------------------------------------

def test_executable_count_frozen_with_neural_draft(spec_session,
                                                   monkeypatch):
    """len(buckets) + 3 executables, and a full continuous-batching run
    under MXNET_RECOMPILE_ERROR never traces a fourth."""
    monkeypatch.setenv("MXNET_RECOMPILE_ERROR", "1")
    names = sorted(spec_session.executables)
    assert names == ["decode", "draft", "prefill_16", "prefill_8",
                     "verify"]
    assert len(names) == len(spec_session.config.buckets) + 3
    got = _run(spec_session, _trace(5, seed=25))
    assert all(len(v) == 8 for v in got.values())
    assert sorted(spec_session.executables) == names
    assert spec_session.fallback_count() == 0


def test_ngram_session_bitexact_and_lean(params, plain_session):
    """The host-side n-gram draft needs no draft executable
    (len(buckets) + 2) and still produces bit-identical output."""
    sess = serve.InferenceSession(params, num_heads=CFG.num_heads,
                                  config=_sconf(spec_k=SPEC_K,
                                                draft="ngram"))
    assert sorted(sess.executables) == ["decode", "prefill_16",
                                       "prefill_8", "verify"]
    assert _run(sess, _trace(4, seed=26)) == _run(plain_session,
                                                  _trace(4, seed=26))
    rep = sess.spec_report()
    assert rep["committed"] == 4 * (8 - 1)  # prefill emits the first
    assert 0.0 <= rep["acceptance_rate"] <= 1.0


def test_draft_resolution_errors(params):
    with pytest.raises(MXNetError):  # draft params without spec_k
        serve.InferenceSession(params, num_heads=CFG.num_heads,
                               config=_sconf(),
                               draft_params=dict(params))
    with pytest.raises(MXNetError):  # more layers than the target has
        serve.InferenceSession(
            params, num_heads=CFG.num_heads,
            config=_sconf(spec_k=2, draft="layers:9"))
    with pytest.raises(MXNetError):  # spec_step on a non-spec session
        serve.InferenceSession(params, num_heads=CFG.num_heads,
                               config=_sconf()).spec_step()


def test_spec_env_knobs(monkeypatch):
    monkeypatch.setenv("MXNET_SERVE_SPEC_K", "5")
    monkeypatch.setenv("MXNET_SERVE_DRAFT", "layers:1")
    cfg = serve.ServeConfig.from_env(slots=2)
    assert cfg.spec_k == 5 and cfg.draft == "layers:1"
    with pytest.raises(MXNetError):
        serve.ServeConfig(spec_k=-1)


# ---------------------------------------------------------------------------
# chaos: a fault at the verify boundary fails only that request
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_chaos_verify_fault_isolates_request(params, monkeypatch):
    """A raise at one request's verify boundary fails THAT request only:
    survivors complete their full generation and both caches drain back
    to all-free."""
    sess = serve.InferenceSession(
        params, num_heads=CFG.num_heads,
        config=_sconf(spec_k=SPEC_K, draft="layers:%d" % CFG.num_layers))
    monkeypatch.setenv("MXNET_FAULT_INJECT", "serve_verify:raise:after=2")
    faults.reset()
    reqs = _trace(3, seed=27, max_new=6)
    done, _ = serve.Scheduler(sess, policy="continuous").run(reqs)
    failed = [r for r in done if r.failed]
    ok = [r for r in done if not r.failed]
    # deterministic slot order: the 2nd serve_verify crossing is rid 1
    assert [r.rid for r in failed] == [1]
    assert "FaultInjected" in failed[0].error
    assert len(ok) == 2
    for r in ok:
        assert len(r.tokens) == 6 and r.done_s >= 0
    assert sess.cache.free_slots == sess.config.slots
    assert sess.cache.free_pages == sess.cache.num_pages
    assert sess.draft_cache.free_pages == sess.draft_cache.num_pages
