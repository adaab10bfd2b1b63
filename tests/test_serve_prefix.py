"""Prefix caching + KV page oversubscription in the serving runtime:
token-hash prefix index with refcounted copy-on-write shared pages,
heap free lists with pinned lowest-first reuse, admit-by-current-need
with watermark preemption, deterministic park/resume bit-exact against
a never-evicted oracle, SLO goodput accounting, and the serve_evict /
serve_resume chaos sites (mxnet_tpu/serve/, docs/serving.md)."""
import numpy as np
import pytest

from mxnet_tpu import serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serve import model as serve_model
from mxnet_tpu.serve import kv_cache
from mxnet_tpu.serve.kv_cache import PagedKVCache
from mxnet_tpu.serve.scheduler import Request, Scheduler, summarize
from mxnet_tpu.testing import faults

from closeness import assert_close_across_executables
from serve_util import lend

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
def _prefix_session(params):
    """Reservation admission + prefix cache (the hit/CoW tests)."""
    sconf = serve.ServeConfig(slots=3, page_size=PAGE, buckets=(8, 16),
                              max_new=8, exact=True, prefix_pages=-1)
    return serve.InferenceSession(params, num_heads=CFG.num_heads,
                                  config=sconf)


@pytest.fixture
def prefix_session(_prefix_session):
    yield from lend(_prefix_session)


@pytest.fixture(scope="module")
def _oversub_session(params):
    """Oversubscribed 5-page pool: 3 one-page prompts admit, growth at
    decode boundaries forces watermark preemption."""
    sconf = serve.ServeConfig(slots=3, page_size=PAGE, buckets=(8, 16),
                              max_new=8, exact=True, num_pages=5,
                              oversub=True, prefix_pages=-1)
    return serve.InferenceSession(params, num_heads=CFG.num_heads,
                                  config=sconf)


@pytest.fixture
def oversub_session(_oversub_session):
    yield from lend(_oversub_session)


def _greedy_oracle(sess, prompt, max_new):
    """Serial full-context greedy continuation — the never-evicted,
    never-cached reference stream."""
    seq = list(prompt)
    out = []
    for _ in range(max_new):
        ref = np.asarray(serve_model.reference_last_logits(
            sess.params, seq, CFG, PAGE, exact=True))
        tok = int(np.argmax(ref))
        out.append(tok)
        seq.append(tok)
    return out


def _trace(n, seed, prompt_len=8, max_new=6, shared_prefix=None):
    """Co-arriving requests; with ``shared_prefix`` every prompt starts
    with that exact token run (prefix-cache hits when it spans full
    pages) followed by ``prompt_len - len(shared_prefix)`` fresh ones."""
    rs = np.random.RandomState(seed)
    base = list(shared_prefix or [])
    fresh = prompt_len - len(base)
    assert fresh >= 1, "need at least one fresh token per prompt"
    return [Request(rid=i,
                    prompt=base + rs.randint(1, CFG.vocab_size,
                                             size=fresh).tolist(),
                    max_new=max_new, arrival_s=0.0)
            for i in range(n)]


# ---------------------------------------------------------------------------
# free-list heap: deterministic lowest-first reuse, no per-release sort
# ---------------------------------------------------------------------------

def test_free_heap_reuse_order_pinned():
    """Releases in ANY order must hand pages/slots back lowest-id-first
    — the contract the old sort-on-every-release implementation gave,
    now kept by the min-heaps."""
    cache = PagedKVCache(num_layers=1, num_heads=2, head_dim=4,
                         page_size=8, num_pages=6, slots=3,
                         max_pages_per_slot=2)
    s0, s1, s2 = cache.alloc(8, 8), cache.alloc(8, 8), cache.alloc(8, 8)
    assert (s0, s1, s2) == (0, 1, 2)
    assert list(cache._tables[s2][:2]) == [4, 5]
    # scrambled release order: middle, then first, then last
    cache.release(s1)
    cache.release(s0)
    cache.release(s2)
    # reuse is lowest-first regardless of how the frees interleaved
    a = cache.alloc(8, 8)
    assert a == 0 and list(cache._tables[a][:2]) == [0, 1]
    b = cache.alloc(8, 8)
    assert b == 1 and list(cache._tables[b][:2]) == [2, 3]
    c = cache.alloc(8, 8)
    assert c == 2 and list(cache._tables[c][:2]) == [4, 5]


# ---------------------------------------------------------------------------
# prefix index bookkeeping (host-side, no dispatch)
# ---------------------------------------------------------------------------

def test_prefix_index_match_register_retention():
    cache = PagedKVCache(num_layers=1, num_heads=2, head_dim=4,
                         page_size=8, num_pages=8, slots=3,
                         max_pages_per_slot=3, prefix_pages=1)
    toks = list(range(1, 21))  # 2 full pages + a 4-token tail
    s0 = cache.alloc(20, 4, tokens=toks)
    assert cache.cached_len(s0) == 0  # nothing published yet
    assert cache.register_prefix(s0, toks) == 2  # full pages only
    assert len(cache.match_prefix(toks)) == 2
    # a diverged first token kills the whole chain, not just one page
    assert cache.match_prefix([9] + toks[1:]) == []
    # page-aligned prompt: hit capped to leave >= 1 token of suffix
    s1 = cache.alloc(16, 4, tokens=toks[:16])
    assert cache.cached_len(s1) == 8
    assert cache.lengths[s1] == 8  # lengths starts AT the cached prefix
    stats = cache.prefix_stats
    assert stats["hits"] == 1 and stats["hit_tokens"] == 8
    cache.release(s1)
    cache.release(s0)
    # retention cap 1: the LRU published page was evicted to the heap
    assert cache.retained_pages == 1
    assert cache.reclaimable_pages == 8
    # retained pages are lazily reclaimed when the heap runs dry
    held = [cache.alloc(24, 0) for _ in range(2)]  # 3 pages each
    assert cache.free_pages == 1
    s2 = cache.alloc(9, 4)  # needs 2: the last free + 1 evicted retained
    assert s2 is not None and cache.retained_pages == 0
    for s in held + [s2]:
        cache.release(s)


def test_oversub_alloc_admits_by_current_need():
    cache = PagedKVCache(num_layers=1, num_heads=2, head_dim=4,
                         page_size=8, num_pages=4, slots=3,
                         max_pages_per_slot=3)
    # reservation: 8 prompt + 8 new = 2 pages each -> only 2 admit
    assert cache.can_admit(8, 8)
    s0 = cache.alloc(8, 8)
    s1 = cache.alloc(8, 8)
    assert s0 is not None and s1 is not None
    assert cache.alloc(8, 8) is None
    cache.release(s0)
    cache.release(s1)
    # oversubscribed: 1 page each now -> all three admit, then grow
    slots = [cache.alloc(8, 8, oversub=True) for _ in range(3)]
    assert None not in slots
    assert cache.free_pages == 1
    assert cache.pages_short(slots[0], 9) == 1
    assert cache.append_pages(slots[0], 9) == 1
    assert cache.append_pages(slots[0], 9) == 0  # idempotent
    assert cache.free_pages == 0
    assert cache.pages_short(slots[1], 9) == 1
    with pytest.raises(MXNetError):
        cache.append_pages(slots[1], 9)  # pool dry: preemption's job
    for s in slots:
        cache.release(s)


# ---------------------------------------------------------------------------
# prefix-cache hit: suffix-only prefill, bit-exact vs the cold miss
# ---------------------------------------------------------------------------

def test_prefix_hit_bitexact_vs_cold_miss(prefix_session):
    """Two prompts sharing a full first page: the second admission maps
    the published page, prefills only the suffix, and its logits (and
    every decode step after) match the full-context reference — i.e.
    what a cold prefill computes — as closely as two executables can
    (tests/closeness.py)."""
    sess = prefix_session
    lookups0 = sess.cache.prefix_stats["lookups"]
    shared = [5, 9, 2, 11, 3, 7, 8, 4]  # one full page
    p_cold = shared + [1, 6]
    p_hit = shared + [2, 9, 14]
    s_cold = sess.try_alloc(len(p_cold), 6, tokens=p_cold)
    first_c, logits_c = sess.prefill(s_cold, p_cold)
    logits_c = np.asarray(logits_c)
    assert sess.cache.cached_len(s_cold) == 0
    s_hit = sess.try_alloc(len(p_hit), 6, tokens=p_hit)
    assert sess.cache.cached_len(s_hit) == PAGE  # mapped, not recomputed
    first_h, logits_h = sess.prefill(s_hit, p_hit)
    logits_h = np.asarray(logits_h)
    for seq, logits in ((p_cold, logits_c), (p_hit, logits_h)):
        ref = np.asarray(serve_model.reference_last_logits(
            sess.params, seq, CFG, PAGE, exact=True))
        assert_close_across_executables(logits, ref)
    stats = sess.cache.prefix_stats
    assert stats["lookups"] - lookups0 == 2
    assert stats["hit_tokens"] >= PAGE
    # decode both: streams stay on the reference with a shared mapped page
    seqs = {s_cold: p_cold + [first_c], s_hit: p_hit + [first_h]}
    for _ in range(3):
        toks, logits = sess.step()
        logits = np.asarray(logits)
        for slot, seq in seqs.items():
            ref = np.asarray(serve_model.reference_last_logits(
                sess.params, seq, CFG, PAGE, exact=True))
            assert_close_across_executables(logits[slot], ref)
            seq.append(toks[slot])


def test_cow_divergence_never_mutates_shared_page(prefix_session):
    """Force the copy-on-write guard on a page two slots share: the
    writer gets a bit-identical private copy, the original page (and
    the other holder's table entry) are untouched, and both streams
    keep decoding on the reference."""
    sess = prefix_session
    shared = [4, 4, 9, 1, 13, 2, 6, 10]
    pa = shared + [3]
    pb = shared + [8, 12]
    sa = sess.try_alloc(len(pa), 6, tokens=pa)
    first_a, _ = sess.prefill(sa, pa)
    sb = sess.try_alloc(len(pb), 6, tokens=pb)
    assert sess.cache.cached_len(sb) == PAGE
    first_b, _ = sess.prefill(sb, pb)
    page = int(sess.cache._tables[sa, 0])
    assert int(sess.cache._tables[sb, 0]) == page  # genuinely shared
    before_k = np.asarray(sess.cache.pools["k_pool"][:, page])
    before_v = np.asarray(sess.cache.pools["v_pool"][:, page])
    copied = sess.cache.ensure_writable(sb, 0, 1)
    assert copied == 1
    new_page = int(sess.cache._tables[sb, 0])
    assert new_page != page
    assert int(sess.cache._tables[sa, 0]) == page  # holder unaffected
    np.testing.assert_array_equal(
        np.asarray(sess.cache.pools["k_pool"][:, page]), before_k)
    np.testing.assert_array_equal(
        np.asarray(sess.cache.pools["v_pool"][:, page]), before_v)
    # the private copy is bit-identical, so attention through it is too
    np.testing.assert_array_equal(
        np.asarray(sess.cache.pools["k_pool"][:, new_page]), before_k)
    np.testing.assert_array_equal(
        np.asarray(sess.cache.pools["v_pool"][:, new_page]), before_v)
    assert sess.cache.prefix_stats["cow_copies"] >= 1
    seqs = {sa: pa + [first_a], sb: pb + [first_b]}
    for _ in range(2):
        toks, logits = sess.step()
        logits = np.asarray(logits)
        for slot, seq in seqs.items():
            ref = np.asarray(serve_model.reference_last_logits(
                sess.params, seq, CFG, PAGE, exact=True))
            assert_close_across_executables(logits[slot], ref)
            seq.append(toks[slot])


@pytest.mark.parametrize("head_dim, lanes", [(16, 32), (128, 128)],
                         ids=["folded", "heads"])
def test_cow_copies_a_page_whatever_the_pools_layout(head_dim, lanes):
    """Copy-on-write copies axis 1, the page, which both layouts of the
    K/V pools share: heads of 16 folded into a last axis of 32, heads of
    128 on an axis of their own.  The private page reads back, as
    (page_size, heads, head_dim) rows, what was appended to the shared
    one, and the shared one is untouched."""
    import jax.numpy as jnp

    # heads of 128 keep their axis in whole sublane tiles of them
    heads, layers = (8 if lanes == head_dim else 2), 2
    cache = PagedKVCache(layers, heads, head_dim, PAGE, 6, 2, 3,
                         prefix_pages=-1)
    assert cache.kv_lanes == lanes
    assert cache.pools["k_pool"].ndim == (4 if lanes != head_dim else 5)
    tokens = list(range(1, PAGE + 2))
    sa = cache.alloc(len(tokens), 4, tokens=tokens)
    page = int(cache.table_row(sa)[0])
    rs = np.random.RandomState(5)
    rows = {w: rs.randn(layers, PAGE, heads, head_dim).astype(np.float32)
            for w in "kv"}
    pools = dict(cache.pools)
    for layer in range(layers):
        for w in "kv":
            kv_cache.append_rows(pools, w, layer, page, jnp.arange(PAGE),
                                 jnp.asarray(rows[w][layer]))
    cache.pools.update(pools)
    assert cache.register_prefix(sa, tokens) == 1
    sb = cache.alloc(len(tokens), 4, tokens=tokens)
    assert cache.cached_len(sb) == PAGE
    assert int(cache.table_row(sb)[0]) == page       # genuinely shared
    assert cache.ensure_writable(sb, 0, 1) == 1
    new_page = int(cache.table_row(sb)[0])
    assert new_page != page and int(cache.table_row(sa)[0]) == page
    for w in "kv":
        for layer in range(layers):
            for at in (page, new_page):
                np.testing.assert_array_equal(
                    np.asarray(kv_cache.read_pages(
                        cache.pools[w + "_pool"], layer,
                        jnp.asarray([at]), head_dim))[0],
                    rows[w][layer])
    assert cache.prefix_stats["cow_copies"] == 1


# ---------------------------------------------------------------------------
# oversubscription: preempt-and-recompute, bit-exact vs never evicted
# ---------------------------------------------------------------------------

def test_preempt_resume_bitexact_vs_never_evicted(oversub_session):
    """A 5-page pool under three 2-page-growth requests MUST preempt;
    every resumed stream must be bit-identical to the serial
    full-context greedy oracle (= the never-evicted stream)."""
    sess = oversub_session
    reqs = _trace(3, seed=23, prompt_len=8, max_new=6)
    oracle = {r.rid: _greedy_oracle(sess, r.prompt, r.max_new)
              for r in reqs}
    sched = Scheduler(sess, policy="continuous")
    done, _ = sched.run(reqs)
    assert sched.stats["preemptions"] > 0
    assert sched.stats["resumes"] == sched.stats["preemptions"]
    assert sched.stats["peak_active"] == 3  # oversub admitted all three
    for r in done:
        assert not r.failed, r.error
        assert r.tokens == oracle[r.rid]
    assert sess.cache.free_slots == sess.config.slots
    assert sess.active_slots() == []


def test_oversub_outlasts_reservation_at_equal_pool(params):
    """At the same 5-page pool, reservation admission can only hold 2
    requests in flight; oversubscription holds all 3 (the acceptance
    criterion's concurrency claim, measured here at test scale)."""
    reserve_conf = serve.ServeConfig(
        slots=3, page_size=PAGE, buckets=(8, 16), max_new=8, exact=True,
        num_pages=5)
    sess_r = serve.InferenceSession(params, num_heads=CFG.num_heads,
                                    config=reserve_conf)
    sched_r = Scheduler(sess_r, policy="continuous")
    done_r, _ = sched_r.run(_trace(3, seed=29, max_new=4))
    assert sched_r.stats["peak_active"] == 2  # 2x2 pages fill the pool

    sconf = serve.ServeConfig(
        slots=3, page_size=PAGE, buckets=(8, 16), max_new=8, exact=True,
        num_pages=5, oversub=True, prefix_pages=-1)
    sess_o = serve.InferenceSession(params, num_heads=CFG.num_heads,
                                    config=sconf)
    sched_o = Scheduler(sess_o, policy="continuous")
    done_o, _ = sched_o.run(_trace(3, seed=29, max_new=4))
    assert sched_o.stats["peak_active"] == 3
    # same tokens either way: admission policy changes capacity, not
    # content
    assert ({r.rid: r.tokens for r in done_o}
            == {r.rid: r.tokens for r in done_r})


def test_spec_decode_composes_with_prefix_and_oversub(params):
    """Speculative decoding (ngram draft) + prefix cache + oversub +
    preemption together still emit the exact serial-reference streams,
    with the executable set frozen at buckets + decode + verify."""
    sconf = serve.ServeConfig(slots=3, page_size=PAGE, buckets=(8, 16),
                              max_new=8, exact=True, num_pages=5,
                              oversub=True, prefix_pages=-1, spec_k=2,
                              draft="ngram")
    sess = serve.InferenceSession(params, num_heads=CFG.num_heads,
                                  config=sconf)
    assert sorted(sess.executables) == ["decode", "prefill_16",
                                        "prefill_8", "verify"]
    shared = [7, 3, 11, 5, 2, 9, 4, 13]  # one full shared page: hits
    reqs = _trace(3, seed=31, prompt_len=16, max_new=6,
                  shared_prefix=shared)
    oracle = {r.rid: _greedy_oracle(sess, r.prompt, r.max_new)
              for r in reqs}
    sched = Scheduler(sess, policy="continuous")
    done, _ = sched.run(reqs)
    for r in done:
        assert not r.failed, r.error
        assert r.tokens == oracle[r.rid]
    assert sess.cache.free_slots == sess.config.slots


def test_executables_frozen_under_recompile_error(params, monkeypatch):
    """MXNET_RECOMPILE_ERROR turns any retrace into a raise; a full
    prefix+oversub run — shared-prefix hits, suffix prefill at non-zero
    offsets, preemption, chunked resume re-prefill — must complete with
    the compile-time executable set and exactly one trace per guard."""
    monkeypatch.setenv("MXNET_RECOMPILE_ERROR", "1")
    sconf = serve.ServeConfig(slots=3, page_size=PAGE, buckets=(8, 16),
                              max_new=8, exact=True, num_pages=7,
                              oversub=True, prefix_pages=-1, watermark=1)
    sess = serve.InferenceSession(params, num_heads=CFG.num_heads,
                                  config=sconf)
    assert sorted(sess.executables) == ["decode", "prefill_16",
                                        "prefill_8"]
    shared = [3, 8, 2, 14, 6, 1, 9, 5]
    # 16-token prompts: resume transcripts exceed the largest bucket,
    # exercising the chunked (multi-dispatch) re-prefill
    reqs = _trace(3, seed=37, prompt_len=16, max_new=6,
                  shared_prefix=shared)
    sched = Scheduler(sess, policy="continuous")
    done, _ = sched.run(reqs)
    assert all(not r.failed for r in done)
    assert sched.stats["preemptions"] > 0  # the run did oversubscribe
    assert sorted(sess.executables) == ["decode", "prefill_16",
                                        "prefill_8"]
    assert sess.fallback_count() == 0
    for name, snap in sess.guard_report().items():
        assert snap["traces"] == 1, (name, snap)


# ---------------------------------------------------------------------------
# chaos: eviction/resume faults are contained to the one request
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_chaos_evict_fault_isolates_victim(oversub_session, monkeypatch):
    """A raise at the serve_evict boundary fails the victim alone:
    survivors finish their exact streams, the pool drains clean, and
    the shared prefix pages stay usable for a fresh admission."""
    sess = oversub_session
    monkeypatch.setenv("MXNET_FAULT_INJECT", "serve_evict:raise")
    faults.reset()
    shared = [2, 12, 7, 1, 9, 15, 4, 6]  # one full page, shared by all
    # 10-token prompts growing to 18 tokens: 3 pages each against the
    # 5-page pool guarantees the eviction path fires
    reqs = _trace(3, seed=41, prompt_len=10, max_new=8,
                  shared_prefix=shared)
    oracle = {r.rid: _greedy_oracle(sess, r.prompt, r.max_new)
              for r in reqs}
    done, _ = Scheduler(sess, policy="continuous").run(reqs)
    failed = [r for r in done if r.failed]
    assert len(failed) == 1
    assert "FaultInjected" in failed[0].error
    survivors = [r for r in done if not r.failed]
    assert len(survivors) == 2
    for r in survivors:
        assert r.tokens == oracle[r.rid]
    assert sess.cache.free_slots == sess.config.slots
    # the shared prefix page survived the faulted eviction: a new
    # request over the same prefix still hits and decodes on the reference
    faults.reset()
    monkeypatch.delenv("MXNET_FAULT_INJECT")
    probe = shared + [11]
    slot = sess.try_alloc(len(probe), 2, tokens=probe)
    assert sess.cache.cached_len(slot) == PAGE
    _, logits = sess.prefill(slot, probe)
    logits = np.asarray(logits)
    ref = np.asarray(serve_model.reference_last_logits(
        sess.params, probe, CFG, PAGE, exact=True))
    assert_close_across_executables(logits, ref)


@pytest.mark.chaos
def test_chaos_resume_fault_isolates_parked(oversub_session,
                                            monkeypatch):
    """A raise at the serve_resume boundary fails the parked request
    alone — it never re-enters the batch, survivors complete their
    exact streams, and every slot returns to the pool."""
    sess = oversub_session
    monkeypatch.setenv("MXNET_FAULT_INJECT", "serve_resume:raise")
    faults.reset()
    reqs = _trace(3, seed=43, prompt_len=8, max_new=6)
    oracle = {r.rid: _greedy_oracle(sess, r.prompt, r.max_new)
              for r in reqs}
    done, _ = Scheduler(sess, policy="continuous").run(reqs)
    failed = [r for r in done if r.failed]
    assert len(failed) == 1
    assert failed[0].preemptions > 0  # it died on the resume path
    assert "FaultInjected" in failed[0].error
    survivors = [r for r in done if not r.failed]
    assert len(survivors) == 2
    for r in survivors:
        assert r.tokens == oracle[r.rid]
    assert sess.cache.free_slots == sess.config.slots
    assert sess.active_slots() == []


# ---------------------------------------------------------------------------
# SLO accounting
# ---------------------------------------------------------------------------

def test_summarize_goodput_under_slo():
    reqs = []
    for i in range(4):
        r = Request(rid=i, prompt=[1], max_new=2)
        r.tokens = [1, 2]
        r.done_s = 1.0
        r.ttft_s = 0.05 if i < 3 else 0.5  # one blows a 100ms budget
        reqs.append(r)
    s = summarize(reqs, makespan_s=2.0, ttft_slo_ms=100.0)
    assert s["completed"] == 4
    assert s["goodput_rps"] == pytest.approx(1.5)  # 3 good / 2s
    assert s["slo_attainment"] == pytest.approx(0.75)
    # without a budget the goodput fields don't appear (bench back-compat)
    assert "goodput_rps" not in summarize(reqs, makespan_s=2.0)


def test_scheduler_slo_admission_prefers_meetable(params):
    """With a TTFT budget configured, a request already past its budget
    yields its admission slot to one that can still meet it."""
    sconf = serve.ServeConfig(slots=1, page_size=PAGE, buckets=(8,),
                              max_new=4, exact=True, ttft_slo_ms=50.0)
    sess = serve.InferenceSession(params, num_heads=CFG.num_heads,
                                  config=sconf)
    rs = np.random.RandomState(47)
    blown = Request(rid=0, prompt=rs.randint(
        1, CFG.vocab_size, size=8).tolist(), max_new=3, arrival_s=-1.0)
    fresh = Request(rid=1, prompt=rs.randint(
        1, CFG.vocab_size, size=8).tolist(), max_new=3, arrival_s=0.0)
    done, mk = Scheduler(sess, policy="serial").run([blown, fresh])
    by_rid = {r.rid: r for r in done}
    # both complete, but the fresh one was admitted first: its queueing
    # wait is the prefill it didn't stand behind
    assert all(not r.failed for r in done)
    assert by_rid[1].done_s < by_rid[0].done_s
    s = summarize(done, mk, ttft_slo_ms=sconf.ttft_slo_ms)
    assert "goodput_rps" in s and s["completed"] == 2
