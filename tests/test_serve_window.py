"""The GPT-2 block's sliding-window layers, stated by the model
(``ModelConfig.layer_types`` + ``sliding_window``): rings of O(1)
per-slot memory (mxnet_tpu/serve/, docs/serving.md "Windowed layers").
Covers the model's validation, windowed decode against the windowed
reference oracle across kv_quant modes, the ring gather's
position-labeled rotation at the ops level (fp32 and bf16), speculative
verify with lengths-only ring rollback, watermark preempt/resume vs a
never-evicted oracle, the ``kv_window`` chaos site, prefix-cache
opt-out, and the frozen executable contract."""
import dataclasses

import numpy as np
import pytest

from mxnet_tpu import serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serve import model as serve_model
from mxnet_tpu.serve.kv_cache import PagedKVCache
from mxnet_tpu.serve.scheduler import Request, Scheduler
from mxnet_tpu.testing import faults

from closeness import LIMIT_SPACINGS, assert_close_across_executables
from serve_util import lend, reference_row, worst_gap_vs_reference

CFG = serve.ModelConfig(vocab_size=61, num_layers=3, d_model=32,
                        num_heads=2, max_len=256)
PAGE = 8
WINDOW = 8
# the stack every session here serves: stated, as a model states it
HYBRID = dataclasses.replace(
    CFG, sliding_window=WINDOW,
    layer_types=("full_attention", "sliding_attention", "sliding_attention"))


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
def _hybrid_session(params):
    sconf = serve.ServeConfig(slots=3, page_size=PAGE, buckets=(16, 32),
                              max_new=8, exact=True)
    return serve.InferenceSession(params, model=HYBRID, config=sconf)


@pytest.fixture
def hybrid_session(_hybrid_session):
    yield from lend(_hybrid_session)


def _greedy_oracle(sess, prompt, max_new):
    seq = list(prompt)
    out = []
    for _ in range(max_new):
        tok = int(np.argmax(reference_row(sess, seq)))
        out.append(tok)
        seq.append(tok)
    return out


def _trace(n, seed, prompt_len=8, max_new=6):
    rs = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rs.randint(1, CFG.vocab_size,
                                      size=prompt_len).tolist(),
                    max_new=max_new, arrival_s=0.0)
            for i in range(n)]


# ---------------------------------------------------------------------------
# config + cache bookkeeping
# ---------------------------------------------------------------------------

def test_the_model_states_its_layers():
    """What ``ServeConfig.layers`` / ``.window`` used to say, the model
    says: the kinds the cache builds, the ring's size (the block's rule,
    asked with the ``ServeConfig``), the guard's tag."""
    assert HYBRID.validate() is HYBRID
    assert HYBRID.kinds == ("full", "window", "window") and HYBRID.hybrid
    assert serve_model.guard_tag(HYBRID) == "-w%dfww" % WINDOW
    # ring bound: ceil((window + span - 1)/page) + 1 with span = the
    # largest bucket (the biggest burst written before any read) ...
    sconf = serve.ServeConfig(page_size=PAGE, buckets=(16, 32), max_new=8)
    assert serve_model.ring_pages(HYBRID, sconf) \
        == (WINDOW + 32 - 1 + PAGE - 1) // PAGE + 1
    # ... or the speculative window, where that is the larger burst
    assert serve_model.ring_pages(HYBRID, serve.ServeConfig(
        page_size=PAGE, buckets=(8,), spec_k=11)) \
        == (WINDOW + 12 - 1 + PAGE - 1) // PAGE + 1
    # a stack stated all full is the classic stack: same guards, no ring
    full = dataclasses.replace(HYBRID, layer_types=("full_attention",) * 3)
    assert full.validate().kinds == CFG.kinds and not full.hybrid
    assert serve_model.guard_tag(full) == serve_model.guard_tag(CFG) == ""
    # nothing is cycled over the depth: the model states every layer
    assert serve_model.config_from_params(
        serve_model.init_params(CFG, seed=0), CFG.num_heads) == CFG


@pytest.mark.parametrize("over, match", [
    (dict(layer_types=("full_attention", "mamba", "full_attention")),
     "not \\['mamba'\\]"),
    (dict(layer_types=("kda", "mla", "kda")), "not \\['kda', 'mla'\\]"),
    (dict(layer_types=("full", "window", "ssm")), "full_attention and"),
    (dict(layer_types=("full_attention", "sliding_attention"),
          sliding_window=WINDOW), "does not cover 3 layers"),
    (dict(layer_types=("full_attention",) * 2 + ("sliding_attention",)),
     "need sliding_window >= 1"),
], ids=["mamba", "kda_mla", "the_caches_words", "short", "no_window"])
def test_the_block_refuses_layers_it_does_not_run(over, match):
    """The GPT-2 block runs ``full_attention`` and ``sliding_attention``
    layers: another block's layer type, the cache's own words, a tuple
    that does not cover the depth and a windowed layer without a window
    are refused by ``validate``, and so by the session."""
    with pytest.raises(MXNetError, match=match):
        dataclasses.replace(CFG, **over).validate()
    with pytest.raises(MXNetError, match=match):
        serve.InferenceSession(
            {}, model=dataclasses.replace(CFG, **over),
            config=serve.ServeConfig(page_size=PAGE, buckets=(16,)))


def test_ring_cache_bookkeeping():
    cache = PagedKVCache(num_layers=3, num_heads=2, head_dim=4,
                         page_size=8, num_pages=4, slots=2,
                         max_pages_per_slot=2,
                         layer_kinds=("full", "window", "ssm"),
                         window=8, ring_pages=3,
                         state={"ssm_state": (1, (2, 4, 4), "float32")})
    assert (cache.n_full, cache.n_window, cache.n_ssm) == (1, 1, 1)
    assert cache.hybrid
    # pools only carry FULL layers; rings and state live beside them
    pools = cache.pools
    assert pools["k_pool"].shape[0] == 1
    # two heads of 4 fold, in a ring as in the pages
    assert pools["kw_pool"].shape == (1, 2, 24, 8)
    assert pools["ssm_state"].shape == (1, 2, 2, 4, 4)
    assert cache.pool_bytes() > 2 * pools["k_pool"].nbytes
    # alloc re-zeroes the slot's recurrence state (rings need no zeroing:
    # stale rows carry out-of-window position labels and mask out)
    import jax.numpy as jnp
    pools["ssm_state"] = jnp.ones_like(pools["ssm_state"])
    slot = cache.alloc(5, 8)
    assert float(jnp.abs(pools["ssm_state"][:, slot]).max()) == 0.0

    # a stack with NO full layers needs no pages at all: admission is
    # bounded by slots alone (the O(1)-per-slot capacity story)
    nofull = PagedKVCache(num_layers=2, num_heads=2, head_dim=4,
                          page_size=8, num_pages=1, slots=3,
                          max_pages_per_slot=1,
                          layer_kinds=("window", "ssm"),
                          window=8, ring_pages=2)
    assert nofull.pages_needed(8, 8) == 0
    slots = [nofull.alloc(8, 8) for _ in range(3)]
    assert all(s is not None for s in slots)
    assert nofull.free_slots == 0


# ---------------------------------------------------------------------------
# bit-exactness: windowed decode vs the windowed reference oracle
# ---------------------------------------------------------------------------

def _hybrid(params, kv_quant):
    sconf = serve.ServeConfig(slots=2, page_size=PAGE, buckets=(16, 32),
                              max_new=16, exact=True, kv_quant=kv_quant)
    return serve.InferenceSession(params, model=HYBRID, config=sconf)


def _prompt13():
    return [np.random.RandomState(7).randint(
        1, CFG.vocab_size, size=13).tolist()]


@pytest.mark.parametrize("kv_quant", ["", "int8", "e4m3"])
def test_hybrid_decode_bitexact_vs_reference(params, kv_quant):
    """Prefill + decode through a full x window x window stack reproduces
    the full-context hybrid reference forward — logits, not just argmax
    — including steps where the window slides past the prompt and the
    ring wraps, at every KV storage precision.  Three executables:
    sound rows read at most 3.5 spacings apart over 5 parameter seeds
    (jax 0.9.0)."""
    # six steps cross position 16: the window slides, the ring wraps
    assert worst_gap_vs_reference(_hybrid(params, kv_quant), _prompt13(),
                                  steps=6) <= LIMIT_SPACINGS


def _stale_ring_row(sess, slots):
    """The ring's smallest fault: the newest row of one slot's window
    ring still holds what the row before it holds."""
    import jax.numpy as jnp

    cache = sess.cache
    row = (int(cache.lengths[slots[0]]) - 1) % cache.ring_tokens
    ring = np.array(cache.pools["kw_pool"])
    ring[:, slots[0], row] = ring[:, slots[0], row - 1]
    cache.pools["kw_pool"] = jnp.asarray(ring, ring.dtype)


@pytest.mark.parametrize("kv_quant", ["", "int8"])
def test_hybrid_comparison_sees_planted_fault(params, kv_quant):
    """The control of the comparison above: one stale ring row reads
    12 346 spacings (fp32 pages) and 20 985 (int8) where the limit is
    32; 12 780 or more over 5 parameter seeds at every precision (the
    stack stated full, window, window)."""
    assert worst_gap_vs_reference(
        _hybrid(params, kv_quant), _prompt13(), steps=6,
        plant=_stale_ring_row) > 30 * LIMIT_SPACINGS


def test_hybrid_cobatched_equals_solo(hybrid_session):
    """Co-batched strangers must not perturb a windowed stream: rings
    are slot-private and the kernels are M-invariant."""
    sess = hybrid_session
    rs = np.random.RandomState(12)
    p = rs.randint(1, CFG.vocab_size, size=9).tolist()

    def run(neighbors):
        slot = sess.try_alloc(len(p), 6)
        first, _ = sess.prefill(slot, p)
        others = []
        for q in neighbors:
            s = sess.try_alloc(len(q), 6)
            sess.prefill(s, q)
            others.append(s)
        out = [first]
        for _ in range(5):
            toks, _ = sess.step()
            out.append(toks[slot])
        sess.reset_cold()
        return out

    solo = run([])
    crowd = run([rs.randint(1, CFG.vocab_size, size=14).tolist(),
                 rs.randint(1, CFG.vocab_size, size=6).tolist()])
    assert solo == crowd


def test_no_full_layers_session_decodes_and_admits_by_slots(params):
    """An all-window stack reserves zero pool pages — every slot
    admits regardless of context length — and still decodes on the
    reference (tests/closeness.py: sound rows at most 4 spacings off)."""
    sconf = serve.ServeConfig(slots=3, page_size=PAGE, buckets=(16,),
                              max_new=8, exact=True)
    sess = serve.InferenceSession(
        params, config=sconf, model=dataclasses.replace(
            HYBRID, layer_types=("sliding_attention",) * 3))
    assert sess.cache.pages_needed(16, 8) == 0
    rs = np.random.RandomState(9)
    prompts = [rs.randint(1, CFG.vocab_size, size=11).tolist()
               for _ in range(3)]
    # all three admit (the walker asserts it): slot-bounded only
    assert worst_gap_vs_reference(sess, prompts, steps=4) <= LIMIT_SPACINGS


# ---------------------------------------------------------------------------
# ops level: windowed kernels and the ring-gather contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_windowed_decode_matches_flash_last_row(dtype):
    """One windowed decode step over a contiguous context equals the
    last row of the windowed flash forward (both built from the same
    M-invariant attend_block, same block geometry; two executables)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as A

    S, H, T, D, B = 2, 2, 24, 16, 8
    rs = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rs.randn(S, H, T, D), dtype)
               for _ in range(3))
    full = jax.jit(lambda a, b, c: A.flash_attention(
        a, b, c, causal=True, block=B, mi=True, window=WINDOW))(q, k, v)
    dec = jax.jit(lambda a, b, c: A.decode_attention(
        a, b, c, jnp.full((S,), T, jnp.int32), block=B, mi=True,
        window=WINDOW))(q[:, :, -1:, :], k, v)
    assert_close_across_executables(np.asarray(dec[:, :, 0], "float32"),
                                    np.asarray(full[:, :, -1], "float32"),
                                    dtype=dtype)


def test_ring_rotation_with_position_labels_is_exact():
    """The windowed ring contract at the ops level: rotating the context
    page-granularly (what the ring gather produces) and labeling every
    row with its absolute position gives the SAME output as the
    contiguous layout — wrapped/stale rows mask out exactly."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as A

    S, H, T, D, B = 2, 2, 24, 16, 8
    rs = np.random.RandomState(4)
    q1 = jnp.asarray(rs.randn(S, H, 1, D), jnp.float32)
    k, v = (jnp.asarray(rs.randn(S, H, T, D), jnp.float32)
            for _ in range(2))
    lengths = jnp.full((S,), T, jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (S, T))
    f = jax.jit(lambda kk, vv, pp: A.decode_attention(
        q1, kk, vv, lengths, block=B, mi=True, window=WINDOW,
        k_positions=pp))
    base = f(k, v, pos)
    for shift_pages in (1, 2):
        r = shift_pages * B
        rot = f(jnp.roll(k, r, axis=2), jnp.roll(v, r, axis=2),
                jnp.roll(pos, r, axis=1))
        # one executable, but the blocks reach the running softmax in
        # another order
        assert_close_across_executables(np.asarray(rot), np.asarray(base))
    # garbage rows beyond the window (position labels < T - WINDOW)
    # must be exact no-ops, not merely small contributions
    k_bad = k.at[:, :, : T - WINDOW].set(1e6)
    v_bad = v.at[:, :, : T - WINDOW].set(-1e6)
    np.testing.assert_array_equal(np.asarray(f(k_bad, v_bad, pos)),
                                  np.asarray(base))


# ---------------------------------------------------------------------------
# speculative decoding on windowed stacks: exact verify, O(1) rollback
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("draft", ["ngram", "layers:2"])
def test_hybrid_spec_decode_matches_oracle(params, draft):
    """Speculation over a windowed stack commits EXACTLY the serial
    greedy stream: rings roll back to the commit point lengths-only.
    ``layers:2`` inherits the target's full,window prefix (and its
    window) as the draft stack."""
    sconf = serve.ServeConfig(slots=2, page_size=PAGE, buckets=(16, 32),
                              max_new=16, exact=True, spec_k=3,
                              draft=draft)
    sess = serve.InferenceSession(params, model=HYBRID, config=sconf)
    if draft == "layers:2":
        assert sess.draft_model.layer_types == HYBRID.layer_types[:2]
        assert sess.draft_model.sliding_window == WINDOW
        assert sess.draft_cache.ring_pages == sess.cache.ring_pages
    rs = np.random.RandomState(7)
    prompt = rs.randint(1, CFG.vocab_size, size=13).tolist()
    oracle = _greedy_oracle(sess, prompt, 10)
    slot = sess.try_alloc(len(prompt), 16)
    first, _ = sess.prefill(slot, prompt)
    got = [first]
    while len(got) < 10:
        out = sess.spec_step()
        got.extend(out[slot])
    assert got[:10] == oracle
    stats = sess.spec_report()
    assert stats["verify_steps"] > 0
    assert stats["committed"] == len(got) - 1  # prefill emitted got[0]


# ---------------------------------------------------------------------------
# preempt/resume, prefix opt-out, chaos, frozen executables
# ---------------------------------------------------------------------------

def test_hybrid_preempt_resume_bitexact_vs_never_evicted(params):
    """Watermark preemption on a hybrid stack: eviction releases only
    the full layers' pages; resume re-prefills through the SAME hybrid
    executables, rebuilding the rings deterministically — every
    resumed stream equals the never-evicted greedy oracle."""
    sconf = serve.ServeConfig(slots=3, page_size=PAGE, buckets=(8, 16),
                              max_new=8, exact=True, num_pages=5,
                              oversub=True, prefix_pages=-1)
    sess = serve.InferenceSession(params, model=HYBRID, config=sconf)
    reqs = _trace(3, seed=23, prompt_len=8, max_new=6)
    oracle = {r.rid: _greedy_oracle(sess, r.prompt, r.max_new)
              for r in reqs}
    sched = Scheduler(sess, policy="continuous")
    done, _ = sched.run(reqs)
    assert sched.stats["preemptions"] > 0
    assert sched.stats["resumes"] == sched.stats["preemptions"]
    for r in done:
        assert not r.failed, r.error
        assert r.tokens == oracle[r.rid]
    assert sess.cache.free_slots == sess.config.slots


def test_hybrid_prefix_cache_opts_out(params):
    """Rings are slot-private, so no window-aligned boundary except
    offset 0 is reconstructible from published pages: hybrid sessions
    neither publish nor hit — and still decode the exact oracle
    streams."""
    sconf = serve.ServeConfig(slots=2, page_size=PAGE, buckets=(16,),
                              max_new=8, exact=True, prefix_pages=-1)
    sess = serve.InferenceSession(params, model=HYBRID, config=sconf)
    prompt = list(range(1, 17))  # two full pages: would hit if published
    for _ in range(2):  # identical prompts back-to-back
        oracle = _greedy_oracle(sess, prompt, 4)
        slot = sess.try_alloc(len(prompt), 4, tokens=prompt)
        first, _ = sess.prefill(slot, prompt)
        got = [first]
        for _ in range(3):
            toks, _ = sess.step()
            got.append(toks[slot])
        assert got == oracle
        sess.release(slot)
    assert sess.cache.prefix_stats["hits"] == 0
    assert sess.cache.prefix_stats["published_pages"] == 0


@pytest.mark.chaos
def test_chaos_kv_window_fault_isolates_request(params, monkeypatch):
    """A raise at the hybrid prefill boundary (before any ring row is
    written) fails only the request whose prefill crossed it; survivors'
    rings stay coherent — their streams match a clean run — and the slot
    pool drains back to full."""
    monkeypatch.setenv("MXNET_FAULT_INJECT", "kv_window:raise:after=2")
    faults.reset()
    sconf = serve.ServeConfig(slots=3, page_size=PAGE, buckets=(8, 16),
                              max_new=8, exact=True)
    sess = serve.InferenceSession(params, model=HYBRID, config=sconf)
    reqs = _trace(3, seed=21, max_new=4)
    done, _ = Scheduler(sess, policy="continuous").run(reqs)
    failed = [r for r in done if r.failed]
    ok = [r for r in done if not r.failed]
    assert len(failed) == 1 and "FaultInjected" in failed[0].error
    assert len(ok) == 2
    assert all(len(r.tokens) == 4 for r in ok)
    assert sess.cache.free_slots == sess.config.slots

    monkeypatch.delenv("MXNET_FAULT_INJECT")
    faults.reset()
    clean = serve.InferenceSession(params, model=HYBRID, config=sconf)
    cdone, _ = Scheduler(clean, policy="continuous").run(
        _trace(3, seed=21, max_new=4))
    want = {r.rid: list(r.tokens) for r in cdone}
    for r in ok:
        assert list(r.tokens) == want[r.rid]


def test_hybrid_executables_frozen_and_guard_tagged(hybrid_session,
                                                    monkeypatch):
    """Windowed layers change executable ARGUMENTS (ring pools, the
    prefill slot scalar), never the executable set: a full load under
    MXNET_RECOMPILE_ERROR=1 completes with len(buckets) + 1 executables
    and one trace each, and the recompile-guard namespace carries the
    window/kind tag so hybrid and classic sessions never alias."""
    session = hybrid_session
    assert session._guard_prefix.endswith("-w%dfww" % WINDOW)
    monkeypatch.setenv("MXNET_RECOMPILE_ERROR", "1")
    rs = np.random.RandomState(13)
    reqs = [Request(rid=i,
                    prompt=rs.randint(1, CFG.vocab_size,
                                      size=3 + 2 * i).tolist(),
                    max_new=5, arrival_s=0.002 * i)
            for i in range(6)]
    done, _ = Scheduler(session, policy="continuous").run(reqs)
    assert all(r.done_s >= 0 and not r.failed for r in done)
    assert sorted(session.executables) == \
        ["decode", "prefill_16", "prefill_32"]
    for name, snap in session.guard_report().items():
        assert snap["traces"] == 1, (name, snap)
        assert snap["signatures"] == 1, (name, snap)
    assert session.fallback_count() == 0
