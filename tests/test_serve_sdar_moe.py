"""The SDAR-MoE block in the serving runtime (``serve/sdar_moe.py``:
generation by diffusion over blocks of 4, QK-normed grouped-query layers
on K/V pages whose rows see both ways inside a block, softmax-routed
experts of which a share is held, an untied head), held to the plain
reference the benchmark keeps, ``benchmark/references/sdar_moe_lm.py``,
loaded from its path: its block-causal forward, its forward of one pass
over one block, and its loop, which keeps no cache.  Toy widths, seeded
weights drawn at 0.3 (at 0.02 a toy model's confidences all lie at 1 / 97
and which row is the most confident is a matter of rounding), logits
compared.

Tolerances, each with its reason:

* ``LIMIT_SPACINGS`` (tests/closeness.py, 32 float32 spacings at the
  row's largest logit) wherever two programs compute the same sums in
  another order: the session's executables against the reference, the
  reference's form that takes the keys before a block from one forward
  against the one that computes them again.  tests/conftest.py sets
  full-precision matmuls, so what is left is float32 rounding; a commit
  that keeps a denoise pass's K/V, a causal mask inside a block and a
  logits row taken as the next token's read in the tens of thousands and
  more (``test_the_comparison_can_fail``).
* The share test adds eight partial results in another order than the
  uncut layer's loop over its experts: 1e-5 of the largest value.
* Scheduler runs return tokens and the pass each was unmasked in, and are
  held to the reference's loop exactly: the same tokens, the same passes.
  Two confidences of one block closer than rounding would flip which is
  unmasked first; at these seeds none is.
"""
import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serve import latent_moe, sdar_moe
from mxnet_tpu.serve import model as serve_model
from mxnet_tpu.serve.scheduler import Request, Scheduler
from mxnet_tpu.serve.session import NO_TOKEN

from closeness import (LIMIT_SPACINGS, assert_close_across_executables,
                       spacings_apart)
from serve_util import lend

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "references", "sdar_moe_lm.py")
_spec = importlib.util.spec_from_file_location("sdar_moe_lm_reference", _REF)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

PAGE, B, MASK = 4, 4, 96
# the reference's configuration: the published config.json's keys, and
# the generation's; experts 4-7 of 16 are held, the mask token's row is
# the vocabulary's last
HF = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, moe_intermediate_size=32, router_experts=16,
          num_experts=4, experts_first=4, num_experts_per_tok=4,
          norm_topk_prob=True, vocab_size=97, num_hidden_layers=2,
          rms_norm_eps=1e-6, rope_theta=1000000,
          max_position_embeddings=256, block_length=B, mask_token_id=MASK,
          denoising_steps=4, confidence_threshold=0.9)
UNCUT = dict(HF, num_experts=16, experts_first=0)


def model_config(hf):
    first, count, routed = reference.held(hf)
    return serve.ModelConfig(
        block="sdar_moe", vocab_size=hf["vocab_size"],
        num_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        num_heads=hf["num_attention_heads"],
        num_key_value_heads=hf["num_key_value_heads"],
        max_len=hf["max_position_embeddings"], attn_head_dim=hf["head_dim"],
        rope_theta=float(hf["rope_theta"]), rms_norm_eps=hf["rms_norm_eps"],
        moe_d_ff=hf["moe_intermediate_size"], n_routed_experts=routed,
        num_experts_per_tok=hf["num_experts_per_tok"],
        norm_topk_prob=hf["norm_topk_prob"], scoring_func="softmax",
        experts_held=(first, count) if count < routed else (),
        block_length=hf["block_length"], mask_token_id=hf["mask_token_id"],
        denoising_steps=hf["denoising_steps"],
        confidence_threshold=hf["confidence_threshold"])


CFG = model_config(HF)
CONF = dict(slots=3, page_size=PAGE, buckets=(8, 16), max_new=16,
            max_prompt=64, exact=False)


def tokens(seed, n):
    return np.random.default_rng(seed).integers(0, MASK, n).tolist()


@pytest.fixture(scope="module")
def params():
    return serve_model.init_params(CFG, seed=3, scale=0.3)


def session(params, cfg=CFG, **over):
    return serve.InferenceSession(
        params, model=cfg, config=serve.ServeConfig(**dict(CONF, **over)))


@pytest.fixture(scope="module")
def _plain(params):
    return session(params)


@pytest.fixture
def plain(_plain):
    yield from lend(_plain)


# -- the reference, compiled once a shape -----------------------------------

_MODEL_KEYS = sorted(set(HF) - {"denoising_steps", "confidence_threshold"})
_DENOISE_LOGITS = reference.denoise_logits      # ``compiled_reference`` below


@functools.lru_cache(maxsize=None)
def _jitted_logits(n):
    return jax.jit(lambda params, seq: reference.logits(params, seq, HF))


def ref_logits(params, seq):
    """The reference's (len(seq), vocab) logits.  One compilation: the
    sequence is padded to 64 tokens in whole blocks, which the rows in
    front of them cannot see."""
    padded = jnp.asarray(list(seq) + [0] * (64 - len(seq)), jnp.int32)
    return np.asarray(_jitted_logits(64)(params, padded))[:len(seq)]


@functools.lru_cache(maxsize=None)
def _jitted_forward(exact):
    return jax.jit(lambda params, seq: serve_model.full_forward(
        params, seq[None], CFG, exact=exact)[0])


def block_forward(params, seq, exact=False):
    """The block's ``full_forward`` of one sequence, compiled."""
    return np.asarray(_jitted_forward(exact)(params,
                                             jnp.asarray(seq, jnp.int32)))


@functools.lru_cache(maxsize=None)
def _jitted_denoise(block):
    """The reference's forward of one pass, which computes everything up
    to the block's end again: one compilation a block's place."""
    return jax.jit(lambda params, seq, visible: _DENOISE_LOGITS(
        params, seq, HF, block, visible))


def ref_denoise(params, seq, hf, block, visible, context=None, cast=None):
    """``reference.denoise_logits`` as the reference's own loop calls it
    (the model's keys of ``hf`` are ``HF``'s), compiled."""
    assert context is None and cast is None
    assert [hf[k] for k in _MODEL_KEYS] == [HF[k] for k in _MODEL_KEYS]
    seq = jnp.asarray(seq, jnp.int32)[:(block + 1) * B]
    return _jitted_denoise(int(block))(params, seq, jnp.asarray(visible))


@pytest.fixture
def compiled_reference(monkeypatch):
    monkeypatch.setattr(reference, "denoise_logits", ref_denoise)


# -- the block against the reference ----------------------------------------

def test_params_are_the_references_spec(params):
    """Name for name and shape for shape: an untied head, no shared
    expert, no bias beside a softmax router."""
    assert {k: tuple(v.shape) for k, v in params.items()} \
        == {k: tuple(v) for k, v in reference.spec(HF).items()}
    assert params["lm_head_weight"].shape == (97, 64)
    assert not [k for k in params if "shared" in k or "router_bias" in k]
    assert params["blk1_q_norm_gamma"].shape == (16,)
    assert params["blk1_experts_up_weight"].shape == (4, 32, 64)
    sdar_moe.check_params(params, CFG)
    assert CFG.kinds == ("full", "full") and not CFG.hybrid
    assert sdar_moe.state_shapes(CFG) == {} and sdar_moe.latent_dim(CFG) == 0
    with pytest.raises(MXNetError, match="the architecture says"):
        sdar_moe.check_params(params, dataclasses.replace(
            CFG, attn_head_dim=32))


@pytest.mark.parametrize("over, match", [
    (dict(block_length=0), "block_length"),
    (dict(denoising_steps=5), "denoising_steps 5 outside"),
    (dict(denoising_steps=0), "denoising_steps 0 outside"),
    (dict(mask_token_id=97), "mask_token_id 97 outside"),
    (dict(scoring_func="sigmoid"), "softmax scores"),
    (dict(tie_word_embeddings=True), "untied head"),
    (dict(num_key_value_heads=3), "4 query heads over 3"),
])
def test_what_the_block_cannot_be_is_refused(over, match):
    with pytest.raises(MXNetError, match=match):
        dataclasses.replace(CFG, **over).validate()


@pytest.mark.parametrize("exact, seed, n", [(False, 0, 40), (True, 1, 40),
                                            (False, 2, 37), (False, 3, 3)])
def test_full_forward_matches_reference(params, exact, seed, n):
    """(a) the block-causal forward, row p over the token at p; a sequence
    that ends inside a block (37 = 9 blocks and one row) sees the keys
    there are."""
    seq = tokens(seed, n)
    want = ref_logits(params, seq) if n % B == 0 else np.asarray(
        _jitted_logits(n)(params, jnp.asarray(seq, jnp.int32)))
    assert_close_across_executables(block_forward(params, seq, exact), want)


def test_a_block_sees_both_ways_and_nothing_past_itself(params):
    """Changing the LAST row of a block changes the logits of its first
    (both ways inside), and of no row of an earlier block (causal from
    block to block)."""
    seq = tokens(5, 24)
    other = list(seq)
    other[15] = (seq[15] + 1) % MASK
    a, b = ref_logits(params, seq), ref_logits(params, other)
    np.testing.assert_array_equal(a[:12], b[:12])
    assert np.abs(a[12] - b[12]).max() > 1e-3
    got = [block_forward(params, s) for s in (seq, other)]
    np.testing.assert_array_equal(got[0][:12], got[1][:12])
    assert np.abs(got[0][12] - got[1][12]).max() > 1e-3


@pytest.mark.parametrize("block", [0, 1, 4])
def test_the_references_two_forms_agree(params, block):
    """``denoise_logits`` with the keys and values in front of the block
    taken from ONE forward of the final tokens (what the chip's comparison
    runs) is ``denoise_logits`` that computes them again, whatever rows of
    the block are visible; the block may be traced."""
    seq = jnp.asarray(tokens(6, 24), jnp.int32)
    context = jax.jit(lambda p, s: reference.context(p, s, HF))(params, seq)
    cached = jax.jit(lambda p, s, blk, vis: reference.denoise_logits(
        p, s, HF, blk, vis, context))
    for visible in ([False] * 4, [True, False, False, True], [True] * 4):
        visible = jnp.asarray(visible)
        want = np.asarray(ref_denoise(params, seq, HF, block, visible))
        got = np.asarray(cached(params, seq, jnp.int32(block), visible))
        assert_close_across_executables(got, want)
    # and with every row visible it is the forward's own rows
    assert_close_across_executables(
        want, ref_logits(params, seq.tolist())[block * B:block * B + B])


def _serve_blocks(sess, prompt, blocks, commit=True):
    """Prefill ``prompt`` into a free slot and step until ``blocks`` blocks
    are committed -> (slot, the whole sequence, [(block index, which rows
    were visible to the pass, the pass's logits (B, V), the confidences of
    the rows it unmasked)] for every pass).
    ``commit=False`` plants a fault: a block that has lost its last mask
    is committed on the host at once, WITHOUT its commit pass, so the
    pages keep the K/V its last denoise pass wrote."""
    slot = sess.try_alloc(len(prompt), 16, tokens=prompt)
    assert sess.prefill(slot, prompt) == (NO_TOKEN, None)
    seq, passes, pending = list(prompt), [], []
    known = len(prompt) % B
    block = len(prompt) // B
    while blocks:
        out, logits = sess.step()
        pending.append(np.asarray(logits)[slot])
        blk = sess._slot_tokens[slot]
        if not commit and not out[slot] and MASK not in blk.tokens:
            # the planted fault: what step() does for a commit, unrun
            sess.cache.lengths[slot] += B
            out[slot] = [(blk.tokens[r], blk.at[r], blk.conf[r])
                         for r in range(blk.known, B)]
            sess._slot_tokens[slot] = type(blk)((), MASK, B, blk.budget)
            pending.append(None)
        if not out[slot]:
            continue
        toks, at, conf = zip(*out[slot])
        assert len(toks) == B - known
        seq += toks
        at = (-1,) * known + at
        conf = (0.0,) * known + conf
        for i, rows in enumerate(pending):
            if rows is not None:
                passes.append((block, [a < i for a in at], rows,
                               [c for a, c in zip(at, conf) if a == i]))
        pending, known, block, blocks = [], 0, block + 1, blocks - 1
    return slot, seq, passes


@pytest.mark.parametrize("n, chunks", [(16, 1), (21, 2), (22, 2), (23, 2),
                                       (24, 2), (3, 0), (43, 3)])
def test_prefill_then_block_passes_through_the_pages(params, plain, n,
                                                     chunks):
    """(b) a prompt's whole blocks prefilled in one chunk or several (21:
    a chunk of 16 and one whose real rows are 4), the P % B tokens left
    opening the first block (0, 1, 2, 3; a prompt of 3 has no whole block
    and no prefill at all), then passes through the pages: the logits of
    EVERY pass, denoise and commit, are the reference's forward of that
    pass with exactly the rows visible that were visible to it."""
    prompt = tokens(20 + n, n)
    before = plain.block_report()
    slot, seq, passes = _serve_blocks(plain, prompt, 3)
    after = plain.block_report()
    assert after["prefill_chunks"] - before["prefill_chunks"] == chunks
    assert after["blocks_committed"] - before["blocks_committed"] == 3
    assert after["tokens_committed"] - before["tokens_committed"] \
        == 3 * B - n % B == len(seq) - n
    # at 0.9 a row a pass (the quota's, or the one row that cleared the
    # threshold), then the commit
    assert len(passes) == 3 * (B + 1) - n % B
    assert sum(after[k] - before[k] for k in (
        "rows_unmasked_by_quota", "rows_unmasked_by_threshold")) \
        == len(seq) - n
    assert int(plain.cache.lengths[slot]) == len(seq)
    for block, visible, rows, conf in passes:
        want = ref_denoise(params, seq, HF, block, visible)
        assert_close_across_executables(
            rows, np.asarray(want),
            err_msg="block %d, visible %r" % (block, visible))
        # the confidence a row was unmasked with is the reference's of
        # that row in that pass: the largest among the rows still masked
        # (quota only at 0.9, or the one row over it)
        if conf:
            _, c = reference.confidence(want, HF)
            masked = np.asarray(c)[~np.asarray(visible)]
            np.testing.assert_allclose(conf, [masked.max()], rtol=1e-4)
    assert plain.fallback_count() == 0


# -- the loop, through the scheduler ----------------------------------------

def _requests(sizes, seed=0, **more):
    return [Request(rid=i, prompt=tokens(seed + i, p), max_new=m,
                    arrival_s=0.0, **more) for i, (p, m) in enumerate(sizes)]


def _worst_passes(prompt, max_new, steps):
    """Passes a request takes where no row clears the threshold."""
    total, known = 0, prompt % B
    while max_new > 0:
        rows = passes = 0
        while rows < B - known:
            rows += B // steps + (passes < B % steps)
            passes += 1
        total += passes + 1
        max_new, known = max_new - (B - known), 0
    return total


LOOPS = {"all_at_once": (0.0, 4), "quota_only": (0.9, 4),
         "some_rows_clear_it": (0.4, 4), "two_passes": (0.9, 2),
         "two_passes_some_clear_it": (0.4, 2), "one_pass": (0.9, 1)}


@pytest.mark.parametrize("name", sorted(LOOPS))
def test_scheduler_run_is_the_references_loop(params, compiled_reference,
                                              name):
    """(c) ``Scheduler.run`` over five requests, more than the slots, whose
    prompts end at every place in a block and whose lengths are no
    multiples of it: the same tokens AND the same pass of unmasking as the
    reference's loop without a cache; at threshold 0 every row clears it
    in the first pass, at 0.9 none does and the quota decides, at 0.4 some
    do."""
    threshold, steps = LOOPS[name]
    hf = dict(HF, confidence_threshold=threshold, denoising_steps=steps)
    sess = session(params, model_config(hf))
    reqs = _requests([(3, 5), (8, 7), (13, 9), (18, 11), (23, 16)])
    done, _ = Scheduler(sess).run(reqs)
    assert not any(r.failed for r in done), [r.error for r in done]
    seen = set()
    for r in done:
        want, at = reference.generate(params, r.prompt, r.max_new, hf)
        assert (list(r.tokens), list(r.passes)) == (want, at)
        assert len(r.tokens) == r.max_new and r.ttft_s > 0
        assert len(r.confidences) == r.max_new
        assert (np.asarray(r.confidences) > threshold).all() \
            or threshold > 0
        seen.update(at)
    rep = sess.block_report()
    assert rep["tokens_committed"] == sum(r.max_new for r in done)
    assert rep["slot_passes"] == rep["denoise_slot_passes"] \
        + rep["commit_slot_passes"]
    assert seen == {"all_at_once": {0}, "quota_only": {0, 1, 2, 3},
                    "some_rows_clear_it": {0, 1, 2, 3}, "two_passes": {0, 1},
                    "two_passes_some_clear_it": {0, 1}, "one_pass": {0}}[name]
    by = (rep["rows_unmasked_by_threshold"], rep["rows_unmasked_by_quota"])
    assert (by[0] > 0, by[1] > 0) == {
        "all_at_once": (True, False), "quota_only": (False, True),
        "some_rows_clear_it": (True, True), "two_passes": (False, True),
        "two_passes_some_clear_it": (True, True),
        "one_pass": (False, True)}[name]
    # between a denoise pass and the commit a block, and the quota's
    # passes and the commit
    blocks = sum(-(-(len(r.prompt) % B + r.max_new) // B) for r in done)
    worst = sum(_worst_passes(len(r.prompt), r.max_new, steps) for r in done)
    if threshold == 0.4:
        assert 2 * blocks < rep["slot_passes"] < worst
    else:
        assert rep["slot_passes"] == (2 * blocks if threshold == 0.0
                                      else worst)
    assert sess.fallback_count() == 0
    assert sorted(sess.executables) == ["block_pass", "prefill_16",
                                        "prefill_8"]


def test_slots_in_different_passes_of_different_blocks(params, plain,
                                                       compiled_reference):
    """(d) a second request admitted while the first is in the second pass
    of its block: from then on one call runs a denoise pass of one slot's
    block and the commit pass of the other's, which the step's span says,
    and both come out as the reference's loop gives them."""
    import time

    mx.profiler.record_spans(True)
    t0 = time.perf_counter()
    try:
        a = plain.try_alloc(9, 8)
        pa = tokens(40, 9)
        plain.prefill(a, pa)
        plain.step()
        plain.step()
        b = plain.try_alloc(6, 6)
        pb = tokens(41, 6)
        plain.prefill(b, pb)
        got = {a: [], b: []}
        for _ in range(14):
            out, _ = plain.step()
            for slot in got:
                got[slot] += out[slot]
        mixed = [s.attrs for s in mx.profiler.spans("session.step",
                                                    since=t0)]
    finally:
        mx.profiler.record_spans(False)
    assert any(s["denoise"] == 1 and s["commit"] == 1 for s in mixed)
    assert all(s["denoise"] + s["commit"] == s["live"] for s in mixed)
    for slot, prompt, n in ((a, pa, 8), (b, pb, 6)):
        want, at = reference.generate(params, prompt, n, HF)
        assert [t for t, _, _ in got[slot]][:n] == want
        assert [p for _, p, _ in got[slot]][:n] == at
        assert all(0 < c <= 1 for _, _, c in got[slot])


def test_the_chunks_of_a_long_prompt_say_which_they_are(plain):
    """A prompt of 30 tokens prefills its 28 whole-block rows in two
    chunks of the largest bucket, and each ``prefill.launch`` span carries
    its ``bucket`` beside the ``largest`` there is, as the autoregressive
    blocks' do (one loop: ``_prefill_chunks``)."""
    import time

    mx.profiler.record_spans(True)
    t0 = time.perf_counter()
    try:
        slot = plain.try_alloc(30, 4)
        plain.prefill(slot, tokens(42, 30))
        prefill, = mx.profiler.spans("session.prefill", since=t0)
        launches = [s.attrs for s in mx.profiler.spans("prefill.launch",
                                                       since=t0)]
    finally:
        mx.profiler.record_spans(False)
        mx.profiler.clear_spans()
    assert prefill.attrs == {"slot": slot, "prompt": 30, "cached": 0,
                             "bucket": 16, "chunks": 2}
    assert launches == [{"bucket": 16, "largest": 16}] * 2


def test_a_length_that_is_no_multiple_of_the_block(params, plain):
    """(d) the last block's tail is dropped: the request ends at its asked
    length, and the session counts the tokens it asked for."""
    before = plain.block_report()["tokens_committed"]
    done, _ = Scheduler(plain).run(_requests([(6, 5), (4, 1), (7, 16)]))
    assert [len(r.tokens) for r in done] == [5, 1, 16]
    assert [len(r.passes) for r in done] == [5, 1, 16]
    assert plain.block_report()["tokens_committed"] - before == 22


def test_eos_inside_a_block(params, plain, compiled_reference):
    """(d) a request stops at the first EOS it commits, the rest of that
    block dropped, as the reference's tokens cut there."""
    prompt = tokens(50, 10)
    want, _ = reference.generate(params, prompt, 12, HF)
    eos = want[4]               # the third row of the second block
    stop = want.index(eos) + 1
    req = Request(rid=0, prompt=prompt, max_new=12, arrival_s=0.0,
                  eos_id=eos)
    Scheduler(plain).run([req])
    assert list(req.tokens) == want[:stop] and not req.failed
    assert (len(prompt) + stop) % B         # the stop lies inside a block
    assert plain.active_slots() == []


def test_a_released_slot_is_reused(params, compiled_reference):
    """(d) one slot, three requests one after the other: what an earlier
    request left in the slot's pages and open block does not reach the
    next."""
    sess = session(params, slots=1)
    reqs = _requests([(11, 7), (5, 9), (18, 6)], seed=60)
    done, _ = Scheduler(sess).run(reqs)
    for r in done:
        want, at = reference.generate(params, r.prompt, r.max_new, HF)
        assert (list(r.tokens), list(r.passes)) == (want, at)
    assert sess.cache.free_pages == sess.cache.num_pages


def test_no_request_is_resumed(params, plain):
    """A request that holds committed tokens cannot be handed to a
    diffusion session's scheduler: refused by name, not replayed wrong."""
    req = Request(rid=7, prompt=tokens(1, 6), max_new=8, arrival_s=0.0)
    req.tokens = [1, 2, 3]
    sched = Scheduler(plain).begin([])
    with pytest.raises(MXNetError, match="cannot resume"):
        sched.submit(req, parked=True)


# -- the share ----------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """(e) the routed parts that all eight shares compute are the uncut
    reference's layer (softmax over all 16, 4 taken, weights over what was
    taken; no shared expert to count once); each share is the reference's
    own share; an assignment is computed by exactly one."""
    cfg = model_config(UNCUT)
    shapes = {k: v for k, v in sdar_moe.param_shapes(cfg).items()
              if k.startswith("blk1_") and ("router" in k or "expert" in k)}
    rs = np.random.RandomState(7)
    p = {k: jnp.asarray((0.3 * rs.randn(*s)).astype(np.float32))
         for k, s in sorted(shapes.items())}
    u = latent_moe.rms_norm(jnp.asarray(
        rs.randn(40, 64).astype(np.float32)), jnp.ones((64,)), 1e-6)
    want = np.asarray(reference.routed(u, p, "blk1_", UNCUT))
    taken, w = latent_moe._route(u, p, "blk1_", cfg)
    weights = np.zeros((40, 16), np.float32)
    np.put_along_axis(weights, np.asarray(taken), np.asarray(w), axis=1)
    np.testing.assert_allclose(
        weights, np.asarray(reference.route(u, p, "blk1_", UNCUT)),
        rtol=1e-5)
    total = np.zeros_like(want)
    computed = np.zeros((40, 4), int)
    for first in range(0, 16, 2):
        hf = dict(UNCUT, num_experts=2, experts_first=first)
        cfg = model_config(hf)
        assert cfg.experts_held == (first, 2)
        mine = {k: (v[first:first + 2] if "experts_" in k else v)
                for k, v in p.items()}
        taken, w = latent_moe._route(u, mine, "blk1_", cfg)
        out, done, _ = latent_moe._routed_experts(u, taken, w, mine, "blk1_",
                                               cfg, False)
        assert (np.asarray(done) == np.asarray(
            latent_moe.held(taken, cfg))).all()      # none dropped
        share = np.asarray(reference.routed(u, mine, "blk1_", hf))
        assert np.abs(np.asarray(out) - share).max() \
            <= 1e-5 * np.abs(want).max()
        total = total + np.asarray(out)
        computed += np.asarray(done)
    assert (computed == 1).all()
    assert np.abs(total - want).max() <= 1e-5 * np.abs(want).max()


# -- the controls -------------------------------------------------------------

def _causal(positions, cfg):
    return positions + 1


@pytest.mark.parametrize("fault", ["stale_commit", "causal_in_block",
                                   "shifted_row"])
def test_the_comparison_can_fail(params, monkeypatch, fault):
    """(f) each broken path reads far over the limit in the comparison
    that a sound one passes: a commit that keeps a denoise pass's K/V (the
    rows computed while the block still held masks) shows in every later
    block; a causal mask inside a block; row p taken as the logits of the
    token at p + 1."""
    if fault == "stale_commit":
        sess = session(params)
        _, seq, passes = _serve_blocks(sess, tokens(70, 8), 3, commit=False)
        gaps = [spacings_apart(rows, np.asarray(ref_denoise(
            params, seq, HF, block, visible)))
            for block, visible, rows, _ in passes]
        first = [g for (blk, *_), g in zip(passes, gaps) if blk == 2]
        later = [g for (blk, *_), g in zip(passes, gaps) if blk > 2]
        assert max(first) <= LIMIT_SPACINGS     # nothing stale in front
        assert min(later) > 1000 * LIMIT_SPACINGS
        return
    seq = tokens(71, 24)
    want = ref_logits(params, seq)
    if fault == "causal_in_block":
        monkeypatch.setattr(sdar_moe, "_horizons", _causal)
    got = np.asarray(jax.jit(       # traced anew, under the patch
        lambda p, t: serve_model.full_forward(p, t, CFG, exact=False))(
            params, jnp.asarray([seq], jnp.int32)))[0]
    if fault == "shifted_row":
        got, want = got[:-1], want[1:]
    assert spacings_apart(got, want) > 1000 * LIMIT_SPACINGS
    if fault == "causal_in_block":      # and the loop takes other tokens
        sess = session(params, slots=1, buckets=(16,))
        req = Request(rid=0, prompt=tokens(72, 16), max_new=8,
                      arrival_s=0.0)
        Scheduler(sess).run([req])
        assert list(req.tokens) != reference.generate(
            params, req.prompt, 8, HF)[0]


@pytest.mark.parametrize("feature, over", [
    ("spec_k", dict(spec_k=2)), ("kv_quant", dict(kv_quant="int8")),
    ("prefix_pages", dict(prefix_pages=8)), ("oversub", dict(oversub=True))])
def test_what_the_block_refuses_is_refused_by_name(params, feature, over):
    """(g) every feature in ``REFUSES``, at construction."""
    assert feature in sdar_moe.REFUSES
    with pytest.raises(MXNetError, match="does not support %s" % feature):
        session(params, **over)


def test_weight_only_int8_is_served(params, compiled_reference):
    """The benchmark's control: the session's weight-only int8 path runs
    the block, and takes other tokens than the float32 weights do."""
    sess = session(params, quant="int8")
    req = Request(rid=0, prompt=tokens(80, 12), max_new=12, arrival_s=0.0)
    Scheduler(sess).run([req])
    assert not req.failed and len(req.tokens) == 12
    deq = sess.dequantized_params()
    want, at = reference.generate(deq, req.prompt, 12, HF)
    assert (list(req.tokens), list(req.passes)) == (want, at)
