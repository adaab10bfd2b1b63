"""The LFM2-MoE block in the serving runtime (``serve/lfm2_moe.py``:
double-gated short convolutions whose whole memory a slot is two rows,
QK-normed grouped-query layers on K/V pages, sigmoid-routed experts with a
selection bias of which a share is held, a tied head), held to the plain
reference the benchmark keeps, ``benchmark/references/lfm2_moe_lm.py``,
loaded from its path: one reference in the repo, with the convolution as
three shifted products.  Toy widths, seeded weights, logits compared; the
selection bias is not zero anywhere in this file.

Tolerances, each with its reason:

* ``LIMIT_SPACINGS`` (tests/closeness.py, 32 float32 spacings at the
  row's largest logit) wherever two programs compute the same sums in
  another order: the session's executables against the reference, a
  prompt in chunks against the same prompt in one bucket.
  tests/conftest.py sets full-precision matmuls, so what is left is
  float32 rounding, the 1e-20 the program adds under the router's sum
  where the reference adds 1e-6 included (``test_the_routers_sum``); the
  convolution rows zeroed between two chunks, the taps reversed, the
  convolution on ``z`` alone, the norm on q and k left out, the other
  rotation and the bias used as a weight read in the hundreds and more
  (``test_the_comparison_can_fail``).
* The share test adds eight partial results in another order than the
  uncut layer's loop over its experts, and the routing test compares
  weights after a sigmoid and a division: 1e-5 of the largest value.
* The norm and the rotation of one head are held to values computed by
  hand in float64, to what a float32 angle at the largest position tried
  (4000) carries: 4000 x 2^-23 of the largest value; a wrong pairing or
  frequency reads of order one.
* Scheduler runs return tokens only: a served token's logit has to lie
  within 1e-5 of the row's spread below the reference's best.
* The same executable on inputs that differ in bucket padding alone: the
  bits (``assert_array_equal``).
"""
import dataclasses
import functools
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serve import latent_moe, lfm2_moe
from mxnet_tpu.serve import model as serve_model
from mxnet_tpu.serve.scheduler import Request, Scheduler

from closeness import (LIMIT_SPACINGS, assert_close_across_executables,
                       spacings_apart)
from serve_util import lend

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "references", "lfm2_moe_lm.py")
_spec = importlib.util.spec_from_file_location("lfm2_moe_lm_reference", _REF)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

PAGE = 4
# the reference's configuration: the published config.json's keys.  The
# published stack here is LFM2-24B-A2B's first ten layers, two leading
# dense ones; kept are its layers 0 and 2-4: conv | a c c
HF = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, intermediate_size=96, moe_intermediate_size=32,
          router_experts=16, num_experts=2, experts_first=4,
          num_experts_per_tok=4, routed_scaling_factor=1,
          norm_topk_prob=True, use_expert_bias=True, vocab_size=97,
          num_hidden_layers=4, num_dense_layers=1,
          layers_kept=(0, 2, 3, 4),
          layer_types=("conv", "conv", "full_attention", "conv", "conv",
                       "conv", "full_attention", "conv", "conv", "conv"),
          conv_L_cache=3, conv_bias=False, norm_eps=1e-5,
          rope_parameters=(("rope_theta", 1000000), ("rope_type", "default")),
          max_position_embeddings=256)
UNCUT = dict(HF, num_experts=16, experts_first=0)


def hf_config(hf):
    return dict(hf, rope_parameters=dict(hf["rope_parameters"]))


def model_config(hf):
    first, count, routed = reference.held(hf)
    return serve.ModelConfig(
        block="lfm2_moe", vocab_size=hf["vocab_size"],
        num_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        num_heads=hf["num_attention_heads"],
        num_key_value_heads=hf["num_key_value_heads"],
        max_len=hf["max_position_embeddings"], attn_head_dim=hf["head_dim"],
        rope_theta=float(dict(hf["rope_parameters"])["rope_theta"]),
        rms_norm_eps=hf["norm_eps"],
        layer_types=tuple(reference.layer_types(hf)),
        conv_L_cache=hf["conv_L_cache"], d_ff=hf["intermediate_size"],
        first_k_dense=hf["num_dense_layers"],
        moe_d_ff=hf["moe_intermediate_size"], n_routed_experts=routed,
        num_experts_per_tok=hf["num_experts_per_tok"],
        routed_scaling_factor=float(hf["routed_scaling_factor"]),
        norm_topk_prob=hf["norm_topk_prob"], tie_word_embeddings=True,
        experts_held=(first, count) if count < routed else ())


CFG = model_config(HF)
CONF = dict(slots=3, page_size=PAGE, buckets=(8, 16), max_new=16,
            max_prompt=64, exact=False)


def test_the_layer_order_is_the_models():
    assert CFG.layer_types == ("conv", "full_attention", "conv", "conv")
    assert CFG.kinds == ("ssm", "full", "ssm", "ssm")
    assert CFG.hybrid and CFG.head_dim == 16 and CFG.kv_heads == 2
    # the cell's cut: published layers 0 and 2-13 of LFM2-24B-A2B's 40
    published = (("conv", "conv") + ("full_attention", "conv", "conv",
                                     "conv") * 10)[:40]
    cut = dict(HF, layer_types=published, num_hidden_layers=13,
               layers_kept=[0] + list(range(2, 14)))
    assert "".join(k[0] for k in reference.layer_types(cut)) \
        == "cfcccfcccfccc"
    assert reference.layer_dense(cut) == [True] + [False] * 12
    # one pool of state, two rows of d a slot a convolution layer
    assert lfm2_moe.state_shapes(CFG) == {
        "conv_state": (3, (2, 64), "float32")}
    assert lfm2_moe.latent_dim(CFG) == 0


@functools.lru_cache(maxsize=None)
def _jitted_reference(hf_items):
    hf = hf_config(dict(hf_items))
    return jax.jit(lambda params, seq: reference.logits(params, seq, hf))


def ref_logits(params, seq, hf=HF):
    """The reference's (len(seq), vocab) logits.  One compilation a
    configuration: the sequence is padded to 96 tokens, which a causal
    model's earlier rows cannot see."""
    padded = jnp.asarray(list(seq) + [0] * (96 - len(seq)), jnp.int32)
    return np.asarray(_jitted_reference(tuple(sorted(hf.items())))(
        params, padded))[:len(seq)]


def tokens(seed, n):
    return np.random.default_rng(seed).integers(
        0, HF["vocab_size"], n).tolist()


def with_bias(params, seed=5):
    """``params`` with every router's selection bias drawn at 0.3: enough
    to change which experts a third of the rows take."""
    rs = np.random.RandomState(seed)
    return {k: jnp.asarray(0.3 * rs.randn(*v.shape), jnp.float32)
            if k.endswith("router_bias") else v for k, v in params.items()}


@pytest.fixture(scope="module")
def params():
    return with_bias(serve_model.init_params(CFG, seed=3))


def session(params, cfg=CFG, **over):
    return serve.InferenceSession(
        params, model=cfg, config=serve.ServeConfig(**dict(CONF, **over)))


@pytest.fixture(scope="module")
def _plain(params):
    return session(params)


@pytest.fixture
def plain(_plain):
    yield from lend(_plain)


def _serve_one(sess, prompt, steps):
    """Prefill ``prompt`` into the lowest free slot and decode ``steps``
    steps; -> (slot, the logits rows returned, the sequence)."""
    slot = sess.try_alloc(len(prompt), 16, tokens=prompt)
    first, logits = sess.prefill(slot, prompt)
    rows, seq = [np.asarray(logits)], list(prompt) + [first]
    for _ in range(steps):
        toks, logits = sess.step()
        rows.append(np.asarray(logits)[slot])
        seq.append(toks[slot])
    return slot, rows, seq


def _worst(rows, want, first_row):
    return max(spacings_apart(row, want[first_row + i])
               for i, row in enumerate(rows))


# -- one head by hand ---------------------------------------------------------

def test_qk_norm_and_rotation_of_one_head_by_hand(params):
    """Query head 1 and key head 0 of layer 1: an RMSNorm over the head's
    16 values with the kind's one scale vector at eps 1e-5, then value i
    turned with value i + 8 by position x 1e6^(-2i / 16)."""
    rs = np.random.RandomState(2)
    u = rs.randn(5, 64)
    pos = np.array([0, 1, 7, 300, 4000])
    p = {k: v for k, v in params.items() if k.startswith("blk1_")}
    p["blk1_q_norm_gamma"] = jnp.asarray(1 + 0.2 * rs.randn(16), jnp.float32)
    p["blk1_k_norm_gamma"] = jnp.asarray(1 + 0.2 * rs.randn(16), jnp.float32)
    q, k, _ = lfm2_moe._qkv(p, "blk1_", jnp.asarray(u, jnp.float32),
                            jnp.asarray(pos), CFG, False)
    assert q.shape == (5, 2, 2, 16) and k.shape == (5, 2, 16)
    for got, w, gamma, head in (
            (np.asarray(q).reshape(5, 4, 16)[:, 1], "q", "q_norm", 1),
            (np.asarray(k)[:, 0], "k", "k_norm", 0)):
        x = (u @ np.asarray(p["blk1_%s_weight" % w], np.float64).T
             )[:, 16 * head:16 * head + 16]
        x = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) \
            * np.asarray(p["blk1_%s_gamma" % gamma], np.float64)
        want = x.copy()
        for i in range(8):
            angle = pos * 1e6 ** (-2 * i / 16)
            want[:, i] = x[:, i] * np.cos(angle) - x[:, i + 8] * np.sin(angle)
            want[:, i + 8] = x[:, i + 8] * np.cos(angle) \
                + x[:, i] * np.sin(angle)
        # a float32 angle at position 4000 carries 4000 * 2^-24 rad
        np.testing.assert_allclose(
            got, want, atol=4000 * 2.0 ** -23 * np.abs(want).max())
    # the reference's own, which rotates at positions 0 .. T - 1
    q, k, _ = lfm2_moe._qkv(p, "blk1_", jnp.asarray(u, jnp.float32),
                            jnp.arange(5), CFG, False)
    rq, rk = reference.qk(jnp.asarray(u, jnp.float32), p, "blk1_",
                          hf_config(HF))
    np.testing.assert_allclose(np.asarray(q).reshape(5, 4, 16),
                               np.asarray(rq), atol=1e-5)
    np.testing.assert_allclose(np.asarray(k), np.asarray(rk), atol=1e-5)


# -- the expert layer: sigmoid scores, the bias, the share -------------------

def _ffn_layer(seed, hf):
    cfg = model_config(hf)
    shapes = {k: v for k, v in lfm2_moe.param_shapes(cfg).items()
              if k.startswith("blk1_") and ("router" in k or "expert" in k)}
    rs = np.random.RandomState(seed)
    return {k: jnp.asarray((0.3 * rs.randn(*s)).astype(np.float32))
            for k, s in sorted(shapes.items())}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sigmoid_routing_with_a_bias_is_the_references_choice(seed):
    """sigmoid over all 16, the 4 largest of score + bias taken, the
    scores of those renormalised, times 1: the bias changes the choice
    and never the weights of what both choices share."""
    cfg, hf = model_config(UNCUT), hf_config(UNCUT)
    p = _ffn_layer(seed, UNCUT)
    assert p["blk1_router_bias"].shape == (16,)
    u = jnp.asarray(np.random.RandomState(seed + 10).randn(40, 64)
                    .astype(np.float32))
    taken, w = latent_moe._route(u, p, "blk1_", cfg)
    want = np.asarray(reference.route(u, p, "blk1_", hf))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(taken), np.asarray(w), axis=1)
    assert ((got > 0) == (want > 0)).all()
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(got.sum(axis=1), 1.0, rtol=1e-5)
    # without the bias other experts are taken ...
    plain_taken, _ = latent_moe._route(
        u, dict(p, blk1_router_bias=jnp.zeros((16,))), "blk1_", cfg)
    assert (np.sort(np.asarray(taken)) != np.sort(np.asarray(plain_taken))
            ).any()
    # ... and a taken expert's weight is its sigmoid over the sum of the
    # four sigmoids taken, the bias nowhere in it
    scores = 1 / (1 + np.exp(-np.asarray(u, np.float64) @ np.asarray(
        p["blk1_router_weight"], np.float64).T))
    mine = np.take_along_axis(scores, np.asarray(taken), axis=1)
    np.testing.assert_allclose(np.asarray(w),
                               mine / mine.sum(-1, keepdims=True), rtol=1e-5)


def test_the_routers_sum():
    """The program adds 1e-20 under the sum of the four scores taken, the
    published implementation 1e-6: with sigmoid scores the sum is never
    small (here over 0.5), so the weights differ by under 2e-6 of
    themselves, a fraction of one float32 spacing of a logit after the
    sum over four experts, inside ``LIMIT_SPACINGS`` many times over."""
    cfg, hf = model_config(UNCUT), hf_config(UNCUT)
    p = _ffn_layer(4, UNCUT)
    u = jnp.asarray(np.random.RandomState(14).randn(200, 64)
                    .astype(np.float32))
    _, w = latent_moe._route(u, p, "blk1_", cfg)
    want = np.asarray(reference.route(u, p, "blk1_", hf))
    assert reference.ROUTER_EPS == 1e-6
    taken_sum = np.sort(1 / (1 + np.exp(-np.asarray(u) @ np.asarray(
        p["blk1_router_weight"]).T)), axis=1)[:, -4:].sum(1)
    assert taken_sum.min() > 0.5
    rel = np.abs(np.sort(np.asarray(w), axis=1) - np.sort(want, axis=1)[
        :, -4:]) / np.sort(want, axis=1)[:, -4:]
    assert rel.max() < 2e-6 + 2 * np.finfo(np.float32).eps


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that all eight shares compute are the uncut
    reference's layer (there is no shared expert to count once); each
    share is the reference's own share; an assignment is computed by
    exactly one; a row none of whose experts a share holds gets exactly
    nothing from it, so ``y = h`` there."""
    p = _ffn_layer(7, UNCUT)
    x = jnp.asarray(np.random.RandomState(17).randn(40, 64)
                    .astype(np.float32))
    u = latent_moe.rms_norm(x, jnp.ones((64,)), 1e-5)
    want = np.asarray(reference.routed(u, p, "blk1_", hf_config(UNCUT)))
    total = np.zeros_like(want)
    computed = np.zeros((40, 4), int)
    passed = 0
    for first in range(0, 16, 2):
        hf = hf_config(dict(UNCUT, num_experts=2, experts_first=first))
        cfg = model_config(hf)
        assert cfg.experts_held == (first, 2)
        mine = {k: (v[first:first + 2] if "experts_" in k else v)
                for k, v in p.items()}
        taken, w = latent_moe._route(u, mine, "blk1_", cfg)
        out, done, _ = latent_moe._routed_experts(u, taken, w, mine, "blk1_",
                                               cfg, False)
        here = np.asarray(latent_moe.held(taken, cfg))
        assert (np.asarray(done) == here).all()      # none dropped
        share = np.asarray(reference.routed(u, mine, "blk1_", hf))
        assert np.abs(np.asarray(out) - share).max() \
            <= 1e-5 * np.abs(want).max()
        none_held = ~here.any(axis=1)
        assert (np.asarray(out)[none_held] == 0).all()
        assert (share[none_held] == 0).all()
        total = total + np.asarray(out)
        computed += np.asarray(done)
        if first > 2:
            continue
        # the block's own layer: h passes through, and is counted
        params = dict(mine, blk1_ffn_norm_gamma=jnp.ones((64,)))
        y, inc = latent_moe._ffn_held(params, 1, x, cfg, False,
                                      jnp.ones((40,), bool), False)
        np.testing.assert_array_equal(np.asarray(y)[none_held],
                                      np.asarray(x)[none_held])
        assert int(inc["rows_without_held_expert"]) == none_held.sum()
        passed += none_held.sum()
    assert (computed == 1).all() and passed > 10
    assert np.abs(total - want).max() <= 1e-5 * np.abs(want).max()


# -- the block against the reference ----------------------------------------

def test_params_are_the_references_spec(params):
    """Name for name and shape for shape; no head matrix beside the
    embedding, no shared expert, a bias beside every router."""
    assert {k: tuple(v.shape) for k, v in params.items()} \
        == {k: tuple(v) for k, v in reference.spec(hf_config(HF)).items()}
    assert "lm_head_weight" not in params
    assert not [k for k in params if "shared" in k]
    assert params["blk0_in_weight"].shape == (3 * 64, 64)
    assert params["blk0_conv_weight"].shape == (64, 3)
    assert params["blk1_q_norm_gamma"].shape == (16,)
    assert params["blk1_router_bias"].shape == (16,)
    assert params["blk1_experts_up_weight"].shape == (2, 32, 64)
    # the taps at a Conv1d's default variance for a fan-in of 3, not 0.02
    assert 0.25 < float(jnp.std(params["blk0_conv_weight"])) < 0.42
    lfm2_moe.check_params(params, CFG)
    with pytest.raises(MXNetError, match="the architecture says"):
        lfm2_moe.check_params(params, dataclasses.replace(
            CFG, conv_L_cache=4))


@pytest.mark.parametrize("exact, seed", [(False, 0), (True, 1)])
def test_full_forward_matches_reference(params, exact, seed):
    seq = tokens(seed, 40)
    got = np.asarray(serve_model.full_forward(
        params, jnp.asarray([seq], jnp.int32), CFG, exact=exact))[0]
    assert_close_across_executables(got, ref_logits(params, seq))


def test_the_tied_sliced_head_is_the_slice_of_the_uncut_logits(params):
    """A slice of the vocabulary is a smaller vocabulary: the tied matrix
    sliced once looks up the same rows for ids inside the slice and gives
    the uncut model's logits at those ids."""
    rs = np.random.RandomState(8)
    wide = dict(params, tok_embed_weight=jnp.concatenate([
        params["tok_embed_weight"],
        jnp.asarray(0.02 * rs.randn(31, 64), jnp.float32)]))
    seq = jnp.asarray(tokens(4, 24), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(lfm2_moe._embed(params, seq)),
        np.asarray(lfm2_moe._embed(wide, seq)))
    x = jnp.asarray(rs.randn(24, 64), jnp.float32)
    cut = np.asarray(lfm2_moe._head(params, x, CFG, False))
    whole = np.asarray(lfm2_moe._head(
        wide, x, dataclasses.replace(CFG, vocab_size=128), False))
    assert whole.shape[-1] == 128 and cut.shape[-1] == 97
    assert_close_across_executables(cut, whole[:, :97])


@pytest.mark.parametrize("exact", [False, True])
def test_prefill_then_decode_through_pages_and_state(params, exact):
    """Three prompts of different lengths share the decode batch: one of a
    single token (the convolution rows written back reach into the zeros),
    one that fills a bucket, one of three chunks.  Every logits row the
    session returns, at every served position, is the reference's full
    forward's row."""
    sess = session(params, exact=exact)
    assert sorted(sess.executables) == ["decode", "prefill_16", "prefill_8"]
    assert sorted(sess.cache.pools) == ["conv_state", "k_pool", "v_pool"]
    assert sess.cache.pools["conv_state"].shape == (3, 3, 2, 64)
    assert sess.cache.pools["k_pool"].shape[0] == 1     # attention layers
    seqs, slots = [], []
    for i, n in enumerate((1, 16, 43)):
        p = tokens(10 + i, n)
        slot = sess.try_alloc(n, 12, tokens=p)
        first, logits = sess.prefill(slot, p)
        assert_close_across_executables(np.asarray(logits),
                                        ref_logits(params, p)[-1])
        seqs.append(p + [first])
        slots.append(slot)
    for _ in range(6):
        toks, logits = sess.step()
        logits = np.asarray(logits)
        for slot, seq in zip(slots, seqs):
            assert_close_across_executables(
                logits[slot], ref_logits(params, seq)[-1])
            seq.append(toks[slot])
    assert sess.fallback_count() == 0


@pytest.mark.parametrize("n, chunks", [(49, 4), (64, 4)])
def test_a_fresh_prompt_in_chunks_is_the_prompt_in_one_bucket(
        params, plain, n, chunks):
    """A fresh prompt of 49 tokens goes as three chunks of 16 and a
    remainder whose real rows number ONE (bucket 8): the two rows written
    back straddle the chunk's start, one from the chunk before and the
    one real row.  64 tokens are four whole chunks (the cell's 8 192 in
    chunks of 2048).  The same prompt
    through a session whose largest bucket holds it goes as one.  Both
    give the reference's row, with buckets + 1 executables each, and the
    chunks after the first are counted as carried."""
    seq = tokens(21, n)
    before = plain.block_report()
    slot = plain.try_alloc(len(seq), 3, tokens=seq)
    first, chunked = plain.prefill(slot, seq)
    after = plain.block_report()
    assert after["prefill_chunks"] - before["prefill_chunks"] == chunks
    assert after["prefills_carried"] - before["prefills_carried"] \
        == after["prefill_chunks_continued"] \
        - before["prefill_chunks_continued"] == chunks - 1
    assert after["prefills_from_zero"] - before["prefills_from_zero"] == 1
    assert after["rows_valid"] - before["rows_valid"] == n
    whole = session(jax.tree.map(jnp.asarray, plain.params), buckets=(64,))
    assert sorted(whole.executables) == ["decode", "prefill_64"]
    slot2 = whole.try_alloc(len(seq), 3, tokens=seq)
    first2, one = whole.prefill(slot2, seq)
    assert whole.block_report()["prefills_carried"] == 0
    assert first == first2
    assert_close_across_executables(np.asarray(chunked), np.asarray(one))
    assert_close_across_executables(np.asarray(chunked),
                                    ref_logits(params, seq)[-1])
    # what the chunks left a slot is what the one bucket left it
    assert_close_across_executables(
        np.asarray(plain.cache.pools["conv_state"][:, slot]),
        np.asarray(whole.cache.pools["conv_state"][:, slot2]))
    seq = seq + [first]
    for _ in range(3):
        toks, logits = plain.step()
        toks2, logits2 = whole.step()
        assert_close_across_executables(np.asarray(logits)[slot],
                                        ref_logits(params, seq)[-1])
        assert_close_across_executables(np.asarray(logits2)[slot2],
                                        np.asarray(logits)[slot])
        seq.append(toks[slot])
    with pytest.raises(MXNetError, match="longest admissible prompt 64"):
        plain.try_alloc(65, 3)


def test_bucket_padding_leaves_the_state_untouched(params, plain):
    """The same executable on a bucket whose padded tail holds other
    tokens: the rows written back, the pages' real rows, the token and the
    logits are the same bits; and an idle slot's rows are not moved by a
    decode step."""
    seq = tokens(40, 5)
    outs = []
    for pad in (0, 7):
        slot = plain.try_alloc(len(seq), 3, tokens=seq)
        toks = np.full((1, 8), pad, np.int32)
        toks[0, :5] = seq
        plain.cache.ensure_writable(slot, 0, 5)
        first, logits, pools, plain.counters = plain._dispatch("prefill_8", (
            plain.params, toks, np.int32(5), np.int32(0),
            plain.cache.table_row(slot), plain.cache.pools, plain.counters,
            np.int32(slot)))
        plain.cache.pools = pools
        outs.append((int(first), np.asarray(logits),
                     np.asarray(pools["conv_state"][:, slot])))
        assert np.abs(outs[-1][2]).min() > 0
        plain.release(slot)
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)
    # a live slot beside two idle ones: only its rows move
    slot, _, _ = _serve_one(plain, seq, 0)
    before = np.asarray(plain.cache.pools["conv_state"])
    plain.step()
    after = np.asarray(plain.cache.pools["conv_state"])
    idle = [s for s in range(3) if s != slot]
    np.testing.assert_array_equal(after[:, idle], before[:, idle])
    np.testing.assert_array_equal(after[:, slot, 0], before[:, slot, 1])
    assert np.abs(after[:, slot, 1] - before[:, slot, 1]).max() > 0


def test_a_slot_admitted_again_starts_from_zeros(params, plain):
    """A slot that served a request of 50 tokens and is admitted again
    gives a shorter request the rows the reference gives it: ``alloc``
    zeroes the slot's convolution rows, and ``release`` leaves nothing a
    later request can see."""
    slot, _, _ = _serve_one(plain, tokens(50, 41), 9)
    assert float(jnp.abs(plain.cache.pools["conv_state"][:, slot]).min()) > 0
    plain.release(slot)
    again = plain.try_alloc(3, 8)
    assert again == slot
    assert float(jnp.abs(plain.cache.pools["conv_state"][:, slot]).max()) \
        == 0
    plain.release(again)
    again, rows, seq = _serve_one(plain, tokens(51, 3), 8)
    assert again == slot
    assert _worst(rows, ref_logits(params, seq), 2) <= LIMIT_SPACINGS


# -- the controls -------------------------------------------------------------

def _another_rotation(x, positions, group):
    """The interleaved pairs (2i, 2i + 1), DeepSeek-V3's, at the same
    frequencies."""
    d = x.shape[-1]
    inv_freq = jnp.asarray([float(group["rope_theta"]) ** (-2.0 * i / d)
                            for i in range(d // 2)], jnp.float32)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _state_zeroed(params, pre, u, context, length, cfg, exact,
                  real=lfm2_moe._conv_rows):
    return real(params, pre, u, jnp.zeros_like(context), length, cfg, exact)


def _taps_reversed(rows, context, weight, bias, length,
                   real=lfm2_moe.causal_conv):
    return real(rows, context, weight[:, ::-1], bias, length)


def _step_reversed(row, context, weight, bias, real=lfm2_moe.conv_step):
    return real(row, context, weight[:, ::-1], bias)


def _conv_on_z(params, pre, u, cfg, exact):
    d = cfg.d_model
    bcz = lfm2_moe._mm(u, params[pre + "in_weight"], exact)
    return bcz[:, 2 * d:], bcz[:, d:2 * d]


def _no_head_norm(x, gamma, eps, real=lfm2_moe.rms_norm):
    """Heads (N, heads, D) pass as they are; the rows' norms stay."""
    return x if x.ndim == 3 else real(x, gamma, eps)


def _bias_as_weight(u, params, pre, cfg, real=latent_moe._route):
    """The weights from ``s + b``, not from ``s``."""
    taken, _ = real(u, params, pre, cfg)
    choice = jax.nn.sigmoid(jnp.einsum(
        "nc,ec->ne", u, params[pre + "router_weight"],
        precision="highest")) + params[pre + "router_bias"]
    w = jnp.take_along_axis(choice, taken, axis=-1)
    return taken, w / w.sum(axis=-1, keepdims=True)


FAULTS = {
    "state zeroed between two chunks": [(lfm2_moe, "_conv_rows",
                                         _state_zeroed)],
    "taps reversed": [(lfm2_moe, "causal_conv", _taps_reversed),
                      (lfm2_moe, "conv_step", _step_reversed)],
    "convolution on z alone": [(lfm2_moe, "_gates", _conv_on_z)],
    "qk norm left out": [(lfm2_moe, "rms_norm", _no_head_norm)],
    "interleaved rotation": [(lfm2_moe, "_rope", _another_rotation)],
    "bias used as a weight": [(latent_moe, "_route", _bias_as_weight)],
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_comparison_can_fail(params, monkeypatch, fault):
    """The controls: each planted fault, served through prefill in three
    chunks and decode, reads hundreds of spacings from the reference (the
    sound session: under 32, the tests above)."""
    for module, name, planted in FAULTS[fault]:
        monkeypatch.setattr(module, name, planted)
    sess = session(params, buckets=(16,))
    _, rows, seq = _serve_one(sess, tokens(62, 37), 6)
    assert _worst(rows, ref_logits(params, seq), 36) > 10 * LIMIT_SPACINGS


def test_zeroed_rows_mid_request_are_seen(params, plain):
    """The other control, planted in the cache: a live slot's convolution
    rows zeroed between two decode steps."""
    seq = tokens(63, 20)
    slot = plain.try_alloc(len(seq), 8, tokens=seq)
    first, _ = plain.prefill(slot, seq)
    pool = plain.cache.pools["conv_state"]
    plain.cache.pools["conv_state"] = pool.at[:, slot].set(0.0)
    _, logits = plain.step()
    assert spacings_apart(np.asarray(logits)[slot], ref_logits(
        params, seq + [first])[-1]) > 10 * LIMIT_SPACINGS


# -- refusals, int8, counters, scopes -----------------------------------------

def test_what_the_block_refuses(params):
    assert lfm2_moe.REFUSES == ("spec_k", "kv_quant")
    for over in (dict(spec_k=2, draft="layers:1"), dict(kv_quant="int8")):
        with pytest.raises(MXNetError, match="does not support"):
            session(params, **over)
    for bad, match in (
            (dict(layer_types=("conv", "mamba") + ("conv",) * 4),
             "layer_types"),
            (dict(layer_types=("conv",) * 5), "layer_types"),
            (dict(num_key_value_heads=3), "key/value heads"),
            (dict(conv_L_cache=1), "conv_L_cache"),
            (dict(scoring_func="softmax"), "sigmoid scores"),
            (dict(n_shared_experts=1), "no shared expert"),
            (dict(tie_word_embeddings=False), "no untied head"),
            (dict(attn_head_dim=0), "attn_head_dim"),
            (dict(experts_held=(12, 8)), "experts_held")):
        with pytest.raises(MXNetError, match=match):
            dataclasses.replace(CFG, **bad).validate()


def test_int8_weights_serve_another_model(params):
    """Weight-only int8 is another model: it serves, and lands beyond
    the float32 limit."""
    sess = session(params, quant="int8", buckets=(16,))
    _, rows, seq = _serve_one(sess, tokens(80, 20), 3)
    assert all(np.isfinite(row).all() for row in rows)
    assert _worst(rows, ref_logits(params, seq), 19) > LIMIT_SPACINGS


def test_scheduler_serves_and_the_block_counts(params):
    """Six requests through ``Scheduler`` on three slots, one of them a
    fresh prompt of four chunks: every served token is the reference's
    choice to rounding, and the block's counters add up."""
    sess = session(params)
    lengths = (5, 55, 16, 9, 30, 12)
    reqs = [Request(rid=i, prompt=tokens(30 + i, n), max_new=6,
                    arrival_s=0.0) for i, n in enumerate(lengths)]
    done, _ = Scheduler(sess, policy="continuous").run(reqs)
    assert not any(r.failed for r in done), [r.error for r in done]
    for r in done:
        fed = list(r.prompt) + list(r.tokens[:-1])
        rows = ref_logits(params, fed)[len(r.prompt) - 1:]
        picked = rows[np.arange(len(r.tokens)), list(r.tokens)]
        assert (rows.max(-1) - picked
                <= 1e-5 * (rows.max(-1) - rows.min(-1))).all()
    rep = sess.block_report()
    chunks = sum(-(-n // 16) for n in lengths)
    assert rep["prefill_chunks"] == chunks
    assert rep["prefills_from_zero"] == len(lengths)
    assert rep["prefills_carried"] == rep["prefill_chunks_continued"] \
        == chunks - len(lengths)
    assert rep["rows_valid"] == sum(lengths)
    # a remainder goes in the smallest bucket that holds it
    assert rep["rows_padded"] == sum(
        (8 if n % 16 <= 8 else 16) - n % 16 for n in lengths if n % 16)
    steps = rep["decode_steps"]
    assert steps == sess.decode_report()["steps"] >= 5
    assert rep["window_rows_visited"] == rep["window_rows_in_band"] == 0
    # the loop reads every slot's table to the longest live context
    assert 0 < rep["full_rows_live"] <= sess.decode_report()[
        "blocks_visited"] * PAGE * 3
    # real rows x 4 experts a token x 3 expert layers, 2 of 16 held
    rows_fed = sum(lengths) + 3 * steps
    assert rep["assignments_asked"] == rows_fed * 4 * 3
    assert 0 < rep["assignments_held"] == rep["assignments_computed"] \
        < rep["assignments_asked"]
    assert rep["distinct_held_experts"] <= 2 * 3 * steps
    assert 0 < rep["rows_without_held_expert"] < rows_fed * 3
    assert (rep["conv_layers"], rep["full_layers"], rep["window_layers"],
            rep["expert_layers"], rep["experts_held"],
            rep["state_bytes_per_slot"], rep["kv_lanes"],
            rep["expert_kernel_layers"]) \
        == (3, 1, 0, 3, 2, 3 * 2 * 64 * 4, 32, 0)
    # the CPU traced it: the loop.  (A TPU's trace takes the kernel's
    # folded form where the pools' last axis is whole lane tiles, 3 layers
    # at the published widths: tests/test_tpu_compile.py; this toy's two
    # heads of 16 are a quarter of a tile and keep the loop there too.)
    assert sess.decode_report()["paged_kernel_layers"] == 0
    assert sess.fallback_count() == 0 and len(sess.executables) == 3


def test_at_the_published_sizes():
    """LFM2-24B-A2B's cut: a slot's state is 10 layers x 2 rows x 2048
    float32 values = 163 840 bytes, the K/V pools fold 8 heads of 64 into
    512 lanes and hold 3 layers, an 8 192-token prompt is four chunks."""
    kinds = ("conv",) + ("full_attention", "conv", "conv", "conv") * 3
    cfg = serve.ModelConfig(
        block="lfm2_moe", vocab_size=8192, num_layers=13, d_model=2048,
        num_heads=32, num_key_value_heads=8, max_len=128000,
        attn_head_dim=64, rope_theta=1e6, rms_norm_eps=1e-5,
        layer_types=kinds, conv_L_cache=3, d_ff=11776, first_k_dense=1,
        moe_d_ff=1536, n_routed_experts=64, num_experts_per_tok=4,
        tie_word_embeddings=True, experts_held=(0, 8)).validate()
    rep = lfm2_moe.report(lfm2_moe.init_counters(cfg), cfg)
    assert (rep["conv_layers"], rep["full_layers"], rep["expert_layers"],
            rep["experts_held"], rep["state_bytes_per_slot"],
            rep["kv_lanes"]) == (10, 3, 12, 8, 163840, 512)
    assert cfg.kinds.count("full") == 3 and lfm2_moe._scale(cfg) == 0.125
    conf = serve.ServeConfig(slots=64, page_size=16, buckets=(512, 2048),
                             max_prompt=8192, max_new=1024, exact=False)
    assert conf.max_pages_per_slot == (8192 + 1024) // 16
    assert -(-8192 // max(conf.buckets)) == 4
    n = sum(math.prod(s) for s in lfm2_moe.param_shapes(cfg).values())
    assert abs(n / 1196.0e6 - 1) < 0.01
    assert lfm2_moe.guard_tag(cfg) == "-lfm2_moe-kv8x64-c3-e8of64k4-" \
        + "cfcccfcccfccc"


def test_the_scopes_are_in_the_executables(plain):
    """The device scopes the trace is read by are in the lowered text of
    the executables that run them."""
    decode = plain.executables["decode"].as_text()
    prefill = plain.executables["prefill_16"].as_text()
    for scope in ("sconv_in", "sconv_mix", "sconv_out", "gqa_qknorm",
                  "gqa_rope", "moe_route", "moe_experts"):
        assert scope in decode and scope in prefill, scope
    assert "gqa_decode" in decode and "gqa_decode" not in prefill
    assert "gqa_prefill" in prefill and "gqa_prefill" not in decode
