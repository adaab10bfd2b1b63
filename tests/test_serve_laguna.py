"""The Laguna block in the serving runtime (``serve/laguna.py``:
sliding-window layers on per-slot rings sized by the model's window, full
grouped-query layers on K/V pages, a query-head count a layer, two rotary
embeddings, a gate a head, softmax-routed experts of which a share is
held), held to the plain reference the benchmark keeps,
``benchmark/references/laguna_lm.py``, loaded from its path: one reference
in the repo, with the band as a mask.  Toy widths, seeded weights, logits
compared.

Tolerances, each with its reason:

* ``LIMIT_SPACINGS`` (tests/closeness.py, 32 float32 spacings at the
  row's largest logit) wherever two programs compute the same sums in
  another order: the session's executables against the reference, a
  prompt in chunks against the same prompt in one bucket.
  tests/conftest.py sets full-precision matmuls, so what is left is
  float32 rounding; a window one key off, a ring read after it was
  written, a stale ring row, the gate left out, the other rotation read
  in the thousands and more (``test_the_comparison_can_fail``).
* The share test adds eight partial results in another order than the
  uncut layer's loop over its experts, and the routing test compares
  weights after a softmax and a division: 1e-5 of the largest value.
* The rotations are held to angles computed by hand in float64, to what a
  float32 angle at the largest position tried (4000) carries: 4000 x 2^-23
  of the largest value; a wrong pairing or frequency reads of order one.
  The frequencies themselves are Python floats: 1e-12.
* Scheduler runs return tokens only: a served token's logit has to lie
  within 1e-5 of the row's spread below the reference's best.
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
from mxnet_tpu.ops import attention as ops_attention
from mxnet_tpu.serve import kv_cache, laguna, latent_moe
from mxnet_tpu.serve import model as serve_model
from mxnet_tpu.serve.scheduler import Request, Scheduler

from closeness import (LIMIT_SPACINGS, assert_close_across_executables,
                       spacings_apart)
from serve_util import lend

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "references", "laguna_lm.py")
_spec = importlib.util.spec_from_file_location("laguna_lm_reference", _REF)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

PAGE, WINDOW = 4, 8
# Laguna-S-2.1's two groups, letter for letter
ROPE = {
    "full_attention": dict(
        rope_theta=500000, rope_type="yarn", factor=128,
        original_max_position_embeddings=8192, beta_slow=1, beta_fast=32,
        attention_factor=1.4852030263919618, partial_rotary_factor=0.5),
    "sliding_attention": dict(rope_type="default", rope_theta=10000,
                              partial_rotary_factor=1),
}
# the reference's configuration: the published config.json's keys.  The
# published stack here has a period of 4 (full sliding sliding sliding)
# over 9 layers, 4 | 6 query heads; kept are its layers 0-4
HF = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, intermediate_size=96, moe_intermediate_size=32,
          shared_expert_intermediate_size=32, router_experts=16,
          num_experts=2, experts_first=4, num_experts_per_tok=4,
          moe_routed_scaling_factor=2.5, norm_topk_prob=True, vocab_size=97,
          num_hidden_layers=5, layers_kept=(0, 1, 2, 3, 4),
          layer_types=("full_attention",) + ("sliding_attention",) * 3
          + ("full_attention",) + ("sliding_attention",) * 3
          + ("full_attention",),
          mlp_layer_types=("dense",) + ("sparse",) * 8,
          num_attention_heads_per_layer=(4, 6, 6, 6, 4, 6, 6, 6, 4),
          sliding_window=WINDOW, rope_parameters=ROPE, rms_norm_eps=1e-6,
          max_position_embeddings=256, moe_router_logit_softcapping=0)
UNCUT = dict(HF, num_experts=16, experts_first=0)


def model_config(hf):
    first, count, routed = reference.held(hf)
    dense = reference.layer_dense(hf)
    return serve.ModelConfig(
        block="laguna", vocab_size=hf["vocab_size"],
        num_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        num_heads=hf["num_attention_heads"],
        num_key_value_heads=hf["num_key_value_heads"],
        max_len=hf["max_position_embeddings"], attn_head_dim=hf["head_dim"],
        num_attention_heads_per_layer=tuple(reference.layer_heads(hf)),
        layer_types=tuple(reference.layer_types(hf)),
        sliding_window=hf["sliding_window"],
        rope_parameters=hf["rope_parameters"],
        mlp_only_layers=tuple(i for i, d in enumerate(dense) if d),
        rms_norm_eps=hf["rms_norm_eps"], d_ff=hf["intermediate_size"],
        moe_d_ff=hf["moe_intermediate_size"], n_routed_experts=routed,
        num_experts_per_tok=hf["num_experts_per_tok"],
        shared_expert_intermediate_size=hf[
            "shared_expert_intermediate_size"],
        routed_scaling_factor=hf["moe_routed_scaling_factor"],
        norm_topk_prob=hf["norm_topk_prob"], scoring_func="softmax",
        experts_held=(first, count) if count < routed else ())


CFG = model_config(HF)
CONF = dict(slots=3, page_size=PAGE, buckets=(8, 16), max_new=16,
            max_prompt=64, exact=False)


def test_the_layer_order_is_the_models():
    assert CFG.layer_types == ("full_attention",) \
        + ("sliding_attention",) * 3 + ("full_attention",)
    assert CFG.kinds == ("full", "window", "window", "window", "full")
    assert CFG.hybrid and CFG.head_dim == 16 and CFG.kv_heads == 2
    assert laguna.layer_heads(CFG) == (4, 6, 6, 6, 4)
    # a cut that keeps other layers reads the published lists there
    assert reference.layer_heads(dict(HF, layers_kept=(0, 4, 5))) \
        == [4, 4, 6]
    assert reference.layer_types(dict(HF, layers_kept=(3, 4))) \
        == ["sliding_attention", "full_attention"]
    # hashable, whatever the rope groups were given as
    assert hash(CFG) == hash(model_config(HF))
    assert laguna.rope_group(CFG, "full_attention") \
        == ROPE["full_attention"]


@functools.lru_cache(maxsize=None)
def _jitted_reference(hf_items):
    hf = dict(hf_items)
    hf["rope_parameters"] = ROPE
    return jax.jit(lambda params, seq: reference.logits(params, seq, hf))


def ref_logits(params, seq, hf=HF):
    """The reference's (len(seq), vocab) logits.  One compilation a
    configuration: the sequence is padded to 96 tokens, which a causal
    model's earlier rows cannot see."""
    padded = jnp.asarray(list(seq) + [0] * (96 - len(seq)), jnp.int32)
    key = tuple(sorted((k, v) for k, v in hf.items()
                       if k != "rope_parameters"))
    return np.asarray(_jitted_reference(key)(params, padded))[:len(seq)]


def tokens(seed, n):
    return np.random.default_rng(seed).integers(
        0, HF["vocab_size"], n).tolist()


@pytest.fixture(scope="module")
def params():
    return serve_model.init_params(CFG, seed=3)


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


# -- the rotations -----------------------------------------------------------

def test_yarn_frequencies_by_hand():
    """Laguna-S-2.1's full layers at the published head of 128: 64 values
    rotated, the correction dims 9 and 18, between them a ramp from the
    plain frequency to the plain frequency over 128."""
    rot, inv, factor = laguna.rope_frequencies(ROPE["full_attention"], 128)
    assert rot == 64 and len(inv) == 32
    assert factor == 1.4852030263919618 \
        == pytest.approx(0.1 * math.log(128) + 1)
    low = 64 * math.log(8192 / (32 * 2 * math.pi)) / (2 * math.log(5e5))
    high = 64 * math.log(8192 / (1 * 2 * math.pi)) / (2 * math.log(5e5))
    assert (math.floor(low), math.ceil(high)) == (9, 18)
    for i in (0, 9):                    # below the ramp: plain
        assert inv[i] == pytest.approx(5e5 ** (-2 * i / 64), rel=1e-12)
    for i in (18, 31):                  # above it: over the factor
        assert inv[i] == pytest.approx(5e5 ** (-2 * i / 64) / 128, rel=1e-12)
    r = (12 - 9) / 9
    assert inv[12] == pytest.approx(
        (1 - r) * 5e5 ** (-24 / 64) + r * 5e5 ** (-24 / 64) / 128, rel=1e-12)
    assert laguna.rope_frequencies(ROPE["full_attention"], 128) \
        == reference.rope_frequencies(ROPE["full_attention"], 128)
    # the window layers: the whole head, plain
    rot, inv, factor = laguna.rope_frequencies(ROPE["sliding_attention"], 128)
    assert (rot, len(inv), factor) == (128, 64, 1.0)
    assert inv[5] == pytest.approx(1e4 ** (-10 / 128), rel=1e-12)


@pytest.mark.parametrize("kind", sorted(ROPE))
def test_the_rotation_pairs_a_value_with_the_one_half_a_turn_on(kind):
    """``rotate_half``: value i turns with value i + rot / 2 by position x
    inv_freq_i, cos and sin times the factor; what lies past ``rot`` is
    left alone."""
    x = np.random.RandomState(1).randn(5, 3, 16)
    pos = np.array([0, 1, 7, 300, 4000])
    got = np.asarray(laguna._rope(jnp.asarray(x, jnp.float32),
                                  jnp.asarray(pos), ROPE[kind]))
    rot, inv, factor = laguna.rope_frequencies(ROPE[kind], 16)
    want = x.copy()
    for i in range(rot // 2):
        angle = pos * inv[i]
        cos, sin = (factor * np.cos(angle))[:, None], \
            (factor * np.sin(angle))[:, None]
        want[..., i] = x[..., i] * cos - x[..., i + rot // 2] * sin
        want[..., i + rot // 2] = x[..., i + rot // 2] * cos \
            + x[..., i] * sin
    assert rot == (8 if kind == "full_attention" else 16)
    # a float32 angle at position 4000 carries 4000 * 2^-24 rad of rounding
    np.testing.assert_allclose(got, want,
                               atol=4000 * 2.0 ** -23 * np.abs(want).max())
    np.testing.assert_array_equal(got[..., rot:], x[..., rot:].astype(
        np.float32))


# -- the expert layer: softmax scores, the share -----------------------------

def _ffn_layer(seed, hf):
    cfg = model_config(hf)
    shapes = {k: v for k, v in laguna.param_shapes(cfg).items()
              if k.startswith("blk1_") and ("router" in k or "expert" in k
                                            or "shared" in k)}
    rs = np.random.RandomState(seed)
    return {k: jnp.asarray((0.3 * rs.randn(*s)).astype(np.float32))
            for k, s in sorted(shapes.items())}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_softmax_routing_is_the_references_choice(seed):
    """softmax over all 16, the 4 largest taken, renormalised, times 2.5;
    no selection bias among the parameters."""
    cfg = laguna._ffn_cfg(model_config(UNCUT))
    p = _ffn_layer(seed, UNCUT)
    assert "blk1_router_bias" not in p
    u = jnp.asarray(np.random.RandomState(seed + 10).randn(40, 64)
                    .astype(np.float32))
    taken, w = latent_moe._route(u, p, "blk1_", cfg)
    want = np.asarray(reference.route(u, p, "blk1_", UNCUT))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(taken), np.asarray(w), axis=1)
    assert ((got > 0) == (want > 0)).all()
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(got.sum(axis=1), 2.5, rtol=1e-5)
    # sigmoid scores weigh the same experts otherwise
    _, other = latent_moe._route(
        u, dict(p, blk1_router_bias=jnp.zeros((16,))), "blk1_",
        dataclasses.replace(cfg, scoring_func="sigmoid"))
    assert float(jnp.abs(other - w).max()) > 1e-3


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that all eight shares compute, plus the shared
    expert counted once, are the uncut reference's layer; each share is
    the reference's own share; an assignment is computed by exactly one."""
    p = _ffn_layer(7, UNCUT)
    u = jnp.asarray(np.random.RandomState(17).randn(40, 64)
                    .astype(np.float32))
    want = np.asarray(reference.routed(u, p, "blk1_", UNCUT)
                      + reference.shared(u, p, "blk1_"))
    total = np.asarray(reference.shared(u, p, "blk1_"))
    computed = np.zeros((40, 4), int)
    for first in range(0, 16, 2):
        hf = dict(UNCUT, num_experts=2, experts_first=first)
        cfg = laguna._ffn_cfg(model_config(hf))
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
        total = total + np.asarray(out)
        computed += np.asarray(done)
    assert (computed == 1).all()
    assert np.abs(total - want).max() <= 1e-5 * np.abs(want).max()


# -- the block against the reference ----------------------------------------

def test_params_are_the_references_spec(params):
    """Name for name and shape for shape, the query, gate and output
    matrices by the layer's own head count: 4 heads in the full layers, 6
    in the window layers, in one model."""
    assert {k: tuple(v.shape) for k, v in params.items()} \
        == {k: tuple(v) for k, v in reference.spec(HF).items()}
    assert params["blk0_q_weight"].shape == (4 * 16, 64)
    assert params["blk1_q_weight"].shape == (6 * 16, 64)
    assert params["blk1_attn_gate_weight"].shape == (6, 64)
    assert params["blk4_o_weight"].shape == (64, 4 * 16)
    assert params["blk1_k_weight"].shape == params["blk4_k_weight"].shape \
        == (2 * 16, 64)
    laguna.check_params(params, CFG)
    with pytest.raises(MXNetError, match="the architecture says"):
        laguna.check_params(params, dataclasses.replace(
            CFG, num_attention_heads_per_layer=(4, 6, 6, 4, 4)))


@pytest.mark.parametrize("exact", [False, True])
@pytest.mark.parametrize("seed", [0, 1])
def test_full_forward_matches_reference(params, exact, seed):
    seq = tokens(seed, 40)          # five windows deep
    got = np.asarray(serve_model.full_forward(
        params, jnp.asarray([seq], jnp.int32), CFG, exact=exact))[0]
    assert_close_across_executables(got, ref_logits(params, seq))


@pytest.mark.parametrize("exact", [False, True])
def test_prefill_then_decode_through_rings_and_pages(params, exact):
    """Three prompts of different lengths share the decode batch: one
    shorter than the window, one that fills a bucket, one of three chunks
    whose ring has wrapped five times before decode begins.  Every logits
    row the session returns, at every served position, is the reference's
    full forward's row; the contexts cross the window and the rings wrap
    again while decoding."""
    sess = session(params, exact=exact)
    assert sorted(sess.executables) == ["decode", "prefill_16", "prefill_8"]
    assert sess.cache.ring_tokens == WINDOW
    seqs, slots = [], []
    for i, n in enumerate((5, 16, 43)):
        p = tokens(10 + i, n)
        slot = sess.try_alloc(n, 12, tokens=p)
        first, logits = sess.prefill(slot, p)
        assert_close_across_executables(np.asarray(logits),
                                        ref_logits(params, p)[-1])
        seqs.append(p + [first])
        slots.append(slot)
    for _ in range(12):
        toks, logits = sess.step()
        logits = np.asarray(logits)
        for slot, seq in zip(slots, seqs):
            assert_close_across_executables(
                logits[slot], ref_logits(params, seq)[-1])
            seq.append(toks[slot])
    assert sess.fallback_count() == 0


def test_a_fresh_prompt_in_chunks_is_the_prompt_in_one_bucket(params, plain):
    """A fresh prompt of 55 tokens goes as three chunks of 16 and a
    remainder of 7 (bucket 8); the same prompt through a session whose
    largest bucket holds it goes as one.  Both give the reference's row,
    with buckets + 1 executables each."""
    seq = tokens(21, 55)
    before = plain.block_report()
    slot = plain.try_alloc(len(seq), 3, tokens=seq)
    first, chunked = plain.prefill(slot, seq)
    after = plain.block_report()
    assert after["prefill_chunks"] - before["prefill_chunks"] == 4
    assert after["prefill_chunks_continued"] \
        - before["prefill_chunks_continued"] == 3
    whole = session(jax.tree.map(jnp.asarray, plain.params),
                    buckets=(8, 64))
    assert sorted(whole.executables) == ["decode", "prefill_64", "prefill_8"]
    slot2 = whole.try_alloc(len(seq), 3, tokens=seq)
    first2, one = whole.prefill(slot2, seq)
    assert whole.block_report()["prefill_chunks"] == 1
    assert first == first2
    assert_close_across_executables(np.asarray(chunked), np.asarray(one))
    assert_close_across_executables(np.asarray(chunked),
                                    ref_logits(params, seq)[-1])
    seq = seq + [first]
    for _ in range(3):
        toks, logits = plain.step()
        toks2, logits2 = whole.step()
        assert_close_across_executables(np.asarray(logits)[slot],
                                        ref_logits(params, seq)[-1])
        assert_close_across_executables(np.asarray(logits2)[slot2],
                                        np.asarray(logits)[slot])
        seq.append(toks[slot])
    # a prompt past max_prompt is still refused, and so is one past the
    # largest bucket where max_prompt is not stated
    with pytest.raises(MXNetError, match="longest admissible prompt 64"):
        plain.try_alloc(65, 3)


_bounded_scan = ops_attention.decode_attention


def _whole_walk(q, k_ctx, v_ctx, lengths, **kw):
    """``decode_attention`` as it was before the scan had a bound: rows
    labelled with their own index carry the same mask, and a call with
    ``k_positions`` walks every block of the table."""
    rows = jnp.arange(k_ctx.shape[-2], dtype=jnp.int32)
    return _bounded_scan(
        q, k_ctx, v_ctx, lengths,
        k_positions=jnp.broadcast_to(rows, (q.shape[0], rows.shape[0])),
        **kw)


# exact -> (max_len, ServeConfig sizes, prompt, the full layers' key block
# and table rows): three chunks at offsets 0, b, 2b of a table that is
# several key blocks wide (not exact, a table within 512 keys is one block)
CHUNKED = {
    True: (256, dict(CONF, exact=True), 43, PAGE, 80),
    False: (1024, dict(slots=2, page_size=16, buckets=(128,), max_new=16,
                       max_prompt=624, exact=False), 300, 320, 640),
}


@pytest.mark.parametrize("exact", [True, False])
def test_a_chunks_scan_ends_at_its_horizon_and_changes_no_bit(
        params, monkeypatch, exact):
    """A prompt of three chunks (offsets 0, 2048, 4096 at the cell's
    sizes; 0, 16, 32 and 0, 128, 256 here): each full layer's scan ends
    at the block that holds ``offset + bucket``, ``prefill_report()`` says
    so, and the first token, the logits and every byte the chunks left
    in the pages and the rings are those of a session whose scan walks
    the whole table; the logits are ``full_forward``'s row."""
    max_len, conf, n, block, rows = CHUNKED[exact]
    cfg = model_config(dict(HF, max_position_embeddings=max_len))
    bucket = max(conf["buckets"])
    assert laguna.prefill_block(rows // conf["page_size"],
                                conf["page_size"], exact) == block
    seq = tokens(77, n)
    sess = session(params, cfg, **conf)
    with monkeypatch.context() as patch:
        # the scan ``paged_prefill_attention`` falls back to on the CPU
        patch.setattr(ops_attention, "decode_attention", _whole_walk)
        whole = session(params, cfg, **conf)
    got, want = [], []
    for s, out in ((sess, got), (whole, want)):
        slot = s.try_alloc(n, 4, tokens=seq)
        first, logits = s.prefill(slot, seq)
        toks, after = s.step()
        out.extend([first, np.asarray(logits), toks[slot],
                    np.asarray(after)[slot]]
                   + [np.asarray(s.cache.pools[name]) for name in
                      ("k_pool", "v_pool", "kw_pool", "vw_pool")])
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert_close_across_executables(got[1], np.asarray(
        serve_model.full_forward(params, jnp.asarray([seq], jnp.int32), cfg,
                                 exact=exact))[0, -1])
    rep = sess.prefill_report()
    offsets = (0, bucket, 2 * bucket)
    visited = [min(-(-(off + bucket) // block) * block, rows)
               for off in offsets]
    assert visited == ([16, 32, 48] if exact else [320, 320, 640])
    # 2 full layers; the 3 window layers' rings are no tables
    assert rep == {"chunks": 3, "rows_visited": 2 * sum(visited),
                   "rows_capacity": 2 * 3 * rows,
                   "visited_share": sum(visited) / (3.0 * rows),
                   "prefill_kernel_layers": 0}     # the CPU runs the scan
    assert rep["rows_visited"] < rep["rows_capacity"]
    assert whole.prefill_report() == rep    # the host counts the same
    assert sorted(sess.executables) == sorted(whole.executables)
    assert len(sess.executables) == len(conf["buckets"]) + 1
    # the twin is another program: its loops' trip counts are constants
    name = "prefill_%d" % bucket
    assert sess.executables[name].as_text() \
        != whole.executables[name].as_text()


def test_a_slot_admitted_again_sees_no_stale_ring_row(params, plain):
    """A slot that served a request of 50 tokens (its rings full of that
    request's rows) and is admitted again gives a shorter request the rows
    the reference gives it: a ring row the new request has not written
    labels outside every band, and nothing is scrubbed."""
    slot, _, _ = _serve_one(plain, tokens(50, 41), 9)
    assert float(jnp.abs(plain.cache.pools["kw_pool"][:, slot]).min()) > 0
    plain.release(slot)
    again, rows, seq = _serve_one(plain, tokens(51, 3), 8)
    assert again == slot
    assert _worst(rows, ref_logits(params, seq), 2) <= LIMIT_SPACINGS


def _another_rotation(x, positions, group):
    """The interleaved pairs (2i, 2i + 1), DeepSeek-V3's, at the same
    frequencies."""
    rot, inv_freq, factor = laguna.rope_frequencies(group, x.shape[-1])
    angle = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = (jnp.cos(angle) * factor)[:, None, :], \
        (jnp.sin(angle) * factor)[:, None, :]
    even, odd = x[..., 0:rot:2], x[..., 1:rot:2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                       axis=-1).reshape(x.shape[:-1] + (rot,))
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def _ring_read_after_the_write(q, k, v, ring_k, ring_v, ring_pos, abs_pos,
                               window, exact, real=laguna.window_prefill):
    """A chunk that folds its rows into the ring first and attends over
    the ring then."""
    pools = {"kw_pool": ring_k[None, None], "vw_pool": ring_v[None, None]}
    first, count = abs_pos[0], abs_pos.shape[0]
    kv_cache.fold_into_ring(pools, "kw", 0, 0, k, first, count)
    kv_cache.fold_into_ring(pools, "vw", 0, 0, v, first, count)
    return real(q, k, v, pools["kw_pool"][0, 0], pools["vw_pool"][0, 0],
                kv_cache.ring_positions(ring_k.shape[0], first + count - 1),
                abs_pos, window, exact)


FAULTS = {
    "window one key short": dict(cfg=dataclasses.replace(
        CFG, sliding_window=WINDOW - 1)),
    "window one key long": dict(cfg=dataclasses.replace(
        CFG, sliding_window=WINDOW + 1)),
    "the gate left out": dict(patch=("_head_gate", lambda params, pre, att,
                                     u, heads, exact, scope="": att)),
    "interleaved rotation": dict(patch=("_rope", _another_rotation)),
    "ring read after the write": dict(patch=("window_prefill",
                                             _ring_read_after_the_write)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_comparison_can_fail(params, monkeypatch, fault):
    """The controls: each planted fault, served through prefill in chunks
    and decode, reads thousands of spacings from the reference (the sound
    session: under 32, the tests above)."""
    plant = FAULTS[fault]
    if "patch" in plant:
        monkeypatch.setattr(laguna, *plant["patch"])
    # a ring of whole pages holds 8 rows for a window of 7, 12 for one of 9
    sess = session(params, cfg=plant.get("cfg", CFG))
    _, rows, seq = _serve_one(sess, tokens(62, 37), 6)
    assert _worst(rows, ref_logits(params, seq), 36) > 30 * LIMIT_SPACINGS


def test_a_stale_ring_row_is_seen(params, plain):
    """The other control, planted in the cache: a ring row inside the band
    overwritten with another position's row."""
    seq = tokens(63, 20)
    slot = plain.try_alloc(len(seq), 8, tokens=seq)
    first, _ = plain.prefill(slot, seq)
    pool = plain.cache.pools["kw_pool"]
    plain.cache.pools["kw_pool"] = pool.at[1, slot, 19 % WINDOW].set(
        pool[1, slot, 17 % WINDOW])
    _, logits = plain.step()
    assert spacings_apart(np.asarray(logits)[slot], ref_logits(
        params, seq + [first])[-1]) > 30 * LIMIT_SPACINGS


# -- rings by the model's window, prompts by max_prompt ----------------------

@pytest.mark.parametrize("buckets, window, rows", [
    ((8, 16), 8, 8), ((8, 64), 8, 8), ((16, 32), 7, 8), ((16, 32), 9, 12)])
def test_a_ring_is_sized_by_the_window_whatever_the_buckets(params, buckets,
                                                            window, rows):
    """``sliding_window`` rows in whole pages, never the window plus the
    largest bucket; ``model.ring_pages`` (the GPT-2 block's rule) would
    hold 6 pages at buckets (8, 16) and 18 at (8, 64)."""
    cfg = dataclasses.replace(CFG, sliding_window=window)
    sess = session(params, cfg=cfg, buckets=buckets)
    assert laguna.ring_pages(cfg, sess.config) * PAGE == rows
    assert sess.cache.ring_tokens == sess.block_report()["ring_rows"] == rows
    assert rows <= window + 2 * PAGE
    assert sess.cache.pools["kw_pool"].shape == (3, 3, rows, 2 * 16)
    assert sess.cache.pools["k_pool"].shape[0] == 2      # the full layers
    assert sess.cache.n_window == 3 and sess.cache.hybrid
    assert serve_model.ring_pages(cfg, sess.config) != rows // PAGE


def test_at_the_published_sizes_a_ring_holds_512_rows():
    """Laguna-S-2.1's window of 512 at pages of 16, buckets (512, 2048):
    512 rows, under the 544 a window of whole pages plus two allows; a
    12 288-token prompt is six chunks, and a slot reserves 832 pages."""
    cfg = dataclasses.replace(CFG, sliding_window=512)
    conf = serve.ServeConfig(slots=16, page_size=16, buckets=(512, 2048),
                             max_prompt=12288, max_new=1024, exact=False)
    assert laguna.ring_pages(cfg, conf) * 16 == 512 <= 544
    assert conf.max_pages_per_slot == (12288 + 1024) // 16
    assert -(-12288 // max(conf.buckets)) == 6
    # what the GPT-2 block's rule would ask for the same window
    assert serve_model.ring_pages(cfg, conf) == 161


def test_what_the_block_refuses(params):
    assert laguna.REFUSES == ("spec_k", "kv_quant")
    for over in (dict(spec_k=2, draft="layers:1"), dict(kv_quant="int8")):
        with pytest.raises(MXNetError, match="does not support"):
            session(params, **over)
    for bad, match in (
            (dict(layer_types=("full_attention", "mla") + ("kda",) * 3),
             "layer_types"),
            (dict(num_attention_heads_per_layer=(4, 5, 6, 6, 4)),
             "a multiple of the 2 key/value heads"),
            (dict(sliding_window=0), "sliding_window >= 1"),
            (dict(rope_parameters={"full_attention":
                                   ROPE["full_attention"]}),
             "a group for 'sliding_attention'"),
            (dict(mlp_only_layers=(1,)), "lead the stack"),
            (dict(scoring_func="tanh"), "scoring_func"),
            (dict(shared_expert_intermediate_size=48), "a whole number"),
            (dict(tie_word_embeddings=True), "no tied head"),
            (dict(attn_head_dim=0), "attn_head_dim"),
            (dict(experts_held=(12, 8)), "experts_held")):
        with pytest.raises(MXNetError, match=match):
            dataclasses.replace(CFG, **bad).validate()


def test_int8_weights_serve_another_model(params):
    """Weight-only int8 is another model: it serves, and lands beyond
    the float32 limit."""
    sess = session(params, quant="int8")
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
    assert rep["prefill_chunks_continued"] == chunks - len(lengths)
    steps = rep["decode_steps"]
    assert steps == sess.decode_report()["steps"] >= 5
    # every slot's whole ring is read in each of the 3 window layers
    assert rep["window_rows_visited"] == steps * 3 * 3 * WINDOW
    assert 0 < rep["window_rows_in_band"] <= rep["window_rows_visited"]
    assert 0 < rep["full_rows_live"] <= 2 * sess.decode_report()[
        "blocks_visited"] * PAGE * 3
    # real rows x 4 experts a token x 4 expert layers, 2 of 16 held
    rows_fed = sum(lengths) + 3 * steps
    assert rep["assignments_asked"] == rows_fed * 4 * 4
    assert 0 < rep["assignments_held"] == rep["assignments_computed"] \
        < rep["assignments_asked"]
    assert rep["distinct_held_experts"] <= 2 * 4 * steps
    assert (rep["window_layers"], rep["full_layers"], rep["expert_layers"],
            rep["experts_held"], rep["sliding_window"], rep["ring_rows"],
            rep["kv_lanes"], rep["expert_kernel_layers"]) \
        == (3, 2, 4, 2, WINDOW, WINDOW, 32, 0)
    assert sess.fallback_count() == 0 and len(sess.executables) == 3
