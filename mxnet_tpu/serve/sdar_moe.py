"""The SDAR-MoE decoder block for the serving runtime: generation by
diffusion over blocks of a few tokens, over a QK-normed grouped-query
attention layer on K/V pages and softmax-routed experts with no shared
one, of which this chip may hold a share.

The seventh block beside ``model.py``'s GPT-2 one, ``latent_moe.py``,
``granite_hybrid.py``, ``bailing_hybrid.py``, ``laguna.py`` and
``lfm2_moe.py``, selected by ``ModelConfig(block="sdar_moe",
block_length=..., mask_token_id=..., denoising_steps=...,
confidence_threshold=...)`` through ``model.BLOCKS``, and the first that
is not autoregressive: it has a :func:`block_pass` where the others have a
``decode_step``, and the session and the scheduler step it by that
(``InferenceSession.step`` commits 0 to ``block_length`` tokens a slot).
The equations (``benchmark/references/sdar_moe_lm.py`` is their plain
form, and the tests hold this module to it; d = ``d_model``, D =
``attn_head_dim``, B = ``block_length``):

* ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))`` in every
  layer, no position table, no bias, an untied head after a final RMSNorm.
* Attn: ``lfm2_moe.py``'s (``q = W_q u`` as (H, D), ``k``, ``v`` as (KV,
  D), an RMSNorm of D values on every query and key head, all D values
  rotated in pairs ``(i, i + D / 2)``, query head ``h`` on key/value head
  ``h // (H / KV)``, scores ``q . k / sqrt(D)``) under another mask: **the
  row at position p sees every key below ``(p // B + 1) * B``**, causal
  from block to block and both ways inside a block.
* FFN: ``latent_moe.py``'s with softmax scores (laguna's): ``s =
  softmax(W_r u)`` over all ``n_routed_experts`` in float32, the
  ``num_experts_per_tok`` largest taken, ``w = s / sum_taken(s)``, the
  experts held here (``experts_held``) and nothing else.
* **Row p's logits are over the token AT position p**: a row that holds
  the mask token predicts itself.

Generation (the JetLM/SDAR repository's ``block_diffusion_generate``,
``low_confidence_dynamic``, greedy).  A prompt's ``P // B`` whole blocks
are prefilled under the mask above and their K/V written; prefill yields
no token.  The ``P % B`` tokens left open the first generated block, whose
other rows hold ``mask_token_id``.  :func:`block_pass` runs every slot's
open block over [the slot's committed K/V | the block's B rows]:

* the B rows' K/V are written at ``lengths .. lengths + B - 1`` and then
  attended (**write-then-attend**: every pass overwrites them, so the
  pages hold what the last pass wrote);
* on every still-masked row ``x0 = argmax`` and ``c = softmax(logits)[x0]``
  in float32, **on the device**; every masked row with ``c >
  confidence_threshold`` is unmasked or, where fewer than the pass's quota
  clear it, the quota's most confident ones (``quota``, a slot: ``B //
  denoising_steps``, one more in the first ``B % denoising_steps`` passes
  of a block; the host counts a block's passes).  A **denoise pass**; the
  host reads the tokens, which rows were unmasked and their ``c``;
* a block that came in with no mask left is thereby run once more over
  its final tokens: its **commit pass**, after which the host moves
  ``lengths`` by B and opens the next block.  At most ``denoising_steps +
  1`` passes a block, at least 2.

One departure from the published loop, stated in the reference and in the
benchmark's configuration too: **the mask token's own logit is left out of
the argmax** (``c`` stays the softmax over every logit).  A trained model
never picks the mask; seeded random weights would, once in ``vocab_size``
draws, and that block would never close.

The pass's attention is ``ops/attention.py:paged_decode_attention`` with
a key/value head's ``H / KV`` query heads x B rows as its rows, which
share the slot's horizon ``lengths + B``: the both-ways block is the form
its docstring allows.  Which reader that is follows the pools' layout at
rest, the cache's (``kv_cache.kv_pool_shape``): SDAR-30B-A3B's 4
key/value heads of 128 fold into 512 lanes, which on a TPU the
paged-attention kernel reads in its folded form, a head a lane tile with
the 32 rows as its left operand, each slot to its own length
(``decode_report()``'s ``paged_kernel_layers``); the ``fori_loop``
elsewhere.  A prefill yields no token, so nothing reads what its LAST
layer's attention and experts would add, and the compiler drops both.

``exact`` selects the M-invariant ``_mm`` as for the GPT-2 block, but the
bit-identity contract does not extend here: prefill's blocks and a pass's
paged read associate differently, so a pass agrees with a full forward to
rounding, not to the bit.

Counters: every executable folds what its routers did into
``counters["moe_stats"]`` (:data:`MOE_COLUMNS`), what its attention did
into ``counters["attn_stats"]`` (:data:`ATTN_COLUMNS`, the window / full
block's names: a block pass counts as a decode step, and there is no
window layer) and what its passes did into ``counters["diffusion_stats"]``
(:data:`DIFFUSION_COLUMNS`); ``InferenceSession.block_report()`` reads all
three.
"""
from __future__ import annotations

from ..base import MXNetError
from ..ops.attention import (decode_attention, paged_decode_attention,
                             paged_prefill_attention)
from . import latent_moe
from .kv_cache import append_rows, kv_pool_shape
from .laguna import ATTN_COLUMNS, MOE_COLUMNS
from .latent_moe import (_ffn_held, _head, _resolve, fold_named, held_range,
                         prefill_block, read_named)
# the expert layer is the latent block's, and so is what it asks of XLA
from .latent_moe import compiler_options  # noqa: F401
from .layers import rms_norm
from .lfm2_moe import _embed, _qkv, _scale
from .model import _mm, check_param_shapes
# the passes run the GPT-2 block's paged reader: its report
from .model import decode_report  # noqa: F401

BLOCK = "sdar_moe"

# ServeConfig features a session over this block refuses at construction
REFUSES = ("spec_k", "kv_quant", "prefix_pages", "oversub")
REFUSES_WHY = ("a block pass is no verify step and its rows have no scale "
               "pool; a published page may end inside a block, whose rows "
               "see each other; a parked request's replay check takes a "
               "first token from prefill, which yields none here: "
               "ROADMAP M7")

# diffusion_stats columns, counted by block_pass over the live slots:
# slot_passes = denoise_slot_passes (the block came in with a mask) +
# commit_slot_passes (it came in with none; = blocks_committed); the rows a
# denoise pass unmasked because they cleared the threshold, and those the
# quota took although they did not; tokens_committed: the rows of committed
# blocks that are generated tokens the request asked for (the host says how
# many: a first block's prompt rows and a last block's tail are not)
DIFFUSION_COLUMNS = ("slot_passes", "denoise_slot_passes",
                     "commit_slot_passes", "rows_unmasked_by_threshold",
                     "rows_unmasked_by_quota", "blocks_committed",
                     "tokens_committed")
# counter name -> its columns
COUNTERS = {"moe_stats": MOE_COLUMNS, "attn_stats": ATTN_COLUMNS,
            "diffusion_stats": DIFFUSION_COLUMNS}


def validate(cfg):
    sizes = (cfg.attn_head_dim, cfg.max_len, cfg.kv_heads, cfg.block_length)
    if min(sizes) < 1 or cfg.attn_head_dim % 2:
        raise MXNetError(
            "ModelConfig(block=%r) needs an even attn_head_dim, max_len, "
            "num_key_value_heads and block_length (got %r)" % (BLOCK, sizes))
    if cfg.num_heads % cfg.kv_heads:
        raise MXNetError("%d query heads over %d key/value heads"
                         % (cfg.num_heads, cfg.kv_heads))
    if not 1 <= cfg.denoising_steps <= cfg.block_length:
        raise MXNetError("denoising_steps %d outside 1..block_length %d"
                         % (cfg.denoising_steps, cfg.block_length))
    if not 0 <= cfg.mask_token_id < cfg.vocab_size:
        raise MXNetError("mask_token_id %d outside the vocabulary's %d rows"
                         % (cfg.mask_token_id, cfg.vocab_size))
    if cfg.scoring_func != "softmax" or cfg.n_shared_experts \
            or cfg.first_k_dense or cfg.tie_word_embeddings:
        raise MXNetError(
            "block %r routes by softmax scores in every layer, has no "
            "shared expert and an untied head (got scoring_func %r, "
            "n_shared_experts %d, first_k_dense %d, tie_word_embeddings %r)"
            % (BLOCK, cfg.scoring_func, cfg.n_shared_experts,
               cfg.first_k_dense, cfg.tie_word_embeddings))
    latent_moe.validate_ffn(cfg)
    return cfg


def param_shapes(cfg):
    """{parameter name: shape}: matrices (out, in) as ``_mm`` takes them;
    the attention's names are the short-convolution block's, the FFN's the
    latent block's."""
    d, hd, kv = cfg.d_model, cfg.head_dim, cfg.kv_heads
    out = {"tok_embed_weight": (cfg.vocab_size, d), "final_norm_gamma": (d,),
           "lm_head_weight": (cfg.vocab_size, d)}
    for i in range(cfg.num_layers):
        p = "blk%d_" % i
        out.update({p + "attn_norm_gamma": (d,),
                    p + "q_weight": (cfg.num_heads * hd, d),
                    p + "k_weight": (kv * hd, d),
                    p + "v_weight": (kv * hd, d),
                    p + "q_norm_gamma": (hd,),
                    p + "k_norm_gamma": (hd,),
                    p + "o_weight": (d, cfg.num_heads * hd)})
        out.update(latent_moe.ffn_param_shapes(cfg, i))
    return out


def init_params(cfg, seed=0, scale=0.02):
    """Fresh float32 parameters (tests and benches): normal matrices, norm
    scales one."""
    return latent_moe.init_from_shapes(param_shapes(cfg), seed, scale)


def check_params(params, cfg):
    """The parameter dict has exactly the architecture's shapes."""
    check_param_shapes(params, param_shapes(cfg), BLOCK)


def latent_dim(cfg):
    """0: every layer keeps per-head K and V pools."""
    return 0


def state_shapes(cfg):
    """Slot-private device state beside the pages: none (a slot's open
    block is a few integers, and the session's)."""
    return {}


def init_counters(cfg):
    """``moe_stats``, ``attn_stats`` and ``diffusion_stats``, (2, columns)
    int32 each, folded by the executables: row 0 the low 30 bits of each
    count, row 1 the carries."""
    import jax.numpy as jnp

    return {name: jnp.zeros((2, len(columns)), jnp.int32)
            for name, columns in COUNTERS.items()}


def guard_tag(cfg):
    """Another block altogether: key/value heads, the experts held of
    those routed, the block's length, passes and mask token."""
    return "-%s-kv%dx%d-e%dof%dk%d-b%ds%dm%d" % (
        BLOCK, cfg.kv_heads, cfg.head_dim, held_range(cfg)[1],
        cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.block_length,
        cfg.denoising_steps, cfg.mask_token_id)


def report(counters, cfg):
    """Host side: the three counters as exact Python ints under their
    names (``InferenceSession.block_report`` documents them), with the
    layers of each kind, the experts held, the block's length and passes
    and the width of the K/V pools' last axis at rest."""
    out = {}
    for name, columns in COUNTERS.items():
        out.update(read_named(counters[name], columns))
    out["full_layers"] = out["expert_layers"] = cfg.num_layers
    out["window_layers"] = 0
    out["experts_held"] = held_range(cfg)[1]
    out["block_length"] = cfg.block_length
    out["denoising_steps"] = cfg.denoising_steps
    out["kv_lanes"] = kv_pool_shape(1, 1, 1, cfg.kv_heads,
                                    cfg.head_dim)[-1]
    return out


def pass_quota(cfg, passes_done):
    """Rows the next denoise pass of a block unmasks at least, after
    ``passes_done`` of them."""
    b, steps = cfg.block_length, cfg.denoising_steps
    return b // steps + (passes_done < b % steps)


def _count(counters, incs, attn, diffusion=None):
    """Fold one executable's routers (``incs``, a dict a layer), its
    attention's counts and its passes' into the three counters."""
    moe = {}
    for layer in incs:
        for name, value in layer.items():
            moe[name] = moe.get(name, 0) + value
    if "decode_steps" not in attn:      # what a block pass had to read
        moe["distinct_held_experts"] = 0
    incs = {"moe_stats": moe, "attn_stats": attn,
            "diffusion_stats": diffusion or {}}
    return dict(counters, **{
        name: fold_named(counters[name], columns, incs[name])
        for name, columns in COUNTERS.items()})


def _horizons(positions, cfg):
    """Keys the row at each of ``positions`` sees: its own block's and
    every block's before it."""
    return (positions // cfg.block_length + 1) * cfg.block_length


def full_forward(params, tokens, cfg, exact, block=None):
    """(n, T) int tokens -> (n, T, V) logits under the block-causal mask,
    row p over the token at p: the forward the cached paths are held
    against.  ``block`` is the attention's key block (T by default)."""
    import jax
    import jax.numpy as jnp

    params, dequantized = _resolve(params)
    t = tokens.shape[-1]
    if t > cfg.max_len:
        raise MXNetError("sequence length %d > model max_len %d"
                         % (t, cfg.max_len))
    kv, hd = cfg.kv_heads, cfg.head_dim
    group = cfg.num_heads // kv
    block = block or t
    pad = -t % block
    positions = jnp.arange(t, dtype=jnp.int32)
    # a last block that the sequence ends inside sees the keys there are
    seen = jnp.repeat(jnp.minimum(_horizons(positions, cfg), t), group)[None]
    valid = jnp.ones((t,), bool)

    def one(seq):
        x = _embed(params, seq)
        for i in range(cfg.num_layers):
            pre = "blk%d_" % i
            u = rms_norm(x, params[pre + "attn_norm_gamma"],
                         cfg.rms_norm_eps)
            q, k, v = _qkv(params, pre, u, positions, cfg, exact)
            k, v = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))
                            ).transpose(1, 0, 2)[None] for a in (k, v))
            att = decode_attention(
                q.transpose(1, 0, 2, 3).reshape(1, kv, t * group, hd), k, v,
                seen, scale=_scale(cfg), block=block, mi=exact)
            att = att.reshape(kv, t, group * hd).transpose(1, 0, 2)
            out = _mm(att.reshape(t, -1), params[pre + "o_weight"], exact)
            x, _ = _ffn_held(params, i, x + out, cfg, exact, valid,
                             dequantized)
        return _head(params, x, cfg, exact)

    return jax.vmap(one)(tokens)


def prefill_forward(params, tokens, length, offset, table_row, pools,
                    counters, cfg, page_size, exact, kv_quant="", slot=None):
    """Bucketed prefill of one chunk of a prompt's whole blocks
    (``model.prefill_forward``'s contract: page-aligned ``offset``,
    ``length`` real tokens, rows past the table on the trash page;
    ``kv_quant`` belongs to a feature this block refuses).  ``length`` is
    a multiple of ``block_length``, which divides the page: every layer
    writes the chunk's key/value heads into the slot's pages, gathers them
    and attends with per-row horizons ``(position // B + 1) * B``, none of
    which reaches past the chunk's real rows.  No head: prefill yields no
    token, and the first of the four results is -1.
    -> (-1, None, pools, counters)."""
    import jax
    import jax.numpy as jnp

    params, dequantized = _resolve(params)
    _, t_b = tokens.shape
    if t_b % page_size or page_size % cfg.block_length:
        raise MXNetError(
            "bucket length %d not a multiple of page size %d, or that not "
            "of block_length %d" % (t_b, page_size, cfg.block_length))
    max_pages = table_row.shape[0]
    pools = dict(pools)
    trash = pools["k_pool"].shape[1] - 1
    offs = jnp.arange(t_b, dtype=jnp.int32)
    abs_pos = offset + offs
    idx = abs_pos // page_size
    pages = jnp.where(idx < max_pages,
                      table_row[jnp.clip(idx, 0, max_pages - 1)], trash)
    offsets = abs_pos % page_size
    valid = offs < length
    scan_block = prefill_block(max_pages, page_size, exact)
    seen = _horizons(abs_pos, cfg)
    x = _embed(params, tokens[0])
    incs = []
    for i in range(cfg.num_layers):
        pre = "blk%d_" % i
        u = rms_norm(x, params[pre + "attn_norm_gamma"], cfg.rms_norm_eps)
        q, k, v = _qkv(params, pre, u, abs_pos, cfg, exact)
        with jax.named_scope("bdiff_prefill"):
            append_rows(pools, "k", i, pages, offsets, k, "")
            append_rows(pools, "v", i, pages, offsets, v, "")
            att = paged_prefill_attention(
                q, pools["k_pool"], pools["v_pool"], i, table_row, abs_pos,
                page_size, scan_block, mi=exact, scale=_scale(cfg),
                horizons=seen)
        out = _mm(att.reshape(t_b, -1), params[pre + "o_weight"], exact)
        x, inc = _ffn_held(params, i, x + out, cfg, exact, valid,
                           dequantized)
        incs.append(inc)
    return jnp.full((), -1, jnp.int32), None, pools, _count(
        counters, incs,
        dict(prefill_chunks=1, prefill_chunks_continued=offset != 0))


def _unmask(logits, tokens, masked, quota, cfg):
    """One pass's choice on the device.  logits (S, B, V), tokens (S, B)
    as the pass took them, masked (S, B) bool: the live slots' rows that
    hold the mask, quota (S,) -> (tokens after the pass, unmasked (S, B)
    bool: the rows this pass unmasked, confidence (S, B) float32: ``c`` of
    the masked rows, 0 elsewhere, by_threshold (S,) bool: whether a slot's
    rows cleared the threshold or were the quota's most confident)."""
    import jax
    import jax.numpy as jnp

    b = tokens.shape[1]
    logits = logits.astype(jnp.float32)
    open_ = logits.at[..., cfg.mask_token_id].set(-jnp.inf)
    x0 = jnp.argmax(open_, axis=-1).astype(jnp.int32)
    c = jnp.exp(jnp.max(open_, axis=-1)
                - jax.nn.logsumexp(logits, axis=-1))
    c = jnp.where(masked, c, -jnp.inf)
    over = c > cfg.confidence_threshold
    by_threshold = over.sum(axis=-1) >= quota
    # a row's rank among its block's: the rows more confident than it,
    # an earlier row first where two are equally so
    row = jnp.arange(b)
    ahead = (c[:, None, :] > c[:, :, None]) | (
        (c[:, None, :] == c[:, :, None]) & (row[None, :] < row[:, None]))
    by_quota = masked & (ahead.sum(axis=-1) < quota[:, None])
    unmasked = jnp.where(by_threshold[:, None], over, by_quota)
    return (jnp.where(unmasked, x0, tokens), unmasked,
            jnp.where(masked, c, 0.0), by_threshold)


def block_pass(params, tokens, quota, fresh, lengths, tables, pools,
               counters, cfg, page_size, exact, kv_quant=""):
    """One pass over every slot's open block, where the other blocks have
    their ``decode_step``.

    tokens: (S, B) int32, each slot's open block, ``mask_token_id`` on the
    rows still masked (an idle slot: anything else; the session's
    executable selects a slot's rows in front of this call, the host's or
    those the pass before left on the device: ``after`` below, which is
    why a pass can be launched before the last one is read); quota: (S,) int32,
    rows this pass unmasks at least in a slot whose block has a mask
    (:func:`pass_quota`), -1 for an idle slot; fresh: (S,) int32, the
    tokens a slot's block delivers when it is committed, for the count
    alone; lengths: (S,) int32, K/V rows committed a slot (the block's
    first position; 0 for an idle slot); tables: (S, max_pages) int32;
    pools and counters as in ``model.prefill_forward``.  Writes the B
    rows' key/value heads at ``lengths .. lengths + B - 1``, attends every
    row over the slot's ``lengths + B`` keys, and unmasks
    (:func:`_unmask`).
    -> (tokens after the pass (S, B), unmasked (S, B) bool, confidence (S,
    B) float32: ``c`` of the rows that came in masked, logits (S, B, V),
    pools, counters)."""
    import jax
    import jax.numpy as jnp

    params, dequantized = _resolve(params)
    s, b = tokens.shape
    n = s * b
    max_pages = tables.shape[1]
    kv, hd = cfg.kv_heads, cfg.head_dim
    group = cfg.num_heads // kv
    pools = dict(pools)
    live = quota >= 0
    abs_pos = lengths[:, None] + jnp.arange(b, dtype=lengths.dtype)
    page_slot = jnp.clip(abs_pos // page_size, 0, max_pages - 1)
    pages = jnp.take_along_axis(tables, page_slot, axis=1).reshape(n)
    offsets = (abs_pos % page_size).reshape(n)
    valid = jnp.repeat(live, b)
    x = _embed(params, tokens.reshape(n))
    incs = []
    for i in range(cfg.num_layers):
        pre = "blk%d_" % i
        u = rms_norm(x, params[pre + "attn_norm_gamma"], cfg.rms_norm_eps)
        q, k, v = _qkv(params, pre, u, abs_pos.reshape(n), cfg, exact)
        with jax.named_scope("bdiff_pass"):
            append_rows(pools, "k", i, pages, offsets, k, "")
            append_rows(pools, "v", i, pages, offsets, v, "")
            # a key/value head's rows: its query heads of every row of the
            # block, which share the slot's horizon
            att = paged_decode_attention(
                q.reshape(s, b, kv, group, hd).transpose(0, 2, 1, 3, 4
                                                         ).reshape(
                    s, kv, b * group, hd),
                pools["k_pool"], pools["v_pool"], i, tables, lengths + b,
                page_size, mi=exact, scale=_scale(cfg))
            att = att.reshape(s, kv, b, group * hd).transpose(0, 2, 1, 3)
        out = _mm(att.reshape(n, -1), params[pre + "o_weight"], exact)
        x, inc = _ffn_held(params, i, x + out, cfg, exact, valid,
                           dequantized)
        incs.append(inc)
    logits = _head(params, x, cfg, exact).reshape(s, b, -1)
    with jax.named_scope("bdiff_unmask"):
        masked = (tokens == cfg.mask_token_id) & live[:, None]
        after, unmasked, confidence, by_threshold = _unmask(
            logits, tokens, masked, quota, cfg)
        denoise = masked.any(axis=1)
        commit = live & ~denoise
        taken = unmasked.sum(axis=1)

        def total(x):
            return x.sum().astype(jnp.int32)

        diffusion = dict(
            slot_passes=total(live), denoise_slot_passes=total(denoise),
            commit_slot_passes=total(commit), blocks_committed=total(commit),
            rows_unmasked_by_threshold=total(
                jnp.where(by_threshold, taken, 0)),
            rows_unmasked_by_quota=total(jnp.where(by_threshold, 0, taken)),
            tokens_committed=total(jnp.where(commit, fresh, 0)))
    return after, unmasked, confidence, logits, pools, _count(
        counters, incs,
        dict(decode_steps=1,
             full_rows_live=cfg.num_layers * total(
                 jnp.where(live, lengths + b, 0))),
        diffusion)
