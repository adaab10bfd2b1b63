"""The LFM2-MoE decoder block for the serving runtime: double-gated short
convolutions whose whole memory a slot is two rows, a few grouped-query
attention layers with a norm on every query and key head over K/V pages,
and sigmoid-routed experts with a selection bias and no shared expert, of
which this chip may hold a share; the head is the embedding.

The sixth block beside ``model.py``'s GPT-2 one, ``latent_moe.py``,
``granite_hybrid.py``, ``bailing_hybrid.py`` and ``laguna.py``, selected by
``ModelConfig(block="lfm2_moe", ...)`` through ``model.BLOCKS``.  The
equations (``benchmark/references/lfm2_moe_lm.py`` is their plain form, and
the tests hold this module to it; d = ``d_model``, D = ``attn_head_dim``,
K = ``conv_L_cache``):

* ``h = x + Mixer(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, no position
  table, no bias, no multiplier on the embedding; after the last layer
  ``logits = E . RMSNorm(y)`` with the embedding's own matrix.
* Mixer of a ``"conv"`` layer: ``[B | C | z] = W_in u`` (d -> 3 d, the
  thirds in this order); ``g = B * z``; ``c_t = sum_j w_j g_{t - (K - 1) +
  j}``, depthwise and causal, tap ``K - 1`` on the current row, ``g`` zero
  before the sequence starts; ``W_out (C * c)``.  **The cache holds, a slot
  a layer, the last ``K - 1`` rows of ``g``** (:func:`state_shapes`:
  ``conv_state``), and no page.
* Mixer of a ``"full_attention"`` layer: ``q = W_q u`` as (H, D), ``k = W_k
  u``, ``v = W_v u`` as (KV, D); every query and key head through an
  RMSNorm of D values (one scale vector for the heads of a kind), then
  rotated: all D values, pairs ``(i, i + D / 2)``, ``inv_freq_i =
  rope_theta^(-2i / D)``; query head ``h`` reads key/value head ``h // (H /
  KV)``; scores ``q . k / sqrt(D)``, causal; ``W_o``.  **The pages hold the
  key/value heads only**, as the Mamba-2 block's attention layers keep
  them (heads narrower than a lane tile fold into the pools' last axis:
  ``kv_cache.kv_pool_shape``).
* FFN: ``latent_moe.py``'s: one SwiGLU of ``d_ff`` in the first
  ``first_k_dense`` layers, then ``s = sigmoid(W_r u)`` over all
  ``n_routed_experts`` in float32, the ``num_experts_per_tok`` largest
  ``s + b`` taken (the bias chooses and does not weigh), ``w = s /
  sum_taken(s)``, the experts held here (``experts_held``) and nothing
  else: a row none of whose experts is held here gets ``y = h``.

Prefill runs the convolution over [the slot's rows | the chunk's ``g``]
(``ops/mamba2.py:causal_conv``, the Mamba-2 block's, at three taps and no
bias): zeros after ``alloc``, or what the chunk before it left; the rows
written back are the last REAL rows', reaching back across the chunk's
start where the chunk has fewer than ``K - 1``, so bucket padding writes
nothing.  Decode (``conv_step``) moves every live slot's rows by one token
in the donated pool; an idle slot's rows are written back as they were.
An attention layer writes the chunk's key/value heads into the slot's
pages at the chunk's offset and attends with per-row horizons; decode
appends a row a slot and reads the pages in place.

``exact`` selects the M-invariant ``_mm`` as for the GPT-2 block, but the
bit-identity contract does not extend here: prefill's blocks and decode's
paged read associate differently, so decode agrees with a full forward to
rounding, not to the bit.

Counters: every executable folds what its routers did into
``counters["moe_stats"]`` (:data:`MOE_COLUMNS`), what its attention did
into ``counters["attn_stats"]`` (:data:`ATTN_COLUMNS`, the window / full
block's names: this block has no window layer and counts 0 there) and what
its convolutions scanned into ``counters["conv_stats"]``
(:data:`CONV_COLUMNS`, the Mamba-2 block's names);
``InferenceSession.block_report()`` reads all three.
"""
from __future__ import annotations

from ..base import MXNetError
from ..ops.attention import (flash_attention, paged_decode_attention,
                             paged_prefill_attention)
from ..ops.mamba2 import causal_conv, conv_step
from . import latent_moe
from .kv_cache import append_rows, kv_pool_shape
from .laguna import ATTN_COLUMNS, MOE_COLUMNS, _rope
from .latent_moe import (_ffn_held, _resolve, fold_named, held_range,
                         prefill_block, read_named)
from .layers import rms_norm
# the expert layer is the latent block's and the K/V pools the Mamba-2
# block's, and so is what both ask of XLA (the same pass would carry the
# expert stacks and the folded pools through a step as bfloat16)
from .latent_moe import compiler_options  # noqa: F401
from .model import _mm, check_param_shapes
# the attention layers run the GPT-2 block's paged reader: its report
from .model import decode_report  # noqa: F401

BLOCK = "lfm2_moe"
KINDS = ("conv", "full_attention")

# ServeConfig features a session over this block refuses at construction
REFUSES = ("spec_k", "kv_quant")
REFUSES_WHY = ("a rejected draft would need the convolution rows before "
               "it, and nothing snapshots a slot's state; the rows are "
               "float32 values that every token shifts, with no scale "
               "pool: ROADMAP M4")

# conv_stats columns: the prefill chunks that began on the zeros ``alloc``
# left and those that took up the rows an earlier chunk wrote; the rows
# those chunks scanned, real and bucket padding
CONV_COLUMNS = ("prefills_from_zero", "prefills_carried", "rows_valid",
                "rows_padded")
# counter name -> its columns
COUNTERS = {"moe_stats": MOE_COLUMNS, "attn_stats": ATTN_COLUMNS,
            "conv_stats": CONV_COLUMNS}


def validate(cfg):
    sizes = (cfg.attn_head_dim, cfg.d_ff, cfg.max_len, cfg.kv_heads)
    if min(sizes) < 1 or cfg.attn_head_dim % 2 or cfg.conv_L_cache < 2:
        raise MXNetError(
            "ModelConfig(block=%r) needs an even attn_head_dim, d_ff, "
            "max_len, num_key_value_heads and conv_L_cache >= 2 (got %r, "
            "conv_L_cache %d)" % (BLOCK, sizes, cfg.conv_L_cache))
    if len(cfg.layer_types) != cfg.num_layers \
            or set(cfg.layer_types) - set(KINDS):
        raise MXNetError("layer_types %r: %d layers, each %s"
                         % (cfg.layer_types, cfg.num_layers,
                            " or ".join(map(repr, KINDS))))
    if cfg.num_heads % cfg.kv_heads:
        raise MXNetError("%d query heads over %d key/value heads"
                         % (cfg.num_heads, cfg.kv_heads))
    if cfg.scoring_func != "sigmoid" or cfg.n_shared_experts:
        raise MXNetError(
            "block %r routes by sigmoid scores with a selection bias and "
            "has no shared expert (got scoring_func %r, n_shared_experts "
            "%d)" % (BLOCK, cfg.scoring_func, cfg.n_shared_experts))
    if not cfg.tie_word_embeddings:
        raise MXNetError("block %r has no untied head" % BLOCK)
    latent_moe.validate_ffn(cfg)
    return cfg


def param_shapes(cfg):
    """{parameter name: shape}: matrices (out, in) as ``_mm`` takes them,
    the depthwise filter (channels, taps) as ``causal_conv`` does; the
    FFN's names are the latent block's."""
    d, hd, kv = cfg.d_model, cfg.head_dim, cfg.kv_heads
    out = {"tok_embed_weight": (cfg.vocab_size, d), "final_norm_gamma": (d,)}
    for i, kind in enumerate(cfg.layer_types):
        p = "blk%d_" % i
        out[p + "operator_norm_gamma"] = (d,)
        if kind == "conv":
            out.update({p + "in_weight": (3 * d, d),
                        p + "conv_weight": (d, cfg.conv_L_cache),
                        p + "out_weight": (d, d)})
        else:
            out.update({p + "q_weight": (cfg.num_heads * hd, d),
                        p + "k_weight": (kv * hd, d),
                        p + "v_weight": (kv * hd, d),
                        p + "q_norm_gamma": (hd,),
                        p + "k_norm_gamma": (hd,),
                        p + "o_weight": (d, cfg.num_heads * hd)})
        out.update(latent_moe.ffn_param_shapes(cfg, i))
    return out


def init_params(cfg, seed=0, scale=0.02):
    """Fresh float32 parameters (tests and benches): normal matrices, norm
    scales one, the router's selection bias zero; the depthwise filter
    normal at 1 / sqrt(3 * taps), the variance of a ``Conv1d``'s default
    (rows no token can tell from zeros test nothing)."""
    params = latent_moe.init_from_shapes(param_shapes(cfg), seed, scale)
    gain = (3.0 * cfg.conv_L_cache) ** -0.5 / scale
    return {name: leaf * gain if name.endswith("conv_weight") else leaf
            for name, leaf in params.items()}


def check_params(params, cfg):
    """The parameter dict has exactly the architecture's shapes."""
    check_param_shapes(params, param_shapes(cfg), BLOCK)


def latent_dim(cfg):
    """0: the attention layers keep per-head K and V pools."""
    return 0


def state_shapes(cfg):
    """What a slot holds in every convolution layer, and all it holds
    there: name -> (layers, one slot's shape a layer, dtype)."""
    return {"conv_state": (cfg.layer_types.count("conv"),
                           (cfg.conv_L_cache - 1, cfg.d_model), "float32")}


def init_counters(cfg):
    """``moe_stats``, ``attn_stats`` and ``conv_stats``, (2, columns)
    int32 each, folded by the executables: row 0 the low 30 bits of each
    count, row 1 the carries."""
    import jax.numpy as jnp

    return {name: jnp.zeros((2, len(columns)), jnp.int32)
            for name, columns in COUNTERS.items()}


def guard_tag(cfg):
    """Another block altogether: key/value heads, the taps, the experts
    held of those routed, the layer pattern's initials."""
    return "-%s-kv%dx%d-c%d-e%dof%dk%d-%s" % (
        BLOCK, cfg.kv_heads, cfg.head_dim, cfg.conv_L_cache,
        held_range(cfg)[1], cfg.n_routed_experts, cfg.num_experts_per_tok,
        "".join(t[0] for t in cfg.layer_types))


def report(counters, cfg):
    """Host side: the three counters as exact Python ints under their
    names (``InferenceSession.block_report`` documents them), with the
    layers of each kind, the experts held, the bytes of state a slot holds
    and the width of the K/V pools' last axis at rest."""
    import math

    import numpy as np

    out = {}
    for name, columns in COUNTERS.items():
        out.update(read_named(counters[name], columns))
    out["conv_layers"] = cfg.layer_types.count("conv")
    out["full_layers"] = cfg.layer_types.count("full_attention")
    out["window_layers"] = 0
    out["expert_layers"] = cfg.num_layers - cfg.first_k_dense
    out["experts_held"] = held_range(cfg)[1]
    out["state_bytes_per_slot"] = sum(
        layers * math.prod(shape) * np.dtype(dtype).itemsize
        for layers, shape, dtype in state_shapes(cfg).values())
    out["kv_lanes"] = kv_pool_shape(1, 1, 1, cfg.kv_heads,
                                    cfg.head_dim)[-1]
    return out


def _count(counters, incs, attn, conv=None):
    """Fold one executable's routers (``incs``, a dict a layer), its
    attention's counts and its convolutions' into the three counters."""
    moe = {}
    for layer in incs:
        for name, value in layer.items():
            moe[name] = moe.get(name, 0) + value
    if "decode_steps" not in attn:      # what a decode step had to read
        moe["distinct_held_experts"] = 0
    incs = {"moe_stats": moe, "attn_stats": attn, "conv_stats": conv or {}}
    return dict(counters, **{
        name: fold_named(counters[name], columns, incs[name])
        for name, columns in COUNTERS.items()})


def _gates(params, pre, u, cfg, exact):
    """u (N, d) -> (g = B * z, what the convolution runs over; C, the gate
    on its output), (N, d) each."""
    import jax

    d = cfg.d_model
    with jax.named_scope("sconv_in"):
        bcz = _mm(u, params[pre + "in_weight"], exact)
        return bcz[:, :d] * bcz[:, 2 * d:], bcz[:, d:2 * d]


def _conv_out(params, pre, c, conv, exact):
    import jax

    with jax.named_scope("sconv_out"):
        return _mm(c * conv, params[pre + "out_weight"], exact)


def _conv_rows(params, pre, u, context, length, cfg, exact):
    """One sequence's rows u (T, d) through a short-convolution mixer,
    from ``context`` (K - 1, d), the rows of ``g`` before row 0; the first
    ``length`` rows are real.  -> (out (T, d), context after row
    ``length - 1``)."""
    import jax

    g, c = _gates(params, pre, u, cfg, exact)
    with jax.named_scope("sconv_mix"):
        conv, context = causal_conv(g, context, params[pre + "conv_weight"],
                                    0.0, length)
    return _conv_out(params, pre, c, conv, exact), context


def _qkv(params, pre, u, positions, cfg, exact):
    """u (N, d) -> normed and rotated q (N, KV, G, D) with a key/value
    head's query heads as its rows, normed and rotated k and plain v
    (N, KV, D)."""
    import jax

    n, kv, hd = u.shape[0], cfg.kv_heads, cfg.head_dim
    q = _mm(u, params[pre + "q_weight"], exact).reshape(n, cfg.num_heads, hd)
    k = _mm(u, params[pre + "k_weight"], exact).reshape(n, kv, hd)
    with jax.named_scope("gqa_qknorm"):
        q = rms_norm(q, params[pre + "q_norm_gamma"], cfg.rms_norm_eps)
        k = rms_norm(k, params[pre + "k_norm_gamma"], cfg.rms_norm_eps)
    with jax.named_scope("gqa_rope"):
        group = {"rope_theta": cfg.rope_theta}      # plain, the whole head
        q, k = _rope(q, positions, group), _rope(k, positions, group)
    return (q.reshape(n, kv, cfg.num_heads // kv, hd), k,
            _mm(u, params[pre + "v_weight"], exact).reshape(n, kv, hd))


def _scale(cfg):
    """The score scale, stated: 1 / 8 at the published heads of 64."""
    return cfg.head_dim ** -0.5


def _head(params, x, cfg, exact):
    x = rms_norm(x, params["final_norm_gamma"], cfg.rms_norm_eps)
    return _mm(x, params["tok_embed_weight"], exact)


def _embed(params, tokens):
    import jax.numpy as jnp

    return jnp.take(params["tok_embed_weight"], tokens.astype(jnp.int32),
                    axis=0)


def full_forward(params, tokens, cfg, exact, block=None):
    """(n, T) int tokens -> (n, T, V) logits from zero convolution rows:
    the forward the cached paths are held against.  ``block`` is the
    attention's key block (T by default)."""
    import jax
    import jax.numpy as jnp

    params, dequantized = _resolve(params)
    t = tokens.shape[-1]
    if t > cfg.max_len:
        raise MXNetError("sequence length %d > model max_len %d"
                         % (t, cfg.max_len))
    positions = jnp.arange(t, dtype=jnp.int32)
    valid = jnp.ones((t,), bool)
    zeros = state_shapes(cfg)["conv_state"][1]
    group = cfg.num_heads // cfg.kv_heads

    def one(seq):
        x = _embed(params, seq)
        for i, kind in enumerate(cfg.layer_types):
            pre = "blk%d_" % i
            u = rms_norm(x, params[pre + "operator_norm_gamma"],
                         cfg.rms_norm_eps)
            if kind == "conv":
                out, _ = _conv_rows(params, pre, u,
                                    jnp.zeros(zeros, u.dtype), t, cfg, exact)
            else:
                q, k, v = _qkv(params, pre, u, positions, cfg, exact)
                k, v = (jnp.repeat(a, group, axis=1).transpose(1, 0, 2)
                        for a in (k, v))
                att = flash_attention(
                    q.reshape(t, cfg.num_heads, -1).transpose(1, 0, 2), k, v,
                    causal=True, scale=_scale(cfg), block=block or t,
                    mi=exact)
                out = _mm(att.transpose(1, 0, 2).reshape(t, -1),
                          params[pre + "o_weight"], exact)
            x, _ = _ffn_held(params, i, x + out, cfg, exact, valid,
                             dequantized)
        return _head(params, x, cfg, exact)

    return jax.vmap(one)(tokens)


def prefill_forward(params, tokens, length, offset, table_row, pools,
                    counters, cfg, page_size, exact, kv_quant="", slot=None):
    """Bucketed prefill of one chunk (``model.prefill_forward``'s
    contract: page-aligned ``offset``, ``length`` real tokens, rows past
    the table on the trash page; ``kv_quant`` belongs to a feature this
    block refuses).  A convolution layer takes ``slot``'s rows from the
    pool, runs the causal convolution over [them | the bucket's ``g``] and
    writes back the last real rows: what a chunk at ``offset > 0`` starts
    from is what the chunk before it left.  An attention layer writes the
    chunk's key/value heads into the slot's pages, gathers them and
    attends with per-row horizons ``offset + j + 1``.  The head runs on
    the last real row only.
    -> (first_token, last_logits, pools, counters)."""
    import jax
    import jax.numpy as jnp

    params, dequantized = _resolve(params)
    _, t_b = tokens.shape
    if t_b % page_size:
        raise MXNetError("bucket length %d not a multiple of page size %d"
                         % (t_b, page_size))
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
    block = prefill_block(max_pages, page_size, exact)
    x = _embed(params, tokens[0])
    incs = []
    ai = ci = 0
    for i, kind in enumerate(cfg.layer_types):
        pre = "blk%d_" % i
        u = rms_norm(x, params[pre + "operator_norm_gamma"],
                     cfg.rms_norm_eps)
        if kind == "conv":
            out, context = _conv_rows(
                params, pre, u, pools["conv_state"][ci, slot], length, cfg,
                exact)
            pools["conv_state"] = pools["conv_state"].at[ci, slot].set(
                context.astype(pools["conv_state"].dtype))
            ci += 1
        else:
            q, k, v = _qkv(params, pre, u, abs_pos, cfg, exact)
            with jax.named_scope("gqa_prefill"):
                append_rows(pools, "k", ai, pages, offsets, k, "")
                append_rows(pools, "v", ai, pages, offsets, v, "")
                att = paged_prefill_attention(
                    q, pools["k_pool"], pools["v_pool"], ai, table_row,
                    abs_pos, page_size, block, mi=exact, scale=_scale(cfg))
            out = _mm(att.reshape(t_b, -1), params[pre + "o_weight"], exact)
            ai += 1
        x, inc = _ffn_held(params, i, x + out, cfg, exact, valid,
                           dequantized)
        if inc is not None:
            incs.append(inc)
    last = _head(params, jnp.take(x, length - 1, axis=0), cfg, exact)
    first_token = jnp.argmax(last, axis=-1).astype(jnp.int32)
    return first_token, last, pools, _count(
        counters, incs,
        dict(prefill_chunks=1, prefill_chunks_continued=offset != 0),
        dict(prefills_from_zero=offset == 0, prefills_carried=offset != 0,
             rows_valid=length, rows_padded=t_b - length))


def decode_step(params, tokens, lengths, tables, pools, counters, cfg,
                page_size, exact, kv_quant=""):
    """One decode step for every slot (``model.decode_step``'s contract).
    A convolution layer shifts every live slot's rows by one token, in the
    donated pool, and writes an idle slot's (length 0) back as they were;
    an attention layer appends each slot's key/value heads at ``lengths``
    and reads the pages in place up to the longest live context.
    -> (next_tokens, logits, pools, counters)."""
    import jax
    import jax.numpy as jnp

    params, dequantized = _resolve(params)
    s = tokens.shape[0]
    max_pages = tables.shape[1]
    pools = dict(pools)
    x = _embed(params, tokens)
    page_slot = jnp.clip(lengths // page_size, 0, max_pages - 1)
    page = jnp.take_along_axis(tables, page_slot[:, None], axis=1)[:, 0]
    offset = lengths % page_size
    valid = jnp.ones((s,), bool)
    live = lengths > 0
    incs = []
    ai = ci = 0
    for i, kind in enumerate(cfg.layer_types):
        pre = "blk%d_" % i
        u = rms_norm(x, params[pre + "operator_norm_gamma"],
                     cfg.rms_norm_eps)
        if kind == "conv":
            g, c = _gates(params, pre, u, cfg, exact)
            with jax.named_scope("sconv_mix"):
                rows = pools["conv_state"][ci]
                conv, context = conv_step(g, rows,
                                          params[pre + "conv_weight"], 0.0)
                pools["conv_state"] = pools["conv_state"].at[ci].set(
                    jnp.where(live[:, None, None],
                              context.astype(rows.dtype), rows))
            out = _conv_out(params, pre, c, conv, exact)
            ci += 1
        else:
            q, k, v = _qkv(params, pre, u, lengths, cfg, exact)
            with jax.named_scope("gqa_decode"):
                append_rows(pools, "k", ai, page, offset, k, "")
                append_rows(pools, "v", ai, page, offset, v, "")
                att = paged_decode_attention(
                    q, pools["k_pool"], pools["v_pool"], ai, tables,
                    lengths + 1, page_size, mi=exact, scale=_scale(cfg))
            out = _mm(att.reshape(s, -1), params[pre + "o_weight"], exact)
            ai += 1
        x, inc = _ffn_held(params, i, x + out, cfg, exact, valid,
                           dequantized)
        if inc is not None:
            incs.append(inc)
    logits = _head(params, x, cfg, exact)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return next_tokens, logits, pools, _count(
        counters, incs,
        dict(decode_steps=1,
             full_rows_live=ai * jnp.where(live, lengths + 1, 0).sum(
                 ).astype(jnp.int32)))
