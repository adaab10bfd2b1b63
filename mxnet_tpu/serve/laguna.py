"""The Laguna decoder block for the serving runtime: sliding-window and
full grouped-query attention layers in one stack, the window layers on
per-slot rings sized by the model's window and the full layers on K/V
pages, a query-head count a layer, a rotary embedding of two kinds, a
sigmoid gate a head, and softmax-routed experts of which this chip may
hold a share.

The fifth block beside ``model.py``'s GPT-2 one, ``latent_moe.py``,
``granite_hybrid.py`` and ``bailing_hybrid.py``, selected by
``ModelConfig(block="laguna", ...)`` through ``model.BLOCKS``.  The
equations (``benchmark/references/laguna_lm.py`` is their plain form, and
the tests hold this module to it; d = ``d_model``, D = ``attn_head_dim``,
layer ``l`` has ``H_l = num_attention_heads_per_layer[l]`` query heads over
``num_key_value_heads`` key/value heads):

* ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, no position
  table, no bias, an untied head after a final RMSNorm.
* Attn: ``q = W_q u`` as (H_l, D), ``k = W_k u``, ``v = W_v u`` as (KV, D);
  ``q`` and ``k`` rotated by the layer kind's ``rope_parameters`` group
  (:func:`rope_frequencies`: plain or YaRN, the pairs ``(i, i + rot / 2)``
  of the first ``rot`` values of a head); query head ``h`` reads key/value
  head ``h // (H_l / KV)``; scores ``q . k / sqrt(D)``; key ``j`` is
  visible to query ``i`` where ``0 <= i - j`` (``"full_attention"``) or
  ``0 <= i - j < sliding_window`` (``"sliding_attention"``); ``o_h <-
  sigmoid(w_gate,h . u) o_h``; ``W_o``.
* FFN: ``latent_moe.py``'s: one SwiGLU in the layers ``mlp_only_layers``
  names (leading ones), then the router over all ``n_routed_experts`` with
  softmax scores, the experts held here (``experts_held``) and one shared
  expert of ``shared_expert_intermediate_size``.

**What the cache holds.**  A full layer keeps its key/value heads in the
K/V pools' pages, as the Mamba-2 block's attention layers do: append
through ``append_rows``, prefill through ``paged_prefill_attention`` with
per-row horizons, decode through ``paged_decode_attention``.  A window layer keeps,
a slot, a ring of :func:`ring_pages` pages: ``sliding_window`` rows rounded
up to whole pages, whatever the buckets are (``kv_cache.py`` owns the
ring's layout; this module writes through ``append_rows`` /
``fold_into_ring`` and labels rows through ``ring_positions``).  Decode
appends one row a slot and attends over the ring's rows whose label lies
inside the band.  A prefill chunk attends over [the ring as the chunks
before it left it | the chunk's own K and V, which never pass through the
ring] under the same band, a block of queries at a time so that a block's
scores are (rows, ring + block) and not (rows, bucket), and only then
folds the chunk's last rows into the ring: bucket padding writes nothing.

``exact`` selects the M-invariant ``_mm`` and attention products as for
the GPT-2 block, but the bit-identity contract does not extend here:
prefill's blocks and decode's ring associate differently, so decode agrees
with a full forward to rounding, not to the bit.

Counters: every executable folds what its routers did into
``counters["moe_stats"]`` (:data:`MOE_COLUMNS`) and what its attention did
into ``counters["attn_stats"]`` (:data:`ATTN_COLUMNS`);
``InferenceSession.block_report()`` reads both.
"""
from __future__ import annotations

import dataclasses
import functools
import math

from ..base import MXNetError
from ..ops.attention import (flash_attention, paged_decode_attention,
                             paged_prefill_attention)
from . import latent_moe
from .kv_cache import (append_rows, fold_into_ring, kv_pool_shape,
                       read_ring, ring_positions)
from .latent_moe import (_ffn_held, _head, _head_gate, _resolve, fold_named,
                         held_range, prefill_block, read_named)
from .layers import rms_norm, window_decode, window_prefill
# the expert layer is the latent block's, and so is what it asks of XLA
# (the same pass would carry the K/V pools and the rings as bfloat16)
from .latent_moe import compiler_options  # noqa: F401
from .model import _mm, check_param_shapes
# the full layers run the GPT-2 block's paged reader: its report
from .model import decode_report  # noqa: F401

BLOCK = "laguna"
KINDS = ("full_attention", "sliding_attention")

# ServeConfig features a session over this block refuses at construction
REFUSES = ("spec_k", "kv_quant")
REFUSES_WHY = ("a draft's rejected rows would already have overwritten "
               "ring rows that the committed stream still sees; the rings "
               "here have no scale pool: ROADMAP M2")

# moe_stats columns: latent_moe._ffn_held's counts
MOE_COLUMNS = ("assignments_asked", "assignments_held",
               "assignments_computed", "distinct_held_experts",
               "rows_without_held_expert", "dispatch_rows", "dispatch_held")
# attn_stats columns.  prefill_chunks_continued: chunks at an offset past
# 0 (a prompt longer than the largest bucket, or a resumed transcript).
# Of the DECODE steps, summed over the window layers: window_rows_visited,
# the ring rows read (every slot's whole ring), and window_rows_in_band,
# those of live slots inside the band; summed over the full layers:
# full_rows_live, the rows of live slots' contexts (what the paged reader
# visits is counted on the host: decode_report()).
ATTN_COLUMNS = ("decode_steps", "prefill_chunks", "prefill_chunks_continued",
                "window_rows_visited", "window_rows_in_band",
                "full_rows_live")


@functools.lru_cache(maxsize=None)
def _ffn_cfg(cfg):
    """``cfg`` with the published keys of the second half said in the
    latent block's words, which its functions read: the leading dense
    layers' count and the shared experts as multiples of one expert."""
    return dataclasses.replace(
        cfg, first_k_dense=len(cfg.mlp_only_layers),
        n_shared_experts=cfg.shared_expert_intermediate_size
        // max(cfg.moe_d_ff, 1))


def layer_heads(cfg):
    """Query heads of each layer."""
    return tuple(cfg.num_attention_heads_per_layer) \
        or (cfg.num_heads,) * cfg.num_layers


def rope_group(cfg, kind):
    """The ``rope_parameters`` group of a layer kind, as a dict."""
    return dict(dict(cfg.rope_parameters)[kind])


def rope_frequencies(group, head_dim):
    """One ``rope_parameters`` group -> (rot, inv_freq (rot / 2,) Python
    floats, the factor on cos and sin).  ``rot = head_dim *
    partial_rotary_factor`` leading values of a head are rotated.
    ``rope_type`` ``"default"``: ``theta^(-2i / rot)``.  ``"yarn"`` (the
    generic initialisation: ``factor``, ``original_max_position_embeddings``,
    ``beta_fast``, ``beta_slow``, ``attention_factor``): between the
    correction dims ``low`` and ``high`` a ramp from the plain frequency to
    the plain frequency over ``factor``."""
    rot = int(head_dim * group.get("partial_rotary_factor", 1))
    theta = float(group["rope_theta"])
    freqs = [theta ** (2.0 * i / rot) for i in range(rot // 2)]
    if group.get("rope_type", "default") == "default":
        return rot, [1.0 / f for f in freqs], 1.0
    orig = group["original_max_position_embeddings"]

    def correction(turns):
        return rot * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(group["beta_fast"])), 0)
    high = min(math.ceil(correction(group["beta_slow"])), rot - 1)
    span = (high - low) or 0.001
    ramp = [min(max((i - low) / span, 0.0), 1.0) for i in range(rot // 2)]
    return rot, [(1.0 - r) / f + r / (group["factor"] * f)
                 for r, f in zip(ramp, freqs)], \
        float(group["attention_factor"])


def ring_pages(cfg, serve):
    """Pages of a slot's ring in every window layer, under the
    ``ServeConfig`` ``serve``: the model's window in whole pages.  Decode
    overwrites the one row that has just left the band, and prefill reads
    the ring before it writes, so no row more is needed, whatever the
    buckets."""
    return -(-cfg.sliding_window // serve.page_size)


def validate(cfg):
    sizes = (cfg.attn_head_dim, cfg.d_ff, cfg.max_len, cfg.kv_heads)
    if min(sizes) < 1 or cfg.attn_head_dim % 2:
        raise MXNetError(
            "ModelConfig(block=%r) needs an even attn_head_dim, d_ff, "
            "max_len and num_key_value_heads (got %r)" % (BLOCK, sizes))
    if len(cfg.layer_types) != cfg.num_layers \
            or set(cfg.layer_types) - set(KINDS):
        raise MXNetError("layer_types %r: %d layers, each %s"
                         % (cfg.layer_types, cfg.num_layers,
                            " or ".join(map(repr, KINDS))))
    heads = layer_heads(cfg)
    if len(heads) != cfg.num_layers \
            or any(h < 1 or h % cfg.kv_heads for h in heads):
        raise MXNetError(
            "num_attention_heads_per_layer %r: %d layers, each a multiple "
            "of the %d key/value heads" % (heads, cfg.num_layers,
                                           cfg.kv_heads))
    if "sliding_attention" in cfg.layer_types and cfg.sliding_window < 1:
        raise MXNetError("sliding_attention layers need sliding_window >= 1 "
                         "(got %d)" % cfg.sliding_window)
    for kind in set(cfg.layer_types):
        group = dict(cfg.rope_parameters).get(kind)
        if group is None or dict(group).get("rope_type", "default") \
                not in ("default", "yarn"):
            raise MXNetError(
                "rope_parameters needs a group for %r of rope_type "
                "\"default\" or \"yarn\" (got %r)" % (kind, group))
        if rope_frequencies(dict(group), cfg.attn_head_dim)[0] % 2:
            raise MXNetError("%r rotates an odd number of values" % kind)
    if tuple(cfg.mlp_only_layers) != tuple(range(len(cfg.mlp_only_layers))):
        raise MXNetError("mlp_only_layers %r: the dense layers lead the "
                         "stack" % (cfg.mlp_only_layers,))
    if cfg.scoring_func not in ("softmax", "sigmoid"):
        raise MXNetError("scoring_func %r: \"softmax\" or \"sigmoid\""
                         % cfg.scoring_func)
    if cfg.shared_expert_intermediate_size % max(cfg.moe_d_ff, 1):
        raise MXNetError(
            "the shared expert (%d wide) is a whole number of experts of %d"
            % (cfg.shared_expert_intermediate_size, cfg.moe_d_ff))
    if cfg.tie_word_embeddings:
        raise MXNetError("block %r has no tied head" % BLOCK)
    latent_moe.validate_ffn(_ffn_cfg(cfg))
    return cfg


def param_shapes(cfg):
    """{parameter name: shape}: matrices (out, in) as ``_mm`` takes them,
    the query, gate and output matrices by the layer's own head count; the
    FFN's names are the latent block's."""
    d, hd, kv = cfg.d_model, cfg.attn_head_dim, cfg.kv_heads
    out = {"tok_embed_weight": (cfg.vocab_size, d), "final_norm_gamma": (d,),
           "lm_head_weight": (cfg.vocab_size, d)}
    for i, h in enumerate(layer_heads(cfg)):
        p = "blk%d_" % i
        out.update({p + "attn_norm_gamma": (d,),
                    p + "q_weight": (h * hd, d),
                    p + "k_weight": (kv * hd, d),
                    p + "v_weight": (kv * hd, d),
                    p + "attn_gate_weight": (h, d),
                    p + "o_weight": (d, h * hd)})
        out.update(latent_moe.ffn_param_shapes(_ffn_cfg(cfg), i))
    return out


def init_params(cfg, seed=0, scale=0.02):
    """Fresh float32 parameters (tests and benches): normal matrices,
    norm scales one, a sigmoid router's selection bias zero."""
    return latent_moe.init_from_shapes(param_shapes(cfg), seed, scale)


def check_params(params, cfg):
    """The parameter dict has exactly the architecture's shapes."""
    check_param_shapes(params, param_shapes(cfg), BLOCK)


def latent_dim(cfg):
    """0: the full layers keep per-head K and V pools."""
    return 0


def state_shapes(cfg):
    """Slot-private recurrent state beside the pages and the rings:
    none."""
    return {}


def init_counters(cfg):
    """``moe_stats`` and ``attn_stats``, (2, columns) int32 each, folded
    by the executables: row 0 the low 30 bits of each count, row 1 the
    carries."""
    import jax.numpy as jnp

    return {"moe_stats": jnp.zeros((2, len(MOE_COLUMNS)), jnp.int32),
            "attn_stats": jnp.zeros((2, len(ATTN_COLUMNS)), jnp.int32)}


def guard_tag(cfg):
    """Another block altogether: key/value heads, the window, the experts
    held of those routed, each layer's kind and query heads."""
    return "-%s-kv%dx%d-w%d-e%dof%dk%d-%s" % (
        BLOCK, cfg.kv_heads, cfg.attn_head_dim, cfg.sliding_window,
        held_range(_ffn_cfg(cfg))[1], cfg.n_routed_experts,
        cfg.num_experts_per_tok,
        "".join("%s%d" % (t[0], h)
                for t, h in zip(cfg.layer_types, layer_heads(cfg))))


def report(counters, cfg):
    """Host side: both counters as exact Python ints under their names
    (``InferenceSession.block_report`` documents them), with the layers
    of each kind, the experts held and the width of the K/V pools' last
    axis at rest (the session adds ``ring_rows``, which it sized)."""
    out = dict(read_named(counters["moe_stats"], MOE_COLUMNS),
               **read_named(counters["attn_stats"], ATTN_COLUMNS))
    out["window_layers"] = cfg.layer_types.count("sliding_attention")
    out["full_layers"] = cfg.layer_types.count("full_attention")
    out["expert_layers"] = cfg.num_layers - len(cfg.mlp_only_layers)
    out["experts_held"] = held_range(_ffn_cfg(cfg))[1]
    out["sliding_window"] = cfg.sliding_window
    out["kv_lanes"] = kv_pool_shape(1, 1, 1, cfg.kv_heads,
                                    cfg.attn_head_dim)[-1]
    return out


def _count(counters, incs, **attn):
    """Fold one executable's routers (``incs``, a dict a layer) and its
    attention's counts into the two counters."""
    moe = {}
    for layer in incs:
        for name, value in layer.items():
            moe[name] = moe.get(name, 0) + value
    if "decode_steps" not in attn:      # what a decode step had to read
        moe["distinct_held_experts"] = 0
    return dict(
        counters,
        moe_stats=fold_named(counters["moe_stats"], MOE_COLUMNS, moe),
        attn_stats=fold_named(counters["attn_stats"], ATTN_COLUMNS, attn))


def _rope(x, positions, group):
    """Rotate the pairs ``(i, i + rot / 2)`` of ``x`` (N, heads, D) at
    ``positions`` (N,); what lies past ``rot`` is left as it is."""
    import jax.numpy as jnp

    rot, inv_freq, factor = rope_frequencies(group, x.shape[-1])
    angle = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos = (jnp.cos(angle) * factor).astype(x.dtype)[:, None, :]
    sin = (jnp.sin(angle) * factor).astype(x.dtype)[:, None, :]
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


def _qkv(params, pre, u, positions, heads, kind, cfg, exact):
    """u (N, d) -> rotated q (N, KV, G, D) with a key/value head's query
    heads as its rows, rotated k and plain v (N, KV, D)."""
    import jax

    n, kv, hd = u.shape[0], cfg.kv_heads, cfg.attn_head_dim
    group = rope_group(cfg, kind)
    with jax.named_scope("gqa_rope"):
        q = _rope(_mm(u, params[pre + "q_weight"], exact).reshape(
            n, heads, hd), positions, group)
        k = _rope(_mm(u, params[pre + "k_weight"], exact).reshape(
            n, kv, hd), positions, group)
    return (q.reshape(n, kv, heads // kv, hd), k,
            _mm(u, params[pre + "v_weight"], exact).reshape(n, kv, hd))


def full_forward(params, tokens, cfg, exact, block=None):
    """(n, T) int tokens -> (n, T, V) logits: the forward the cached paths
    are held against.  ``block`` is the attention's key block (T by
    default)."""
    import jax
    import jax.numpy as jnp

    params, dequantized = _resolve(params)
    ffn = _ffn_cfg(cfg)
    t = tokens.shape[-1]
    if t > cfg.max_len:
        raise MXNetError("sequence length %d > model max_len %d"
                         % (t, cfg.max_len))
    positions = jnp.arange(t, dtype=jnp.int32)
    valid = jnp.ones((t,), bool)

    def one(seq):
        x = jnp.take(params["tok_embed_weight"], seq.astype(jnp.int32),
                     axis=0)
        for i, (kind, heads) in enumerate(zip(cfg.layer_types,
                                              layer_heads(cfg))):
            pre = "blk%d_" % i
            u = rms_norm(x, params[pre + "attn_norm_gamma"],
                         cfg.rms_norm_eps)
            q, k, v = _qkv(params, pre, u, positions, heads, kind, cfg,
                           exact)
            k, v = (jnp.repeat(a, heads // cfg.kv_heads, axis=1
                               ).transpose(1, 0, 2) for a in (k, v))
            att = flash_attention(
                q.reshape(t, heads, -1).transpose(1, 0, 2), k, v,
                causal=True, block=block or t, mi=exact,
                window=cfg.sliding_window
                if kind == "sliding_attention" else 0)
            att = att.transpose(1, 0, 2).reshape(t, -1)
            x = x + _mm(_head_gate(params, pre, att, u, heads, exact,
                                   scope="attn_gate"),
                        params[pre + "o_weight"], exact)
            x, _ = _ffn_held(params, i, x, ffn, exact, valid, dequantized)
        return _head(params, x, cfg, exact)

    return jax.vmap(one)(tokens)


def prefill_forward(params, tokens, length, offset, table_row, pools,
                    counters, cfg, page_size, exact, kv_quant="", slot=None):
    """Bucketed prefill of one chunk (``model.prefill_forward``'s
    contract: page-aligned ``offset``, ``length`` real tokens, rows past
    the table on the trash page; ``kv_quant`` belongs to a feature this
    block refuses).  A full layer writes the chunk's key/value heads into
    the slot's pages, gathers them and attends with per-row horizons
    ``offset + j + 1``.  A window layer reads ``slot``'s ring as the
    chunks before left it, attends over it and the chunk's own rows under
    the band, then folds the chunk's last real rows into the ring: what a
    chunk at ``offset > 0`` sees of the past is what the chunk before it
    left.  The head runs on the last real row only.
    -> (first_token, last_logits, pools, counters)."""
    import jax
    import jax.numpy as jnp

    params, dequantized = _resolve(params)
    ffn = _ffn_cfg(cfg)
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
    x = jnp.take(params["tok_embed_weight"], tokens[0].astype(jnp.int32),
                 axis=0)
    incs = []
    fi = wi = 0
    for i, (kind, heads) in enumerate(zip(cfg.layer_types,
                                          layer_heads(cfg))):
        pre = "blk%d_" % i
        u = rms_norm(x, params[pre + "attn_norm_gamma"], cfg.rms_norm_eps)
        q, k, v = _qkv(params, pre, u, abs_pos, heads, kind, cfg, exact)
        if kind == "sliding_attention":
            with jax.named_scope("swa_prefill"):
                rows = pools["kw_pool"].shape[2]
                att = window_prefill(
                    q, k, v,
                    read_ring(pools["kw_pool"], wi, cfg.attn_head_dim, slot),
                    read_ring(pools["vw_pool"], wi, cfg.attn_head_dim, slot),
                    ring_positions(rows, offset - 1), abs_pos,
                    cfg.sliding_window, exact)
            with jax.named_scope("swa_append"):
                fold_into_ring(pools, "kw", wi, slot, k, offset, length)
                fold_into_ring(pools, "vw", wi, slot, v, offset, length)
            wi += 1
        else:
            with jax.named_scope("gqa_prefill"):
                append_rows(pools, "k", fi, pages, offsets, k, "")
                append_rows(pools, "v", fi, pages, offsets, v, "")
                att = paged_prefill_attention(
                    q, pools["k_pool"], pools["v_pool"], fi, table_row,
                    abs_pos, page_size, block, mi=exact)
            fi += 1
        att = _head_gate(params, pre, att.reshape(t_b, -1), u, heads, exact,
                         scope="attn_gate")
        x = x + _mm(att, params[pre + "o_weight"], exact)
        x, inc = _ffn_held(params, i, x, ffn, exact, valid, dequantized)
        if inc is not None:
            incs.append(inc)
    last = _head(params, jnp.take(x, length - 1, axis=0), cfg, exact)
    first_token = jnp.argmax(last, axis=-1).astype(jnp.int32)
    return first_token, last, pools, _count(
        counters, incs, prefill_chunks=1,
        prefill_chunks_continued=offset != 0)


def decode_step(params, tokens, lengths, tables, pools, counters, cfg,
                page_size, exact, kv_quant=""):
    """One decode step for every slot (``model.decode_step``'s contract).
    A full layer appends each slot's key/value heads at ``lengths`` and
    reads the pages in place up to the longest live context; a window
    layer appends them at ``lengths % rows`` of the slot's ring and
    attends over the ring's rows inside the band.  An idle slot (length 0)
    writes the trash page and its own ring's row 0, which labels outside
    every band until the slot's next request has overwritten it.
    -> (next_tokens, logits, pools, counters)."""
    import jax
    import jax.numpy as jnp

    params, dequantized = _resolve(params)
    ffn = _ffn_cfg(cfg)
    s = tokens.shape[0]
    max_pages = tables.shape[1]
    pools = dict(pools)
    x = jnp.take(params["tok_embed_weight"], tokens.astype(jnp.int32),
                 axis=0)
    page_slot = jnp.clip(lengths // page_size, 0, max_pages - 1)
    page = jnp.take_along_axis(tables, page_slot[:, None], axis=1)[:, 0]
    offset = lengths % page_size
    slot_ids = jnp.arange(s)
    valid = jnp.ones((s,), bool)
    live = lengths > 0
    incs = []
    in_band = jnp.zeros((), jnp.int32)
    fi = wi = 0
    for i, (kind, heads) in enumerate(zip(cfg.layer_types,
                                          layer_heads(cfg))):
        pre = "blk%d_" % i
        u = rms_norm(x, params[pre + "attn_norm_gamma"], cfg.rms_norm_eps)
        q, k, v = _qkv(params, pre, u, lengths, heads, kind, cfg, exact)
        if kind == "sliding_attention":
            with jax.named_scope("swa_append"):
                row = lengths % pools["kw_pool"].shape[2]
                append_rows(pools, "kw", wi, slot_ids, row, k, "")
                append_rows(pools, "vw", wi, slot_ids, row, v, "")
            with jax.named_scope("swa_decode"):
                att, seen = window_decode(
                    q, read_ring(pools["kw_pool"], wi, cfg.attn_head_dim),
                    read_ring(pools["vw_pool"], wi, cfg.attn_head_dim),
                    lengths, cfg.sliding_window, exact)
            in_band = in_band + jnp.where(live, seen, 0).sum().astype(
                jnp.int32)
            wi += 1
        else:
            with jax.named_scope("gqa_decode"):
                append_rows(pools, "k", fi, page, offset, k, "")
                append_rows(pools, "v", fi, page, offset, v, "")
                att = paged_decode_attention(
                    q, pools["k_pool"], pools["v_pool"], fi, tables,
                    lengths + 1, page_size, mi=exact)
            fi += 1
        att = _head_gate(params, pre, att.reshape(s, -1), u, heads, exact,
                         scope="attn_gate")
        x = x + _mm(att, params[pre + "o_weight"], exact)
        x, inc = _ffn_held(params, i, x, ffn, exact, valid, dequantized)
        if inc is not None:
            incs.append(inc)
    logits = _head(params, x, cfg, exact)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    rings = pools["kw_pool"].shape[2] if wi else 0
    return next_tokens, logits, pools, _count(
        counters, incs, decode_steps=1,
        window_rows_visited=wi * s * rings, window_rows_in_band=in_band,
        full_rows_live=fi * jnp.where(live, lengths + 1, 0).sum().astype(
            jnp.int32))
