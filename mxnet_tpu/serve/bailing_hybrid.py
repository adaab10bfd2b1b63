"""The BailingHybrid decoder block for the serving runtime: KDA
linear-attention layers with slot-private matrix state, a gated
latent-attention layer among every few over a latent page pool, and
group-routed experts of which this chip may hold a share.

The fourth block beside ``model.py``'s GPT-2 one, ``latent_moe.py`` and
``granite_hybrid.py``, selected by ``ModelConfig(block="bailing_hybrid",
...)`` through ``model.BLOCKS``.  The equations (``benchmark/references/
bailing_hybrid_lm.py`` is their plain form, and the tests hold this module
to it; d = ``d_model``, H heads of width D = ``kda_head_dim``):

* ``h = x + Mix(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, no position
  table, no bias, an untied head after a final RMSNorm.
* KDA layer (``layer_types[i] == "kda"``; arXiv:2510.26692): ``[q | k | v]
  = silu(conv(W_q u | W_k u | W_v u))``, depthwise, causal,
  ``kda_d_conv`` taps, no bias; ``q <- q / |q| / sqrt(D)``, ``k <- k /
  |k|``; the log-decay a channel ``g = kda_lower_bound * sigmoid(exp(A_log)
  * (W_f u + dt_bias))`` (the safe gate), ``beta = sigmoid(W_b u)`` a
  head; the recurrence of ``ops/kda.py``; ``out = W_o [RMSNorm_head(o) *
  sigmoid(W_g u)]``.  **The cache holds, a slot a layer, the state ``S``
  (H, D, D) in float32 and the last ``kda_d_conv - 1`` rows of the
  pre-activation ``[q | k | v]``** (:func:`state_shapes`), and no page.
* MLA layer (``"mla"``): ``latent_moe.py``'s latent attention, its rows in
  the latent pool's pages, then ``o_h <- o_h * sigmoid(w_gate,h . u)``
  before ``W_o`` (a gate a head).  The latent pool has one layer for each
  of these and none for the others.
* FFN: ``latent_moe.py``'s: one SwiGLU in the first ``first_k_dense``
  layers, then the router over all ``n_routed_experts`` with its group
  limit, the experts held here (``experts_held``) and the shared expert.

Prefill runs the chunked form (``kda_chunk_size`` rows a chunk) from the
state the slot's pool rows hold: zero after ``alloc``, or what an earlier
chunk of the same request left.  Bucket padding is ``g = 0, beta = 0``,
an identity of the recurrence, and the convolution context written back
is the last real rows'.  Decode runs the recurrence one token a slot.  The
two associate differently, so ``exact`` selects the M-invariant ``_mm``
but decode agrees with a full forward to rounding, not to the bit.

Counters: every executable folds what it did into ``counters
["moe_stats"]`` (:data:`COLUMNS`); ``InferenceSession.block_report()``
reads it.
"""
from __future__ import annotations

from ..base import MXNetError
from ..ops.kda import MAX_EXPONENT, kda_chunked, kda_step
from ..ops.mamba2 import causal_conv, conv_step
from . import latent_moe
from .kv_cache import append_latent_rows, read_latent_context
from .latent_moe import (_attend_absorbed, _attend_materialised,
                         _ffn_held as _ffn, _head, _head_gate,
                         _query_and_row, _resolve, fold_named, held_range,
                         prefill_block, read_named)
from .layers import rms_norm
# the expert layer is the latent block's, and so is what it asks of XLA
from .latent_moe import compiler_options  # noqa: F401
from .model import _mm, check_param_shapes

BLOCK = "bailing_hybrid"

# ServeConfig features a session over this block refuses at construction
REFUSES = ("spec_k", "kv_quant")
REFUSES_WHY = ("a rejected draft would need the state before it, and "
               "nothing snapshots a slot's state; neither the state nor a "
               "latent row has a row to scale: ROADMAP M3, M4")

# moe_stats columns: what the routers and the recurrent layers did.
# assignments_asked: real rows x experts a token, of all the experts;
# _held: those that fell on experts held here; _computed: those of them
# whose tile the loop reached (fewer: dropped).  distinct_held_experts:
# the sum over DECODE steps and expert layers of the held experts at
# least one row reached; rows_without_held_expert: real rows a layer that
# reached none; state_slot_layers: (slot, KDA layer) states read and
# written; dispatch_rows: padded rows the expert layers laid out, and
# dispatch_held the held assignments they are read against.
COLUMNS = ("decode_steps", "prefill_chunks", "assignments_asked",
           "assignments_held", "assignments_computed",
           "distinct_held_experts", "rows_without_held_expert",
           "state_slot_layers", "dispatch_rows", "dispatch_held")

_L2_EPS = 1e-6      # under the square root of a query's or key's length


def _heads(cfg):
    return cfg.kda_n_heads or cfg.num_heads


def _qkv_dim(cfg):
    return 3 * _heads(cfg) * cfg.kda_head_dim


def validate(cfg):
    sizes = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
             cfg.kv_lora_rank, cfg.d_ff, cfg.max_len, cfg.kda_head_dim,
             cfg.kda_chunk_size)
    if min(sizes) < 1 or cfg.kda_d_conv < 2 or cfg.kda_lower_bound >= 0:
        raise MXNetError(
            "ModelConfig(block=%r) needs the latent block's sizes, "
            "kda_head_dim, kda_chunk_size, kda_d_conv >= 2 and a negative "
            "kda_lower_bound (got %r, %d, %r)"
            % (BLOCK, sizes, cfg.kda_d_conv, cfg.kda_lower_bound))
    if -cfg.kda_lower_bound * (cfg.kda_chunk_size // 2) > MAX_EXPONENT:
        raise MXNetError(
            "kda_chunk_size %d at kda_lower_bound %g leaves float32's range "
            "(ops/kda.py)" % (cfg.kda_chunk_size, cfg.kda_lower_bound))
    if cfg.qk_rope_head_dim % 2:
        raise MXNetError("qk_rope_head_dim %d is not even"
                         % cfg.qk_rope_head_dim)
    if len(cfg.layer_types) != cfg.num_layers \
            or set(cfg.layer_types) - {"kda", "mla"}:
        raise MXNetError("layer_types %r: %d layers, each \"kda\" or "
                         "\"mla\"" % (cfg.layer_types, cfg.num_layers))
    latent_moe.validate_ffn(cfg)
    return cfg


def param_shapes(cfg):
    """{parameter name: shape}: matrices (out, in) as ``_mm`` takes them,
    the depthwise filter (channels, taps), a layer's held experts stacked
    on a leading axis.  The FFN's names and an ``"mla"`` layer's are the
    latent block's."""
    d, h, w = cfg.d_model, _heads(cfg), cfg.kda_head_dim
    theirs = latent_moe.param_shapes(cfg)
    out = {}
    for i, kind in enumerate(cfg.layer_types):
        p = "blk%d_" % i
        if kind == "mla":
            out[p + "attn_gate_weight"] = (cfg.num_heads, d)
            continue
        for name in ("q_weight", "kv_a_weight", "kv_norm_gamma",
                     "kv_b_weight", "o_weight"):
            del theirs[p + name]
        out.update({p + "kda_q_weight": (h * w, d),
                    p + "kda_k_weight": (h * w, d),
                    p + "kda_v_weight": (h * w, d),
                    p + "kda_conv_weight": (_qkv_dim(cfg), cfg.kda_d_conv),
                    p + "kda_f_weight": (h * w, d),
                    p + "kda_dt_bias": (h * w,),
                    p + "kda_A_log": (h,),
                    p + "kda_b_weight": (h, d),
                    p + "kda_g_weight": (h * w, d),
                    p + "kda_o_norm_gamma": (w,),
                    p + "kda_o_weight": (d, h * w)})
    out.update(theirs)
    return out


def decay_init(cfg):
    """-> (A_log (H,), dt_bias (H * D,)) numpy: per head a gate sharpness
    ``exp(A_log)`` from 0.5 to 2, and per channel a bias under which the
    decay a token at ``W_f u = 0`` runs from 0.999 down to 0.2,
    log-spaced in ``1 - decay`` and laid across a head's channels in
    another order (channel c takes rung 37 c mod D).  Under a 0.02 normal
    draw every channel would decay by ~0.08 a token, a state forgotten
    within two tokens, against which a wrong carried state cannot be told
    from a right one."""
    import numpy as np

    h, w = _heads(cfg), cfg.kda_head_dim
    sharp = 0.5 * 4.0 ** (np.arange(h) / max(h - 1, 1))
    rung = ((np.arange(w) * 37) % w) / max(w - 1, 1)
    decay = 1.0 - 0.001 * 800.0 ** rung
    share = np.log(decay) / cfg.kda_lower_bound     # sigmoid's value
    bias = np.log(share / (1.0 - share))[None, :] / sharp[:, None]
    return np.log(sharp), bias.reshape(-1)


def init_params(cfg, seed=0, scale=0.02):
    """Fresh float32 parameters (tests and benches): normal matrices,
    norm scales one, the router's selection bias zero, the decays of
    :func:`decay_init`, the depthwise filter normal at 1 / sqrt(3 * taps)
    (a state no token can tell from zero tests nothing)."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    a_log, dt_bias = decay_init(cfg)
    params = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        if name.endswith("kda_A_log"):
            params[name] = jnp.asarray(a_log, jnp.float32)
        elif name.endswith("kda_dt_bias"):
            params[name] = jnp.asarray(dt_bias, jnp.float32)
        elif name.endswith("_gamma"):
            params[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith("_bias"):
            params[name] = jnp.zeros(shape, jnp.float32)
        else:
            std = (3.0 * shape[1]) ** -0.5 \
                if name.endswith("kda_conv_weight") else scale
            params[name] = (std * jax.random.normal(key, shape)
                            ).astype(jnp.float32)
    return params


def check_params(params, cfg):
    """The parameter dict has exactly the architecture's shapes."""
    check_param_shapes(params, param_shapes(cfg), BLOCK)


def latent_dim(cfg):
    """Values the cache holds a token in ONE latent pool, whose layers
    are the ``"mla"`` ones."""
    return latent_moe.latent_dim(cfg)


def state_shapes(cfg):
    """What a slot holds in every KDA layer, beside the pages of the
    latent layers: name -> (layers, one slot's shape a layer, dtype)."""
    n, h, w = cfg.layer_types.count("kda"), _heads(cfg), cfg.kda_head_dim
    return {"kda_state": (n, (h, w, w), "float32"),
            "conv_state": (n, (cfg.kda_d_conv - 1, _qkv_dim(cfg)),
                           "float32")}


def init_counters(cfg):
    """``moe_stats`` (2, len(COLUMNS)) int32, folded by the executables:
    row 0 the low 30 bits of each count, row 1 the carries."""
    import jax.numpy as jnp

    return {"moe_stats": jnp.zeros((2, len(COLUMNS)), jnp.int32)}


def decode_report(stats, table_width):
    """``None``: a latent layer of :func:`decode_step` gathers every
    slot's whole table, as the latent block's does."""
    return None


def guard_tag(cfg):
    """Another block altogether: latent width, the experts held of those
    routed, the KDA sizes, the layer pattern's initials."""
    return "-%s-c%d-e%dof%dk%d-kda%dx%d-%s" % (
        BLOCK, latent_dim(cfg), held_range(cfg)[1], cfg.n_routed_experts,
        cfg.num_experts_per_tok, _heads(cfg), cfg.kda_head_dim,
        "".join(t[0] for t in cfg.layer_types))


def report(counters, cfg):
    """Host side: ``moe_stats`` as exact Python ints under their names
    (``InferenceSession.block_report`` documents them), with the layers
    of each kind, the experts held and the bytes of state a slot holds."""
    import math

    import numpy as np

    out = read_named(counters["moe_stats"], COLUMNS)
    out["kda_layers"] = cfg.layer_types.count("kda")
    out["mla_layers"] = cfg.layer_types.count("mla")
    out["expert_layers"] = cfg.num_layers - cfg.first_k_dense
    out["experts_held"] = held_range(cfg)[1]
    out["state_bytes_per_slot"] = sum(
        layers * math.prod(shape) * np.dtype(dtype).itemsize
        for layers, shape, dtype in state_shapes(cfg).values())
    return out


def _count(counters, incs, **inc):
    """Fold one executable's routers (``incs``, a dict a layer) and its
    own counts into ``counters["moe_stats"]``."""
    for layer in incs:
        for name, value in layer.items():
            inc[name] = inc.get(name, 0) + value
    if "decode_steps" not in inc:
        inc["distinct_held_experts"] = 0
    return dict(counters, moe_stats=fold_named(counters["moe_stats"],
                                               COLUMNS, inc))


def _kda_inputs(params, pre, u, cfg, exact):
    """u (N, d) -> the pre-activation [q | k | v] rows (N, 3 H D), the
    log-decay g (N, H, D) and the rate beta (N, H), both float32, and the
    output gate's argument (N, H D)."""
    import jax
    import jax.numpy as jnp

    n, h, w = u.shape[0], _heads(cfg), cfg.kda_head_dim
    rows = jnp.concatenate(
        [_mm(u, params[pre + "kda_%s_weight" % m], exact) for m in "qkv"],
        axis=-1)
    with jax.named_scope("kda_gate"):
        f = (_mm(u, params[pre + "kda_f_weight"], exact).astype(jnp.float32)
             + params[pre + "kda_dt_bias"]).reshape(n, h, w)
        sharp = jnp.exp(params[pre + "kda_A_log"].astype(jnp.float32))
        g = cfg.kda_lower_bound * jax.nn.sigmoid(sharp[:, None] * f)
        beta = jax.nn.sigmoid(
            _mm(u, params[pre + "kda_b_weight"], exact).astype(jnp.float32))
    return rows, g, beta, _mm(u, params[pre + "kda_g_weight"], exact)


def _kda_heads(rows, cfg):
    """Convolved rows (N, 3 H D) -> q, k, v (N, H, D) float32: SiLU, the
    query and the key at unit length, the query scaled by 1 / sqrt(D)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n, h, w = rows.shape[0], _heads(cfg), cfg.kda_head_dim
    q, k, v = (a.reshape(n, h, w) for a in jnp.split(
        jax.nn.silu(rows.astype(jnp.float32)), 3, axis=-1))
    q, k = (a * lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + _L2_EPS)
            for a in (q, k))
    return q * w ** -0.5, k, v


def _kda_out(params, pre, o, gate, cfg, exact):
    """The recurrence's o (N, H, D) -> the mixer's output (N, d): the
    norm a head, the gate, the out-projection."""
    import jax

    with jax.named_scope("kda_out_norm"):
        o = rms_norm(o, params[pre + "kda_o_norm_gamma"], cfg.rms_norm_eps)
        y = o.reshape(o.shape[0], -1).astype(gate.dtype) \
            * jax.nn.sigmoid(gate)
    return _mm(y, params[pre + "kda_o_weight"], exact)


def _kda_rows(params, pre, u, state, context, length, cfg, exact):
    """One sequence's rows u (T, d) through a KDA mixer, from ``state``
    (H, D, D) and ``context`` (taps - 1, 3 H D); the first ``length`` rows
    are real.  -> (out (T, d), state, context)."""
    import jax
    import jax.numpy as jnp

    rows, g, beta, gate = _kda_inputs(params, pre, u, cfg, exact)
    with jax.named_scope("kda_conv"):
        rows, context = causal_conv(rows, context,
                                    params[pre + "kda_conv_weight"], 0.0,
                                    length)
    with jax.named_scope("kda_scan"):
        q, k, v = _kda_heads(rows, cfg)
        # bucket padding: identities of the recurrence
        real = jnp.arange(u.shape[0])[:, None] < length
        o, state = kda_chunked(
            q, k, v, jnp.where(real[..., None], g, 0.0),
            jnp.where(real, beta, 0.0), state, cfg.kda_chunk_size,
            cfg.kda_lower_bound)
    return _kda_out(params, pre, o, gate, cfg, exact), state, context


def full_forward(params, tokens, cfg, exact, block=None):
    """(n, T) int tokens -> (n, T, V) logits from zero state, materialised
    attention over the sequence's own rows: the forward the cached paths
    are held against.  ``block`` is the attention's key block (T by
    default)."""
    import jax
    import jax.numpy as jnp

    params, dequantized = _resolve(params)
    t = tokens.shape[-1]
    if t > cfg.max_len:
        raise MXNetError("sequence length %d > model max_len %d"
                         % (t, cfg.max_len))
    shapes = state_shapes(cfg)
    positions = jnp.arange(t, dtype=jnp.int32)
    valid = jnp.ones((t,), bool)

    def one(seq):
        x = jnp.take(params["tok_embed_weight"], seq.astype(jnp.int32),
                     axis=0)
        for i, kind in enumerate(cfg.layer_types):
            pre = "blk%d_" % i
            u = rms_norm(x, params[pre + "attn_norm_gamma"],
                         cfg.rms_norm_eps)
            if kind == "kda":
                out, _, _ = _kda_rows(
                    params, pre, u,
                    jnp.zeros(shapes["kda_state"][1], jnp.float32),
                    jnp.zeros(shapes["conv_state"][1], u.dtype), t, cfg,
                    exact)
            else:
                q, rows = _query_and_row(params, pre, u, positions, cfg,
                                         exact)
                att = _attend_materialised(params, pre, q, rows,
                                           positions + 1, cfg, exact,
                                           block or t)
                out = _mm(_head_gate(params, pre, att, u, cfg.num_heads,
                                     exact), params[pre + "o_weight"], exact)
            x, _ = _ffn(params, i, x + out, cfg, exact, valid,
                        dequantized)
        return _head(params, x, cfg, exact)

    return jax.vmap(one)(tokens)


def prefill_forward(params, tokens, length, offset, table_row, pools,
                    counters, cfg, page_size, exact, kv_quant="", slot=None):
    """Bucketed prefill of one chunk (``model.prefill_forward``'s
    contract: page-aligned ``offset``, ``length`` real tokens, rows past
    the table on the trash page; ``kv_quant`` belongs to a feature this
    block refuses).  A KDA layer takes ``slot``'s state and convolution
    context from the pools, runs the chunked form over the bucket and
    writes both back: what a chunk at ``offset > 0`` starts from is what
    the chunk before it left.  A latent layer writes the chunk's rows
    into the slot's pages, gathers them and attends in the materialised
    form with per-row horizons ``offset + j + 1``.  The head runs on the
    last real row only.
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
    trash = pools["latent_pool"].shape[1] - 1
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
    ki = li = 0
    for i, kind in enumerate(cfg.layer_types):
        pre = "blk%d_" % i
        u = rms_norm(x, params[pre + "attn_norm_gamma"], cfg.rms_norm_eps)
        if kind == "kda":
            out, state, context = _kda_rows(
                params, pre, u, pools["kda_state"][ki, slot],
                pools["conv_state"][ki, slot], length, cfg, exact)
            pools["kda_state"] = pools["kda_state"].at[ki, slot].set(state)
            pools["conv_state"] = pools["conv_state"].at[ki, slot].set(
                context.astype(pools["conv_state"].dtype))
            ki += 1
        else:
            with jax.named_scope("mla_prefill"):
                q, rows = _query_and_row(params, pre, u, abs_pos, cfg, exact)
                append_latent_rows(pools, li, pages, offsets, rows)
                ctx = read_latent_context(pools["latent_pool"], li,
                                          table_row)
                att = _attend_materialised(params, pre, q, ctx, abs_pos + 1,
                                           cfg, exact, block)
            out = _mm(_head_gate(params, pre, att, u, cfg.num_heads, exact),
                      params[pre + "o_weight"], exact)
            li += 1
        x, inc = _ffn(params, i, x + out, cfg, exact, valid, dequantized)
        if inc is not None:
            incs.append(inc)
    last = _head(params, jnp.take(x, length - 1, axis=0), cfg, exact)
    first_token = jnp.argmax(last, axis=-1).astype(jnp.int32)
    return first_token, last, pools, _count(
        counters, incs, prefill_chunks=1, state_slot_layers=ki)


def decode_step(params, tokens, lengths, tables, pools, counters, cfg,
                page_size, exact, kv_quant=""):
    """One decode step for every slot (``model.decode_step``'s contract).
    A KDA layer advances every slot's state and convolution context by
    one token, in the donated pools; a latent layer appends each slot's
    row at ``lengths`` and attends in the absorbed form over the slot's
    gathered pages.  An idle slot's state moves too, and is zeroed before
    anything reads it (``alloc``).
    -> (next_tokens, logits, pools, counters)."""
    import jax
    import jax.numpy as jnp

    params, dequantized = _resolve(params)
    s = tokens.shape[0]
    max_pages = tables.shape[1]
    pools = dict(pools)
    t_cap = max_pages * page_size
    x = jnp.take(params["tok_embed_weight"], tokens.astype(jnp.int32),
                 axis=0)
    page_slot = jnp.clip(lengths // page_size, 0, max_pages - 1)
    page = jnp.take_along_axis(tables, page_slot[:, None], axis=1)[:, 0]
    offset = lengths % page_size
    valid = jnp.ones((s,), bool)
    incs = []
    ki = li = 0
    for i, kind in enumerate(cfg.layer_types):
        pre = "blk%d_" % i
        u = rms_norm(x, params[pre + "attn_norm_gamma"], cfg.rms_norm_eps)
        if kind == "kda":
            rows, g, beta, gate = _kda_inputs(params, pre, u, cfg, exact)
            with jax.named_scope("kda_conv"):
                rows, context = conv_step(rows, pools["conv_state"][ki],
                                          params[pre + "kda_conv_weight"],
                                          0.0)
                pools["conv_state"] = pools["conv_state"].at[ki].set(
                    context.astype(pools["conv_state"].dtype))
            with jax.named_scope("kda_decode"):
                q, k, v = _kda_heads(rows, cfg)
                o, state = kda_step(q, k, v, g, beta, pools["kda_state"][ki])
                pools["kda_state"] = pools["kda_state"].at[ki].set(state)
            out = _kda_out(params, pre, o, gate, cfg, exact)
            ki += 1
        else:
            with jax.named_scope("mla_decode"):
                q, rows = _query_and_row(params, pre, u, lengths, cfg, exact)
                append_latent_rows(pools, li, page, offset, rows)
                ctx = read_latent_context(pools["latent_pool"], li, tables)
                att = _attend_absorbed(params, pre, q, ctx, lengths + 1, cfg,
                                       exact, page_size if exact else t_cap)
            out = _mm(_head_gate(params, pre, att, u, cfg.num_heads, exact),
                      params[pre + "o_weight"], exact)
            li += 1
        x, inc = _ffn(params, i, x + out, cfg, exact, valid, dequantized)
        if inc is not None:
            incs.append(inc)
    logits = _head(params, x, cfg, exact)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return next_tokens, logits, pools, _count(
        counters, incs, decode_steps=1, state_slot_layers=s * ki)
