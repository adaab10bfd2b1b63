"""The Qwen3-Next decoder block for the serving runtime: Gated DeltaNet
layers whose memory a slot is a matrix state a head and three convolution
rows, a gated grouped-query attention layer among every few over K/V
pages, and in every layer softmax-routed experts, of which this chip may
hold a share, beside a shared expert behind a sigmoid gate.

The ninth block, selected by ``ModelConfig(block="qwen3_next", ...)``
through ``model.BLOCKS``.  The equations (``benchmark/references/
qwen3_next_lm.py`` is their plain form, and the tests hold this module to
it; d = ``d_model``; every RMSNorm but the DeltaNet output norm is
**zero-centred**, ``x / rms(x) * (1 + w)``, its ``w`` a ``*_norm_weight``
that starts at zero):

* ``h = x + Mix(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, no position
  table, no bias, an untied head after a final RMSNorm.
* Gated DeltaNet layer (``layer_types[i] == "linear_attention"``;
  arXiv:2412.06464), Hk = ``linear_num_key_heads`` query / key heads of Dk
  = ``linear_key_head_dim``, Hv = ``linear_num_value_heads`` value heads of
  Dv = ``linear_value_head_dim``: ``[q | k | v | z] = W_qkvz u`` (the rows
  of the fused matrix in this order, each part whole and head-major: a
  loader reorders the checkpoint's, which interleaves them a key head),
  ``[b | a] = W_ba u``; ``[q | k | v] <- silu(conv([q | k | v]))``,
  depthwise, causal, ``linear_conv_kernel_dim`` taps, no bias; ``q <- q /
  |q| / sqrt(Dk)``, ``k <- k / |k|``; value head ``h`` reads query / key
  head ``h // (Hv / Hk)``; ``beta = sigmoid(b)``, the log-decay a head ``g
  = -exp(A_log) * softplus(a + dt_bias)`` in float32, unbounded below; the
  recurrence of ``ops/gdn.py``; ``out = W_o [w_n * o / rms(o) *
  silu(z)]``, the norm a head with a plain scale.  **The cache holds, a
  slot a layer, the state ``S`` (Hv, Dk, Dv) in float32 and the last
  ``taps - 1`` rows of the pre-activation ``[q | k | v]``**
  (:func:`state_shapes`), and no page.
* gated attention layer (``"full_attention"``): ``W_q u`` is, a head,
  ``[q | gate]`` (D = ``attn_head_dim`` each); ``k = W_k u``, ``v = W_v
  u`` as (KV, D); every query and key head through a zero-centred RMSNorm
  of D values, then the first ``D * partial_rotary_factor`` values
  rotated, pairs ``(i, i + rot / 2)``; query head ``h`` reads key/value
  head ``h // (H / KV)``; scores ``q . k / sqrt(D)``, causal; ``out = W_o
  [attn * sigmoid(gate)]``, the gate an element.  **The pages hold the
  key/value heads only**; 2 heads of 256 fold into a last axis of 512
  (``kv_cache.kv_pool_shape``), which the paged readers take as heads of
  two lane tiles (``ops/paged_attention.py``).
* FFN: ``latent_moe.py``'s: ``p = softmax(W_r u)`` over all
  ``n_routed_experts`` in float32, the ``num_experts_per_tok`` largest
  taken, ``w = p / sum_taken(p)``, the experts held here
  (``experts_held``), plus ``sigmoid(w_s . u) * SwiGLU_shared(u)``
  (``shared_expert_gate``).

Prefill runs the chunked form (``gdn_chunk_size`` rows a chunk) from the
state the slot's pool rows hold: zero after ``alloc``, or what an earlier
chunk of the same request left: a prompt of eight buckets carries its
state and its convolution rows through eight calls.  Bucket padding is ``g
= 0, beta = 0``, an identity of the recurrence, and the convolution
context written back is the last real rows'.  Decode runs the recurrence
one token a slot in the donated pools; an idle slot's state moves too, and
is zeroed before anything reads it (``alloc``).  An attention layer writes
the chunk's key/value heads into the slot's pages at the chunk's offset
and attends with per-row horizons; decode appends a row a slot and reads
the pages in place.  Prefill's chunks and decode's steps associate
differently, so ``exact`` selects the M-invariant ``_mm`` but decode agrees
with a full forward to rounding, not to the bit.

Counters: every executable folds what it did into ``counters
["moe_stats"]`` (:data:`COLUMNS`); ``InferenceSession.block_report()``
reads it.
"""
from __future__ import annotations

from ..base import MXNetError
from ..ops.attention import (flash_attention, paged_decode_attention,
                             paged_prefill_attention)
from ..ops.gdn import gdn_chunked, gdn_step
from ..ops.mamba2 import causal_conv, conv_step
from . import latent_moe
from .kv_cache import append_rows, kv_pool_shape
from .laguna import _rope
from .latent_moe import (_ffn_held, _resolve, fold_named, held_range,
                         prefill_block, read_named)
from .layers import rms_norm
# the expert layer is the latent block's and the K/V pools the Mamba-2
# block's, and so is what both ask of XLA
from .latent_moe import compiler_options  # noqa: F401
from .model import _mm, check_param_shapes
# the attention layers run the GPT-2 block's paged reader: its report
from .model import decode_report  # noqa: F401

BLOCK = "qwen3_next"
KINDS = ("linear_attention", "full_attention")

# ServeConfig features a session over this block refuses at construction
REFUSES = ("spec_k", "kv_quant")
REFUSES_WHY = ("a rejected draft would need the state and the convolution "
               "rows before it, and nothing snapshots a slot's state; the "
               "state is float32 values that every token rescales, with no "
               "row to scale: ROADMAP M3, M4")

# moe_stats columns.  assignments_*, distinct_held_experts,
# rows_without_held_expert, dispatch_*: latent_moe._ffn_held's counts
# (distinct_held_experts over DECODE steps only).  state_slot_layers: (slot, DeltaNet
# layer) states read and written.  prefills_from_zero / prefills_carried:
# the prefill chunks that began on the zeros ``alloc`` left and those that
# took up the state an earlier chunk wrote.  full_rows_live: summed over
# the DECODE steps and the attention layers, the rows of live slots'
# contexts (what the paged reader visits is counted on the host:
# decode_report()).
COLUMNS = ("decode_steps", "prefill_chunks", "assignments_asked",
           "assignments_held", "assignments_computed",
           "distinct_held_experts", "rows_without_held_expert",
           "state_slot_layers", "prefills_from_zero", "prefills_carried",
           "full_rows_live", "dispatch_rows", "dispatch_held")

_L2_EPS = 1e-6      # under the square root of a query's or key's length


def _gdn_dims(cfg):
    """-> (key heads, value heads, key width, value width)."""
    return (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim)


def _conv_dim(cfg):
    """Channels of ``[q | k | v]``, what the convolution runs over."""
    hk, hv, dk, dv = _gdn_dims(cfg)
    return 2 * hk * dk + hv * dv


def validate(cfg):
    sizes = _gdn_dims(cfg) + (cfg.attn_head_dim, cfg.kv_heads, cfg.max_len,
                              cfg.gdn_chunk_size)
    if min(sizes) < 1 or cfg.linear_conv_kernel_dim < 2:
        raise MXNetError(
            "ModelConfig(block=%r) needs the linear_* sizes, attn_head_dim, "
            "num_key_value_heads, max_len, gdn_chunk_size and "
            "linear_conv_kernel_dim >= 2 (got %r, %d)"
            % (BLOCK, sizes, cfg.linear_conv_kernel_dim))
    hk, hv = _gdn_dims(cfg)[:2]
    if hv % hk or cfg.num_heads % cfg.kv_heads:
        raise MXNetError(
            "%d value heads over %d key heads, %d query heads over %d "
            "key/value heads" % (hv, hk, cfg.num_heads, cfg.kv_heads))
    rot = cfg.attn_head_dim * cfg.partial_rotary_factor
    if rot != int(rot) or int(rot) % 2:
        raise MXNetError("partial_rotary_factor %g of a head of %d is not an "
                         "even count" % (cfg.partial_rotary_factor,
                                         cfg.attn_head_dim))
    if len(cfg.layer_types) != cfg.num_layers \
            or set(cfg.layer_types) - set(KINDS):
        raise MXNetError("layer_types %r: %d layers, each %s"
                         % (cfg.layer_types, cfg.num_layers,
                            " or ".join(map(repr, KINDS))))
    if cfg.scoring_func != "softmax" or cfg.first_k_dense \
            or cfg.n_shared_experts != 1 or not cfg.shared_expert_gate:
        raise MXNetError(
            "block %r routes every layer by softmax scores beside one gated "
            "shared expert (got scoring_func %r, first_k_dense %d, "
            "n_shared_experts %d, shared_expert_gate %r)"
            % (BLOCK, cfg.scoring_func, cfg.first_k_dense,
               cfg.n_shared_experts, cfg.shared_expert_gate))
    if cfg.tie_word_embeddings:
        raise MXNetError("block %r has no tied head" % BLOCK)
    latent_moe.validate_ffn(cfg)
    return cfg


def param_shapes(cfg):
    """{parameter name: shape}: matrices (out, in) as ``_mm`` takes them,
    the depthwise filter (channels, taps), a layer's held experts stacked
    on a leading axis.  The FFN's names are the latent block's, its norm's
    but for the ending (``*_norm_weight``: zero-centred)."""
    d, hd, kv = cfg.d_model, cfg.head_dim, cfg.kv_heads
    hk, hv, dk, dv = _gdn_dims(cfg)
    out = {"tok_embed_weight": (cfg.vocab_size, d),
           "final_norm_weight": (d,), "lm_head_weight": (cfg.vocab_size, d)}
    for i, kind in enumerate(cfg.layer_types):
        p = "blk%d_" % i
        out[p + "attn_norm_weight"] = (d,)
        if kind == "full_attention":
            out.update({p + "q_weight": (cfg.num_heads * 2 * hd, d),
                        p + "k_weight": (kv * hd, d),
                        p + "v_weight": (kv * hd, d),
                        p + "q_norm_weight": (hd,),
                        p + "k_norm_weight": (hd,),
                        p + "o_weight": (d, cfg.num_heads * hd)})
        else:
            out.update({
                p + "gdn_qkvz_weight": (_conv_dim(cfg) + hv * dv, d),
                p + "gdn_ba_weight": (2 * hv, d),
                p + "gdn_conv_weight": (_conv_dim(cfg),
                                        cfg.linear_conv_kernel_dim),
                p + "gdn_A_log": (hv,), p + "gdn_dt_bias": (hv,),
                p + "gdn_o_norm_gamma": (dv,),
                p + "gdn_o_weight": (d, hv * dv)})
        ffn = latent_moe.ffn_param_shapes(cfg, i)
        ffn[p + "ffn_norm_weight"] = ffn.pop(p + "ffn_norm_gamma")
        out.update(ffn)
    return out


def decay_init(cfg):
    """-> (A_log (Hv,), dt_bias (Hv,)) numpy: ``dt_bias`` the published 1,
    and ``A_log`` such that a head's decay a token at ``a = 0`` runs from
    0.999 down to 0.2 over the heads, log-spaced in ``1 - decay``.  The
    published draw (``A`` uniform in (0, 16)) forgets a state within a
    token, against which a wrong carried state cannot be told from a right
    one."""
    import numpy as np

    hv = cfg.linear_num_value_heads
    decay = 1.0 - 0.001 * 800.0 ** (np.arange(hv) / max(hv - 1, 1))
    return (np.log(-np.log(decay) / np.log1p(np.e)), np.ones(hv))


def init_params(cfg, seed=0, scale=0.02):
    """Fresh float32 parameters (tests and benches): normal matrices, the
    zero-centred norms' ``w`` zero and the plain scale one, the decays of
    :func:`decay_init`, the depthwise filter normal at 1 / sqrt(3 * taps)
    (a state no token can tell from zero tests nothing)."""
    import jax.numpy as jnp

    params = latent_moe.init_from_shapes(param_shapes(cfg), seed, scale)
    a_log, dt_bias = decay_init(cfg)
    gain = (3.0 * cfg.linear_conv_kernel_dim) ** -0.5 / scale
    for name, leaf in params.items():
        if name.endswith("_norm_weight"):
            params[name] = jnp.zeros_like(leaf)
        elif name.endswith("gdn_A_log"):
            params[name] = jnp.asarray(a_log, jnp.float32)
        elif name.endswith("gdn_dt_bias"):
            params[name] = jnp.asarray(dt_bias, jnp.float32)
        elif name.endswith("gdn_conv_weight"):
            params[name] = leaf * gain
    return params


def check_params(params, cfg):
    """The parameter dict has exactly the architecture's shapes."""
    check_param_shapes(params, param_shapes(cfg), BLOCK)


def latent_dim(cfg):
    """0: the attention layers keep per-head K and V pools."""
    return 0


def state_shapes(cfg):
    """What a slot holds in every DeltaNet layer, beside the pages of the
    attention layers: name -> (layers, one slot's shape a layer, dtype)."""
    n = cfg.layer_types.count("linear_attention")
    _, hv, dk, dv = _gdn_dims(cfg)
    return {"gdn_state": (n, (hv, dk, dv), "float32"),
            "conv_state": (n, (cfg.linear_conv_kernel_dim - 1,
                               _conv_dim(cfg)), "float32")}


def init_counters(cfg):
    """``moe_stats`` (2, len(COLUMNS)) int32, folded by the executables:
    row 0 the low 30 bits of each count, row 1 the carries."""
    import jax.numpy as jnp

    return {"moe_stats": jnp.zeros((2, len(COLUMNS)), jnp.int32)}


def guard_tag(cfg):
    """Another block altogether: key/value heads, the experts held of
    those routed, the DeltaNet heads, the layer pattern's initials."""
    return "-%s-kv%dx%d-e%dof%dk%d-gdn%dx%d-%s" % (
        BLOCK, cfg.kv_heads, cfg.head_dim, held_range(cfg)[1],
        cfg.n_routed_experts, cfg.num_experts_per_tok,
        cfg.linear_num_value_heads, cfg.linear_value_head_dim,
        "".join(t[0] for t in cfg.layer_types))


def report(counters, cfg):
    """Host side: ``moe_stats`` as exact Python ints under their names
    (``InferenceSession.block_report`` documents them), with the layers
    of each kind (no window layer: the two window counts are 0), the
    experts held, the bytes of state a slot holds and the width of the
    K/V pools' last axis at rest."""
    import math

    import numpy as np

    out = read_named(counters["moe_stats"], COLUMNS)
    out["gdn_layers"] = cfg.layer_types.count("linear_attention")
    out["full_layers"] = cfg.layer_types.count("full_attention")
    out["window_layers"] = out["window_rows_visited"] \
        = out["window_rows_in_band"] = 0
    out["expert_layers"] = cfg.num_layers
    out["experts_held"] = held_range(cfg)[1]
    out["state_bytes_per_slot"] = sum(
        layers * math.prod(shape) * np.dtype(dtype).itemsize
        for layers, shape, dtype in state_shapes(cfg).values())
    out["kv_lanes"] = kv_pool_shape(1, 1, 1, cfg.kv_heads,
                                    cfg.head_dim)[-1]
    return out


def _count(counters, incs, **inc):
    """Fold one executable's routers (``incs``, a dict a layer) and its
    own counts into ``counters["moe_stats"]``."""
    for layer in incs:
        for name, value in layer.items():
            inc[name] = inc.get(name, 0) + value
    if "decode_steps" not in inc:       # what a decode step had to read
        inc["distinct_held_experts"] = 0
    return dict(counters, moe_stats=fold_named(counters["moe_stats"],
                                               COLUMNS, inc))


def _norm(x, w, eps):
    """The zero-centred RMSNorm: ``x / rms(x) * (1 + w)``."""
    return rms_norm(x, 1.0 + w, eps)


def _ffn(params, i, x, cfg, exact, valid, dequantized):
    """``latent_moe._ffn_held`` behind this block's zero-centred norm: the
    expert layer reads its norm's scale under its own name."""
    pre = "blk%d_" % i
    return _ffn_held(
        dict(params, **{pre + "ffn_norm_gamma":
                        1.0 + params[pre + "ffn_norm_weight"]}),
        i, x, cfg, exact, valid, dequantized)


def _gdn_inputs(params, pre, u, cfg, exact):
    """u (N, d) -> the pre-activation [q | k | v] rows (N, 2 Hk Dk + Hv
    Dv), the log-decay g and the rate beta (N, Hv), both float32, and the
    output gate's argument z (N, Hv Dv)."""
    import jax
    import jax.numpy as jnp

    hv, split = cfg.linear_num_value_heads, _conv_dim(cfg)
    qkvz = _mm(u, params[pre + "gdn_qkvz_weight"], exact)
    with jax.named_scope("gdn_gate"):
        ba = _mm(u, params[pre + "gdn_ba_weight"], exact).astype(jnp.float32)
        beta = jax.nn.sigmoid(ba[:, :hv])
        g = -jnp.exp(params[pre + "gdn_A_log"].astype(jnp.float32)) \
            * jax.nn.softplus(ba[:, hv:] + params[pre + "gdn_dt_bias"])
    return qkvz[:, :split], g, beta, qkvz[:, split:]


def _gdn_heads(rows, cfg):
    """Convolved rows (N, 2 Hk Dk + Hv Dv) -> q, k (N, Hv, Dk), v (N, Hv,
    Dv) float32: SiLU, the query and the key at unit length, the query
    scaled by 1 / sqrt(Dk), a query / key head once for each value head
    that reads it."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = rows.shape[0]
    hk, hv, dk, dv = _gdn_dims(cfg)
    rows = jax.nn.silu(rows.astype(jnp.float32))
    q, k = (rows[:, j * hk * dk:(j + 1) * hk * dk].reshape(n, hk, dk)
            for j in range(2))
    q, k = (jnp.repeat(
        a * lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + _L2_EPS),
        hv // hk, axis=1) for a in (q, k))
    return q * dk ** -0.5, k, rows[:, 2 * hk * dk:].reshape(n, hv, dv)


def _gdn_out(params, pre, o, z, cfg, exact):
    """The recurrence's o (N, Hv, Dv) -> the mixer's output (N, d): the
    norm a head, the gate, the out-projection."""
    import jax

    with jax.named_scope("gdn_out_norm"):
        o = rms_norm(o, params[pre + "gdn_o_norm_gamma"], cfg.rms_norm_eps)
        y = o.reshape(o.shape[0], -1).astype(z.dtype) * jax.nn.silu(z)
    return _mm(y, params[pre + "gdn_o_weight"], exact)


def _gdn_rows(params, pre, u, state, context, length, cfg, exact):
    """One sequence's rows u (T, d) through a DeltaNet mixer, from
    ``state`` (Hv, Dk, Dv) and ``context`` (taps - 1, channels); the first
    ``length`` rows are real.  -> (out (T, d), state, context)."""
    import jax
    import jax.numpy as jnp

    rows, g, beta, z = _gdn_inputs(params, pre, u, cfg, exact)
    with jax.named_scope("gdn_conv"):
        rows, context = causal_conv(rows, context,
                                    params[pre + "gdn_conv_weight"], 0.0,
                                    length)
    with jax.named_scope("gdn_scan"):
        q, k, v = _gdn_heads(rows, cfg)
        # bucket padding: identities of the recurrence
        real = jnp.arange(u.shape[0])[:, None] < length
        o, state = gdn_chunked(q, k, v, jnp.where(real, g, 0.0),
                               jnp.where(real, beta, 0.0), state,
                               cfg.gdn_chunk_size)
    return _gdn_out(params, pre, o, z, cfg, exact), state, context


def _qkv(params, pre, u, positions, cfg, exact):
    """u (N, d) -> normed and rotated q (N, KV, G, D) with a key/value
    head's query heads as its rows, the gate's argument (N, H D), normed
    and rotated k and plain v (N, KV, D)."""
    import jax

    n, kv, hd = u.shape[0], cfg.kv_heads, cfg.head_dim
    qg = _mm(u, params[pre + "q_weight"], exact).reshape(
        n, cfg.num_heads, 2 * hd)
    k = _mm(u, params[pre + "k_weight"], exact).reshape(n, kv, hd)
    with jax.named_scope("gattn_qknorm"):
        q = _norm(qg[..., :hd], params[pre + "q_norm_weight"],
                  cfg.rms_norm_eps)
        k = _norm(k, params[pre + "k_norm_weight"], cfg.rms_norm_eps)
    with jax.named_scope("gattn_rope"):
        group = {"rope_theta": cfg.rope_theta,
                 "partial_rotary_factor": cfg.partial_rotary_factor}
        q, k = _rope(q, positions, group), _rope(k, positions, group)
    return (q.reshape(n, kv, cfg.num_heads // kv, hd),
            qg[..., hd:].reshape(n, -1), k,
            _mm(u, params[pre + "v_weight"], exact).reshape(n, kv, hd))


def _attn_out(params, pre, att, gate, exact):
    """att, gate (N, H D) -> the mixer's output (N, d): the sigmoid gate
    an element, the out-projection."""
    import jax

    with jax.named_scope("gattn_gate"):
        att = att * jax.nn.sigmoid(gate).astype(att.dtype)
    return _mm(att, params[pre + "o_weight"], exact)


def _scale(cfg):
    """The score scale, stated: 1 / 16 at the published heads of 256."""
    return cfg.head_dim ** -0.5


def _head(params, x, cfg, exact):
    x = _norm(x, params["final_norm_weight"], cfg.rms_norm_eps)
    return _mm(x, params["lm_head_weight"], exact)


def _embed(params, tokens):
    import jax.numpy as jnp

    return jnp.take(params["tok_embed_weight"], tokens.astype(jnp.int32),
                    axis=0)


def full_forward(params, tokens, cfg, exact, block=None):
    """(n, T) int tokens -> (n, T, V) logits from zero state: the forward
    the cached paths are held against.  ``block`` is the attention's key
    block (T by default)."""
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
    group = cfg.num_heads // cfg.kv_heads

    def one(seq):
        x = _embed(params, seq)
        for i, kind in enumerate(cfg.layer_types):
            pre = "blk%d_" % i
            u = _norm(x, params[pre + "attn_norm_weight"], cfg.rms_norm_eps)
            if kind == "linear_attention":
                out, _, _ = _gdn_rows(
                    params, pre, u,
                    jnp.zeros(shapes["gdn_state"][1], jnp.float32),
                    jnp.zeros(shapes["conv_state"][1], u.dtype), t, cfg,
                    exact)
            else:
                q, gate, k, v = _qkv(params, pre, u, positions, cfg, exact)
                k, v = (jnp.repeat(a, group, axis=1).transpose(1, 0, 2)
                        for a in (k, v))
                att = flash_attention(
                    q.reshape(t, cfg.num_heads, -1).transpose(1, 0, 2), k, v,
                    causal=True, scale=_scale(cfg), block=block or t,
                    mi=exact)
                out = _attn_out(params, pre,
                                att.transpose(1, 0, 2).reshape(t, -1), gate,
                                exact)
            x, _ = _ffn(params, i, x + out, cfg, exact, valid, dequantized)
        return _head(params, x, cfg, exact)

    return jax.vmap(one)(tokens)


def prefill_forward(params, tokens, length, offset, table_row, pools,
                    counters, cfg, page_size, exact, kv_quant="", slot=None):
    """Bucketed prefill of one chunk (``model.prefill_forward``'s
    contract: page-aligned ``offset``, ``length`` real tokens, rows past
    the table on the trash page; ``kv_quant`` belongs to a feature this
    block refuses).  A DeltaNet layer takes ``slot``'s state and
    convolution context from the pools, runs the chunked form over the
    bucket and writes both back: what a chunk at ``offset > 0`` starts
    from is what the chunk before it left.  An attention layer writes the
    chunk's key/value heads into the slot's pages and attends over them
    with per-row horizons ``offset + j + 1``.  The head runs on the last
    real row only.
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
    ai = gi = 0
    for i, kind in enumerate(cfg.layer_types):
        pre = "blk%d_" % i
        u = _norm(x, params[pre + "attn_norm_weight"], cfg.rms_norm_eps)
        if kind == "linear_attention":
            out, state, context = _gdn_rows(
                params, pre, u, pools["gdn_state"][gi, slot],
                pools["conv_state"][gi, slot], length, cfg, exact)
            pools["gdn_state"] = pools["gdn_state"].at[gi, slot].set(state)
            pools["conv_state"] = pools["conv_state"].at[gi, slot].set(
                context.astype(pools["conv_state"].dtype))
            gi += 1
        else:
            q, gate, k, v = _qkv(params, pre, u, abs_pos, cfg, exact)
            with jax.named_scope("gattn_prefill"):
                append_rows(pools, "k", ai, pages, offsets, k, "")
                append_rows(pools, "v", ai, pages, offsets, v, "")
                att = paged_prefill_attention(
                    q, pools["k_pool"], pools["v_pool"], ai, table_row,
                    abs_pos, page_size, block, mi=exact, scale=_scale(cfg))
            out = _attn_out(params, pre, att.reshape(t_b, -1), gate, exact)
            ai += 1
        x, inc = _ffn(params, i, x + out, cfg, exact, valid, dequantized)
        incs.append(inc)
    last = _head(params, jnp.take(x, length - 1, axis=0), cfg, exact)
    first_token = jnp.argmax(last, axis=-1).astype(jnp.int32)
    return first_token, last, pools, _count(
        counters, incs, prefill_chunks=1, state_slot_layers=gi,
        prefills_from_zero=offset == 0, prefills_carried=offset != 0)


def decode_step(params, tokens, lengths, tables, pools, counters, cfg,
                page_size, exact, kv_quant=""):
    """One decode step for every slot (``model.decode_step``'s contract).
    A DeltaNet layer advances every slot's state and convolution context
    by one token, in the donated pools; an attention layer appends each
    slot's key/value heads at ``lengths`` and reads the slot's pages in
    place.  An idle slot's state moves too, and is zeroed before anything
    reads it (``alloc``).
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
    incs = []
    ai = gi = 0
    for i, kind in enumerate(cfg.layer_types):
        pre = "blk%d_" % i
        u = _norm(x, params[pre + "attn_norm_weight"], cfg.rms_norm_eps)
        if kind == "linear_attention":
            rows, g, beta, z = _gdn_inputs(params, pre, u, cfg, exact)
            with jax.named_scope("gdn_conv"):
                rows, context = conv_step(rows, pools["conv_state"][gi],
                                          params[pre + "gdn_conv_weight"],
                                          0.0)
                pools["conv_state"] = pools["conv_state"].at[gi].set(
                    context.astype(pools["conv_state"].dtype))
            with jax.named_scope("gdn_decode"):
                q, k, v = _gdn_heads(rows, cfg)
                o, state = gdn_step(q, k, v, g, beta, pools["gdn_state"][gi])
                pools["gdn_state"] = pools["gdn_state"].at[gi].set(state)
            out = _gdn_out(params, pre, o, z, cfg, exact)
            gi += 1
        else:
            q, gate, k, v = _qkv(params, pre, u, lengths, cfg, exact)
            with jax.named_scope("gattn_decode"):
                append_rows(pools, "k", ai, page, offset, k, "")
                append_rows(pools, "v", ai, page, offset, v, "")
                att = paged_decode_attention(
                    q, pools["k_pool"], pools["v_pool"], ai, tables,
                    lengths + 1, page_size, mi=exact, scale=_scale(cfg))
            out = _attn_out(params, pre, att.reshape(s, -1), gate, exact)
            ai += 1
        x, inc = _ffn(params, i, x + out, cfg, exact, valid, dequantized)
        incs.append(inc)
    logits = _head(params, x, cfg, exact)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return next_tokens, logits, pools, _count(
        counters, incs, decode_steps=1, state_slot_layers=s * gi,
        full_rows_live=ai * jnp.where(lengths > 0, lengths + 1, 0).sum(
            ).astype(jnp.int32))
