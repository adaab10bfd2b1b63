"""The Phi-4-flash (SambaY) decoder block for the serving runtime: Mamba-1
and window differential attention in the first half of the stack, one
full-attention layer whose K/V pages every later attention layer reads,
gated memory units on one Mamba layer's scan output, no positions.

The eighth block behind ``model.BLOCKS``, selected by
``ModelConfig(block="phi4flash", ...)``.  The equations
(``benchmark/references/phi4flash_lm.py`` is their plain form, and the
tests hold this module to it; N = ``num_layers``, 0-based layer ``i``,
d = ``d_model``; arXiv:2507.06607, arXiv:2312.00752, arXiv:2410.05258):

* ``x0 = E[token]``; no positions anywhere.  Every layer:
  ``x <- x + Mixer_i(LN(x))``, then ``x <- x + MLP(LN(x))``; ``LN`` is
  LayerNorm with scale and bias at ``layer_norm_eps``; ``[g | y] = W1 u``,
  ``MLP(u) = W2 (silu(g) * y)``, no bias; ``logits = E . LN_f(x)``: the
  head is the embedding.
* the kind of layer ``i`` (:func:`layer_rule`): ``i`` even is a Mamba
  position, ``i`` odd an attention position.  ``i < N/2``: even
  ``"mamba"``, odd ``"sliding_attention"`` (``sliding_window`` keys, the
  query's own included).  ``i = N/2``: ``"mamba"``, which also hands out
  its memory ``m``.  ``i = N/2 + 1``: ``"full_attention"``, **the owner
  of the pages**.  ``i >= N/2 + 2``: even ``"gmu"``, odd
  ``"cross_attention"``.
* Mamba-1 (``d_inner = mamba_expand * d``, ``R = mamba_dt_rank``):
  ``[x | z] = W_in u``; ``x <- silu(conv1d(x))`` depthwise, causal,
  ``mamba_d_conv`` taps, with bias; ``[dt_r | B | C] = W_x x`` (R + N_s +
  N_s); ``dt = softplus(W_dt dt_r + b_dt)``; ``A = -exp(A_log)``
  (d_inner, N_s); the recurrence of ``ops/mamba1.py`` with ``D x``;
  ``out = W_out (y * silu(z))``.  At layer N/2 the memory is **``m_t =
  y_t``: the scan's output with the ``D x`` term, before the gate
  ``silu(z)``**.
* gated memory unit: ``out = W_out (silu(W_in u) * m_t)``; ``m_t`` is the
  memory layer's for the same token.  It owns no cache.
* differential attention (all three attention kinds).  ``ModelConfig``
  counts differential heads: ``num_heads`` H pairs of two published
  heads of ``half = head_dim / 2`` (published heads ``2p``, ``2p + 1`` are
  halves 1 and 2 of pair ``p``) over ``num_key_value_heads`` KV key/value
  pairs; query pair ``p`` reads key/value pair ``p // (H / KV)``.  With
  ``S_s = softmax(q_s k_s^T / sqrt(half))`` under the layer's mask and
  ``V = [v_1 | v_2]``: ``a = S_1 V - lambda S_2 V``; ``lambda = exp(lq1 .
  lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6
  exp(-0.3 i)``; ``a <- RMSNorm(a) * (1 - lambda_init)`` over the pair's
  ``head_dim`` values with a learned scale; ``out = W_o concat(a) + b_o``.
  Self layers: ``[q | k | v] = W_qkv u + b``.  Cross layers: ``q = W_q u +
  b`` only; keys and values are the owner's, all of them up to the
  query's position.

**One softmax kernel serves the two.**  With ``K = [k_1 | k_2]`` as one
head of ``head_dim``, ``[q_1 | 0] . K = q_1 . k_1`` and ``[0 | q_2] . K =
q_2 . k_2``: a layer is grouped-query attention over KV heads of
``head_dim`` with ``2 H / KV`` query rows a key/value head and the scale
stated as ``1 / sqrt(half)``, followed by the subtraction, the norm and
the factor (:func:`_query_rows`, :func:`_differential`).  So the pages
and the rings hold K and V once, KV heads of ``head_dim``, and the paged
readers of ``ops/attention.py`` and ``laguna.py``'s window readers read
them as they read any grouped-query layer's.  The zeros double the score
products; ``tests/test_serve_phi4flash.py`` holds the identity to the
four-softmax definition.

**What the cache holds** (``ModelConfig.kinds``).  A Mamba layer
(``"ssm"``), a slot: ``ssm_state``, ``h`` as (N_s, d_inner) float32 (the
channels on the lanes: ``ops/mamba1.py`` has why), and ``conv_state``, the
last ``mamba_d_conv - 1`` pre-activation rows of ``x``.  A window layer
(``"window"``): a ring of :func:`ring_pages` pages a slot, laguna's.  The
full layer (``"full"``): the K/V pools' ONE layer of pages.  A gated
memory unit and a cross-attention layer (``"shared"``): nothing.
``m_t`` lives inside a step and is never cached.

**A prefill that stops half-way.**  Nothing after the owner's K/V
projection writes any cache, so :func:`prefill_forward` runs the layers up
to the memory layer, and the owner's K/V projection, over every row of the
chunk, and the owner's attention, its MLP and every later layer over ONE
row, the chunk's last real row: the architecture's own prefill, not a
short cut (the rows left out feed nothing).  It does so in every chunk:
the session does not tell a chunk whether it ends its prompt, and one row
through half the stack costs less than a condition would.

``exact`` selects the M-invariant ``_mm`` and attention products, but
prefill's scan and decode's step, a chunk's blocks and a ring associate
differently: decode agrees with a full forward to rounding, not to the
bit.

Counters: every executable folds what it did into ``counters
["yoco_stats"]`` (:data:`COLUMNS`); ``InferenceSession.block_report()``
reads it.
"""
from __future__ import annotations

import math

from ..base import MXNetError
from ..ops.attention import flash_attention, paged_decode_attention
from ..ops.mamba1 import selective_scan, selective_step
from ..ops.mamba2 import causal_conv, conv_step
from .kv_cache import (append_rows, fold_into_ring, read_ring,
                       ring_positions)
from .latent_moe import fold_named, read_named
from .layers import rms_norm, window_decode, window_prefill
from .model import _mm, _resolve_params, check_param_shapes
# the rest of the surface is other blocks': the rings are laguna's (the
# model's window in whole pages); the pass that would carry the K/V pools
# and the rings through a step as bfloat16 is off as for the Mamba-2 block;
# the paged layers run the GPT-2 block's paged reader, so its report; the
# key block of a prefill scan is the latent block's (no chunk of this
# block scans: its one paged query row a chunk reads as a decode step's)
from .granite_hybrid import compiler_options  # noqa: F401
from .laguna import ring_pages  # noqa: F401
from .latent_moe import prefill_block  # noqa: F401
from .model import decode_report  # noqa: F401

BLOCK = "phi4flash"
KINDS = ("mamba", "sliding_attention", "full_attention", "gmu",
         "cross_attention")

# ServeConfig features a session over this block refuses at construction
REFUSES = ("spec_k", "kv_quant")
REFUSES_WHY = ("a rejected draft would need the state before it, and "
               "nothing snapshots a slot's state; the state is a float32 "
               "accumulator with no row to scale: ROADMAP M4")

# yoco_stats columns.  cross_rows: the rows that ran the layers after the
# owner's K/V projection in prefill (one a chunk).  Of the DECODE steps:
# window_rows_in_band, the ring rows of live slots inside the band, summed
# over the window layers; shared_rows_read, the rows of live slots'
# contexts, once for each layer that reads the owner's pages.
COLUMNS = ("decode_steps", "prefill_chunks", "rows_valid", "rows_padded",
           "cross_rows", "prefills_from_zero", "prefills_carried",
           "window_rows_in_band", "shared_rows_read")


def layer_rule(n):
    """The published stack of ``n`` layers (``n % 4 == 0``), as
    ``layer_types``."""
    if n < 4 or n % 4:
        raise MXNetError("the %s stack has a multiple of 4 layers, not %d"
                         % (BLOCK, n))
    half = n // 2
    return tuple(
        ("mamba" if i <= half else "gmu") if i % 2 == 0 else
        "sliding_attention" if i < half else
        "full_attention" if i == half + 1 else "cross_attention"
        for i in range(n))


def owner_layer(cfg):
    """The layer whose pages every cross-attention layer reads."""
    return cfg.layer_types.index("full_attention")


def memory_layer(cfg):
    """The Mamba layer whose scan output the gated memory units read: the
    last one."""
    return max(i for i, t in enumerate(cfg.layer_types) if t == "mamba")


def lambda_init(i):
    """The differential attention's ``lambda_init`` at layer ``i``."""
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def _d_inner(cfg):
    return cfg.mamba_expand * cfg.d_model


def _dt_rank(cfg):
    return cfg.mamba_dt_rank or -(-cfg.d_model // 16)


def validate(cfg):
    sizes = (cfg.d_ff, cfg.max_len, cfg.mamba_d_state, cfg.mamba_expand,
             cfg.kv_heads)
    if min(sizes) < 1 or cfg.mamba_d_conv < 2:
        raise MXNetError(
            "ModelConfig(block=%r) needs d_ff, max_len, mamba_d_state, "
            "mamba_expand and num_key_value_heads (got %r, mamba_d_conv %d)"
            % (BLOCK, sizes, cfg.mamba_d_conv))
    types = tuple(cfg.layer_types)
    if len(types) != cfg.num_layers or set(types) - set(KINDS):
        raise MXNetError("layer_types %r: %d layers, each of %s"
                         % (types, cfg.num_layers, ", ".join(KINDS)))
    if types.count("full_attention") != 1 \
            and ("cross_attention" in types or "full_attention" in types):
        raise MXNetError(
            "layer_types %r: cross_attention layers read ONE full_attention "
            "layer's pages, and the stack has %d"
            % (types, types.count("full_attention")))
    if "full_attention" not in types:
        raise MXNetError("layer_types %r has no full_attention layer: the "
                         "%s prefill ends at its K/V projection"
                         % (types, BLOCK))
    owner = types.index("full_attention")
    if set(types[:owner]) - {"mamba", "sliding_attention"} \
            or set(types[owner + 1:]) - {"gmu", "cross_attention"}:
        raise MXNetError(
            "layer_types %r: mamba and sliding_attention layers before the "
            "full_attention layer, gmu and cross_attention layers after it"
            % (types,))
    if "gmu" in types and "mamba" not in types:
        raise MXNetError("layer_types %r: a gmu reads a mamba layer's scan "
                         "output, and the stack has none" % (types,))
    if cfg.d_model % cfg.num_heads or cfg.num_heads % cfg.kv_heads \
            or cfg.head_dim % 2:
        raise MXNetError(
            "d_model %d as %d differential heads of an even width over %d "
            "key/value heads" % (cfg.d_model, cfg.num_heads, cfg.kv_heads))
    if cfg.num_heads * cfg.head_dim != cfg.d_model:
        raise MXNetError("%d differential heads of %d are not d_model %d"
                         % (cfg.num_heads, cfg.head_dim, cfg.d_model))
    if "sliding_attention" in types and cfg.sliding_window < 1:
        raise MXNetError("sliding_attention layers need sliding_window >= 1 "
                         "(got %d)" % cfg.sliding_window)
    if not cfg.tie_word_embeddings:
        raise MXNetError("block %r has no untied head" % BLOCK)
    return cfg


def param_shapes(cfg):
    """{parameter name: shape}: matrices (out, in) as ``_mm`` takes them.
    An attention projection's bias is ``*_b``: a leaf named ``*_bias`` is
    zero wherever weights are made by name, and a zero bias left out of a
    program cannot be told from one put in."""
    d, hd, di = cfg.d_model, cfg.head_dim, _d_inner(cfg)
    kvd = cfg.kv_heads * hd
    n, r = cfg.mamba_d_state, _dt_rank(cfg)
    out = {"tok_embed_weight": (cfg.vocab_size, d),
           "final_norm_gamma": (d,), "final_norm_beta": (d,)}
    for i, kind in enumerate(cfg.layer_types):
        p = "blk%d_" % i
        out.update({p + "mixer_norm_gamma": (d,), p + "mixer_norm_beta": (d,),
                    p + "ffn_norm_gamma": (d,), p + "ffn_norm_beta": (d,),
                    p + "ffn_in_weight": (2 * cfg.d_ff, d),
                    p + "ffn_out_weight": (d, cfg.d_ff)})
        if kind == "mamba":
            out.update({p + "in_weight": (2 * di, d),
                        p + "conv_weight": (di, cfg.mamba_d_conv),
                        p + "conv_bias": (di,),
                        p + "x_weight": (r + 2 * n, di),
                        p + "dt_weight": (di, r), p + "dt_bias": (di,),
                        p + "A_log": (di, n), p + "D": (di,),
                        p + "out_weight": (d, di)})
        elif kind == "gmu":
            out.update({p + "gmu_in_weight": (di, d),
                        p + "gmu_out_weight": (d, di)})
        else:
            if kind == "cross_attention":
                out.update({p + "q_weight": (d, d), p + "q_b": (d,)})
            else:
                out.update({p + "qkv_weight": (d + 2 * kvd, d),
                            p + "qkv_b": (d + 2 * kvd,)})
            out.update({p + "o_weight": (d, d), p + "o_b": (d,),
                        p + "subln_gamma": (hd,)})
            out.update({p + "lambda_" + v: (hd // 2,)
                        for v in ("q1", "k1", "q2", "k2")})
    return out


def init_params(cfg, seed=0, scale=0.02):
    """Fresh float32 parameters (tests and benches): normal matrices and
    attention biases, norm scales one and their biases zero, the
    convolution's bias zero, the ``lambda`` vectors normal at 0.1; in
    every channel ``A`` a ladder from 1 to the state size; ``dt`` (through
    ``dt_bias``) log-spaced from 0.001 to 0.1 across the channels in
    another order, its projection at the variance of the published
    uniform +- R^-0.5; ``D`` one; the depthwise filter normal at 1 /
    sqrt(3 * taps) (a state no token can tell from zero tests
    nothing)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    shapes = param_shapes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    di, n = _d_inner(cfg), cfg.mamba_d_state
    chan = np.arange(di)
    dt = 0.001 * 100.0 ** (((chan * 27) % di) / max(di - 1, 1))
    fixed = {"A_log": np.broadcast_to(np.log(np.arange(1.0, n + 1)), (di, n)),
             "dt_bias": np.log(np.expm1(dt)), "D": np.ones(di)}
    stds = {"conv_weight": (3.0 * cfg.mamba_d_conv) ** -0.5,
            "dt_weight": (3.0 * _dt_rank(cfg)) ** -0.5}
    params = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        leaf = name.split("_", 1)[1]
        if leaf in fixed:
            params[name] = jnp.asarray(fixed[leaf], jnp.float32)
        elif name.endswith("_gamma"):
            params[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith(("_beta", "_bias")):
            params[name] = jnp.zeros(shape, jnp.float32)
        else:
            std = 0.1 if leaf.startswith("lambda_") else stds.get(leaf, scale)
            params[name] = (std * jax.random.normal(key, shape)
                            ).astype(jnp.float32)
    return params


def check_params(params, cfg):
    """The parameter dict has exactly the architecture's shapes."""
    check_param_shapes(params, param_shapes(cfg), BLOCK)


def latent_dim(cfg):
    """0: the owner keeps per-head K and V pools."""
    return 0


def state_shapes(cfg):
    """What a slot holds in every Mamba layer: name -> (layers, one slot's
    shape a layer, dtype).  The state lies (N_s, d_inner)."""
    n = cfg.layer_types.count("mamba")
    return {"ssm_state": (n, (cfg.mamba_d_state, _d_inner(cfg)), "float32"),
            "conv_state": (n, (cfg.mamba_d_conv - 1, _d_inner(cfg)),
                           "float32")}


def init_counters(cfg):
    """``yoco_stats`` (2, len(COLUMNS)) int32, folded by the executables:
    row 0 the low 30 bits of each count, row 1 the carries."""
    import jax.numpy as jnp

    return {"yoco_stats": jnp.zeros((2, len(COLUMNS)), jnp.int32)}


def guard_tag(cfg):
    """Another block altogether: key/value heads, the window, the Mamba
    sizes, the layer pattern's initials."""
    return "-%s-kv%dx%d-w%d-m%dx%d-%s" % (
        BLOCK, cfg.kv_heads, cfg.head_dim, cfg.sliding_window,
        _d_inner(cfg), cfg.mamba_d_state,
        "".join(t[0] for t in cfg.layer_types))


def report(counters, cfg):
    """Host side: ``yoco_stats`` as exact Python ints under their names
    (``InferenceSession.block_report`` documents them), with the layers
    of each kind, the layers that read the owner's pages in a step (the
    owner among them) and the bytes of state a slot holds."""
    import numpy as np

    out = read_named(counters["yoco_stats"], COLUMNS)
    types = cfg.layer_types
    out["mamba_layers"] = types.count("mamba")
    out["window_layers"] = types.count("sliding_attention")
    out["full_layers"] = types.count("full_attention")
    out["gmu_layers"] = types.count("gmu")
    out["cross_layers"] = types.count("cross_attention")
    out["shared_readers"] = out["full_layers"] + out["cross_layers"]
    out["sliding_window"] = cfg.sliding_window
    out["state_bytes_per_slot"] = sum(
        layers * math.prod(shape) * np.dtype(dtype).itemsize
        for layers, shape, dtype in state_shapes(cfg).values())
    return out


def _count(counters, **inc):
    """Fold one executable's counts into ``counters["yoco_stats"]``."""
    return dict(counters, yoco_stats=fold_named(counters["yoco_stats"],
                                                COLUMNS, inc))


def _ln(params, name, x, cfg):
    """LayerNorm with scale and bias, rsqrt form; row-wise."""
    import jax.numpy as jnp
    from jax import lax

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + cfg.layer_norm_eps) \
        * params[name + "_gamma"] + params[name + "_beta"]


def _mlp(params, pre, x, cfg, exact):
    import jax
    import jax.numpy as jnp

    u = _ln(params, pre + "ffn_norm", x, cfg)
    gate, value = jnp.split(_mm(u, params[pre + "ffn_in_weight"], exact), 2,
                            axis=-1)
    return x + _mm(jax.nn.silu(gate) * value, params[pre + "ffn_out_weight"],
                   exact)


def _scan_inputs(params, pre, conv, cfg, exact):
    """The convolution's rows (N, d_inner), before the activation -> x
    (N, d_inner) activated, dt (N, d_inner) positive float32, A (d_inner,
    N_s), B and C (N, N_s) as ``ops/mamba1.py`` takes them."""
    import jax
    import jax.numpy as jnp

    r, n = _dt_rank(cfg), cfg.mamba_d_state
    x = jax.nn.silu(conv)
    dbc = _mm(x, params[pre + "x_weight"], exact)
    dt = jax.nn.softplus(
        _mm(dbc[:, :r], params[pre + "dt_weight"], exact).astype(jnp.float32)
        + params[pre + "dt_bias"])
    return (x, dt, -jnp.exp(params[pre + "A_log"].astype(jnp.float32)),
            dbc[:, r:r + n], dbc[:, r + n:])


def _mamba_rows(params, pre, u, state, context, length, cfg, exact):
    """One sequence's rows u (T, d) through a Mamba-1 mixer, from
    ``state`` (N_s, d_inner) and ``context`` (K - 1, d_inner); the first
    ``length`` rows are real.  -> (out (T, d), the scan's y (T, d_inner):
    the memory, state, context)."""
    import jax
    import jax.numpy as jnp

    x, z = jnp.split(_mm(u, params[pre + "in_weight"], exact), 2, axis=-1)
    with jax.named_scope("ssm_conv"):
        conv, context = causal_conv(x, context, params[pre + "conv_weight"],
                                    params[pre + "conv_bias"], length)
    with jax.named_scope("ssm_scan"):
        x, dt, a, b, c = _scan_inputs(params, pre, conv, cfg, exact)
        y, state = selective_scan(x, dt, a, b, c, params[pre + "D"], state,
                                  length)
    y = y.astype(z.dtype)
    out = _mm(y * jax.nn.silu(z), params[pre + "out_weight"], exact)
    return out, y, state, context


def _gmu(params, pre, u, memory, exact):
    import jax

    return _mm(jax.nn.silu(_mm(u, params[pre + "gmu_in_weight"], exact))
               * memory, params[pre + "gmu_out_weight"], exact)


def _score_scale(cfg):
    return (cfg.head_dim // 2) ** -0.5


def _query_rows(q, cfg):
    """q (N, d) -> (N, KV, G, head_dim): each differential head as the two
    rows ``[q_1 | 0]`` and ``[0 | q_2]``, a key/value head's G = 2 H / KV
    rows together (pair, half: the half runs fastest)."""
    import jax.numpy as jnp

    n, hd = q.shape[0], cfg.head_dim
    first = (jnp.arange(hd) < hd // 2).astype(q.dtype)
    q = q.reshape(n, cfg.num_heads, 1, hd) * jnp.stack([first, 1 - first])
    return q.reshape(n, cfg.kv_heads, -1, hd)


def _lambda(params, pre, i):
    import jax.numpy as jnp

    f32 = jnp.float32
    dot = lambda a, b: jnp.sum(params[pre + "lambda_" + a].astype(f32)
                               * params[pre + "lambda_" + b].astype(f32))
    return jnp.exp(dot("q1", "k1")) - jnp.exp(dot("q2", "k2")) \
        + lambda_init(i)


def _differential(params, pre, i, att, cfg, exact):
    """The rows' results att (N, KV, G x head_dim) -> the mixer's output
    (N, d): ``S_1 V - lambda S_2 V``, the norm over a pair's values, the
    factor, ``W_o`` and its bias."""
    import jax

    with jax.named_scope("diff_norm"):
        n, hd = att.shape[0], cfg.head_dim
        att = att.reshape(n, cfg.num_heads, 2, hd)
        a = att[:, :, 0] - _lambda(params, pre, i).astype(att.dtype) \
            * att[:, :, 1]
        a = rms_norm(a, params[pre + "subln_gamma"], cfg.rms_norm_eps) \
            * (1.0 - lambda_init(i))
    return _mm(a.reshape(n, -1), params[pre + "o_weight"], exact) \
        + params[pre + "o_b"]


def _qkv(params, pre, u, cfg, exact):
    """u (N, d) -> the query rows (N, KV, G, head_dim), k and v (N, KV,
    head_dim) of a self-attention layer."""
    d = cfg.d_model
    qkv = _mm(u, params[pre + "qkv_weight"], exact) + params[pre + "qkv_b"]
    return (_query_rows(qkv[:, :d], cfg),) + _key_value_heads(qkv[:, d:],
                                                              cfg)


def _key_value_heads(kv, cfg):
    """[k | v] (N, 2 x KV x head_dim) -> k, v (N, KV, head_dim)."""
    kv = kv.reshape(kv.shape[0], 2, cfg.kv_heads, cfg.head_dim)
    return kv[:, 0], kv[:, 1]


def _kv_only(params, pre, u, cfg, exact):
    """The owner's K and V (N, KV, head_dim) without its queries."""
    d = cfg.d_model
    return _key_value_heads(_mm(u, params[pre + "qkv_weight"][d:], exact)
                            + params[pre + "qkv_b"][d:], cfg)


def _queries(params, pre, kind, u, cfg, exact):
    """The query rows of a layer that reads the owner's pages."""
    if kind == "cross_attention":
        q = _mm(u, params[pre + "q_weight"], exact) + params[pre + "q_b"]
    else:
        d = cfg.d_model
        q = _mm(u, params[pre + "qkv_weight"][:d], exact) \
            + params[pre + "qkv_b"][:d]
    return _query_rows(q, cfg)


def _head(params, x, cfg, exact):
    return _mm(_ln(params, "final_norm", x, cfg), params["tok_embed_weight"],
               exact)


def _embed(params, tokens):
    import jax.numpy as jnp

    return jnp.take(params["tok_embed_weight"], tokens.astype(jnp.int32),
                    axis=0)


def full_forward(params, tokens, cfg, exact, block=None):
    """(n, T) int tokens -> (n, T, V) logits from zero state, every row
    through every layer: the forward the cached paths are held against.
    ``block`` is the attention's key block (T by default)."""
    import jax
    import jax.numpy as jnp

    params = _resolve_params(params)
    t = tokens.shape[-1]
    if t > cfg.max_len:
        raise MXNetError("sequence length %d > model max_len %d"
                         % (t, cfg.max_len))
    shapes = state_shapes(cfg)
    group = 2 * cfg.num_heads // cfg.kv_heads

    def attend(q, k, v, window):
        k, v = (jnp.repeat(a, group, axis=1).transpose(1, 0, 2)
                for a in (k, v))
        att = flash_attention(
            q.reshape(t, -1, cfg.head_dim).transpose(1, 0, 2), k, v,
            causal=True, scale=_score_scale(cfg), block=block or t,
            mi=exact, window=window)
        return att.transpose(1, 0, 2).reshape(t, cfg.kv_heads, -1)

    def one(seq):
        x = _embed(params, seq)
        memory = owned = None
        for i, kind in enumerate(cfg.layer_types):
            pre = "blk%d_" % i
            u = _ln(params, pre + "mixer_norm", x, cfg)
            if kind == "mamba":
                out, memory, _, _ = _mamba_rows(
                    params, pre, u,
                    jnp.zeros(shapes["ssm_state"][1], jnp.float32),
                    jnp.zeros(shapes["conv_state"][1], u.dtype), t, cfg,
                    exact)
            elif kind == "gmu":
                out = _gmu(params, pre, u, memory, exact)
            else:
                if kind == "cross_attention":
                    q = _queries(params, pre, kind, u, cfg, exact)
                    k, v = owned
                else:
                    q, k, v = _qkv(params, pre, u, cfg, exact)
                if kind == "full_attention":
                    owned = (k, v)
                out = _differential(
                    params, pre, i, attend(
                        q, k, v, cfg.sliding_window
                        if kind == "sliding_attention" else 0), cfg, exact)
            x = _mlp(params, pre, x + out, cfg, exact)
        return _head(params, x, cfg, exact)

    return jax.vmap(one)(tokens)


def prefill_forward(params, tokens, length, offset, table_row, pools,
                    counters, cfg, page_size, exact, kv_quant="", slot=None):
    """Bucketed prefill of one chunk (``model.prefill_forward``'s
    contract: page-aligned ``offset``, ``length`` real tokens, rows past
    the table on the trash page; ``kv_quant`` belongs to a feature this
    block refuses).  Over every row of the chunk: a Mamba layer takes
    ``slot``'s state and convolution context from the pools, scans the
    bucket and writes both back; a window layer reads the slot's ring as
    the chunks before left it, attends over it and the chunk's own rows
    under the band, then folds the chunk's last real rows in; the owner
    writes the chunk's key/value heads into the slot's pages.  Over the
    chunk's last real row alone: the owner's attention and MLP, the gated
    memory units (on that row's memory) and the cross-attention layers
    (over the owner's pages up to that row), and the head.
    -> (first_token, last_logits, pools, counters)."""
    import jax
    import jax.numpy as jnp

    params = _resolve_params(params)
    _, t_b = tokens.shape
    if t_b % page_size:
        raise MXNetError("bucket length %d not a multiple of page size %d"
                         % (t_b, page_size))
    max_pages = table_row.shape[0]
    pools = dict(pools)
    trash = pools["k_pool"].shape[1] - 1
    abs_pos = offset + jnp.arange(t_b, dtype=jnp.int32)
    idx = abs_pos // page_size
    pages = jnp.where(idx < max_pages,
                      table_row[jnp.clip(idx, 0, max_pages - 1)], trash)
    offsets = abs_pos % page_size
    scale = _score_scale(cfg)
    # the one row the second half runs on, its position and its horizon
    last = length - 1
    tables, horizon = table_row[None], (offset + length)[None]
    x = _embed(params, tokens[0])
    memory = None
    mi = wi = 0
    for i, kind in enumerate(cfg.layer_types):
        pre = "blk%d_" % i
        u = _ln(params, pre + "mixer_norm", x, cfg)
        if kind == "mamba":
            out, memory, state, context = _mamba_rows(
                params, pre, u, pools["ssm_state"][mi, slot],
                pools["conv_state"][mi, slot], length, cfg, exact)
            pools["ssm_state"] = pools["ssm_state"].at[mi, slot].set(state)
            pools["conv_state"] = pools["conv_state"].at[mi, slot].set(
                context.astype(pools["conv_state"].dtype))
            mi += 1
        elif kind == "sliding_attention":
            q, k, v = _qkv(params, pre, u, cfg, exact)
            with jax.named_scope("swa_prefill"):
                rows = pools["kw_pool"].shape[2]
                att = window_prefill(
                    q, k, v,
                    read_ring(pools["kw_pool"], wi, cfg.head_dim, slot),
                    read_ring(pools["vw_pool"], wi, cfg.head_dim, slot),
                    ring_positions(rows, offset - 1), abs_pos,
                    cfg.sliding_window, exact, scale)
            with jax.named_scope("swa_append"):
                fold_into_ring(pools, "kw", wi, slot, k, offset, length)
                fold_into_ring(pools, "vw", wi, slot, v, offset, length)
            out = _differential(params, pre, i, att, cfg, exact)
            wi += 1
        elif kind == "gmu":
            out = _gmu(params, pre, u, memory, exact)
        else:
            if kind == "full_attention":
                with jax.named_scope("yoco_append"):
                    k, v = _kv_only(params, pre, u, cfg, exact)
                    append_rows(pools, "k", 0, pages, offsets, k, "")
                    append_rows(pools, "v", 0, pages, offsets, v, "")
                # from here on: the chunk's last real row and no other
                x, u = x[last][None], u[last][None]
                if memory is not None:
                    memory = memory[last][None]
            with jax.named_scope("yoco_prefill"):
                att = paged_decode_attention(
                    _queries(params, pre, kind, u, cfg, exact),
                    pools["k_pool"], pools["v_pool"], 0, tables, horizon,
                    page_size, mi=exact, scale=scale)
            out = _differential(params, pre, i, att.reshape(1, cfg.kv_heads,
                                                            -1), cfg, exact)
        x = _mlp(params, pre, x + out, cfg, exact)
    logits = _head(params, x[0], cfg, exact)
    first_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return first_token, logits, pools, _count(
        counters, prefill_chunks=1, rows_valid=length,
        rows_padded=t_b - length, cross_rows=1,
        prefills_from_zero=offset == 0, prefills_carried=offset != 0)


def decode_step(params, tokens, lengths, tables, pools, counters, cfg,
                page_size, exact, kv_quant=""):
    """One decode step for every slot (``model.decode_step``'s contract),
    every layer.  A Mamba layer advances every slot's state and
    convolution context by one token, in the donated pools; a window
    layer appends each slot's key/value heads at ``lengths % rows`` of its
    ring and attends over the ring's rows inside the band; the owner
    appends them at ``lengths`` in the slot's pages; the owner and every
    cross-attention layer read those pages in place up to the longest
    live context; a gated memory unit reads the memory layer's scan
    output of this step.  An idle slot's state moves too, and is zeroed
    before anything reads it (``alloc``).
    -> (next_tokens, logits, pools, counters)."""
    import jax
    import jax.numpy as jnp

    params = _resolve_params(params)
    s = tokens.shape[0]
    max_pages = tables.shape[1]
    pools = dict(pools)
    x = _embed(params, tokens)
    page_slot = jnp.clip(lengths // page_size, 0, max_pages - 1)
    page = jnp.take_along_axis(tables, page_slot[:, None], axis=1)[:, 0]
    offset = lengths % page_size
    slot_ids = jnp.arange(s)
    live = lengths > 0
    scale = _score_scale(cfg)
    in_band = jnp.zeros((), jnp.int32)
    memory = None
    mi = wi = readers = 0
    for i, kind in enumerate(cfg.layer_types):
        pre = "blk%d_" % i
        u = _ln(params, pre + "mixer_norm", x, cfg)
        if kind == "mamba":
            xz = _mm(u, params[pre + "in_weight"], exact)
            xin, z = jnp.split(xz, 2, axis=-1)
            with jax.named_scope("ssm_conv"):
                conv, context = conv_step(
                    xin, pools["conv_state"][mi],
                    params[pre + "conv_weight"], params[pre + "conv_bias"])
                pools["conv_state"] = pools["conv_state"].at[mi].set(
                    context.astype(pools["conv_state"].dtype))
            with jax.named_scope("ssm_decode"):
                xs, dt, a, b, c = _scan_inputs(params, pre, conv, cfg, exact)
                memory, state = selective_step(
                    xs, dt, a, b, c, params[pre + "D"],
                    pools["ssm_state"][mi])
                pools["ssm_state"] = pools["ssm_state"].at[mi].set(state)
            memory = memory.astype(z.dtype)
            out = _mm(memory * jax.nn.silu(z), params[pre + "out_weight"],
                      exact)
            mi += 1
        elif kind == "sliding_attention":
            q, k, v = _qkv(params, pre, u, cfg, exact)
            with jax.named_scope("swa_append"):
                row = lengths % pools["kw_pool"].shape[2]
                append_rows(pools, "kw", wi, slot_ids, row, k, "")
                append_rows(pools, "vw", wi, slot_ids, row, v, "")
            with jax.named_scope("swa_decode"):
                att, seen = window_decode(
                    q, read_ring(pools["kw_pool"], wi, cfg.head_dim),
                    read_ring(pools["vw_pool"], wi, cfg.head_dim), lengths,
                    cfg.sliding_window, exact, scale)
            in_band = in_band + jnp.where(live, seen, 0).sum().astype(
                jnp.int32)
            out = _differential(params, pre, i, att, cfg, exact)
            wi += 1
        elif kind == "gmu":
            out = _gmu(params, pre, u, memory, exact)
        else:
            with jax.named_scope("yoco_decode"):
                if kind == "full_attention":
                    q, k, v = _qkv(params, pre, u, cfg, exact)
                    append_rows(pools, "k", 0, page, offset, k, "")
                    append_rows(pools, "v", 0, page, offset, v, "")
                else:
                    q = _queries(params, pre, kind, u, cfg, exact)
                att = paged_decode_attention(
                    q, pools["k_pool"], pools["v_pool"], 0, tables,
                    lengths + 1, page_size, mi=exact, scale=scale)
            out = _differential(params, pre, i, att.reshape(s, cfg.kv_heads,
                                                            -1), cfg, exact)
            readers += 1
        x = _mlp(params, pre, x + out, cfg, exact)
    logits = _head(params, x, cfg, exact)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return next_tokens, logits, pools, _count(
        counters, decode_steps=1, window_rows_in_band=in_band,
        shared_rows_read=readers * jnp.where(live, lengths + 1, 0).sum(
        ).astype(jnp.int32))
