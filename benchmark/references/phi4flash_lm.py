"""Plain reference: the SambaY decoder as Phi-4-mini-flash-reasoning
configures it (``model_type: phi4flash``; keys as in the model's
``config.json``, and the Mamba-1 sizes its modelling file hard-codes under
the names the configuration file gives them): Mamba-1 and window
differential attention in the first half of the stack, one full-attention
layer whose keys and values every later attention layer reads, gated
memory units on one Mamba layer's scan output, no positions.

Written from the published ``config.json``, the SambaY paper
(arXiv:2507.06607), the Mamba paper (arXiv:2312.00752, algorithm 2: the
recurrence below is its definition), the differential-attention paper
(arXiv:2410.05258, section 2.1 and its ``lambda`` re-parameterisation) and
the published modelling file.  N = ``num_hidden_layers``, 0-based layer
``i``, d = ``hidden_size``:

* ``x0 = E[token]``: nothing is added to ``x`` and no query or key is
  rotated.  Every layer: ``x <- x + Mixer_i(LN(x))``, then ``x <- x +
  MLP(LN(x))``; ``LN`` is LayerNorm with scale and bias at
  ``layer_norm_eps``; ``[g | y] = W1 u`` (2 x ``intermediate_size``),
  ``MLP(u) = W2 (silu(g) * y)``, no bias.  ``logits = E . LN_f(x)``: the
  head is the embedding (``tie_word_embeddings``).
* the kind of layer ``i`` is ``layer_types[i]``, which the configuration
  file spells out by the modelling file's rule: ``i`` even is a Mamba
  position, ``i`` odd an attention position; ``i < N/2``: ``"mamba"`` /
  ``"sliding_attention"``; ``i = N/2``: ``"mamba"``, which also hands out
  its memory; ``i = N/2 + 1``: ``"full_attention"``, whose keys and values
  the later attention layers read; ``i >= N/2 + 2``: ``"gmu"`` /
  ``"cross_attention"``.
* Mamba-1 layer (``d_inner = mamba_expand * d``, R = ``mamba_dt_rank``, N_s
  = ``mamba_d_state``): ``[x | z] = W_in u``; ``x <- silu(conv1d(x))``:
  depthwise, causal (``mamba_d_conv - 1`` zero rows before the sequence),
  with bias; ``[dt_r | B | C] = W_x x`` of widths R | N_s | N_s; ``dt =
  softplus(W_dt dt_r + b_dt)``; ``A = -exp(A_log)``, a value a channel and
  state; per channel ``c`` a state of N_s values, zero before the
  sequence: ``h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c]
  B_t[n] x_t[c]``, ``y_t[c] = sum_n C_t[n] h_t[c, n] + D[c] x_t[c]``;
  ``out = W_out (y * silu(z))``.  The memory of the last Mamba layer is
  ``m_t = y_t``: with the ``D x`` term, before the gate.
* gated memory unit: ``out = W_out (silu(W_in u) * m_t)``, no bias.
* differential attention, all three attention kinds:
  ``num_attention_heads`` query heads and ``num_key_value_heads``
  key/value heads of ``hidden_size / num_attention_heads``; consecutive
  heads ``(2p, 2p + 1)`` are halves 1 and 2 of pair ``p``, and query pair
  ``p`` reads key/value pair ``p // (query pairs / key/value pairs)``.
  ``S_s = softmax(q_s k_s^T / sqrt(head width))`` over the keys ``j`` a
  query ``i`` may see (``0 <= i - j``, and ``i - j < sliding_window`` in a
  ``"sliding_attention"`` layer); ``V = [v_1 | v_2]``; ``a = S_1 V -
  lambda S_2 V``; ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 i)``; ``a <-
  RMSNorm(a) * (1 - lambda_init)`` over the pair's values, with a learned
  scale, at ``layer_norm_eps``; ``out = W_o concat(a) + b_o``.  Self
  layers: ``[q | k | v] = W_qkv u + b``.  Cross layers: ``q = W_q u + b``
  only, against the ``"full_attention"`` layer's keys and values.

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision,
no kernels, no cache, no batching, nothing imported from the program under
test.  **The recurrence runs token by token** (``lax.scan`` over ``t``),
**every row runs every layer** (the program's prefill runs the second half
of the stack for one row), and **a pair's two softmaxes are computed as
two** (the program computes them as rows of one grouped-query attention).
So that the published widths fit one chip beside the weights, attention
runs one query pair at a time (``lax.map``) and the head is computed a
block of rows at a time into one (T, vocab) buffer.
"""
import math

import jax
import jax.numpy as jnp

PRECISION = "highest"


def _sizes(cfg):
    d = cfg["hidden_size"]
    return (cfg["mamba_expand"] * d, cfg["mamba_dt_rank"],
            cfg["mamba_d_state"], d // cfg["num_attention_heads"])


def spec(cfg):
    """{parameter name: shape} for a configuration.  Matrices are stored
    (out, in) as the checkpoints store them, the depthwise filter as
    (channels, taps); there is no head matrix.  An attention projection's
    bias is ``*_b`` (``weights.py`` draws it; a ``*_bias`` it sets to
    zero)."""
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    di, r, n, hd = _sizes(cfg)
    kvd = cfg["num_key_value_heads"] * hd
    out = {"tok_embed_weight": (v, d), "final_norm_gamma": (d,),
           "final_norm_beta": (d,)}
    for i, kind in enumerate(cfg["layer_types"]):
        p = "blk%d_" % i
        out.update({p + "mixer_norm_gamma": (d,), p + "mixer_norm_beta": (d,),
                    p + "ffn_norm_gamma": (d,), p + "ffn_norm_beta": (d,),
                    p + "ffn_in_weight": (2 * f, d),
                    p + "ffn_out_weight": (d, f)})
        if kind == "mamba":
            out.update({p + "in_weight": (2 * di, d),
                        p + "conv_weight": (di, cfg["mamba_d_conv"]),
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
                        p + "subln_gamma": (2 * hd,)})
            out.update({p + "lambda_" + s: (hd,)
                        for s in ("q1", "k1", "q2", "k2")})
    return out


def _layer_norm(x, p, name, cfg):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + cfg["layer_norm_eps"]) \
        * p[name + "_gamma"] + p[name + "_beta"]


def _linear(x, w):
    return jnp.matmul(x, w.T, precision=PRECISION)


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def _attention(u, p, pre, i, kind, cfg, owned):
    """-> (the mixer's output, the keys and values it used)."""
    t, d = u.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    if kind == "cross_attention":
        q = _linear(u, p[pre + "q_weight"]) + p[pre + "q_b"]
        k, v = owned
    else:
        qkv = _linear(u, p[pre + "qkv_weight"]) + p[pre + "qkv_b"]
        q = qkv[:, :d]
        k = qkv[:, d:d + kv * hd].reshape(t, kv // 2, 2, hd)
        v = qkv[:, d + kv * hd:].reshape(t, kv // 2, 2 * hd)
    q = q.reshape(t, h // 2, 2, hd)
    behind = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    seen = behind >= 0
    if kind == "sliding_attention":
        seen = seen & (behind < cfg["sliding_window"])
    lam = jnp.exp(jnp.sum(p[pre + "lambda_q1"] * p[pre + "lambda_k1"])) \
        - jnp.exp(jnp.sum(p[pre + "lambda_q2"] * p[pre + "lambda_k2"])) \
        + lambda_init(i)
    per_kv = (h // 2) // (kv // 2)

    def softmax(qs, ks):
        scores = jnp.matmul(qs, ks.T, precision=PRECISION) / math.sqrt(hd)
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)

    def one_pair(pair):
        qp = q[:, pair]                                  # (t, 2, hd)
        kp, vp = k[:, pair // per_kv], v[:, pair // per_kv]
        first = jnp.matmul(softmax(qp[:, 0], kp[:, 0]), vp,
                           precision=PRECISION)
        second = jnp.matmul(softmax(qp[:, 1], kp[:, 1]), vp,
                            precision=PRECISION)
        a = first - lam.astype(first.dtype) * second
        var = jnp.mean(jnp.square(a), axis=-1, keepdims=True)
        return a / jnp.sqrt(var + cfg["layer_norm_eps"]) \
            * p[pre + "subln_gamma"] * (1.0 - lambda_init(i))

    ctx = jax.lax.map(one_pair, jnp.arange(h // 2))      # (pairs, t, 2 hd)
    out = _linear(ctx.transpose(1, 0, 2).reshape(t, d).astype(u.dtype),
                  p[pre + "o_weight"]) + p[pre + "o_b"]
    return out, (k, v)


def _mamba(u, p, pre, cfg):
    """-> (the mixer's output, the scan's output y: the memory)."""
    t = u.shape[0]
    di, r, n, _ = _sizes(cfg)
    taps = cfg["mamba_d_conv"]
    xz = _linear(u, p[pre + "in_weight"])
    x, z = xz[:, :di], xz[:, di:]
    padded = jnp.concatenate([jnp.zeros((taps - 1, di), x.dtype), x])
    x = jax.nn.silu(p[pre + "conv_bias"] + sum(
        padded[j:j + t] * p[pre + "conv_weight"][:, j] for j in range(taps)))
    dbc = _linear(x, p[pre + "x_weight"])
    dt = jax.nn.softplus(_linear(dbc[:, :r], p[pre + "dt_weight"])
                         + p[pre + "dt_bias"])
    b, c = dbc[:, r:r + n], dbc[:, r + n:]
    a = -jnp.exp(p[pre + "A_log"])
    skip = p[pre + "D"]

    def token(h, row):
        x_t, dt_t, b_t, c_t = row            # (di,), (di,), (n,), (n,)
        h = jnp.exp(dt_t[:, None] * a) * h \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return h, jnp.sum(h * c_t[None, :], axis=-1) + skip * x_t

    _, y = jax.lax.scan(token, jnp.zeros((di, n), x.dtype), (x, dt, b, c))
    return _linear(y * jax.nn.silu(z), p[pre + "out_weight"]), y


def _head(x, embed, rows=256):
    """(T, d) . (V, d)^T, a block of rows at a time, written into one
    (T, V) buffer where it lies."""
    t, v = x.shape[0], embed.shape[0]
    if t % rows:
        return _linear(x, embed).astype(jnp.float32)

    def block(i, out):
        part = _linear(jax.lax.dynamic_slice_in_dim(x, i * rows, rows),
                       embed).astype(jnp.float32)
        return jax.lax.dynamic_update_slice_in_dim(out, part, i * rows, 0)

    return jax.lax.fori_loop(0, t // rows, block,
                             jnp.zeros((t, v), jnp.float32))


def logits(params, tokens, cfg, cast=None):
    """(T,) int tokens -> (T, vocab) float32 logits of one sequence.

    ``cast`` computes in a lower precision: parameters and activations,
    the state among them, are held in that type."""
    p = params
    if cast is not None:
        p = {k: v.astype(cast) for k, v in params.items()}
    x = p["tok_embed_weight"][tokens]
    memory = owned = None
    for i, kind in enumerate(cfg["layer_types"]):
        pre = "blk%d_" % i
        u = _layer_norm(x, p, pre + "mixer_norm", cfg)
        if kind == "mamba":
            out, memory = _mamba(u, p, pre, cfg)
        elif kind == "gmu":
            out = _linear(jax.nn.silu(_linear(u, p[pre + "gmu_in_weight"]))
                          * memory, p[pre + "gmu_out_weight"])
        else:
            out, used = _attention(u, p, pre, i, kind, cfg, owned)
            if kind == "full_attention":
                owned = used
        x = x + out
        u = _layer_norm(x, p, pre + "ffn_norm", cfg)
        gate, value = jnp.split(_linear(u, p[pre + "ffn_in_weight"]), 2,
                                axis=-1)
        x = x + _linear(jax.nn.silu(gate) * value, p[pre + "ffn_out_weight"])
    return _head(_layer_norm(x, p, "final_norm", cfg), p["tok_embed_weight"])
