"""Plain reference: the BailingHybrid decoder as Ling-3.0-flash configures
it (``model_type: bailing_hybrid``; keys as in the model's ``config.json``):
Kimi Delta Attention (KDA) layers, a gated latent-attention (MLA) layer at
every ``layer_group_size``-th place, one leading dense SwiGLU, then
group-routed experts with one shared expert.

Written from the published ``config.json``, the KDA paper (arXiv:2510.26692,
section 3: the recurrence below is its definition, not its chunked
algorithm), the DeepSeek-V2 / V3 papers for the latent attention and the
router (arXiv:2405.04434 section 2.1, arXiv:2412.19437 section 2.1), and
``references/deepseek_v3_lm.py``, whose latent attention this repeats:

* block: ``h = x + Mix(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, RMSNorm
  with a learned scale at ``rms_norm_eps``; after the last block ``logits =
  W_head . RMSNorm(y)``.  No position table, no bias, the head is untied.
* which mixer: the published stack's layer ``i`` is MLA where ``(i + 1) %
  layer_group_size == 0`` and KDA elsewhere; ``layers_kept`` names the
  published layers a cut configuration keeps, in order.
* KDA layer, H = ``num_attention_heads`` heads (``num_kv_heads_for_linear
  _attn`` 0) of D = ``head_dim``: ``q, k, v = silu(conv(W_q u)),
  silu(conv(W_k u)), silu(conv(W_v u))`` (``linear_silu``), the convolution
  depthwise, causal (``short_conv_kernel_size - 1`` zero rows before the
  sequence), without bias; ``q <- q / |q| / sqrt(D)``, ``k <- k / |k|``
  (``use_qk_norm``); per channel the log-decay ``g = kda_lower_bound *
  sigmoid(exp(A_log_h) * (W_f u + dt_bias))`` (``kda_safe_gate``; ``W_f``
  full rank, ``no_kda_lora``), ``alpha = exp(g)``; per head ``beta =
  sigmoid(W_b u)``; per head a state ``S`` of D x D, zero before the
  sequence:
  ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``,
  ``o_t = S_t^T q_t``; ``out = W_o [RMSNorm_head(o_t) * sigmoid(W_g u)]``
  with one learned scale of D shared by the heads (``group_norm_size`` 1).
* MLA layer: ``q = W_q u`` as (H, nope + rope) (``q_lora_rank`` null);
  ``[c | r] = W_kva u``; ``c <- RMSNorm(c)``; rotary embedding on the
  interleaved pairs of ``q``'s rope part and of the one shared ``r``
  (``rope_theta``, ``rope_interleave``); ``[k_nope_h | v_h] = W_kvb,h c``;
  scores over ``[q_nope | q_rope]`` against ``[k_nope | r]`` divided by
  ``sqrt(nope + rope)``; causal softmax; ``o_h <- o_h * sigmoid(w_gate,h .
  u)`` (``gated_attention_proj_granularity_type: head_wise``); ``W_o``.
* FFN: SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; after them ``s = sigmoid(W_r u)`` over
  all ``router_experts`` experts; choice by ``s + b``
  (``moe_router_enable_expert_bias``): ``n_group`` equal groups, a group's
  score the sum of its two largest, the ``topk_group`` best groups kept,
  the ``num_experts_per_tok`` largest inside them taken (``noaux_tc``);
  ``w = routed_scaling_factor * s / sum_taken(s)``; plus one shared SwiGLU
  of ``num_shared_experts * moe_shared_expert_intermediate_size``.
* **the share**: ``num_experts`` counts the experts HELD (``experts_first``
  on, of ``router_experts``); the router, its groups and the weights'
  normalisation are over all of them, and the layer's result is the held
  experts' part plus the shared expert: what the other chips of the
  deployment would add is left out, and that partial result goes on to the
  next layer.  ``vocab_size`` counts the rows of the vocabulary held: a
  smaller vocabulary.  With ``num_experts == router_experts`` and the whole
  vocabulary this is the uncut model.

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision,
no kernels, no cache, no batching, nothing imported from the program under
test.  **The recurrence runs token by token** (``lax.scan`` over ``t``):
the definition, where the program runs a chunked form in prefill and one
step a token in decode.  Attention runs one head at a time (``lax.map``);
every held expert is computed for every token, by a loop, and masked.

Departures from the published implementation, each also under ``assumed``
in the configuration file: it computes the same recurrence by a chunked
algorithm; the lengths of ``q`` and ``k`` get 1e-6 under the root; what
``use_qk_norm`` does inside the latent layer the config does not say, and
this keeps the RMSNorm on the latent ``c`` and no per-head norm; the
rotated pairs stay where they were; a group not kept is out of the choice
whatever its scores (the published code fills with 0, the same for
positive ``s + b``); the sum of the taken scores gets the published
``1e-20`` added; ``expert_swiglu_limit_list`` is 0 in every layer kept (no
clamp); the MTP module is left out; weights are float32 where the
checkpoint is bfloat16.
"""
import jax
import jax.numpy as jnp

PRECISION = "highest"
L2_EPS = 1e-6


def layer_types(cfg):
    """"kda" | "mla" for each layer kept, from the published period."""
    kept = cfg.get("layers_kept") or range(cfg["num_hidden_layers"])
    return ["mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda"
            for i in kept]


def held(cfg):
    """-> (first, count, router width): the experts held of those
    routed."""
    return (cfg.get("experts_first", 0), cfg["num_experts"],
            cfg.get("router_experts", cfg["num_experts"]))


def spec(cfg):
    """{parameter name: shape} for a configuration.  Matrices are stored
    (out, in) as the checkpoints store them, the depthwise filter as
    (channels, taps) over ``[q | k | v]``; the held experts of a layer are
    stacked on a leading axis."""
    d, h, w = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank, v = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["vocab_size"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = cfg["num_shared_experts"] * cfg["moe_shared_expert_intermediate_size"]
    _, e, routed = held(cfg)
    out = {"tok_embed_weight": (v, d), "final_norm_gamma": (d,),
           "lm_head_weight": (v, d)}
    for i, kind in enumerate(layer_types(cfg)):
        p = "blk%d_" % i
        out.update({p + "attn_norm_gamma": (d,), p + "ffn_norm_gamma": (d,)})
        if kind == "mla":
            out.update({p + "q_weight": (h * (nope + rope), d),
                        p + "kv_a_weight": (rank + rope, d),
                        p + "kv_norm_gamma": (rank,),
                        p + "kv_b_weight": (h * (nope + vd), rank),
                        p + "attn_gate_weight": (h, d),
                        p + "o_weight": (d, h * vd)})
        else:
            out.update({p + "kda_q_weight": (h * w, d),
                        p + "kda_k_weight": (h * w, d),
                        p + "kda_v_weight": (h * w, d),
                        p + "kda_conv_weight": (
                            3 * h * w, cfg["short_conv_kernel_size"]),
                        p + "kda_f_weight": (h * w, d),
                        p + "kda_dt_bias": (h * w,), p + "kda_A_log": (h,),
                        p + "kda_b_weight": (h, d),
                        p + "kda_g_weight": (h * w, d),
                        p + "kda_o_norm_gamma": (w,),
                        p + "kda_o_weight": (d, h * w)})
        if i < cfg["first_k_dense_replace"]:
            out.update({p + "gate_weight": (f, d), p + "up_weight": (f, d),
                        p + "down_weight": (d, f)})
        else:
            out.update({
                p + "router_weight": (routed, d),
                p + "router_bias": (routed,),
                p + "experts_gate_weight": (e, fe, d),
                p + "experts_up_weight": (e, fe, d),
                p + "experts_down_weight": (e, d, fe),
                p + "shared_gate_weight": (fs, d),
                p + "shared_up_weight": (fs, d),
                p + "shared_down_weight": (d, fs),
            })
    return out


def _rms_norm(x, gamma, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * gamma


def _linear(x, w):
    return jnp.matmul(x, w.T, precision=PRECISION)


def _unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + jnp.asarray(L2_EPS, x.dtype))


def kda_recurrence(q, k, v, alpha, beta, state):
    """The definition, token by token.  q, k, alpha: (T, H, D); v:
    (T, H, D); beta: (T, H); state: (H, D, D) [key, value].
    -> (o (T, H, D), state after the last token)."""
    def token(s, row):
        q_t, k_t, v_t, a_t, b_t = row
        s = a_t[:, :, None] * s
        predicted = jnp.sum(k_t[:, :, None] * s, axis=1)
        s = s + (b_t[:, None] * k_t)[:, :, None] \
            * (v_t - predicted)[:, None, :]
        return s, jnp.sum(q_t[:, :, None] * s, axis=1)

    state, o = jax.lax.scan(token, state, (q, k, v, alpha, beta))
    return o, state


def _kda(u, p, pre, cfg):
    t = u.shape[0]
    h, w = cfg["num_attention_heads"], cfg["head_dim"]
    taps = cfg["short_conv_kernel_size"]
    rows = jnp.concatenate([_linear(u, p[pre + "kda_%s_weight" % m])
                            for m in "qkv"], axis=-1)
    padded = jnp.concatenate([jnp.zeros((taps - 1, rows.shape[1]),
                                        rows.dtype), rows])
    conv = sum(padded[j:j + t] * p[pre + "kda_conv_weight"][:, j]
               for j in range(taps))
    q, k, v = (a.reshape(t, h, w)
               for a in jnp.split(jax.nn.silu(conv), 3, axis=-1))
    q, k = _unit(q) * jnp.asarray(w ** -0.5, q.dtype), _unit(k)
    f = (_linear(u, p[pre + "kda_f_weight"])
         + p[pre + "kda_dt_bias"]).reshape(t, h, w)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p[pre + "kda_A_log"])[:, None] * f)
    beta = jax.nn.sigmoid(_linear(u, p[pre + "kda_b_weight"]))
    o, _ = kda_recurrence(q, k, v, jnp.exp(g), beta,
                          jnp.zeros((h, w, w), q.dtype))
    o = _rms_norm(o, p[pre + "kda_o_norm_gamma"], cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(_linear(u, p[pre + "kda_g_weight"]))
    return _linear(o.reshape(t, h * w) * gate, p[pre + "kda_o_weight"])


def _rope(x, positions, theta):
    """Rotate the interleaved pairs of ``x`` (T, ..., rope) at
    ``positions`` (T,)."""
    rope = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (rope // 2,))
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _mla(u, p, pre, cfg):
    h = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    t = u.shape[0]
    positions = jnp.arange(t)
    q = _linear(u, p[pre + "q_weight"]).reshape(t, h, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], positions, cfg["rope_theta"])],
        axis=-1)
    kva = _linear(u, p[pre + "kv_a_weight"])
    c = _rms_norm(kva[:, :rank], p[pre + "kv_norm_gamma"],
                  cfg["rms_norm_eps"])
    r = _rope(kva[:, rank:], positions, cfg["rope_theta"])
    kv = _linear(c, p[pre + "kv_b_weight"]).reshape(t, h, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(r[:, None, :], (t, h, rope))],
        axis=-1)
    v = kv[..., nope:]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one_head(qkv):
        qh, kh, vh = qkv
        scores = jnp.matmul(qh, kh.T, precision=PRECISION) \
            / (nope + rope) ** 0.5
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.matmul(jax.nn.softmax(scores, axis=-1), vh,
                          precision=PRECISION)

    ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                                 v.transpose(1, 0, 2))).transpose(1, 0, 2)
    gate = jax.nn.sigmoid(_linear(u, p[pre + "attn_gate_weight"]))
    return _linear((ctx * gate[:, :, None]).reshape(t, h * vd),
                   p[pre + "o_weight"])


def _swiglu(u, gate, up, down):
    return _linear(jax.nn.silu(_linear(u, gate)) * _linear(u, up), down)


def route(u, p, pre, cfg):
    """-> (T, router width) combine weights over ALL the experts routed:
    zero for those not taken."""
    t = u.shape[0]
    scores = jax.nn.sigmoid(_linear(u, p[pre + "router_weight"]))
    choice = scores + p[pre + "router_bias"]
    groups = cfg["n_group"]
    if groups > 1:
        grouped = choice.reshape(t, groups, -1)
        group_score = jnp.sort(grouped, axis=-1)[..., -2:].sum(axis=-1)
        _, best = jax.lax.top_k(group_score, cfg["topk_group"])
        kept = jnp.zeros((t, groups), bool).at[
            jnp.arange(t)[:, None], best].set(True)
        choice = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(
            choice.shape)
    _, taken = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    mask = jnp.zeros(scores.shape, bool).at[
        jnp.arange(t)[:, None], taken].set(True)
    kept = jnp.where(mask, scores, 0.0)
    if cfg["norm_topk_prob"]:
        kept = kept / (kept.sum(-1, keepdims=True) + 1e-20)
    return kept * cfg["routed_scaling_factor"]


def routed(u, p, pre, cfg):
    """The held experts' part of the routed result, (T, d)."""
    first, count, _ = held(cfg)
    weights = route(u, p, pre, cfg)[:, first:first + count]

    def one_expert(acc, xs):
        gate, up, down, w = xs
        return acc + w[:, None] * _swiglu(u, gate, up, down), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (p[pre + "experts_gate_weight"], p[pre + "experts_up_weight"],
         p[pre + "experts_down_weight"], weights.T))
    return out


def shared(u, p, pre):
    return _swiglu(u, p[pre + "shared_gate_weight"],
                   p[pre + "shared_up_weight"], p[pre + "shared_down_weight"])


def _block(x, p, i, kind, cfg):
    pre = "blk%d_" % i
    eps = cfg["rms_norm_eps"]
    mixer = _mla if kind == "mla" else _kda
    x = x + mixer(_rms_norm(x, p[pre + "attn_norm_gamma"], eps), p, pre, cfg)
    u = _rms_norm(x, p[pre + "ffn_norm_gamma"], eps)
    if i < cfg["first_k_dense_replace"]:
        return x + _swiglu(u, p[pre + "gate_weight"], p[pre + "up_weight"],
                           p[pre + "down_weight"])
    return x + routed(u, p, pre, cfg) + shared(u, p, pre)


def logits(params, tokens, cfg, cast=None):
    """(T,) int tokens -> (T, vocab held) float32 logits of one sequence.

    ``cast`` computes in a lower precision: parameters and activations,
    the state among them, are held in that type."""
    p = params
    if cast is not None:
        p = {k: v.astype(cast) for k, v in params.items()}
    x = p["tok_embed_weight"][tokens]
    for i, kind in enumerate(layer_types(cfg)):
        x = _block(x, p, i, kind, cfg)
    x = _rms_norm(x, p["final_norm_gamma"], cfg["rms_norm_eps"])
    return _linear(x, p["lm_head_weight"]).astype(jnp.float32)
