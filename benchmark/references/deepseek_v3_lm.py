"""Plain reference: the DeepSeek-V3 decoder block as kanana-2-30b-a3b
configures it (``model_type: deepseek_v3``; keys as in the model's
``config.json``).

Written from the DeepSeek-V2 / V3 papers (arXiv:2405.04434 section 2.1,
arXiv:2412.19437 section 2.1) and the published ``config.json``:

* block: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; after
  the last block ``logits = W_head . RMSNorm(y)``.  No position embedding
  is added to ``x``, no bias anywhere, the head is untied.
* latent attention without a query bottleneck (``q_lora_rank`` null):
  ``q = W_q u`` as (H, nope + rope); ``[c | r] = W_kva u`` (rank + rope);
  ``c <- RMSNorm(c)``; the rope parts of ``q`` and the one shared ``r``
  get rotary position embedding on interleaved pairs ``(2j, 2j + 1)``
  with angle ``t * theta ** (-2j / rope)``;
  ``[k_nope_h | v_h] = W_kvb,h c``; scores over ``[q_nope | q_rope]``
  against ``[k_nope | r]`` divided by ``sqrt(nope + rope)``, causal
  softmax, ``o = W_o concat_h(sum_j p_j v_j)``.
* the first ``first_k_dense_replace`` layers have one SwiGLU of width
  ``intermediate_size``; every later layer routes: ``s = sigmoid(W_r u)``
  over all experts, the ``num_experts_per_tok`` largest ``s + b`` are
  taken (``b`` = ``e_score_correction_bias``, for the choice only),
  ``w = routed_scaling_factor * s / sum_taken(s)``,
  ``FFN(u) = sum_taken w_e SwiGLU_e(u) + SwiGLU_shared(u)`` with the
  shared experts as one SwiGLU of width ``n_shared_experts *
  moe_intermediate_size``.  Every expert is computed for every token, by a
  loop, and masked: no token is ever dropped.

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision,
no kernels, no cache, no batching, nothing imported from the program under
test.  Attention runs one head at a time (``lax.map``) so that a full-width
sequence fits beside the float32 weights.

Departures from the published implementation, each also under ``assumed``
in the configuration file: the rotated pairs stay where they were (the
published code moves them into two halves, a fixed permutation applied to
queries and keys alike, which no score can see); ``n_group`` =
``topk_group`` = 1, so there is no group limit to apply; the sum of the
taken scores gets the published ``1e-20`` added; weights are float32 where
the checkpoint is bfloat16.
"""
import jax
import jax.numpy as jnp

PRECISION = "highest"


def _sizes(cfg):
    h = cfg["num_attention_heads"]
    return (cfg["hidden_size"], h, cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])


def spec(cfg):
    """{parameter name: shape} for a configuration.  Matrices are stored
    (out, in) as the checkpoints store them; the routed experts of a
    layer are stacked on a leading axis."""
    d, h, nope, rope, vd, rank = _sizes(cfg)
    v = cfg["vocab_size"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    e, fs = cfg["n_routed_experts"], cfg["n_shared_experts"] * fe
    out = {"tok_embed_weight": (v, d), "final_norm_gamma": (d,),
           "lm_head_weight": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        p = "blk%d_" % i
        out.update({
            p + "attn_norm_gamma": (d,),
            p + "q_weight": (h * (nope + rope), d),
            p + "kv_a_weight": (rank + rope, d),
            p + "kv_norm_gamma": (rank,),
            p + "kv_b_weight": (h * (nope + vd), rank),
            p + "o_weight": (d, h * vd),
            p + "ffn_norm_gamma": (d,),
        })
        if i < cfg["first_k_dense_replace"]:
            out.update({p + "gate_weight": (f, d), p + "up_weight": (f, d),
                        p + "down_weight": (d, f)})
        else:
            out.update({
                p + "router_weight": (e, d), p + "router_bias": (e,),
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


def _swiglu(u, gate, up, down):
    return _linear(jax.nn.silu(_linear(u, gate)) * _linear(u, up), down)


def _attention(u, p, pre, cfg):
    d, h, nope, rope, vd, rank = _sizes(cfg)
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
                                 v.transpose(1, 0, 2)))
    return _linear(ctx.transpose(1, 0, 2).reshape(t, h * vd),
                   p[pre + "o_weight"])


def route(u, p, pre, cfg):
    """-> (T, E) combine weights: zero for the experts not taken."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(_linear(u, p[pre + "router_weight"]))
    _, taken = jax.lax.top_k(scores + p[pre + "router_bias"], k)
    mask = jnp.zeros(scores.shape, bool).at[
        jnp.arange(u.shape[0])[:, None], taken].set(True)
    kept = jnp.where(mask, scores, 0.0)
    if cfg["norm_topk_prob"]:
        kept = kept / (kept.sum(-1, keepdims=True) + 1e-20)
    return kept * cfg["routed_scaling_factor"]


def _routed_ffn(u, p, pre, cfg):
    weights = route(u, p, pre, cfg)

    def one_expert(acc, xs):
        gate, up, down, w = xs
        return acc + w[:, None] * _swiglu(u, gate, up, down), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (p[pre + "experts_gate_weight"], p[pre + "experts_up_weight"],
         p[pre + "experts_down_weight"], weights.T))
    return routed + _swiglu(u, p[pre + "shared_gate_weight"],
                            p[pre + "shared_up_weight"],
                            p[pre + "shared_down_weight"])


def _block(x, p, i, cfg):
    pre = "blk%d_" % i
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p[pre + "attn_norm_gamma"], eps), p,
                       pre, cfg)
    u = _rms_norm(x, p[pre + "ffn_norm_gamma"], eps)
    if i < cfg["first_k_dense_replace"]:
        return x + _swiglu(u, p[pre + "gate_weight"], p[pre + "up_weight"],
                           p[pre + "down_weight"])
    return x + _routed_ffn(u, p, pre, cfg)


def logits(params, tokens, cfg, cast=None):
    """(T,) int tokens -> (T, vocab) float32 logits of one sequence.

    ``cast`` computes in a lower precision: parameters and activations
    are held in that type."""
    p = params
    if cast is not None:
        p = {k: v.astype(cast) for k, v in params.items()}
    x = p["tok_embed_weight"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = _block(x, p, i, cfg)
    x = _rms_norm(x, p["final_norm_gamma"], cfg["rms_norm_eps"])
    return _linear(x, p["lm_head_weight"]).astype(jnp.float32)
