"""Plain reference: the Qwen3-Next decoder as Qwen3-Next-80B-A3B-Instruct
configures it (``model_type: qwen3_next``; keys as in the model's
``config.json``): Gated DeltaNet layers, a gated grouped-query attention
layer at every ``full_attention_interval``-th place, and in every layer
softmax-routed experts beside one shared expert behind a sigmoid gate.

Written from the published ``config.json``, the Gated DeltaNet paper
(arXiv:2412.06464, section 3: the recurrence below is its definition, not
its chunked algorithm) and the model card's description of the block:

* block: ``h = x + Mix(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; after
  the last block ``logits = W_head . RMSNorm(y)``.  No position table, no
  bias, the head is untied.  Every RMSNorm but the DeltaNet output norm is
  **zero-centred**: ``x / rms(x) * (1 + w)`` at ``rms_norm_eps``.
* which mixer: the published stack's layer ``i`` is attention where ``(i +
  1) % full_attention_interval == 0`` and DeltaNet elsewhere;
  ``layers_kept`` names the published layers a cut configuration keeps.
* Gated DeltaNet layer, ``linear_num_key_heads`` key heads of
  ``linear_key_head_dim`` and ``linear_num_value_heads`` value heads of
  ``linear_value_head_dim``: ``[q | k | v | z] = W_qkvz u``, ``[b | a] =
  W_ba u``; ``[q | k | v] <- silu(conv([q | k | v]))``, depthwise, causal
  (``linear_conv_kernel_dim - 1`` zero rows before the sequence), no bias;
  ``q <- q / |q| / sqrt(D_k)``, ``k <- k / |k|``; a query / key head is
  used by ``value heads / key heads`` consecutive value heads.  A value
  head: ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``,
  ``alpha = exp(g)``, a state ``S`` of ``D_k x D_v``, zero before the
  sequence: ``S_t = alpha_t S_{t-1} + k_t (beta_t (v_t - (alpha_t
  S_{t-1})^T k_t))^T``, ``o_t = S_t^T q_t``; ``out = W_o [w_n * o / rms(o)
  * silu(z)]``, the norm a head with a plain scale.
* gated attention layer, ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads of ``head_dim``: ``W_q u`` is, a
  head, ``[q | gate]``; ``k = W_k u``, ``v = W_v u``; ``q`` and ``k``
  through a zero-centred RMSNorm a head; the first ``head_dim *
  partial_rotary_factor`` values of each rotated, halves against each
  other, at ``rope_theta``; causal softmax attention at ``1 /
  sqrt(head_dim)``; ``out = W_o [attn * sigmoid(gate)]``, the gate an
  element.
* FFN: ``p = softmax(W_r u)`` over all ``router_experts``; the
  ``num_experts_per_tok`` largest taken; ``w = p / sum_taken(p)``
  (``norm_topk_prob``); ``y = sum_taken w_e SwiGLU_e(u) + sigmoid(w_s . u)
  * SwiGLU_shared(u)``.
* **the share**: ``num_experts`` counts the experts HELD (``experts_first``
  on, of ``router_experts``); the router and the weights' normalisation
  are over all of them, and the layer's result is the held experts' part
  plus the shared expert: what the other chips of the deployment would
  add is left out, and that partial result goes on to the next layer.
  ``vocab_size`` counts the rows of the vocabulary held: a smaller
  vocabulary.  With ``num_experts == router_experts`` and the whole
  vocabulary this is the uncut model.

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision,
no kernels, no cache, no batching, nothing imported from the program under
test.  **The recurrence runs token by token** (``lax.scan`` over ``t``):
the definition, where the program runs a chunked form in prefill and one
step a token in decode.  Attention runs one head at a time and a block of
``ROW_BLOCK`` query rows at a time (``lax.map``), so that a sequence of
17 408 tokens fits beside the weights; every held expert is computed for
every token, by a loop, and masked.

Departures from the published implementation, each also under ``assumed``
in the configuration file: it computes the same recurrence by a chunked
kernel; the lengths of ``q`` and ``k`` get 1e-6 under the root; the fused
projections' rows lie ``[q | k | v | z]`` and ``[b | a]``, each part whole
(the checkpoint interleaves them a key head); the multi-token-prediction
layer is left out; weights are float32 where the checkpoint is bfloat16.
"""
import jax
import jax.numpy as jnp

PRECISION = "highest"
L2_EPS = 1e-6
ROW_BLOCK = 1024    # query rows of one head whose scores are held at once


def layer_types(cfg):
    """"linear_attention" | "full_attention" for each layer kept, from the
    published period."""
    kept = cfg.get("layers_kept") or range(cfg["num_hidden_layers"])
    return ["full_attention" if (i + 1) % cfg["full_attention_interval"] == 0
            else "linear_attention" for i in kept]


def held(cfg):
    """-> (first, count, router width): the experts held of those
    routed."""
    return (cfg.get("experts_first", 0), cfg["num_experts"],
            cfg.get("router_experts", cfg["num_experts"]))


def gdn_dims(cfg):
    """-> (key heads, value heads, key width, value width)."""
    return (cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])


def spec(cfg):
    """{parameter name: shape} for a configuration.  Matrices are stored
    (out, in), the depthwise filter as (channels, taps) over ``[q | k |
    v]``; the held experts of a layer are stacked on a leading axis."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    hk, hv, dk, dv = gdn_dims(cfg)
    fe, fs = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    _, e, routed = held(cfg)
    out = {"tok_embed_weight": (v, d), "final_norm_weight": (d,),
           "lm_head_weight": (v, d)}
    for i, kind in enumerate(layer_types(cfg)):
        p = "blk%d_" % i
        out.update({p + "attn_norm_weight": (d,), p + "ffn_norm_weight": (d,)})
        if kind == "full_attention":
            out.update({p + "q_weight": (h * 2 * hd, d),
                        p + "k_weight": (kv * hd, d),
                        p + "v_weight": (kv * hd, d),
                        p + "q_norm_weight": (hd,),
                        p + "k_norm_weight": (hd,),
                        p + "o_weight": (d, h * hd)})
        else:
            out.update({
                p + "gdn_qkvz_weight": (2 * hk * dk + 2 * hv * dv, d),
                p + "gdn_ba_weight": (2 * hv, d),
                p + "gdn_conv_weight": (2 * hk * dk + hv * dv,
                                        cfg["linear_conv_kernel_dim"]),
                p + "gdn_A_log": (hv,), p + "gdn_dt_bias": (hv,),
                p + "gdn_o_norm_gamma": (dv,),
                p + "gdn_o_weight": (d, hv * dv)})
        out.update({
            p + "router_weight": (routed, d),
            p + "experts_gate_weight": (e, fe, d),
            p + "experts_up_weight": (e, fe, d),
            p + "experts_down_weight": (e, d, fe),
            p + "shared_gate_weight": (fs, d),
            p + "shared_up_weight": (fs, d),
            p + "shared_down_weight": (d, fs),
            p + "shared_expert_gate_weight": (1, d),
        })
    return out


def _rms(x, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _norm(x, w, eps):
    """The zero-centred RMSNorm."""
    return _rms(x, eps) * (1 + w)


def _linear(x, w):
    return jnp.matmul(x, w.T, precision=PRECISION)


def _unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + jnp.asarray(L2_EPS, x.dtype))


def gdn_recurrence(q, k, v, alpha, beta, state):
    """The definition, token by token.  q, k: (T, H, K); v: (T, H, V);
    alpha, beta: (T, H); state: (H, K, V).
    -> (o (T, H, V), state after the last token)."""
    def token(s, row):
        q_t, k_t, v_t, a_t, b_t = row
        s = a_t[:, None, None] * s
        predicted = jnp.sum(k_t[:, :, None] * s, axis=1)
        s = s + k_t[:, :, None] \
            * (b_t[:, None] * (v_t - predicted))[:, None, :]
        return s, jnp.sum(q_t[:, :, None] * s, axis=1)

    state, o = jax.lax.scan(token, state, (q, k, v, alpha, beta))
    return o, state


def _gdn(u, p, pre, cfg):
    t = u.shape[0]
    hk, hv, dk, dv = gdn_dims(cfg)
    taps = cfg["linear_conv_kernel_dim"]
    qkvz = _linear(u, p[pre + "gdn_qkvz_weight"])
    rows, z = qkvz[:, :2 * hk * dk + hv * dv], qkvz[:, 2 * hk * dk + hv * dv:]
    padded = jnp.concatenate([jnp.zeros((taps - 1, rows.shape[1]),
                                        rows.dtype), rows])
    conv = jax.nn.silu(sum(padded[j:j + t] * p[pre + "gdn_conv_weight"][:, j]
                           for j in range(taps)))
    q = conv[:, :hk * dk].reshape(t, hk, dk)
    k = conv[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
    v = conv[:, 2 * hk * dk:].reshape(t, hv, dv)
    q, k = _unit(q) * jnp.asarray(dk ** -0.5, q.dtype), _unit(k)
    q, k = (jnp.repeat(a, hv // hk, axis=1) for a in (q, k))
    ba = _linear(u, p[pre + "gdn_ba_weight"])
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p[pre + "gdn_A_log"]) * jax.nn.softplus(
        ba[:, hv:] + p[pre + "gdn_dt_bias"])
    o, _ = gdn_recurrence(q, k, v, jnp.exp(g), beta,
                          jnp.zeros((hv, dk, dv), q.dtype))
    o = _rms(o, cfg["rms_norm_eps"]) * p[pre + "gdn_o_norm_gamma"]
    return _linear(o.reshape(t, hv * dv) * jax.nn.silu(z),
                   p[pre + "gdn_o_weight"])


def _rope(x, positions, rot, theta):
    """Rotate the pairs ``(i, i + rot / 2)`` of the first ``rot`` values
    of ``x`` (T, heads, D) at ``positions`` (T,)."""
    inv_freq = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(angle).astype(x.dtype)[:, None, :]
    sin = jnp.sin(angle).astype(x.dtype)[:, None, :]
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


def _attention(u, p, pre, cfg):
    t = u.shape[0]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    rot = int(hd * cfg["partial_rotary_factor"])
    positions = jnp.arange(t)
    qg = _linear(u, p[pre + "q_weight"]).reshape(t, h, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = _linear(u, p[pre + "k_weight"]).reshape(t, kv, hd)
    v = _linear(u, p[pre + "v_weight"]).reshape(t, kv, hd)
    q = _rope(_norm(q, p[pre + "q_norm_weight"], eps), positions, rot,
              cfg["rope_theta"])
    k = _rope(_norm(k, p[pre + "k_norm_weight"], eps), positions, rot,
              cfg["rope_theta"])
    block = ROW_BLOCK if t % ROW_BLOCK == 0 else t

    def one_head(xs):
        qh, kh, vh = xs

        def one_block(ys):
            qb, first = ys
            scores = jnp.matmul(qb, kh.T, precision=PRECISION) / hd ** 0.5
            seen = (first + jnp.arange(block))[:, None] >= positions[None, :]
            scores = jnp.where(seen, scores, -jnp.inf)
            return jnp.matmul(jax.nn.softmax(scores, axis=-1), vh,
                              precision=PRECISION)

        return jax.lax.map(one_block, (
            qh.reshape(t // block, block, hd),
            jnp.arange(0, t, block))).reshape(t, hd)

    k, v = (jnp.repeat(a, h // kv, axis=1).transpose(1, 0, 2) for a in (k, v))
    ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2), k, v)
                      ).transpose(1, 0, 2)
    return _linear((ctx * jax.nn.sigmoid(gate)).reshape(t, h * hd),
                   p[pre + "o_weight"])


def _swiglu(u, gate, up, down):
    return _linear(jax.nn.silu(_linear(u, gate)) * _linear(u, up), down)


def route(u, p, pre, cfg):
    """-> (T, router width) combine weights over ALL the experts routed:
    zero for those not taken."""
    t = u.shape[0]
    scores = jax.nn.softmax(_linear(u, p[pre + "router_weight"]), axis=-1)
    _, taken = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    mask = jnp.zeros(scores.shape, bool).at[
        jnp.arange(t)[:, None], taken].set(True)
    kept = jnp.where(mask, scores, 0.0)
    if cfg["norm_topk_prob"]:
        kept = kept / kept.sum(-1, keepdims=True)
    return kept


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
    """The shared expert behind its sigmoid gate, (T, d)."""
    return jax.nn.sigmoid(_linear(u, p[pre + "shared_expert_gate_weight"])) \
        * _swiglu(u, p[pre + "shared_gate_weight"],
                  p[pre + "shared_up_weight"], p[pre + "shared_down_weight"])


def _block(x, p, i, kind, cfg):
    pre = "blk%d_" % i
    eps = cfg["rms_norm_eps"]
    mixer = _attention if kind == "full_attention" else _gdn
    x = x + mixer(_norm(x, p[pre + "attn_norm_weight"], eps), p, pre, cfg)
    u = _norm(x, p[pre + "ffn_norm_weight"], eps)
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
    x = _norm(x, p["final_norm_weight"], cfg["rms_norm_eps"])
    return _linear(x, p["lm_head_weight"]).astype(jnp.float32)
