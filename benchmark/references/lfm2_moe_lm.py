"""Plain reference: the LFM2-MoE decoder as LFM2-24B-A2B configures it
(``model_type: lfm2_moe``; keys as in the model's ``config.json``):
double-gated short convolutions and grouped-query attention layers with a
norm on every query and key head in the published ``layer_types`` order,
leading dense SwiGLU layers, then sigmoid-routed experts with a selection
bias and no shared expert; the head is the embedding.

Written from the published ``config.json`` and from what its keys mean in
the ``transformers`` library's ``lfm2_moe`` model (d = ``hidden_size``,
K = ``conv_L_cache``, eps = ``norm_eps``):

* block: ``h = x + Mixer(RMSNorm(x; operator_norm))``, ``y = h +
  FFN(RMSNorm(h; ffn_norm))``, RMSNorm with a learned scale; the embedding
  has no multiplier; after the last block ``logits = E . RMSNorm(y;
  embedding_norm)`` with the embedding's own matrix
  (``tie_word_embeddings``).  No position table, no bias (``conv_bias``
  false).
* ``conv`` mixer: ``[B | C | z] = W_in u`` (d -> 3 d, the thirds in this
  order); ``g = B * z``; ``c_t = sum_{j < K} w_j g_{t - (K - 1) + j}``, one
  filter of K taps a channel, ``g`` zero before the sequence starts (a
  ``Conv1d`` with ``groups = d`` and ``K - 1`` zeros in front); ``W_out (C
  * c)``.  Computed as K shifted products.
* ``full_attention`` mixer: ``q = W_q u`` as ``num_attention_heads`` heads
  of ``head_dim``, ``k = W_k u``, ``v = W_v u`` as ``num_key_value_heads``;
  ``q_h <- RMSNorm(q_h; q_layernorm)``, ``k_h <- RMSNorm(k_h;
  k_layernorm)`` over the head's values, one scale vector for all heads
  of a kind; both rotated: all ``head_dim`` values, pairs ``(i, i +
  head_dim / 2)`` (``rotate_half``), ``inv_freq_i = rope_theta^(-2i /
  head_dim)``; query head ``h`` reads key/value head ``h //
  (num_attention_heads / num_key_value_heads)``; scores ``q . k /
  sqrt(head_dim)``, a key ``j`` visible to a query ``i`` where ``j <= i``;
  softmax; ``W_o``.
* FFN: SwiGLU of ``intermediate_size`` in the first ``num_dense_layers``
  layers kept; elsewhere ``s = sigmoid(W_r u)`` over all ``router_experts``
  experts, the ``num_experts_per_tok`` largest ``s + b`` taken
  (``use_expert_bias``: ``b`` chooses and does not weigh), ``w =
  routed_scaling_factor * s / (sum_taken(s) + 1e-6)`` (``norm_topk_prob``),
  the routed SwiGLU experts of ``moe_intermediate_size`` applied to ``u``
  and weighted on their output.  No shared expert.
* **the share**: ``num_experts`` counts the experts HELD (``experts_first``
  on, of ``router_experts``); the router and the weights' normalisation
  are over all of them, and the layer's result is the held experts' part
  alone: what the other chips of the deployment would add is left out, a
  row none of whose experts is held gets ``y = h``, and that partial
  result goes on to the next layer.  ``vocab_size`` counts the rows of the
  vocabulary held: a smaller vocabulary, and the tied matrix is sliced
  once.  With ``num_experts == router_experts`` and the whole vocabulary
  this is the uncut model.  ``layers_kept`` names the published layers a
  cut configuration keeps, in order; ``layer_types`` stays the published
  list and is read at those places; the first ``num_dense_layers`` of the
  layers kept are the dense ones.

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision,
no kernels, no cache, no batching, nothing imported from the program under
test.  Attention is computed a block of ``Q_BLOCK`` queries and one
key/value head's group of query heads at a time (``lax.map``), so that a
request of 9 216 tokens fits beside the weights; a block sees every key
under the causal mask.  Every held expert is computed for every token, by
a loop, and masked.

Departures from the published implementation, each also under ``assumed``
in the configuration file: ``tie_word_embeddings`` true and ``head_dim`` =
``hidden_size / num_attention_heads`` (the catalog's row carries neither
key); weights are float32 where the checkpoint is bfloat16.  The program
under test adds 1e-20, not 1e-6, under the router's sum (the function it
shares with three other blocks): the weights differ by 1e-6 / sum_taken(s),
under one float32 rounding of a logit wherever four sigmoids sum past 0.1,
and ``tests/test_serve_lfm2_moe.py`` shows it on the CPU at ``highest``.
"""
import jax
import jax.numpy as jnp

PRECISION = "highest"
Q_BLOCK = 512       # queries a block of the attention
ROUTER_EPS = 1e-6   # under the sum of the scores taken


def kept(cfg):
    """The published layers a configuration keeps, in order."""
    return list(cfg.get("layers_kept") or range(cfg["num_hidden_layers"]))


def layer_types(cfg):
    """``"conv"`` | ``"full_attention"`` for each layer kept."""
    return [cfg["layer_types"][i] for i in kept(cfg)]


def layer_dense(cfg):
    """Whether each layer kept has the dense FFN: the leading ones."""
    return [i < cfg["num_dense_layers"] for i in range(len(kept(cfg)))]


def held(cfg):
    """-> (first, count, router width): the experts held of those
    routed."""
    return (cfg.get("experts_first", 0), cfg["num_experts"],
            cfg.get("router_experts", cfg["num_experts"]))


def head_dim(cfg):
    return cfg.get("head_dim") \
        or cfg["hidden_size"] // cfg["num_attention_heads"]


def spec(cfg):
    """{parameter name: shape} for a configuration.  Matrices are stored
    (out, in) as the checkpoints store them, the depthwise filter
    (channels, taps); the held experts of a layer are stacked on a leading
    axis."""
    d, hd, v = cfg["hidden_size"], head_dim(cfg), cfg["vocab_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    _, e, routed = held(cfg)
    out = {"tok_embed_weight": (v, d), "final_norm_gamma": (d,)}
    for i, (kind, dense) in enumerate(zip(layer_types(cfg),
                                          layer_dense(cfg))):
        p = "blk%d_" % i
        out.update({p + "operator_norm_gamma": (d,),
                    p + "ffn_norm_gamma": (d,)})
        if kind == "conv":
            out.update({p + "in_weight": (3 * d, d),
                        p + "conv_weight": (d, cfg["conv_L_cache"]),
                        p + "out_weight": (d, d)})
        else:
            out.update({p + "q_weight": (h * hd, d),
                        p + "k_weight": (kv * hd, d),
                        p + "v_weight": (kv * hd, d),
                        p + "q_norm_gamma": (hd,), p + "k_norm_gamma": (hd,),
                        p + "o_weight": (d, h * hd)})
        if dense:
            out.update({p + "gate_weight": (f, d), p + "up_weight": (f, d),
                        p + "down_weight": (d, f)})
        else:
            out.update({p + "router_weight": (routed, d),
                        p + "router_bias": (routed,),
                        p + "experts_gate_weight": (e, fe, d),
                        p + "experts_up_weight": (e, fe, d),
                        p + "experts_down_weight": (e, d, fe)})
    return out


def _rms_norm(x, gamma, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * gamma


def _linear(x, w):
    return jnp.matmul(x, w.T, precision=PRECISION)


def short_conv(u, p, pre, cfg):
    """The double-gated short convolution of (T, d) rows from a sequence's
    start: K shifted products."""
    t, d = u.shape
    taps = cfg["conv_L_cache"]
    bcz = _linear(u, p[pre + "in_weight"])
    b, c, z = bcz[:, :d], bcz[:, d:2 * d], bcz[:, 2 * d:]
    g = jnp.pad(b * z, ((taps - 1, 0), (0, 0)))
    conv = sum(g[j:j + t] * p[pre + "conv_weight"][:, j]
               for j in range(taps))
    return _linear(c * conv, p[pre + "out_weight"])


def rope(x, positions, theta):
    """Rotate the pairs ``(i, i + D / 2)`` of ``x`` (T, heads, D) at
    ``positions`` (T,): ``rotate_half``."""
    d = x.shape[-1]
    inv_freq = jnp.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)],
                           jnp.float32)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(angle).astype(x.dtype)[:, None, :]
    sin = jnp.sin(angle).astype(x.dtype)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attend(q, k, v):
    """q (T, KV, G, D), k and v (T, KV, D) -> (T, KV, G, D): softmax
    attention under the causal mask; a block of queries and one key/value
    head at a time."""
    t, kv, g, d = q.shape
    block = min(Q_BLOCK, t)
    pad = -t % block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
    starts = jnp.arange(0, t + pad, block)
    cols = jnp.arange(t)

    def one_head(head):
        qh, kh, vh = head           # (T + pad, G, D), (T, D) twice

        def one_block(start):
            rows = start + jnp.arange(block)
            qb = jax.lax.dynamic_slice_in_dim(qh, start, block)
            scores = jnp.einsum("qgd,kd->gqk", qb, kh, precision=PRECISION) \
                / d ** 0.5
            scores = jnp.where((cols[None, :] <= rows[:, None])[None],
                               scores, -jnp.inf)
            return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(scores, axis=-1),
                              vh, precision=PRECISION)

        return jax.lax.map(one_block, starts).reshape(t + pad, g, d)

    out = jax.lax.map(one_head, (q.transpose(1, 0, 2, 3),
                                 k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3)[:t]


def qk(u, p, pre, cfg):
    """u (T, d) -> the normed and rotated q (T, H, D) and k (T, KV, D)."""
    t = u.shape[0]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        head_dim(cfg)
    theta = float(cfg["rope_parameters"]["rope_theta"])
    positions = jnp.arange(t)
    q = _rms_norm(_linear(u, p[pre + "q_weight"]).reshape(t, h, hd),
                  p[pre + "q_norm_gamma"], cfg["norm_eps"])
    k = _rms_norm(_linear(u, p[pre + "k_weight"]).reshape(t, kv, hd),
                  p[pre + "k_norm_gamma"], cfg["norm_eps"])
    return rope(q, positions, theta), rope(k, positions, theta)


def attention(u, p, pre, cfg):
    t = u.shape[0]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        head_dim(cfg)
    q, k = qk(u, p, pre, cfg)
    v = _linear(u, p[pre + "v_weight"]).reshape(t, kv, hd)
    ctx = _attend(q.reshape(t, kv, h // kv, hd), k, v)
    return _linear(ctx.reshape(t, h * hd), p[pre + "o_weight"])


def _swiglu(u, gate, up, down):
    return _linear(jax.nn.silu(_linear(u, gate)) * _linear(u, up), down)


def route(u, p, pre, cfg):
    """-> (T, router width) combine weights over ALL the experts routed:
    zero for those not taken."""
    t = u.shape[0]
    scores = jax.nn.sigmoid(_linear(u, p[pre + "router_weight"]))
    choice = scores + p[pre + "router_bias"] \
        if cfg.get("use_expert_bias", True) else scores
    _, taken = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    mask = jnp.zeros(scores.shape, bool).at[
        jnp.arange(t)[:, None], taken].set(True)
    picked = jnp.where(mask, scores, 0.0)
    if cfg["norm_topk_prob"]:
        picked = picked / (picked.sum(-1, keepdims=True) + ROUTER_EPS)
    return picked * cfg["routed_scaling_factor"]


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


def _block(x, p, i, kind, dense, cfg):
    pre = "blk%d_" % i
    u = _rms_norm(x, p[pre + "operator_norm_gamma"], cfg["norm_eps"])
    x = x + (short_conv(u, p, pre, cfg) if kind == "conv"
             else attention(u, p, pre, cfg))
    u = _rms_norm(x, p[pre + "ffn_norm_gamma"], cfg["norm_eps"])
    if dense:
        return x + _swiglu(u, p[pre + "gate_weight"], p[pre + "up_weight"],
                           p[pre + "down_weight"])
    return x + routed(u, p, pre, cfg)


def logits(params, tokens, cfg, cast=None):
    """(T,) int tokens -> (T, vocab held) float32 logits of one sequence.

    ``cast`` computes in a lower precision: parameters and activations are
    held in that type."""
    p = params
    if cast is not None:
        p = {k: v.astype(cast) for k, v in params.items()}
    x = p["tok_embed_weight"][tokens]
    for i, (kind, dense) in enumerate(zip(layer_types(cfg),
                                          layer_dense(cfg))):
        x = _block(x, p, i, kind, dense, cfg)
    x = _rms_norm(x, p["final_norm_gamma"], cfg["norm_eps"])
    return _linear(x, p["tok_embed_weight"]).astype(jnp.float32)
