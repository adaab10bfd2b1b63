"""Plain reference: the Laguna decoder as Laguna-S-2.1 configures it
(``model_type: laguna``; keys as in the model's ``config.json``): sliding-
window and full grouped-query attention layers in the published
``layer_types`` order with a query-head count a layer, a rotary embedding
of two kinds, a sigmoid gate a head on attention's output, one leading
dense SwiGLU, then softmax-routed experts with one shared expert.

Written from the published ``config.json`` and from what its keys mean in
the ``transformers`` library (``rope_parameters`` with
``rope_type: yarn`` is the library's generic YaRN initialisation;
``sliding_window``, ``layer_types``, ``mlp_only_layers``,
``norm_topk_prob`` and the expert keys are the Qwen-MoE family's):

* block: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, RMSNorm
  with a learned scale at ``rms_norm_eps``; after the last block ``logits =
  W_head . RMSNorm(y)``.  No position table, no bias (``attention_bias``
  false), the head is untied.
* attention, layer ``l`` with ``H_l = num_attention_heads_per_layer[l]``
  query heads, ``num_key_value_heads`` key/value heads, all of
  ``head_dim``: ``q = W_q u``, ``k = W_k u``, ``v = W_v u``; ``q`` and
  ``k`` rotated (below); query head ``h`` reads key/value head ``h //
  (H_l / num_key_value_heads)``; scores ``q . k / sqrt(head_dim)``; a key
  ``j`` is visible to a query ``i`` where ``0 <= i - j`` in a
  ``full_attention`` layer and where ``0 <= i - j < sliding_window`` in a
  ``sliding_attention`` layer; softmax; ``o_h <- sigmoid(w_gate,h . u)
  o_h`` (``gating: per-head``); ``W_o``.
* the rotation: pairs ``(i, i + rot / 2)`` of the first ``rot = head_dim *
  partial_rotary_factor`` values of a head (``rotate_half``), the rest left
  as they are.  ``rope_type: default``: ``inv_freq_i = theta^(-2i /
  rot)``.  ``rope_type: yarn``: ``f_i = theta^(2i / rot)``; ``low =
  floor(rot ln(orig / (beta_fast 2 pi)) / (2 ln theta))``, ``high =
  ceil(rot ln(orig / (beta_slow 2 pi)) / (2 ln theta))``, both clipped to
  ``[0, rot - 1]``; ``r_i = clip((i - low) / (high - low), 0, 1)``;
  ``inv_freq_i = (1 - r_i) / f_i + r_i / (factor f_i)``; cos and sin times
  ``attention_factor``.
* FFN: SwiGLU of ``intermediate_size`` where ``mlp_layer_types[l]`` is
  ``dense``; elsewhere ``p = softmax(W_r u)`` over all ``router_experts``
  experts (``moe_router_logit_softcapping`` c > 0: of ``c tanh(. / c)``),
  the ``num_experts_per_tok`` largest taken, ``w = moe_routed_scaling_factor
  * p / sum_taken(p)`` (``norm_topk_prob``), the routed SwiGLU experts of
  ``moe_intermediate_size`` applied to ``u`` and weighted on their output
  (``moe_apply_router_weight_on_input`` false), plus one shared SwiGLU of
  ``shared_expert_intermediate_size``.
* **the share**: ``num_experts`` counts the experts HELD (``experts_first``
  on, of ``router_experts``); the router and the weights' normalisation are
  over all of them, and the layer's result is the held experts' part plus
  the shared expert: what the other chips of the deployment would add is
  left out, and that partial result goes on to the next layer.
  ``vocab_size`` counts the rows of the vocabulary held: a smaller
  vocabulary.  With ``num_experts == router_experts`` and the whole
  vocabulary this is the uncut model.  ``layers_kept`` names the published
  layers a cut configuration keeps, in order; the per-layer lists
  (``layer_types``, ``mlp_layer_types``, ``num_attention_heads_per_layer``)
  are the published ones, read at those places.

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision,
no kernels, no cache, no batching, nothing imported from the program under
test.  The band is a mask.  Attention is computed a block of ``Q_BLOCK``
queries and one key/value head's group of query heads at a time
(``lax.map``), so that a request of 13 312 tokens fits beside the weights:
a full layer's block sees every key under the causal mask; a window
layer's block sees the ``Q_BLOCK + sliding_window - 1`` keys that can lie
inside its band and skips the key blocks wholly outside it (the same
sums: their weights are exactly zero).  Every held expert is computed for
every token, by a loop, and masked.

Departures from the published implementation, each also under ``assumed``
in the configuration file: softmax scores (the config names no score
function); the shared expert is added ungated; the gate is one sigmoid a
head from ``u`` through its own matrix, before ``W_o``; no norm on ``q``
and ``k``; weights are float32 where the checkpoint is bfloat16.
"""
import math

import jax
import jax.numpy as jnp

PRECISION = "highest"
Q_BLOCK = 512       # queries a block of the attention


def kept(cfg):
    """The published layers a configuration keeps, in order."""
    return list(cfg.get("layers_kept") or range(cfg["num_hidden_layers"]))


def layer_types(cfg):
    """``"full_attention"`` | ``"sliding_attention"`` for each layer kept."""
    return [cfg["layer_types"][i] for i in kept(cfg)]


def layer_heads(cfg):
    """Query heads of each layer kept."""
    per_layer = cfg.get("num_attention_heads_per_layer")
    return [per_layer[i] if per_layer else cfg["num_attention_heads"]
            for i in kept(cfg)]


def layer_dense(cfg):
    """Whether each layer kept has the dense FFN."""
    return [cfg["mlp_layer_types"][i] == "dense" for i in kept(cfg)]


def held(cfg):
    """-> (first, count, router width): the experts held of those
    routed."""
    return (cfg.get("experts_first", 0), cfg["num_experts"],
            cfg.get("router_experts", cfg["num_experts"]))


def spec(cfg):
    """{parameter name: shape} for a configuration.  Matrices are stored
    (out, in) as the checkpoints store them; the held experts of a layer
    are stacked on a leading axis."""
    d, hd, v = cfg["hidden_size"], cfg["head_dim"], cfg["vocab_size"]
    kv = cfg["num_key_value_heads"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = cfg["shared_expert_intermediate_size"]
    _, e, routed = held(cfg)
    out = {"tok_embed_weight": (v, d), "final_norm_gamma": (d,),
           "lm_head_weight": (v, d)}
    for i, (h, dense) in enumerate(zip(layer_heads(cfg), layer_dense(cfg))):
        p = "blk%d_" % i
        out.update({p + "attn_norm_gamma": (d,), p + "ffn_norm_gamma": (d,),
                    p + "q_weight": (h * hd, d), p + "k_weight": (kv * hd, d),
                    p + "v_weight": (kv * hd, d),
                    p + "attn_gate_weight": (h, d),
                    p + "o_weight": (d, h * hd)})
        if dense:
            out.update({p + "gate_weight": (f, d), p + "up_weight": (f, d),
                        p + "down_weight": (d, f)})
        else:
            out.update({
                p + "router_weight": (routed, d),
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


def rope_frequencies(group, head_dim):
    """One ``rope_parameters`` group -> (rot, inv_freq (rot / 2,) as
    Python floats, the factor on cos and sin)."""
    rot = int(head_dim * group.get("partial_rotary_factor", 1))
    theta = float(group["rope_theta"])
    freqs = [theta ** (2.0 * i / rot) for i in range(rot // 2)]
    if group.get("rope_type", "default") == "default":
        return rot, [1.0 / f for f in freqs], 1.0
    if group["rope_type"] != "yarn":
        raise ValueError("rope_type %r" % group["rope_type"])
    orig = group["original_max_position_embeddings"]

    def correction(turns):
        return rot * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(group["beta_fast"])), 0)
    high = min(math.ceil(correction(group["beta_slow"])), rot - 1)
    span = (high - low) or 0.001
    inv = []
    for i, f in enumerate(freqs):
        r = min(max((i - low) / span, 0.0), 1.0)
        inv.append((1.0 - r) / f + r / (group["factor"] * f))
    return rot, inv, float(group["attention_factor"])


def _rope(x, positions, group):
    """Rotate the pairs ``(i, i + rot / 2)`` of ``x`` (T, heads, head_dim)
    at ``positions`` (T,)."""
    rot, inv_freq, factor = rope_frequencies(group, x.shape[-1])
    angle = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos = (jnp.cos(angle) * factor).astype(x.dtype)[:, None, :]
    sin = (jnp.sin(angle) * factor).astype(x.dtype)[:, None, :]
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


def _attend(q, k, v, window):
    """q (T, KV, G, D), k and v (T, KV, D) -> (T, KV, G, D): softmax
    attention under the causal mask, and inside the band of ``window``
    keys where it is not 0; a block of queries and one key/value head at
    a time."""
    t, kv, g, d = q.shape
    block = min(Q_BLOCK, t)
    pad = -t % block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
    reach = min(window - 1, t) if window else 0     # keys before a block
    k_front = jnp.pad(k, ((reach, pad), (0, 0), (0, 0)))
    v_front = jnp.pad(v, ((reach, pad), (0, 0), (0, 0)))
    starts = jnp.arange(0, t + pad, block)

    def one_head(head):
        qh, kh, vh = head   # (T + pad, G, D), (reach + T + pad, D) twice

        def one_block(start):
            rows = start + jnp.arange(block)
            qb = jax.lax.dynamic_slice_in_dim(qh, start, block)
            if window:      # the keys that can lie inside this block's band
                kb = jax.lax.dynamic_slice_in_dim(kh, start, reach + block)
                vb = jax.lax.dynamic_slice_in_dim(vh, start, reach + block)
                cols = start - reach + jnp.arange(reach + block)
            else:
                kb, vb, cols = kh, vh, jnp.arange(t + pad)
            seen = (cols[None, :] >= 0) & (cols[None, :] <= rows[:, None])
            if window:
                seen = seen & (rows[:, None] - cols[None, :] < window)
            scores = jnp.einsum("qgd,kd->gqk", qb, kb, precision=PRECISION) \
                / d ** 0.5
            scores = jnp.where(seen[None], scores, -jnp.inf)
            return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(scores, axis=-1),
                              vb, precision=PRECISION)

        return jax.lax.map(one_block, starts).reshape(t + pad, g, d)

    out = jax.lax.map(one_head, (q.transpose(1, 0, 2, 3),
                                 k_front.transpose(1, 0, 2),
                                 v_front.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3)[:t]


def _attention(u, p, pre, kind, heads, cfg):
    t = u.shape[0]
    kv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    group = cfg["rope_parameters"][kind]
    positions = jnp.arange(t)
    q = _rope(_linear(u, p[pre + "q_weight"]).reshape(t, heads, hd),
              positions, group)
    k = _rope(_linear(u, p[pre + "k_weight"]).reshape(t, kv, hd),
              positions, group)
    v = _linear(u, p[pre + "v_weight"]).reshape(t, kv, hd)
    ctx = _attend(q.reshape(t, kv, heads // kv, hd), k, v,
                  cfg["sliding_window"] if kind == "sliding_attention" else 0)
    gate = jax.nn.sigmoid(_linear(u, p[pre + "attn_gate_weight"]))
    return _linear((ctx.reshape(t, heads, hd) * gate[:, :, None]
                    ).reshape(t, heads * hd), p[pre + "o_weight"])


def _swiglu(u, gate, up, down):
    return _linear(jax.nn.silu(_linear(u, gate)) * _linear(u, up), down)


def route(u, p, pre, cfg):
    """-> (T, router width) combine weights over ALL the experts routed:
    zero for those not taken."""
    t = u.shape[0]
    logits = _linear(u, p[pre + "router_weight"])
    cap = cfg.get("moe_router_logit_softcapping", 0)
    if cap:
        logits = cap * jnp.tanh(logits / cap)
    scores = jax.nn.softmax(logits, axis=-1)
    _, taken = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    mask = jnp.zeros(scores.shape, bool).at[
        jnp.arange(t)[:, None], taken].set(True)
    picked = jnp.where(mask, scores, 0.0)
    if cfg["norm_topk_prob"]:
        picked = picked / picked.sum(-1, keepdims=True)
    return picked * cfg["moe_routed_scaling_factor"]


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


def _block(x, p, i, kind, heads, dense, cfg):
    pre = "blk%d_" % i
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p[pre + "attn_norm_gamma"], eps), p, pre,
                       kind, heads, cfg)
    u = _rms_norm(x, p[pre + "ffn_norm_gamma"], eps)
    if dense:
        return x + _swiglu(u, p[pre + "gate_weight"], p[pre + "up_weight"],
                           p[pre + "down_weight"])
    return x + routed(u, p, pre, cfg) + shared(u, p, pre)


def logits(params, tokens, cfg, cast=None):
    """(T,) int tokens -> (T, vocab held) float32 logits of one sequence.

    ``cast`` computes in a lower precision: parameters and activations are
    held in that type."""
    p = params
    if cast is not None:
        p = {k: v.astype(cast) for k, v in params.items()}
    x = p["tok_embed_weight"][tokens]
    for i, (kind, heads, dense) in enumerate(zip(
            layer_types(cfg), layer_heads(cfg), layer_dense(cfg))):
        x = _block(x, p, i, kind, heads, dense, cfg)
    x = _rms_norm(x, p["final_norm_gamma"], cfg["rms_norm_eps"])
    return _linear(x, p["lm_head_weight"]).astype(jnp.float32)
