"""Plain reference: the GraniteMoeHybrid decoder as granite-4.0-h-micro
configures it (``model_type: granitemoehybrid``; keys as in the model's
``config.json``): Mamba-2 layers and a few grouped-query attention layers
in the published ``layer_types`` order, no positions, no experts.

Written from the published ``config.json``, the Mamba-2 paper
(arXiv:2405.21060, section 7 and listing 1: the recurrence below is its
definition, not its chunked algorithm) and the published implementation
(``transformers`` ``GraniteMoeHybridModel``):

* ``x0 = E[token] * embedding_multiplier``.  ``position_embedding_type``
  is ``nope``: nothing is added to ``x`` and no query or key is rotated.
* every layer: ``x <- x + residual_multiplier * Mixer(RMSNorm(x))``, then
  ``x <- x + residual_multiplier * MLP(RMSNorm(x))``; RMSNorm with a
  learned scale at ``rms_norm_eps``.  ``num_local_experts`` is 0, so the
  MLP is the shared SwiGLU alone: ``[g | v] = W_in u`` (2 x
  ``shared_intermediate_size``), ``MLP(u) = W_out (silu(g) * v)``.
* attention layer: ``q = W_q u`` as ``num_attention_heads`` heads of
  ``hidden_size / num_attention_heads``, ``k`` and ``v`` as
  ``num_key_value_heads``; scores ``q . k * attention_multiplier``; causal
  softmax; query head ``j`` reads key/value head
  ``j // (num_attention_heads / num_key_value_heads)``; ``o = W_o
  concat(heads)``.  No bias.
* Mamba-2 layer: ``[z | xBC | dt] = W_in u`` of widths ``d_inner`` |
  ``d_inner + 2 * mamba_n_groups * mamba_d_state`` | ``mamba_n_heads``
  (``d_inner = mamba_n_heads * mamba_d_head``); ``xBC <-
  silu(conv1d(xBC))``: depthwise, causal (``mamba_d_conv - 1`` zero rows
  before the sequence), with bias; ``[x | B | C] = xBC``, ``B`` and ``C``
  shared by the heads of a group; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``, a scalar a head; per head a state ``h`` of
  ``mamba_d_head`` x ``mamba_d_state``, zero before the sequence:
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t``,
  ``y_t = h_t C_t + D x_t``;
  ``y <- RMSNorm(y * silu(z))`` over each group's ``d_inner /
  mamba_n_groups`` channels with a learned scale; ``out = W_out y``.
* ``logits = E . RMSNorm(x) / logits_scaling``: the head is the embedding
  (``tie_word_embeddings``).

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision,
no kernels, no cache, no batching, nothing imported from the program under
test.  **The recurrence runs token by token** (``lax.scan`` over ``t``):
the definition, where the program runs the chunked form in prefill and
one step a token in decode.  Attention runs one key/value head's group of
query heads at a time (``lax.map``).

Departures from the published implementation, each also under ``assumed``
in the configuration file: it computes the same recurrence by the chunked
algorithm at ``mamba_chunk_size`` (a different order of the same sums);
its ``time_step_limit`` clamp of ``dt`` is (0, inf) by default and does
nothing; weights are float32 where the checkpoint is bfloat16.
"""
import jax
import jax.numpy as jnp

PRECISION = "highest"


def _sizes(cfg):
    heads, group, state = (cfg["mamba_n_heads"], cfg["mamba_n_groups"],
                           cfg["mamba_d_state"])
    d_inner = heads * cfg["mamba_d_head"]
    return heads, group, state, d_inner, d_inner + 2 * group * state


def spec(cfg):
    """{parameter name: shape} for a configuration.  Matrices are stored
    (out, in) as the checkpoints store them, the depthwise filter as
    (channels, taps); there is no head matrix."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, f = d // h, cfg["shared_intermediate_size"]
    heads, _, _, d_inner, conv_dim = _sizes(cfg)
    out = {"tok_embed_weight": (v, d), "final_norm_gamma": (d,)}
    for i, kind in enumerate(cfg["layer_types"]):
        p = "blk%d_" % i
        out.update({p + "mixer_norm_gamma": (d,), p + "ffn_norm_gamma": (d,),
                    p + "ffn_in_weight": (2 * f, d),
                    p + "ffn_out_weight": (d, f)})
        if kind == "attention":
            out.update({p + "q_weight": (h * hd, d),
                        p + "k_weight": (kv * hd, d),
                        p + "v_weight": (kv * hd, d),
                        p + "o_weight": (d, h * hd)})
        else:
            out.update({p + "in_weight": (d_inner + conv_dim + heads, d),
                        p + "conv_weight": (conv_dim, cfg["mamba_d_conv"]),
                        p + "conv_bias": (conv_dim,),
                        p + "dt_bias": (heads,), p + "A_log": (heads,),
                        p + "D": (heads,),
                        p + "gate_norm_gamma": (d_inner,),
                        p + "out_weight": (d, d_inner)})
    return out


def _rms_norm(x, gamma, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * gamma


def _linear(x, w):
    return jnp.matmul(x, w.T, precision=PRECISION)


def _attention(u, p, pre, cfg):
    t, d = u.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, group = d // h, h // kv
    q = _linear(u, p[pre + "q_weight"]).reshape(t, kv, group, hd)
    k = _linear(u, p[pre + "k_weight"]).reshape(t, kv, hd)
    v = _linear(u, p[pre + "v_weight"]).reshape(t, kv, hd)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one_kv_head(qkv):
        qg, kh, vh = qkv                     # (group, t, hd), (t, hd) x 2
        scores = jnp.matmul(qg, kh.T, precision=PRECISION) \
            * cfg["attention_multiplier"]
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.matmul(jax.nn.softmax(scores, axis=-1), vh,
                          precision=PRECISION)

    ctx = jax.lax.map(one_kv_head, (q.transpose(1, 2, 0, 3),
                                    k.transpose(1, 0, 2),
                                    v.transpose(1, 0, 2)))
    # (kv, group, t, hd) -> (t, kv * group * hd): head j = kv * group + g
    return _linear(ctx.transpose(2, 0, 1, 3).reshape(t, h * hd),
                   p[pre + "o_weight"])


def _mamba(u, p, pre, cfg):
    t = u.shape[0]
    heads, group, state, d_inner, conv_dim = _sizes(cfg)
    taps, width = cfg["mamba_d_conv"], cfg["mamba_d_head"]
    zxd = _linear(u, p[pre + "in_weight"])
    z, xbc, dt = (zxd[:, :d_inner], zxd[:, d_inner:d_inner + conv_dim],
                  zxd[:, d_inner + conv_dim:])
    padded = jnp.concatenate([jnp.zeros((taps - 1, conv_dim), xbc.dtype),
                              xbc])
    conv = p[pre + "conv_bias"] + sum(
        padded[j:j + t] * p[pre + "conv_weight"][:, j] for j in range(taps))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_inner].reshape(t, heads, width)
    per_head = heads // group      # B and C of a head: its group's
    b = jnp.repeat(xbc[:, d_inner:d_inner + group * state].reshape(
        t, group, state), per_head, axis=1)
    c = jnp.repeat(xbc[:, d_inner + group * state:].reshape(
        t, group, state), per_head, axis=1)
    dt = jax.nn.softplus(dt + p[pre + "dt_bias"])
    a = -jnp.exp(p[pre + "A_log"])
    skip = p[pre + "D"]

    def token(h, row):
        x_t, dt_t, b_t, c_t = row            # (H, P), (H,), (H, N), (H, N)
        h = jnp.exp(dt_t * a)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1) + skip[:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((heads, width, state), x.dtype),
                        (x, dt, b, c))
    y = y.reshape(t, d_inner) * jax.nn.silu(z)
    y = _rms_norm(y.reshape(t, group, d_inner // group),
                  p[pre + "gate_norm_gamma"].reshape(group, -1),
                  cfg["rms_norm_eps"]).reshape(t, d_inner)
    return _linear(y, p[pre + "out_weight"])


def _block(x, p, i, cfg):
    pre = "blk%d_" % i
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mixer = _attention if cfg["layer_types"][i] == "attention" else _mamba
    x = x + res * mixer(_rms_norm(x, p[pre + "mixer_norm_gamma"], eps), p,
                        pre, cfg)
    u = _rms_norm(x, p[pre + "ffn_norm_gamma"], eps)
    gate, value = jnp.split(_linear(u, p[pre + "ffn_in_weight"]), 2, axis=-1)
    return x + res * _linear(jax.nn.silu(gate) * value,
                             p[pre + "ffn_out_weight"])


def logits(params, tokens, cfg, cast=None):
    """(T,) int tokens -> (T, vocab) float32 logits of one sequence.

    ``cast`` computes in a lower precision: parameters and activations,
    the state among them, are held in that type."""
    p = params
    if cast is not None:
        p = {k: v.astype(cast) for k, v in params.items()}
    x = p["tok_embed_weight"][tokens] * jnp.asarray(
        cfg["embedding_multiplier"], p["tok_embed_weight"].dtype)
    for i in range(cfg["num_hidden_layers"]):
        x = _block(x, p, i, cfg)
    x = _rms_norm(x, p["final_norm_gamma"], cfg["rms_norm_eps"])
    return (_linear(x, p["tok_embed_weight"])
            / cfg["logits_scaling"]).astype(jnp.float32)
