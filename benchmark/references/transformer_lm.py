"""Plain reference: GPT-2-shaped decoder-only language model.

Written from Radford et al. 2019 (GPT-2) as Cerebras-GPT (arXiv:2304.03208)
uses it: learned token and position embeddings, pre-norm blocks
``x + Attn(LN(x))``, ``x + MLP(LN(x))`` with biased projections, a final
LayerNorm and a linear head.  Straightforward ``jax.numpy`` in float32 with
``highest`` matmul precision, no kernels, no cache, no batching tricks, and
nothing imported from the program under test.

Departures from the published model, both stated in the configuration
files under ``assumed``: the head is untied and has a bias (Cerebras-GPT
ties it to the token embedding), and GELU is the tanh approximation
(GPT-2's ``gelu_new``; Cerebras-GPT's config says ``gelu``).
"""
import jax
import jax.numpy as jnp

PRECISION = "highest"
LN_EPS = 1e-5


def spec(cfg):
    """{parameter name: shape} for a configuration (keys as in
    ``configs/*.json``)."""
    c, v, f = cfg["d_model"], cfg["vocab_size"], cfg["d_ff"]
    out = {
        "tok_embed_weight": (v, c),
        "pos_embed": (1, cfg["seq_len"], c),
        "final_ln_gamma": (c,), "final_ln_beta": (c,),
        "lm_head_weight": (v, c), "lm_head_bias": (v,),
    }
    for i in range(cfg["num_layers"]):
        p = "blk%d_" % i
        out.update({
            p + "ln1_gamma": (c,), p + "ln1_beta": (c,),
            p + "attn_in_weight": (3 * c, c), p + "attn_in_bias": (3 * c,),
            p + "attn_out_weight": (c, c), p + "attn_out_bias": (c,),
            p + "ln2_gamma": (c,), p + "ln2_beta": (c,),
            p + "ffn1_weight": (f, c), p + "ffn1_bias": (f,),
            p + "ffn2_weight": (c, f), p + "ffn2_bias": (c,),
        })
    return out


def _layer_norm(x, gamma, beta):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * gamma + beta


def _linear(x, w, b):
    # weights are stored (out, in), as nn.Linear and the checkpoints do
    return jnp.matmul(x, w.T, precision=PRECISION) + b


def _block(x, p, i, num_heads):
    t, c = x.shape
    d = c // num_heads
    pre = "blk%d_" % i
    h = _layer_norm(x, p[pre + "ln1_gamma"], p[pre + "ln1_beta"])
    qkv = _linear(h, p[pre + "attn_in_weight"], p[pre + "attn_in_bias"])
    q, k, v = (a.reshape(t, num_heads, d).transpose(1, 0, 2)
               for a in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("hqd,hkd->hqk", q, k, precision=PRECISION) / d ** 0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("hqk,hkd->hqd", attn, v, precision=PRECISION)
    ctx = ctx.transpose(1, 0, 2).reshape(t, c)
    x = x + _linear(ctx, p[pre + "attn_out_weight"], p[pre + "attn_out_bias"])
    h = _layer_norm(x, p[pre + "ln2_gamma"], p[pre + "ln2_beta"])
    h = _linear(h, p[pre + "ffn1_weight"], p[pre + "ffn1_bias"])
    h = jax.nn.gelu(h, approximate=True)
    return x + _linear(h, p[pre + "ffn2_weight"], p[pre + "ffn2_bias"])


def logits(params, tokens, cfg, cast=None):
    """(T,) int tokens -> (T, vocab) float32 logits of one sequence.

    ``cast`` computes in a lower precision (the control of the
    correctness check): parameters and activations are held in that
    type."""
    p = params
    if cast is not None:
        p = {k: v.astype(cast) for k, v in params.items()}
    t = tokens.shape[0]
    x = p["tok_embed_weight"][tokens] + p["pos_embed"][0, :t]
    # remat keeps one block's activations alive, so full depth fits
    # beside the float32 weights
    block = jax.checkpoint(_block, static_argnums=(2, 3))
    for i in range(cfg["num_layers"]):
        x = block(x, p, i, cfg["num_heads"])
    x = _layer_norm(x, p["final_ln_gamma"], p["final_ln_beta"])
    return _linear(x, p["lm_head_weight"], p["lm_head_bias"]).astype(
        jnp.float32)


def sequence_loss(params, tokens, labels, cfg):
    """Summed next-token cross-entropy of one sequence."""
    logp = jax.nn.log_softmax(logits(params, tokens, cfg), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()


def make_loss_and_grads(cfg):
    """-> f(params, tokens (B, T), labels (B, T)) = (mean cross-entropy
    over every token, its gradient), one sequence at a time with the
    gradients accumulated, so a full-width step fits beside the weights."""
    one = jax.jit(jax.value_and_grad(
        lambda p, t, l: sequence_loss(p, t, l, cfg)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    div = jax.jit(lambda g, n: jax.tree.map(lambda x: x / n, g),
                  donate_argnums=0)

    def loss_and_grads(params, aux, tokens, labels):
        total, grads = 0.0, None
        for row in range(tokens.shape[0]):
            loss, g = one(params, tokens[row], labels[row])
            total = total + loss
            grads = g if grads is None else add(grads, g)
        n = float(tokens.size)
        return total / n, div(grads, n), aux

    return loss_and_grads


def aux_spec(cfg):
    """No auxiliary (non-gradient) state in this model."""
    return {}
