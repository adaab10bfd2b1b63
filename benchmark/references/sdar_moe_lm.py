"""Plain reference: the SDAR-MoE decoder as SDAR-30B-A3B-Chat configures it
(``model_type: sdar_moe``; keys as in the model's ``config.json``) and the
block-diffusion loop that generates with it: a QK-normed grouped-query
attention layer and softmax-routed experts without a shared one in every
layer, under a mask that is causal from block to block and sees both ways
inside a block; a block of ``block_length`` tokens starts as mask tokens
and is passed through the model until none is left.

Written from the published ``config.json`` and from what its keys mean in
the ``transformers`` library's Qwen3-MoE model, which the checkpoint's own
modelling file follows (d = ``hidden_size``, D = ``head_dim``, eps =
``rms_norm_eps``, B = ``block_length``), and from the JetLM/SDAR
repository's ``generate.py: block_diffusion_generate``:

* layer: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``,
  RMSNorm with a learned scale; no position table, no bias
  (``attention_bias`` false); after the last layer ``logits = W_head
  RMSNorm(y)``, the head untied.  Every layer is alike
  (``decoder_sparse_step`` 1, ``mlp_only_layers`` empty:
  ``intermediate_size`` is used by none).
* Attn: ``q = W_q u`` as ``num_attention_heads`` heads of D, ``k = W_k u``,
  ``v = W_v u`` as ``num_key_value_heads``; ``q_h <- RMSNorm(q_h;
  q_norm)``, ``k_h <- RMSNorm(k_h; k_norm)`` over the head's values, one
  scale vector for all heads of a kind; both rotated: all D values, pairs
  ``(i, i + D / 2)`` (``rotate_half``), ``inv_freq_i = rope_theta^(-2i /
  D)``; query head ``h`` reads key/value head ``h // (heads / kv heads)``;
  scores ``q . k / sqrt(D)``; **a key at position j is visible to the row
  at position p where ``j < (p // B + 1) * B``**; softmax; ``W_o``.
* MoE: ``s = softmax(W_r u)`` over all ``router_experts`` in float32; the
  ``num_experts_per_tok`` largest taken; ``w = s / sum_taken(s)``
  (``norm_topk_prob``); ``sum_e w_e W_down_e (silu(W_gate_e u) * W_up_e
  u)``; no shared expert.
* **row p's logits are over the token AT position p**: a row that holds
  the mask token predicts itself, and nothing is shifted.
* generation: a prompt of P tokens fills ``P // B`` whole blocks; the ``P %
  B`` left open the first generated block, whose other rows hold
  ``mask_token_id``.  A block is passed through the model over [everything
  before it | its own B rows] until it holds no mask.  A **denoise pass**
  takes, on every still-masked row, ``x0 = argmax`` and ``c =
  softmax(logits)[x0]`` in float32, and unmasks every masked row with ``c >
  confidence_threshold`` or, where fewer than the pass's quota clear it,
  the quota's most confident ones (``low_confidence_dynamic``; the quota of
  pass ``i`` is ``B // denoising_steps``, one more in the first ``B %
  denoising_steps`` passes); an unmasked row stays.  Then the next block
  starts (the published loop first runs the finished block once more to
  store its keys and values, its commit pass: this reference keeps no
  cache and has nothing to store).

* **the share**: ``num_experts`` counts the experts HELD (``experts_first``
  on, of ``router_experts``); the router and the weights' normalisation are
  over all of them, and the layer's result is the held experts' part alone:
  what the other chips of the deployment would add is left out, a row none
  of whose experts is held gets ``y = h``, and that partial result goes on
  to the next layer.  ``vocab_size`` counts the rows of the vocabulary
  held, of the embedding and of the head alike: a smaller vocabulary, whose
  last row is the mask token's (``mask_token_id`` names it by its place in
  the slice).  With ``num_experts == router_experts`` and the whole
  vocabulary this is the uncut model.

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision,
no kernels, no cache, no batching, nothing imported from the program under
test.  Attention is computed a block of ``Q_BLOCK`` queries and one
key/value head's group of query heads at a time (``lax.map``), so that a
request of 4 096 tokens fits beside the weights.  Every held expert is
computed for every token, by a loop, and masked.

Departures from the published implementation, each also under ``assumed``
in the configuration file: **the mask token's own logit is left out of the
argmax** (``c`` is still the softmax over every logit): a trained model
never picks the mask, seeded random weights would once in ``vocab_size``
draws, and that block would never close; ``block_length``,
``denoising_steps``, the strategy and its threshold, which the config
does not carry, are the generation script's to choose and are stated in
the configuration; weights are float32 where the checkpoint is bfloat16.

:func:`denoise_logits` may be given ``context``, the keys and values of
every position from ONE block-causal forward of the request's final tokens
(:func:`context`: plain arrays this module computed itself), and then
computes the block's B rows alone.  Under the mask nothing before a block
can see into it or past it, so the rows before a block are what they are
in the forward over ``[0, (block + 1) * B)``; ``tests/
test_serve_sdar_moe.py`` holds the two forms to each other.
"""
import jax
import jax.numpy as jnp

PRECISION = "highest"
Q_BLOCK = 512       # queries a block of the attention


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
    (out, in) as the checkpoints store them; the held experts of a layer
    are stacked on a leading axis."""
    d, hd, v = cfg["hidden_size"], head_dim(cfg), cfg["vocab_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    fe = cfg["moe_intermediate_size"]
    _, e, routed = held(cfg)
    out = {"tok_embed_weight": (v, d), "final_norm_gamma": (d,),
           "lm_head_weight": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        p = "blk%d_" % i
        out.update({p + "attn_norm_gamma": (d,),
                    p + "q_weight": (h * hd, d),
                    p + "k_weight": (kv * hd, d),
                    p + "v_weight": (kv * hd, d),
                    p + "q_norm_gamma": (hd,), p + "k_norm_gamma": (hd,),
                    p + "o_weight": (d, h * hd),
                    p + "ffn_norm_gamma": (d,),
                    p + "router_weight": (routed, d),
                    p + "experts_gate_weight": (e, fe, d),
                    p + "experts_up_weight": (e, fe, d),
                    p + "experts_down_weight": (e, d, fe)})
    return out


def _rms_norm(x, gamma, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * gamma


def _linear(x, w):
    return jnp.matmul(x, w.T, precision=PRECISION)


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


def horizons(positions, cfg):
    """Keys the row at each of ``positions`` sees: those of its own block
    and of every block before it."""
    b = cfg["block_length"]
    return (positions // b + 1) * b


def _attend(q, k, v, seen):
    """q (T, KV, G, D), k and v (K, KV, D), seen (T,): the row sees keys
    ``0 .. seen - 1`` -> (T, KV, G, D); a block of queries and one
    key/value head at a time."""
    t, kv, g, d = q.shape
    block = min(Q_BLOCK, t)
    pad = -t % block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
    seen = jnp.pad(seen, (0, pad), constant_values=1)
    starts = jnp.arange(0, t + pad, block)
    cols = jnp.arange(k.shape[0])

    def one_head(head):
        qh, kh, vh = head           # (T + pad, G, D), (K, D) twice

        def one_block(start):
            qb = jax.lax.dynamic_slice_in_dim(qh, start, block)
            sb = jax.lax.dynamic_slice_in_dim(seen, start, block)
            scores = jnp.einsum("qgd,kd->gqk", qb, kh, precision=PRECISION) \
                / d ** 0.5
            scores = jnp.where((cols[None, :] < sb[:, None])[None], scores,
                               -jnp.inf)
            return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(scores, axis=-1),
                              vh, precision=PRECISION)

        return jax.lax.map(one_block, starts).reshape(t + pad, g, d)

    out = jax.lax.map(one_head, (q.transpose(1, 0, 2, 3),
                                 k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3)[:t]


def qkv(u, positions, p, pre, cfg):
    """u (T, d) at ``positions`` -> the normed and rotated q (T, H, D) and
    k (T, KV, D), and v (T, KV, D)."""
    t = u.shape[0]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        head_dim(cfg)
    theta = float(cfg["rope_theta"])
    q = _rms_norm(_linear(u, p[pre + "q_weight"]).reshape(t, h, hd),
                  p[pre + "q_norm_gamma"], cfg["rms_norm_eps"])
    k = _rms_norm(_linear(u, p[pre + "k_weight"]).reshape(t, kv, hd),
                  p[pre + "k_norm_gamma"], cfg["rms_norm_eps"])
    return rope(q, positions, theta), rope(k, positions, theta), \
        _linear(u, p[pre + "v_weight"]).reshape(t, kv, hd)


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
    picked = jnp.where(mask, scores, 0.0)
    if cfg["norm_topk_prob"]:
        picked = picked / picked.sum(-1, keepdims=True)
    return picked


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


def _forward(p, tokens, positions, cfg, behind=None):
    """The layers over ``tokens`` (T,) at ``positions``, which are
    consecutive.  ``behind`` gives a layer's keys and values at EVERY
    position (a pair of (K, KV, D) arrays a layer): the rows' own are
    written over theirs at ``positions``, so a key's place is its position
    and a row's horizon masks what lies past its block.  Without it the
    rows see each other alone.  -> (logits (T, vocab held), the rows' own
    keys and values, a pair a layer)."""
    t = tokens.shape[0]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    seen = horizons(positions, cfg)
    x = p["tok_embed_weight"][tokens]
    own = []
    for i in range(cfg["num_hidden_layers"]):
        pre = "blk%d_" % i
        u = _rms_norm(x, p[pre + "attn_norm_gamma"], cfg["rms_norm_eps"])
        q, k, v = qkv(u, positions, p, pre, cfg)
        own.append((k, v))
        if behind is not None:
            k, v = (jax.lax.dynamic_update_slice_in_dim(
                whole, rows, positions[0], 0)
                for whole, rows in zip(behind[i], (k, v)))
        ctx = _attend(q.reshape(t, kv, h // kv, -1), k, v, seen)
        x = x + _linear(ctx.reshape(t, -1), p[pre + "o_weight"])
        u = _rms_norm(x, p[pre + "ffn_norm_gamma"], cfg["rms_norm_eps"])
        x = x + routed(u, p, pre, cfg)
    x = _rms_norm(x, p["final_norm_gamma"], cfg["rms_norm_eps"])
    return _linear(x, p["lm_head_weight"]).astype(jnp.float32), own


def _cast(params, cast):
    if cast is None:
        return params
    return {k: v.astype(cast) for k, v in params.items()}


def logits(params, tokens, cfg, cast=None):
    """(T,) int tokens -> (T, vocab held) float32 logits of one sequence
    under the block-causal mask; row p is over the token at p.

    ``cast`` computes in a lower precision: parameters and activations are
    held in that type."""
    return _forward(_cast(params, cast), tokens,
                    jnp.arange(tokens.shape[0]), cfg)[0]


def context(params, tokens, cfg, cast=None):
    """The keys (normed and rotated) and values of every position of
    ``tokens`` (T,) from one block-causal forward: a pair of (T, KV, D)
    arrays a layer, what :func:`denoise_logits` takes as ``context``."""
    return _forward(_cast(params, cast), tokens,
                    jnp.arange(tokens.shape[0]), cfg)[1]


def denoise_logits(params, tokens, cfg, block, visible, context=None,
                   cast=None):
    """What one pass over block ``block`` of ``tokens`` (T,) computes: the
    forward over positions ``[0, (block + 1) * B)`` with the block's rows
    that are not ``visible`` ((B,) bool) replaced by the mask token -> the
    block's (B, vocab held) rows of logits.

    With ``context`` (:func:`context` of the same ``tokens``, or of a
    sequence that agrees with them in front of the block) the B rows alone
    are computed, over its rows in front of the block and their own:
    ``block`` may then be traced, and what ``context`` holds from the
    block on is overwritten or never seen."""
    b = cfg["block_length"]
    p = _cast(params, cast)
    start = block * b
    mask = jnp.asarray(cfg["mask_token_id"], tokens.dtype)
    if context is None:
        fed = tokens[:start + b].at[start:].set(
            jnp.where(visible, tokens[start:start + b], mask))
        return _forward(p, fed, jnp.arange(start + b), cfg)[0][start:]
    rows = jnp.where(visible, jax.lax.dynamic_slice_in_dim(tokens, start, b),
                     mask)
    return _forward(p, rows, start + jnp.arange(b), cfg, context)[0]


def confidence(rows, cfg):
    """(..., vocab held) logits -> (x0, c): the best token of each row,
    the mask token left out, and ``softmax(row)[x0]`` in float32."""
    rows = rows.astype(jnp.float32)
    open_ = rows.at[..., cfg["mask_token_id"]].set(-jnp.inf)
    return jnp.argmax(open_, axis=-1), jnp.exp(
        jnp.max(open_, axis=-1) - jax.nn.logsumexp(rows, axis=-1))


def quota(cfg, pass_index):
    """Rows pass ``pass_index`` (0-based) of a block unmasks at least."""
    b, steps = cfg["block_length"], cfg["denoising_steps"]
    return b // steps + (pass_index < b % steps)


def unmask(rows, masked, cfg, pass_index):
    """One denoise pass's choice: logits ``rows`` (B, vocab held) and which
    rows are still ``masked`` -> (x0 (B,), c (B,) with -inf on the rows
    not masked, chosen (B,) bool).  ``low_confidence_dynamic``: every
    masked row over the threshold, or the quota's most confident."""
    x0, c = confidence(rows, cfg)
    c = jnp.where(masked, c, -jnp.inf)
    over = c > cfg["confidence_threshold"]
    n = min(int(quota(cfg, pass_index)), int(masked.sum()))
    if int(over.sum()) >= quota(cfg, pass_index):
        return x0, c, over
    _, top = jax.lax.top_k(c, n)
    return x0, c, jnp.zeros(c.shape, bool).at[top].set(True)


def generate(params, prompt, max_new, cfg):
    """The published loop, greedy, without a cache: every pass is a forward
    over everything up to the block's end.  -> (the ``max_new`` tokens
    generated, the denoise pass of its block, 0-based, in which each was
    unmasked); the last block's rows past ``max_new`` are dropped."""
    b = cfg["block_length"]
    seq = [int(t) for t in prompt]
    n_prompt = len(seq)
    passes = [-1] * n_prompt
    while len(seq) < n_prompt + max_new:
        block = len(seq) // b
        start = block * b
        known = len(seq) - start
        seq = seq + [cfg["mask_token_id"]] * (b - known)
        passes = passes + [-1] * (b - known)
        masked = jnp.arange(b) >= known
        for i in range(cfg["denoising_steps"]):
            if not bool(masked.any()):
                break
            rows = denoise_logits(params, jnp.asarray(seq, jnp.int32), cfg,
                                  block, ~masked)
            x0, _, chosen = unmask(rows, masked, cfg, i)
            for j in range(b):
                if bool(chosen[j]):
                    seq[start + j], passes[start + j] = int(x0[j]), i
            masked = masked & ~chosen
    end = n_prompt + max_new
    return seq[n_prompt:end], passes[n_prompt:end]
