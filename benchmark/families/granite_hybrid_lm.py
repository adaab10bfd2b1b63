"""Family ``granite_hybrid_lm``: the GraniteMoeHybrid decoder (Mamba-2
layers, a few grouped-query attention layers, no positions, a tied head)
that ``mxnet_tpu/serve/granite_hybrid.py`` serves.  A configuration's keys
are the published ``config.json``'s.

This family is **served and not yet trained**: ``Module.fit`` has no
recurrent layer with a backward (ROADMAP M4), so the names a training job
asks for raise ``ManifestError`` and nothing stands in for them.  What a
serving job asks for: ``reference`` (the plain forward), ``model_config``
(the architecture as the program's public ``serve.ModelConfig`` takes
it), ``published_init`` (the Mamba-2 leaves that ``weights.py``'s rules by
name would set to values under which the state does nothing) and the
counts of work under its two roofline metrics.
"""
import jax.numpy as jnp

from manifest import ManifestError
from references import granite_hybrid_lm as reference

BLOCK = "granitemoehybrid"      # the program's name for it (model.BLOCKS)
# what the block's report() counts since the session was built; the rest
# of it is constant
COUNTED = ("decode_steps", "prefill_chunks", "rows_valid", "rows_padded",
           "prefills_from_zero", "prefills_carried")


def _not_trained(*_args, **_kwargs):
    raise ManifestError(
        "family granite_hybrid_lm is served and not yet trained: Module.fit "
        "has no recurrent layer with a backward (ROADMAP M4)")


symbol = batches = items_per_row = grad_scale = _not_trained
train_flops_per_item = output_bytes_per_row = _not_trained


def model_config(cfg):
    """The configuration as keyword arguments of ``serve.ModelConfig``."""
    return dict(
        block=BLOCK, vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_len=cfg["max_position_embeddings"],
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        d_ff=cfg["shared_intermediate_size"],
        layer_types=tuple(cfg["layer_types"]),
        mamba_n_heads=cfg["mamba_n_heads"], mamba_d_head=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"],
        mamba_n_groups=cfg["mamba_n_groups"],
        mamba_d_conv=cfg["mamba_d_conv"],
        mamba_chunk_size=cfg["mamba_chunk_size"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        tie_word_embeddings=bool(cfg["tie_word_embeddings"]))


def published_init(params, cfg):
    """``params`` (what ``weights.maker`` made, for the program and for
    the reference alike) with the Mamba-2 leaves as the published Mamba-2
    initialisation sets them, made deterministic: per head ``A`` a ladder
    from 1 to 16, ``dt_bias = softplus^-1(dt)`` with ``dt`` log-spaced
    from 0.001 to 0.1 and laid across the heads in another order (head h
    takes rung 27 h mod heads), ``D`` one; the depthwise filter keeps its
    seeded normal draw at the variance of the published uniform
    (-1 / sqrt(taps), 1 / sqrt(taps)).  Pure: a function of its
    arguments."""
    heads, taps = cfg["mamba_n_heads"], cfg["mamba_d_conv"]
    rung = jnp.arange(heads, dtype=jnp.float32)
    last = max(heads - 1, 1)
    dt = 0.001 * 100.0 ** (((rung * 27) % heads) / last)
    out = dict(params)
    for i, kind in enumerate(cfg["layer_types"]):
        if kind != "mamba":
            continue
        p = "blk%d_" % i
        out[p + "A_log"] = jnp.log(1.0 + 15.0 * rung / last)
        out[p + "dt_bias"] = jnp.log(jnp.expm1(dt))
        out[p + "D"] = jnp.ones((heads,), jnp.float32)
        out[p + "conv_weight"] = params[p + "conv_weight"] * (
            (3.0 * taps) ** -0.5 / cfg["init_std"])
    return out


def _layers(cfg):
    mamba = cfg["layer_types"].count("mamba")
    return mamba, len(cfg["layer_types"]) - mamba


def _d_inner(cfg):
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def _conv_dim(cfg):
    return _d_inner(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def mamba_params(cfg):
    """One Mamba-2 mixer's matrices: W_in, W_out and the depthwise
    filter."""
    d, di, cd = cfg["hidden_size"], _d_inner(cfg), _conv_dim(cfg)
    return (di + cd + cfg["mamba_n_heads"]) * d + d * di \
        + cd * cfg["mamba_d_conv"]


def attention_params(cfg):
    """One attention mixer's matrices: W_q, W_k, W_v, W_o."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return 2 * d * d + 2 * cfg["num_key_value_heads"] * (d // h) * d


def mlp_params(cfg):
    return 3 * cfg["shared_intermediate_size"] * cfg["hidden_size"]


def head_params(cfg):
    return cfg["vocab_size"] * cfg["hidden_size"]


def matmul_params(cfg):
    """Every matrix a token passes through, the tied head left out."""
    mamba, attention = _layers(cfg)
    return mamba * mamba_params(cfg) + attention * attention_params(cfg) \
        + (mamba + attention) * mlp_params(cfg)


def n_params(cfg):
    """Every parameter of the model as the program holds it: the tied
    embedding once, norm scales, the convolution's bias and the three
    per-head vectors of a Mamba-2 layer included."""
    d = cfg["hidden_size"]
    mamba, attention = _layers(cfg)
    small = (mamba + attention) * 2 * d + d + mamba * (
        _conv_dim(cfg) + 3 * cfg["mamba_n_heads"] + _d_inner(cfg))
    return head_params(cfg) + matmul_params(cfg) + small


def state_values_per_slot(cfg):
    """Values a slot holds in ONE Mamba-2 layer: the state and the
    convolution's carried rows."""
    return _d_inner(cfg) * cfg["mamba_d_state"] \
        + (cfg["mamba_d_conv"] - 1) * _conv_dim(cfg)


def kv_values_per_token(cfg):
    """Values a token holds in ONE attention layer: K and V."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return 2 * cfg["num_key_value_heads"] * (d // h)


def decode_least_bytes(cfg, live_slots, live_rows, weight_bytes=4,
                       cache_bytes=4):
    """Least bytes one decode step must move: every matmul weight once
    and the tied head once (the embedding is a look-up of one row a slot
    and is left out), each live slot's state and convolution rows read
    and written in every Mamba-2 layer, and the live K/V rows of every
    slot's context (``live_rows``: tokens, summed over the slots) read in
    every attention layer."""
    mamba, attention = _layers(cfg)
    return (matmul_params(cfg) + head_params(cfg)) * weight_bytes \
        + 2 * live_slots * mamba * state_values_per_slot(cfg) * cache_bytes \
        + live_rows * attention * kv_values_per_token(cfg) * cache_bytes


def scan_flops(cfg, tokens):
    """The chunked scan's own products in ONE Mamba-2 layer: within each
    chunk of q rows the q (q + 1) / 2 causal pairs of ``C B^T`` (a group)
    and of its product with ``dt x`` (a head), then a chunk's addition to
    the state and the entering state's part in its rows."""
    chunk = cfg["mamba_chunk_size"]
    whole, rest = divmod(tokens, chunk)
    pairs = whole * chunk * (chunk + 1) // 2 + rest * (rest + 1) // 2
    heads, width, state = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                           cfg["mamba_d_state"])
    return 2 * pairs * (cfg["mamba_n_groups"] * state + heads * width) \
        + 4 * tokens * heads * width * state


def prefill_flops(cfg, tokens, offset=0):
    """Operations the prefill of ``tokens`` prompt tokens from position
    ``offset`` needs: 2 per matmul parameter per token; the chunked scan's
    own products in every Mamba-2 layer; causal attention in the attention
    layers, a token at position p against p + 1 keys over heads of
    ``hidden_size / num_attention_heads`` (scores and values); the head
    for the last token only, which is all a prefill returns."""
    mamba, attention = _layers(cfg)
    keys = tokens * offset + tokens * (tokens + 1) // 2
    return 2 * tokens * matmul_params(cfg) + mamba * scan_flops(cfg, tokens) \
        + attention * keys * 4 * cfg["hidden_size"] + 2 * head_params(cfg)


def state_bytes_per_slot(cfg, cache_bytes=4):
    return _layers(cfg)[0] * state_values_per_slot(cfg) * cache_bytes
