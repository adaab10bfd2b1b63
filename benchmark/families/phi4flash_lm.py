"""Family ``phi4flash_lm``: the SambaY decoder of Phi-4-mini-flash-reasoning
(Mamba-1 and window differential attention in the first half, one
full-attention layer whose pages every later cross-attention layer reads,
gated memory units on one Mamba layer's scan output, LayerNorm, no
positions, a tied head) that ``mxnet_tpu/serve/phi4flash.py`` serves.  A
configuration's keys are the published ``config.json``'s, with the Mamba-1
sizes its modelling file hard-codes (``mamba_d_state``, ``mamba_d_conv``,
``mamba_expand``, ``mamba_dt_rank``) and the layer list its rule gives
(``layer_types``) spelled out beside them.

This family is **served and not yet trained**: ``Module.fit`` has no
recurrent layer with a backward (ROADMAP M4), so the names a training job
asks for raise ``ManifestError`` and nothing stands in for them.  What a
serving job asks for: ``reference`` (the plain forward), ``model_config``
(the architecture as the program's public ``serve.ModelConfig`` takes
it), ``published_init`` (the Mamba-1 leaves and the ``lambda`` vectors,
which ``weights.py``'s rules by name would set to values under which the
state, or the subtraction, does nothing) and the counts of work under its
roofline metrics, which know five kinds of layer.
"""
import jax.numpy as jnp

from manifest import ManifestError
from references import phi4flash_lm as reference

BLOCK = "phi4flash"     # the program's name for it (model.BLOCKS)
# what the block's report() counts since the session was built; the rest
# of it is constant
COUNTED = ("decode_steps", "prefill_chunks", "rows_valid", "rows_padded",
           "cross_rows", "prefills_from_zero", "prefills_carried",
           "window_rows_in_band", "shared_rows_read")
# elementwise operations a (channel, state) pair a token of the scan: the
# decay's product and its exp, its product with the state, the input's
# product with B, the sum, the product with C and the sum of the read-out
SCAN_OPS = 7


def _not_trained(*_args, **_kwargs):
    raise ManifestError(
        "family phi4flash_lm is served and not yet trained: Module.fit has "
        "no recurrent layer with a backward (ROADMAP M4)")


symbol = batches = items_per_row = grad_scale = _not_trained
train_flops_per_item = output_bytes_per_row = _not_trained


def layer_rule(n):
    """The modelling file's rule at ``n`` layers, as ``layer_types``."""
    half = n // 2
    return [("mamba" if i <= half else "gmu") if i % 2 == 0 else
            "sliding_attention" if i < half else
            "full_attention" if i == half + 1 else "cross_attention"
            for i in range(n)]


def model_config(cfg):
    """The configuration as keyword arguments of ``serve.ModelConfig``,
    which counts differential heads: pairs of the published ones."""
    unserved = [key for key, served in (
        ("tie_word_embeddings", True), ("mlp_bias", False),
        ("lm_head_bias", False), ("hidden_act", "silu"))
        if cfg.get(key, served) != served]
    if unserved or list(cfg["layer_types"]) != layer_rule(
            cfg["num_hidden_layers"]):
        raise ManifestError(
            "the program's phi4flash block does not serve %s, and "
            "layer_types is the rule's list at num_hidden_layers"
            % (unserved or "this"))
    return dict(
        block=BLOCK, vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"] // 2,
        num_key_value_heads=cfg["num_key_value_heads"] // 2,
        max_len=cfg["max_position_embeddings"],
        d_ff=cfg["intermediate_size"],
        layer_types=tuple(cfg["layer_types"]),
        sliding_window=cfg["sliding_window"],
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_expand=cfg["mamba_expand"], mamba_dt_rank=cfg["mamba_dt_rank"],
        layer_norm_eps=float(cfg["layer_norm_eps"]),
        rms_norm_eps=float(cfg["layer_norm_eps"]),
        tie_word_embeddings=True)


def published_init(params, cfg):
    """``params`` (what ``weights.maker`` made, for the program and for the
    reference alike) with the Mamba-1 leaves as the published Mamba-1
    initialisation sets them, made deterministic: in every channel ``A`` a
    ladder from 1 to ``mamba_d_state``; ``dt_bias = softplus^-1(dt)`` with
    ``dt`` log-spaced from 0.001 to 0.1 and laid across the channels in
    another order (channel c takes rung 27 c mod d_inner); ``D`` one; the
    depthwise filter and ``W_dt`` keep their seeded normal draws at the
    variances of the published uniforms (+- 1 / sqrt(taps), +- 1 /
    sqrt(dt_rank)); the four ``lambda`` vectors of an attention layer keep
    theirs at the published 0.1.  Pure: a function of its arguments."""
    d, n = cfg["hidden_size"], cfg["mamba_d_state"]
    di, taps, rank = cfg["mamba_expand"] * d, cfg["mamba_d_conv"], \
        cfg["mamba_dt_rank"]
    std = cfg["init_std"]
    rung = jnp.arange(di, dtype=jnp.float32)
    dt = 0.001 * 100.0 ** (((rung * 27) % di) / max(di - 1, 1))
    out = dict(params)
    for i, kind in enumerate(cfg["layer_types"]):
        p = "blk%d_" % i
        if kind == "mamba":
            out[p + "A_log"] = jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (di, n))
            out[p + "dt_bias"] = jnp.log(jnp.expm1(dt))
            out[p + "D"] = jnp.ones((di,), jnp.float32)
            out[p + "conv_weight"] = params[p + "conv_weight"] * (
                (3.0 * taps) ** -0.5 / std)
            out[p + "dt_weight"] = params[p + "dt_weight"] * (
                (3.0 * rank) ** -0.5 / std)
        elif kind != "gmu":
            for leaf in ("q1", "k1", "q2", "k2"):
                out[p + "lambda_" + leaf] = params[p + "lambda_" + leaf] * (
                    0.1 / std)
    return out


def _count(cfg, *kinds):
    return sum(cfg["layer_types"].count(k) for k in kinds)


def _d_inner(cfg):
    return cfg["mamba_expand"] * cfg["hidden_size"]


def _kv_values(cfg):
    """Values a token holds in ONE attention layer's cache: K and V."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return 2 * cfg["num_key_value_heads"] * (d // h)


def mlp_params(cfg):
    return 3 * cfg["intermediate_size"] * cfg["hidden_size"]


def mamba_params(cfg):
    """One Mamba-1 mixer's matrices: W_in, the depthwise filter, W_x, W_dt,
    A and W_out."""
    d, di = cfg["hidden_size"], _d_inner(cfg)
    r, n = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
    return 2 * di * d + di * cfg["mamba_d_conv"] + (r + 2 * n) * di \
        + di * r + di * n + d * di


def attention_params(cfg):
    """One self-attention mixer's matrices: W_qkv and W_o."""
    d = cfg["hidden_size"]
    return (d + _kv_values(cfg)) * d + d * d


def gmu_params(cfg):
    return 2 * _d_inner(cfg) * cfg["hidden_size"]


def cross_params(cfg):
    return 2 * cfg["hidden_size"] ** 2


def head_params(cfg):
    return cfg["vocab_size"] * cfg["hidden_size"]


def matmul_params(cfg):
    """Every matrix a token passes through in a decode step, the tied head
    left out."""
    return _count(cfg, "mamba") * mamba_params(cfg) \
        + _count(cfg, "sliding_attention", "full_attention") \
        * attention_params(cfg) + _count(cfg, "gmu") * gmu_params(cfg) \
        + _count(cfg, "cross_attention") * cross_params(cfg) \
        + len(cfg["layer_types"]) * mlp_params(cfg)


def n_params(cfg):
    """Every parameter of the model as the program holds it: the tied
    embedding once; the norms' scales and biases, the projections' biases,
    the ``lambda`` vectors and the sub-layer norm's scale, and the three
    per-channel vectors of a Mamba layer included."""
    d, di = cfg["hidden_size"], _d_inner(cfg)
    hd = d // cfg["num_attention_heads"]
    layers = len(cfg["layer_types"])
    small = layers * 4 * d + 2 * d + _count(cfg, "mamba") * 3 * di \
        + _count(cfg, "sliding_attention", "full_attention") * (
            d + _kv_values(cfg)) + _count(cfg, "cross_attention") * d \
        + _count(cfg, "sliding_attention", "full_attention",
                 "cross_attention") * (d + 6 * hd)
    return head_params(cfg) + matmul_params(cfg) + small


def state_values_per_slot(cfg):
    """Values a slot holds in ONE Mamba layer: the state and the
    convolution's carried rows."""
    return _d_inner(cfg) * (cfg["mamba_d_state"] + cfg["mamba_d_conv"] - 1)


def state_bytes_per_slot(cfg, cache_bytes=4):
    return _count(cfg, "mamba") * state_values_per_slot(cfg) * cache_bytes


def ring_bytes_per_slot(cfg, cache_bytes=4):
    """A slot's rings: ``sliding_window`` rows of K and V a window layer."""
    return _count(cfg, "sliding_attention") * cfg["sliding_window"] \
        * _kv_values(cfg) * cache_bytes


def page_bytes_per_token(cfg, cache_bytes=4):
    """A token's K and V in the ONE layer that owns pages."""
    return _count(cfg, "full_attention") * _kv_values(cfg) * cache_bytes


def decode_least_bytes(cfg, live_slots, live_rows, band_rows=None,
                       weight_bytes=4, cache_bytes=4):
    """Least bytes one decode step must move: every matmul weight once and
    the tied head once (the embedding is a look-up of one row a slot and is
    left out); each live slot's state and convolution rows read and
    written in every Mamba layer; the rows inside the band (``band_rows``:
    min(a slot's context, the window), summed over the slots; at most
    ``live_rows`` and ``live_slots`` windows where it is not given) read in
    every window layer; and the live rows of every slot's context
    (``live_rows``: tokens, summed over the slots) read in the layer that
    owns the pages ONCE FOR EACH of its readers, itself and every
    cross-attention layer: nothing keeps a page on the chip from one layer
    to the next."""
    if band_rows is None:
        band_rows = min(live_rows, live_slots * cfg["sliding_window"])
    readers = _count(cfg, "full_attention", "cross_attention")
    return (matmul_params(cfg) + head_params(cfg)) * weight_bytes \
        + 2 * live_slots * _count(cfg, "mamba") * state_values_per_slot(cfg) \
        * cache_bytes \
        + band_rows * _count(cfg, "sliding_attention") * _kv_values(cfg) \
        * cache_bytes \
        + live_rows * readers * _kv_values(cfg) * cache_bytes


def scan_flops(cfg, tokens):
    """The selective scan's own elementwise operations in ONE Mamba layer,
    whatever implements it: :data:`SCAN_OPS` a (channel, state) pair a
    token."""
    return SCAN_OPS * tokens * _d_inner(cfg) * cfg["mamba_d_state"]


def scan_least_bytes(cfg, tokens, cache_bytes=4):
    """Least bytes ONE Mamba layer's scan of ``tokens`` rows must move: x
    and dt read and y written a row, B and C a row, the state read and
    written once."""
    di, n = _d_inner(cfg), cfg["mamba_d_state"]
    return (tokens * (3 * di + 2 * n) + 2 * di * n) * cache_bytes


def attention_flops_per_key(cfg):
    """Score and value products a token spends on ONE key in ONE
    differential attention layer: two score products a pair over the
    published head width (the identity's zeros are not counted) and two
    value products a pair over a value of twice that width: 6 d."""
    return 6 * cfg["hidden_size"]


def prefill_flops(cfg, tokens, offset=0):
    """Operations the prefill of ``tokens`` prompt tokens from position
    ``offset`` needs.  Over every token: 2 per matmul parameter of the
    layers up to the memory layer and of the owner's K and V projection;
    the scan in every Mamba layer; window attention in the window layers, a
    token at position p against min(p + 1, window) keys.  Over ONE token,
    the last: the owner's query and output projections and its MLP, every
    gated memory unit and cross-attention layer with its MLP, each reader's
    attention against all ``offset + tokens`` keys, and the head."""
    d = cfg["hidden_size"]
    first_half = _count(cfg, "mamba") * (mamba_params(cfg) + mlp_params(cfg)) \
        + _count(cfg, "sliding_attention") * (attention_params(cfg)
                                              + mlp_params(cfg)) \
        + _count(cfg, "full_attention") * _kv_values(cfg) * d
    second_half = _count(cfg, "full_attention") * (2 * d * d
                                                   + mlp_params(cfg)) \
        + _count(cfg, "gmu") * (gmu_params(cfg) + mlp_params(cfg)) \
        + _count(cfg, "cross_attention") * (cross_params(cfg)
                                            + mlp_params(cfg))
    window = cfg["sliding_window"]
    keys = sum(min(offset + j + 1, window) for j in range(tokens))
    readers = _count(cfg, "full_attention", "cross_attention")
    return 2 * tokens * first_half + 2 * second_half \
        + _count(cfg, "mamba") * scan_flops(cfg, tokens) \
        + _count(cfg, "sliding_attention") * keys \
        * attention_flops_per_key(cfg) \
        + readers * (offset + tokens) * attention_flops_per_key(cfg) \
        + 2 * head_params(cfg)
