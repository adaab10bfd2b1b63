"""Family ``bailing_hybrid_lm``: the BailingHybrid decoder (KDA
linear-attention layers, a gated latent-attention layer at every
``layer_group_size``-th place, group-routed experts of which a chip holds a
share) that ``mxnet_tpu/serve/bailing_hybrid.py`` serves.  A configuration's
keys are the published ``config.json``'s; ``num_experts`` and ``vocab_size``
count what is HELD, with ``router_experts`` (the router's published width),
``experts_first`` and ``layers_kept`` beside them.

This family is **served and not yet trained**: ``Module.fit`` has neither a
recurrent layer nor an expert layer with a backward (ROADMAP M1, M4), so
the names a training job asks for raise ``ManifestError`` and nothing
stands in for them.  What a serving job asks for: ``reference`` (the plain
forward), ``model_config`` (the architecture as the program's public
``serve.ModelConfig`` takes it), ``published_init`` (the KDA leaves that
``weights.py``'s rules by name would set to values under which the state
does nothing) and the counts of work under its two roofline metrics.
"""
import jax.numpy as jnp

from manifest import ManifestError
from references import bailing_hybrid_lm as reference

BLOCK = "bailing_hybrid"        # the program's name for it (model.BLOCKS)
# what the block's report() counts since the session was built; the rest
# of it is constant
COUNTED = ("decode_steps", "prefill_chunks", "assignments_asked",
           "assignments_held", "assignments_computed",
           "distinct_held_experts", "rows_without_held_expert",
           "state_slot_layers")


def _not_trained(*_args, **_kwargs):
    raise ManifestError(
        "family bailing_hybrid_lm is served and not yet trained: Module.fit "
        "has no recurrent or expert layer with a backward (ROADMAP M1, M4)")


symbol = batches = items_per_row = grad_scale = _not_trained
train_flops_per_item = output_bytes_per_row = _not_trained


def model_config(cfg):
    """The configuration as keyword arguments of ``serve.ModelConfig``."""
    if cfg["moe_shared_expert_intermediate_size"] \
            != cfg["moe_intermediate_size"]:
        raise ManifestError("the program's shared expert is n_shared_experts "
                            "x moe_intermediate_size wide")
    first, count, routed = reference.held(cfg)
    return dict(
        block=BLOCK, vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        max_len=cfg["max_position_embeddings"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        d_ff=cfg["intermediate_size"],
        first_k_dense=cfg["first_k_dense_replace"],
        moe_d_ff=cfg["moe_intermediate_size"], n_routed_experts=routed,
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["num_shared_experts"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        experts_held=(first, count) if count < routed else (),
        layer_types=tuple(reference.layer_types(cfg)),
        kda_n_heads=cfg["num_kv_heads_for_linear_attn"],
        kda_head_dim=cfg["head_dim"],
        kda_d_conv=cfg["short_conv_kernel_size"],
        kda_lower_bound=float(cfg["kda_lower_bound"]),
        kda_chunk_size=cfg["kda_chunk_size"])


def published_init(params, cfg):
    """``params`` (what ``weights.maker`` made, for the program and for
    the reference alike) with the KDA leaves set as the published KDA
    initialisation means them, made deterministic and carried over to the
    safe gate: per head a gate sharpness ``exp(A_log)`` log-spaced from
    0.5 to 2; per channel ``dt_bias`` such that at ``W_f u = 0`` the decay
    a token runs from 0.999 down to 0.2, log-spaced in ``1 - decay`` (the
    range the published ``A`` in 1..16 times ``dt`` in 0.001..0.1 gives the
    unbounded gate) and laid across a head's channels in another order
    (channel c takes rung 37 c mod D); the depthwise filter keeps its
    seeded normal draw at the variance of the published uniform
    (-1 / sqrt(taps), 1 / sqrt(taps)).  Pure: a function of its
    arguments."""
    h, w = cfg["num_attention_heads"], cfg["head_dim"]
    taps = cfg["short_conv_kernel_size"]
    sharp = 0.5 * 4.0 ** (jnp.arange(h, dtype=jnp.float32) / max(h - 1, 1))
    rung = ((jnp.arange(w) * 37) % w).astype(jnp.float32) / max(w - 1, 1)
    share = jnp.log1p(-0.001 * 800.0 ** rung) / cfg["kda_lower_bound"]
    bias = (jnp.log(share) - jnp.log1p(-share))[None, :] / sharp[:, None]
    out = dict(params)
    for i, kind in enumerate(reference.layer_types(cfg)):
        if kind != "kda":
            continue
        p = "blk%d_" % i
        out[p + "kda_A_log"] = jnp.log(sharp)
        out[p + "kda_dt_bias"] = bias.reshape(-1)
        out[p + "kda_conv_weight"] = params[p + "kda_conv_weight"] * (
            (3.0 * taps) ** -0.5 / cfg["init_std"])
    return out


def _layers(cfg):
    """-> (KDA layers, MLA layers, dense-FFN layers, expert layers)."""
    kinds = reference.layer_types(cfg)
    dense = cfg["first_k_dense_replace"]
    return kinds.count("kda"), kinds.count("mla"), dense, len(kinds) - dense


def _kda_width(cfg):
    return cfg["num_attention_heads"] * cfg["head_dim"]


def kda_params(cfg):
    """One KDA mixer's matrices: W_q, W_k, W_v, W_f, W_g, W_o, W_b and the
    depthwise filter."""
    d, hw = cfg["hidden_size"], _kda_width(cfg)
    return 6 * hw * d + cfg["num_attention_heads"] * d \
        + 3 * hw * cfg["short_conv_kernel_size"]


def mla_params(cfg):
    """One latent-attention mixer's matrices: W_q, W_kva, W_kvb, W_o and
    the gate a head."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    return h * (nope + rope) * d + (rank + rope) * d \
        + h * (nope + vd) * rank + d * h * vd + h * d


def expert_params(cfg):
    """One routed expert: gate, up and down."""
    return 3 * cfg["moe_intermediate_size"] * cfg["hidden_size"]


def shared_params(cfg):
    return 3 * cfg["num_shared_experts"] * cfg["hidden_size"] \
        * cfg["moe_shared_expert_intermediate_size"]


def router_params(cfg):
    return reference.held(cfg)[2] * cfg["hidden_size"]


def dense_ffn_params(cfg):
    return 3 * cfg["intermediate_size"] * cfg["hidden_size"]


def head_params(cfg):
    return cfg["vocab_size"] * cfg["hidden_size"]


def fixed_params(cfg):
    """Every matrix a token passes through whatever it is routed to, the
    head left out: the mixers, the dense FFN, shared experts, routers."""
    kda, mla, dense, moe = _layers(cfg)
    return kda * kda_params(cfg) + mla * mla_params(cfg) \
        + dense * dense_ffn_params(cfg) \
        + moe * (shared_params(cfg) + router_params(cfg))


def n_params(cfg):
    """Every parameter of the model as the program holds it (the share:
    the experts and the vocabulary rows held; untied head; norm scales, the
    router's selection bias and the KDA vectors included)."""
    d, h, w = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    kda, mla, _, moe = _layers(cfg)
    count, routed = reference.held(cfg)[1:]
    small = (kda + mla) * 2 * d + d + kda * (h * w + h + w) \
        + mla * cfg["kv_lora_rank"] + moe * routed
    return 2 * head_params(cfg) + fixed_params(cfg) + small \
        + moe * count * expert_params(cfg)


def state_values_per_slot(cfg):
    """Values a slot holds in ONE KDA layer: the state and the
    convolution's carried rows."""
    return _kda_width(cfg) * cfg["head_dim"] \
        + (cfg["short_conv_kernel_size"] - 1) * 3 * _kda_width(cfg)


def state_bytes_per_slot(cfg, cache_bytes=4):
    return _layers(cfg)[0] * state_values_per_slot(cfg) * cache_bytes


def latent_values_per_token(cfg):
    """Values a token holds in ONE latent layer."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def decode_least_bytes(cfg, distinct_experts, live_slots, live_rows,
                       weight_bytes=4, cache_bytes=4):
    """Least bytes one decode step must move: every matrix outside the
    routed experts once and the head once (the embedding is a look-up of
    one row a slot and is left out), the held experts that at least one
    row reached (``distinct_experts``: their sum over the expert layers,
    counted by the program's routers), each live slot's KDA state and
    convolution rows read and written in every KDA layer, and the live
    latent rows of every slot's context (``live_rows``: tokens, summed
    over the slots) read in every latent layer."""
    kda, mla, _, _ = _layers(cfg)
    weights = fixed_params(cfg) + head_params(cfg) \
        + distinct_experts * expert_params(cfg)
    return weights * weight_bytes \
        + 2 * live_slots * kda * state_values_per_slot(cfg) * cache_bytes \
        + live_rows * mla * latent_values_per_token(cfg) * cache_bytes


def chunk_flops(cfg, tokens):
    """The chunked form's own products in ONE KDA layer, a head of D x D:
    within each chunk of C rows the C (C + 1) / 2 causal pairs of ``K K^T``
    and ``Q K^T`` (over D each), of the unit-triangular solve (over the 2 D
    columns of its right-hand side) and of ``B U`` (over D), then the
    entering state's part in ``U`` and in the rows, and the chunk's
    addition to the state (three products of rows x D x D)."""
    chunk, w = cfg["kda_chunk_size"], cfg["head_dim"]
    whole, rest = divmod(tokens, chunk)
    pairs = whole * chunk * (chunk + 1) // 2 + rest * (rest + 1) // 2
    return cfg["num_attention_heads"] * (
        2 * pairs * (2 * w + 2 * w + w) + 3 * 2 * tokens * w * w)


def held_experts_per_token(cfg):
    """Assignments a token makes to the experts held here, in one expert
    layer, when the routing is balanced: its experts a token times the
    share held."""
    _, count, routed = reference.held(cfg)
    return cfg["num_experts_per_tok"] * count / routed


def active_params_per_token(cfg):
    """Matmul parameters one token passes through here, the head left out:
    everything outside the routed experts, and the held experts it takes
    under balanced routing."""
    return fixed_params(cfg) + _layers(cfg)[3] \
        * held_experts_per_token(cfg) * expert_params(cfg)


def prefill_flops(cfg, tokens, offset=0):
    """Operations the prefill of ``tokens`` prompt tokens from position
    ``offset`` needs: 2 per active matmul parameter per token; the chunked
    form's own products in every KDA layer; causal attention in the latent
    layers, a token at position p against p + 1 keys over heads of
    ``nope + rope`` (scores) and ``v_head_dim`` (values); the head for the
    last token only, which is all a prefill returns."""
    kda, mla, _, _ = _layers(cfg)
    keys = tokens * offset + tokens * (tokens + 1) // 2
    per_key = 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    return 2 * tokens * active_params_per_token(cfg) \
        + kda * chunk_flops(cfg, tokens) + mla * keys * per_key \
        + 2 * head_params(cfg)
