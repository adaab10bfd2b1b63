"""Family ``qwen3_next_lm``: the Qwen3-Next decoder (Gated DeltaNet layers,
a gated grouped-query attention layer at every ``full_attention_interval``
-th place, softmax-routed experts of which a chip holds a share beside a
gated shared expert) that ``mxnet_tpu/serve/qwen3_next.py`` serves.  A
configuration's keys are the published ``config.json``'s; ``num_experts``
and ``vocab_size`` count what is HELD, with ``router_experts`` (the
router's published width), ``experts_first`` and ``layers_kept`` beside
them.

This family is **served and not yet trained**: ``Module.fit`` has neither a
recurrent layer nor an expert layer with a backward (ROADMAP M1, M4), so
the names a training job asks for raise ``ManifestError`` and nothing
stands in for them.  What a serving job asks for: ``reference`` (the plain
forward), ``model_config`` (the architecture as the program's public
``serve.ModelConfig`` takes it), ``published_init`` (the leaves that
``weights.py``'s rules by name would set to values under which the state
does nothing, and the zero-centred norms' ``w``) and the counts of work
under its two roofline metrics.  Should the paged readers become kernels
of their own name, :func:`kv_bytes_per_token` and :func:`attention_flops`
say what each must move.
"""
import jax.numpy as jnp

from manifest import ManifestError
from references import qwen3_next_lm as reference

BLOCK = "qwen3_next"        # the program's name for it (model.BLOCKS)
# what the block's report() counts since the session was built; the rest
# of it is constant
COUNTED = ("decode_steps", "prefill_chunks", "assignments_asked",
           "assignments_held", "assignments_computed",
           "distinct_held_experts", "rows_without_held_expert",
           "state_slot_layers", "prefills_from_zero", "prefills_carried",
           "full_rows_live")


def _not_trained(*_args, **_kwargs):
    raise ManifestError(
        "family qwen3_next_lm is served and not yet trained: Module.fit "
        "has no recurrent or expert layer with a backward (ROADMAP M1, M4)")


symbol = batches = items_per_row = grad_scale = _not_trained
train_flops_per_item = output_bytes_per_row = _not_trained


def model_config(cfg):
    """The configuration as keyword arguments of ``serve.ModelConfig``."""
    if cfg["shared_expert_intermediate_size"] != cfg["moe_intermediate_size"]:
        raise ManifestError("the program's shared expert is n_shared_experts "
                            "x moe_intermediate_size wide")
    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"]:
        raise ManifestError("the program routes every layer")
    first, count, routed = reference.held(cfg)
    return dict(
        block=BLOCK, vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        attn_head_dim=cfg["head_dim"],
        max_len=cfg["max_position_embeddings"],
        partial_rotary_factor=float(cfg["partial_rotary_factor"]),
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        moe_d_ff=cfg["moe_intermediate_size"], n_routed_experts=routed,
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=1, shared_expert_gate=True, scoring_func="softmax",
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        experts_held=(first, count) if count < routed else (),
        tie_word_embeddings=bool(cfg["tie_word_embeddings"]),
        layer_types=tuple(reference.layer_types(cfg)),
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        gdn_chunk_size=cfg["gdn_chunk_size"])


def published_init(params, cfg):
    """``params`` (what ``weights.maker`` made, for the program and for
    the reference alike) with the leaves the rules by name get wrong set
    as the configuration's ``assumed`` group says: every zero-centred
    norm's ``w`` (``*_norm_weight``) zero, the published start; ``dt_bias``
    the published 1 and ``A_log`` such that at ``a = 0`` the 32 heads'
    decays a token run from 0.999 down to 0.2, log-spaced in ``1 - decay``
    (the published ``A`` uniform in (0, 16) forgets a state within a
    token); the depthwise filter keeps its seeded normal draw at the
    variance of the published uniform (-1 / sqrt(taps), 1 / sqrt(taps)).
    Pure: a function of its arguments."""
    hv, taps = cfg["linear_num_value_heads"], cfg["linear_conv_kernel_dim"]
    decay = 1.0 - 0.001 * 800.0 ** (
        jnp.arange(hv, dtype=jnp.float32) / max(hv - 1, 1))
    a_log = jnp.log(-jnp.log(decay) / jnp.log1p(jnp.e))
    out = {}
    for name, leaf in params.items():
        if name.endswith("_norm_weight"):
            leaf = jnp.zeros_like(leaf)
        elif name.endswith("gdn_A_log"):
            leaf = a_log
        elif name.endswith("gdn_dt_bias"):
            leaf = jnp.ones_like(leaf)
        elif name.endswith("gdn_conv_weight"):
            leaf = leaf * ((3.0 * taps) ** -0.5 / cfg["init_std"])
        out[name] = leaf
    return out


def _layers(cfg):
    """-> (Gated DeltaNet layers, attention layers); every layer routes."""
    kinds = reference.layer_types(cfg)
    return kinds.count("linear_attention"), kinds.count("full_attention")


def gdn_params(cfg):
    """One Gated DeltaNet mixer's matrices: W_qkvz, W_ba, W_o and the
    depthwise filter."""
    d = cfg["hidden_size"]
    hk, hv, dk, dv = reference.gdn_dims(cfg)
    conv = 2 * hk * dk + hv * dv
    return (conv + hv * dv) * d + 2 * hv * d + hv * dv * d \
        + conv * cfg["linear_conv_kernel_dim"]


def attn_params(cfg):
    """One gated attention mixer's matrices: W_q (query and gate), W_k,
    W_v, W_o."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (2 * h + 2 * kv) * hd * d + h * hd * d


def expert_params(cfg):
    """One routed expert: gate, up and down."""
    return 3 * cfg["moe_intermediate_size"] * cfg["hidden_size"]


def shared_params(cfg):
    """The shared expert and its gate's row."""
    return (3 * cfg["shared_expert_intermediate_size"] + 1) \
        * cfg["hidden_size"]


def router_params(cfg):
    return reference.held(cfg)[2] * cfg["hidden_size"]


def head_params(cfg):
    return cfg["vocab_size"] * cfg["hidden_size"]


def fixed_params(cfg):
    """Every matrix a token passes through whatever it is routed to, the
    head left out: the mixers, shared experts, routers."""
    gdn, attn = _layers(cfg)
    return gdn * gdn_params(cfg) + attn * attn_params(cfg) \
        + (gdn + attn) * (shared_params(cfg) + router_params(cfg))


def n_params(cfg):
    """Every parameter of the model as the program holds it (the share:
    the experts and the vocabulary rows held; untied head; the norms' and
    the decays' vectors included)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    gdn, attn = _layers(cfg)
    small = (gdn + attn) * 2 * d + d + gdn * (2 * hv + dv) + attn * 2 * hd
    return 2 * head_params(cfg) + fixed_params(cfg) + small \
        + (gdn + attn) * reference.held(cfg)[1] * expert_params(cfg)


def state_values_per_slot(cfg):
    """Values a slot holds in ONE DeltaNet layer: the state and the
    convolution's carried rows."""
    hk, hv, dk, dv = reference.gdn_dims(cfg)
    return hv * dk * dv \
        + (cfg["linear_conv_kernel_dim"] - 1) * (2 * hk * dk + hv * dv)


def state_bytes_per_slot(cfg, cache_bytes=4):
    return _layers(cfg)[0] * state_values_per_slot(cfg) * cache_bytes


def kv_values_per_token(cfg):
    """Values a token holds in ONE attention layer: its key and its value
    heads."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def kv_bytes_per_token(cfg, cache_bytes=4):
    return _layers(cfg)[1] * kv_values_per_token(cfg) * cache_bytes


def decode_least_bytes(cfg, distinct_experts, live_slots, live_rows,
                       weight_bytes=4, cache_bytes=4):
    """Least bytes one decode step must move: every matrix outside the
    routed experts once and the head once (the embedding is a look-up of
    one row a slot and is left out), the held experts that at least one
    row reached (``distinct_experts``: their sum over the expert layers,
    counted by the program's routers), each live slot's state and
    convolution rows read and written in every DeltaNet layer, and the
    live K/V rows of every slot's context (``live_rows``: tokens, summed
    over the slots) read in every attention layer."""
    gdn, attn = _layers(cfg)
    weights = fixed_params(cfg) + head_params(cfg) \
        + distinct_experts * expert_params(cfg)
    return weights * weight_bytes \
        + 2 * live_slots * gdn * state_values_per_slot(cfg) * cache_bytes \
        + live_rows * attn * kv_values_per_token(cfg) * cache_bytes


def chunk_flops(cfg, tokens):
    """The chunked form's own products in ONE DeltaNet layer, a value head
    of Dk x Dv: within each chunk of C rows the C (C + 1) / 2 causal pairs
    of ``K K^T`` and ``Q K^T`` (over Dk each), of the unit-triangular
    solve (over the Dk + Dv columns of its right-hand side) and of ``B U``
    (over Dv), then the entering state's part in ``U`` and in the rows,
    and the chunk's addition to the state (three products of rows x Dk x
    Dv)."""
    chunk = cfg["gdn_chunk_size"]
    _, hv, dk, dv = reference.gdn_dims(cfg)
    whole, rest = divmod(tokens, chunk)
    pairs = whole * chunk * (chunk + 1) // 2 + rest * (rest + 1) // 2
    return hv * (2 * pairs * (2 * dk + (dk + dv) + dv)
                 + 3 * 2 * tokens * dk * dv)


def attention_flops(cfg, tokens):
    """Causal attention over a whole prompt of ``tokens`` tokens in ONE
    attention layer, in however many chunks it is fed: a token at position
    p against p + 1 keys, heads of ``head_dim`` for scores and as much for
    values."""
    keys = tokens * (tokens + 1) // 2
    return keys * 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]


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
    return fixed_params(cfg) + sum(_layers(cfg)) \
        * held_experts_per_token(cfg) * expert_params(cfg)


def prefill_flops(cfg, tokens):
    """Operations the prefill of a whole prompt of ``tokens`` tokens needs,
    in however many chunks the program feeds it: 2 per active matmul
    parameter per token; the chunked form's own products in every
    DeltaNet layer; causal attention in the attention layers; the head
    for the last token only, which is all a prefill returns."""
    gdn, attn = _layers(cfg)
    return 2 * tokens * active_params_per_token(cfg) \
        + gdn * chunk_flops(cfg, tokens) \
        + attn * attention_flops(cfg, tokens) + 2 * head_params(cfg)
