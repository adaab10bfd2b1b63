"""Family ``lfm2_moe_lm``: the LFM2-MoE decoder (double-gated short
convolutions and QK-normed grouped-query attention layers in the published
``layer_types`` order, leading dense layers, sigmoid-routed experts with a
selection bias and no shared expert of which a chip holds a share, a tied
head) that ``mxnet_tpu/serve/lfm2_moe.py`` serves.  A configuration's keys
are the published ``config.json``'s; ``num_experts`` and ``vocab_size``
count what is HELD, with ``router_experts`` (the router's published width),
``experts_first`` and ``layers_kept`` beside them; ``layer_types`` stays the
published list and is read at the places ``layers_kept`` names.

This family is **served and not yet trained**: ``Module.fit`` has neither a
short-convolution layer nor an expert layer with a backward (ROADMAP M0,
M1), so the names a training job asks for raise ``ManifestError`` and
nothing stands in for them.  What a serving job asks for: ``reference``
(the plain forward), ``model_config`` (the architecture as the program's
public ``serve.ModelConfig`` takes it), ``published_init`` (the depthwise
filters, which ``weights.py``'s rule by name would draw at 0.02) and the
counts of work under its two roofline metrics, which know three kinds of
layer.
"""
from manifest import ManifestError
from references import lfm2_moe_lm as reference

BLOCK = "lfm2_moe"      # the program's name for it (model.BLOCKS)
# what the block's report() counts since the session was built; the rest
# of it is constant
COUNTED = ("assignments_asked", "assignments_held", "assignments_computed",
           "distinct_held_experts", "rows_without_held_expert",
           "decode_steps", "prefill_chunks", "prefill_chunks_continued",
           "window_rows_visited", "window_rows_in_band", "full_rows_live",
           "prefills_from_zero", "prefills_carried", "rows_valid",
           "rows_padded")


def _not_trained(*_args, **_kwargs):
    raise ManifestError(
        "family lfm2_moe_lm is served and not yet trained: Module.fit has "
        "no short-convolution or expert layer with a backward (ROADMAP M0, "
        "M1)")


symbol = batches = items_per_row = grad_scale = _not_trained
train_flops_per_item = output_bytes_per_row = _not_trained


def model_config(cfg):
    """The configuration as keyword arguments of ``serve.ModelConfig``."""
    unserved = [key for key, served in (
        ("conv_bias", False), ("use_expert_bias", True),
        ("tie_word_embeddings", True), ("norm_topk_prob", True))
        if cfg.get(key, served) != served]
    if cfg["rope_parameters"].get("rope_type", "default") != "default":
        unserved.append("rope_type")
    if unserved or len(reference.kept(cfg)) != cfg["num_hidden_layers"]:
        raise ManifestError(
            "the program's lfm2_moe block does not serve %s, and "
            "num_hidden_layers counts layers_kept" % (unserved or "this"))
    first, count, routed = reference.held(cfg)
    return dict(
        block=BLOCK, vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_len=cfg["max_position_embeddings"],
        attn_head_dim=reference.head_dim(cfg),
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        rms_norm_eps=float(cfg["norm_eps"]),
        layer_types=tuple(reference.layer_types(cfg)),
        conv_L_cache=cfg["conv_L_cache"], d_ff=cfg["intermediate_size"],
        first_k_dense=cfg["num_dense_layers"],
        moe_d_ff=cfg["moe_intermediate_size"], n_routed_experts=routed,
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=True, scoring_func="sigmoid",
        tie_word_embeddings=True,
        experts_held=(first, count) if count < routed else ())


def published_init(params, cfg):
    """``params`` (what ``weights.maker`` made, for the program and for the
    reference alike) with each depthwise filter's seeded normal draw at the
    variance of PyTorch's ``Conv1d`` default for a fan-in of
    ``conv_L_cache`` (uniform on +-1 / sqrt(taps): 1 / (3 taps)) and not
    at ``init_std``: at 0.02 a wrong or zeroed convolution context moves a
    logit by less than rounding and no check could tell it.  Pure: a
    function of its arguments."""
    gain = (3.0 * cfg["conv_L_cache"]) ** -0.5 / cfg["init_std"]
    return {name: leaf * gain if name.endswith("conv_weight") else leaf
            for name, leaf in params.items()}


def _layers(cfg):
    """-> (convolution layers, attention layers, dense-FFN layers, expert
    layers)."""
    kinds = reference.layer_types(cfg)
    dense = sum(reference.layer_dense(cfg))
    conv = kinds.count("conv")
    return conv, len(kinds) - conv, dense, len(kinds) - dense


def conv_params(cfg):
    """One short-convolution mixer: W_in, W_out and the depthwise
    filter."""
    d = cfg["hidden_size"]
    return 3 * d * d + d * d + d * cfg["conv_L_cache"]


def attention_params(cfg):
    """One attention mixer: W_q, W_o, W_k, W_v and the two norms' scale
    vectors."""
    d, hd = cfg["hidden_size"], reference.head_dim(cfg)
    return 2 * cfg["num_attention_heads"] * hd * d \
        + 2 * cfg["num_key_value_heads"] * hd * d + 2 * hd


def expert_params(cfg):
    """One routed expert: gate, up and down."""
    return 3 * cfg["moe_intermediate_size"] * cfg["hidden_size"]


def router_params(cfg):
    """A router's matrix and its selection bias."""
    return reference.held(cfg)[2] * (cfg["hidden_size"] + 1)


def dense_ffn_params(cfg):
    return 3 * cfg["intermediate_size"] * cfg["hidden_size"]


def head_params(cfg):
    """The tied matrix's slice: the embedding and the head, once."""
    return cfg["vocab_size"] * cfg["hidden_size"]


def fixed_params(cfg):
    """Every matrix a token passes through whatever it is routed to, the
    head left out: the mixers, the dense FFN, the routers."""
    conv, attn, dense, moe = _layers(cfg)
    return conv * conv_params(cfg) + attn * attention_params(cfg) \
        + dense * dense_ffn_params(cfg) + moe * router_params(cfg)


def n_params(cfg):
    """Every parameter of the model as the program holds it (the share:
    the experts and the vocabulary rows held; the tied matrix once; norm
    scales included)."""
    conv, attn, _, moe = _layers(cfg)
    norms = (2 * (conv + attn) + 1) * cfg["hidden_size"]
    return head_params(cfg) + fixed_params(cfg) + norms \
        + moe * reference.held(cfg)[1] * expert_params(cfg)


def kv_values_per_token(cfg):
    """Values a token holds in ONE attention layer: its key/value heads'
    keys and values."""
    return 2 * cfg["num_key_value_heads"] * reference.head_dim(cfg)


def conv_values_per_slot(cfg):
    """Values a slot holds in ONE convolution layer: the last
    ``conv_L_cache - 1`` rows of ``g``."""
    return (cfg["conv_L_cache"] - 1) * cfg["hidden_size"]


def decode_least_bytes(cfg, distinct_experts, live_rows, live_slots,
                       weight_bytes=4, cache_bytes=4):
    """Least bytes one decode step must move: every matrix outside the
    routed experts once and the head's slice once (the embedding is a
    look-up of one row a slot and is left out), the held experts that at
    least one row reached (``distinct_experts``: their sum over the expert
    layers, counted by the program's routers), the live K/V rows of every
    slot's context (``live_rows``: tokens, summed over the slots) read in
    every attention layer, and each live slot's convolution rows read and
    written in every convolution layer."""
    conv, attn, _, _ = _layers(cfg)
    weights = fixed_params(cfg) + head_params(cfg) \
        + distinct_experts * expert_params(cfg)
    return weights * weight_bytes + cache_bytes * (
        attn * live_rows * kv_values_per_token(cfg)
        + 2 * conv * live_slots * conv_values_per_slot(cfg))


def held_experts_per_token(cfg):
    """Assignments a token makes to the experts held here, in one expert
    layer, when the routing is balanced: its experts a token times the
    share held (half an expert at 8 of 64 and 4 a token)."""
    _, count, routed = reference.held(cfg)
    return cfg["num_experts_per_tok"] * count / routed


def active_params_per_token(cfg):
    """Matmul parameters one token passes through here, the head left out:
    everything outside the routed experts, and the held experts it takes
    under balanced routing."""
    return fixed_params(cfg) + _layers(cfg)[3] \
        * held_experts_per_token(cfg) * expert_params(cfg)


def causal_keys(tokens):
    """Keys the queries of a prompt of ``tokens`` see under the causal
    mask, summed: query i sees i + 1."""
    return tokens * (tokens + 1) // 2


def prefill_flops(cfg, tokens):
    """Operations the prefill of a whole prompt of ``tokens`` tokens needs,
    in however many chunks the program feeds it: 2 per active matmul
    parameter per token (the held experts' share of the assignments;
    the depthwise filter's taps are among the parameters, a multiply and
    an add each); causal attention over heads of ``head_dim`` for scores
    and as much for values in the attention layers; the two gates of a
    convolution layer, a multiply a channel each; the head for the last
    token only, which is all a prefill returns."""
    conv, attn, _, _ = _layers(cfg)
    per_key = 2 * 2 * reference.head_dim(cfg) * cfg["num_attention_heads"]
    return 2 * tokens * active_params_per_token(cfg) \
        + attn * per_key * causal_keys(tokens) \
        + conv * 2 * cfg["hidden_size"] * tokens \
        + 2 * head_params(cfg)
