"""Family ``sdar_moe_lm``: the SDAR-MoE decoder (a QK-normed grouped-query
attention layer and softmax-routed experts without a shared one in every
layer, of which a chip holds a share, an untied head; generation by
diffusion over blocks of ``block_length`` tokens whose rows see each other
both ways) that ``mxnet_tpu/serve/sdar_moe.py`` serves.  A configuration's
keys are the published ``config.json``'s; ``num_experts`` and
``vocab_size`` count what is HELD, with ``router_experts`` (the router's
published width) and ``experts_first`` beside them; the generation's keys
(``block_length``, ``denoising_steps``, ``confidence_threshold``,
``mask_token_id``: the mask token's place in the held slice, its last row)
are the generation script's, which the config does not carry.

This family is **served and not yet trained**: ``Module.fit`` has no expert
layer with a backward and no masked-diffusion loss (ROADMAP M1, M7), so the
names a training job asks for raise ``ManifestError`` and nothing stands in
for them.  What a serving job asks for: ``reference`` (the plain forward,
the forward of one pass and the loop), ``model_config`` (the architecture
as the program's public ``serve.ModelConfig`` takes it), ``published_init``
and the counts of work under its two roofline metrics.
"""
from manifest import ManifestError
from references import sdar_moe_lm as reference

BLOCK = "sdar_moe"      # the program's name for it (model.BLOCKS)
# what the block's report() counts since the session was built; the rest
# of it is constant
COUNTED = ("assignments_asked", "assignments_held", "assignments_computed",
           "distinct_held_experts", "rows_without_held_expert",
           "decode_steps", "prefill_chunks", "prefill_chunks_continued",
           "window_rows_visited", "window_rows_in_band", "full_rows_live",
           "slot_passes", "denoise_slot_passes", "commit_slot_passes",
           "rows_unmasked_by_threshold", "rows_unmasked_by_quota",
           "blocks_committed", "tokens_committed")


def _not_trained(*_args, **_kwargs):
    raise ManifestError(
        "family sdar_moe_lm is served and not yet trained: Module.fit has "
        "no expert layer with a backward and no masked-diffusion loss "
        "(ROADMAP M1, M7)")


symbol = batches = items_per_row = grad_scale = _not_trained
train_flops_per_item = output_bytes_per_row = _not_trained


def model_config(cfg):
    """The configuration as keyword arguments of ``serve.ModelConfig``."""
    unserved = [key for key, served in (
        ("attention_bias", False), ("tie_word_embeddings", False),
        ("norm_topk_prob", True), ("rope_scaling", None),
        ("use_sliding_window", False), ("mlp_only_layers", []),
        ("decoder_sparse_step", 1), ("hidden_act", "silu"),
        ("remasking_strategy", "low_confidence_dynamic"))
        if cfg.get(key, served) != served]
    if unserved:
        raise ManifestError("the program's sdar_moe block does not serve %s"
                            % unserved)
    first, count, routed = reference.held(cfg)
    return dict(
        block=BLOCK, vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_len=cfg["max_position_embeddings"],
        attn_head_dim=reference.head_dim(cfg),
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        moe_d_ff=cfg["moe_intermediate_size"], n_routed_experts=routed,
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=True, scoring_func="softmax",
        experts_held=(first, count) if count < routed else (),
        block_length=cfg["block_length"],
        mask_token_id=cfg["mask_token_id"],
        denoising_steps=cfg["denoising_steps"],
        confidence_threshold=float(cfg["confidence_threshold"]))


def published_init(params, cfg):
    """``params`` (what ``weights.maker`` made, for the program and for
    the reference alike) as they are: every matrix a normal draw at
    ``init_std``, every norm's scale one, which is ``weights.py``'s rule by
    name; the block has no parameter that needs another."""
    return params


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
    return reference.held(cfg)[2] * cfg["hidden_size"]


def head_params(cfg):
    """The head's slice (the embedding's is as large, and a look-up)."""
    return cfg["vocab_size"] * cfg["hidden_size"]


def fixed_params(cfg):
    """Every matrix a row passes through whatever it is routed to, the
    head left out: the attention and the routers."""
    return cfg["num_hidden_layers"] * (attention_params(cfg)
                                       + router_params(cfg))


def n_params(cfg):
    """Every parameter of the model as the program holds it (the share:
    the experts and the vocabulary rows held, of the embedding and of the
    head; norm scales included)."""
    layers = cfg["num_hidden_layers"]
    norms = (2 * layers + 1) * cfg["hidden_size"]
    return 2 * head_params(cfg) + fixed_params(cfg) + norms \
        + layers * reference.held(cfg)[1] * expert_params(cfg)


def kv_values_per_token(cfg):
    """Values a token holds in ONE layer: its key/value heads' keys and
    values."""
    return 2 * cfg["num_key_value_heads"] * reference.head_dim(cfg)


def decode_least_bytes(cfg, distinct_experts, live_rows, live_slots,
                       weight_bytes=4, cache_bytes=4):
    """Least bytes ONE block pass must move: every matrix outside the
    routed experts once and the head's slice once (the embedding is a
    look-up of a block's rows a slot and is left out), the held experts
    that at least one row reached (``distinct_experts``: their sum over
    the layers, counted by the program's routers), in every layer the K/V
    rows inside every live slot's horizon (``live_rows``: committed rows
    and the block's own, summed over the slots) read, and the block's own
    rows written.  A pass yields no token by itself: five passes make four
    tokens a slot at the configuration's worst case."""
    weights = fixed_params(cfg) + head_params(cfg) \
        + distinct_experts * expert_params(cfg)
    rows = live_rows + live_slots * cfg["block_length"]
    return weights * weight_bytes + cache_bytes * cfg["num_hidden_layers"] \
        * rows * kv_values_per_token(cfg)


def held_experts_per_token(cfg):
    """Assignments a token makes to the experts held here, in one layer,
    when the routing is balanced: its experts a token times the share held
    (one expert at 16 of 128 and 8 a token)."""
    _, count, routed = reference.held(cfg)
    return cfg["num_experts_per_tok"] * count / routed


def active_params_per_token(cfg):
    """Matmul parameters one token passes through here, the head left out:
    everything outside the routed experts, and the held experts it takes
    under balanced routing."""
    return fixed_params(cfg) + cfg["num_hidden_layers"] \
        * held_experts_per_token(cfg) * expert_params(cfg)


def prefilled_tokens(cfg, tokens):
    """The rows of a prompt of ``tokens`` tokens that prefill computes: its
    whole blocks (the rest open the first generated block)."""
    return tokens - tokens % cfg["block_length"]


def block_causal_keys(cfg, tokens):
    """Keys the rows of ``tokens`` tokens in whole blocks see under the
    block-causal mask, summed: each of block j's B rows sees (j + 1) B."""
    b = cfg["block_length"]
    blocks = tokens // b
    return b * b * blocks * (blocks + 1) // 2


def prefill_flops(cfg, tokens):
    """Operations the prefill of a whole prompt of ``tokens`` tokens needs,
    in however many chunks the program feeds it: its whole blocks alone; 2
    per active matmul parameter per row (the held experts' share of the
    assignments); attention under the block-causal mask over heads of
    ``head_dim`` for scores and as much for values; no head, since a
    prefill yields no token, and of the LAST layer its keys and values
    alone: what its attention and its experts would add to the rows nobody
    reads."""
    rows = prefilled_tokens(cfg, tokens)
    layers = cfg["num_hidden_layers"]
    per_key = 2 * 2 * reference.head_dim(cfg) * cfg["num_attention_heads"]
    kv_only = 2 * cfg["num_key_value_heads"] * reference.head_dim(cfg) \
        * cfg["hidden_size"]
    per_row = (layers - 1) * active_params_per_token(cfg) / layers + kv_only
    return 2 * rows * per_row \
        + (layers - 1) * per_key * block_causal_keys(cfg, rows)
