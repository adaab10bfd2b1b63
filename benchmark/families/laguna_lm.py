"""Family ``laguna_lm``: the Laguna decoder (sliding-window and full
grouped-query attention layers in the published ``layer_types`` order with
a query-head count a layer, two rotary embeddings, a gate a head,
softmax-routed experts of which a chip holds a share) that
``mxnet_tpu/serve/laguna.py`` serves.  A configuration's keys are the
published ``config.json``'s; ``num_experts`` and ``vocab_size`` count what
is HELD, with ``router_experts`` (the router's published width),
``experts_first`` and ``layers_kept`` beside them; the per-layer lists stay
the published ones and are read at the places ``layers_kept`` names.

This family is **served and not yet trained**: ``Module.fit`` has neither a
windowed attention layer nor an expert layer with a backward (ROADMAP M0,
M1), so the names a training job asks for raise ``ManifestError`` and
nothing stands in for them.  What a serving job asks for: ``reference``
(the plain forward), ``model_config`` (the architecture as the program's
public ``serve.ModelConfig`` takes it), ``published_init`` (the identity:
no leaf of this model is idle under ``weights.py``'s rules by name) and the
counts of work under its two roofline metrics, which know two kinds of
attention layer.
"""
from manifest import ManifestError
from references import laguna_lm as reference

BLOCK = "laguna"        # the program's name for it (model.BLOCKS)
# what the block's report() counts since the session was built; the rest
# of it is constant
COUNTED = ("assignments_asked", "assignments_held", "assignments_computed",
           "distinct_held_experts", "rows_without_held_expert",
           "decode_steps", "prefill_chunks", "prefill_chunks_continued",
           "window_rows_visited", "window_rows_in_band", "full_rows_live")


def _not_trained(*_args, **_kwargs):
    raise ManifestError(
        "family laguna_lm is served and not yet trained: Module.fit has no "
        "windowed attention or expert layer with a backward (ROADMAP M0, M1)")


symbol = batches = items_per_row = grad_scale = _not_trained
train_flops_per_item = output_bytes_per_row = _not_trained


def model_config(cfg):
    """The configuration as keyword arguments of ``serve.ModelConfig``."""
    unserved = [key for key, served in (
        ("moe_router_logit_softcapping", 0), ("attention_bias", False),
        ("moe_apply_router_weight_on_input", False),
        ("tie_word_embeddings", False), ("gating", "per-head"),
        ("decoder_sparse_step", 1)) if cfg.get(key, served) != served]
    if unserved or len(reference.kept(cfg)) != cfg["num_hidden_layers"]:
        raise ManifestError(
            "the program's laguna block does not serve %s, and "
            "num_hidden_layers counts layers_kept" % (unserved or "this"))
    first, count, routed = reference.held(cfg)
    dense = reference.layer_dense(cfg)
    return dict(
        block=BLOCK, vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_len=cfg["max_position_embeddings"],
        attn_head_dim=cfg["head_dim"],
        num_attention_heads_per_layer=tuple(reference.layer_heads(cfg)),
        layer_types=tuple(reference.layer_types(cfg)),
        sliding_window=cfg["sliding_window"],
        rope_parameters={kind: dict(cfg["rope_parameters"][kind])
                         for kind in set(reference.layer_types(cfg))},
        mlp_only_layers=tuple(i for i, d in enumerate(dense) if d),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        d_ff=cfg["intermediate_size"],
        moe_d_ff=cfg["moe_intermediate_size"], n_routed_experts=routed,
        num_experts_per_tok=cfg["num_experts_per_tok"],
        shared_expert_intermediate_size=cfg[
            "shared_expert_intermediate_size"],
        routed_scaling_factor=float(cfg["moe_routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]), scoring_func="softmax",
        experts_held=(first, count) if count < routed else ())


def published_init(params, cfg):
    """``params`` (what ``weights.maker`` made) as they are: the gates'
    arguments ``w . u`` have a standard deviation of ~1.1 at 0.02 normal
    weights, the softmax router's logits likewise, so every mechanism of
    the block is at work under the rule by name."""
    return params


def _layers(cfg):
    """-> (query heads of each full layer, of each window layer, dense-FFN
    layers, expert layers)."""
    kinds, heads = reference.layer_types(cfg), reference.layer_heads(cfg)
    dense = sum(reference.layer_dense(cfg))
    return ([h for k, h in zip(kinds, heads) if k == "full_attention"],
            [h for k, h in zip(kinds, heads) if k == "sliding_attention"],
            dense, len(kinds) - dense)


def attention_params(cfg, heads):
    """One attention layer's matrices at ``heads`` query heads: W_q, W_o,
    the gate a head, W_k and W_v."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return 2 * heads * hd * d + heads * d \
        + 2 * cfg["num_key_value_heads"] * hd * d


def expert_params(cfg):
    """One routed expert: gate, up and down."""
    return 3 * cfg["moe_intermediate_size"] * cfg["hidden_size"]


def shared_params(cfg):
    return 3 * cfg["shared_expert_intermediate_size"] * cfg["hidden_size"]


def router_params(cfg):
    return reference.held(cfg)[2] * cfg["hidden_size"]


def dense_ffn_params(cfg):
    return 3 * cfg["intermediate_size"] * cfg["hidden_size"]


def head_params(cfg):
    return cfg["vocab_size"] * cfg["hidden_size"]


def fixed_params(cfg):
    """Every matrix a token passes through whatever it is routed to, the
    head left out: attention, the dense FFN, shared experts, routers."""
    full, window, dense, moe = _layers(cfg)
    return sum(attention_params(cfg, h) for h in full + window) \
        + dense * dense_ffn_params(cfg) \
        + moe * (shared_params(cfg) + router_params(cfg))


def n_params(cfg):
    """Every parameter of the model as the program holds it (the share:
    the experts and the vocabulary rows held; untied head; norm scales
    included)."""
    full, window, _, moe = _layers(cfg)
    count = reference.held(cfg)[1]
    norms = (2 * len(full + window) + 1) * cfg["hidden_size"]
    return 2 * head_params(cfg) + fixed_params(cfg) + norms \
        + moe * count * expert_params(cfg)


def kv_values_per_token(cfg):
    """Values a token holds in ONE attention layer: its key/value heads'
    keys and values."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def decode_least_bytes(cfg, distinct_experts, live_rows, band_rows,
                       weight_bytes=4, cache_bytes=4):
    """Least bytes one decode step must move: every matrix outside the
    routed experts once and the head's slice once (the embedding is a
    look-up of one row a slot and is left out), the held experts that at
    least one row reached (``distinct_experts``: their sum over the expert
    layers, counted by the program's routers), the live K/V rows of every
    slot's context (``live_rows``: tokens, summed over the slots) read in
    every full layer, and the rows inside the band (``band_rows``: min(a
    slot's context, sliding_window), summed over the slots) read in every
    window layer."""
    full, window, _, _ = _layers(cfg)
    weights = fixed_params(cfg) + head_params(cfg) \
        + distinct_experts * expert_params(cfg)
    return weights * weight_bytes \
        + (len(full) * live_rows + len(window) * band_rows) \
        * kv_values_per_token(cfg) * cache_bytes


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


def causal_keys(tokens):
    """Keys the queries of a prompt of ``tokens`` see under the causal
    mask, summed: query i sees i + 1."""
    return tokens * (tokens + 1) // 2


def band_keys(tokens, window):
    """The same inside a band of ``window`` keys: query i sees min(i + 1,
    window)."""
    inside = min(tokens, window)
    return causal_keys(inside) + (tokens - inside) * window


def prefill_flops(cfg, tokens):
    """Operations the prefill of a whole prompt of ``tokens`` tokens needs,
    in however many chunks the program feeds it: 2 per active matmul
    parameter per token (the held experts' share of the assignments);
    attention over heads of ``head_dim`` for scores and as much for
    values, causal in the full layers and inside the band in the window
    layers, each at its own query-head count; the head for the last token
    only, which is all a prefill returns."""
    full, window, _, _ = _layers(cfg)
    per_key_head = 2 * 2 * cfg["head_dim"]
    return 2 * tokens * active_params_per_token(cfg) \
        + per_key_head * (sum(full) * causal_keys(tokens) + sum(window)
                          * band_keys(tokens, cfg["sliding_window"])) \
        + 2 * head_params(cfg)
