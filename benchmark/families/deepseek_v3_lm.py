"""Family ``deepseek_v3_lm``: the DeepSeek-V3 decoder block (latent
attention, routed and shared experts) that ``mxnet_tpu/serve/latent_moe.py``
serves.  A configuration's keys are the published ``config.json``'s.

This family is **served and not yet trained**: ``Module.fit`` has no
expert layer with a backward (ROADMAP M1), so the names a training job
asks for raise ``ManifestError`` and nothing stands in for them.  What a
serving job asks for: ``reference`` (the plain forward), ``model_config``
(the architecture as the program's public ``serve.ModelConfig`` takes it)
and the counts of work under its two roofline metrics.
"""
from manifest import ManifestError
from references import deepseek_v3_lm as reference


def _not_trained(*_args, **_kwargs):
    raise ManifestError(
        "family deepseek_v3_lm is served and not yet trained: Module.fit "
        "has no expert layer with a backward (ROADMAP M1)")


symbol = batches = items_per_row = grad_scale = _not_trained
train_flops_per_item = output_bytes_per_row = _not_trained


def model_config(cfg):
    """The configuration as keyword arguments of ``serve.ModelConfig``."""
    return dict(
        block="deepseek_v3", vocab_size=cfg["vocab_size"],
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
        moe_d_ff=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]))


def _layers(cfg):
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def attention_params(cfg):
    """One layer's attention matrices: W_q, W_kva, W_kvb, W_o."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    return h * (nope + rope) * d + (rank + rope) * d \
        + h * (nope + vd) * rank + d * h * vd


def expert_params(cfg):
    """One routed expert: gate, up and down."""
    return 3 * cfg["moe_intermediate_size"] * cfg["hidden_size"]


def shared_params(cfg):
    return cfg["n_shared_experts"] * expert_params(cfg)


def router_params(cfg):
    return cfg["n_routed_experts"] * cfg["hidden_size"]


def dense_ffn_params(cfg):
    return 3 * cfg["intermediate_size"] * cfg["hidden_size"]


def head_params(cfg):
    return cfg["vocab_size"] * cfg["hidden_size"]


def n_params(cfg):
    """Every parameter of the model as the program holds it (untied
    head; norm scales and the router's selection bias included)."""
    d = cfg["hidden_size"]
    dense, moe = _layers(cfg)
    norms = cfg["num_hidden_layers"] * (2 * d + cfg["kv_lora_rank"]) + d
    return 2 * head_params(cfg) + norms \
        + cfg["num_hidden_layers"] * attention_params(cfg) \
        + dense * dense_ffn_params(cfg) \
        + moe * (cfg["n_routed_experts"] * (expert_params(cfg) + 1)
                 + shared_params(cfg) + router_params(cfg))


def decode_least_bytes(cfg, distinct_experts, live_rows, weight_bytes=4,
                       cache_bytes=4):
    """Least bytes one decode step must read: every matmul weight outside
    the routed experts once (attention, the dense layers' FFN, shared
    experts, routers, the head; the embedding is a look-up of one row a
    slot and is left out), the routed experts that at least one row
    reached (``distinct_experts``: their sum over the expert layers), and
    the live latent rows of every slot's context (``live_rows``: tokens,
    summed over the slots) in every layer."""
    dense, moe = _layers(cfg)
    fixed = cfg["num_hidden_layers"] * attention_params(cfg) \
        + dense * dense_ffn_params(cfg) \
        + moe * (shared_params(cfg) + router_params(cfg)) + head_params(cfg)
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return (fixed + distinct_experts * expert_params(cfg)) * weight_bytes \
        + live_rows * cfg["num_hidden_layers"] * row * cache_bytes


def active_params_per_token(cfg):
    """Matmul parameters one token passes through, the head left out:
    attention everywhere, the dense FFN or the router with the experts
    taken and the shared ones."""
    dense, moe = _layers(cfg)
    return cfg["num_hidden_layers"] * attention_params(cfg) \
        + dense * dense_ffn_params(cfg) \
        + moe * (cfg["num_experts_per_tok"] * expert_params(cfg)
                 + shared_params(cfg) + router_params(cfg))


def prefill_flops(cfg, tokens, offset=0):
    """Operations the prefill of ``tokens`` prompt tokens from position
    ``offset`` needs: 2 per active matmul parameter per token; causal
    attention, a token at position p against p + 1 keys over heads of
    ``qk_head_dim`` (scores) and ``v_head_dim`` (values); the head for
    the last token only, which is all a prefill returns."""
    keys = tokens * offset + tokens * (tokens + 1) // 2
    per_key = 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    return 2 * tokens * active_params_per_token(cfg) \
        + cfg["num_hidden_layers"] * keys * per_key + 2 * head_params(cfg)
