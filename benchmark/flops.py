"""Operations and bytes a model needs, from its shapes.

Kept with the benchmark so that no later PR can change how a utilization
or a roofline share is computed.  Counts are of the algorithm, not of a
program: recomputation is not counted, a multiply-add is two operations.
"""


def lm_matmul_params(cfg):
    """Parameters that sit in a matrix multiplication: the blocks'
    projections and the head (embeddings are look-ups, biases and norms
    are negligible and left out)."""
    d, f = cfg["d_model"], cfg["d_ff"]
    return cfg["num_layers"] * (4 * d * d + 2 * d * f) \
        + cfg["vocab_size"] * d


def lm_params(cfg):
    """Every parameter of the model as the program builds it (untied,
    biased head)."""
    d, f, v = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    block = 4 * d * d + 2 * d * f + (3 * d + d + f + d) + 4 * d
    return v * d + cfg["seq_len"] * d + cfg["num_layers"] * block \
        + 2 * d + v * d + v


def lm_train_flops_per_token(cfg, context):
    """6 per matmul parameter (forward 2, backward 4) plus attention,
    12 * L * T * d per token.  Attention is counted UNMASKED (the PaLM
    convention): a causal kernel needs half of that term."""
    return 6 * lm_matmul_params(cfg) \
        + 12 * cfg["num_layers"] * context * cfg["d_model"]


def lm_forward_flops_per_token(cfg, context):
    """Forward only, for a token that attends to ``context`` keys."""
    return 2 * lm_matmul_params(cfg) \
        + 4 * cfg["num_layers"] * context * cfg["d_model"]


def lm_decode_bytes(cfg, live_lengths, weight_bytes=4, kv_bytes=4):
    """Least bytes one decode step must read: every matmul weight once,
    and the keys and values of each live slot's context."""
    weights = lm_matmul_params(cfg) * weight_bytes
    kv = sum(live_lengths) * 2 * cfg["num_layers"] * cfg["d_model"] * kv_bytes
    return weights + kv


def train_step_bytes(n_params, batch_bytes, output_bytes, param_bytes=4):
    """Least bytes one SGD-momentum step must move: read the weights and
    the momentum, write both (16 bytes a float32 parameter), read the
    batch and write the outputs ``fit`` asks for."""
    return 4 * n_params * param_bytes + batch_bytes + output_bytes


def roofline_seconds(flops, nbytes, peaks):
    """(least seconds the chip could take, which peak bounds it)."""
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bytes")
