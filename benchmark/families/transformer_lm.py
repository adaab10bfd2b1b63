"""Family ``transformer_lm``: the GPT-2-shaped decoder of
``mxnet_tpu.models.transformer``.

A configuration is data (``configs/<name>.json``); its ``family`` names
this file, which says how the program builds that model through its
public API, which plain reference follows it, how seeded batches for it
are made and how its work is counted.  A new configuration of this family
needs no code; a new family is a new file beside this one.
"""
import numpy as np

import flops
from references import transformer_lm as reference


def zipf_tokens(rng, vocab, shape, exponent=1.0, shift=2.7):
    """Token ids with a Zipf-Mandelbrot rank-frequency law (natural text:
    exponent ~1, Piantadosi 2014), over a seeded permutation of the ids."""
    ranks = np.arange(vocab, dtype=np.float64)
    p = 1.0 / (ranks + shift) ** exponent
    ids = rng.permutation(vocab)
    return ids[rng.choice(vocab, size=shape, p=p / p.sum())].astype(np.int32)


def symbol(cfg):
    from mxnet_tpu.models import transformer

    return transformer.get_symbol(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_layers"],
        d_model=cfg["d_model"], num_heads=cfg["num_heads"],
        d_ff=cfg["d_ff"], seq_len=cfg["seq_len"])


def batches(cfg, job, rng):
    """``n_batches`` of (tokens, next-token labels), int32 (B, T)."""
    n, t = job["batch_size"] * job["n_batches"], cfg["seq_len"]
    seq = zipf_tokens(rng, cfg["vocab_size"], (n, t + 1))
    return seq[:, :-1], seq[:, 1:]


def items_per_row(cfg):
    return cfg["seq_len"]


def grad_scale(batch_size):
    # SoftmaxOutput(normalization="batch") already yields the mean over
    # tokens; Module.fit's rescale_grad = 1/batch_size comes on top of it
    return 1.0 / batch_size


def train_flops_per_item(cfg):
    return flops.lm_train_flops_per_token(cfg, cfg["seq_len"])


def n_params(cfg):
    return flops.lm_params(cfg)


def output_bytes_per_row(cfg, dtype_bytes):
    return cfg["seq_len"] * cfg["vocab_size"] * dtype_bytes
