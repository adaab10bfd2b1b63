"""The routed-expert layer's grouped-matmul kernel
(``ops/grouped_matmul.py``) against the ``fori_loop`` it replaces on a
TPU, which stays in ``serve/latent_moe.py:_routed_experts`` as the
fallback and is the oracle here: the same call runs once as the CPU
runs it (the loop) and once with the predicate answered "kernel", the
kernel in Pallas's TPU interpreter.

Tolerance, with its reason: the loop on the CPU multiplies in float32;
the kernel rounds each matmul's operands to bfloat16 (eps 2**-8) and
accumulates in float32, as the chip's default precision does.  Three
matmuls deep, over 128-256 terms each, the largest difference read over
the cases below was 0.4 % of the output's largest magnitude; the limit
is 4 eps = 1.6 %, and a tile computed with its neighbour's expert reads
over 100 % (``test_the_comparison_can_fail``).  ``computed`` has no
arithmetic in it and is held to equality.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from mxnet_tpu.ops import grouped_matmul
from mxnet_tpu.serve import latent_moe

from serve_util import expert_layer_config

BF16_EPS = 2.0 ** -8
LIMIT = 4 * BF16_EPS
D, F = 128, 128


def stacks(cfg, seed):
    held = latent_moe.held_range(cfg)[1]
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"blk1_experts_%s_weight" % name: 0.1 * jax.random.normal(
        key, (held, D, F) if name == "down" else (held, F, D), jnp.float32)
        for key, name in zip(keys, ("gate", "up", "down"))}


def choices(rng, rows, experts, top_k, hot=(), hot_share=0.0):
    """(rows, top_k) distinct expert ids a row, as ``top_k`` gives them;
    a ``hot_share`` of the rows take ``hot`` first."""
    taken = np.stack([rng.permutation(experts)[:top_k] for _ in range(rows)])
    for r in range(int(rows * hot_share)):
        rest = [e for e in taken[r] if e not in hot]
        taken[r] = (list(hot) + rest)[:top_k]
    return jnp.asarray(taken, jnp.int32)


# name: (rows, experts, top_k, held, hot experts, their share of the rows)
CASES = {
    # 16 rows x 6 of 128: tile 8, most experts one or two rows, some none
    "decode": (16, 128, 6, (), (), 0.0),
    # 64 rows x 2 of 8: tile 16, an expert's group one tile or two
    "prefill_tile_16": (64, 8, 2, (), (), 0.0),
    # 128 rows x 2 of 8: tile 32; expert 3 takes half the assignments,
    # four tiles in a row that share one read
    "prefill_one_hot_expert": (128, 8, 2, (), (3,), 1.0),
    # 512 rows x 2 of 8: tile 128, the largest bucket's (an MXU pass)
    "prefill_tile_128": (512, 8, 2, (), (), 0.0),
    # every row takes the same two experts: the tiles past in_use
    # outnumber those in use
    "tiles_past_in_use": (64, 16, 2, (), (5, 9), 1.0),
    # one chip's share: experts 8..15 of 32 held, the rest of the
    # assignments belong to other chips
    "held_share": (48, 32, 4, (8, 8), (), 0.0),
    # a share none of whose experts any row takes: no tile in use
    "held_share_unreached": (8, 32, 2, (30, 2), (0, 1), 1.0),
}


def both_ways(name, monkeypatch, seed=0):
    rows, experts, top_k, held, hot, share = CASES[name]
    cfg = expert_layer_config(D, F, experts, top_k, held)
    rng = np.random.default_rng(seed)
    params = stacks(cfg, seed)
    u = jnp.asarray(rng.standard_normal((rows, D)), jnp.float32)
    taken = choices(rng, rows, experts, top_k, hot, share)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (rows, top_k)), jnp.float32)

    def run():
        with jax.default_matmul_precision("default"):
            out, computed, _ = latent_moe._routed_experts(
                u, taken, w, params, "blk1_", cfg, False)
        return np.asarray(out), np.asarray(computed)

    loop = run()
    monkeypatch.setattr(latent_moe, "grouped_swiglu_eligible",
                        lambda *a: True)
    with pltpu.force_tpu_interpret_mode():
        kernel = run()
    return loop, kernel, (cfg, taken)


# the tile a case is named for: what ``_tile_rows`` gives its sizes
TILES = {"decode": 8, "prefill_tile_16": 16, "prefill_one_hot_expert": 32,
         "prefill_tile_128": 128, "held_share": 8}


@pytest.mark.parametrize("name", sorted(TILES))
def test_the_cases_cover_the_cells_tiles(name):
    rows, experts, top_k = CASES[name][:3]
    assert latent_moe._tile_rows(rows * top_k, experts) == TILES[name]


def gap(got, want):
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_is_the_loop_to_bfloat16_rounding(name, monkeypatch):
    (want, want_done), (got, got_done), (cfg, taken) = both_ways(
        name, monkeypatch)
    np.testing.assert_array_equal(got_done, want_done)
    np.testing.assert_array_equal(
        want_done, np.asarray(latent_moe.held(taken, cfg)))
    if name == "held_share_unreached":
        assert not want_done.any() and not got.any() and not want.any()
        return
    assert want_done.any() and np.max(np.abs(want)) > 0.1
    assert gap(got, want) <= LIMIT
    # rounding, not equality: the kernel did its own arithmetic
    assert gap(got, want) > 0


def test_the_comparison_can_fail(monkeypatch):
    """Every tile computed with the next expert's matrices: far over the
    limit."""
    real = grouped_matmul.grouped_swiglu

    def shifted(x, expert_of_tile, in_use, gate, up, down, tile):
        return real(x, (expert_of_tile + 1) % gate.shape[0], in_use, gate,
                    up, down, tile=tile)

    monkeypatch.setattr(latent_moe, "grouped_swiglu", shifted)
    (want, _), (got, _), _ = both_ways("prefill_one_hot_expert", monkeypatch)
    assert gap(got, want) > 0.5


def test_rows_past_the_tiles_in_use_are_zero():
    """The kernel alone: tiles at or past ``in_use`` are zero whatever
    their ``x`` rows and their expert hold, and ``in_use`` 0 is all
    zeros."""
    tile, tiles, experts = 8, 6, 4
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((tiles * tile, D)), jnp.float32)
    gate, up = (jnp.asarray(0.1 * rng.standard_normal((experts, F, D)),
                            jnp.float32) for _ in range(2))
    down = jnp.asarray(0.1 * rng.standard_normal((experts, D, F)),
                       jnp.float32)
    expert_of_tile = jnp.asarray([0, 0, 2, 3, 3, 3], jnp.int32)
    with pltpu.force_tpu_interpret_mode():
        for in_use in (0, 3, 6):
            y = np.asarray(grouped_matmul.grouped_swiglu(
                x, expert_of_tile, jnp.int32(in_use), gate, up, down,
                tile=tile))
            assert np.abs(y[:in_use * tile]).max(axis=1).all()
            assert not y[in_use * tile:].any()


def aval(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


# what sends a call where: (backend, exact, stacks' dtype, dequantized
# inside the trace, d, moe_d_ff, tile) -> kernel?
PREDICATE = {
    "kanana_decode": (("tpu", False, "float32", False, 2048, 768, 8), True),
    "ling_bucket_1024": (("tpu", False, "float32", False, 2560, 768, 16),
                         True),
    "bfloat16_stacks": (("tpu", False, "bfloat16", False, 2048, 768, 128),
                        True),
    "cpu": (("cpu", False, "float32", False, 2048, 768, 8), False),
    "gpu": (("gpu", False, "float32", False, 2048, 768, 8), False),
    "exact": (("tpu", True, "float32", False, 2048, 768, 8), False),
    "int8_tree_dequantized_in_the_trace": (
        ("tpu", False, "float32", True, 2048, 768, 8), False),
    "int8_stacks": (("tpu", False, "int8", False, 2048, 768, 8), False),
    "toy_d": (("tpu", False, "float32", False, 64, 768, 8), False),
    "toy_d_ff": (("tpu", False, "float32", False, 2048, 24, 8), False),
    "d_ff_not_whole_lanes": (("tpu", False, "float32", False, 2048, 832, 8),
                             False),
    "tile_not_whole_sublanes": (("tpu", False, "float32", False, 2048, 768,
                                 4), False),
}


@pytest.mark.parametrize("name", sorted(PREDICATE))
def test_predicate_table(name, monkeypatch):
    (backend, exact, dtype, dequantized, d, f, tile), kernel = PREDICATE[name]
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    x = aval((12 * tile, d))
    gate = up = aval((4, f, d), dtype)
    down = aval((4, d, f), dtype)
    assert grouped_matmul.grouped_swiglu_eligible(
        x, gate, up, down, tile, exact, dequantized) is kernel


def test_kernel_name_carries_its_tile():
    assert grouped_matmul.kernel_name(8) == "moe_grouped_swiglu_t8"
    assert grouped_matmul.kernel_name(128) == "moe_grouped_swiglu_t128"
