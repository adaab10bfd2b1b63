"""The cell ``granite4h-micro-chat`` (family ``granite_hybrid_lm``, kind
``serve_closed_block``): it loads, rehearses on the CPU at its toy sizes
and comes out `correct`; it comes out not `correct` under its control and
when the state is broken underneath (a slot admitted over the state the
request before it left, a convolution context taken from the bucket's
padded tail); both roofline readers return a number from a recorded run;
and the counts of work under them are the numbers worked by hand below.

``test_manifest.py::test_every_cell_loads[granite4h-micro-chat]`` fails on
its pinned list of kinds (``PERF.md``, Open questions); this file loads
and rehearses the cell in its place.
"""
import json
import math
import os

import pytest

import manifest
import run

CELL = "granite4h-micro-chat"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(BENCH, "configs", "granite-4.0-h-micro.json")


def execute(seed, trace=0, **keywords):
    result, _ = run.execute(["--workload", CELL, "--seed", str(seed),
                             "--seconds", "1", "--trace", str(trace),
                             "--rehearse"], **keywords)
    return result


def sized(rehearse):
    with open(CONFIG) as f:
        return manifest.sized(json.load(f), rehearse)


@pytest.fixture(scope="module")
def family():
    return manifest.load_module("families", "granite_hybrid_lm")


def test_the_cell_loads():
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and cell.kind == "serve_closed_block"
    assert cell.family_name == "granite_hybrid_lm"
    names = {e["name"] for e in cell.end_to_end}
    assert names == {"serve_tokens_per_s", "serve_ttft_p95_ms",
                     "serve_gap_p95_ms", "setup_s"}
    per_layer = {entry["name"] for entry, _ in cell.per_layer}
    assert {"ssm_decode_roofline", "ssm_prefill_roofline",
            "decode_call_ms.serve", "prefill_call_ms.serve",
            "sched_host_ms.serve", "hbm_peak_gb.serve"} == per_layer
    # the traffic is the dense chat cell's, but for its kind and its why
    dense = manifest.Cell("cgpt1.3b-chat").traffic
    assert {k: v for k, v in cell.traffic.items() if k not in ("kind", "why")} \
        == {k: v for k, v in dense.items() if k not in ("kind", "why")}


def test_the_configuration_is_the_catalogs(family):
    """Every number of the published ``config.json`` under its own key,
    nothing reduced."""
    cfg = sized(False)
    assert cfg["reduced"] == {}
    assert (cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]) \
        == (40, 2048, 100352)
    assert cfg["layer_types"] == (["mamba"] * 5 + ["attention"]
                                  + ["mamba"] * 4) * 4
    model = family.model_config(cfg)
    assert (model["mamba_n_heads"] * model["mamba_d_head"]
            == cfg["mamba_expand"] * cfg["hidden_size"])


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 11])
def test_sound_run_is_correct(seed, capsys):
    result = execute(seed)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert "check state_values_not_finite      0" in capsys.readouterr().out


@pytest.mark.parametrize("seed", [1, 2])
def test_int8_serving_is_not_correct(seed):
    assert execute(seed, control=True)["correct"] is False


def test_state_left_unzeroed_at_alloc_is_not_correct(monkeypatch):
    from mxnet_tpu.serve import kv_cache

    monkeypatch.setattr(kv_cache.PagedKVCache, "_scrub_state",
                        lambda self, slot: None)
    assert execute(1)["correct"] is False


def test_conv_context_from_the_buckets_tail_is_not_correct(monkeypatch):
    from mxnet_tpu.serve import granite_hybrid

    conv = granite_hybrid.causal_conv
    monkeypatch.setattr(
        granite_hybrid, "causal_conv",
        lambda rows, context, weight, bias, length: conv(
            rows, context, weight, bias, rows.shape[0]))
    assert execute(1)["correct"] is False


def test_both_roofline_readers_read_a_recorded_run(family):
    """What ``run.py`` hands a reader, with the counts and module times of
    this cell's first traced run on a v5e (PR 30: 56 decode events of
    40.6 ms at 16 live slots and ~6 400 live rows, 13 prefill events of
    50 ms over prompts of 193 tokens on average); a CPU's trace has no
    device plane, so a rehearsal has nothing for them to read."""
    cfg = sized(False)
    facts = {"step_live": [(16, 6400)] * 56, "config": cfg,
             "block": {"mamba_layers": 36}, "decode_module": "decode",
             "prefill_module": "prefill", "family": "granite_hybrid_lm",
             "bench_root": BENCH, "prefill_tokens": [128, 512, 96, 36]}
    run_ = {"facts": facts, "peaks": manifest.load_peaks("TPU v5 lite"),
            "trace": {"modules": {"jit_decode_fn(1)": (56, 56 * 0.0406),
                                  "jit_prefill_fn(2)": (9, 9 * 0.06),
                                  "jit_prefill_fn(3)": (4, 4 * 0.0275)}}}
    decode = manifest.load_module("metrics", "ssm_decode_roofline").read(run_)
    assert decode == pytest.approx(
        100 * family.decode_least_bytes(cfg, 16, 6400) / 819e9 / 0.0406)
    assert 45 < decode < 47
    prefill = manifest.load_module("metrics", "ssm_prefill_roofline").read(
        run_)
    flops = sum(family.prefill_flops(cfg, n) for n in (128, 512, 96, 36)) / 4
    assert prefill == pytest.approx(100 * flops / 197e12 / 0.05)
    assert 11 < prefill < 13
    # nothing to read is None, not an error: an untraced run, a run of
    # another block, a trace without the module
    for name in ("ssm_decode_roofline", "ssm_prefill_roofline"):
        read = manifest.load_module("metrics", name).read
        assert read(dict(run_, trace=None)) is None
        assert read(dict(run_, facts=dict(facts, block={}))) is None
        assert read(dict(run_, facts={"moe": {}, "step_live": [(1, 1)],
                                      "prefill_tokens": [5]})) is None
        assert read(dict(run_, trace={"modules": {}})) is None


def test_a_program_without_the_block_fails_at_once(monkeypatch):
    """What the driver sees on the parent commit: a ``ManifestError``
    before any weight is made (``run.execute`` turns it into exit 2)."""
    from mxnet_tpu.serve import model as serve_model
    import weights

    monkeypatch.delitem(serve_model.BLOCKS, "granitemoehybrid")
    monkeypatch.setattr(weights, "maker", lambda *a, **k: pytest.fail(
        "weights were made"))
    with pytest.raises(SystemExit) as exit_info:
        execute(1)
    assert exit_info.value.code == 2


def test_training_names_say_served_not_trained(family):
    for name in ("symbol", "batches", "items_per_row", "grad_scale",
                 "train_flops_per_item", "output_bytes_per_row"):
        with pytest.raises(manifest.ManifestError, match="not yet trained"):
            getattr(family, name)(sized(True))


def test_published_init_overwrites_four_leaves_a_mamba_layer(family):
    import jax.numpy as jnp
    import numpy as np

    cfg = sized(True)
    spec = family.reference.spec(cfg)
    params = {k: jnp.full(shape, 0.02, jnp.float32)
              for k, shape in spec.items()}
    out = family.published_init(params, cfg)
    changed = sorted(k for k in out if out[k] is not params[k])
    assert changed == sorted(
        "blk%d_%s" % (i, leaf) for i, kind in enumerate(cfg["layer_types"])
        if kind == "mamba" for leaf in ("A_log", "D", "conv_weight",
                                        "dt_bias"))
    a = -np.exp(np.asarray(out["blk0_A_log"]))
    dt = np.log1p(np.exp(np.asarray(out["blk0_dt_bias"])))
    assert (a.min(), a.max()) == (-16.0, -1.0)
    np.testing.assert_allclose(sorted(dt)[::len(dt) - 1], [0.001, 0.1],
                               rtol=1e-4)
    assert sorted(np.argsort(dt)) != list(np.argsort(dt))    # another order
    np.testing.assert_allclose(np.asarray(out["blk0_conv_weight"]),
                               12 ** -0.5, rtol=1e-6)


def test_counts_of_work_by_hand(family):
    """Toy sizes: d 128, 4 query heads of 32 over 2 key/value heads, SwiGLU
    256, vocabulary 2048; Mamba-2: 8 heads of 32 (d_inner 256), state 16,
    1 group, 4 taps, chunk 16; layers m m a m."""
    cfg = sized(True)
    conv_dim = 256 + 2 * 16
    mamba = (256 + conv_dim + 8) * 128 + 128 * 256 + conv_dim * 4
    attention = 2 * 128 * 128 + 2 * 2 * 32 * 128
    mlp, head = 3 * 256 * 128, 2048 * 128
    assert (family.mamba_params(cfg), family.attention_params(cfg),
            family.mlp_params(cfg)) == (mamba, attention, mlp) \
        == (104576, 49152, 98304)
    matmul = 3 * mamba + attention + 4 * mlp
    assert family.matmul_params(cfg) == matmul == 756096
    # every parameter: the reference's own shapes
    assert family.n_params(cfg) == sum(
        math.prod(shape) for shape in family.reference.spec(cfg).values()) \
        == 1021096
    # a slot's state in one Mamba layer: 8 x 32 x 16 and 3 rows of 288
    state = 256 * 16 + 3 * conv_dim
    assert family.state_values_per_slot(cfg) == state == 4960
    assert family.state_bytes_per_slot(cfg) == 3 * state * 4
    # a decode step: every matrix and the head once, 3 live slots' state
    # read and written in 3 layers, 100 live rows of 2 x 2 x 32 values
    assert family.decode_least_bytes(cfg, 3, 100) \
        == (matmul + head) * 4 + 2 * 3 * 3 * state * 4 + 100 * 128 * 4 \
        == 4481280
    # the scan over 40 rows at chunk 16: two whole chunks and one of 8,
    # 136 + 136 + 36 causal pairs against a group's state 16 and 8 heads
    # of 32, then the state's two products
    scan = 2 * 308 * (16 + 256) + 4 * 40 * 8 * 32 * 16
    assert family.scan_flops(cfg, 40) == scan == 822912
    # a prefill of 40 tokens from position 0: 2 a token a matmul
    # parameter, the scan in 3 layers, 820 (query, key) pairs in one
    # attention layer over 4 heads of 32 twice, the head once
    assert family.prefill_flops(cfg, 40) \
        == 2 * 40 * matmul + 3 * scan + 820 * 4 * 128 + 2 * head == 63900544
    assert family.prefill_flops(cfg, 40, offset=16) \
        - family.prefill_flops(cfg, 40) == 640 * 4 * 128


def test_at_the_published_sizes(family):
    cfg = sized(False)
    assert family.n_params(cfg) == 3191396096          # 12.77 GB in float32
    assert family.mamba_params(cfg) == 25838592
    assert family.attention_params(cfg) == 10485760
    assert family.state_bytes_per_slot(cfg) == 77377536     # 77.4 MB
    # a decode step at 16 live slots holding 16 contexts of 400 tokens:
    # 12.77 GB of weights, 2 x 1.24 GB of state, 0.1 GB of K/V rows
    least = family.decode_least_bytes(cfg, 16, 6400)
    assert 15.3e9 < least < 15.4e9
    # a prefill of 512 tokens: 3.1 TFLOP, of which the scans are 1.9 %
    assert 3.1e12 < family.prefill_flops(cfg, 512) < 3.2e12
    assert 0.018 < 36 * family.scan_flops(cfg, 512) \
        / family.prefill_flops(cfg, 512) < 0.02
