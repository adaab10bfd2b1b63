"""The cell ``ling3-flash-l7-reason`` (family ``bailing_hybrid_lm``, kind
``serve_closed_share``): it loads, rehearses on the CPU at its toy sizes
and comes out `correct`; it comes out not `correct` under its control and
when the run is broken underneath (a slot admitted over the state the
request before it left, a held expert's tile skipped); both roofline
readers return a number from a recorded run; and the counts of work under
them are the numbers worked by hand below.

``test_manifest.py::test_every_cell_loads[ling3-flash-l7-reason]`` fails on
its pinned list of kinds (``PERF.md``, Open questions); this file loads
and rehearses the cell in its place.
"""
import json
import math
import os

import pytest

import manifest
import run

CELL = "ling3-flash-l7-reason"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(BENCH, "configs", "ling-3.0-flash-l7-ep8.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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
    return manifest.load_module("families", "bailing_hybrid_lm")


def test_the_cell_loads():
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and cell.kind == "serve_closed_share"
    assert cell.family_name == "bailing_hybrid_lm"
    names = {e["name"] for e in cell.end_to_end}
    # no time to first token: its p95 is too sparse a tail here (PERF.md)
    assert names == {"serve_tokens_per_s", "serve_gap_p95_ms", "setup_s"}
    per_layer = {entry["name"] for entry, _ in cell.per_layer}
    assert {"kda_decode_roofline", "kda_prefill_roofline",
            "decode_call_ms.serve", "sched_host_ms.serve",
            "hbm_peak_gb.serve"} == per_layer
    job = cell.traffic
    assert (job["clients"], job["pool"], job["warmup_requests"],
            job["check_requests"], job["trace_seconds"]) == (64, 128, 64,
                                                             12, 3)
    assert job["serve_config"] == dict(slots=64, page_size=16,
                                       buckets=[256, 1024], max_new=1024,
                                       exact=False)
    assert job["prompt"] == dict(median=256, sigma=0.8, min=32, max=1024)
    assert job["output"] == dict(median=384, sigma=0.6, min=64, max=1024)
    assert (job["pairing_seed"], job["order_seed"]) == (0, 0)
    assert job["control"] == {"quant": "int8"}
    assert job["host_allocator"] == manifest.Cell(
        "cgpt1.3b-chat").traffic["host_allocator"]


def test_the_configuration_is_the_catalogs(family):
    """Every number of the published ``config.json`` under its own key but
    the five cut, each with its reason; the cut is one chip's share."""
    cfg = sized(False)
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        published = next(r for r in rows if r["name"] == "Ling-3.0-flash")
        differ = sorted(k for k, v in published["config"].items()
                        if cfg.get(k, "missing") != v)
        assert differ == sorted(cfg["reduced"])
        assert cfg["published"] == {k: published["config"][k]
                                    for k in cfg["reduced"]}
    assert sorted(cfg["reduced"]) == [
        "first_k_dense_replace", "num_experts", "num_hidden_layers",
        "num_nextn_predict_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["router_experts"], cfg["vocab_size"]) == (7, 64, 512, 19648)
    assert 8 * cfg["vocab_size"] == 157184 and cfg["n_group"] == 8
    assert family.reference.layer_types(cfg) == [
        "kda", "kda", "kda", "kda", "mla", "kda", "kda"]
    model = family.model_config(cfg)
    assert model["experts_held"] == (0, 64)
    assert (model["n_routed_experts"], model["num_experts_per_tok"],
            model["n_group"], model["topk_group"]) == (512, 8, 8, 4)
    assert (model["kda_head_dim"], model["kda_d_conv"],
            model["kda_lower_bound"], model["kda_chunk_size"]) \
        == (128, 4, -5.0, 32)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 11])
def test_sound_run_is_correct(seed, capsys):
    result = execute(seed)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    out = capsys.readouterr().out
    assert "check state_values_not_finite      0" in out
    assert "check moe_assignments_dropped      0" in out


@pytest.mark.parametrize("seed", [1, 2])
def test_int8_serving_is_not_correct(seed):
    assert execute(seed, control=True)["correct"] is False


def test_state_left_unzeroed_at_alloc_is_not_correct(monkeypatch):
    from mxnet_tpu.serve import kv_cache

    monkeypatch.setattr(kv_cache.PagedKVCache, "_scrub_state",
                        lambda self, slot: None)
    assert execute(1)["correct"] is False


def test_a_held_experts_tile_skipped_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    from jax import lax

    loop = lax.fori_loop
    monkeypatch.setattr(lax, "fori_loop", lambda lo, hi, body, init:
                        loop(lo, jnp.maximum(hi - 1, 0), body, init))
    assert execute(1)["correct"] is False


def test_assignments_held_and_not_computed_fail_the_check(monkeypatch):
    """The check this kind adds: the block's own counts, held minus
    computed, limit 0; a report without them reads not-a-number."""
    job = manifest.load_module("jobs", "serve_closed_share")
    real = manifest.load_module

    def fake(counts):
        class Base(object):
            @staticmethod
            def run(*_args):
                return {"facts": {"block": counts}, "checks": []}

        return lambda directory, name, root=None: (
            Base if name == "serve_closed_block" else real(directory, name,
                                                            root))

    cell = manifest.Cell(CELL)
    log = lambda *a: None
    for counts, passes in (
            (dict(assignments_held=9, assignments_computed=9), True),
            (dict(assignments_held=9, assignments_computed=7), False),
            (dict(ssm_layers=3), False)):
        monkeypatch.setattr(manifest, "load_module", fake(counts))
        (name, value, limit), = job.run(cell, None, None, None, 0.0,
                                        log)["checks"]
        assert (name, limit) == ("moe_assignments_dropped", 0)
        assert bool(value <= limit) is passes


def test_both_roofline_readers_read_a_recorded_run(family):
    """What ``run.py`` hands a reader, with counts and module times of the
    order of this cell's traced runs on a v5e; a CPU's trace has no device
    plane, so a rehearsal has nothing for them to read."""
    cfg = sized(False)
    facts = {"step_live": [(64, 36000)] * 90, "config": cfg,
             "block": {"kda_layers": 6, "decode_steps": 90,
                       "distinct_held_experts": 90 * 240},
             "decode_module": "decode", "prefill_module": "prefill",
             "family": "bailing_hybrid_lm", "bench_root": BENCH,
             "prefill_tokens": [200, 900, 96, 310]}
    run_ = {"facts": facts, "peaks": manifest.load_peaks("TPU v5 lite"),
            "trace": {"modules": {"jit_decode_fn(1)": (90, 90 * 0.024),
                                  "jit_prefill_fn(2)": (3, 3 * 0.035),
                                  "jit_prefill_fn(3)": (1, 0.055)}}}
    decode = manifest.load_module("metrics", "kda_decode_roofline").read(run_)
    assert decode == pytest.approx(
        100 * family.decode_least_bytes(cfg, 240, 64, 36000) / 819e9 / 0.024)
    assert 45 < decode < 55
    prefill = manifest.load_module("metrics", "kda_prefill_roofline").read(
        run_)
    flops = sum(family.prefill_flops(cfg, n)
                for n in (200, 900, 96, 310)) / 4
    assert prefill == pytest.approx(100 * flops / 197e12 / 0.04)
    assert 3 < prefill < 8
    # nothing to read is None, not an error: an untraced run, a run of
    # another block, a trace without the module, the parent's program
    for name in ("kda_decode_roofline", "kda_prefill_roofline"):
        read = manifest.load_module("metrics", name).read
        assert read(dict(run_, trace=None)) is None
        assert read(dict(run_, facts=dict(facts, block={}))) is None
        assert read(dict(run_, facts=dict(
            facts, block={"mamba_layers": 36}))) is None
        assert read(dict(run_, facts={"moe": {}, "step_live": [(1, 1)],
                                      "prefill_tokens": [5]})) is None
        assert read(dict(run_, trace={"modules": {}})) is None


def test_a_program_without_the_block_fails_at_once(monkeypatch):
    """What the driver sees on the parent commit: a ``ManifestError``
    before any weight is made (``run.execute`` turns it into exit 2)."""
    from mxnet_tpu.serve import model as serve_model
    import weights

    monkeypatch.delitem(serve_model.BLOCKS, "bailing_hybrid")
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


def test_published_init_overwrites_three_leaves_a_kda_layer(family):
    import jax.numpy as jnp
    import numpy as np

    cfg = sized(True)
    spec = family.reference.spec(cfg)
    params = {k: jnp.full(shape, 0.02, jnp.float32)
              for k, shape in spec.items()}
    out = family.published_init(params, cfg)
    changed = sorted(k for k in out if out[k] is not params[k])
    kinds = family.reference.layer_types(cfg)
    assert kinds == ["kda", "kda", "kda", "mla"]
    assert changed == sorted(
        "blk%d_kda_%s" % (i, leaf) for i, kind in enumerate(kinds)
        if kind == "kda" for leaf in ("A_log", "conv_weight", "dt_bias"))
    sharp = np.exp(np.asarray(out["blk0_kda_A_log"]))
    assert (sharp.min(), sharp.max()) == pytest.approx((0.5, 2.0))
    bias = np.asarray(out["blk0_kda_dt_bias"]).reshape(4, 16)
    decay = np.exp(-5.0 / (1.0 + np.exp(-sharp[:, None] * bias)))
    # every head spans the whole range, in another order than the channels'
    np.testing.assert_allclose(decay.min(axis=1), 0.2, rtol=1e-4)
    np.testing.assert_allclose(decay.max(axis=1), 0.999, rtol=1e-5)
    assert sorted(np.argsort(decay[0])) != list(np.argsort(decay[0]))
    np.testing.assert_allclose(np.asarray(out["blk0_kda_conv_weight"]),
                               12 ** -0.5, rtol=1e-6)
    # the program's own initialiser draws the same decays
    from mxnet_tpu import serve
    from mxnet_tpu.serve import bailing_hybrid

    a_log, dt_bias = bailing_hybrid.decay_init(
        serve.ModelConfig(**family.model_config(cfg)))
    np.testing.assert_allclose(a_log, out["blk0_kda_A_log"], rtol=1e-6,
                               atol=1e-7)
    np.testing.assert_allclose(dt_bias, out["blk0_kda_dt_bias"], rtol=1e-5)


def test_counts_of_work_by_hand(family):
    """Toy sizes: d 128, 4 heads; KDA heads of 16 (width 64), 4 taps,
    chunk 8; MLA 32 + 16 / 32 over a latent of 64; dense SwiGLU 256;
    experts of 64, 4 held of 16 routed, 4 a token, one shared of 64;
    vocabulary 2048; published layers 0, 3, 4, 5 of a period of 6: kda |
    kda kda mla."""
    cfg = sized(True)
    kda = 6 * 64 * 128 + 4 * 128 + 3 * 64 * 4
    mla = 4 * 48 * 128 + (64 + 16) * 128 + 4 * 64 * 64 + 128 * 4 * 32 \
        + 4 * 128
    expert, dense, router, head = (3 * 64 * 128, 3 * 256 * 128, 16 * 128,
                                   2048 * 128)
    assert (family.kda_params(cfg), family.mla_params(cfg),
            family.expert_params(cfg), family.shared_params(cfg),
            family.router_params(cfg), family.dense_ffn_params(cfg)) \
        == (kda, mla, expert, expert, router, dense) \
        == (50432, 68096, 24576, 24576, 2048, 98304)
    fixed = 3 * kda + mla + dense + 3 * (expert + router)
    assert family.fixed_params(cfg) == fixed == 397568
    # every parameter: the reference's own shapes
    assert family.n_params(cfg) == sum(
        math.prod(shape) for shape in family.reference.spec(cfg).values()) \
        == 1218284
    # a slot's state in one KDA layer: 4 x 16 x 16 and 3 rows of 192
    state = 64 * 16 + 3 * 192
    assert family.state_values_per_slot(cfg) == state == 1600
    assert family.state_bytes_per_slot(cfg) == 3 * state * 4
    # a decode step: every matrix outside the experts and the head once, 7
    # held experts reached, 3 live slots' state read and written in 3
    # layers, 100 live rows of 80 values in one latent layer
    assert family.decode_least_bytes(cfg, 7, 3, 100) \
        == (fixed + head + 7 * expert) * 4 + 2 * 3 * 3 * state * 4 \
        + 100 * 80 * 4 == 3474176
    # the chunked form over 20 rows at chunk 8: two whole chunks and one
    # of 4, 36 + 36 + 10 causal pairs, each over 5 x 16 values (K K^T,
    # Q K^T, the solve's two halves, B U), then three products of rows x
    # 16 x 16 with the state, in 4 heads
    chunked = 4 * (2 * 82 * 80 + 3 * 2 * 20 * 16 * 16)
    assert family.chunk_flops(cfg, 20) == chunked == 175360
    # one held expert a token a layer when the routing is balanced
    assert family.held_experts_per_token(cfg) == 1.0
    active = fixed + 3 * expert
    assert family.active_params_per_token(cfg) == active
    # a prefill of 20 tokens from position 0: 2 a token an active
    # parameter, the chunked form in 3 layers, 210 (query, key) pairs in
    # one latent layer over 4 heads of 48 + 32 twice, the head once
    assert family.prefill_flops(cfg, 20) \
        == 2 * 20 * active + 3 * chunked + 210 * 2 * 4 * 80 + 2 * head \
        == 20036608
    assert family.prefill_flops(cfg, 20, offset=16) \
        - family.prefill_flops(cfg, 20) == 320 * 2 * 4 * 80


def test_at_the_published_sizes(family):
    cfg = sized(False)
    assert family.n_params(cfg) == 2866268096          # 11.47 GB in float32
    assert family.kda_params(cfg) == 63045632
    assert family.mla_params(cfg) == 31965184
    assert family.expert_params(cfg) == 5898240
    assert family.state_bytes_per_slot(cfg) == 13467648     # 13.5 MB
    # a decode step at 64 live slots holding contexts of 560 tokens that
    # reaches 40 held experts a layer: 2.2 GB of weights outside the
    # experts, 5.7 GB of experts, 2 x 0.86 GB of state, 0.08 GB of rows
    least = family.decode_least_bytes(cfg, 240, 64, 64 * 560)
    assert 9.6e9 < least < 9.8e9
    # a prefill of 512 tokens: 0.56 TFLOP, of which the chunked forms
    # are 2 %
    assert 0.55e12 < family.prefill_flops(cfg, 512) < 0.58e12
    assert 0.015 < 6 * family.chunk_flops(cfg, 512) \
        / family.prefill_flops(cfg, 512) < 0.03
