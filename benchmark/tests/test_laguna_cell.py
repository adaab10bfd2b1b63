"""The cell ``laguna-s2.1-l5-code`` (family ``laguna_lm``, kind
``serve_closed_long``): it loads, rehearses on the CPU at its toy sizes
(prompts of up to six chunks of the largest bucket) and comes out
`correct`; it comes out not `correct` under its control and when the run
is broken underneath (a served token altered, a ring row left stale, a
held expert's tile skipped); the three readers return a number from a
recorded run; and the counts of work under them are the numbers worked by
hand below.

``test_manifest.py::test_every_cell_loads[laguna-s2.1-l5-code]`` fails on
its pinned list of kinds (``PERF.md``, Open questions); this file loads
and rehearses the cell in its place.
"""
import json
import math
import os

import pytest

import manifest
import run

CELL = "laguna-s2.1-l5-code"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(BENCH, "configs", "laguna-s-2.1-l5-ep8.json")
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
    return manifest.load_module("families", "laguna_lm")


def test_the_cell_loads():
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and cell.kind == "serve_closed_long"
    assert cell.family_name == "laguna_lm"
    names = {e["name"] for e in cell.end_to_end}
    # its time to first token too: the p95 is a 12 288-token prompt's six
    # chunks and spread 0.1 % over eight seeds (PERF.md section 2)
    assert names == {"serve_tokens_per_s", "serve_gap_p95_ms",
                     "serve_ttft_p95_ms", "setup_s"}
    per_layer = {entry["name"] for entry, _ in cell.per_layer}
    assert {"swa_decode_roofline", "swa_prefill_roofline",
            "attn_rows_visited_ratio.serve", "decode_call_ms.serve",
            "prefill_call_ms.serve", "sched_host_ms.serve",
            "hbm_peak_gb.serve"} == per_layer
    job = cell.traffic
    assert (job["clients"], job["pool"], job["warmup_requests"],
            job["check_requests"], job["trace_seconds"]) == (16, 96, 32, 6, 3)
    assert job["serve_config"] == dict(
        slots=16, page_size=16, buckets=[512, 2048], max_prompt=12288,
        max_new=1024, exact=False)
    assert job["prompt"] == dict(median=3072, sigma=0.9, min=256, max=12288)
    assert job["output"] == dict(median=192, sigma=0.7, min=16, max=1024)
    assert (job["pairing_seed"], job["order_seed"]) == (0, 0)
    assert job["control"] == {"quant": "int8"}
    assert job["host_allocator"] == manifest.Cell(
        "cgpt1.3b-chat").traffic["host_allocator"]
    # two prompts in three are longer than the largest bucket, one in
    # three longer than 4 608 (three chunks, a ring wrapped nine times)
    base = manifest.load_module("jobs", "serve_closed")
    prompts = sorted(p for p, _ in base.length_pool(job))
    assert (prompts[0], prompts[48], prompts[-1]) == (306, 3108, 12288)
    assert sum(p > 2048 for p in prompts) == 65
    assert sum(p > 4608 for p in prompts) == 31


def test_the_configuration_is_the_catalogs(family):
    """Every key of the published ``config.json`` under its own name but
    the three cut, each with its reason; the per-layer lists whole, read
    at ``layers_kept``; the cut is one chip's share."""
    cfg = sized(False)
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        published = next(r for r in rows if r["name"] == "Laguna-S-2.1")
        differ = sorted(k for k, v in published["config"].items()
                        if cfg.get(k, "missing") != v)
        assert differ == sorted(cfg["reduced"])
        assert cfg["published"] == {k: published["config"][k]
                                    for k in cfg["reduced"]}
        assert cfg["source"].startswith(published["source_url"])
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["router_experts"], cfg["vocab_size"]) == (5, 32, 256, 12544)
    assert 8 * cfg["vocab_size"] == 100352 and len(cfg["layer_types"]) == 48
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"],
            cfg["sliding_window"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["moe_routed_scaling_factor"]) \
        == (3072, 128, 8, 512, 12288, 1024, 10, 2.5)
    for key in ("precision", "routing", "shared_expert", "gate", "qk_norm",
                "rope", "hidden_act", "init_std"):
        assert cfg["assumed"][key]
    assert "eight" in cfg["deployment"]
    assert family.reference.layer_types(cfg) == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert family.reference.layer_heads(cfg) == [48, 72, 72, 72, 48]
    assert family.reference.layer_dense(cfg) == [True] + [False] * 4
    model = family.model_config(cfg)
    assert model["experts_held"] == (0, 32)
    assert (model["n_routed_experts"], model["num_experts_per_tok"],
            model["scoring_func"], model["mlp_only_layers"],
            model["sliding_window"], model["attn_head_dim"]) \
        == (256, 10, "softmax", (0,), 512, 128)
    assert sorted(model["rope_parameters"]) == ["full_attention",
                                                "sliding_attention"]
    # what the program's block does not serve is refused, not ignored
    for key, value in (("moe_router_logit_softcapping", 30.0),
                       ("gating", "per-layer"), ("attention_bias", True)):
        with pytest.raises(manifest.ManifestError, match="does not serve"):
            family.model_config(dict(cfg, **{key: value}))


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 11])
def test_sound_run_is_correct(seed, capsys):
    result = execute(seed)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    out = capsys.readouterr().out
    assert "check state_values_not_finite      0" in out
    assert "check moe_assignments_dropped      0" in out
    assert "max_prompt 192" in out and "3 executables" in out
    # a prompt of several chunks of the largest bucket was checked
    assert "a prompt of 192, fed in 3 chunk(s)" in out


@pytest.mark.parametrize("seed", [1, 2])
def test_int8_serving_is_not_correct(seed):
    assert execute(seed, control=True)["correct"] is False


def test_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from mxnet_tpu import serve

    step = serve.InferenceSession.step

    def altered(self):
        tokens, logits = step(self)
        slot = min(tokens)
        tokens[slot] = (tokens[slot] + 1) % self.model.vocab_size
        return tokens, logits

    monkeypatch.setattr(serve.InferenceSession, "step", altered)
    assert execute(1)["correct"] is False


def test_a_ring_left_stale_is_not_correct(monkeypatch):
    """A prefill chunk whose values never reach the window layers' rings:
    decode then reads what the slot's last request left there."""
    from mxnet_tpu.serve import laguna

    fold = laguna.fold_into_ring
    monkeypatch.setattr(
        laguna, "fold_into_ring", lambda pools, which, *rest:
        fold(pools, which, *rest) if which == "kw" else None)
    assert execute(1)["correct"] is False


def test_a_held_experts_tile_skipped_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    from jax import lax

    loop = lax.fori_loop
    monkeypatch.setattr(lax, "fori_loop", lambda lo, hi, body, init:
                        loop(lo, jnp.maximum(hi - 1, 0), body, init))
    assert execute(1)["correct"] is False


def test_the_three_readers_read_a_recorded_run(family):
    """What ``run.py`` hands a reader, with counts and module times of the
    order of this cell's traced runs on a v5e; a CPU's trace has no device
    plane, so a rehearsal has nothing for the rooflines to read."""
    cfg = sized(False)
    block = {"window_layers": 3, "full_layers": 2, "decode_steps": 150,
             "distinct_held_experts": 150 * 60, "full_rows_live": 150 * 2
             * 72000, "window_rows_in_band": 150 * 3 * 8000,
             "window_rows_visited": 150 * 3 * 16 * 512}
    facts = {"step_live": [(16, 72000, 8000)] * 150, "config": cfg,
             "block": block, "decode": {"steps": 150, "blocks_visited":
                                        150 * 640},
             "serve_config": {"slots": 16, "page_size": 16},
             "decode_module": "decode", "prefill_module": "prefill",
             "family": "laguna_lm", "bench_root": BENCH,
             "prefill_tokens": [300, 3000, 12288, 5000]}
    run_ = {"facts": facts, "peaks": manifest.load_peaks("TPU v5 lite"),
            "trace": {"modules": {"jit_decode_fn(1)": (150, 150 * 0.014),
                                  "jit_prefill_fn(2)": (10, 10 * 0.045),
                                  "jit_prefill_fn(3)": (3, 3 * 0.018)}}}
    read = {name: manifest.load_module("metrics", name).read for name in (
        "swa_decode_roofline", "swa_prefill_roofline",
        "attn_rows_visited_ratio.serve")}
    decode = read["swa_decode_roofline"](run_)
    assert decode == pytest.approx(
        100 * family.decode_least_bytes(cfg, 60, 72000, 8000) / 819e9 / 0.014)
    assert 40 < decode < 55
    # the prompts' operations over ALL the chunks' device time
    prefill = read["swa_prefill_roofline"](run_)
    flops = sum(family.prefill_flops(cfg, n) for n in (300, 3000, 12288,
                                                       5000))
    assert prefill == pytest.approx(100 * flops / 197e12 / 0.504)
    assert 15 < prefill < 30
    # (640 blocks x 16 rows x 16 slots x 2 full layers + 3 x 16 rings of
    # 512) over (2 x 72 000 live rows + 3 x 8 000 in the band)
    ratio = read["attn_rows_visited_ratio.serve"](run_)
    assert ratio == pytest.approx((640 * 16 * 16 * 2 + 3 * 16 * 512)
                                  / (2 * 72000 + 3 * 8000))
    assert 2.0 < ratio < 2.2
    # nothing to read is None, not an error: an untraced run, a run of
    # another block, a trace without the module, the parent's program
    for name in ("swa_decode_roofline", "swa_prefill_roofline"):
        assert read[name](dict(run_, trace=None)) is None
        assert read[name](dict(run_, facts=dict(facts, block={}))) is None
        assert read[name](dict(run_, facts=dict(
            facts, block={"kda_layers": 6, "decode_steps": 9}))) is None
        assert read[name](dict(run_, facts={
            "moe": {}, "step_live": [(1, 1)], "prefill_tokens": [5]})) is None
        assert read[name](dict(run_, trace={"modules": {}})) is None
    ratio = read["attn_rows_visited_ratio.serve"]
    assert ratio(dict(run_, trace=None)) is not None    # a program counter
    assert ratio(dict(run_, facts=dict(facts, block={}))) is None
    assert ratio(dict(run_, facts={"step_live": [(1, 1)]})) is None
    assert ratio(dict(run_, facts=dict(facts, decode=None))) is None


def test_a_program_without_the_block_fails_at_once(monkeypatch):
    """What the driver sees on the parent commit: a ``ManifestError``
    before any weight is made and before ``ServeConfig`` is asked for
    ``max_prompt`` (``run.execute`` turns it into exit 2)."""
    from mxnet_tpu import serve
    from mxnet_tpu.serve import model as serve_model
    import weights

    monkeypatch.delitem(serve_model.BLOCKS, "laguna")
    monkeypatch.setattr(weights, "maker", lambda *a, **k: pytest.fail(
        "weights were made"))
    monkeypatch.setattr(serve, "ServeConfig", lambda *a, **k: pytest.fail(
        "a ServeConfig was built"))
    with pytest.raises(SystemExit) as exit_info:
        execute(1)
    assert exit_info.value.code == 2


def test_training_names_say_served_not_trained(family):
    for name in ("symbol", "batches", "items_per_row", "grad_scale",
                 "train_flops_per_item", "output_bytes_per_row"):
        with pytest.raises(manifest.ManifestError, match="not yet trained"):
            getattr(family, name)(sized(True))


def test_published_init_is_the_identity(family):
    params = {"blk1_attn_gate_weight": object()}
    assert family.published_init(params, sized(True)) is params


def test_counts_of_work_by_hand(family):
    """Toy sizes: d 128; 2 key/value heads of 32 under 4 | 6 6 6 | 4 query
    heads, window 16; dense SwiGLU 256; experts of 64, 4 held of 16 routed,
    4 a token, one shared of 64; vocabulary 2048; published layers 0-4:
    full | sliding sliding sliding full."""
    cfg = sized(True)
    # W_q and W_o of h x 32 x 128 each, the gate's h x 128, W_k and W_v
    attn4 = 2 * 4 * 32 * 128 + 4 * 128 + 2 * 2 * 32 * 128
    attn6 = 2 * 6 * 32 * 128 + 6 * 128 + 2 * 2 * 32 * 128
    expert, dense, router, head = (3 * 64 * 128, 3 * 256 * 128, 16 * 128,
                                   2048 * 128)
    assert (family.attention_params(cfg, 4), family.attention_params(cfg, 6),
            family.expert_params(cfg), family.shared_params(cfg),
            family.router_params(cfg), family.dense_ffn_params(cfg)) \
        == (attn4, attn6, expert, expert, router, dense) \
        == (49664, 66304, 24576, 24576, 2048, 98304)
    fixed = 2 * attn4 + 3 * attn6 + dense + 4 * (expert + router)
    assert family.fixed_params(cfg) == fixed == 503040
    # every parameter: the reference's own shapes
    assert family.n_params(cfg) == sum(
        math.prod(shape) for shape in family.reference.spec(cfg).values()) \
        == 1421952
    assert family.kv_values_per_token(cfg) == 2 * 2 * 32
    # a decode step: every matrix outside the experts and the head once, 7
    # held experts reached, 100 live rows in 2 full layers and 40 rows
    # inside the band in 3 window layers, 128 values a row
    assert family.decode_least_bytes(cfg, 7, 100, 40) \
        == (fixed + head + 7 * expert) * 4 + (2 * 100 + 3 * 40) * 128 * 4 \
        == 3912704
    # one held expert a token a layer when the routing is balanced
    assert family.held_experts_per_token(cfg) == 1.0
    active = fixed + 4 * expert
    assert family.active_params_per_token(cfg) == active
    # 20 queries: 210 causal (query, key) pairs; inside a band of 16 the
    # first 16 see 136 and the last 4 see 16 each
    assert (family.causal_keys(20), family.band_keys(20, 16),
            family.band_keys(9, 16)) == (210, 200, 45)
    # a prefill of 20 tokens: 2 a token an active parameter; pairs over
    # heads of 32 for scores and 32 for values (2 x 2 x 32 a pair a head),
    # 8 full-layer heads causal and 18 window-layer heads in the band; the
    # head once
    assert family.prefill_flops(cfg, 20) \
        == 2 * 20 * active + 128 * (8 * 210 + 18 * 200) + 2 * head \
        == 25253888


def test_at_the_published_sizes(family):
    cfg = sized(False)
    # 6.87 GB in float32: ISSUE.md's 1 717 M
    assert family.n_params(cfg) == sum(
        math.prod(shape) for shape in family.reference.spec(cfg).values()) \
        == 1716986880
    assert family.attention_params(cfg, 48) == 44187648
    assert family.attention_params(cfg, 72) == 63135744
    assert family.expert_params(cfg) == 9437184
    assert family.kv_values_per_token(cfg) * 4 == 8192
    # a decode step at 16 slots holding contexts of 4 500 tokens that
    # reaches 15 held experts a layer: 1.73 GB of matrices outside the
    # experts, 0.15 of the head's slice, 2.26 of experts, 1.18 of full
    # layers' rows, 0.20 of rings
    least = family.decode_least_bytes(cfg, 60, 16 * 4500, 16 * 512)
    assert 5.4e9 < least < 5.6e9
    # a prompt of 12 288 tokens: 11.8 TFLOP of matmuls (479 M active
    # parameters a token: 1.25 held experts a layer), 3.7 of causal
    # attention in 2 layers, 0.7 of banded attention in 3
    assert family.active_params_per_token(cfg) == 431923200 + 5 * 9437184
    flops = family.prefill_flops(cfg, 12288)
    assert 16.1e12 < flops < 16.3e12
    full = 512 * 96 * family.causal_keys(12288)
    band = 512 * 216 * family.band_keys(12288, 512)
    assert 3.6e12 < full < 3.8e12 and 0.65e12 < band < 0.7e12
