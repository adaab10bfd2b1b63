"""The cell ``lfm2-24b-l13-docqa`` (family ``lfm2_moe_lm``, kind
``serve_closed_long``): it loads, rehearses on the CPU at its toy sizes
(prompts of up to three chunks of the largest bucket, whose convolution
rows are carried from chunk to chunk) and comes out `correct`; it comes out
not `correct` under its control and when the run is broken underneath (a
served token altered, a slot's convolution rows zeroed mid-request, a held
expert's tile skipped); the three readers return a number from a recorded
run; and the counts of work under them are the numbers worked by hand
below.

``test_manifest.py::test_every_cell_loads[lfm2-24b-l13-docqa]`` fails on
its pinned list of kinds (``PERF.md``, Open questions); this file loads
and rehearses the cell in its place.
"""
import json
import math
import os

import pytest

import manifest
import run

CELL = "lfm2-24b-l13-docqa"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(BENCH, "configs", "lfm2-24b-a2b-l13-ep8.json")
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
    return manifest.load_module("families", "lfm2_moe_lm")


def test_the_cell_loads():
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and cell.kind == "serve_closed_long"
    assert cell.family_name == "lfm2_moe_lm"
    names = {e["name"] for e in cell.end_to_end}
    assert {"serve_tokens_per_s", "serve_gap_p95_ms", "setup_s"} <= names \
        <= {"serve_tokens_per_s", "serve_gap_p95_ms", "serve_ttft_p95_ms",
            "setup_s"}
    per_layer = {entry["name"] for entry, _ in cell.per_layer}
    assert {"sconv_decode_roofline", "sconv_prefill_roofline",
            "attn_rows_visited_ratio.serve", "decode_call_ms.serve",
            "sched_host_ms.serve", "hbm_peak_gb.serve"} <= per_layer
    job = cell.traffic
    assert (job["clients"], job["pool"], job["warmup_requests"],
            job["check_requests"], job["trace_seconds"]) \
        == (64, 128, 64, 6, 3)
    assert job["serve_config"] == dict(
        slots=64, page_size=16, buckets=[512, 2048], max_prompt=8192,
        max_new=1024, exact=False)
    assert job["prompt"] == dict(median=2048, sigma=0.9, min=128, max=8192)
    assert job["output"] == dict(median=192, sigma=0.7, min=16, max=1024)
    assert (job["pairing_seed"], job["order_seed"]) == (0, 0)
    assert job["control"] == {"quant": "int8"}
    assert job["host_allocator"] == manifest.Cell(
        "cgpt1.3b-chat").traffic["host_allocator"]
    # half the prompts are longer than the largest bucket and carry
    # convolution rows over two to four chunks; eight are four whole ones
    base = manifest.load_module("jobs", "serve_closed")
    pool = base.length_pool(job)
    prompts = sorted(p for p, _ in pool)
    assert (prompts[0], prompts[64], prompts[-1]) == (187, 2066, 8192)
    assert [sum(p > n for p in prompts) for n in (2048, 4096, 6144)] \
        == [64, 28, 14]
    assert sum(p == 8192 for p in prompts) == 8
    assert max(p + o for p, o in pool) <= 8192 + 1024


def test_the_configuration_is_the_catalogs(family):
    """Every key of the published ``config.json`` under its own name but
    the four cut, each with its reason; ``layer_types`` whole, read at
    ``layers_kept``; the cut is one chip's share."""
    cfg = sized(False)
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        published = next(r for r in rows if r["name"] == "LFM2-24B-A2B")
        differ = sorted(k for k, v in published["config"].items()
                        if cfg.get(k, "missing") != v)
        assert differ == sorted(cfg["reduced"])
        assert cfg["published"] == {k: published["config"][k]
                                    for k in cfg["reduced"]}
        assert cfg["source"].startswith(published["source_url"])
    assert sorted(cfg["reduced"]) == ["num_dense_layers", "num_experts",
                                      "num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["router_experts"], cfg["vocab_size"]) \
        == (13, 1, 8, 64, 8192)
    assert 8 * cfg["vocab_size"] == 65536 and len(cfg["layer_types"]) == 40
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["conv_L_cache"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
            cfg["rope_parameters"]["rope_theta"], cfg["norm_eps"]) \
        == (2048, 32, 8, 64, 3, 11776, 1536, 4, 1, 1000000, 1e-5)
    for key in ("precision", "tie_word_embeddings", "conv", "router_eps",
                "head_dim", "hidden_act", "qk_norm", "rope", "init"):
        assert cfg["assumed"][key]
    assert "eight" in cfg["deployment"] and "58 %" in cfg["deployment"]
    assert "".join(k[0] for k in family.reference.layer_types(cfg)) \
        == "cfcccfcccfccc"
    assert family.reference.layer_dense(cfg) == [True] + [False] * 12
    model = family.model_config(cfg)
    assert model["experts_held"] == (0, 8)
    assert (model["n_routed_experts"], model["num_experts_per_tok"],
            model["scoring_func"], model["first_k_dense"],
            model["conv_L_cache"], model["attn_head_dim"],
            model["tie_word_embeddings"], model["rope_theta"]) \
        == (64, 4, "sigmoid", 1, 3, 64, True, 1e6)
    # the rehearsal keeps an attention layer and more than two conv layers
    toy = family.reference.layer_types(sized(True))
    assert toy.count("full_attention") >= 1 and toy.count("conv") >= 2
    # what the program's block does not serve is refused, not ignored
    for key, value in (("conv_bias", True), ("use_expert_bias", False),
                       ("tie_word_embeddings", False)):
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
    assert "(conv_state)" in out and "prefills_carried" in out
    # a prompt of several chunks of the largest bucket was checked: the
    # comparison sees carried state
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


def test_convolution_rows_zeroed_mid_request_are_not_correct(monkeypatch):
    """Every twentieth decode step one live slot's rows of the state pool
    are zeroed behind the program's back: the tokens it serves next are
    another model's."""
    from mxnet_tpu import serve

    step = serve.InferenceSession.step
    calls = []

    def zeroed(self):
        calls.append(1)
        live = self.cache.active_slots()
        if live and len(calls) % 20 == 0:
            pool = self.cache.pools["conv_state"]
            self.cache.pools["conv_state"] = pool.at[:, min(live)].set(0.0)
        return step(self)

    monkeypatch.setattr(serve.InferenceSession, "step", zeroed)
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
    block = {"conv_layers": 10, "full_layers": 3, "window_layers": 0,
             "decode_steps": 150, "distinct_held_experts": 150 * 94,
             "full_rows_live": 150 * 3 * 192000, "window_rows_in_band": 0,
             "window_rows_visited": 0}
    facts = {"step_live": [(64, 192000, 0)] * 150, "config": cfg,
             "block": block, "decode": {"steps": 150, "blocks_visited":
                                        150 * 520},
             "serve_config": {"slots": 64, "page_size": 16},
             "decode_module": "decode", "prefill_module": "prefill",
             "family": "lfm2_moe_lm", "bench_root": BENCH,
             "prefill_tokens": [300, 3000, 8192, 5000]}
    run_ = {"facts": facts, "peaks": manifest.load_peaks("TPU v5 lite"),
            "trace": {"modules": {"jit_decode_fn(1)": (150, 150 * 0.015),
                                  "jit_prefill_fn(2)": (8, 8 * 0.060),
                                  "jit_prefill_fn(3)": (2, 2 * 0.020)}}}
    read = {name: manifest.load_module("metrics", name).read for name in (
        "sconv_decode_roofline", "sconv_prefill_roofline",
        "attn_rows_visited_ratio.serve")}
    decode = read["sconv_decode_roofline"](run_)
    assert decode == pytest.approx(
        100 * family.decode_least_bytes(cfg, 94, 192000, 64) / 819e9 / 0.015)
    assert 50 < decode < 65
    # the prompts' operations over ALL the chunks' device time
    prefill = read["sconv_prefill_roofline"](run_)
    flops = sum(family.prefill_flops(cfg, n) for n in (300, 3000, 8192,
                                                       5000))
    assert prefill == pytest.approx(100 * flops / 197e12 / 0.520)
    assert 8 < prefill < 20
    # 520 blocks x 16 rows x 64 slots x 3 attention layers over 3 x
    # 192 000 live rows: the loop's reading
    ratio = read["attn_rows_visited_ratio.serve"](run_)
    assert ratio == pytest.approx(520 * 16 * 64 * 3 / (3 * 192000))
    assert 2.7 < ratio < 2.8
    # nothing to read is None, not an error: an untraced run, a run of
    # another block, a trace without the module, the parent's program
    for name in ("sconv_decode_roofline", "sconv_prefill_roofline"):
        assert read[name](dict(run_, trace=None)) is None
        assert read[name](dict(run_, facts=dict(facts, block={}))) is None
        assert read[name](dict(run_, facts=dict(
            facts, block={"window_layers": 3, "decode_steps": 9}))) is None
        assert read[name](dict(run_, facts={
            "moe": {}, "step_live": [(1, 1)], "prefill_tokens": [5]})) is None
        assert read[name](dict(run_, trace={"modules": {}})) is None


def test_a_program_without_the_block_fails_at_once(monkeypatch):
    """What the driver sees on the parent commit: a ``ManifestError``
    before any weight is made and before ``ServeConfig`` is asked for
    ``max_prompt`` (``run.execute`` turns it into exit 2)."""
    from mxnet_tpu import serve
    from mxnet_tpu.serve import model as serve_model
    import weights

    monkeypatch.delitem(serve_model.BLOCKS, "lfm2_moe")
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


def test_published_init_sets_the_taps(family):
    """Every leaf as ``weights.py`` made it but the depthwise filters,
    which go from 0.02 to the standard deviation of a uniform draw on
    +-1 / sqrt(3): a third."""
    import jax.numpy as jnp

    cfg = sized(False)
    params = {"blk0_conv_weight": jnp.full((4, 3), 0.02),
              "blk0_in_weight": jnp.full((4, 4), 0.02)}
    out = family.published_init(params, cfg)
    assert float(out["blk0_conv_weight"][0, 0]) == pytest.approx(1 / 3)
    assert out["blk0_in_weight"] is params["blk0_in_weight"]


def test_counts_of_work_by_hand(family):
    """Toy sizes: d 128; 4 query heads over 2 key/value heads of 32; 3
    taps; dense SwiGLU 256; experts of 64, 2 held of 8 routed, 4 a token;
    vocabulary 2048, tied; published layers 0 and 2-5: conv | a c c c."""
    cfg = sized(True)
    # W_in 3 d x d, W_out d x d, 3 taps a channel
    conv = 3 * 128 * 128 + 128 * 128 + 128 * 3
    # W_q and W_o of 4 x 32 x 128, W_k and W_v of 2 x 32 x 128, two norms
    attn = 2 * 4 * 32 * 128 + 2 * 2 * 32 * 128 + 2 * 32
    expert, dense, router, head = (3 * 64 * 128, 3 * 256 * 128,
                                   8 * (128 + 1), 2048 * 128)
    assert (family.conv_params(cfg), family.attention_params(cfg),
            family.expert_params(cfg), family.router_params(cfg),
            family.dense_ffn_params(cfg), family.head_params(cfg)) \
        == (conv, attn, expert, router, dense, head) \
        == (65920, 49216, 24576, 1032, 98304, 262144)
    fixed = 4 * conv + attn + dense + 4 * router
    assert family.fixed_params(cfg) == fixed == 415328
    # every parameter: the reference's own shapes
    assert family.n_params(cfg) == sum(
        math.prod(shape) for shape in family.reference.spec(cfg).values()) \
        == 875488
    assert family.kv_values_per_token(cfg) == 2 * 2 * 32
    assert family.conv_values_per_slot(cfg) == 2 * 128
    # a decode step: every matrix outside the experts and the head once, 7
    # held experts reached, 100 live rows in 1 attention layer, 3 live
    # slots' two rows read and written in 4 convolution layers
    assert family.decode_least_bytes(cfg, 7, 100, 3) \
        == (fixed + head + 7 * expert) * 4 \
        + (100 * 128 + 2 * 4 * 3 * 256) * 4 == 3473792
    # one held expert a token a layer when the routing is balanced
    assert family.held_experts_per_token(cfg) == 1.0
    active = fixed + 4 * expert
    assert family.active_params_per_token(cfg) == active
    assert family.causal_keys(20) == 210
    # a prefill of 20 tokens: 2 a token an active parameter; 210 pairs
    # over 4 heads of 32 for scores and 32 for values; the two gates of 4
    # convolution layers, a multiply a channel each; the head once
    assert family.prefill_flops(cfg, 20) \
        == 2 * 20 * active + 2 * 2 * 32 * 4 * 210 + 4 * 2 * 128 * 20 \
        + 2 * head == 21197568


def test_at_the_published_sizes(family):
    cfg = sized(False)
    # 4.78 GB in float32: ISSUE.md's 1 196.0 M, to the parameter
    n = family.n_params(cfg)
    assert n == sum(math.prod(shape) for shape
                    in family.reference.spec(cfg).values()) == 1196018816
    assert abs(n / 1196.0e6 - 1) < 0.01
    assert family.conv_params(cfg) == 16783360
    assert family.attention_params(cfg) == 10485888
    assert family.expert_params(cfg) == 9437184
    assert family.dense_ffn_params(cfg) == 72351744
    assert family.kv_values_per_token(cfg) * 4 == 4096
    # a slot's state: 10 layers x 2 rows x 2048 float32 values
    assert 10 * family.conv_values_per_slot(cfg) * 4 == 163840
    # a decode step at 64 slots holding contexts of 3 000 tokens that
    # reaches 7.9 held experts a layer: 1.09 GB of matrices outside the
    # experts, 0.07 of the head's slice, 3.58 of experts, 2.36 of K/V
    # rows, 0.02 of convolution rows
    least = family.decode_least_bytes(cfg, 12 * 7.9, 64 * 3000, 64)
    assert 7.0e9 < least < 7.2e9
    assert 0.32 < 3 * 64 * 3000 * 4096 / least < 0.34    # K/V: a third
    # a prompt of 8 192 tokens: 5.4 TFLOP of matmuls (329.8 M active
    # parameters a token: half a held expert a layer), 0.8 of causal
    # attention in 3 layers
    assert family.active_params_per_token(cfg) == 273216640 + 6 * 9437184
    flops = family.prefill_flops(cfg, 8192)
    assert 6.2e12 < flops < 6.3e12
