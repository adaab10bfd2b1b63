"""The cell ``qwen3next-l8-longdoc`` (family ``qwen3_next_lm``, kind
``serve_closed_long``): it loads, rehearses on the CPU at its toy sizes
(prompts of up to four chunks of the largest bucket, whose DeltaNet state
and convolution rows are carried from chunk to chunk) and comes out
`correct`; it comes out not `correct` under its control and when the run is
broken underneath (a served token altered, a decode step's state update
applied twice, a slot's state zeroed mid-request, a held expert's tile
skipped); the three readers return a number from a recorded run; and the
counts of work under them are the numbers worked by hand below.

``test_manifest.py::test_every_cell_loads[qwen3next-l8-longdoc]`` fails on
its pinned list of kinds (``PERF.md``, Open questions); this file loads
and rehearses the cell in its place.
"""
import json
import math
import os

import pytest

import manifest
import run

CELL = "qwen3next-l8-longdoc"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(BENCH, "configs", "qwen3-next-80b-a3b-l8-ep8.json")
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
    return manifest.load_module("families", "qwen3_next_lm")


def test_the_cell_loads():
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and cell.kind == "serve_closed_long"
    assert cell.family_name == "qwen3_next_lm"
    assert {e["name"] for e in cell.end_to_end} == {
        "serve_tokens_per_s", "serve_gap_p95_ms", "serve_ttft_p95_ms",
        "setup_s"}
    per_layer = {entry["name"] for entry, _ in cell.per_layer}
    assert {"gdn_decode_roofline", "gdn_prefill_roofline",
            "attn_rows_visited_ratio.serve", "prefill_chunk_ms.serve",
            "decode_ahead_share.serve", "step_host_cpu_ms.serve",
            "decode_host_ms.serve", "decode_call_ms.serve",
            "prefill_call_ms.serve", "sched_host_ms.serve",
            "hbm_peak_gb.serve"} <= per_layer
    job = cell.traffic
    assert (job["clients"], job["pool"], job["warmup_requests"],
            job["check_requests"], job["trace_seconds"]) \
        == (32, 96, 32, 4, 3)
    assert job["serve_config"] == dict(
        slots=32, page_size=16, buckets=[512, 2048], max_prompt=16384,
        max_new=1024, exact=False)
    assert job["prompt"] == dict(median=4096, sigma=0.9, min=256, max=16384)
    assert job["output"] == dict(median=256, sigma=0.7, min=16, max=1024)
    assert (job["pairing_seed"], job["order_seed"]) == (0, 0)
    assert job["control"] == {"quant": "int8"}
    assert job["host_allocator"] == manifest.Cell(
        "cgpt1.3b-chat").traffic["host_allocator"]
    # most prompts are longer than the largest bucket and carry their state
    # over two to eight chunks
    base = manifest.load_module("jobs", "serve_closed")
    pool = base.length_pool(job)
    prompts = sorted(p for p, _ in pool)
    assert prompts[0] >= 256 and prompts[-1] == 16384
    assert 3900 < prompts[48] < 4300
    assert [sum(p > n for p in prompts) for n in (2048, 8192, 14336)] \
        == [75, 21, 8]
    assert max(p + o for p, o in pool) <= 16384 + 1024


def test_the_configuration_is_the_catalogs(family):
    """Every key of the published ``config.json`` under its own name but
    the three cut, each with its reason; the cut is one chip's share."""
    cfg = sized(False)
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        published = next(r for r in rows
                         if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        differ = sorted(k for k, v in published["config"].items()
                        if cfg.get(k, "missing") != v)
        assert differ == sorted(cfg["reduced"])
        assert cfg["published"] == {k: published["config"][k]
                                    for k in cfg["reduced"]}
        assert cfg["source"].startswith(published["source_url"])
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["router_experts"], cfg["vocab_size"]) == (8, 64, 512, 18992)
    assert 8 * cfg["vocab_size"] == 151936
    for key in ("precision", "zero_centred_norms", "fused_projections",
                "gdn", "gdn_chunk_size", "decay_init", "conv_init", "rope",
                "routing", "init_std", "reference", "mtp"):
        assert cfg["assumed"][key]
    assert "eight" in cfg["deployment"] and "26 %" in cfg["deployment"]
    assert "".join(k[0] for k in family.reference.layer_types(cfg)) \
        == "lllflllf"
    model = family.model_config(cfg)
    assert model["experts_held"] == (0, 64)
    assert (model["n_routed_experts"], model["num_experts_per_tok"],
            model["scoring_func"], model["shared_expert_gate"],
            model["attn_head_dim"], model["num_key_value_heads"],
            model["partial_rotary_factor"], model["rope_theta"],
            model["gdn_chunk_size"], model["linear_num_value_heads"]) \
        == (512, 10, "softmax", True, 256, 2, 0.25, 1e7, 64, 32)
    # the rehearsal keeps an attention layer and three DeltaNet layers
    toy = family.reference.layer_types(sized(True))
    assert toy.count("full_attention") == 1
    assert toy.count("linear_attention") == 3
    # what the program's block does not serve is refused, not ignored
    for key, value in (("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
                       ("shared_expert_intermediate_size", 1024)):
        with pytest.raises(manifest.ManifestError):
            family.model_config(dict(cfg, **{key: value}))


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 11])
def test_sound_run_is_correct(seed, capsys):
    result = execute(seed)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    out = capsys.readouterr().out
    assert "check state_values_not_finite      0" in out
    assert "check moe_assignments_dropped      0" in out
    assert "max_prompt 256" in out and "3 executables" in out
    assert "(gdn_state, conv_state)" in out and "prefills_carried" in out
    assert "state_slot_layers" in out and "full_rows_live" in out
    # a prompt of several chunks of the largest bucket was checked: the
    # comparison sees carried state
    assert "a prompt of 256, fed in 4 chunk(s)" in out


@pytest.mark.parametrize("seed", [1, 2])
def test_int8_serving_is_not_correct(seed):
    assert execute(seed, control=True)["correct"] is False


def test_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from mxnet_tpu import serve

    step = serve.InferenceSession.step

    def altered(self, **how):
        tokens, logits = step(self, **how)
        if tokens:
            slot = min(tokens)
            tokens[slot] = (tokens[slot] + 1) % self.model.vocab_size
        return tokens, logits

    monkeypatch.setattr(serve.InferenceSession, "step", altered)
    assert execute(1)["correct"] is False


def test_a_state_update_applied_twice_is_not_correct(monkeypatch):
    """What a rematerialized update of a donated state pool does (ROADMAP
    M4 (f)): every decode step moves the state by two tokens."""
    from mxnet_tpu.serve import qwen3_next

    step = qwen3_next.gdn_step

    def twice(q, k, v, g, beta, state):
        _, state = step(q, k, v, g, beta, state)
        return step(q, k, v, g, beta, state)

    monkeypatch.setattr(qwen3_next, "gdn_step", twice)
    assert execute(1)["correct"] is False


def test_a_state_zeroed_mid_request_is_not_correct(monkeypatch):
    """Every twentieth decode step one live slot's matrix states are zeroed
    behind the program's back: the tokens it serves next are another
    model's."""
    from mxnet_tpu import serve

    step = serve.InferenceSession.step
    calls = []

    def zeroed(self, **how):
        calls.append(1)
        live = self.cache.active_slots()
        if live and len(calls) % 20 == 0:
            pool = self.cache.pools["gdn_state"]
            self.cache.pools["gdn_state"] = pool.at[:, min(live)].set(0.0)
        return step(self, **how)

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
    block = {"gdn_layers": 6, "full_layers": 2, "window_layers": 0,
             "decode_steps": 100, "distinct_held_experts": 100 * 240,
             "full_rows_live": 100 * 2 * 182400, "window_rows_in_band": 0,
             "window_rows_visited": 0}
    facts = {"step_live": [(32, 182400, 0)] * 100, "config": cfg,
             "block": block, "decode": {"steps": 100, "blocks_visited":
                                        100 * 357.5},
             "serve_config": {"slots": 32, "page_size": 16},
             "decode_module": "decode", "prefill_module": "prefill",
             "family": "qwen3_next_lm", "bench_root": BENCH,
             "prefill_tokens": [300, 3000, 16384, 5000]}
    run_ = {"facts": facts, "peaks": manifest.load_peaks("TPU v5 lite"),
            "trace": {"modules": {"jit_decode_fn(1)": (100, 100 * 0.013),
                                  "jit_prefill_fn(2)": (13, 13 * 0.070),
                                  "jit_prefill_fn(3)": (2, 2 * 0.020)}}}
    read = {name: manifest.load_module("metrics", name).read for name in (
        "gdn_decode_roofline", "gdn_prefill_roofline",
        "attn_rows_visited_ratio.serve")}
    decode = read["gdn_decode_roofline"](run_)
    assert decode == pytest.approx(
        100 * family.decode_least_bytes(cfg, 240, 32, 182400) / 819e9
        / 0.013)
    assert 55 < decode < 70
    # the prompts' operations over ALL the chunks' device time
    prefill = read["gdn_prefill_roofline"](run_)
    flops = sum(family.prefill_flops(cfg, n) for n in (300, 3000, 16384,
                                                       5000))
    assert prefill == pytest.approx(100 * flops / 197e12 / 0.950)
    assert 8 < prefill < 20
    # 357.5 blocks a slot x 16 rows x 32 slots x 2 attention layers over
    # 2 x 182 400 live rows: the kernel's reading, each slot's own pages
    ratio = read["attn_rows_visited_ratio.serve"](run_)
    assert ratio == pytest.approx(357.5 * 16 * 32 * 2 / (2 * 182400))
    assert 1.0 < ratio < 1.01
    # nothing to read is None, not an error: an untraced run, a run of
    # another block, a trace without the module, the parent's program
    for name in ("gdn_decode_roofline", "gdn_prefill_roofline"):
        assert read[name](dict(run_, trace=None)) is None
        assert read[name](dict(run_, facts=dict(facts, block={}))) is None
        assert read[name](dict(run_, facts=dict(
            facts, block={"kda_layers": 3, "decode_steps": 9}))) is None
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

    monkeypatch.delitem(serve_model.BLOCKS, "qwen3_next")
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


def test_published_init_sets_the_decays_the_taps_and_the_norms(family):
    """Every leaf as ``weights.py`` made it but the zero-centred norms'
    ``w`` (zero), ``dt_bias`` (the published 1), ``A_log`` (decays a token
    from 0.999 down to 0.2 over the 32 heads at ``a = 0``) and the
    depthwise filters, which go from 0.02 to 1 / sqrt(12)."""
    import jax.numpy as jnp
    import numpy as np

    cfg = sized(False)
    params = {"blk0_gdn_conv_weight": jnp.full((4, 4), 0.02),
              "blk0_gdn_A_log": jnp.zeros((32,)),
              "blk0_gdn_dt_bias": jnp.zeros((32,)),
              "blk0_attn_norm_weight": jnp.full((4,), 0.02),
              "blk0_gdn_o_norm_gamma": jnp.ones((4,)),
              "blk0_gdn_qkvz_weight": jnp.full((4, 4), 0.02)}
    out = family.published_init(params, cfg)
    assert float(out["blk0_gdn_conv_weight"][0, 0]) \
        == pytest.approx(12 ** -0.5)
    assert float(jnp.abs(out["blk0_attn_norm_weight"]).max()) == 0.0
    assert out["blk0_gdn_qkvz_weight"] is params["blk0_gdn_qkvz_weight"]
    assert out["blk0_gdn_o_norm_gamma"] is params["blk0_gdn_o_norm_gamma"]
    decay = np.exp(-np.exp(np.asarray(out["blk0_gdn_A_log"]))
                   * np.log1p(np.exp(np.asarray(out["blk0_gdn_dt_bias"]))))
    assert decay[0] == pytest.approx(0.999, abs=1e-5)
    assert decay[-1] == pytest.approx(0.2, abs=1e-5)
    assert (np.diff(decay) < 0).all()
    # the program's own start is the same
    from mxnet_tpu import serve
    from mxnet_tpu.serve import qwen3_next

    a_log, dt_bias = qwen3_next.decay_init(
        serve.ModelConfig(**family.model_config(cfg)))
    np.testing.assert_allclose(a_log, np.asarray(out["blk0_gdn_A_log"]),
                               rtol=1e-5)
    assert dt_bias.tolist() == [1.0] * 32


def test_counts_of_work_by_hand(family):
    """Toy sizes: d 128; 4 query heads over 2 key/value heads of 32; 2 key
    and 4 value heads of 16, 4 taps; experts of 64, 4 held of 16 routed, 4 a
    token, a shared expert of 64 behind its gate; vocabulary 2048, untied;
    published layers 0-3: gdn gdn gdn attn."""
    cfg = sized(True)
    # W_qkvz (2 x 32 + 2 x 64) x d, W_ba 8 x d, W_o d x 64, 4 taps a channel
    gdn = 192 * 128 + 8 * 128 + 128 * 64 + 128 * 4
    # W_q (query and gate) 2 x 4 x 32 x d, W_k and W_v 2 x 32 x d, W_o
    attn = 2 * 4 * 32 * 128 + 2 * 2 * 32 * 128 + 4 * 32 * 128
    expert, shared, router, head = (3 * 64 * 128, (3 * 64 + 1) * 128,
                                    16 * 128, 2048 * 128)
    assert (family.gdn_params(cfg), family.attn_params(cfg),
            family.expert_params(cfg), family.shared_params(cfg),
            family.router_params(cfg), family.head_params(cfg)) \
        == (gdn, attn, expert, shared, router, head) \
        == (34304, 65536, 24576, 24704, 2048, 262144)
    fixed = 3 * gdn + attn + 4 * (shared + router)
    assert family.fixed_params(cfg) == fixed == 275456
    # every parameter: the reference's own shapes
    assert family.n_params(cfg) == sum(
        math.prod(shape) for shape in family.reference.spec(cfg).values()) \
        == 1194248
    assert family.kv_values_per_token(cfg) == 2 * 2 * 32
    assert family.state_values_per_slot(cfg) == 4 * 16 * 16 + 3 * 128
    # a decode step: every matrix outside the experts and the head once, 7
    # held experts reached, 3 live slots' state and rows read and written
    # in 3 DeltaNet layers, 100 live rows in 1 attention layer
    assert family.decode_least_bytes(cfg, 7, 3, 100) \
        == (fixed + head + 7 * expert) * 4 \
        + (2 * 3 * 3 * 1408 + 100 * 128) * 4 == 2991104
    # one held expert a token a layer when the routing is balanced
    assert family.held_experts_per_token(cfg) == 1.0
    active = fixed + 4 * expert
    assert family.active_params_per_token(cfg) == active
    # the chunked form over 20 rows at chunks of 8: 2 x 36 + 10 pairs
    pairs = 2 * 36 + 10
    assert family.chunk_flops(cfg, 20) \
        == 4 * (2 * pairs * (32 + 32 + 16) + 6 * 20 * 16 * 16) == 175360
    assert family.attention_flops(cfg, 20) == 210 * 2 * 2 * 4 * 32
    assert family.prefill_flops(cfg, 20) \
        == 2 * 20 * active + 3 * 175360 + 210 * 512 + 2 * head == 16108288


def test_at_the_published_sizes(family):
    cfg = sized(False)
    # 7.92 GB in float32: ISSUE.md's 1 978.8 M, to the parameter
    n = family.n_params(cfg)
    assert n == sum(math.prod(shape) for shape
                    in family.reference.spec(cfg).values()) == 1978847360
    assert abs(n / 1978.8e6 - 1) < 0.001
    assert family.gdn_params(cfg) == 33718272          # 33.72 M
    assert family.attn_params(cfg) == 27262976         # 27.26 M
    assert family.expert_params(cfg) == 3145728
    assert family.kv_bytes_per_token(cfg) == 8192
    # a slot's state: 6 layers x 2.195 MB
    assert family.state_bytes_per_slot(cfg) == 13172736
    # a decode step at 32 slots holding contexts of 5 700 tokens that
    # reaches 30 held experts a layer: 1.16 GB of matrices outside the
    # experts, 0.16 of the head's slice, 3.02 of experts, 0.84 of state,
    # 1.49 of K/V rows: ISSUE.md's 6.7 GB
    least = family.decode_least_bytes(cfg, 8 * 30, 32, 32 * 5700)
    assert 6.6e9 < least < 6.8e9
    assert 0.12 < 2 * 32 * 13172736 / least < 0.13
    # a chunk of 2 048 rows from position 0: 1.3 TFLOP of matmuls (320.5 M
    # active parameters a token: 1.25 held experts a layer), 0.07 of causal
    # attention in 2 layers, 0.06 of the chunked form in 6
    assert family.active_params_per_token(cfg) == 290406400 + 10 * 3145728
    assert 1.43e12 < family.prefill_flops(cfg, 2048) < 1.45e12
    assert 6 * family.chunk_flops(cfg, 2048) < 0.06e12
    # a prompt of 16 384: attention's share grows to 4.4 of 15.4 TFLOP
    assert 15.3e12 < family.prefill_flops(cfg, 16384) < 15.5e12
    assert 4.3e12 < 2 * family.attention_flops(cfg, 16384) < 4.5e12
