"""The cell ``sdar-30b-l12-chat`` (family ``sdar_moe_lm``, kind
``serve_closed_diffusion``): it loads, rehearses on the CPU at its toy
sizes (prompts of up to two chunks, ending at every place in a block, and
outputs that are no multiples of it; a threshold that some of a toy model's
rows clear) and comes out `correct`; it comes out not `correct` under its
control and when the run is broken underneath (a served token altered, the
order of unmasking altered, a commit that keeps a denoise pass's K/V); the
readers return a number from a recorded run; and the counts of work under
them are the numbers worked by hand below.

``test_manifest.py::test_every_cell_loads[sdar-30b-l12-chat]`` fails on its
pinned list of kinds (``PERF.md``, Open questions); this file loads and
rehearses the cell in its place.
"""
import json
import math
import os

import pytest

import manifest
import run

CELL = "sdar-30b-l12-chat"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(BENCH, "configs", "sdar-30b-a3b-l12-ep8.json")
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
    return manifest.load_module("families", "sdar_moe_lm")


def test_the_cell_loads():
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and cell.kind == "serve_closed_diffusion"
    assert cell.family_name == "sdar_moe_lm"
    assert {e["name"] for e in cell.end_to_end} == {
        "serve_tokens_per_s", "serve_gap_p95_ms", "serve_ttft_p95_ms",
        "setup_s"}
    per_layer = {entry["name"] for entry, _ in cell.per_layer}
    assert per_layer == {
        "bdiff_decode_roofline", "bdiff_prefill_roofline",
        "block_passes_per_token.serve", "attn_rows_visited_ratio.serve",
        "decode_call_ms.serve", "prefill_call_ms.serve",
        "sched_host_ms.serve", "hbm_peak_gb.serve"}
    job = cell.traffic
    assert (job["clients"], job["pool"], job["warmup_requests"],
            job["check_requests"], job["check_blocks"],
            job["trace_seconds"]) == (32, 128, 32, 12, 48, 3)
    assert job["serve_config"] == dict(
        slots=32, page_size=16, buckets=[512, 2048], max_prompt=3072,
        max_new=1024, exact=False)
    assert job["prompt"] == dict(median=512, sigma=1.0, min=32, max=3072)
    assert job["output"] == dict(median=256, sigma=0.7, min=32, max=1024)
    assert (job["pairing_seed"], job["order_seed"]) == (0, 0)
    assert job["control"] == {"quant": "int8"}
    assert job["host_allocator"] == manifest.Cell(
        "cgpt1.3b-chat").traffic["host_allocator"]
    # eleven prompts are longer than the largest bucket and go in two
    # chunks; no context passes 4 096
    base = manifest.load_module("jobs", "serve_closed")
    pool = base.length_pool(job)
    prompts = sorted(p for p, _ in pool)
    assert (prompts[0], prompts[64], prompts[-1]) == (36, 517, 3072)
    assert sum(p > 2048 for p in prompts) == 11
    assert max(p + o for p, o in pool) <= 3072 + 1024 == 4096
    # the K/V pools: 32 slots x 4 096 tokens x 48 KiB
    cfg = cell.config
    assert 32 * 4096 * 12 * 2 * 4 * 128 * 4 == 6442450944
    assert cfg["mask_token_id"] == cfg["vocab_size"] - 1


def test_the_configuration_is_the_catalogs(family):
    """Every key of the published ``config.json`` under its own name but
    the three cut, each with its reason; the cut is one chip's share; what
    the config does not carry is under ``assumed``."""
    cfg = sized(False)
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        published = next(r for r in rows if r["name"] == "SDAR-30B-A3B-Chat")
        differ = sorted(k for k, v in published["config"].items()
                        if cfg.get(k, "missing") != v)
        assert differ == sorted(cfg["reduced"])
        assert cfg["published"] == {k: published["config"][k]
                                    for k in cfg["reduced"]}
        assert cfg["source"].startswith(published["source_url"])
        assert set(published["not_given"]) == {"block length",
                                               "noise schedule"}
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["router_experts"], cfg["vocab_size"]) == (12, 16, 128, 18992)
    assert 8 * cfg["vocab_size"] == 151936 and 8 * cfg["num_experts"] == 128
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["rope_theta"], cfg["rms_norm_eps"]) \
        == (2048, 32, 4, 128, 768, 8, 1000000, 1e-6)
    assert (cfg["block_length"], cfg["denoising_steps"],
            cfg["remasking_strategy"], cfg["confidence_threshold"],
            cfg["mask_token_id"]) \
        == (4, 4, "low_confidence_dynamic", 0.9, 18991)
    for key in ("precision", "head_dim", "intermediate_size", "qk_norm",
                "rope", "router", "block_length", "denoising_steps",
                "remasking_strategy", "mask_token_id", "logits",
                "mask_logit", "init"):
        assert cfg["assumed"][key]
    assert "eight" in cfg["deployment"] and "33 %" in cfg["deployment"]
    assert abs(math.comb(112, 8) / math.comb(128, 8) - 0.33) < 0.01
    model = family.model_config(cfg)
    assert model["experts_held"] == (0, 16)
    assert (model["n_routed_experts"], model["num_experts_per_tok"],
            model["scoring_func"], model["attn_head_dim"],
            model["block_length"], model["mask_token_id"],
            model["denoising_steps"], model["confidence_threshold"]) \
        == (128, 8, "softmax", 128, 4, 18991, 4, 0.9)
    # what the program's block does not serve is refused, not ignored
    for key, value in (("attention_bias", True), ("use_sliding_window", True),
                       ("tie_word_embeddings", True),
                       ("remasking_strategy", "random")):
        with pytest.raises(manifest.ManifestError, match="does not serve"):
            family.model_config(dict(cfg, **{key: value}))


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 11])
def test_sound_run_is_correct(seed, capsys):
    result = execute(seed)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    out = capsys.readouterr().out
    assert "check moe_assignments_dropped      0" in out
    assert "check streams_not_matching_requests 0" in out
    assert "max_prompt 96" in out and "3 executables" in out
    assert "blocks of 4 in 4 denoising steps at threshold 0.007" in out
    # both branches of the unmasking ran in the window
    line = out.split("the block in the window: ")[1].split(";")[0].split()
    counts = dict(zip(line[0::2], map(int, line[1::2])))
    assert counts["rows_unmasked_by_threshold"] > 0
    assert counts["rows_unmasked_by_quota"] > 0
    assert counts["slot_passes"] == counts["denoise_slot_passes"] \
        + counts["commit_slot_passes"]
    assert counts["prefill_chunks_continued"] > 0


@pytest.mark.parametrize("seed", [1, 2])
def test_int8_serving_is_not_correct(seed):
    assert execute(seed, control=True)["correct"] is False


def _altered_step(change):
    """``InferenceSession.step`` with what it hands out passed through
    ``change(pairs)`` for the lowest slot that committed."""
    from mxnet_tpu import serve

    step = serve.InferenceSession.step

    def altered(self):
        out, logits = step(self)
        committed = [slot for slot in sorted(out) if out[slot]]
        if committed:
            out[committed[0]] = change(self, out[committed[0]])
        return out, logits

    return serve.InferenceSession, "step", altered


def test_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    def one_off(session, pairs):
        token, at, confidence = pairs[0]
        return [((token + 1) % (session.model.vocab_size - 1), at,
                 confidence)] + pairs[1:]

    monkeypatch.setattr(*_altered_step(one_off))
    assert execute(1)["correct"] is False


def test_unmask_order_altered_is_not_correct(monkeypatch):
    """The tokens as the program made them, the passes handed out in
    another order: the comparison rebuilds passes with other rows visible
    than the program's had, and the rows it then holds the served tokens
    to are other rows."""
    def reversed_order(session, pairs):
        tokens, at, confidences = zip(*pairs)
        return list(zip(tokens, reversed(at), confidences))

    monkeypatch.setattr(*_altered_step(reversed_order))
    assert execute(1)["correct"] is False


def test_a_commit_that_keeps_a_denoise_passs_rows_is_not_correct(monkeypatch):
    """Every block is committed on the host as soon as it holds no mask,
    without its commit pass: the pages keep the K/V that the last denoise
    pass wrote while a row was still masked, and every later block of the
    request reads them."""
    from mxnet_tpu import serve
    from mxnet_tpu.serve import session as session_mod

    step = serve.InferenceSession.step

    def uncommitted(self):
        out, logits = step(self)
        mask, b = self.model.mask_token_id, self.model.block_length
        for slot, blk in list(self._slot_tokens.items()):
            if not out[slot] and mask not in blk.tokens:
                self.cache.lengths[slot] += b
                out[slot] = [(blk.tokens[row], blk.at[row], blk.conf[row])
                             for row in range(blk.known, b)]
                self._slot_tokens[slot] = session_mod._OpenBlock(
                    (), mask, b, blk.budget)
        return out, logits

    monkeypatch.setattr(serve.InferenceSession, "step", uncommitted)
    assert execute(1)["correct"] is False


def test_the_readers_read_a_recorded_run(family):
    """What ``run.py`` hands a reader, with counts and module times of the
    order of this cell's traced runs on a v5e; a CPU's trace has no device
    plane, so a rehearsal has nothing for the rooflines to read."""
    cfg = sized(False)
    block = {"full_layers": 12, "window_layers": 0, "decode_steps": 250,
             "distinct_held_experts": 250 * 190, "slot_passes": 250 * 32,
             "tokens_committed": 6300, "full_rows_live": 250 * 12 * 34000,
             "window_rows_in_band": 0, "window_rows_visited": 0}
    facts = {"step_live": [(32, 34000, 26)] * 250, "config": cfg,
             "block": block,
             "decode": {"steps": 250, "blocks_visited": 250 * 67.5},
             "serve_config": {"slots": 32, "page_size": 16},
             "decode_module": "block_pass", "prefill_module": "prefill",
             "family": "sdar_moe_lm", "bench_root": BENCH,
             "prefill_tokens": [300, 3000, 2, 517]}
    run_ = {"facts": facts, "peaks": manifest.load_peaks("TPU v5 lite"),
            "trace": {"modules": {"jit_block_pass_fn(1)": (250, 250 * 0.0105),
                                  "jit_prefill_fn(2)": (3, 3 * 0.035),
                                  "jit_prefill_fn(3)": (2, 2 * 0.010)}}}
    read = {name: manifest.load_module("metrics", name).read for name in (
        "bdiff_decode_roofline", "bdiff_prefill_roofline",
        "block_passes_per_token.serve", "attn_rows_visited_ratio.serve")}
    decode = read["bdiff_decode_roofline"](run_)
    assert decode == pytest.approx(
        100 * family.decode_least_bytes(cfg, 190, 34000, 32) / 819e9
        / 0.0105)
    assert 65 < decode < 80
    # the prompts' operations over ALL the chunks' device time
    prefill = read["bdiff_prefill_roofline"](run_)
    flops = sum(family.prefill_flops(cfg, n) for n in (300, 3000, 2, 517))
    assert prefill == pytest.approx(100 * flops / 197e12 / 0.125)
    assert 5 < prefill < 15
    assert read["block_passes_per_token.serve"](run_) \
        == pytest.approx(8000 / 6300)
    # the kernel's reading: each slot's own pages, 67.5 a slot in the mean
    ratio = read["attn_rows_visited_ratio.serve"](run_)
    assert ratio == pytest.approx(67.5 * 16 * 32 * 12 / (12 * 34000))
    assert 1.0 < ratio < 1.03
    # nothing to read is None, not an error: an untraced run, a run of
    # another block, a trace without the module, the parent's program
    for name in ("bdiff_decode_roofline", "bdiff_prefill_roofline"):
        assert read[name](dict(run_, trace=None)) is None
        assert read[name](dict(run_, facts=dict(facts, block={}))) is None
        assert read[name](dict(run_, facts=dict(
            facts, block={"conv_layers": 3, "decode_steps": 9}))) is None
        assert read[name](dict(run_, facts={
            "moe": {}, "step_live": [(1, 1)], "prefill_tokens": [5]})) is None
        assert read[name](dict(run_, trace={"modules": {}})) is None
    assert read["block_passes_per_token.serve"](
        dict(run_, facts={"block": {"decode_steps": 9}})) is None
    assert read["block_passes_per_token.serve"](dict(run_, facts={})) is None


def test_a_program_without_the_block_fails_at_once(monkeypatch):
    """What the driver sees on the parent commit: a ``ManifestError``
    before any weight is made and before ``ModelConfig`` is asked for
    ``block_length`` (``run.execute`` turns it into exit 2)."""
    from mxnet_tpu import serve
    from mxnet_tpu.serve import model as serve_model
    import weights

    monkeypatch.delitem(serve_model.BLOCKS, "sdar_moe")
    monkeypatch.setattr(weights, "maker", lambda *a, **k: pytest.fail(
        "weights were made"))
    monkeypatch.setattr(serve, "ModelConfig", lambda *a, **k: pytest.fail(
        "a ModelConfig was built"))
    with pytest.raises(SystemExit) as exit_info:
        execute(1)
    assert exit_info.value.code == 2


def test_training_names_say_served_not_trained(family):
    for name in ("symbol", "batches", "items_per_row", "grad_scale",
                 "train_flops_per_item", "output_bytes_per_row"):
        with pytest.raises(manifest.ManifestError, match="not yet trained"):
            getattr(family, name)(sized(True))


def test_counts_of_work_by_hand(family):
    """Toy sizes: d 128; 4 query heads over 2 key/value heads of 32;
    experts of 64, 2 held of 8 routed, 4 a token; vocabulary 2048, an
    untied head; 2 layers; blocks of 4."""
    cfg = sized(True)
    # W_q and W_o of 4 x 32 x 128, W_k and W_v of 2 x 32 x 128, two norms
    attn = 2 * 4 * 32 * 128 + 2 * 2 * 32 * 128 + 2 * 32
    expert, router, head = 3 * 64 * 128, 8 * 128, 2048 * 128
    assert (family.attention_params(cfg), family.expert_params(cfg),
            family.router_params(cfg), family.head_params(cfg)) \
        == (attn, expert, router, head) == (49216, 24576, 1024, 262144)
    fixed = 2 * (attn + router)
    assert family.fixed_params(cfg) == fixed == 100480
    # every parameter: the reference's own shapes
    assert family.n_params(cfg) == sum(
        math.prod(shape) for shape in family.reference.spec(cfg).values()) \
        == 2 * head + fixed + 5 * 128 + 2 * 2 * expert == 723712
    assert family.kv_values_per_token(cfg) == 2 * 2 * 32
    # a block pass: every matrix outside the experts and the head once, 3
    # held experts reached, 100 rows inside 3 live slots' horizons read
    # and the 3 blocks' 12 rows written, in 2 layers
    assert family.decode_least_bytes(cfg, 3, 100, 3) \
        == (fixed + head + 3 * expert) * 4 + 2 * (100 + 12) * 128 * 4 \
        == 1860096
    # one held expert a token a layer when the routing is balanced
    assert family.held_experts_per_token(cfg) == 1.0
    active = fixed + 2 * expert
    assert family.active_params_per_token(cfg) == active
    # a prompt of 22 tokens: 5 whole blocks are prefilled; block j's 4
    # rows see 4 (j + 1) keys: 16 x 15
    assert family.prefilled_tokens(cfg, 22) == 20
    assert family.block_causal_keys(cfg, 20) == 16 * 15 == 240
    assert (family.prefilled_tokens(cfg, 3),
            family.prefill_flops(cfg, 3)) == (0, 0)
    # 2 a row an active parameter of the first layer and the last layer's
    # K and V alone; 240 pairs over 4 heads of 32 for scores and 32 for
    # values in the first layer; no head
    assert family.prefill_flops(cfg, 22) \
        == 2 * 20 * (active / 2 + 2 * 2 * 32 * 128) + 2 * 2 * 32 * 4 * 240 \
        == 3770880


def test_at_the_published_sizes(family):
    cfg = sized(False)
    # 4.85 GB in float32: ISSUE.md's 1 213.5 M
    n = family.n_params(cfg)
    assert n == sum(math.prod(shape) for shape
                    in family.reference.spec(cfg).values()) == 1213453312
    assert abs(n / 1213.5e6 - 1) < 0.001
    assert family.attention_params(cfg) == 18874624
    assert family.expert_params(cfg) == 4718592
    assert family.kv_values_per_token(cfg) * 4 * 12 == 48 * 1024
    # a pass at 32 slots holding contexts of 1 050 tokens that reaches all
    # 16 held experts a layer: 0.92 GB of matrices outside the experts,
    # 0.16 of the head's slice, 3.62 of experts, 1.66 of K/V rows
    least = family.decode_least_bytes(cfg, 12 * 16, 32 * 1050, 32)
    assert 6.3e9 < least < 6.4e9
    assert 0.25 < 12 * 32 * 1050 * 4096 / least < 0.27    # K/V: a quarter
    # a prompt of 3 072 tokens: 1.6 TFLOP of matmuls in 11 layers and the
    # last layer's K and V (286.3 M active parameters a token: one held
    # expert a layer), 0.85 of block-causal attention
    assert family.active_params_per_token(cfg) == 229641216 + 12 * 4718592
    flops = family.prefill_flops(cfg, 3072)
    assert 2.4e12 < flops < 2.5e12
