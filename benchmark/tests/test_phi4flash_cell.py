"""The cell ``phi4-flash-l24-reason`` (family ``phi4flash_lm``, kind
``serve_closed_block``): it loads, rehearses on the CPU at its toy sizes
and comes out `correct`; it comes out not `correct` under its control and
when the served path is broken underneath (a served token altered, the
owner's pages left stale under the cross-attention layers, the memory
gated before the units read it, a slot admitted over the state the request before it
left); the readers of its per-layer metrics return numbers from a recorded
run and nothing from a run that has nothing for them; and the counts of
work under them are the numbers worked by hand below, at the toy and at
the published widths.

``test_manifest.py::test_every_cell_loads[phi4-flash-l24-reason]`` fails
on its pinned list of kinds (``PERF.md``, Open questions); this file loads
and rehearses the cell in its place.
"""
import json
import math
import os

import pytest

import manifest
import run

CELL = "phi4-flash-l24-reason"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(BENCH, "configs", "phi-4-mini-flash-l24.json")
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
    return manifest.load_module("families", "phi4flash_lm")


def test_the_cell_loads():
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and cell.kind == "serve_closed_block"
    assert cell.family_name == "phi4flash_lm"
    names = {e["name"] for e in cell.end_to_end}
    assert names == {"serve_tokens_per_s", "serve_ttft_p95_ms",
                     "serve_gap_p95_ms", "setup_s"}
    per_layer = {entry["name"] for entry, _ in cell.per_layer}
    assert {"yoco_decode_roofline", "yoco_prefill_roofline",
            "cross_rows_per_prefill_row.serve",
            "decode_ahead_share.serve", "decode_call_ms.serve",
            "prefill_call_ms.serve", "sched_host_ms.serve",
            "hbm_peak_gb.serve", "swa_ring_copy_ms.serve",
            # the session's own spans, which need nothing of the job
            # (not prefill_chunk_ms.serve: a fifth of this cell's chunks
            # are of the largest bucket and a 3 s stretch holds six, so
            # one traced stretch in four has none for it to read)
            "step_host_cpu_ms.serve", "decode_host_ms.serve",
            "admit_stall_ms.serve"} == per_layer
    sc = cell.traffic["serve_config"]
    assert (cell.traffic["clients"], sc["slots"], sc["page_size"],
            sc["max_new"]) == (24, 24, 16, 2048)   # ISSUE 54's fallback
    assert cell.traffic["prompt"] == {"median": 256, "sigma": 0.8,
                                      "min": 64, "max": 1024}
    assert cell.traffic["output"] == {"median": 768, "sigma": 0.6,
                                      "min": 128, "max": 2048}
    # ISSUE.md's buckets: no prompt outgrows the largest, and the prompts
    # past the smaller one (a fifth of the pool) run the larger
    assert sc["buckets"] == [512, 2048]
    assert min(sc["buckets"]) < cell.traffic["prompt"]["max"] \
        <= max(sc["buckets"])


def test_the_configuration_is_the_catalogs_but_for_depth(family):
    """Every number of the published ``config.json`` under its own key;
    depth alone reduced, and the layer list the rule's at that depth."""
    cfg = sized(False)
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 32}
    assert cfg["layer_types"] == family.layer_rule(24)
    assert [cfg["layer_types"].count(k) for k in (
        "mamba", "sliding_attention", "full_attention", "gmu",
        "cross_attention")] == [7, 6, 1, 5, 5]
    assert cfg["layer_types"][12:14] == ["mamba", "full_attention"]
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Phi-4-mini-flash-reasoning")
        differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
        assert differs == ["num_hidden_layers"]
    model = family.model_config(cfg)
    assert (model["num_heads"], model["num_key_value_heads"]) == (20, 10)
    assert model["d_model"] // model["num_heads"] == 128


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 11])
def test_sound_run_is_correct(seed, capsys):
    result = execute(seed)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    out = capsys.readouterr().out
    assert "check state_values_not_finite      0" in out
    # one row a prompt ran the second half of the stack
    window = out.split("the block in the window: ")[1].split(";")[0].split()
    block = dict(zip(window[::2], map(int, window[1::2])))
    assert block["cross_rows"] == block["prefill_chunks"] > 0
    assert block["rows_valid"] > 4 * block["cross_rows"]
    assert (block["shared_readers"], block["mamba_layers"]) == (2, 3)


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


def test_pages_left_stale_under_the_cross_layers_is_not_correct(monkeypatch):
    """The owner's decode appends lost: its own reads and the
    cross-attention layers' end at the prompt."""
    from mxnet_tpu.serve import phi4flash

    append = phi4flash.append_rows

    def lost(pools, which, layer, major, minor, rows, kv_quant=""):
        if which in ("kw", "vw") or rows.shape[0] != 4:   # 4: the slots
            append(pools, which, layer, major, minor, rows, kv_quant)

    monkeypatch.setattr(phi4flash, "append_rows", lost)
    assert execute(1)["correct"] is False


def test_the_memory_gated_before_the_units_read_it_is_not_correct(
        monkeypatch):
    """The gated memory units given a memory that a gate has already
    scaled, in the place of the scan's own output."""
    import jax

    from mxnet_tpu.serve import phi4flash

    gmu = phi4flash._gmu
    monkeypatch.setattr(
        phi4flash, "_gmu", lambda params, pre, u, memory, exact: gmu(
            params, pre, u, memory * jax.nn.silu(memory), exact))
    assert execute(1)["correct"] is False


def test_state_left_unzeroed_at_alloc_is_not_correct(monkeypatch):
    from mxnet_tpu.serve import kv_cache

    monkeypatch.setattr(kv_cache.PagedKVCache, "_scrub_state",
                        lambda self, slot: None)
    assert execute(1)["correct"] is False


def test_the_readers_read_a_recorded_run(family):
    """What ``run.py`` hands a reader, with counts and module times of the
    order this cell's first traced run on a v5e gave (PR 54: decode events
    of 26.5 ms at 32 live slots, prefill events of 27 ms over prompts of
    ~230 tokens); a CPU's trace has no device plane, so a rehearsal has
    nothing for them to read."""
    cfg = sized(False)
    block = {"shared_readers": 6, "window_layers": 6, "mamba_layers": 7,
             "decode_steps": 100, "window_rows_in_band": 100 * 6 * 15000,
             "cross_rows": 5, "rows_valid": 1500}
    facts = {"step_live": [(32, 38000)] * 100, "config": cfg, "block": block,
             "decode_module": "decode", "prefill_module": "prefill",
             "family": "phi4flash_lm", "bench_root": BENCH,
             "prefill_tokens": [128, 512, 96, 364, 400]}
    run_ = {"facts": facts, "peaks": manifest.load_peaks("TPU v5 lite"),
            "trace": {"modules": {"jit_decode_fn(1)": (100, 100 * 0.025),
                                  "jit_prefill_fn(2)": (4, 4 * 0.06),
                                  "jit_prefill_fn(3)": (1, 0.11)}}}
    read = lambda name: manifest.load_module("metrics", name).read
    decode = read("yoco_decode_roofline")(run_)
    assert decode == pytest.approx(100 * family.decode_least_bytes(
        cfg, 32, 38000, 15000) / 819e9 / 0.025)
    assert 70 < decode < 80
    prefill = read("yoco_prefill_roofline")(run_)
    flops = sum(family.prefill_flops(cfg, n) for n in facts[
        "prefill_tokens"]) / 5
    assert prefill == pytest.approx(100 * flops / 197e12 / 0.07)
    assert 5 < prefill < 10
    assert read("cross_rows_per_prefill_row.serve")(run_) \
        == pytest.approx(5 / 1500.0)
    # nothing to read is None, not an error: an untraced run, a run of
    # another block (the parent's program under these files), a trace
    # without the module or without the kernel among its operations
    for name in ("yoco_decode_roofline", "yoco_prefill_roofline"):
        assert read(name)(dict(run_, trace=None)) is None
        assert read(name)(dict(run_, facts=dict(facts, block={}))) is None
        assert read(name)(dict(run_, facts={
            "moe": {}, "step_live": [(1, 1)], "prefill_tokens": [5]})) is None
        assert read(name)(dict(run_, trace={"modules": {}})) is None
    # the rings' slices and relayouts, of the ten longest operations, a
    # decode event (the first traced run: 0.394 + 0.343 + 0.320 s of 112
    # steps, 9.4 ms a step); nothing once they are not among the ten
    ops = [["slice-done", 0.4], ["fusion.689", 0.3], ["slice", 0.3],
           ["copy", 0.3], ["paged_decode_attention_f128_p32", 0.2]]
    ring = dict(run_, trace=dict(run_["trace"], device_ops=ops))
    assert read("swa_ring_copy_ms.serve")(ring) == pytest.approx(10.0)
    assert read("swa_ring_copy_ms.serve")(run_) is None
    assert read("swa_ring_copy_ms.serve")(dict(run_, trace=None)) is None
    assert read("swa_ring_copy_ms.serve")(dict(ring, trace=dict(
        ring["trace"], device_ops=ops[1:2]))) is None
    assert read("swa_ring_copy_ms.serve")(dict(ring, facts={})) is None
    assert read("cross_rows_per_prefill_row.serve")(
        dict(run_, facts={"block": {"mamba_layers": 36}})) is None
    assert read("cross_rows_per_prefill_row.serve")({"facts": {}}) is None


def test_a_program_without_the_block_fails_at_once(monkeypatch):
    """What the driver sees on the parent commit: a ``ManifestError``
    before any weight is made (``run.execute`` turns it into exit 2)."""
    from mxnet_tpu.serve import model as serve_model
    import weights

    monkeypatch.delitem(serve_model.BLOCKS, "phi4flash")
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


def test_published_init_overwrites_the_mamba_and_lambda_leaves(family):
    import jax.numpy as jnp
    import numpy as np

    cfg = sized(True)
    spec = family.reference.spec(cfg)
    params = {k: jnp.full(shape, 0.02, jnp.float32)
              for k, shape in spec.items()}
    out = family.published_init(params, cfg)
    changed = sorted(k for k in out if out[k] is not params[k])
    want = []
    for i, kind in enumerate(cfg["layer_types"]):
        if kind == "mamba":
            want += ["blk%d_%s" % (i, leaf) for leaf in (
                "A_log", "D", "conv_weight", "dt_bias", "dt_weight")]
        elif kind != "gmu":
            want += ["blk%d_lambda_%s" % (i, leaf)
                     for leaf in ("q1", "k1", "q2", "k2")]
    assert changed == sorted(want)
    a = -np.exp(np.asarray(out["blk0_A_log"]))
    assert a.shape == (256, 4) and (a[7] == [-1, -2, -3, -4]).all()
    dt = np.log1p(np.exp(np.asarray(out["blk0_dt_bias"])))
    np.testing.assert_allclose(sorted(dt)[::len(dt) - 1], [0.001, 0.1],
                               rtol=1e-4)
    assert sorted(np.argsort(dt)) != list(np.argsort(dt))    # another order
    np.testing.assert_allclose(np.asarray(out["blk0_conv_weight"]),
                               12 ** -0.5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out["blk0_dt_weight"]),
                               24 ** -0.5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out["blk1_lambda_q1"]), 0.1,
                               rtol=1e-6)


def test_counts_of_work_by_hand(family):
    """Toy sizes: d 128, 8 heads of 16 over 4 key/value heads, SwiGLU 256,
    vocabulary 2048; Mamba-1: d_inner 256, state 4, dt_rank 8, 4 taps;
    window 16; layers m w m w m F g c."""
    cfg = sized(True)
    mamba = 2 * 256 * 128 + 256 * 4 + (8 + 8) * 256 + 256 * 8 + 256 * 4 \
        + 128 * 256
    attention = (128 + 2 * 64) * 128 + 128 * 128
    gmu, cross, mlp, head = 2 * 256 * 128, 2 * 128 * 128, 3 * 256 * 128, \
        2048 * 128
    assert (family.mamba_params(cfg), family.attention_params(cfg),
            family.gmu_params(cfg), family.cross_params(cfg),
            family.mlp_params(cfg)) == (mamba, attention, gmu, cross, mlp) \
        == (106496, 49152, 65536, 32768, 98304)
    matmul = 3 * mamba + 3 * attention + gmu + cross + 8 * mlp
    assert family.matmul_params(cfg) == matmul == 1351680
    # every parameter: the reference's own shapes
    assert family.n_params(cfg) == sum(
        math.prod(shape) for shape in family.reference.spec(cfg).values())
    # a slot: 4 + 3 rows of 256 a Mamba layer, 16 rows of K and V (2 x 64
    # values) a window layer, K and V a token in the one layer of pages
    assert family.state_bytes_per_slot(cfg) == 3 * 7 * 256 * 4 == 21504
    assert family.ring_bytes_per_slot(cfg) == 2 * 16 * 128 * 4 == 16384
    assert family.page_bytes_per_token(cfg) == 128 * 4 == 512
    # a decode step: every matrix and the head once, 3 live slots' state
    # read and written in 3 layers, 40 rows inside the band in 2 window
    # layers, 100 live rows once for each of the 2 readers of the pages
    assert family.decode_least_bytes(cfg, 3, 100, 40) \
        == (matmul + head) * 4 + 2 * 3 * 3 * 7 * 256 * 4 \
        + 40 * 2 * 512 + 100 * 2 * 512 == 6727680
    # without the band's rows: at most the live rows, at most 3 windows
    assert family.decode_least_bytes(cfg, 3, 100) \
        - family.decode_least_bytes(cfg, 3, 100, 40) == 8 * 2 * 512
    assert family.scan_flops(cfg, 40) == 7 * 40 * 256 * 4 == 286720
    assert family.scan_least_bytes(cfg, 40) \
        == (40 * (3 * 256 + 8) + 2 * 256 * 4) * 4 == 132352
    # a prefill of 40 tokens from position 0.  Every token: the three
    # Mamba and two window layers with their MLPs and the owner's K and V
    # projection; the scans; 16 + 15 + ... windows: 136 + 24 x 16 keys a
    # window layer at 6 d a key.  One token: the owner's q and o and its
    # MLP, the gated memory unit and the cross layer with theirs, 40 keys
    # for each of 2 readers, the head
    first = 3 * (mamba + mlp) + 2 * (attention + mlp) + 128 * 128
    second = (2 * 128 * 128 + mlp) + (gmu + mlp) + (cross + mlp)
    assert family.prefill_flops(cfg, 40) \
        == 2 * 40 * first + 2 * second + 3 * 286720 \
        + 2 * (136 + 24 * 16) * 6 * 128 + 2 * 40 * 6 * 128 + 2 * head \
        == 77152256
    assert family.prefill_flops(cfg, 40, offset=16) \
        - family.prefill_flops(cfg, 40) \
        == 2 * (40 * 16 - 136 - 24 * 16) * 768 + 2 * 16 * 768


def test_at_the_published_sizes(family):
    cfg = sized(False)
    assert family.n_params(cfg) == 3022860288           # 12.09 GB in float32
    assert 12.09e9 < 4 * family.n_params(cfg) < 12.1e9
    assert round(family.mamba_params(cfg) / 1e6, 2) == 41.23
    assert family.attention_params(cfg) == 19660800
    assert family.gmu_params(cfg) == 26214400
    assert family.cross_params(cfg) == 13107200
    assert family.mlp_params(cfg) == 78643200
    assert family.head_params(cfg) == 512163840
    # all 32 layers: the published 3.8 B, which one chip cannot hold
    whole = dict(cfg, num_hidden_layers=32, layer_types=family.layer_rule(32))
    assert 15.4e9 < 4 * family.n_params(whole) < 15.42e9
    # a slot: 2.72 MB of state, 31.5 MB of rings, 10 240 B a token of pages
    assert family.state_bytes_per_slot(cfg) == 2723840
    assert family.ring_bytes_per_slot(cfg) == 31457280
    assert family.page_bytes_per_token(cfg) == 10240
    # a decode step at 32 live slots holding contexts of 1 200 tokens:
    # 12.09 GB of weights, 2.36 GB of pages (six readers), 1.0 GB of
    # rings, 0.17 GB of state
    least = family.decode_least_bytes(cfg, 32, 38400)
    assert 15.6e9 < least < 15.7e9
    # a prefill of 256 tokens: 0.74 TFLOP, where every row through every
    # layer would be 1.42: the second half is 48 % of a token's products
    assert 0.74e12 < family.prefill_flops(cfg, 256) < 0.75e12
    # the scans' elementwise operations are 0.2 % of it
    assert 0.001 < 7 * family.scan_flops(cfg, 256) \
        / family.prefill_flops(cfg, 256) < 0.003
