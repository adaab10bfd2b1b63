"""The reader of the share's layout counts (``moe_dispatch_rows_ratio
.serve``: ``dispatch_rows`` over ``dispatch_held`` of ``facts["block"]``)
on the block a rehearsal of ``qwen3next-l8-longdoc`` recorded, and which
cells it is reported in."""
import json
import os

import pytest

import manifest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = "moe_dispatch_rows_ratio.serve"

# "serve: the block in the window" of `run.py --workload qwen3next-l8-
# longdoc --seed 5700000001 --seconds 3 --trace 1 --rehearse` at PR 57:
# the counts the family lists are the window's, the two dispatch_* the
# session's up to the window's close (jobs/serve_closed_long.py)
BLOCK = dict(
    assignments_asked=102576, assignments_computed=26739,
    assignments_held=26739, decode_steps=108, dispatch_held=32593,
    dispatch_rows=190336, distinct_held_experts=1241, expert_kernel_layers=0,
    expert_layers=4, experts_held=4, full_layers=1, full_rows_live=61717,
    gdn_layers=3, kv_lanes=64, prefill_chunks=117, prefills_carried=66,
    prefills_from_zero=51, rows_without_held_expert=6589,
    state_bytes_per_slot=16896, state_slot_layers=1647, window_layers=0,
    window_rows_in_band=0, window_rows_visited=0)


def read(block):
    return manifest.load_module("metrics", NAME, BENCH).read(
        {"facts": {"block": block}})


def test_the_ratio_is_rows_laid_out_over_assignments_held():
    assert read(BLOCK) == pytest.approx(190336 / 32593)
    # a layout with no padding; a second round doubles a call's rows
    assert read(dict(BLOCK, dispatch_rows=32593)) == 1.0
    assert read(dict(BLOCK, dispatch_rows=2 * 190336)) \
        == pytest.approx(2 * 190336 / 32593)


@pytest.mark.parametrize("block", [
    {k: v for k, v in BLOCK.items() if not k.startswith("dispatch_")},
    dict(BLOCK, dispatch_rows=0, dispatch_held=0), {}, None],
    ids=["the-parent", "nothing-held-yet", "no-counts", "no-block"])
def test_a_report_without_the_counts_gives_nothing(block):
    """The parent of PR 57, whose traced runs these files are laid over,
    and a block that holds no share: nothing, and nothing raised."""
    assert read(block) is None


def test_benchmark_json_lists_it_where_a_share_reports_ttft():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = json.load(f)
    entry, = [p for p in listed["per_layer"] if p["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "ratio", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "serve_ttft_p95_ms",
        "workloads": ["qwen3next-l8-longdoc", "laguna-s2.1-l5-code",
                      "lfm2-24b-l13-docqa", "sdar-30b-l12-chat"]}
    for cell in entry["workloads"]:
        assert NAME in [p["name"] for p, _ in
                        manifest.Cell(cell).per_layer]
