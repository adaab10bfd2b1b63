"""The reduction from a trace to numbers, on a small recorded trace.

``data/fit_step.trace.json`` is a cut of a real
trace of ``cgpt1.3b-fit`` on a TPU v5e (PR 23): one whole train step, the
epoch boundary's small programs after it, and the benchmark's
``update_metric`` span over the gap that follows, 2 629 device operations.
The expected numbers were worked out from the file by the plain sweep in
this test, which shares no code with ``trace_reduce``.
"""
import json
import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "fit_step.trace.json")


@pytest.fixture(scope="module")
def trace():
    with open(DATA) as f:
        return json.load(f)


def sweep_busy_ns(events):
    """Covered nanoseconds by counting open intervals at each boundary."""
    marks = sorted([(s, 1) for _, s, d in events if d > 0]
                   + [(s + d, -1) for _, s, d in events if d > 0])
    covered, depth, since = 0, 0, None
    for when, step in marks:
        if depth == 0 and step == 1:
            since = when
        depth += step
        if depth == 0:
            covered += when - since
    return covered


def test_planes_and_lines_of_the_recorded_trace(trace):
    assert [p["name"] for p in trace["planes"]] == ["/device:TPU:0",
                                                    "/host:CPU"]
    assert [p["name"] for p in tr.device_planes(trace)] == ["/device:TPU:0"]
    ops = tr.line_events(tr.device_planes(trace)[0], tr.OPS_LINE)
    assert len(ops) == 2629


def test_busy_union(trace):
    ops = tr.line_events(tr.device_planes(trace)[0], tr.OPS_LINE)
    assert sweep_busy_ns(ops) == 332336388
    assert tr.busy_seconds(trace) == pytest.approx(0.332336388, abs=1e-12)
    # no operation overlaps another in this step, so the sum agrees
    assert sum(d for _, _, d in ops) == 332336388
    # averaged over the chips used; a plane the cell does not use is left out
    assert tr.busy_seconds(trace, chips=1) == tr.busy_seconds(trace)


def test_module_times_and_the_step(trace):
    modules = tr.module_times(trace)
    step = "jit_step(6125965555010132507)"
    assert modules[step] == (1, pytest.approx(0.323926809))
    assert max(modules, key=lambda k: modules[k][1]) == step
    assert modules["jit_fn(16523057608155512145)"][0] == 50
    assert sum(c for c, _ in modules.values()) == 106


def test_top_operations_sum_a_kernels_layers(trace):
    top = tr.top_device_ops(trace, n=3)
    assert [name for name, _ in top] == [
        "flash_mha_bwd_dkv_block_q_major_128_block_q_128_block_k_major_128"
        "_block_k_128",
        "jvp_jit_flash_attention__",
        "flash_mha_bwd_dq_block_q_major_128_block_k_major_128_block_k_128"]
    # eight layers' instances of one kernel under one name
    assert top[0][1] == pytest.approx(0.05922293)
    assert tr.op_label("%fusion.849 = (bf16[2048]{0}) fusion(...)") \
        == "fusion.849"
    assert tr.op_label("%flash_mha_fwd.19 = (bf16[4]) custom-call()") \
        == "flash_mha_fwd"
    assert tr.op_label("while.45") == "while"


def test_idle_gaps_go_to_the_span_that_covers_them(trace):
    assert tr.host_spans(trace) == [("update_metric", 6026917805,
                                     7461373161)]
    gaps = dict(tr.idle_gaps(trace))
    assert gaps["update_metric"] == pytest.approx(1.146604421)
    assert gaps["host:other"] == pytest.approx(0.042679941)
    busy = tr.busy_intervals(tr.device_planes(trace)[0])
    span = (busy[-1][1] - busy[0][0]) / 1e9
    assert sum(gaps.values()) + tr.busy_seconds(trace) == pytest.approx(span)


def test_gap_attribution_prefers_the_innermost_span():
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": tr.OPS_LINE, "events": [
            ["a", 0, 10], ["b", 110, 10], ["c", 1120, 10], ["d", 1140, 10]]}]},
        {"name": "/device:TPU:1", "lines": [{"name": tr.OPS_LINE, "events": [
            ["a", 0, 40], ["inside", 10, 20], ["over", 30, 20]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench:tick", 5, 1200], ["bench:step", 20, 80],
            ["other", 0, 2000]]}]}]}
    # gap 10..110 lies in tick and in step: step is the innermost;
    # gap 120..1120 is covered by tick alone; gap 1130..1140 by tick
    gaps = tr.idle_gaps(trace)
    assert [name for name, _ in gaps] == ["tick", "step"]
    assert [s for _, s in gaps] == pytest.approx([1.01e-6, 1e-7])
    # on TPU:1 a loop's body lies inside it and another op runs over its
    # end: the union is 0..50, not the sum of 80
    assert tr.busy_seconds(trace) == pytest.approx((40 + 50) / 2 / 1e9)
    assert tr.busy_seconds(trace, chips=1) == pytest.approx(40e-9)
    summary = tr.summarize(trace, window_s=2e-6, chips=1)
    assert summary["devices"] == 2 and summary["busy_s"] == 40e-9
