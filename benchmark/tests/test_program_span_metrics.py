"""The two readers of the program's own spans (``decode_host_ms.serve``,
``admit_stall_ms.serve``) on hand-written records, and which cell they
are reported in."""
import collections
import json
import os

import pytest

import manifest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

# what mxnet_tpu.profiler.spans() hands out
Rec = collections.namedtuple("Rec", "id parent name start_s end_s attrs")


def reader(name):
    return manifest.load_module("metrics", name, BENCH)


def tick_with_two_admissions_behind_three_live_slots():
    """One tick, 100 ms: two admissions (20 ms and 30 ms, their prefills
    starting 1 ms and 2 ms in), then a step of 17 ms whose wait is 15."""
    return [
        Rec(3, 2, "session.prefill", 0.002, 0.020, {"slot": 3}),
        Rec(2, 1, "serve.admit", 0.001, 0.021,
            {"rid": 7, "slot": 3, "resume": 0, "queued_ms": 4.0}),
        Rec(5, 4, "session.prefill", 0.023, 0.050, {"slot": 4}),
        Rec(4, 1, "serve.admit", 0.021, 0.051,
            {"rid": 8, "slot": 4, "resume": 0, "queued_ms": 24.0}),
        Rec(7, 6, "step.prepare", 0.052, 0.0525, {}),
        Rec(8, 6, "step.launch", 0.0525, 0.0535, {}),
        Rec(9, 6, "step.wait", 0.0535, 0.0685, {}),
        Rec(10, 6, "step.commit", 0.0685, 0.069, {}),
        Rec(6, 1, "session.step", 0.052, 0.069, {"live": 5}),
        Rec(1, None, "serve.tick", 0.0, 0.100,
            {"live": 3, "admitted": 2, "finished": 0}),
    ]


def test_decode_host_is_the_step_less_its_wait():
    read = reader("decode_host_ms.serve").value
    assert read(tick_with_two_admissions_behind_three_live_slots()) \
        == pytest.approx(2.0)
    # a step whose wait is missing is skipped, not counted whole
    cut = [Rec(21, 20, "step.commit", 0.2, 0.201, {}),
           Rec(20, None, "session.step", 0.19, 0.201, {"live": 5})]
    assert read(cut) is None
    assert read(tick_with_two_admissions_behind_three_live_slots() + cut) \
        == pytest.approx(2.0)


def test_admit_stall_sums_a_ticks_admissions_behind_live_slots():
    read = reader("admit_stall_ms.serve").value
    records = tick_with_two_admissions_behind_three_live_slots()
    assert read(records) == pytest.approx(50.0)
    # a tick that began with nothing live stalls nobody
    idle = [r._replace(attrs=dict(r.attrs, live=0)) if r.name == "serve.tick"
            else r for r in records]
    assert read(idle) is None
    # ticks that admit nobody count as 0: 19 of them put the 95th
    # percentile of 20 ticks on a quiet one
    quiet = [Rec(100 + i, None, "serve.tick", 1.0 + i, 1.5 + i, {"live": 4})
             for i in range(19)]
    assert read(records + quiet) == 0.0
    assert read(records + quiet[:18]) == pytest.approx(50.0)
    # an admission that found no room is not a stall of its length
    full = [Rec(201, 200, "serve.admit", 3.0, 3.5, {"rid": 9, "slot": -1}),
            Rec(200, None, "serve.tick", 3.0, 3.6, {"live": 16})]
    assert read(full) == 0.0


@pytest.mark.parametrize("name", [
    "decode_host_ms.serve", "admit_stall_ms.serve"])
def test_no_spans_give_nothing(name, monkeypatch):
    from mxnet_tpu import profiler

    mod = reader(name)
    assert mod.value([]) is None
    # the window is what read() asks the program for
    asked = []
    monkeypatch.setattr(
        profiler, "spans",
        lambda name=None, since=None, until=None:
        asked.append((since, until)) or [])
    assert mod.read({"window": (10.0, 13.0)}) is None
    assert asked == [(10.0, 13.0)]
    # a program that has no spans at all (the parent commit)
    monkeypatch.delattr(profiler, "spans")
    assert mod.read({"window": (10.0, 13.0)}) is None


def test_the_metrics_are_reported_where_no_test_pins_the_cells_list():
    """The readers find spans in every serving cell; the manifest lists
    the one whose per-layer list ``benchmark/tests`` leaves open (PERF.md
    section 7 (n)): a later PR appends the others to ``workloads``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    found = {"decode_host_ms.serve": [], "admit_stall_ms.serve": []}
    for name in cells:
        for entry, _ in manifest.Cell(name).per_layer:
            if entry["name"] in found:
                assert entry["source"] == "program_span"
                found[entry["name"]].append(name)
    assert found == {"decode_host_ms.serve": ["cgpt1.3b-chat"],
                     "admit_stall_ms.serve": ["cgpt1.3b-chat"]}
