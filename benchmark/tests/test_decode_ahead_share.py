"""The reader of what PR 53 put on the program's ``session.step`` spans
(``decode_ahead_share.serve``: the span's ``ahead``) on hand-written
records, and which cells it is reported in."""
import collections
import json
import os

import pytest

import manifest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = "decode_ahead_share.serve"

# what mxnet_tpu.profiler.spans() hands out
Rec = collections.namedtuple("Rec", "id parent name start_s end_s attrs cpu_s",
                             defaults=(None,))


def reader():
    return manifest.load_module("metrics", NAME, BENCH)


def step(first_id, t0, ahead, wait_ms=10.0):
    """One ``session.step`` at ``t0``: a launch, a token read, and the
    span itself with ``ahead`` (left out for ``None``, as the parent's
    and a diffusion block's spans leave it out)."""
    attrs = {"live": 5}
    if ahead is not None:
        attrs["ahead"] = ahead
    launch, wait = 1e-3, wait_ms / 1e3
    return [
        Rec(first_id + 1, first_id, "step.launch", t0, t0 + launch, {}),
        Rec(first_id + 2, first_id, "step.wait", t0 + launch,
            t0 + launch + wait, {}, 2e-4),
        Rec(first_id, None, "session.step", t0, t0 + launch + wait, attrs,
            1.2e-3),
    ]


def steps(pattern):
    return [r for i, ahead in enumerate(pattern)
            for r in step(10 * (i + 1), 1.0 + 0.02 * i, ahead)]


def test_the_share_is_the_steps_that_ran_ahead_over_all_of_them():
    read = reader().value
    assert read(steps([1, 1, 1, 0, 1, 1, 0, 1])) == pytest.approx(0.75)
    assert read(steps([0, 0, 0])) == 0.0
    assert read(steps([1, 1])) == 1.0
    # the other spans of a tick count nothing
    others = [Rec(900, None, "serve.tick", 0.5, 0.6, {"live": 3}),
              Rec(901, 900, "session.prefill", 0.5, 0.55, {"slot": 1})]
    assert read(steps([1, 0]) + others) == pytest.approx(0.5)


@pytest.mark.parametrize("records", [
    [], steps([None, None, None]),
    [Rec(1, None, "serve.tick", 0.5, 0.6, {"live": 3})]],
    ids=["no-spans", "no-attribute", "no-step"])
def test_spans_that_carry_no_such_attribute_give_nothing(records):
    """The parent of PR 53 and a diffusion block's pass: the files are
    laid over the parent, whose traced runs have to end all the same."""
    assert reader().value(records) is None


def test_a_step_without_the_attribute_is_left_out_of_the_share():
    assert reader().value(steps([1, None, 0, None])) == pytest.approx(0.5)


def test_read_asks_the_program_for_the_window(monkeypatch):
    from mxnet_tpu import profiler

    mod, asked = reader(), []
    monkeypatch.setattr(
        profiler, "spans",
        lambda name=None, since=None, until=None:
        asked.append((since, until)) or steps([1, 1, 0, 1]))
    assert mod.read({"window": (10.0, 13.0)}) == pytest.approx(0.75)
    assert asked == [(10.0, 13.0)]
    monkeypatch.delattr(profiler, "spans")
    assert mod.read({"window": (10.0, 13.0)}) is None


def test_the_metric_is_reported_in_exactly_the_two_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["per_layer"][-1]["name"] == NAME
    found = []
    for cell in (w["name"] for w in bench["workloads"]):
        for entry, mod in manifest.Cell(cell).per_layer:
            if entry["name"] == NAME:
                assert entry["source"] == "program_span"
                assert (entry["layer"], entry["unit"], entry["better"]) \
                    == (mod.LAYER, mod.UNIT, "higher")
                found.append(cell)
    assert found == ["cgpt1.3b-chat", "lfm2-24b-l13-docqa"]
