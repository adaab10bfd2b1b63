"""The reader of what PR 55 put on a diffusion block's ``session.step``
spans (``block_ahead_share.serve``: the span's ``ahead``, which a block
pass's call carries since it runs ahead) on hand-written records, and
which cell it is reported in."""
import collections
import json
import os

import pytest

import manifest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = "block_ahead_share.serve"

# what mxnet_tpu.profiler.spans() hands out
Rec = collections.namedtuple("Rec", "id parent name start_s end_s attrs cpu_s",
                             defaults=(None,))


def reader():
    return manifest.load_module("metrics", NAME, BENCH)


def block_pass(first_id, t0, ahead, wait_ms=10.0):
    """One ``session.step`` of a diffusion session at ``t0``: a launch, the
    read of the pass's rows, and the span itself with what the pass held
    and ``ahead`` (left out for ``None``, as the parent's spans leave it
    out)."""
    attrs = {"live": 32, "denoise": 25, "commit": 7}
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


def passes(pattern):
    return [r for i, ahead in enumerate(pattern)
            for r in block_pass(10 * (i + 1), 1.0 + 0.02 * i, ahead)]


@pytest.mark.parametrize("pattern, share", [
    ([1, 1, 1, 0, 1, 1, 0, 1], 0.75), ([0, 0, 0], 0.0), ([1, 1], 1.0),
    # one finish every eleven passes, as the cell's traffic has it
    ([1] * 10 + [0], 10 / 11)],
    ids=["three-in-four", "none", "all", "a-finish-in-eleven"])
def test_the_share_is_the_passes_that_ran_ahead_over_all_of_them(pattern,
                                                                 share):
    assert reader().value(passes(pattern)) == pytest.approx(share)


def test_the_other_spans_of_a_tick_count_nothing():
    others = [Rec(900, None, "serve.tick", 0.5, 0.6, {"live": 3}),
              Rec(901, 900, "session.prefill", 0.5, 0.55, {"slot": 1}),
              # a launch ahead is a child, not a call
              Rec(902, 10, "step.prepare", 1.0, 1.0005, {})]
    assert reader().value(passes([1, 0]) + others) == pytest.approx(0.5)


@pytest.mark.parametrize("records", [
    [], passes([None, None, None]),
    [Rec(1, None, "serve.tick", 0.5, 0.6, {"live": 3})]],
    ids=["an-empty-window", "no-attribute", "no-step"])
def test_spans_that_carry_no_such_attribute_give_nothing(records):
    """The parent of PR 55, whose block pass never ran ahead and whose span
    carried ``live``, ``denoise`` and ``commit`` alone: the files are laid
    over the parent, whose traced runs have to end all the same."""
    assert reader().value(records) is None


def test_a_pass_without_the_attribute_is_left_out_of_the_share():
    assert reader().value(passes([1, None, 0, None])) == pytest.approx(0.5)


def test_read_asks_the_program_for_the_window(monkeypatch):
    from mxnet_tpu import profiler

    mod, asked = reader(), []
    monkeypatch.setattr(
        profiler, "spans",
        lambda name=None, since=None, until=None:
        asked.append((since, until)) or passes([1, 1, 0, 1]))
    assert mod.read({"window": (10.0, 13.0)}) == pytest.approx(0.75)
    assert asked == [(10.0, 13.0)]
    monkeypatch.delattr(profiler, "spans")
    assert mod.read({"window": (10.0, 13.0)}) is None


def test_the_metric_is_reported_in_the_diffusion_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [e for e in bench["per_layer"] if e["name"] == NAME]
    assert entry["workloads"] == ["sdar-30b-l12-chat"]
    assert entry["moves"] == "serve_tokens_per_s"
    found = []
    for cell in (w["name"] for w in bench["workloads"]):
        for listed, mod in manifest.Cell(cell).per_layer:
            if listed["name"] == NAME:
                assert listed["source"] == "program_span"
                assert (listed["layer"], listed["unit"], listed["better"]) \
                    == (mod.LAYER, mod.UNIT, "higher")
                found.append(cell)
    assert found == ["sdar-30b-l12-chat"]
