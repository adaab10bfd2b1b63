"""The two readers of what PR 52 put on the program's spans
(``step_host_cpu_ms.serve``: a span's ``cpu_s``; ``prefill_chunk_ms.serve``:
a ``prefill.launch``'s ``bucket`` and ``largest``) on hand-written records,
and which cells they are reported in."""
import collections
import json
import os

import pytest

import manifest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAMES = ("step_host_cpu_ms.serve", "prefill_chunk_ms.serve")

# what mxnet_tpu.profiler.spans() hands out, and what it handed out at the
# parent commit
Rec = collections.namedtuple("Rec", "id parent name start_s end_s attrs cpu_s",
                             defaults=(None,))
Old = collections.namedtuple("Old", "id parent name start_s end_s attrs")


def reader(name):
    return manifest.load_module("metrics", name, BENCH)


def step(first_id, t0, host_ran_ms, host_waited_ms, wait_ms=15.0,
         wait_ran_ms=0.5):
    """One ``session.step`` at ``t0``: a launch in which the thread ran
    ``host_ran_ms`` and waited ``host_waited_ms``, then a token read of
    ``wait_ms`` in which it ran ``wait_ran_ms``.  Only the step and its
    read carry ``cpu_s``, as in the program."""
    launch = (host_ran_ms + host_waited_ms) / 1e3
    wait = wait_ms / 1e3
    return [
        Rec(first_id + 1, first_id, "step.launch", t0, t0 + launch, {}),
        Rec(first_id + 2, first_id, "step.wait", t0 + launch,
            t0 + launch + wait, {}, wait_ran_ms / 1e3),
        Rec(first_id, None, "session.step", t0, t0 + launch + wait,
            {"live": 5}, (host_ran_ms + wait_ran_ms) / 1e3),
    ]


def three_steps():
    return (step(10, 1.0, 0.75, 0.5) + step(20, 2.0, 1.0, 1.5)
            + step(30, 3.0, 0.875, 0.25))


def test_ran_is_the_step_less_its_wait_and_a_part_of_the_host_time():
    records = three_steps()
    ran = reader("step_host_cpu_ms.serve").value(records)
    # the mean over these three steps, whose hosts are 1.25, 2.5 and
    # 1.125 ms: the thread ran 0.875 of them and waited the rest
    assert ran == pytest.approx(0.875)
    assert ran < reader("decode_host_ms.serve").value(records) == \
        pytest.approx(1.25)
    # a thread that never left the core: ran is the host time
    busy = [r for i in range(20)
            for r in step(100 + 10 * i, 10.0 + i, 1.0 + 0.01 * (i % 3), 0.0)]
    assert reader("step_host_cpu_ms.serve").value(busy) == pytest.approx(
        reader("decode_host_ms.serve").value(busy), rel=0.01)


def test_a_clock_that_ticks_in_ten_milliseconds_reads_right_in_the_mean():
    """The chip's host: a step's ``cpu_s`` is 0 or 10 ms.  Of 200 steps
    whose thread ran 1 ms outside the read, 20 caught a tick there."""
    records = []
    for i in range(200):
        tick = 10.0 if i % 10 == 3 else 0.0
        one = step(1000 + 10 * i, 100.0 + i, 0.0, 1.5, wait_ran_ms=0.0)
        one[2] = one[2]._replace(cpu_s=tick / 1e3)
        records += one
    assert reader("step_host_cpu_ms.serve").value(records) \
        == pytest.approx(1.0)


def test_a_step_without_its_wait_is_skipped():
    read = reader("step_host_cpu_ms.serve").value
    cut = [Rec(51, 50, "step.commit", 5.0, 5.001, {}),
           Rec(50, None, "session.step", 4.99, 5.001, {"live": 5}, 0.011)]
    assert read(cut) is None
    assert read(three_steps() + cut) == pytest.approx(read(three_steps()))


def old(records):
    return [Old(*r[:6]) for r in records]


def prefill(first_id, t0, buckets, chunk_ms=30.0, launch_ms=2.0,
            attrs=True):
    """One ``session.prefill`` at ``t0`` of ``len(buckets)`` chunks of a
    session whose largest bucket is 2048: the launches one after the
    other, then a wait that ends ``chunk_ms`` a chunk after the first
    launch began, then a publish."""
    out, t = [], t0 + 1e-4
    for i, bucket in enumerate(buckets):
        out.append(Rec(first_id + 1 + i, first_id, "prefill.launch", t,
                       t + launch_ms / 1e3,
                       {"bucket": bucket, "largest": 2048} if attrs else {}))
        t += launch_ms / 1e3
    end = t0 + 1e-4 + len(buckets) * chunk_ms / 1e3
    n = first_id + 1 + len(buckets)
    out.append(Rec(n, first_id, "prefill.wait", t, end, {}))
    out.append(Rec(n + 1, first_id, "prefill.publish", end, end + 1e-4, {}))
    out.append(Rec(first_id, None, "session.prefill", t0, end + 2e-4,
                   {"slot": 3, "bucket": buckets[-1],
                    "chunks": len(buckets)}))
    return out


def test_a_chunk_is_a_large_bucket_prefill_over_its_chunks():
    read = reader("prefill_chunk_ms.serve").value
    one = prefill(100, 1.0, [2048], chunk_ms=30.0)
    two = prefill(200, 2.0, [2048, 2048], chunk_ms=32.0)
    assert read(one) == pytest.approx(30.0)
    # two chunks of the large bucket count half their wall a chunk
    assert read(two) == pytest.approx(32.0)
    assert read(one + two) == pytest.approx(31.0)
    # mixed buckets, and the small bucket alone, are left out
    mixed = prefill(300, 3.0, [2048, 512], chunk_ms=20.0)
    small = prefill(400, 4.0, [512], chunk_ms=14.0)
    assert read(one + two + mixed + small) == pytest.approx(31.0)
    assert read(mixed) is None
    # a stretch that held the small bucket only reads nothing: never the
    # small bucket's chunk under the large one's name
    assert read(small) is None
    # a prefill cut off before its token read, and a diffusion block's
    # prompt shorter than a block (no chunk), are skipped
    cut = [r for r in prefill(500, 5.0, [2048], chunk_ms=99.0)
           if r.name != "prefill.wait"]
    none = [r for r in prefill(600, 6.0, [2048], chunk_ms=99.0)
            if r.name != "prefill.launch"]
    assert read(one + cut + none[1:]) == pytest.approx(30.0)


@pytest.mark.parametrize("name", NAMES)
def test_records_of_the_parent_commit_give_nothing(name, monkeypatch):
    """A check lays these files over the parent, whose ``SpanRecord`` has
    six fields and whose ``prefill.launch`` carries no attribute: its
    traced runs have to end all the same."""
    from mxnet_tpu import profiler

    mod = reader(name)
    assert mod.value([]) is None
    records = old(three_steps() + prefill(100, 1.0, [2048], attrs=False))
    assert mod.value(records) is None
    # a platform without the thread's CPU clock records None
    blind = [r._replace(cpu_s=None) for r in three_steps()]
    if name == "step_host_cpu_ms.serve":
        assert mod.value(blind) is None
    # the window is what read() asks the program for
    asked = []
    monkeypatch.setattr(
        profiler, "spans",
        lambda name=None, since=None, until=None:
        asked.append((since, until)) or records)
    assert mod.read({"window": (10.0, 13.0)}) is None
    assert asked == [(10.0, 13.0)]
    monkeypatch.delattr(profiler, "spans")
    assert mod.read({"window": (10.0, 13.0)}) is None


def test_each_metric_is_reported_in_exactly_the_two_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    found = {name: [] for name in NAMES}
    for cell in cells:
        for entry, _ in manifest.Cell(cell).per_layer:
            if entry["name"] in found:
                assert entry["source"] == "program_span"
                assert entry["layer"] == "step program"
                found[entry["name"]].append(cell)
    assert found == {name: ["cgpt1.3b-chat", "lfm2-24b-l13-docqa"]
                     for name in NAMES}
