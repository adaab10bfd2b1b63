"""The program's own host spans (``mxnet_tpu.profiler.span``, and the
sites on ``Scheduler.tick``, the session's calls and ``fit``'s batch;
docs/performance.md, "Spans"): free when off, whole and nested when on,
in the profiler's trace while a session runs, bounded."""
import collections
import glob
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler, serve
from mxnet_tpu.serve import model as serve_model

from serve_util import lend
from test_serve_blocks import SDAR

CFG = serve.ModelConfig(vocab_size=61, num_layers=2, d_model=32,
                        num_heads=2, max_len=64)
PAGE = 8

SERVE_TREE = {
    "serve.tick": {"serve.admit", "session.step", "serve.finish"},
    "serve.admit": {"session.prefill"},
    "serve.finish": set(),
    "session.prefill": {"prefill.launch", "prefill.wait", "prefill.publish"},
    "session.step": {"step.prepare", "step.launch", "step.wait",
                     "step.commit"},
}
FIT_TREE = {
    "fit.batch": {"fit.forward_backward", "fit.update", "fit.next_batch",
                  "fit.update_metric", "fit.callbacks"},
    "fit.forward_backward": {"trainstep.stage", "trainstep.hygiene",
                             "trainstep.launch", "fit.adopt"},
}


def names_of(tree):
    return set(tree).union(*tree.values())


@pytest.fixture(autouse=True)
def _spans_off_and_empty():
    profiler.record_spans(False)
    profiler.clear_spans()
    yield
    profiler.record_spans(False)
    profiler.clear_spans()


@pytest.fixture(scope="module")
def params():
    return serve_model.init_params(CFG, seed=3)


@pytest.fixture(scope="module")
def _session(params):
    sconf = serve.ServeConfig(slots=3, page_size=PAGE, buckets=(8, 16),
                              max_new=8, exact=True)
    return serve.InferenceSession(params, num_heads=CFG.num_heads,
                                  config=sconf)


@pytest.fixture
def session(_session):
    yield from lend(_session)


@pytest.fixture(scope="module")
def _block_session():
    """A diffusion block's session: its step is a block pass."""
    sconf = serve.ServeConfig(slots=3, page_size=PAGE, buckets=(8, 16),
                              max_new=8)
    return serve.InferenceSession(serve.init_params(SDAR, seed=3, scale=0.3),
                                  model=SDAR, config=sconf)


@pytest.fixture
def stepped(request):
    """The session of a decode step, or of a block pass: both step by
    the one ``InferenceSession.step``."""
    yield from lend(request.getfixturevalue(
        {"decode": "_session", "block_pass": "_block_session"}[
            request.param]))


both_steps = pytest.mark.parametrize("stepped", ["decode", "block_pass"],
                                     indirect=True)


def requests(n=6, seed=0, spread_s=0.0, below=61):
    rng = np.random.default_rng(seed)
    return [serve.Request(rid=i,
                          prompt=rng.integers(0, below, 5 + i).tolist(),
                          max_new=3 + i % 4, arrival_s=spread_s * i)
            for i in range(n)]


def fit_three_batches():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.default_rng(0)
    train = mx.io.NDArrayIter(
        rng.normal(size=(24, 8)).astype("float32"),
        rng.integers(0, 4, 24).astype("float32"), batch_size=8)
    mod = mx.mod.Module(net)
    seen = []
    mod.fit(train, num_epoch=1, optimizer="sgd",
            batch_end_callback=lambda p: seen.append(p.nbatch))
    assert seen == [0, 1, 2]
    assert type(mod._fused).__name__ == "TrainStep"


def by_id(records):
    return {r.id: r for r in records}


def assert_whole_and_nested(records, tree):
    """Only the tree's names, every child under the parent the tree
    gives it and inside its interval."""
    assert {r.name for r in records} == names_of(tree)
    index = by_id(records)
    for r in records:
        if r.parent is None:
            assert r.name in ("serve.tick", "fit.batch"), r
            continue
        parent = index[r.parent]
        assert r.name in tree[parent.name], (parent.name, r.name)
        assert parent.start_s <= r.start_s <= r.end_s <= parent.end_s, r


# -- off ----------------------------------------------------------------------

class CountedAnnotation(object):
    """Stands in for ``TraceAnnotation``: no session, every use counted."""

    made = asked = 0

    def __init__(self, *args, **kwargs):
        type(self).made += 1

    @classmethod
    def is_enabled(cls):
        cls.asked += 1
        return False


def test_off_the_sites_read_no_clock_and_make_no_annotation(
        session, monkeypatch):
    clock = []
    monkeypatch.setattr(profiler, "_perf_counter",
                        lambda: clock.append(1) or 0.0)
    monkeypatch.setattr(CountedAnnotation, "made", 0)
    monkeypatch.setattr(CountedAnnotation, "asked", 0)
    monkeypatch.setattr(profiler, "TraceAnnotation", CountedAnnotation)
    steps = session.decode_report()["steps"]
    done, _ = serve.Scheduler(session).run(requests())
    assert all(len(r.tokens) == r.max_new for r in done)
    steps = session.decode_report()["steps"] - steps
    crossed = CountedAnnotation.asked
    # a step, its four segments and the tick around it at the very least
    assert steps > 0 and crossed >= 6 * steps
    fit_three_batches()
    assert CountedAnnotation.asked >= crossed + 3 * 10
    assert clock == [] and CountedAnnotation.made == 0
    assert profiler.spans() == [] and profiler.spans_dropped() == 0


def test_off_a_tick_and_a_step_read_neither_clock(session, monkeypatch):
    """Off, a site hands out the shared handle: no ``_Span`` is made and
    neither the wall clock nor the thread's CPU clock is read, by a
    ``Scheduler`` tick or by a bare ``session.step``."""
    def never(*args, **kwargs):
        raise AssertionError("a span site that is off touched this")

    for name in ("_perf_counter", "_thread_time", "_Span"):
        monkeypatch.setattr(profiler, name, never)
    sched = serve.Scheduler(session)
    sched.begin(requests(2))
    while sched.tick():
        pass
    assert sched.stats["finished"] == 2
    slot = session.try_alloc(5, 2)
    session.prefill(slot, [1, 2, 3, 4, 5])
    tokens, _ = session.step()
    assert slot in tokens
    assert profiler.spans() == []


def test_off_the_handle_is_shared_and_takes_what_it_is_given():
    with profiler.span("a", x=1) as first:
        first.set(slot=3)
    with profiler.span("b") as second:
        pass
    assert first is second and not first.on
    assert profiler.spans() == []


# -- on: record_spans ---------------------------------------------------------

def test_serve_spans_are_the_vocabulary_whole_and_nested(session):
    steps = session.decode_report()["steps"]
    profiler.record_spans(True)
    sched = serve.Scheduler(session)
    reqs = requests(spread_s=0.002)
    done, _ = sched.run(reqs)
    profiler.record_spans(False)
    records = profiler.spans()
    assert_whole_and_nested(records, SERVE_TREE)
    count = collections.Counter(r.name for r in records)
    stats = sched.stats
    assert count["session.step"] == session.decode_report()["steps"] - steps
    assert count["serve.tick"] >= count["session.step"]
    assert count["serve.admit"] == stats["admitted"] == len(reqs)
    assert count["serve.finish"] == stats["finished"] == len(reqs)
    assert sum(r.attrs["prompt"] for r in profiler.spans("serve.admit")) \
        == sum(len(r.prompt) for r in reqs)
    ticks = profiler.spans("serve.tick")
    assert sum(t.attrs["admitted"] for t in ticks) == len(reqs)
    assert sum(t.attrs["finished"] for t in ticks) == len(reqs)
    assert ticks[0].attrs["live"] == 0 and ticks[-1].attrs["live"] >= 1

    # a request's admission and its completion share rid and slot, and
    # the slot joins them to the session's spans between
    admits = {r.attrs["rid"]: r for r in profiler.spans("serve.admit")}
    finishes = {r.attrs["rid"]: r for r in profiler.spans("serve.finish")}
    prefills = {r.parent: r for r in profiler.spans("session.prefill")}
    assert sorted(admits) == sorted(finishes) == [r.rid for r in reqs]
    for req in done:
        admit, finish = admits[req.rid], finishes[req.rid]
        assert admit.attrs["slot"] == finish.attrs["slot"] >= 0
        assert admit.attrs["resume"] == 0
        assert admit.attrs["prompt"] == len(req.prompt)
        assert set(finish.attrs) == {"rid", "slot"}
        assert len(req.tokens) == req.max_new
        prefill = prefills[admit.id]
        assert prefill.attrs["slot"] == admit.attrs["slot"]
        assert prefill.attrs == {
            "slot": admit.attrs["slot"], "prompt": len(req.prompt),
            "cached": 0, "chunks": 1,
            "bucket": 8 if len(req.prompt) <= 8 else 16}
        # queued_ms is the scheduler's own clock at the span's start,
        # less the request's arrival
        queued_ms = admit.attrs["queued_ms"]
        assert queued_ms >= 0
        assert queued_ms == pytest.approx(
            (admit.start_s - sched._t0 - req.arrival_s) * 1e3, abs=1e-6)
        assert queued_ms / 1e3 <= req.ttft_s


def test_a_fresh_prompt_in_chunks_says_so_in_its_spans(params):
    """With ``ServeConfig.max_prompt`` above the largest bucket a fresh
    prompt of 37 tokens is admitted as one ``serve.admit`` whose ``prompt``
    is the whole prompt, and its ``session.prefill`` says three chunks, the
    last one's bucket, and one ``prefill.launch`` a chunk."""
    sess = serve.InferenceSession(
        params, num_heads=CFG.num_heads, config=serve.ServeConfig(
            slots=2, page_size=PAGE, buckets=(8, 16), max_new=8,
            max_prompt=40, exact=True))
    prompt = np.random.default_rng(7).integers(0, CFG.vocab_size,
                                               37).tolist()
    profiler.record_spans(True)
    done, _ = serve.Scheduler(sess).run([serve.Request(
        rid=0, prompt=prompt, max_new=3, arrival_s=0.0)])
    profiler.record_spans(False)
    assert not done[0].failed and len(done[0].tokens) == 3
    admit, = profiler.spans("serve.admit")
    prefill, = profiler.spans("session.prefill")
    assert (admit.attrs["prompt"], admit.attrs["resume"]) == (37, 0)
    assert prefill.parent == admit.id
    assert prefill.attrs == {"slot": admit.attrs["slot"], "prompt": 37,
                             "cached": 0, "chunks": 3, "bucket": 8}
    launches = [r for r in profiler.spans("prefill.launch")
                if r.parent == prefill.id]
    assert [r.attrs for r in launches] == [
        {"bucket": 16, "largest": 16}, {"bucket": 16, "largest": 16},
        {"bucket": 8, "largest": 16}]


def test_each_chunk_of_a_two_chunk_prompt_says_which_it_is(session, params):
    """A ``prefill.launch`` span carries its chunk's ``bucket`` beside
    the ``largest`` the session has; the prompt within a bucket is one
    launch of its bucket."""
    sess = serve.InferenceSession(
        params, num_heads=CFG.num_heads, config=serve.ServeConfig(
            slots=2, page_size=PAGE, buckets=(8, 16), max_new=8,
            max_prompt=40, exact=True))
    prompt = np.random.default_rng(11).integers(0, CFG.vocab_size,
                                                28).tolist()
    profiler.record_spans(True)
    sess.prefill(sess.try_alloc(28, 2), prompt)
    session.prefill(session.try_alloc(6, 2), prompt[:6])
    profiler.record_spans(False)
    long, short = profiler.spans("session.prefill")
    assert (long.attrs["bucket"], long.attrs["chunks"]) == (16, 2)
    by_parent = collections.defaultdict(list)
    for r in profiler.spans("prefill.launch"):
        by_parent[r.parent].append(r.attrs)
    assert by_parent[long.id] == [{"bucket": 16, "largest": 16}] * 2
    assert by_parent[short.id] == [{"bucket": 8, "largest": 16}]


def test_chunks_that_carry_state_say_so_in_their_spans():
    """The same on the block whose chunks carry slot-private state (the
    short convolutions' two rows): a fresh prompt of 37 tokens is one
    ``session.prefill`` of three chunks and one ``prefill.launch`` a
    chunk, and the block counts two of them as carried."""
    cfg = serve.ModelConfig(
        block="lfm2_moe", vocab_size=61, num_layers=3, d_model=32,
        num_heads=4, num_key_value_heads=2, max_len=64, attn_head_dim=8,
        rope_theta=1e6, rms_norm_eps=1e-5,
        layer_types=("conv", "full_attention", "conv"), conv_L_cache=3,
        d_ff=48, first_k_dense=1, moe_d_ff=16, n_routed_experts=8,
        num_experts_per_tok=4, experts_held=(2, 2), tie_word_embeddings=True)
    sess = serve.InferenceSession(
        serve.init_params(cfg, seed=3), model=cfg, config=serve.ServeConfig(
            slots=2, page_size=PAGE, buckets=(8, 16), max_new=8,
            max_prompt=40, exact=False))
    prompt = np.random.default_rng(7).integers(0, 61, 37).tolist()
    profiler.record_spans(True)
    done, _ = serve.Scheduler(sess).run([serve.Request(
        rid=0, prompt=prompt, max_new=3, arrival_s=0.0)])
    profiler.record_spans(False)
    assert not done[0].failed and len(done[0].tokens) == 3
    admit, = profiler.spans("serve.admit")
    prefill, = profiler.spans("session.prefill")
    assert prefill.parent == admit.id
    assert prefill.attrs == {"slot": admit.attrs["slot"], "prompt": 37,
                             "cached": 0, "chunks": 3, "bucket": 8}
    assert len([r for r in profiler.spans("prefill.launch")
                if r.parent == prefill.id]) == 3
    rep = sess.block_report()
    assert (rep["prefills_from_zero"], rep["prefills_carried"]) == (1, 2)


@both_steps
def test_a_step_is_its_launches_then_its_read_in_order(stepped):
    """A call is the launches it makes (a ``step.prepare`` and a
    ``step.launch`` each: its own step's unless an earlier call left that
    in flight, and the next step's where ``ahead`` is 1), then the read of
    one step and its commit; a block pass's call likewise, and its span
    says what the pass it read held."""
    session = stepped
    before = session.decode_report()
    profiler.record_spans(True)
    # a diffusion block's prompt holds no mask token (60)
    serve.Scheduler(session).run(requests(below=60))
    profiler.record_spans(False)
    children = collections.defaultdict(list)
    for r in sorted(profiler.spans(), key=lambda r: r.start_s):
        children[r.parent].append(r)
    steps = sorted(profiler.spans("session.step"), key=lambda r: r.start_s)
    assert steps
    shares, in_flight, launches = [], 0, 0
    for step in steps:
        parts = children[step.id]
        made = (1 - in_flight) + step.attrs["ahead"]
        assert [p.name for p in parts] == (
            ["step.prepare", "step.launch"] * made
            + ["step.wait", "step.commit"])
        for first, then in zip(parts, parts[1:]):
            assert first.end_s <= then.start_s
        assert 1 <= step.attrs["live"] <= 3
        if session.diffusion:
            # every end is foreseen, so the pass read carried every slot
            assert step.attrs["denoise"] + step.attrs["commit"] \
                == step.attrs["live"]
        else:
            assert set(step.attrs) == {"live", "ahead"}
        shares.append(sum(p.end_s - p.start_s for p in parts)
                      / (step.end_s - step.start_s))
        in_flight = step.attrs["ahead"]
        launches += made
    # every request ends by max_new, which the scheduler foresees: the
    # last call leaves nothing in flight, and some call ran ahead
    assert in_flight == 0
    ahead = sum(step.attrs["ahead"] for step in steps)
    assert 0 < ahead < len(steps)
    after = session.decode_report()
    assert after["steps"] - before["steps"] == launches == len(steps)
    assert after["steps_ahead"] - before["steps_ahead"] == ahead
    # what lies between the segments is a few `with` statements
    assert 0.9 <= float(np.median(shares)) <= 1.0


@both_steps
def test_a_bare_step_says_whether_it_ran_ahead(stepped):
    session = stepped
    slot = session.try_alloc(5, 6)
    session.prefill(slot, [1, 2, 3, 4, 5])
    profiler.record_spans(True)
    for ahead in (False, True, True, False):
        tokens, _ = session.step(ahead=True) if ahead else session.step()
        assert list(tokens) == [slot]
    profiler.record_spans(False)
    steps = sorted(profiler.spans("session.step"), key=lambda r: r.start_s)
    assert [s.attrs["ahead"] for s in steps] == [0, 1, 1, 0]
    launched = collections.Counter(
        r.parent for r in profiler.spans("step.launch"))
    # the last call reads what the third left in flight: it launches none
    assert [launched[s.id] for s in steps] == [1, 2, 1, 0]
    prepared = collections.Counter(
        r.parent for r in profiler.spans("step.prepare"))
    assert prepared == launched


def test_a_resumed_request_is_admitted_again_with_resume_set(params):
    """Oversubscribed pages: a preempted request's transcript comes back
    through ``serve.admit`` with ``resume=1``, in as many chunks as the
    largest bucket makes of it."""
    sconf = serve.ServeConfig(slots=3, page_size=PAGE, buckets=(8, 16),
                              max_new=8, exact=True, num_pages=7,
                              oversub=True, watermark=1)
    sess = serve.InferenceSession(params, num_heads=CFG.num_heads,
                                  config=sconf)
    rng = np.random.default_rng(37)
    reqs = [serve.Request(rid=i, prompt=rng.integers(0, 61, 16).tolist(),
                          max_new=8) for i in range(3)]
    profiler.record_spans(True)
    sched = serve.Scheduler(sess)
    done, _ = sched.run(reqs)
    profiler.record_spans(False)
    assert all(not r.failed for r in done) and sched.stats["resumes"] > 0
    assert_whole_and_nested(profiler.spans(), SERVE_TREE)
    admits = profiler.spans("serve.admit")
    resumed = [a for a in admits
               if a.attrs["resume"] == 1 and a.attrs["slot"] >= 0]
    assert len(resumed) == sched.stats["resumes"]
    prefills = {r.parent: r for r in profiler.spans("session.prefill")}
    launches = collections.Counter(
        r.parent for r in profiler.spans("prefill.launch"))
    for admit in admits:
        if admit.attrs["slot"] == -1:     # found no room: nothing ran
            assert admit.id not in prefills
            continue
        prefill = prefills[admit.id]
        assert prefill.attrs["prompt"] == admit.attrs["prompt"]
        assert launches[prefill.id] == prefill.attrs["chunks"] \
            == -(-prefill.attrs["prompt"] // 16)
    assert sched.stats["admitted"] == len(prefills)


def test_fit_spans_are_the_vocabulary_whole_and_nested():
    profiler.record_spans(True)
    fit_three_batches()
    profiler.record_spans(False)
    records = profiler.spans()
    assert_whole_and_nested(records, FIT_TREE)
    batches = profiler.spans("fit.batch")
    assert [(b.attrs["epoch"], b.attrs["nbatch"]) for b in batches] \
        == [(0, 0), (0, 1), (0, 2)]
    count = collections.Counter(r.name for r in records)
    assert set(count.values()) == {3}
    index = by_id(records)
    order = [r.name for r in sorted(records, key=lambda r: r.start_s)
             if r.parent is not None
             and index[r.parent].name == "fit.forward_backward"][:4]
    assert order == ["trainstep.stage", "trainstep.hygiene",
                     "trainstep.launch", "fit.adopt"]


def test_spans_filters_by_name_and_window_and_hands_out_copies():
    profiler.record_spans(True)
    with profiler.span("outer", k=1):
        with profiler.span("inner") as sp:
            sp.set(late=2)
    with profiler.span("outer", k=2):
        pass
    profiler.record_spans(False)
    first, second = profiler.spans("outer")
    inner, = profiler.spans("inner")
    assert (first.attrs, second.attrs) == ({"k": 1}, {"k": 2})
    assert inner.parent == first.id and inner.attrs == {"late": 2}
    assert [r.name for r in profiler.spans()] == ["inner", "outer", "outer"]
    assert profiler.spans(since=second.start_s) == [second]
    assert profiler.spans(until=first.end_s) == [inner, first]
    assert profiler.spans(since=first.start_s, until=inner.end_s) == [inner]
    first.attrs["k"] = 99
    assert profiler.spans("outer")[0].attrs == {"k": 1}
    profiler.clear_spans()
    assert profiler.spans() == []


# -- on: a cpu_span's CPU time beside its wall time --------------------------

def _spin(cpu_seconds):
    """Compute until this thread has had ``cpu_seconds`` of a core,
    however long the machine takes to give them."""
    end = time.thread_time() + cpu_seconds
    while time.thread_time() < end:
        pass


# what two reads of a CPU clock may disagree by
GRAIN_S = 1e-3


@pytest.mark.parametrize("work, ran_at_least, ran_at_most", [
    (time.sleep, 0.0, 0.02), (_spin, 0.2, float("inf"))],
    ids=["a_sleep_waited", "a_busy_loop_ran"])
def test_a_cpu_span_says_whether_its_thread_ran_or_waited(
        work, ran_at_least, ran_at_most):
    profiler.record_spans(True)
    with profiler.cpu_span("stretch", k=1) as sp:
        work(0.2)
        sp.set(n=2)
    profiler.record_spans(False)
    r, = profiler.spans("stretch")
    wall = r.end_s - r.start_s
    # ran = cpu_s, waited = wall - cpu_s; the CPU clock is read inside the
    # wall clock's stretch
    assert wall >= 0.2 and ran_at_least <= r.cpu_s <= ran_at_most
    assert r.cpu_s <= wall + GRAIN_S
    assert r.attrs == {"k": 1, "n": 2}


def test_what_another_thread_ran_is_not_this_threads():
    """Another thread computes while this one sleeps inside a span:
    ``cpu_s`` stays this thread's own."""
    done = threading.Event()

    def other():
        _spin(0.2)
        done.set()

    worker = threading.Thread(target=other)
    profiler.record_spans(True)
    with profiler.cpu_span("asleep"):
        worker.start()
        assert done.wait(60)
    profiler.record_spans(False)
    worker.join(30)
    assert not worker.is_alive()
    r, = profiler.spans("asleep")
    assert r.cpu_s <= 0.05 and r.end_s - r.start_s >= 0.2 - GRAIN_S


def test_a_parents_cpu_time_covers_its_childrens():
    profiler.record_spans(True)
    with profiler.cpu_span("outer"):
        with profiler.cpu_span("first"):
            _spin(0.05)
        time.sleep(0.05)
        with profiler.cpu_span("second"):
            _spin(0.05)
    profiler.record_spans(False)
    outer, = profiler.spans("outer")
    first, = profiler.spans("first")
    second, = profiler.spans("second")
    assert min(first.cpu_s, second.cpu_s) >= 0.05
    assert outer.cpu_s >= first.cpu_s + second.cpu_s
    # the self values: the span's less its children's, as for wall time
    self_wall = (outer.end_s - outer.start_s) - sum(
        c.end_s - c.start_s for c in (first, second))
    self_ran = outer.cpu_s - first.cpu_s - second.cpu_s
    assert self_wall >= 0.05 and 0 <= self_ran <= 0.02


def test_on_a_plain_span_reads_no_cpu_clock(session, monkeypatch):
    """A read of the thread's CPU clock is a slow system call on some
    hosts, so only a ``cpu_span`` pays it: of the serving spans a decode
    call's ``session.step`` and its ``step.wait``, which
    ``step_host_cpu_ms.serve`` reads."""
    reads = []
    monkeypatch.setattr(profiler, "_thread_time",
                        lambda: reads.append(1) or 0.25 * len(reads))
    profiler.record_spans(True)
    with profiler.span("plain", k=1):
        pass
    assert reads == []
    sched = serve.Scheduler(session)
    sched.begin(requests(2))
    while sched.tick():
        pass
    profiler.record_spans(False)
    plain, = profiler.spans("plain")
    assert plain.cpu_s is None and plain.attrs == {"k": 1}
    with_cpu = {r.name for r in profiler.spans() if r.cpu_s is not None}
    assert with_cpu == {"session.step", "step.wait"}
    steps = profiler.spans("session.step")
    assert len(reads) == 4 * len(steps) > 0
    # a step's reads enclose its wait's: 0.75 against 0.25 by this clock
    assert {r.cpu_s for r in steps} == {0.75}
    assert {r.cpu_s for r in profiler.spans("step.wait")} == {0.25}


def test_a_record_of_six_fields_still_reads_and_has_no_cpu_time():
    r = profiler.SpanRecord(1, None, "session.step", 0.5, 0.75, {"live": 2})
    assert r.cpu_s is None
    assert (r.name, r.end_s - r.start_s, r.attrs) \
        == ("session.step", 0.25, {"live": 2})
    assert r._fields == ("id", "parent", "name", "start_s", "end_s",
                         "attrs", "cpu_s")


def test_a_platform_without_the_cpu_clock_records_none(monkeypatch):
    monkeypatch.setattr(profiler, "_thread_time", lambda: None)
    profiler.record_spans(True)
    with profiler.cpu_span("s", k=1):
        pass
    profiler.record_spans(False)
    r, = profiler.spans("s")
    assert r.cpu_s is None and r.end_s >= r.start_s


def test_a_span_entered_while_off_stays_off():
    with profiler.span("outer") as outer:
        profiler.record_spans(True)
        with profiler.span("inner"):
            pass
    profiler.record_spans(False)
    assert not outer.on
    only, = profiler.spans()
    assert (only.name, only.parent) == ("inner", None)


def test_the_record_is_bounded_and_says_what_it_dropped():
    profiler.record_spans(True)
    for _ in range(profiler.SPAN_CAPACITY + 10):
        with profiler.span("s"):
            pass
    profiler.record_spans(False)
    assert profiler.SPAN_CAPACITY == 2 ** 18
    assert profiler.spans_dropped() == 10
    records = profiler.spans()
    assert len(records) == profiler.SPAN_CAPACITY
    assert records[-1].id - records[0].id == profiler.SPAN_CAPACITY - 1
    profiler.clear_spans()
    assert profiler.spans_dropped() == 0


def test_two_threads_nest_independently():
    profiler.record_spans(True)
    inside = threading.Barrier(2, timeout=30)

    def work(tag):
        with profiler.span("outer", tag=tag):
            inside.wait()                 # both outers are open now
            with profiler.span("inner", tag=tag):
                inside.wait()

    threads = [threading.Thread(target=work, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    profiler.record_spans(False)
    outers = {r.attrs["tag"]: r for r in profiler.spans("outer")}
    inners = {r.attrs["tag"]: r for r in profiler.spans("inner")}
    assert sorted(outers) == sorted(inners) == [0, 1]
    for tag in (0, 1):
        assert outers[tag].parent is None
        assert inners[tag].parent == outers[tag].id


# -- on: a profiler session ---------------------------------------------------

def test_a_profiler_session_switches_the_sites_on_and_off(session, tmp_path):
    """As ``benchmark/tracer.py`` starts it: the sites follow
    ``TraceAnnotation.is_enabled()``, and the spans are in the trace, on
    a host plane, with their attributes as the events' stats."""
    import jax
    from jax.profiler import ProfileData

    sched = serve.Scheduler(session)
    sched.run(requests(3))
    assert profiler.spans() == []
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        assert profiler.TraceAnnotation.is_enabled()
        sched.run(requests(3, seed=1))
    finally:
        jax.profiler.stop_trace()
    assert not profiler.TraceAnnotation.is_enabled()
    recorded = profiler.spans()
    assert_whole_and_nested(recorded, SERVE_TREE)
    sched.run(requests(3, seed=2))
    assert len(profiler.spans()) == len(recorded)

    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    found = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith(profiler.SPAN_PREFIX):
                    found[event.name].append((plane.name, dict(event.stats)))
    assert set(found) == {"mx:" + n for n in names_of(SERVE_TREE)}
    steps = found["mx:session.step"]
    assert len(steps) == len(profiler.spans("session.step"))
    for plane_name, stats in steps:
        assert plane_name.startswith("/host:")
        assert 1 <= stats["live"] <= 3
    # what set() added later is there too
    assert all(stats["slot"] >= 0 and "queued_ms" in stats
               for _, stats in found["mx:serve.admit"])
    assert all({"bucket", "chunks", "prompt"} <= set(stats)
               for _, stats in found["mx:session.prefill"])
    assert all(0 < stats["bucket"] <= stats["largest"]
               for _, stats in found["mx:prefill.launch"])
