"""A decode step launched before the last one's tokens are read
(``InferenceSession.step(ahead=True)``; docs/serving.md, "A step ahead").

What is held here, for every block with a ``decode_step``: a run in which
the scheduler lets steps run ahead serves every request the tokens of the
same run in series (the same executables on the same inputs, so the
streams are held to equality); a slot released while a step that carried
it was in flight (an ``eos_id``, a cancel, a fault, a drain, a watermark
eviction) gets no token from that step and leaks nothing, and the request
admitted into the same slot before that step is read gets its own tokens,
recurrent state included; the executables stay ``buckets + 1`` with no
drift and no fallback; a speculating session and a diffusion block never
run ahead.
"""
import contextlib
import dataclasses

import numpy as np
import pytest

from mxnet_tpu import serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serve.scheduler import Request, Scheduler
from mxnet_tpu.testing import faults

from serve_util import lend
from test_serve_blocks import (BAILING, GPT2, GRANITE, LAGUNA, LATENT, LFM2,
                               PHI4, SDAR)

# granite's multipliers at 1: at the published 12 a toy model's tied head
# repeats the prompt's last token for ever, and no stream tells a step
# from the next
BLOCKS = {"gpt2": GPT2, "deepseek_v3": LATENT,
          "granitemoehybrid": dataclasses.replace(
              GRANITE, embedding_multiplier=1.0, residual_multiplier=1.0),
          "bailing_hybrid": BAILING, "laguna": LAGUNA, "lfm2_moe": LFM2,
          "phi4flash": PHI4}
# a prompt of 37 tokens goes in three chunks, one of 22 in two
CONF = dict(slots=3, page_size=8, buckets=(8, 16), max_new=8, max_prompt=40)
every_block = pytest.mark.parametrize("sess", sorted(BLOCKS), indirect=True)


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("MXNET_FAULT_INJECT", raising=False)
    faults.reset()
    yield
    faults.reset()


def build(name, **conf):
    cfg = BLOCKS.get(name, SDAR)
    return serve.InferenceSession(
        # drawn wide: at the default 0.02 a toy model with a tied head
        # repeats itself too
        serve.init_params(cfg, seed=3, scale=0.3), model=cfg,
        config=serve.ServeConfig(**dict(CONF, **conf)))


@pytest.fixture(scope="module")
def _built():
    return {}


@pytest.fixture
def sess(request, _built):
    name = request.param
    if name not in _built:
        _built[name] = build(name)
    yield from lend(_built[name])


@contextlib.contextmanager
def in_series(sess):
    """Every step of ``sess`` as a call with no argument makes it: launch,
    read, return, whatever the scheduler foresees."""
    sess.step = lambda ahead=False: type(sess).step(sess)
    try:
        yield
    finally:
        del sess.step


@contextlib.contextmanager
def watched(sess, calls):
    """``calls`` gets, of every step, (whether it was told it may run
    ahead, the live slots it began with, the slots it returned)."""
    def step(**how):
        live = sess.active_slots()
        out = type(sess).step(sess, **how)
        calls.append((bool(how.get("ahead")), live, sorted(out[0])))
        return out

    sess.step = step
    try:
        yield
    finally:
        del sess.step


def trace(n=8, longest=40, **more):
    rng = np.random.default_rng(11)
    lengths = [min(p, longest) for p in [5, 37, 9, 16, 3, 22, 12, 7][:n]]
    new = [6, 3, 8, 1, 5, 2, 7, 4][:n]
    return [Request(rid=i, prompt=rng.integers(0, 61, p).tolist(), max_new=m,
                    arrival_s=0.0, **more)
            for i, (p, m) in enumerate(zip(lengths, new))]


def streams(requests):
    return {r.rid: list(r.tokens) for r in requests}


def served_in_series(sess, requests):
    with in_series(sess):
        done, _ = Scheduler(sess).run(requests)
    assert not any(r.failed for r in done), [r.error for r in done]
    return streams(done)


# -- (a), (c): the streams, the executables -----------------------------------

@every_block
def test_a_run_ahead_serves_the_streams_of_the_run_in_series(sess):
    want = served_in_series(sess, trace())
    guards = sess.guard_report()
    calls = []
    with watched(sess, calls):
        done, _ = Scheduler(sess).run(trace())
    assert not any(r.failed for r in done), [r.error for r in done]
    assert streams(done) == want
    assert all(len(r.tokens) == r.max_new for r in done)
    # eight requests through three slots: finishes, admissions and the
    # chunked prefills fall between steps, some of which ran ahead; every
    # end was foreseen, so every live slot got its token from every call
    ahead = [a for a, _, _ in calls]
    assert any(ahead) and not all(ahead) and not ahead[-1]
    assert all(live == got for _, live, got in calls)
    # the executables are the ones that were compiled, on their avals
    assert len(sess.executables) == len(CONF["buckets"]) + 1
    assert sess.fallback_count() == 0
    for name, guard in sess.guard_report().items():
        assert guard["signatures"] == guards[name]["signatures"], name
        assert guard["traces"] == guards[name]["traces"], name
    assert sess.guard_report()["decode"]["calls"] \
        == guards["decode"]["calls"] + len(calls)


@every_block
def test_the_other_policies_run_ahead_too(sess):
    want = served_in_series(sess, trace(5))
    for policy in ("serial", "static"):
        calls = []
        with watched(sess, calls):
            done, _ = Scheduler(sess, policy=policy).run(trace(5))
        assert streams(done) == want
        assert any(a for a, _, _ in calls)


def test_an_arrival_that_could_be_admitted_holds_the_step_back():
    """A free slot and a request that has arrived since the tick's
    admissions: the step does not run ahead, so the admission finds an idle
    chip at the next boundary."""
    sess = build("gpt2")
    sched = Scheduler(sess).begin([
        Request(rid=i, prompt=[7 + i, 8, 9], max_new=8) for i in range(2)])
    sched.tick()
    assert sched._foresees_no_end(False)
    late = Request(rid=9, prompt=[1, 2, 3], max_new=4,
                   arrival_s=sched.now())
    sched.submit(late)
    assert not sched._foresees_no_end(False)
    # with no room for it nothing changes at the next boundary
    assert sched._foresees_no_end(True)
    while sched.tick():
        pass
    assert len(late.tokens) == 4 and not late.failed


# -- (b): a slot released while a step that carried it is in flight -----------

def admit(sess, prompt, max_new):
    slot = sess.try_alloc(len(prompt), max_new, tokens=prompt)
    first, _ = sess.prefill(slot, prompt)
    return slot, [first]


def alone(sess, prompt, n):
    """``n`` tokens of ``prompt`` served by itself, in series."""
    slot, tokens = admit(sess, prompt, n)
    while len(tokens) < n:
        tokens.append(sess.step()[0][slot])
    sess.release(slot)
    return tokens


@every_block
def test_a_slot_released_and_filled_again_under_a_step_in_flight(sess):
    rng = np.random.default_rng(5)
    a, b, c = (rng.integers(0, 61, n).tolist() for n in (6, 11, 19))
    want_b, want_c = alone(sess, b, 7), alone(sess, c, 5)
    baseline = sess.state_report()
    slot_a, _ = admit(sess, a, 8)
    slot_b, got_b = admit(sess, b, 7)
    out, _ = sess.step(ahead=True)          # the next step is in flight
    assert sorted(out) == sorted([slot_a, slot_b])
    got_b.append(out[slot_b])
    sess.release(slot_a)                    # ... and carries a's slot
    slot_c, got_c = admit(sess, c, 5)
    assert slot_c == slot_a
    out, _ = sess.step(ahead=True)          # reads the step that carried a
    assert sorted(out) == [slot_b]          # a's row is dropped, not c's
    got_b.append(out[slot_b])
    while len(got_c) < 5:
        out, _ = sess.step(ahead=len(got_c) < 4)
        got_b.append(out[slot_b])
        got_c.append(out[slot_c])
    # c was fed its own first token and began from its own state
    assert got_c == want_c
    assert got_b == want_b[:len(got_b)]
    sess.release(slot_c)
    out, _ = sess.step()                    # nothing is in flight now
    assert sorted(out) == [slot_b]
    sess.release(slot_b)
    assert sess.state_report() == baseline
    assert sess.fallback_count() == 0


@every_block
def test_a_step_nobody_waits_for_is_not_read(sess):
    rng = np.random.default_rng(6)
    a, b = (rng.integers(0, 61, n).tolist() for n in (7, 12))
    want_b = alone(sess, b, 3)
    slot_a, _ = admit(sess, a, 8)
    sess.step(ahead=True)
    sess.release(slot_a)                    # the step in flight carried a
    steps = sess.guard_report()["decode"]["calls"]
    slot_b, got_b = admit(sess, b, 3)
    got_b.append(sess.step()[0][slot_b])    # launched anew and read: b's
    got_b.append(sess.step()[0][slot_b])
    assert got_b == want_b
    assert sess.guard_report()["decode"]["calls"] == steps + 2


def steady():
    """Three requests of eight tokens fill the slots (one prompt in two
    chunks), so six ticks in a row run ahead; two more wait for a slot,
    and take the one an end nobody foresaw frees, behind the step in
    flight."""
    rng = np.random.default_rng(13)
    sizes = [(5, 8), (22, 8), (9, 8), (12, 6), (18, 5)]
    return [Request(rid=i, prompt=rng.integers(0, 61, p).tolist(), max_new=m,
                    arrival_s=0.0) for i, (p, m) in enumerate(sizes)]


def ticks(sess, requests, between=None):
    """``requests`` through a scheduler tick by tick -> (the scheduler,
    what :func:`watched` saw); ``between(sched, n, calls)`` is called
    after the n-th tick."""
    calls = []
    sched = Scheduler(sess).begin(requests)
    n = 0
    with watched(sess, calls):
        while sched.tick():
            n += 1
            if between is not None:
                between(sched, n, calls)
    return sched, calls


def filled_behind_a_step(calls):
    """Some call returned tokens for fewer slots than were live: the step
    it read was launched before a slot was released and filled again."""
    return any(len(got) < len(live) for _, live, got in calls)


def but(streams_, rid):
    return {k: v for k, v in streams_.items() if k != rid}


@every_block
def test_an_end_of_sequence_nobody_foresaw(sess):
    whole = served_in_series(sess, steady())
    # one of the three long requests ends early, on a token it emits from
    # a decode step and has not emitted before (a toy model's stream may
    # repeat itself from its second token on)
    rid, at = next((rid, i) for rid in (1, 0, 2)
                   for i, t in enumerate(whole[rid])
                   if 1 <= i < 7 and t not in whole[rid][:i])

    def requests():
        reqs = steady()
        reqs[rid].eos_id = whole[rid][at]
        return reqs

    want = served_in_series(sess, requests())
    assert want == {**whole, rid: whole[rid][:at + 1]}
    sched, calls = ticks(sess, requests())
    assert not any(r.failed for r in sched._queue)
    assert streams(sched._queue) == want
    # the step in flight carried the slot: the request that took it got
    # nothing from that step, and then its own tokens
    assert filled_behind_a_step(calls)


@every_block
def test_a_cancel_under_a_step_in_flight(sess):
    want = served_in_series(sess, steady())

    def between(sched, n, calls):
        if n == 3:
            assert calls[-1][0]             # the last call ran ahead
            assert sched.cancel(1)

    sched, calls = ticks(sess, steady(), between)
    got = streams(sched._queue)
    assert sched._queue[1].cancelled and got[1] == want[1][:4]
    assert but(got, 1) == but(want, 1)
    assert filled_behind_a_step(calls)


@pytest.mark.chaos
@every_block
def test_a_decode_fault_under_a_step_in_flight(sess, monkeypatch):
    want = served_in_series(sess, steady())
    # the eighth crossing of the decode boundary: the third tick's, for the
    # request in slot 1
    monkeypatch.setenv("MXNET_FAULT_INJECT", "serve_decode:raise:after=8")
    faults.reset()
    sched, calls = ticks(sess, steady())
    failed = [r for r in sched._queue if r.failed]
    assert len(failed) == 1 and "FaultInjected" in failed[0].error
    got, rid = streams(sched._queue), failed[0].rid
    assert 1 < len(got[rid]) < 8 and got[rid] == want[rid][:len(got[rid])]
    assert but(got, rid) == but(want, rid)
    # the fault fell before the tick's step, which read the step in flight
    # (it carried the failed request: dropped) and did not run ahead, since
    # the slot was free for a waiting arrival
    assert (False, [0, 2], [0, 2]) in calls


@every_block
def test_a_drain_under_a_step_in_flight_replays_bit_exact(sess):
    """Failover: the requests drained mid-decode, one step in flight, go
    back in through the resume path, whose re-prefill is held to the last
    committed token; every stream ends as the run in series ends it."""
    want = served_in_series(sess, steady())
    drained = []

    def between(sched, n, calls):
        if n == 4:
            assert calls[-1][0]
            drained.extend(sched.drain())

    first, _ = ticks(sess, steady(), between)
    resumable, fresh = drained
    assert len(resumable) == 3 and len(fresh) == 2 and not first.outstanding
    second = Scheduler(sess).begin([])
    for req in resumable:
        second.submit(req, parked=True)
    for req in fresh:
        second.submit(req)
    while second.tick():
        pass
    assert not any(r.failed for r in first._queue), \
        [r.error for r in first._queue]
    assert streams(first._queue) == want
    assert second.stats["resumes"] == len(resumable)


@pytest.mark.parametrize("name", ["gpt2", "granitemoehybrid", "lfm2_moe",
                                  "phi4flash"])
def test_a_watermark_eviction_under_a_step_in_flight(name):
    sess = build(name, num_pages=7, oversub=True, watermark=1,
                 max_prompt=0)
    baseline = sess.state_report()
    rng = np.random.default_rng(37)

    def requests():
        return [Request(rid=i, prompt=rng.integers(0, 61, 16).tolist(),
                        max_new=8) for i in range(4)]

    with in_series(sess):
        sched = Scheduler(sess)
        done, _ = sched.run(requests())
    want = streams(done)
    assert sched.stats["preemptions"] > 0
    rng = np.random.default_rng(37)
    calls = []
    with watched(sess, calls):
        sched = Scheduler(sess)
        done, _ = sched.run(requests())
    assert not any(r.failed for r in done), [r.error for r in done]
    assert streams(done) == want
    assert sched.stats["preemptions"] > 0 and sched.stats["resumes"] > 0
    assert any(a for a, _, _ in calls)
    sess.reset_cold()
    assert sess.state_report() == baseline


# -- (d): who never runs ahead -------------------------------------------------

def test_a_speculating_session_never_runs_ahead():
    sess = build("gpt2", spec_k=2, draft="ngram", max_prompt=0)
    calls = []
    with watched(sess, calls):
        done, _ = Scheduler(sess).run(trace(4, longest=16))
    assert calls == [] and all(len(r.tokens) == r.max_new for r in done)
    # told it may, a bare step of such a session still reads what it
    # launched: the verify step that follows feeds the host's tokens
    want = alone(sess, [3, 1, 4, 1, 5], 6)
    slot, got = admit(sess, [3, 1, 4, 1, 5], 6)
    got.append(sess.step(ahead=True)[0][slot])
    assert sess.decode_report()["steps_ahead"] == 0
    while len(got) < 6:
        got.extend(sess.spec_step({slot: 6 - len(got)})[slot])
    assert got == want


def test_a_diffusion_block_never_runs_ahead():
    sess = build("sdar_moe", max_prompt=0)
    calls = []
    with watched(sess, calls):
        done, _ = Scheduler(sess).run(trace(4, longest=16))
    assert calls and not any(a for a, _, _ in calls)
    assert all(len(r.tokens) == r.max_new for r in done)
    slot = sess.try_alloc(6, 8)
    sess.prefill(slot, [1, 2, 3, 4, 5, 6])
    steps = sess._decode_stats["steps"]
    for _ in range(3):
        sess.step(ahead=True)               # a block pass, read as it was
    assert sess._decode_stats["steps"] == steps + 3
    assert sess._decode_stats["steps_ahead"] == 0


def test_a_bare_step_is_the_synchronous_call():
    """No argument: what ``prefill`` and ``release`` do between two steps
    is seen by the next one, as the tests' and ``bench_serve.py``'s loops
    expect."""
    sess = build("gpt2")
    slot_a, _ = admit(sess, [5, 6, 7], 8)
    out, _ = sess.step()
    assert sorted(out) == [slot_a]
    slot_b, _ = admit(sess, [8, 9], 8)
    out, _ = sess.step()
    assert sorted(out) == sorted([slot_a, slot_b])
    sess.release(slot_a)
    out, _ = sess.step()
    assert sorted(out) == [slot_b]
    with pytest.raises(MXNetError):
        sess.cache.release(slot_a)          # released once, and only once
    assert sess.decode_report()["steps_ahead"] == 0
