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
drift and no fallback; a speculating session never runs ahead.

A diffusion block's pass runs ahead too (the file's second half): there a
request hands back ``(token, pass, confidence)`` triples, all three held to
the run in series; the scheduler foresees every end but a cancel, so no
pass is launched for a request that is gone (the device's own
``diffusion_stats`` equal the serial run's); what a release leaves in
flight is dropped, and a slot prefilled under a pass gets its first pass
one call later, its known rows from the host.
"""
import contextlib
import dataclasses

import numpy as np
import pytest

from mxnet_tpu import serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serve import sdar_moe
from mxnet_tpu.serve.scheduler import Request, Scheduler
from mxnet_tpu.serve.session import NO_TOKEN
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


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("MXNET_FAULT_INJECT", raising=False)
    faults.reset()
    yield
    faults.reset()


# the toy diffusion block at three thresholds: at 0.2 some passes' rows
# clear it, and a block is committed after one to four denoise passes; at
# 0.9 none does (the quota's four passes a block), at 0 all do (one)
DIFFUSION = {"some_rows_clear_it": 0.2, "quota_only": 0.9,
             "all_at_once": 0.0}
BLOCKS.update({"sdar_moe:" + name: dataclasses.replace(
    SDAR, confidence_threshold=at) for name, at in DIFFUSION.items()})
AUTOREGRESSIVE = sorted(name for name in BLOCKS if ":" not in name)
# four blocks of four tokens a request at most
DIFFUSION_CONF = dict(max_new=16)


def build(name, **conf):
    cfg = BLOCKS[name]
    if name.startswith("sdar_moe"):
        conf = dict(DIFFUSION_CONF, **conf)
    return serve.InferenceSession(
        # drawn wide: at the default 0.02 a toy model with a tied head
        # repeats itself too
        serve.init_params(cfg, seed=3, scale=0.3), model=cfg,
        config=serve.ServeConfig(**dict(CONF, **conf)))


every_block = pytest.mark.parametrize("sess", AUTOREGRESSIVE, indirect=True)
every_threshold = pytest.mark.parametrize(
    "sess", ["sdar_moe:" + name for name in sorted(DIFFUSION)],
    indirect=True)
some_rows_clear_it = pytest.mark.parametrize(
    "sess", ["sdar_moe:some_rows_clear_it"], indirect=True)


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


# -- a diffusion block's pass one ahead ----------------------------------------

def block_trace(n=8, **more):
    """Prompts that end at every place in a block of four, lengths that
    are no multiples of it; a prompt holds no mask token (60)."""
    rng = np.random.default_rng(11)
    sizes = [(5, 14), (37, 11), (9, 16), (16, 9), (3, 13), (22, 10),
             (12, 7), (7, 4)][:n]
    return [Request(rid=i, prompt=rng.integers(0, 60, p).tolist(), max_new=m,
                    arrival_s=0.0, **more) for i, (p, m) in enumerate(sizes)]


def triples(requests):
    return {r.rid: list(zip(r.tokens, r.passes, r.confidences))
            for r in requests}


def counted(sess):
    """What the block-pass executable counted of its passes, on the
    device."""
    report = sess.block_report()
    return {name: report[name] for name in sdar_moe.DIFFUSION_COLUMNS}


def since(sess, before):
    return {name: n - before[name] for name, n in counted(sess).items()}


def blocks_in_series(sess, requests):
    """-> (every request's triples, the device's counts) of the run in
    series."""
    before = counted(sess)
    with in_series(sess):
        done, _ = Scheduler(sess).run(requests)
    assert not any(r.failed for r in done), [r.error for r in done]
    return triples(done), since(sess, before)


def denoise_passes(request):
    """The denoise passes each block took that the request generated
    whole (a first block whose leading rows the prompt gave takes
    fewer)."""
    at = list(request.passes)[-len(request.prompt) % 4:]
    return [max(at[i:i + 4]) + 1 for i in range(0, len(at) - 3, 4)]


@every_threshold
def test_a_block_run_ahead_serves_the_triples_of_the_run_in_series(sess):
    want, want_counts = blocks_in_series(sess, block_trace())
    guards = sess.guard_report()
    before, calls = counted(sess), []
    with watched(sess, calls):
        done, _ = Scheduler(sess).run(block_trace())
    assert not any(r.failed for r in done), [r.error for r in done]
    # the tokens, the pass each was unmasked in, the confidence it was
    # unmasked with: the same executable on the same inputs
    assert triples(done) == want
    assert all(len(r.tokens) == r.max_new for r in done)
    taken = {n for r in done for n in denoise_passes(r)}
    assert taken == {0.0: {1}, 0.9: {4}, 0.2: {1, 2, 3, 4}}[
        sess.model.confidence_threshold]
    # every end was foreseen: no pass was launched for a request that was
    # gone, so the device counted the passes of the run in series
    assert since(sess, before) == want_counts
    ahead = [a for a, _, _ in calls]
    assert any(ahead) and not all(ahead) and not ahead[-1]
    assert sum(ahead) > len(ahead) // 2
    assert all(live == got for _, live, got in calls)
    assert len(sess.executables) == len(CONF["buckets"]) + 1
    assert sess.fallback_count() == 0
    for name, guard in sess.guard_report().items():
        assert guard["signatures"] == guards[name]["signatures"], name
        assert guard["traces"] == guards[name]["traces"], name
    assert sess.guard_report()["block_pass"]["calls"] \
        == guards["block_pass"]["calls"] + len(calls)


@pytest.mark.parametrize("policy", ["serial", "static"])
@some_rows_clear_it
def test_the_other_policies_run_a_pass_ahead_too(sess, policy):
    want, want_counts = blocks_in_series(sess, block_trace(5))
    before, calls = counted(sess), []
    with watched(sess, calls):
        done, _ = Scheduler(sess, policy=policy).run(block_trace(5))
    assert triples(done) == want and since(sess, before) == want_counts
    assert any(a for a, _, _ in calls)


@pytest.mark.parametrize("row", [0, 1, 2, 3])
@some_rows_clear_it
def test_an_end_of_sequence_inside_a_block_is_foreseen(sess, row):
    """The host holds a block's every token before its commit pass is
    read, so an ``eos_id`` at any place of a block ends the request as the
    run in series ends it, and no pass runs ahead over that commit."""
    whole, _ = blocks_in_series(sess, block_trace(5))
    lengths = {r.rid: len(r.prompt) for r in block_trace(5)}
    # a token at that place of a later block than the request's first,
    # which the request has not delivered before
    rid, at = next((rid, i) for rid in sorted(whole)
                   for i, (tok, _, _) in enumerate(whole[rid])
                   if i >= 4 and (lengths[rid] + i) % 4 == row
                   and tok not in [t for t, _, _ in whole[rid][:i]])

    def requests():
        reqs = block_trace(5)
        reqs[rid].eos_id = whole[rid][at][0]
        return reqs

    want, want_counts = blocks_in_series(sess, requests())
    assert want == {**whole, rid: whole[rid][:at + 1]}
    before, calls = counted(sess), []
    with watched(sess, calls):
        done, _ = Scheduler(sess).run(requests())
    assert triples(done) == want
    assert since(sess, before) == want_counts
    assert any(a for a, _, _ in calls)
    assert all(live == got for _, live, got in calls)


def block_steady():
    """Three requests of sixteen tokens fill the slots; two more wait for
    a slot, and take the one a cancel frees, behind the pass in flight."""
    rng = np.random.default_rng(13)
    sizes = [(5, 16), (22, 16), (9, 16), (12, 10), (18, 7)]
    return [Request(rid=i, prompt=rng.integers(0, 60, p).tolist(), max_new=m,
                    arrival_s=0.0) for i, (p, m) in enumerate(sizes)]


@every_threshold
def test_a_cancel_under_a_pass_in_flight(sess):
    want, _ = blocks_in_series(sess, block_steady())

    def between(sched, n, calls):
        if n == 3:
            assert calls[-1][0]             # the last call ran ahead
            assert sched.cancel(1)

    sched, calls = ticks(sess, block_steady(), between)
    got = triples(sched._queue)
    assert sched._queue[1].cancelled and len(got[1]) < 16
    assert got[1] == want[1][:len(got[1])]
    assert but(got, 1) == but(want, 1)
    # the pass in flight carried the slot: nothing of it reached the
    # request that took the slot, which then got its own blocks
    assert filled_behind_a_step(calls)
    assert not any(r.failed for r in sched._queue if r.rid != 1)


def admit_block(sess, prompt, max_new):
    slot = sess.try_alloc(len(prompt), max_new, tokens=prompt)
    assert sess.prefill(slot, prompt) == (NO_TOKEN, None)
    return slot


def alone_blocks(sess, prompt, n):
    """The first ``n`` triples of ``prompt`` served by itself, in series."""
    slot, got = admit_block(sess, prompt, n), []
    while len(got) < n:
        got += sess.step()[0][slot]
    sess.release(slot)
    return got[:n]


@every_threshold
def test_a_slot_released_and_filled_again_under_a_pass_in_flight(sess):
    rng = np.random.default_rng(5)
    a, b, c = (rng.integers(0, 60, n).tolist() for n in (6, 11, 19))
    want_b, want_c = alone_blocks(sess, b, 12), alone_blocks(sess, c, 8)
    baseline = sess.state_report()
    slot_a, slot_b = admit_block(sess, a, 16), admit_block(sess, b, 12)
    out, _ = sess.step(ahead=True)          # the next pass is in flight
    assert sorted(out) == sorted([slot_a, slot_b])
    got_b, got_c = list(out[slot_b]), []
    sess.release(slot_a)                    # ... and carries a's slot
    slot_c = admit_block(sess, c, 8)
    assert slot_c == slot_a
    out, _ = sess.step(ahead=True)          # reads the pass that carried a
    assert sorted(out) == [slot_b]          # a's rows are dropped, not c's
    got_b += out[slot_b]
    while len(got_c) < 8:
        out, _ = sess.step(ahead=True)
        got_b += out[slot_b]
        got_c += out[slot_c]
    # c began from its own known rows and its own pages
    assert got_c[:8] == want_c
    assert got_b[:12] == want_b[:len(got_b)]
    sess.release(slot_c)
    out, _ = sess.step()                    # reads what was in flight
    assert sorted(out) == [slot_b]
    assert sess._flight is None
    sess.release(slot_b)
    assert sess.state_report() == baseline
    assert sess.fallback_count() == 0


@every_threshold
def test_a_slot_prefilled_under_a_pass_gets_its_first_pass_one_call_later(
        sess):
    rng = np.random.default_rng(8)
    a, b = (rng.integers(0, 60, n).tolist() for n in (9, 14))
    want_b = alone_blocks(sess, b, 8)
    slot_a = admit_block(sess, a, 16)
    sess.step(ahead=True)                   # a's second pass is in flight
    slot_b = admit_block(sess, b, 8)
    passes = sess.decode_report()["steps"]
    out, _ = sess.step(ahead=True)          # reads it; launches b's first
    assert sorted(out) == [slot_a]
    assert sess.decode_report()["steps"] == passes + 1
    blk = sess._slot_tokens[slot_b]         # the host's rows, as prefilled
    assert (blk.passes, blk.tokens) == (0, b[12:] + [60, 60])
    out, _ = sess.step()                    # b's first pass, its rows the
    assert sorted(out) == [slot_a, slot_b]  # host's; nothing is launched
    assert sess.decode_report()["steps"] == passes + 1
    assert sess._slot_tokens[slot_b].passes == 1 and out[slot_b] == []
    got_b = []
    while len(got_b) < 8:
        got_b += sess.step(ahead=len(got_b) < 4)[0][slot_b]
    assert got_b[:8] == want_b


@some_rows_clear_it
def test_a_pass_nobody_waits_for_is_not_read(sess):
    rng = np.random.default_rng(6)
    a, b = (rng.integers(0, 60, n).tolist() for n in (7, 10))
    want_b = alone_blocks(sess, b, 6)
    slot_a = admit_block(sess, a, 16)
    sess.step(ahead=True)
    sess.release(slot_a)                    # the pass in flight carried a
    passes = sess.guard_report()["block_pass"]["calls"]
    slot_b, got_b, calls = admit_block(sess, b, 6), [], 0
    while len(got_b) < 6:
        out, _ = sess.step()                # launched anew and read: b's
        assert sorted(out) == [slot_b]
        got_b += out[slot_b]
        calls += 1
    assert got_b[:6] == want_b
    assert sess.guard_report()["block_pass"]["calls"] == passes + calls


@pytest.mark.parametrize("ends_by, flags", [
    ("max_new", [True, True, True, False]),
    ("eos_in_its_last_block", [True, True, True, False]),
    ("eos_in_its_first_block", [True, False])])
def test_no_pass_runs_ahead_over_a_requests_last_commit(ends_by, flags):
    """At threshold 0 a block is one denoise pass and its commit.  A
    prompt of 6 leaves 2 known rows: the first commit delivers 2 tokens,
    the second 4, which spend ``max_new`` 6; the call that reads that
    commit, or one that delivers the ``eos_id``, is told to keep the chip
    free, every other to run ahead."""
    sess = build("sdar_moe:all_at_once")
    prompt = np.random.default_rng(9).integers(0, 60, 6).tolist()
    whole = [tok for tok, _, _ in alone_blocks(sess, prompt, 6)]
    eos = {"max_new": -1, "eos_in_its_first_block": whole[1],
           "eos_in_its_last_block": next(
               t for t in whole[2:] if t not in whole[:2])}[ends_by]
    req = Request(rid=0, prompt=prompt, max_new=6, eos_id=eos)
    before, calls = counted(sess), []
    with watched(sess, calls):
        Scheduler(sess).run([req])
    assert [a for a, _, _ in calls] == flags
    assert list(req.tokens) == whole[:len(req.tokens)] and not req.failed
    assert req.tokens[-1] == eos or len(req.tokens) == 6
    # one pass a call: none was launched behind the request's end
    assert since(sess, before)["slot_passes"] == len(flags)
    assert sess._flight is None and sess.active_slots() == []


def test_a_bare_block_step_is_the_synchronous_call():
    """No argument: what ``prefill`` and ``release`` do between two passes
    is seen by the next one; told it may, a call leaves one pass in
    flight and every later call launches one."""
    sess = build("sdar_moe:quota_only")
    slot_a = admit_block(sess, [5, 6, 7], 8)
    out, _ = sess.step()
    assert sorted(out) == [slot_a]
    slot_b = admit_block(sess, [8, 9], 8)
    out, _ = sess.step()
    assert sorted(out) == sorted([slot_a, slot_b])
    sess.release(slot_a)
    out, _ = sess.step()
    assert sorted(out) == [slot_b]
    report = sess.decode_report()
    assert (report["steps"], report["steps_ahead"]) == (3, 0)
    for _ in range(3):
        out, _ = sess.step(ahead=True)
        assert sorted(out) == [slot_b]
    report = sess.decode_report()
    assert (report["steps"], report["steps_ahead"]) == (7, 3)
    assert sess._flight is not None
    sess.step()                             # reads it, launches nothing
    assert sess._flight is None
    assert sess.decode_report()["steps"] == 7
