"""Failover edge cases: replica death at every request phase must lose
nothing and change nothing (mxnet_tpu/serve/supervisor.py).

The invariant under test everywhere: a completed response from a run
with replica kills is bit-identical to the same trace on a never-failed
single session — failover re-admits drained requests through the PR 14
park/resume path, whose re-prefill asserts the replayed token against
the last committed one.
"""
import pytest

from mxnet_tpu import serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serve import model as serve_model
from mxnet_tpu.testing import faults

from serve_util import lend

CFG = serve.ModelConfig(vocab_size=61, num_layers=2, d_model=32,
                        num_heads=2, max_len=64)
SCONF = serve.ServeConfig(slots=3, page_size=8, buckets=(8, 16),
                          max_new=8, exact=True)


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("MXNET_FAULT_INJECT", raising=False)
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def params():
    return serve_model.init_params(CFG, seed=3)


@pytest.fixture(scope="module")
def _pool(params):
    return [serve.InferenceSession(params, num_heads=CFG.num_heads,
                                   config=SCONF) for _ in range(3)]


@pytest.fixture
def pool(_pool):
    yield from lend(*_pool)


def _mk(n=8, max_new=6):
    return [serve.Request(rid=i, prompt=[1 + i, 2, 3], max_new=max_new)
            for i in range(n)]


@pytest.fixture(scope="module")
def oracle(_pool):
    out, _ = serve.Scheduler(_pool[2]).run(_mk(12))
    assert all(not r.failed for r in out)
    streams = {r.rid: list(r.tokens) for r in out}
    for sess in _pool:
        sess.reset_cold()
    return streams


# ---------------------------------------------------------------------------
# the kill-phase matrix
# ---------------------------------------------------------------------------

# serve_replica_kill hits alternate r0 (odd), r1 (even) while both
# replicas are live, and fire BEFORE the tick body — so `after=` picks
# both the victim and the phase its requests die in.  With max_new=6 a
# request commits 2 tokens on r0's tick 1 (prefill + that tick's step)
# and one more per tick after, finishing on tick 5:
#   hit 1 = r0 tick 1: nothing prefilled yet -> fresh requeue path
#   hit 5 = r0 tick 3: mid-decode, 3 tokens committed -> resume path
#   hit 9 = r0 tick 5: 5 of 6 tokens committed -> resume replays the
#           last committed token, then generates exactly one more
@pytest.mark.chaos
@pytest.mark.parametrize("after,phase", [(1, "during-prefill"),
                                         (5, "mid-decode"),
                                         (9, "final-token")])
def test_kill_phase_matrix_zero_lost_bit_exact(monkeypatch, pool, oracle,
                                               after, phase):
    monkeypatch.setenv("MXNET_FAULT_INJECT",
                       "serve_replica_kill:kill:after=%d" % after)
    faults.reset()
    rs = serve.ReplicaSet(sessions=pool[:2], rejoin_backoff_s=30.0)
    out, makespan = rs.run(_mk(8))
    s = serve.summarize(out, makespan)
    assert s["completed"] == 8 and s["failed"] == 0, (phase, s)
    assert rs.counters["deaths"] == 1
    assert all(oracle[r.rid] == r.tokens for r in out), phase
    if phase == "during-prefill":
        # nothing was committed: everything re-enters as fresh work
        death = next(e for e in rs.events if e["event"] == "death")
        assert death["drained_resumable"] == 0
        assert rs.counters["failover_requests"] == 0
    else:
        assert rs.counters["failover_requests"] > 0
        assert s["resumes"] == rs.counters["failover_requests"]
    # failover must not mint new executables on the survivor
    assert rs.executables_per_replica() == [len(SCONF.buckets) + 1] * 2


@pytest.mark.chaos
def test_kill_with_all_survivor_slots_busy(monkeypatch, pool, oracle):
    # 12 requests over 2x3 slots: when r0 dies the survivor is full,
    # so failover requests must WAIT for slots (not shed, not lost)
    # and still replay bit-exactly
    monkeypatch.setenv("MXNET_FAULT_INJECT",
                       "serve_replica_kill:kill:after=5")
    faults.reset()
    rs = serve.ReplicaSet(sessions=pool[:2], rejoin_backoff_s=30.0)
    out, makespan = rs.run(_mk(12))
    s = serve.summarize(out, makespan)
    assert s["completed"] == 12 and s["failed"] == 0 and s["shed"] == 0
    assert all(oracle[r.rid] == r.tokens for r in out)


@pytest.mark.chaos
def test_last_replica_dying_raises_typed(monkeypatch, pool):
    monkeypatch.setenv("MXNET_FAULT_INJECT",
                       "serve_replica_kill:kill:sticky=1")
    faults.reset()
    rs = serve.ReplicaSet(sessions=pool[:1], rejoin_backoff_s=30.0)
    reqs = _mk(4)
    with pytest.raises(serve.ServeUnavailable) as ei:
        rs.run(reqs)
    assert ei.value.replicas == 1 and ei.value.outstanding == 4
    assert isinstance(ei.value, MXNetError)  # catchable as the base type
    # the outstanding requests were failed typed, not dropped
    assert all(r.failed and "ServeUnavailable" in r.error for r in reqs)
    # and the incident artifact still got written on the way out
    assert rs.incident_path is not None


@pytest.mark.chaos
def test_both_replicas_die_then_unavailable(monkeypatch, pool):
    # consecutive kills (descending after=) take out r0 then r1 before
    # the work finishes; huge backoff keeps them dead
    monkeypatch.setenv("MXNET_FAULT_INJECT",
                       "serve_replica_kill:kill:after=2,"
                       "serve_replica_kill:kill:after=1")
    faults.reset()
    rs = serve.ReplicaSet(sessions=pool[:2], rejoin_backoff_s=30.0)
    reqs = _mk(8)
    with pytest.raises(serve.ServeUnavailable):
        rs.run(reqs)
    assert rs.counters["deaths"] == 2
    assert all(r.failed for r in reqs)


@pytest.mark.chaos
def test_mini_soak_kill_and_rejoin(monkeypatch, pool, oracle):
    # the fast in-tree cousin of the bench soak: kill r0 mid-traffic,
    # let it rejoin cold, and require zero lost + bit-exact streams
    monkeypatch.setenv("MXNET_FAULT_INJECT",
                       "serve_replica_kill:kill:after=5")
    faults.reset()
    rs = serve.ReplicaSet(sessions=pool[:3], rejoin_backoff_s=0.005)
    out, makespan = rs.run(_mk(12))
    s = serve.summarize(out, makespan)
    assert s["completed"] == 12 and s["failed"] == 0
    assert rs.counters["deaths"] == 1 and rs.counters["rejoins"] == 1
    assert all(oracle[r.rid] == r.tokens for r in out)
    assert rs.executables_per_replica() == [len(SCONF.buckets) + 1] * 3
    for sess in pool[:3]:
        assert sess.fallback_count() == 0
        assert sess.active_slots() == []


# ---------------------------------------------------------------------------
# the primitives failover is built from
# ---------------------------------------------------------------------------

def test_scheduler_drain_splits_resumable_from_fresh(pool):
    sched = serve.Scheduler(pool[0])
    reqs = _mk(5)
    sched.begin(reqs)
    sched.tick(wait=False)  # 3 slots prefill + step; 2 stay pending
    resumable, fresh = sched.drain()
    assert [r.rid for r in resumable] == [0, 1, 2]
    assert all(len(r.tokens) == 2 for r in resumable)
    assert [r.rid for r in fresh] == [3, 4]
    assert not sched.outstanding and sched.load == 0
    assert pool[0].active_slots() == []  # slots released best-effort


def test_resume_replay_divergence_is_fatal(pool):
    # failover trusts the replay assertion; corrupt a committed stream
    # and the scheduler must refuse to serve the wrong bytes
    sched = serve.Scheduler(pool[0])
    reqs = _mk(1)
    sched.begin(reqs)
    sched.tick(wait=False)
    resumable, _ = sched.drain()
    req = resumable[0]
    req.tokens[-1] = (req.tokens[-1] + 1) % CFG.vocab_size  # corrupt
    sched.submit(req, parked=True)
    with pytest.raises(MXNetError, match="resume replay diverged"):
        sched.tick(wait=False)


def test_scheduler_submit_mid_run(pool):
    sched = serve.Scheduler(pool[0])
    sched.begin(_mk(2))
    sched.tick(wait=False)
    late = serve.Request(rid=50, prompt=[9, 8, 7], max_new=4)
    sched.submit(late)
    while sched.tick(wait=False):
        pass
    assert late.done_s >= 0 and len(late.tokens) == 4
