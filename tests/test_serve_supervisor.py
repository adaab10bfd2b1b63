"""Replica supervisor: dispatch, overload shedding, circuit breaker,
watchdog liveness, rejoin, and the incident artifact
(mxnet_tpu/serve/supervisor.py, docs/serving.md "Resilience").

Determinism notes the chaos specs below rely on:

* ``serve_replica_kill`` fires at the top of every live replica's tick,
  in replica-index order — so while both of two replicas are live, the
  site's hit counter alternates r0 (odd hits), r1 (even hits), and
  ``after=N`` parity picks the replica.
* A spec entry that *raises* skips the hit-count increment of every
  entry after it in the list, so multi-entry specs that must fire on
  CONSECUTIVE hits are written with descending ``after=`` values.
"""
import json
import os
import subprocess
import sys
import types

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
    for var in ("MXNET_SERVE_REPLICAS", "MXNET_SERVE_STEP_TIMEOUT_S",
                "MXNET_SERVE_DEADLINE_MS", "MXNET_SERVE_BREAKER_K"):
        monkeypatch.delenv(var, raising=False)
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def params():
    return serve_model.init_params(CFG, seed=3)


@pytest.fixture(scope="module")
def _pool(params):
    # sessions are expensive to compile; share three identical-config
    # ones across the module and hand them back cold after every test
    return [serve.InferenceSession(params, num_heads=CFG.num_heads,
                                   config=SCONF) for _ in range(3)]


@pytest.fixture
def pool(_pool):
    yield from lend(*_pool)


def _mk(n=8, max_new=6):
    return [serve.Request(rid=i, prompt=[1 + i, 2, 3], max_new=max_new)
            for i in range(n)]


def _oracle(sess, n=8, max_new=6):
    out, _ = serve.Scheduler(sess).run(_mk(n, max_new))
    for r in out:
        assert not r.failed, r.error
    return {r.rid: list(r.tokens) for r in out}


# ---------------------------------------------------------------------------
# construction + env knobs
# ---------------------------------------------------------------------------

def test_env_knobs_and_validation(monkeypatch, pool):
    monkeypatch.setenv("MXNET_SERVE_REPLICAS", "0")
    with pytest.raises(MXNetError, match=">= 1 replica"):
        serve.ReplicaSet(params="x", num_heads=2)
    monkeypatch.delenv("MXNET_SERVE_REPLICAS")
    with pytest.raises(MXNetError, match="params"):
        serve.ReplicaSet(replicas=2)  # no weights, no sessions
    monkeypatch.setenv("MXNET_SERVE_DEADLINE_MS", "250")
    monkeypatch.setenv("MXNET_SERVE_STEP_TIMEOUT_S", "7.5")
    monkeypatch.setenv("MXNET_SERVE_BREAKER_K", "4")
    rs = serve.ReplicaSet(sessions=pool[:2])
    assert (rs.deadline_ms, rs.step_timeout_s, rs.breaker_k) \
        == (250.0, 7.5, 4)
    assert rs.queue_cap == 4 * 2 * SCONF.slots  # default: 4x total slots
    with pytest.raises(MXNetError, match="breaker K"):
        serve.ReplicaSet(sessions=pool[:2], breaker_k=0)


def test_mismatched_configs_rejected():
    mk = lambda slots: types.SimpleNamespace(config=serve.ServeConfig(
        slots=slots, page_size=8, buckets=(8, 16)))
    with pytest.raises(MXNetError, match="share one ServeConfig"):
        serve.ReplicaSet(sessions=[mk(2), mk(3)])


# ---------------------------------------------------------------------------
# dispatch: multi-replica runs complete bit-exactly
# ---------------------------------------------------------------------------

def test_two_replicas_bit_exact_vs_single_session(pool):
    rs = serve.ReplicaSet(sessions=pool[:2])
    out, makespan = rs.run(_mk(8))
    s = serve.summarize(out, makespan)
    assert s["completed"] == 8 and s["failed"] == 0
    # clean runs write no incident artifact
    assert rs.incident_path is None and rs.events == []
    # replicated dispatch never changes content: every stream matches a
    # plain single-session scheduler run of the same trace
    oracle = _oracle(pool[2])
    assert all(oracle[r.rid] == r.tokens for r in out)
    # identical-config replicas share recompile guards: executable
    # count per replica stays at the frozen len(buckets)+1
    assert rs.executables_per_replica() == [len(SCONF.buckets) + 1] * 2


def test_followup_requests_flow_through_dispatcher(pool):
    spawned = []

    def followup(req, now_s):
        if req.rid < 2 and not spawned:
            nxt = serve.Request(rid=100, prompt=[7, 8, 9], max_new=4,
                                arrival_s=now_s)
            spawned.append(nxt)
            return nxt
        return None

    rs = serve.ReplicaSet(sessions=pool[:2])
    out, makespan = rs.run(_mk(4), followup=followup)
    s = serve.summarize(out, makespan)
    assert len(spawned) == 1 and s["completed"] == 5
    assert any(r.rid == 100 and r.done_s >= 0 for r in out)


# ---------------------------------------------------------------------------
# overload protection: bounded queue + deadline-aware shedding
# ---------------------------------------------------------------------------

def test_queue_cap_sheds_typed(pool):
    rs = serve.ReplicaSet(sessions=pool[:2], queue_cap=2)
    out, makespan = rs.run(_mk(12))
    s = serve.summarize(out, makespan)
    assert s["shed"] > 0 and s["faulted"] == 0
    assert s["completed"] + s["shed"] == 12  # nothing silently lost
    for r in out:
        if r.failed:
            assert r.shed and "ServeOverloaded" in r.error \
                and "queue full" in r.error
    assert rs.counters["shed"] == s["shed"]
    # the shed split is pinned: queue overflow, never deadline
    assert rs.counters["shed_queue"] == s["shed"]
    assert rs.counters["shed_deadline"] == 0
    assert s["shed_queue"] == s["shed"] and s["shed_deadline"] == 0
    assert all(r.shed_kind == "queue" for r in out if r.shed)
    shed_events = [e for e in rs.events if e["event"] == "shed"]
    assert len(shed_events) == s["shed"]
    assert all(e["kind"] == "queue" for e in shed_events)


def test_deadline_lapse_sheds_typed(pool):
    # a 1us budget lapses before the first tick: everything queued sheds
    rs = serve.ReplicaSet(sessions=pool[:2], deadline_ms=1e-3)
    out, makespan = rs.run(_mk(8))
    s = serve.summarize(out, makespan)
    assert s["shed"] == 8 and s["completed"] == 0
    assert all("deadline lapsed" in r.error or "projected TTFT" in r.error
               for r in out)
    # the shed split is pinned: all deadline, no queue overflow
    assert rs.counters["shed_deadline"] == 8
    assert rs.counters["shed_queue"] == 0
    assert s["shed_deadline"] == 8 and s["shed_queue"] == 0
    assert all(r.shed_kind == "deadline" for r in out)


def test_per_request_deadline_overrides_default(pool):
    rs = serve.ReplicaSet(sessions=pool[:2])  # no global deadline
    reqs = _mk(8)
    reqs[5].deadline_ms = 1e-3  # only this one carries a budget
    out, makespan = rs.run(reqs)
    s = serve.summarize(out, makespan)
    assert s["shed"] == 1 and s["completed"] == 7
    assert next(r for r in out if r.rid == 5).shed


# ---------------------------------------------------------------------------
# chaos: dispatch faults, breaker, watchdog, rejoin
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_dispatch_fault_fails_one_request_typed(monkeypatch, pool):
    monkeypatch.setenv("MXNET_FAULT_INJECT", "serve_dispatch:raise:after=3")
    faults.reset()
    rs = serve.ReplicaSet(sessions=pool[:2])
    out, makespan = rs.run(_mk(8))
    s = serve.summarize(out, makespan)
    assert s["completed"] == 7 and s["faulted"] == 1 and s["shed"] == 0
    bad = [r for r in out if r.failed]
    assert len(bad) == 1 and "FaultInjected" in bad[0].error
    assert rs.counters["dispatch_faults"] == 1


@pytest.mark.chaos
def test_breaker_tolerates_faults_below_k(monkeypatch, pool):
    # descending after= -> r0 faults at its ticks 1 and 2, consecutively
    monkeypatch.setenv("MXNET_FAULT_INJECT",
                       "serve_replica_kill:raise:after=3,"
                       "serve_replica_kill:raise:after=1")
    faults.reset()
    rs = serve.ReplicaSet(sessions=pool[:2], breaker_k=3,
                          rejoin_backoff_s=30.0)
    out, makespan = rs.run(_mk(8))
    s = serve.summarize(out, makespan)
    assert s["completed"] == 8 and rs.counters["deaths"] == 0
    evs = [e for e in rs.events if e["event"] == "breaker_fault"]
    assert [e["replica"] for e in evs] == [0, 0]
    assert evs[-1]["consecutive"] == 2  # got to K-1, then the clean
    #                                     tick reset the streak


@pytest.mark.chaos
def test_breaker_ejects_at_k_consecutive(monkeypatch, pool):
    monkeypatch.setenv("MXNET_FAULT_INJECT",
                       "serve_replica_kill:raise:after=5,"
                       "serve_replica_kill:raise:after=3,"
                       "serve_replica_kill:raise:after=1")
    faults.reset()
    rs = serve.ReplicaSet(sessions=pool[:2], breaker_k=3,
                          rejoin_backoff_s=30.0)
    out, makespan = rs.run(_mk(8))
    s = serve.summarize(out, makespan)
    # the ejected replica's work failed over; nothing was lost
    assert s["completed"] == 8 and s["failed"] == 0
    assert rs.counters["deaths"] == 1
    death = next(e for e in rs.events if e["event"] == "death")
    assert death["replica"] == 0 and "circuit breaker" in death["detail"]


@pytest.mark.chaos
def test_watchdog_marks_hung_replica_dead(monkeypatch, pool):
    # r0 wedges at its 2nd tick; the 0.3s watchdog delivers StepHung
    # into the supervisor loop, r0 is ejected, r1 finishes everything
    monkeypatch.setenv("MXNET_FAULT_INJECT",
                       "serve_replica_kill:hang:after=3:seconds=2")
    faults.reset()
    rs = serve.ReplicaSet(sessions=pool[:2], step_timeout_s=0.3,
                          rejoin_backoff_s=30.0)
    out, makespan = rs.run(_mk(8))
    s = serve.summarize(out, makespan)
    assert s["completed"] == 8 and s["failed"] == 0
    death = next(e for e in rs.events if e["event"] == "death")
    assert death["replica"] == 0 and "watchdog" in death["detail"]
    assert rs._watchdog is None  # stopped in the run's finally


@pytest.mark.chaos
def test_rejoin_probe_backoff_then_cold_rejoin(monkeypatch, pool):
    # kill r0 immediately; two probe faults (descending after= so they
    # hit consecutive probes) double the backoff, the third probe wins
    monkeypatch.setenv("MXNET_FAULT_INJECT",
                       "serve_replica_kill:kill:after=1,"
                       "serve_rejoin:raise:after=2,"
                       "serve_rejoin:raise:after=1")
    faults.reset()
    rs = serve.ReplicaSet(sessions=pool[:2], rejoin_backoff_s=0.002)
    out, makespan = rs.run(_mk(12))
    s = serve.summarize(out, makespan)
    assert s["completed"] == 12 and s["failed"] == 0
    assert rs.counters["probes_failed"] == 2
    assert rs.counters["rejoins"] == 1
    assert rs.replicas[0].state == "live"
    pf = [e for e in rs.events if e["event"] == "probe_failed"]
    assert pf[1]["next_backoff_s"] == pytest.approx(
        2 * pf[0]["next_backoff_s"])


def test_reset_cold_drops_slots_and_prefix_index(params):
    # the rejoin path's cold restart: slots released, prefix index gone.
    # needs its own session: the pool keeps the prefix cache off, and
    # publishing requires a full prompt page (page_size tokens)
    cfg = serve.ServeConfig(slots=3, page_size=8, buckets=(8, 16),
                            max_new=8, exact=True, prefix_pages=-1)
    sess = serve.InferenceSession(params, num_heads=CFG.num_heads,
                                  config=cfg)
    reqs = [serve.Request(rid=i, prompt=[5, 4, 3, 2, 1, 2, 3, 4],
                          max_new=4) for i in range(2)]
    out, _ = serve.Scheduler(sess).run(reqs)
    assert all(not r.failed for r in out)
    assert len(sess.cache._key_of) > 0  # prefixes were published
    sess.reset_cold()
    assert sess.active_slots() == []
    assert len(sess.cache._key_of) == 0
    assert len(sess.cache._retained) == 0
    assert sess.cache.free_pages == sess.cache.num_pages


# ---------------------------------------------------------------------------
# incident artifact + diagnose tool
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_incident_artifact_rendered_by_diagnose(monkeypatch, pool,
                                                tmp_path):
    monkeypatch.setenv("MXNET_FAULT_INJECT",
                       "serve_replica_kill:kill:after=5")
    faults.reset()
    rs = serve.ReplicaSet(sessions=pool[:2], rejoin_backoff_s=30.0,
                          incident_dir=str(tmp_path))
    out, _ = rs.run(_mk(8))
    assert rs.incident_path is not None \
        and rs.incident_path.startswith(str(tmp_path))
    payload = json.loads(open(rs.incident_path).read())
    assert payload["kind"] == "mxnet_tpu-serve-incident"
    assert payload["counters"]["deaths"] == 1
    assert [e["event"] for e in payload["timeline"]].count("failover") \
        == payload["counters"]["failover_requests"]
    tool = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "diagnose.py")
    res = subprocess.run([sys.executable, tool, str(tmp_path)],
                         capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stderr
    assert "SERVE INCIDENT" in res.stdout
    assert "death" in res.stdout and "failover" in res.stdout
    assert "chaos-killed" in res.stdout


def test_summarize_surfaces_robustness_counters(pool):
    # shed + faulted split, resumes counted — no chaos needed: shed via
    # a tiny queue, and the counters must reconcile with `failed`
    rs = serve.ReplicaSet(sessions=pool[:2], queue_cap=1)
    out, makespan = rs.run(_mk(10))
    s = serve.summarize(out, makespan)
    for key in ("shed", "shed_queue", "shed_deadline", "faulted",
                "cancelled", "preemptions", "resumes"):
        assert key in s
    assert s["failed"] == s["shed"] + s["faulted"] + s["cancelled"]
    assert s["shed"] == s["shed_queue"] + s["shed_deadline"]
    assert s["cancelled"] == 0  # nothing cancels in a closed run
    assert s["resumes"] == sum(r.resumes for r in out)
