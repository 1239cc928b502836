"""Admission flight recorder: decision traces, explain surfaces, and
the merged host+sidecar Chrome trace.

Acceptance shape (ISSUE 4): for any scheduled-then-skipped workload,
``GET /api/workloads/<ns>/<name>/explain`` (and ``tools/explain.py``)
returns a non-empty reason chain whose final event matches the
workload's actual state, on BOTH the host path and the solver path —
including a breaker-open fallback cycle from the chaos harness — and a
merged Chrome-trace export contains host cycle spans and sidecar solve
spans sharing the same cycle id.
"""

import json
import os
import subprocess
import sys
import tempfile
import urllib.error
import urllib.request

import pytest

from kueue_oss_tpu import metrics, obs
from kueue_oss_tpu.api.types import (
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    PreemptionPolicy,
    PreemptionPolicyValue,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_oss_tpu.core.queue_manager import QueueManager
from kueue_oss_tpu.core.store import Store
from kueue_oss_tpu.scheduler.scheduler import Scheduler
from kueue_oss_tpu.solver.engine import SolverEngine
from kueue_oss_tpu.solver.resilience import OPEN, SolverHealth, SolverUnavailable
from kueue_oss_tpu.solver.service import SolverClient, SolverServer


@pytest.fixture(autouse=True)
def _reset():
    metrics.reset_all()
    obs.recorder.clear()
    obs.recorder.enabled = True
    yield
    metrics.reset_all()
    obs.recorder.clear()


def _mk_env(nominal=1000, preemption=False):
    store = Store()
    store.upsert_resource_flavor(ResourceFlavor(name="default"))
    cq = ClusterQueue(
        name="cq", resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name="default", resources=[
                ResourceQuota(name="cpu", nominal=nominal)])])])
    if preemption:
        cq.preemption = PreemptionPolicy(
            within_cluster_queue=PreemptionPolicyValue.LOWER_PRIORITY)
    store.upsert_cluster_queue(cq)
    store.upsert_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    queues = QueueManager(store)
    return store, queues, Scheduler(store, queues)


def _submit(store, name, cpu=800, priority=0, t=0.0):
    store.add_workload(Workload(
        name=name, queue_name="lq", priority=priority, creation_time=t,
        podsets=[PodSet(name="main", count=1, requests={"cpu": cpu})]))


def _solver_store(n_cqs=4, quota=8, n_wl=24):
    store = Store()
    store.upsert_resource_flavor(ResourceFlavor(name="f"))
    for i in range(n_cqs):
        store.upsert_cluster_queue(ClusterQueue(
            name=f"cq{i}", resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[FlavorQuotas(name="f", resources=[
                    ResourceQuota(name="cpu", nominal=quota)])])]))
        store.upsert_local_queue(LocalQueue(
            name=f"lq{i}", cluster_queue=f"cq{i}"))
    for i in range(n_wl):
        store.add_workload(Workload(
            name=f"w{i}", queue_name=f"lq{i % n_cqs}", uid=i + 1,
            creation_time=float(i),
            podsets=[PodSet(name="main", count=1, requests={"cpu": 1})]))
    return store


# ---------------------------------------------------------------------------
# host path
# ---------------------------------------------------------------------------


def test_host_path_assigned_and_skip_chain():
    store, queues, sched = _mk_env(nominal=1000)
    _submit(store, "w1", t=0.0)
    _submit(store, "w2", t=1.0)  # doesn't fit behind w1
    sched.run_until_quiet(now=0.0, tick=1.0)

    assert store.workloads["default/w1"].is_admitted
    chain1 = obs.recorder.explain("default/w1")
    assert chain1 and chain1[0].kind == obs.ASSIGNED
    assert chain1[0].path == obs.HOST
    assert chain1[0].detail["flavors"] == {"main": {"cpu": "default"}}

    assert not store.workloads["default/w2"].is_quota_reserved
    chain2 = obs.recorder.explain("default/w2")
    assert chain2, "a scheduled-then-skipped workload has a reason chain"
    assert chain2[0].kind == obs.SKIPPED
    assert "insufficient" in chain2[0].reason
    assert chain2[0].cluster_queue == "cq"
    # counters track the journal
    assert metrics.decision_events_total.value(obs.ASSIGNED) >= 1
    assert metrics.decision_events_total.value(obs.SKIPPED) >= 1


def test_no_fit_reason_survives_with_structured_detail():
    store, queues, sched = _mk_env(nominal=1000)
    _submit(store, "big", cpu=5000)  # exceeds max capacity: NoFit
    sched.schedule(now=0.0)
    chain = obs.recorder.explain("default/big")
    assert chain and chain[0].kind == obs.SKIPPED
    assert chain[0].reason_slug == "no_fit"
    assert "insufficient quota for cpu in flavor default" in chain[0].reason
    assert chain[0].detail["mode"] == "NoFit"
    assert any("insufficient quota" in r
               for r in chain[0].detail["podsets"]["main"])
    assert metrics.decision_skips_total.value("no_fit") == 1


def test_preemption_records_victim_and_preemptor():
    store, queues, sched = _mk_env(nominal=1000, preemption=True)
    _submit(store, "victim", cpu=800, priority=0, t=0.0)
    sched.schedule(now=0.0)
    _submit(store, "vip", cpu=800, priority=10, t=1.0)
    sched.run_until_quiet(now=2.0, tick=1.0)

    assert store.workloads["default/vip"].is_admitted
    v = obs.recorder.explain("default/victim")
    kinds = [ev.kind for ev in v]
    assert obs.PREEMPTED in kinds
    preempted = next(ev for ev in v if ev.kind == obs.PREEMPTED)
    assert "default/vip" in preempted.reason
    p = obs.recorder.explain("default/vip")
    assert p[0].kind == obs.ASSIGNED  # newest-first: final outcome
    assert any(ev.reason_slug == "preempting" and
               ev.detail["targets"] == ["default/victim"]
               for ev in p)


def test_eviction_event_host_path():
    store, queues, sched = _mk_env()
    _submit(store, "w1")
    sched.schedule(now=0.0)
    sched.evict_workload("default/w1", reason="Deactivated",
                         message="stopped by user", now=1.0)
    chain = obs.recorder.explain("default/w1")
    assert chain[0].kind == obs.EVICTED
    assert chain[0].reason == "stopped by user"
    assert chain[0].reason_slug == "Deactivated"


# ---------------------------------------------------------------------------
# solver path
# ---------------------------------------------------------------------------


def test_solver_path_admitted_and_parked_events():
    store = _solver_store(n_cqs=2, quota=4, n_wl=12)  # 8 fit, 4 park
    queues = QueueManager(store)
    engine = SolverEngine(store, queues)
    result = engine.drain(now=0.0)
    assert result.admitted == 8
    admitted = next(k for k, w in store.workloads.items()
                    if w.is_quota_reserved)
    chain = obs.recorder.explain(admitted)
    assert chain[0].kind == obs.SOLVER_ADMITTED
    assert chain[0].path == obs.SOLVER
    assert chain[0].detail["flavors"] == {"cpu": "f"}
    parked = next(k for k, w in store.workloads.items()
                  if not w.is_quota_reserved)
    pchain = obs.recorder.explain(parked)
    assert pchain and pchain[0].kind == obs.SKIPPED
    assert pchain[0].reason_slug == "solver_parked"
    assert pchain[0].path == obs.SOLVER


def test_breaker_open_fallback_chain_from_chaos_harness():
    """Dead sidecar -> breaker trips -> drains degrade to the host path:
    the journal shows the solver-fallback cycle events (tagged with the
    breaker state) AND every workload's final event still matches its
    actual admitted-by-host state."""
    store = _solver_store(n_cqs=4, quota=8, n_wl=24)
    queues = QueueManager(store)
    now = [0.0]
    health = SolverHealth(failure_threshold=1, cooldown_s=1e9,
                          clock=lambda: now[0])
    sched = Scheduler(store, queues, solver_min_backlog=8)
    engine = SolverEngine(
        store, queues, scheduler=sched, health=health,
        remote=SolverClient("/nonexistent-solver.sock", timeout_s=5.0,
                            max_retries=0, backoff_base_s=0.001,
                            sleep=lambda _s: None))
    sched.solver = engine
    sched.run_until_quiet(now=0.0, tick=1.0)

    assert health.state == OPEN
    cycle_events = [ev for ev in obs.recorder.events()
                    if ev.workload == obs.CYCLE_SCOPE]
    slugs = {ev.reason_slug for ev in cycle_events}
    assert "backend_error" in slugs, "the first drain's fault is recorded"
    # a drain refused by the OPEN breaker is itself journaled
    with pytest.raises(SolverUnavailable, match="breaker"):
        engine.drain(now=99.0)
    open_evs = [ev for ev in obs.recorder.events()
                if ev.reason_slug == "breaker_open"]
    assert open_evs and open_evs[-1].breaker == "open"
    assert open_evs[-1].path == obs.SOLVER
    # host cycles finished the round: final events match actual state
    for key, wl in store.workloads.items():
        chain = obs.recorder.explain(key)
        assert chain, f"no decisions for {key}"
        if wl.is_quota_reserved:
            assert chain[0].kind == obs.ASSIGNED
            assert chain[0].path == obs.HOST
        else:
            assert chain[0].kind == obs.SKIPPED


# ---------------------------------------------------------------------------
# merged chrome trace: host cycle spans + sidecar solve spans
# ---------------------------------------------------------------------------


def test_chrome_trace_merges_host_and_sidecar_spans():
    from kueue_oss_tpu.debugger.profiling import Tracer, attach_to_scheduler

    store = _solver_store(n_cqs=4, quota=8, n_wl=24)
    queues = QueueManager(store)
    path = os.path.join(tempfile.mkdtemp(), "solver.sock")
    srv = SolverServer(path)
    srv.serve_in_background()
    tracer = Tracer()
    try:
        sched = Scheduler(store, queues, solver_min_backlog=8)
        attach_to_scheduler(sched, tracer)
        engine = SolverEngine(store, queues, scheduler=sched,
                              remote=SolverClient(path, timeout_s=60.0))
        sched.solver = engine
        sched.run_until_quiet(now=0.0, tick=1.0)
    finally:
        srv.shutdown()
        srv.server_close()
        # a live tracer holds the span switch on for whatever test this
        # worker runs next (tests/test_spans.py starts from "off")
        from kueue_oss_tpu.obs import spans
        spans.remove_sink(tracer)
    assert sum(1 for w in store.workloads.values()
               if w.is_quota_reserved) == 24  # capacity 32 >= all 24

    trace = json.loads(tracer.chrome_trace())
    events = trace["traceEvents"]
    host_cycles = {e["args"]["cycle"] for e in events
                   if e["name"] == "schedule" and e.get("args")}
    sidecar = [e for e in events if e["name"] == "sidecar_solve"]
    drains = [e for e in events if e["name"] == "solver_drain"]
    assert host_cycles and sidecar and drains
    # the sidecar solve span and a host cycle span share a cycle id
    assert any(e["args"]["cycle"] in host_cycles for e in sidecar), (
        f"sidecar cycles {[e['args'] for e in sidecar]} never meet "
        f"host cycles {host_cycles}")
    # every drain serves the host cycle that follows it, so its cycle id
    # must resolve to a real schedule span
    assert all(e["args"]["cycle"] in host_cycles for e in drains)
    ev = obs.recorder.events()
    assert any(e.kind == obs.SOLVER_ADMITTED for e in ev)


# ---------------------------------------------------------------------------
# dashboard surfaces
# ---------------------------------------------------------------------------


def test_dashboard_metrics_explain_and_decisions_endpoints():
    from kueue_oss_tpu.viz import Dashboard, DashboardServer

    store, queues, sched = _mk_env(nominal=1000)
    _submit(store, "running", t=0.0)
    _submit(store, "waiting", t=1.0)
    sched.run_until_quiet(now=0.0, tick=1.0)
    dash = Dashboard(store, queues)
    srv = DashboardServer(dash)
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        # /metrics renders the Prometheus exposition, recorder series in
        text = urllib.request.urlopen(
            f"{base}/metrics", timeout=5).read().decode()
        assert "# TYPE kueue_decision_events_total counter" in text
        assert 'kueue_decision_events_total{kind="assigned"}' in text
        assert "kueue_tpu_solver_breaker_state" in text

        # per-workload explain: non-empty chain, final event = state
        data = json.loads(urllib.request.urlopen(
            f"{base}/api/workloads/default/waiting/explain",
            timeout=5).read())
        assert data["workload"] == "default/waiting"
        assert data["events"], "skipped workload explains non-empty"
        assert data["events"][0]["kind"] == obs.SKIPPED
        assert "insufficient" in data["events"][0]["reason"]
        data = json.loads(urllib.request.urlopen(
            f"{base}/api/workloads/default/running/explain",
            timeout=5).read())
        assert data["events"][0]["kind"] == obs.ASSIGNED

        # unknown workload with no journal entries -> 404
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                f"{base}/api/workloads/default/ghost/explain", timeout=5)
        assert exc.value.code == 404

        # /api/decisions groups the last-N cycles, newest first
        data = json.loads(urllib.request.urlopen(
            f"{base}/api/decisions?cycles=3", timeout=5).read())
        assert data["cycles"]
        assert data["cycles"][0]["cycle"] >= data["cycles"][-1]["cycle"]
        kinds = {ev["kind"] for c in data["cycles"]
                 for ev in c["events"]}
        assert obs.ASSIGNED in kinds

        # overview carries the PR-3 resilience series
        data = json.loads(urllib.request.urlopen(
            f"{base}/api/overview", timeout=5).read())
        assert data["solver"]["breakerState"] == "closed"
        assert data["solver"]["breakerTrips"] == 0
        assert "fallbacks" in data["solver"]
        assert "remoteFailures" in data["solver"]
    finally:
        srv.stop()


def test_overview_shows_breaker_trip():
    from kueue_oss_tpu.viz import Dashboard

    store, queues, _ = _mk_env()
    health = SolverHealth(failure_threshold=1, cooldown_s=1e9)
    health.record_failure()
    metrics.solver_fallback_total.inc("breaker_open")
    metrics.solver_remote_failures_total.inc("connection")
    view = Dashboard(store, queues).solver_view()
    assert view["breakerState"] == "open"
    assert view["breakerTrips"] == 1
    assert view["fallbacks"] == {"breaker_open": 1}
    assert view["remoteFailures"] == {"connection": 1}


# ---------------------------------------------------------------------------
# tools/explain.py end to end
# ---------------------------------------------------------------------------


def test_explain_cli_end_to_end():
    store, queues, sched = _mk_env(nominal=1000)
    _submit(store, "w1", t=0.0)
    _submit(store, "w2", t=1.0)
    sched.run_until_quiet(now=0.0, tick=1.0)
    journal = os.path.join(tempfile.mkdtemp(), "decisions.jsonl")
    n = obs.recorder.dump_jsonl(journal)
    assert n > 0

    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "explain.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, tool, "--journal", journal, "default/w2"],
        capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr
    assert "default/w2" in out.stdout
    assert "skipped" in out.stdout
    assert "insufficient" in out.stdout, (
        "the CLI prints the kept no-fit reason")

    # summary mode lists every workload's latest decision
    out = subprocess.run(
        [sys.executable, tool, "--journal", journal],
        capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr
    assert "default/w1" in out.stdout and "default/w2" in out.stdout
    assert "assigned" in out.stdout

    # unknown workload: clean failure, not a stack trace
    out = subprocess.run(
        [sys.executable, tool, "--journal", journal, "default/ghost"],
        capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 1
    assert "no decisions recorded" in out.stdout


def test_explain_cli_cycles_mode_inline():
    import io

    from tools.explain import main as explain_main

    store, queues, sched = _mk_env(nominal=1000)
    _submit(store, "w1")
    sched.schedule(now=0.0)
    journal = os.path.join(tempfile.mkdtemp(), "d.jsonl")
    obs.recorder.dump_jsonl(journal)
    buf = io.StringIO()
    assert explain_main(["--journal", journal, "--cycles", "2"],
                        out=buf) == 0
    assert "cycle 1:" in buf.getvalue()
    assert "assigned" in buf.getvalue()


# ---------------------------------------------------------------------------
# recorder mechanics
# ---------------------------------------------------------------------------


def test_recorder_ring_and_per_workload_bounds():
    rec = obs.FlightRecorder(max_events=8, per_workload=4)
    for i in range(20):
        rec.record(obs.SKIPPED, "ns/w", cycle=i, reason=f"r{i}",
                   reason_slug="no_fit")
    assert len(rec.events()) == 8
    assert rec.events()[-1].cycle == 19
    chain = rec.explain("ns/w")
    assert len(chain) == 4  # per-workload cap
    assert chain[0].cycle == 19  # newest first
    rec.clear()
    assert not rec.events() and not rec.explain("ns/w")


def test_recorder_disabled_is_a_noop():
    rec = obs.FlightRecorder()
    rec.enabled = False
    assert rec.record(obs.ASSIGNED, "ns/w") is None
    assert not rec.events()


def test_decisions_groups_host_and_solver_by_cycle():
    rec = obs.FlightRecorder()
    rec.record(obs.ASSIGNED, "ns/a", cycle=3, path=obs.HOST)
    rec.record(obs.SOLVER_ADMITTED, "ns/b", cycle=3, path=obs.SOLVER)
    rec.record(obs.SKIPPED, "ns/c", cycle=2, reason_slug="no_fit")
    groups = rec.decisions(last_cycles=1)
    assert len(groups) == 1 and groups[0]["cycle"] == 3
    paths = {ev["path"] for ev in groups[0]["events"]}
    assert paths == {obs.HOST, obs.SOLVER}


def test_journal_roundtrip_preserves_events():
    obs.recorder.record(obs.SKIPPED, "ns/w", cycle=7, cluster_queue="cq",
                        reason="why not", reason_slug="no_fit",
                        detail={"mode": "NoFit"})
    path = os.path.join(tempfile.mkdtemp(), "j.jsonl")
    obs.recorder.dump_jsonl(path)
    back = obs.load_jsonl(path)
    assert len(back) == 1
    ev = back[0]
    assert (ev.kind, ev.workload, ev.cycle, ev.cluster_queue) == (
        obs.SKIPPED, "ns/w", 7, "cq")
    assert ev.reason == "why not" and ev.detail == {"mode": "NoFit"}
