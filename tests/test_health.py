"""Cluster health layer: per-cycle ledger, queue-wait SLO engine with
burn-rate alerts, and the durable explain journal (ISSUE 10).

Acceptance shape: a seeded contention run produces a burn-rate alert
whose exemplar links to a ledger row AND a non-empty ``explain`` chain
for the same cycle — asserted end-to-end, and still true after a
SIGKILL + recover (journal + ledger restored from the checkpoint-time
ring dumps).
"""

import json
import os
import re
import subprocess
import sys
import tempfile

import pytest

from kueue_oss_tpu import metrics, obs
from kueue_oss_tpu.api.types import (
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_oss_tpu.core.queue_manager import QueueManager
from kueue_oss_tpu.core.store import Store
from kueue_oss_tpu.obs.health import SLOEngine
from kueue_oss_tpu.scheduler.scheduler import Scheduler

pytestmark = pytest.mark.slo

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset():
    metrics.reset_all()
    metrics.exemplars_enabled = True
    obs.recorder.clear()
    obs.recorder.enabled = True
    obs.cycle_ledger.clear()
    obs.cycle_ledger.enabled = True
    obs.slo_engine.reset()
    obs.slo_engine.enabled = True
    yield
    metrics.reset_all()
    metrics.exemplars_enabled = True
    obs.recorder.clear()
    obs.cycle_ledger.clear()
    obs.slo_engine.reset()


def _mk_env(nominal=1000):
    store = Store()
    store.upsert_resource_flavor(ResourceFlavor(name="default"))
    store.upsert_cluster_queue(ClusterQueue(
        name="cq", resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name="default", resources=[
                ResourceQuota(name="cpu", nominal=nominal)])])]))
    store.upsert_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    queues = QueueManager(store)
    return store, queues, Scheduler(store, queues)


def _submit(store, name, cpu=400, priority=0, t=0.0):
    store.add_workload(Workload(
        name=name, queue_name="lq", priority=priority, creation_time=t,
        podsets=[PodSet(name="main", count=1, requests={"cpu": cpu})]))


# ---------------------------------------------------------------------------
# SLO engine: deterministic virtual-clock burn-rate sequences
# ---------------------------------------------------------------------------


def test_burn_rate_alert_fires_and_clears_on_virtual_clock():
    eng = SLOEngine(target=0.99, threshold_s=10.0, fast_window_s=300.0,
                    slow_window_s=3600.0, burn_threshold=6.0,
                    clock=lambda: 0.0)
    # a breached stream: every admission waits 100s > 10s threshold
    for i in range(30):
        eng.observe_admission("cq", 100.0, now=float(i * 10),
                              cycle=7, workload="ns/bad")
    rep = eng.evaluate(now=300.0)
    sli = next(s for s in rep["slis"]
               if s["scope"] == "cq" and s["key"] == "cq")
    assert sli["burnFast"] > 6.0 and sli["burnSlow"] > 6.0
    assert sli["alert"]["state"] == "firing"
    assert sli["alert"]["exemplar"]["workload"] == "ns/bad"
    assert sli["alert"]["exemplar"]["cycle"] == 7
    assert metrics.slo_alerts_firing.value("cq", "cq") == 1.0
    assert metrics.slo_alert_transitions_total.value(
        "cq", "cq", "fired") == 1

    # recovery: the fast window fills with good admissions and rolls
    # past the breaches -> the alert clears (fast-window recovery is
    # the clear condition; the slow window may still carry the burn)
    for i in range(200):
        eng.observe_admission("cq", 1.0, now=400.0 + i)
    rep = eng.evaluate(now=1000.0)
    sli = next(s for s in rep["slis"]
               if s["scope"] == "cq" and s["key"] == "cq")
    assert sli["burnFast"] == 0.0
    assert sli["alert"]["state"] == "clear"
    assert metrics.slo_alerts_firing.value("cq", "cq") == 0.0
    assert metrics.slo_alert_transitions_total.value(
        "cq", "cq", "cleared") == 1
    # re-fire is a fresh transition
    for i in range(30):
        eng.observe_admission("cq", 100.0, now=2000.0 + i)
    rep = eng.evaluate(now=2030.0)
    assert rep["alerts"], "the regression re-fires"
    assert metrics.slo_alert_transitions_total.value(
        "cq", "cq", "fired") == 2


def test_alert_requires_both_windows_burning():
    """A short bad blip inside an otherwise healthy hour must NOT page:
    the fast window burns but the slow window (diluted by the healthy
    bulk) stays under the threshold."""
    eng = SLOEngine(target=0.9, threshold_s=10.0, fast_window_s=300.0,
                    slow_window_s=3600.0, burn_threshold=3.0,
                    clock=lambda: 0.0)
    for i in range(1000):                      # healthy bulk, old
        eng.observe_admission("cq", 1.0, now=float(i))
    for i in range(5):                         # recent blip
        eng.observe_admission("cq", 100.0, now=3300.0 + i)
    rep = eng.evaluate(now=3400.0)
    sli = next(s for s in rep["slis"] if s["scope"] == "cq")
    assert sli["burnFast"] > 3.0, "the blip saturates the fast window"
    assert sli["burnSlow"] < 3.0, "the hour dilutes it"
    assert sli["alert"]["state"] == "clear"
    assert not rep["alerts"]


def test_per_priority_slis_are_tracked_separately():
    eng = SLOEngine(target=0.9, threshold_s=10.0, burn_threshold=2.0,
                    clock=lambda: 0.0)
    eng.observe_admission("cq-a", 100.0, priority=0, now=1.0)
    eng.observe_admission("cq-b", 1.0, priority=100, now=1.0)
    rep = eng.evaluate(now=2.0)
    by_key = {(s["scope"], s["key"]): s for s in rep["slis"]}
    assert by_key[("priority", "0")]["fast"]["bad"] == 1
    assert by_key[("priority", "100")]["fast"]["bad"] == 0
    assert by_key[("cq", "cq-a")]["fast"]["bad"] == 1
    assert by_key[("cq", "cq-b")]["fast"]["bad"] == 0


def test_starvation_watchdog_surfaces_oldest_pending_age():
    store, queues, sched = _mk_env(nominal=1000)
    _submit(store, "runs", cpu=900, t=0.0)
    _submit(store, "starved", cpu=900, t=5.0)  # never fits behind runs
    sched.run_until_quiet(now=10.0, tick=1.0)
    eng = SLOEngine(starvation_threshold_s=100.0, clock=lambda: 0.0)
    rep = eng.evaluate(now=500.0, queues=queues)
    starved = [s for s in rep["starvation"] if s["starved"]]
    assert starved and starved[0]["clusterQueue"] == "cq"
    assert starved[0]["workload"] == "default/starved"
    assert starved[0]["oldestAgeSeconds"] == pytest.approx(495.0)
    assert metrics.starvation_oldest_pending_seconds.value(
        "cq") == pytest.approx(495.0)
    # under the threshold: reported but not flagged
    rep = eng.evaluate(now=50.0, queues=queues)
    assert all(not s["starved"] for s in rep["starvation"])


# ---------------------------------------------------------------------------
# exemplars: histogram -> OpenMetrics exposition round trip
# ---------------------------------------------------------------------------


def test_exemplar_round_trip_through_exposition():
    store, queues, sched = _mk_env(nominal=1000)
    _submit(store, "w1", t=0.0)
    sched.schedule(now=100.0)
    ex = metrics.quota_reserved_wait_time_seconds.exemplars("cq")
    assert ex, "the admission recorded an exemplar"
    (labels, value, _ts) = next(iter(ex.values()))
    assert labels == {"cycle": "1", "workload": "default/w1"}
    assert value == pytest.approx(100.0)
    om = metrics.registry.render(openmetrics=True)
    m = re.search(
        r'kueue_quota_reserved_wait_time_seconds_bucket\{[^}]*\} \d+ '
        r'# \{cycle="(\d+)",workload="([^"]+)"\} ([0-9.]+)', om)
    assert m, "exposition carries the exemplar"
    assert m.group(1) == "1" and m.group(2) == "default/w1"
    assert float(m.group(3)) == pytest.approx(100.0)
    assert om.strip().endswith("# EOF")
    # the classic format stays exemplar-free (no grammar for them)
    classic = metrics.registry.render()
    assert " # {" not in classic and "# EOF" not in classic
    # the exemplar joins the ledger row and the decision chain
    cycle = int(m.group(1))
    assert obs.cycle_ledger.rows_for_cycle(cycle)
    assert obs.recorder.explain(m.group(2))


def test_exemplars_disabled_record_nothing():
    metrics.exemplars_enabled = False
    h = metrics.Histogram("t_exoff", "t", buckets=(1.0,))
    h.observe(value=0.5, exemplar={"cycle": "1"})
    assert h.exemplars() == {}


# ---------------------------------------------------------------------------
# cycle ledger: host rows, solver rows, recorder join
# ---------------------------------------------------------------------------


def test_host_cycle_ledger_row_matches_stats_and_joins_recorder():
    store, queues, sched = _mk_env(nominal=1000)
    _submit(store, "w1", cpu=800, t=0.0)
    _submit(store, "w2", cpu=800, t=1.0)  # no fit behind w1
    sched.schedule(now=10.0)   # cycle 1: w1 (the CQ head) admits
    sched.schedule(now=11.0)   # cycle 2: w2 heads, NoFit-skips
    rows = obs.cycle_ledger.rows_for_cycle(1)
    assert len(rows) == 1 and rows[0].kind == obs.HOST_CYCLE
    row = rows[0]
    assert row.heads == 1 and row.admitted == 1 and row.skipped == 0
    row2 = obs.cycle_ledger.rows_for_cycle(2)[0]
    assert row2.heads == 1 and row2.admitted == 0 and row2.skipped == 1
    assert sum(row2.skip_slugs.values()) == 1
    # the slug breakdown mirrors the recorder's per-reason counters
    slug = next(iter(row2.skip_slugs))
    assert metrics.decision_skips_total.value(slug) == 1
    # the row's phases are its spans' durations by name (obs/spans.py)
    assert set(row.phases) == {"requeue", "snapshot", "nominate",
                               "entries", "flush"}
    assert row.mono_ns > 0
    assert row.duration_s >= 0.0
    assert row.breaker == "closed"
    # the recorder's decision events carry the SAME cycle id
    cycles = {ev.cycle for ev in obs.recorder.events()}
    assert row.cycle in cycles and row2.cycle in cycles
    assert metrics.ledger_records_total.value(obs.HOST_CYCLE) == 2
    # empty cycles record no row (the serve loop's idle polls): w2
    # parked inadmissible leaves later cycles headless
    sched.schedule(now=12.0)
    sched.schedule(now=13.0)
    host_rows = [r for r in obs.cycle_ledger.rows()
                 if r.kind == obs.HOST_CYCLE]
    assert all(r.heads > 0 for r in host_rows)


def test_solver_drain_ledger_row_records_arm_and_frame():
    store = Store()
    store.upsert_resource_flavor(ResourceFlavor(name="f"))
    store.upsert_cluster_queue(ClusterQueue(
        name="cq0", resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name="f", resources=[
                ResourceQuota(name="cpu", nominal=8)])])]))
    store.upsert_local_queue(LocalQueue(name="lq0", cluster_queue="cq0"))
    for i in range(12):  # 8 fit, 4 park
        store.add_workload(Workload(
            name=f"w{i}", queue_name="lq0", uid=i + 1,
            creation_time=float(i),
            podsets=[PodSet(name="main", count=1,
                            requests={"cpu": 1})]))
    queues = QueueManager(store)
    from kueue_oss_tpu.solver.engine import SolverEngine

    engine = SolverEngine(store, queues)
    result = engine.drain(now=100.0)
    assert result.admitted == 8
    rows = [r for r in obs.cycle_ledger.rows()
            if r.kind == obs.SOLVER_DRAIN]
    assert len(rows) == 1
    row = rows[0]
    assert row.admitted == 8 and row.parked == 4
    assert row.solver_arm in ("single", "mesh")
    assert row.frame_kind == "sync" and row.frame_bytes > 0
    assert row.frame_reason == "first_sync"
    assert {"solve", "apply"} <= set(row.phases)
    # second drain with churn ships a delta frame
    sched = Scheduler(store, queues)
    admitted = [k for k, w in store.workloads.items()
                if w.is_quota_reserved]
    for key in admitted[:2]:
        sched.finish_workload(key, now=101.0)
    result2 = engine.drain(now=102.0)
    assert result2.admitted == 2
    rows = [r for r in obs.cycle_ledger.rows()
            if r.kind == obs.SOLVER_DRAIN]
    assert rows[-1].frame_kind == "delta"
    assert 0 < rows[-1].frame_bytes < rows[0].frame_bytes
    # recorder decisions for the drain share the row's cycle id
    drain_cycles = {ev.cycle for ev in obs.recorder.events()
                    if ev.path == obs.SOLVER}
    assert rows[-1].cycle in drain_cycles


def test_ledger_ring_bound_and_jsonl_roundtrip(tmp_path):
    led = obs.CycleLedger(max_cycles=4)
    for c in range(10):
        led.record(c, obs.HOST_CYCLE, admitted=c)
    assert len(led.rows()) == 4
    assert led.rows()[-1].cycle == 9
    path = str(tmp_path / "ledger.jsonl")
    assert led.dump_jsonl(path) == 4
    back = obs.load_ledger_jsonl(path)
    assert [r.cycle for r in back] == [6, 7, 8, 9]
    assert back[-1].admitted == 9
    # torn tail tolerated
    with open(path, "a") as f:
        f.write('{"cycle": 99, "kind": "ho')
    back = obs.load_ledger_jsonl(path)
    assert len(back) == 4
    assert obs.load_ledger_jsonl.last_skipped == 1
    # restore continues the seq counter monotonically
    led2 = obs.CycleLedger()
    assert led2.restore(back) == 4
    row = led2.record(50, obs.HOST_CYCLE)
    assert row.seq > max(r.seq for r in back)


# ---------------------------------------------------------------------------
# dashboard surfaces
# ---------------------------------------------------------------------------


def test_dashboard_slo_health_and_ledger_embedded_decisions():
    import urllib.request

    from kueue_oss_tpu.viz import Dashboard, DashboardServer

    store, queues, sched = _mk_env(nominal=1000)
    _submit(store, "running", t=0.0)
    _submit(store, "waiting", cpu=900, t=1.0)
    sched.run_until_quiet(now=50.0, tick=1.0)
    srv = DashboardServer(Dashboard(store, queues))
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        slo = json.loads(urllib.request.urlopen(
            f"{base}/api/slo", timeout=5).read())
        assert {"objective", "slis", "alerts",
                "starvation"} <= set(slo)
        keys = {(s["scope"], s["key"]) for s in slo["slis"]}
        assert ("cq", "cq") in keys and ("priority", "0") in keys
        assert slo["starvation"], "the blocked workload is watched"

        health = json.loads(urllib.request.urlopen(
            f"{base}/api/health", timeout=5).read())
        assert health["status"] in ("ok", "degraded", "critical")
        assert health["breakerState"] == "closed"
        assert health["ledger"]["rows"] >= 1

        dec = json.loads(urllib.request.urlopen(
            f"{base}/api/decisions?cycles=5", timeout=5).read())
        with_rows = [c for c in dec["cycles"] if c.get("ledger")]
        assert with_rows, "decision groups embed their ledger rows"
        group = with_rows[0]
        assert all(r["cycle"] == group["cycle"]
                   for r in group["ledger"])

        om = urllib.request.urlopen(
            f"{base}/metrics?format=openmetrics", timeout=5
        ).read().decode()
        assert om.strip().endswith("# EOF")
        req = urllib.request.Request(
            f"{base}/metrics",
            headers={"Accept": "application/openmetrics-text"})
        om2 = urllib.request.urlopen(req, timeout=5).read().decode()
        assert om2.strip().endswith("# EOF")
        classic = urllib.request.urlopen(
            f"{base}/metrics", timeout=5).read().decode()
        assert "# EOF" not in classic
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# acceptance: contention -> alert -> exemplar -> ledger row -> explain
# ---------------------------------------------------------------------------


def _contention_run(store, queues, sched, now=5000.0):
    """Seeded contention: every admission has waited ~now seconds (far
    past the objective threshold), and one oversized workload stays
    pending for the starvation watchdog."""
    for i in range(4):
        _submit(store, f"slow{i}", cpu=200, t=float(i))
    _submit(store, "never", cpu=5000, t=0.0)  # NoFit: pending forever
    sched.run_until_quiet(now=now, tick=1.0)


def test_e2e_contention_alert_exemplar_links_ledger_and_explain():
    obs.slo_engine.threshold_s = 60.0
    obs.slo_engine.burn_threshold = 2.0
    store, queues, sched = _mk_env(nominal=1000)
    _contention_run(store, queues, sched)
    report = obs.slo_engine.evaluate(now=5010.0, queues=queues)
    firing = [a for a in report["alerts"] if a["scope"] == "cq"]
    assert firing, "the contention run fires a burn-rate alert"
    alert = firing[0]
    ex = alert["exemplar"]
    assert ex and ex["workload"].startswith("default/slow")
    assert ex["waitSeconds"] > 60.0
    # exemplar -> ledger row for the same cycle
    rows = obs.cycle_ledger.rows_for_cycle(ex["cycle"])
    assert rows and any(r.admitted for r in rows)
    # exemplar -> non-empty explain chain for the same cycle
    chain = obs.recorder.explain(ex["workload"])
    assert chain and any(ev.cycle == ex["cycle"] for ev in chain)
    assert chain[0].kind == obs.ASSIGNED
    # the same exemplar is visible in the OpenMetrics exposition
    om = metrics.registry.render(openmetrics=True)
    assert f'workload="{ex["workload"]}"' in om
    # starvation watchdog sees the never-fitting workload
    starved = [s for s in report["starvation"]
               if s["workload"] == "default/never"]
    assert starved and starved[0]["oldestAgeSeconds"] > 4000


def test_alert_survives_in_process_checkpoint_recover(tmp_path):
    """Journal + ledger ride the checkpoint; after recovery into a
    fresh process state the SLO windows rebuild from the restored
    journal and the alert -> ledger -> explain links still hold."""
    from kueue_oss_tpu.persist import PersistenceManager

    obs.slo_engine.threshold_s = 60.0
    obs.slo_engine.burn_threshold = 2.0
    d = str(tmp_path)
    mgr = PersistenceManager(d, fsync="off",
                             checkpoint_interval_seconds=0.0)
    store = Store()
    mgr.attach(store)
    store.upsert_resource_flavor(ResourceFlavor(name="default"))
    store.upsert_cluster_queue(ClusterQueue(
        name="cq", resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name="default", resources=[
                ResourceQuota(name="cpu", nominal=1000)])])]))
    store.upsert_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    queues = QueueManager(store)
    sched = Scheduler(store, queues)
    _contention_run(store, queues, sched)
    mgr.checkpoint()
    assert os.path.exists(os.path.join(
        d, f"journal-{mgr.segment:08d}.jsonl"))
    assert os.path.exists(os.path.join(
        d, f"ledger-{mgr.segment:08d}.jsonl"))
    mgr.close()

    # "restart": the in-memory rings and SLO windows are gone
    obs.recorder.clear()
    obs.cycle_ledger.clear()
    obs.slo_engine.reset()
    mgr2 = PersistenceManager(d, fsync="off")
    rr = mgr2.recover()
    mgr2.close()
    assert rr.journal_events_restored > 0
    assert rr.ledger_rows_restored > 0
    # explain + ledger survive the restart verbatim. The replayed
    # windows anchor on the journal's recorded wall timestamps, so the
    # evaluation instant is the journal's final ts, not the virtual
    # scheduler clock.
    last_ts = max(ev.ts for ev in obs.recorder.events())
    eng = SLOEngine(target=0.99, threshold_s=60.0, burn_threshold=2.0,
                    clock=lambda: last_ts)
    assert eng.replay_journal(obs.recorder.events()) >= 4
    report = eng.evaluate(now=last_ts)
    firing = [a for a in report["alerts"] if a["scope"] == "cq"]
    assert firing, "the alert re-derives from the restored journal"
    ex = firing[0]["exemplar"]
    assert obs.cycle_ledger.rows_for_cycle(ex["cycle"])
    chain = obs.recorder.explain(ex["workload"])
    assert chain and any(ev.cycle == ex["cycle"] for ev in chain)
    # post-restore events continue the journal order monotonically
    ev = obs.recorder.record(obs.EVICTED, "default/slow0", cycle=99)
    assert ev.seq > max(e.seq for e in obs.recorder.events()[:-1])


# ---------------------------------------------------------------------------
# acceptance: SIGKILL + recover in real processes
# ---------------------------------------------------------------------------

_CRASH_DRIVER = """
import json, os, signal, sys

sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from kueue_oss_tpu import obs
from kueue_oss_tpu.api.types import (ClusterQueue, FlavorQuotas,
    LocalQueue, PodSet, ResourceFlavor, ResourceGroup, ResourceQuota,
    Workload)
from kueue_oss_tpu.core.queue_manager import QueueManager
from kueue_oss_tpu.core.store import Store
from kueue_oss_tpu.obs.health import SLOEngine
from kueue_oss_tpu.persist import PersistenceManager
from kueue_oss_tpu.scheduler.scheduler import Scheduler

phase, dirpath = sys.argv[1], sys.argv[2]
mgr = PersistenceManager(dirpath, fsync="always",
                         checkpoint_interval_seconds=0.0)
if phase == "run":
    store = Store()
    mgr.attach(store)
    store.upsert_resource_flavor(ResourceFlavor(name="default"))
    store.upsert_cluster_queue(ClusterQueue(
        name="cq", resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name="default", resources=[
                ResourceQuota(name="cpu", nominal=1000)])])]))
    store.upsert_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    queues = QueueManager(store)
    sched = Scheduler(store, queues)
    for i in range(4):
        store.add_workload(Workload(
            name=f"slow{{i}}", queue_name="lq", creation_time=float(i),
            podsets=[PodSet(name="main", count=1,
                            requests={{"cpu": 200}})]))
    sched.run_until_quiet(now=5000.0, tick=1.0)
    mgr.checkpoint()   # journal + ledger ride the checkpoint
    # post-checkpoint WAL tail, then die mid-flight: the recover phase
    # must still see the checkpoint-time rings
    sched.finish_workload("default/slow0", now=5001.0)
    mgr.flush()
    os.kill(os.getpid(), signal.SIGKILL)

rr = mgr.recover()
mgr.close()
last_ts = max(ev.ts for ev in obs.recorder.events())
eng = SLOEngine(target=0.99, threshold_s=60.0, burn_threshold=2.0,
                clock=lambda: last_ts)
replayed = eng.replay_journal(obs.recorder.events())
report = eng.evaluate(now=last_ts)
firing = [a for a in report["alerts"] if a["scope"] == "cq"]
ex = firing[0]["exemplar"] if firing else None
chain = obs.recorder.explain(ex["workload"]) if ex else []
print(json.dumps({{
    "journal_events_restored": rr.journal_events_restored,
    "ledger_rows_restored": rr.ledger_rows_restored,
    "replayed_admissions": replayed,
    "alert_firing": bool(firing),
    "exemplar": ex,
    "ledger_rows_for_cycle": len(
        obs.cycle_ledger.rows_for_cycle(ex["cycle"])) if ex else 0,
    "explain_chain_len": len(chain),
    "explain_cycle_match": bool(
        ex and any(e.cycle == ex["cycle"] for e in chain)),
}}))
"""


def test_sigkill_then_recover_restores_journal_ledger_and_alert(
        tmp_path):
    driver = str(tmp_path / "driver.py")
    with open(driver, "w") as f:
        f.write(_CRASH_DRIVER.format(repo=REPO_ROOT))
    d = str(tmp_path / "durable")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    run = subprocess.run([sys.executable, driver, "run", d],
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert run.returncode == -9, (
        f"run phase must die by SIGKILL, got {run.returncode}: "
        f"{run.stderr[-2000:]}")
    rec = subprocess.run([sys.executable, driver, "recover", d],
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert rec.returncode == 0, rec.stderr[-2000:]
    status = json.loads(rec.stdout.strip().splitlines()[-1])
    assert status["journal_events_restored"] >= 4
    assert status["ledger_rows_restored"] >= 1
    assert status["replayed_admissions"] >= 4
    assert status["alert_firing"], (
        "the burn-rate alert re-derives after SIGKILL+recover")
    assert status["ledger_rows_for_cycle"] >= 1
    assert status["explain_chain_len"] >= 1
    assert status["explain_cycle_match"], (
        "exemplar links the restored explain chain at the same cycle")


# ---------------------------------------------------------------------------
# offline CLI: tools/slo.py
# ---------------------------------------------------------------------------


def test_slo_cli_summary_join_and_recompute(tmp_path):
    import io

    from tools.slo import main as slo_main

    obs.slo_engine.threshold_s = 60.0
    store, queues, sched = _mk_env(nominal=1000)
    _contention_run(store, queues, sched)
    ledger = str(tmp_path / "ledger.jsonl")
    journal = str(tmp_path / "decisions.jsonl")
    assert obs.cycle_ledger.dump_jsonl(ledger) > 0
    assert obs.recorder.dump_jsonl(journal) > 0

    buf = io.StringIO()
    assert slo_main(["--ledger", ledger], out=buf) == 0
    text = buf.getvalue()
    assert "host cycle(s)" in text and "skips by reason" in text

    # the ledger<->journal cycle join
    row = obs.cycle_ledger.rows()[0]
    buf = io.StringIO()
    assert slo_main(["--ledger", ledger, "--journal", journal,
                     "--cycle", str(row.cycle)], out=buf) == 0
    text = buf.getvalue()
    assert f"cycle {row.cycle}:" in text
    assert "decision event(s)" in text

    # offline SLO recompute from the journal's recorded waits
    buf = io.StringIO()
    assert slo_main(["--journal", journal, "--slo",
                     "--threshold", "60", "--target", "0.99"],
                    out=buf) == 0
    text = buf.getvalue()
    assert "admission(s) replayed" in text
    assert "[firing]" in text


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------


def test_obs_configure_applies_and_resets():
    from kueue_oss_tpu.config.configuration import load

    cfg = load({"observability": {
        "ledgerMaxCycles": 16, "exemplars": False,
        "slo": {"queueWaitTarget": 0.9, "queueWaitThreshold": 42.0,
                "fastWindow": 60.0, "slowWindow": 600.0,
                "burnRateThreshold": 3.5, "starvationThreshold": 99.0},
    }})
    try:
        obs.configure(cfg.observability)
        assert obs.cycle_ledger.max_cycles == 16
        assert metrics.exemplars_enabled is False
        assert obs.slo_engine.threshold_s == 42.0
        assert obs.slo_engine.burn_threshold == 3.5
        assert obs.slo_engine.starvation_threshold_s == 99.0
        assert obs.slo_engine.fast_window_s == 60.0
    finally:
        obs.configure(load({}).observability)
    assert metrics.exemplars_enabled is True
    assert obs.slo_engine.threshold_s == 300.0
