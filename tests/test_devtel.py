"""Device-telemetry tests (obs/devtel.py, docs/OBSERVABILITY.md
"Device telemetry & fabric tracing").

Contract under test, by layer:

1. compile accounting — the per-(kernel, arm, shape-bucket) detector
   flags exactly the fresh calls (fresh vs warm vs re-armed by
   ``forget``), emits metric/span/ledger artifacts, and replaces the
   engine router's one-shot compile-tainted warm set;
2. transfer ledger + HBM watermarks — drains account donated/full
   upload bytes into the unified ``solver_transfer_bytes_total``
   family and gauge the resident-problem watermark, in-process AND
   through the sidecar wire (``tx`` direction, tenant-labelled);
3. fabric tracing — merged Chrome traces put each remote source
   (sidecar per tenant, farm grant-wait) on its own stable synthetic
   track with thread_name metadata, distinct from host thread tracks,
   and the farm stamps a grant-wait histogram + ledger field;
4. deep capture — virtual-clock trigger/budget/cooldown/single-slot
   lifecycle, alert-sink and phase-regression arming, and the
   ``GET/POST /api/telemetry`` + ``GET /api/trace`` surfaces;
5. config — observability.devtel load/validate/apply round trip.
"""

import json
import os
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
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_oss_tpu.config import load as load_config
from kueue_oss_tpu.config import validate as validate_config
from kueue_oss_tpu.debugger.profiling import Tracer, attach_to_scheduler
from kueue_oss_tpu.federation import attach_farm, build_member
from kueue_oss_tpu.obs import devtel
from kueue_oss_tpu.obs.devtel import (
    CompileDetector,
    DeepCapture,
    shape_bucket,
)
from kueue_oss_tpu.obs.health import phase_regression, slo
from kueue_oss_tpu.obs.ledger import SOLVER_DRAIN
from kueue_oss_tpu.solver.service import SolverServer

pytestmark = pytest.mark.devtel


@pytest.fixture(autouse=True)
def _reset():
    metrics.reset_all()
    obs.recorder.clear()
    obs.cycle_ledger.clear()
    devtel.reset()
    phase_regression.reset()
    yield
    metrics.reset_all()
    obs.recorder.clear()
    obs.cycle_ledger.clear()
    devtel.reset()
    phase_regression.reset()


# ---------------------------------------------------------------------------
# shared builders (the federation-test cluster shape)
# ---------------------------------------------------------------------------


def _seed_cluster(store, n_cqs=4, quota=8):
    store.upsert_resource_flavor(ResourceFlavor(name="f"))
    for i in range(n_cqs):
        store.upsert_cluster_queue(ClusterQueue(
            name=f"cq{i}", preemption=PreemptionPolicy(),
            resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[FlavorQuotas(name="f", resources=[
                    ResourceQuota(name="cpu", nominal=quota)])])]))
        store.upsert_local_queue(LocalQueue(
            name=f"lq{i}", cluster_queue=f"cq{i}"))


def _wl(i, cpu=1):
    return Workload(
        name=f"w{i}", queue_name=f"lq{i % 4}", uid=i + 1,
        creation_time=float(i),
        podsets=[PodSet(name="main", count=1, requests={"cpu": cpu})])


def _churn(member, cycles, uid0, churn=2):
    uid = uid0
    for cyc in range(1, cycles + 1):
        admitted = sorted(
            k for k, w in member.store.workloads.items()
            if w.is_quota_reserved and not w.is_finished)
        for k in admitted[:churn]:
            member.scheduler.finish_workload(k, now=float(cyc))
        for _ in range(churn):
            member.store.add_workload(_wl(uid))
            uid += 1
        member.drain(now=float(cyc))
    return uid


def _member(name, socket_path=None, **kw):
    m = build_member(name, socket_path=socket_path, pad_to=64,
                     seed=lambda s: _seed_cluster(s), **kw)
    for i in range(24):
        m.store.add_workload(_wl(i))
    return m


def _enable(**flags):
    c = devtel.collector
    c.enabled = True
    for k, v in flags.items():
        setattr(c, k, v)
    return c


# ---------------------------------------------------------------------------
# 1. compile accounting: fresh vs warm vs re-armed
# ---------------------------------------------------------------------------


def test_shape_bucket_pow2_ceiling():
    assert shape_bucket(0) == "0"
    assert shape_bucket(1) == "1"
    assert shape_bucket(2) == "2"
    assert shape_bucket(3) == "4"
    assert shape_bucket(64) == "64"
    assert shape_bucket(65) == "128"


def test_compile_detector_fresh_vs_warm_and_forget():
    det = CompileDetector()
    assert det.observe_solve("full", "single", 100, 0.5) is True
    # warm: same bucket (100 and 120 both pad into 128)
    assert det.observe_solve("full", "single", 120, 0.01) is False
    # a NEW padded width is a fresh compile even on a warm arm
    assert det.observe_solve("full", "single", 200, 0.4) is True
    # a different arm compiles its own program
    assert det.observe_solve("full", "mesh", 100, 0.6) is True
    assert det.compiles == 3
    assert metrics.solver_compiles_total.collect() == {
        ("full", "single", "128"): 1.0,
        ("full", "single", "256"): 1.0,
        ("full", "mesh", "128"): 1.0}
    assert metrics.solver_compile_seconds.count() == 3
    # the ledger-row event feed pops clean
    events = det.drain_events()
    assert [e["bucket"] for e in events] == ["128", "256", "128"]
    assert det.drain_events() == []
    # arm reset (mesh demotion) re-arms exactly that arm's keys
    det.forget("full", "mesh")
    assert not det.seen("full", "mesh", 100)
    assert det.seen("full", "single", 100)
    assert det.observe_solve("full", "mesh", 100, 0.6) is True


def test_compile_detector_emits_tracer_span():
    tracer = Tracer(clock=lambda: 10.0)
    det = CompileDetector(tracer=tracer)
    det.observe_solve("lean", "single", 30, 0.25)
    spans = tracer.spans()
    assert len(spans) == 1
    name, tid, ts_us, dur_us, args = spans[0]
    assert name == "xla_compile"
    assert dur_us == 250_000 and ts_us == 10_000_000 - 250_000
    assert args["kernel"] == "lean" and args["bucket"] == "32"
    # the span rides devtel's own synthetic track, not the caller's
    assert tid == tracer.track("devtel")


def test_engine_router_uses_detector_verdict():
    """With devtel on, the router's EMA feed follows the detector:
    fresh (compile-bearing) walls stay out, warm walls feed — and the
    drain's ledger row carries the compile events."""
    _enable()
    m = _member("local")
    m.drain(now=0.0)
    _churn(m, 3, 100)
    assert devtel.collector.compiles.compiles >= 1
    assert metrics.solver_compiles_total.total() >= 1
    rows = [r for r in obs.cycle_ledger.rows() if r.kind == SOLVER_DRAIN]
    assert rows, "solver drains must have recorded ledger rows"
    first = rows[0]
    assert first.device.get("compiles", 0) >= 1
    assert first.device["compile_events"][0]["kernel"]
    # warm drains at the same padded width carry no compile events
    assert any("compiles" not in r.device for r in rows[1:]), \
        "every drain claims a compile: the warm path never engaged"
    # the EMA was fed by warm samples (the legacy path would have
    # discarded the first per-arm sample unconditionally)
    assert m.engine._arm_ema, "warm walls must feed the router EMA"


def test_engine_legacy_warm_set_when_devtel_off():
    """devtel disabled: the router falls back to the one-shot
    compile-tainted warm set (no devtel metrics, no verdicts)."""
    m = _member("local")
    m.drain(now=0.0)
    assert metrics.solver_compiles_total.total() == 0
    assert devtel.collector.compiles.compiles == 0
    assert m.engine._arm_warm, "legacy warm set must engage when off"


# ---------------------------------------------------------------------------
# 2. transfer ledger + HBM watermarks: in-process and sidecar
# ---------------------------------------------------------------------------


def test_transfer_and_hbm_accounting_in_process():
    _enable()
    m = _member("local")
    m.drain(now=0.0)         # first drain: full upload
    _churn(m, 3, 100)        # then donated delta scatters
    c = devtel.collector
    assert c.transfer_bytes.get("h2d", 0) > 0, \
        "uploads/scatters must land in the unified transfer family"
    fam = metrics.solver_transfer_bytes_total.collect()
    assert sum(v for k, v in fam.items() if k[0] == "h2d") == \
        c.transfer_bytes["h2d"]
    # the portable watermark gauged something while problems were
    # resident, and the ledger rows carry the same field
    rows = [r for r in obs.cycle_ledger.rows()
            if r.kind == SOLVER_DRAIN and r.device]
    assert any(r.device.get("hbm_resident_bytes", 0) > 0 for r in rows)
    assert c.hbm_resident_bytes >= 0  # post-churn watermark snapshot


def test_transfer_accounting_and_grant_wait_through_sidecar():
    _enable()
    path = os.path.join(tempfile.mkdtemp(), "farm.sock")
    srv = SolverServer(path)
    farm = attach_farm(srv, weights={"cp-a": 2.0, "cp-b": 1.0})
    srv.serve_in_background()
    try:
        for name, uid0 in (("cp-a", 0), ("cp-b", 1000)):
            m = _member(name, socket_path=path)
            m.drain(now=0.0)
            _churn(m, 2, uid0 + 100)
            # the client's grant-wait echo landed on the ledger rows
            rows = [r for r in obs.cycle_ledger.rows()
                    if r.kind == SOLVER_DRAIN
                    and r.session.get("tenant") == name]
            assert rows, f"no solver rows for tenant {name}"
            assert all(r.grant_wait_ms >= 0.0 for r in rows)
            assert m.engine.remote.last_grant_wait_ms >= 0.0
    finally:
        srv.shutdown()
        srv.server_close()
    # request frames were accounted on the tx direction, per tenant
    fam = metrics.solver_transfer_bytes_total.collect()
    tx_tenants = {k[2] for k, v in fam.items() if k[0] == "tx" and v > 0}
    assert {"cp-a", "cp-b"} <= tx_tenants, fam
    # every farm grant stamped the per-tenant wait histogram
    assert metrics.solver_farm_grant_wait_seconds.count("cp-a") >= 3
    assert metrics.solver_farm_grant_wait_seconds.count("cp-b") >= 3
    assert farm.served["cp-a"] >= 3 and farm.served["cp-b"] >= 3


# ---------------------------------------------------------------------------
# 3. fabric tracing: one timeline, distinct tracks per source/tenant
# ---------------------------------------------------------------------------


def test_tracer_synthetic_tracks_are_stable_and_distinct():
    tracer = Tracer()
    a = tracer.track("sidecar:cp-a", tenant="cp-a")
    b = tracer.track("sidecar:cp-b", tenant="cp-b")
    assert a != b
    assert tracer.track("sidecar:cp-a") == a, "track ids must be stable"
    tracer.add_span("sidecar_solve", 0, 10, source="sidecar:cp-a")
    tracer.add_span("sidecar_solve", 5, 10, source="sidecar:cp-b")
    trace = json.loads(tracer.chrome_trace())
    names = {e["args"]["name"]: e["tid"] for e in trace["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert names["sidecar:cp-a"] == a and names["sidecar:cp-b"] == b
    meta = [e for e in trace["traceEvents"] if e.get("ph") == "M"
            and e["args"]["name"] == "sidecar:cp-a"]
    assert meta[0]["args"]["tenant"] == "cp-a"
    solves = {e["tid"] for e in trace["traceEvents"]
              if e.get("ph") == "X" and e["name"] == "sidecar_solve"}
    assert solves == {a, b}, "spans must land on their source's track"
    # the registry survives a span-ring clear (steady-state export)
    tracer.clear()
    assert tracer.track("sidecar:cp-a") == a


def test_one_timeline_host_farm_and_sidecar_spans(tmp_path):
    """ISSUE acceptance: a live federation twin's merged Chrome trace
    holds host-cycle, farm grant-wait, and sidecar solve spans with
    distinct track ids per source/tenant."""
    _enable()
    path = os.path.join(tempfile.mkdtemp(), "farm.sock")
    srv = SolverServer(path)
    farm = attach_farm(srv, weights={"cp-a": 1.0, "cp-b": 1.0})
    srv.serve_in_background()
    tracers = {}
    try:
        for name, uid0 in (("cp-a", 0), ("cp-b", 1000)):
            m = _member(name, socket_path=path)
            tracers[name] = Tracer()
            attach_to_scheduler(m.scheduler, tracers[name])
            m.drain(now=0.0)
            _churn(m, 2, uid0 + 100)
    finally:
        srv.shutdown()
        srv.server_close()
    for name, tracer in tracers.items():
        trace = json.loads(tracer.chrome_trace())
        events = trace["traceEvents"]
        xs = [e for e in events if e.get("ph") == "X"]
        drains = [e for e in xs if e["name"] == "solver_drain"]
        solves = [e for e in xs if e["name"] == "sidecar_solve"]
        waits = [e for e in xs if e["name"] == "farm_grant_wait"]
        assert drains and solves and waits, \
            f"{name}: {sorted({e['name'] for e in xs})}"
        # the remote spans ride synthetic tracks distinct from the
        # host drain's thread track, labelled by source
        host_tids = {e["tid"] for e in drains}
        assert {e["tid"] for e in solves}.isdisjoint(host_tids)
        assert {e["tid"] for e in waits}.isdisjoint(host_tids)
        assert {e["tid"] for e in solves}.isdisjoint(
            {e["tid"] for e in waits})
        labels = {e["tid"]: e["args"]["name"] for e in events
                  if e.get("ph") == "M" and e["name"] == "thread_name"}
        assert any(v == f"sidecar:{name}" for v in labels.values())
        assert any(v == f"farm:{name}" for v in labels.values())
        # grant-wait precedes its solve on the timeline (end-skew
        # alignment survives the merge)
        w, s = waits[-1], solves[-1]
        assert w["ts"] <= s["ts"], (w, s)
        # spans join the ledger/journal on the cycle id
        cycles = {r.cycle for r in obs.cycle_ledger.rows()}
        assert any(e["args"].get("cycle") in cycles for e in solves)


# ---------------------------------------------------------------------------
# 4. deep capture: virtual-clock lifecycle + triggers
# ---------------------------------------------------------------------------


def test_capture_trigger_budget_cooldown_single_slot(tmp_path):
    now = [0.0]
    cap = DeepCapture(dir=str(tmp_path), max_seconds=5.0,
                      cooldown_s=300.0, clock=lambda: now[0])
    assert cap.trigger("manual", {"who": "test"}) is True
    art = os.path.join(str(tmp_path), "capture-001-manual")
    marker = json.load(open(os.path.join(art, "capture.json")))
    assert marker["reason"] == "manual" and "endedAt" not in marker
    # single slot: a second trigger while one is in flight is refused
    assert cap.trigger("slo_burn") is False
    assert metrics.solver_deep_captures_total.collect()[
        ("slo_burn", "suppressed_busy")] == 1
    # budget: poll is a no-op until max_seconds elapses
    now[0] = 4.9
    assert cap.poll() is False
    now[0] = 5.1
    assert cap.poll() is True and cap.active() is None
    marker = json.load(open(os.path.join(art, "capture.json")))
    assert marker["endedAt"] == 5.1
    assert marker["durationSeconds"] == pytest.approx(5.1)
    # cooldown runs from capture START: still cooling at t=200
    now[0] = 200.0
    assert cap.trigger("manual") is False
    assert metrics.solver_deep_captures_total.collect()[
        ("manual", "suppressed_cooldown")] == 1
    assert cap.status()["cooldownRemainingSeconds"] == pytest.approx(100.0)
    # past the window a new capture starts, in its own directory
    now[0] = 301.0
    assert cap.trigger("phase_regression") is True
    assert os.path.isdir(os.path.join(
        str(tmp_path), "capture-002-phase_regression"))
    # stop() force-finishes; disarm refuses outright
    assert cap.stop() is True
    cap.armed = False
    now[0] = 1000.0
    assert cap.trigger("manual") is False
    assert metrics.solver_deep_captures_total.collect()[
        ("manual", "disarmed")] == 1
    assert len(cap.history) == 2


def test_slo_burn_sink_arms_capture(tmp_path):
    now = [0.0]
    c = _enable(capture_enabled=True)
    c.capture.dir = str(tmp_path)
    c.capture.clock = lambda: now[0]
    c.attach_alerts()
    try:
        assert c._slo_sink in slo.sinks
        c.attach_alerts()  # idempotent
        assert slo.sinks.count(c._slo_sink) == 1
        # a cleared transition must not trigger
        c._slo_sink("cleared", {"scope": "cq", "key": "cq0"})
        assert c.capture.active() is None
        c._slo_sink("fired", {"scope": "cq", "key": "cq0",
                              "exemplar": {"cycle": 7}})
        rec = c.capture.active()
        assert rec and rec["reason"] == "slo_burn"
        assert rec["detail"]["key"] == "cq0"
    finally:
        c.detach_alerts()
    assert c._slo_sink not in slo.sinks


def test_phase_regression_trips_capture_on_drain(tmp_path):
    now = [0.0]
    c = _enable(capture_enabled=True)
    c.capture.dir = str(tmp_path)
    c.capture.clock = lambda: now[0]
    # baseline 30 quiet samples, then a sustained 10x spike
    for _ in range(30):
        phase_regression.feed("solver", {"solve": 0.01})
    for _ in range(10):
        phase_regression.feed("solver", {"solve": 0.1})
    assert phase_regression.regressing(), "detector must be tripped"
    c.on_drain()
    rec = c.capture.active()
    assert rec and rec["reason"] == "phase_regression"
    assert rec["detail"]["phases"][0]["phase"] == "solve"
    # the same drain hook finishes the capture once the budget elapses
    now[0] = c.capture.max_seconds + 1.0
    c.on_drain()
    assert c.capture.active() is None


# ---------------------------------------------------------------------------
# 5. config load / validate / apply
# ---------------------------------------------------------------------------


def test_devtel_config_load_validate_apply(tmp_path):
    cfg = load_config({"observability": {"devtel": {
        "enabled": True, "captureEnabled": True,
        "captureMaxSeconds": 2.5, "captureCooldownSeconds": 60,
        "hbmWatermarks": False, "captureDir": str(tmp_path)}}})
    dtl = cfg.observability.devtel
    assert dtl.enabled and dtl.capture_enabled
    assert dtl.capture_max_seconds == 2.5
    assert dtl.capture_cooldown_seconds == 60.0
    assert dtl.hbm_watermarks is False and dtl.transfer_ledger is True
    assert validate_config(cfg) == []
    bad = load_config({"observability": {"devtel": {
        "captureMaxSeconds": 0, "captureCooldownSeconds": -1}}})
    errs = validate_config(bad)
    assert any("captureMaxSeconds" in e for e in errs)
    assert any("captureCooldownSeconds" in e for e in errs)
    # obs.configure applies onto the process-wide collector (and the
    # capture_dir fallback only fills a blank captureDir)
    obs.configure(cfg.observability, capture_dir="/unused-fallback")
    c = devtel.collector
    try:
        assert c.enabled and c.capture_enabled and not c.hbm_enabled
        assert c.capture.max_seconds == 2.5
        assert c.capture.dir == str(tmp_path)
        assert c._sink_registered, "capture on => alert sink registered"
    finally:
        devtel.reset()
    assert not c._sink_registered


# ---------------------------------------------------------------------------
# 6. dashboard + offline CLI surfaces
# ---------------------------------------------------------------------------


def test_dashboard_trace_and_telemetry_endpoints():
    from kueue_oss_tpu.core.queue_manager import QueueManager
    from kueue_oss_tpu.core.store import Store
    from kueue_oss_tpu.viz import Dashboard, DashboardServer

    store = Store()
    _seed_cluster(store)
    dash = Dashboard(store, QueueManager(store))
    tracer = Tracer()
    tracer.add_span("solver_drain", 0, 100, cycle=1)
    tracer.add_span("solver_drain", 200, 100, cycle=2)
    tracer.add_span("sidecar_solve", 210, 50, source="sidecar:cp-a",
                    cycle=2)
    dash.tracer = tracer
    srv = DashboardServer(dash)
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        trace = json.loads(urllib.request.urlopen(
            f"{base}/api/trace", timeout=5).read())
        xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
        assert len(xs) == 3
        # ?cycles=1 windows to the newest cycle only
        trace = json.loads(urllib.request.urlopen(
            f"{base}/api/trace?cycles=1", timeout=5).read())
        xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
        assert {e["args"]["cycle"] for e in xs} == {2}
        assert any(e["name"] == "sidecar_solve" for e in xs)

        tele = json.loads(urllib.request.urlopen(
            f"{base}/api/telemetry", timeout=5).read())
        assert tele["enabled"] is False
        assert tele["capture"]["armed"] is True

        def post(body):
            req = urllib.request.Request(
                f"{base}/api/telemetry", method="POST",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            try:
                resp = urllib.request.urlopen(req, timeout=5)
                return resp.status, json.loads(resp.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        code, out = post({"action": "trigger", "reason": "operator"})
        assert code == 200 and out["ok"]
        assert out["status"]["capture"]["active"]["reason"] == "manual"
        code, out = post({"action": "trigger"})
        assert code == 409, "single slot: second trigger is refused"
        code, out = post({"action": "stop"})
        assert code == 200 and out["status"]["capture"]["active"] is None
        code, out = post({"action": "disarm"})
        assert code == 200 and out["status"]["capture"]["armed"] is False
        code, out = post({"action": "self-destruct"})
        assert code == 409 and "action" in out["error"]
    finally:
        srv.stop()


def test_tools_trace_cli_joins_artifacts(tmp_path, capsys):
    import importlib

    trace_cli = importlib.import_module("tools.trace")
    _enable()
    m = _member("local")
    tracer = Tracer()
    attach_to_scheduler(m.scheduler, tracer)
    m.drain(now=0.0)
    _churn(m, 2, 100)
    trace_path = str(tmp_path / "trace.json")
    with open(trace_path, "w") as fh:
        fh.write(tracer.chrome_trace())
    ledger_path = str(tmp_path / "ledger.jsonl")
    obs.cycle_ledger.dump_jsonl(ledger_path)
    journal_path = str(tmp_path / "decisions.jsonl")
    obs.recorder.dump_jsonl(journal_path)
    rc = trace_cli.main(["--trace", trace_path, "--ledger", ledger_path,
                         "--journal", journal_path, "--cycles", "3"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "cycle " in out and "ledger" in out and "span" in out
    # single-cycle mode reports exactly that cycle's join
    cyc = obs.cycle_ledger.rows()[-1].cycle
    rc = trace_cli.main(["--ledger", ledger_path, "--cycle", str(cyc)])
    out = capsys.readouterr().out
    assert rc == 0 and f"cycle {cyc}:" in out
    # no inputs at all yield a nonzero exit
    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").close()
    assert trace_cli.main(["--journal", empty]) == 1
