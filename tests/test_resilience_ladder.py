"""Unified degradation ladder (kueue_oss_tpu/resilience/) tests.

Covers the tentpole contract of docs/ROBUSTNESS.md "Degradation
ladder": condition-severity level math, unified cooldown hysteresis
with single-probe gating, every subsystem's fault handlers reporting
through the process-wide controller (solver breaker, mesh/device
arms, WAL durability rungs, streaming fences, farm backpressure), the
runtime farm re-weighting satellite, and the /api surfaces.
"""

import threading

import pytest

from kueue_oss_tpu import metrics, obs, resilience
from kueue_oss_tpu.resilience import DegradationController


class _Clock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# controller: levels, hysteresis, events
# ---------------------------------------------------------------------------


class TestDegradationController:
    def test_level_is_max_severity_of_active_conditions(self):
        ctl = DegradationController(clock=_Clock())
        assert ctl.level(resilience.SOLVER) == 0
        assert ctl.rung(resilience.SOLVER) == "mesh"
        ctl.report(resilience.SOLVER, "mesh_broken", True, reason="ici")
        assert ctl.level(resilience.SOLVER) == 1
        assert ctl.rung(resilience.SOLVER) == "single"
        ctl.report(resilience.SOLVER, "breaker_open", True)
        assert ctl.level(resilience.SOLVER) == 2
        assert ctl.rung(resilience.SOLVER) == "host"
        # healing the breaker drops to the mesh condition's level, not 0
        ctl.report(resilience.SOLVER, "breaker_open", False)
        assert ctl.level(resilience.SOLVER) == 1
        ctl.report(resilience.SOLVER, "mesh_broken", False)
        assert ctl.level(resilience.SOLVER) == 0
        assert ctl.max_level() == 0

    def test_unknown_condition_is_a_hard_error(self):
        ctl = DegradationController(clock=_Clock())
        with pytest.raises(KeyError):
            ctl.report(resilience.SOLVER, "made_up", True)
        with pytest.raises(KeyError):
            ctl.report("made_up_subsystem", "mesh_broken", True)

    def test_transitions_only_on_state_change(self):
        ctl = DegradationController(clock=_Clock())
        assert ctl.report(resilience.STREAMING, "stream_off", True)
        assert not ctl.report(resilience.STREAMING, "stream_off", True)
        assert ctl.report(resilience.STREAMING, "stream_off", False)
        assert not ctl.report(resilience.STREAMING, "stream_off", False)
        assert len(ctl.history) == 2

    def test_repeat_fault_restarts_cooldown(self):
        """Hysteresis: a probe may only fire after a QUIET period —
        every repeat observation of an active fault pushes it out."""
        clk = _Clock()
        ctl = DegradationController(clock=clk)
        ctl.report(resilience.SOLVER, "mesh_broken", True)
        clk.t = 9.0
        ctl.report(resilience.SOLVER, "mesh_broken", True)  # re-observed
        clk.t = 10.0  # 10s after first fault, 1s after the repeat
        assert not ctl.begin_probe(resilience.SOLVER, "mesh_broken", 10.0)
        clk.t = 19.0
        assert ctl.begin_probe(resilience.SOLVER, "mesh_broken", 10.0)

    def test_single_probe_slot(self):
        clk = _Clock(100.0)
        ctl = DegradationController(clock=clk)
        ctl.report(resilience.PERSISTENCE, "fsync_degraded", True)
        clk.t = 200.0
        assert ctl.begin_probe(resilience.PERSISTENCE,
                               "fsync_degraded", 10.0)
        # the slot is taken until the probe reports back
        assert not ctl.begin_probe(resilience.PERSISTENCE,
                                   "fsync_degraded", 10.0)
        ctl.end_probe(resilience.PERSISTENCE, "fsync_degraded",
                      success=False)
        # failed probe restarted the cooldown
        assert not ctl.begin_probe(resilience.PERSISTENCE,
                                   "fsync_degraded", 10.0)
        clk.t = 211.0
        assert ctl.begin_probe(resilience.PERSISTENCE,
                               "fsync_degraded", 10.0)

    def test_probe_requires_active_condition(self):
        ctl = DegradationController(clock=_Clock())
        assert not ctl.begin_probe(resilience.SOLVER, "mesh_broken", 0.0)

    def test_metrics_events_and_snapshot(self):
        ctl = resilience.controller
        obs.recorder.clear()
        obs.cycle_ledger.clear()
        ctl.report(resilience.FEDERATION, "backpressure", True,
                   reason="queue full", cycle=7)
        snap = ctl.snapshot()
        assert snap["degraded"] and snap["maxLevel"] == 1
        fed = snap["subsystems"][resilience.FEDERATION]
        assert fed["level"] == 1 and fed["rung"] == "dedicated"
        assert fed["conditions"] == {"backpressure": "queue full"}
        assert metrics.degradation_level.value(
            resilience.FEDERATION) == 1
        ev = [e for e in obs.recorder.events()
              if e.kind == obs.DEGRADATION]
        assert ev and ev[-1].detail["new_level"] == 1
        assert ev[-1].reason_slug == "federation_backpressure"
        row = obs.cycle_ledger.last_row(obs.DEGRADATION_ROW)
        assert row is not None and row.cycle == 7
        ctl.report(resilience.FEDERATION, "backpressure", False)
        assert metrics.degradation_level.value(
            resilience.FEDERATION) == 0
        t = ctl.transitions_for(resilience.FEDERATION)
        assert [e["active"] for e in t] == [True, False]

    def test_history_bounded(self):
        ctl = DegradationController(clock=_Clock(), history_limit=4)
        for i in range(6):
            ctl.report(resilience.STREAMING, "stream_off", i % 2 == 0)
        assert len(ctl.history) == 4
        assert ctl.history[0]["seq"] == 3

    def test_use_swaps_process_controller(self):
        scratch = DegradationController(clock=_Clock())
        with resilience.use(scratch) as ctl:
            assert resilience.controller is scratch is ctl
            resilience.controller.report(
                resilience.SOLVER, "device_error", True)
        assert resilience.controller is not scratch
        assert resilience.controller.level(resilience.SOLVER) == 0

    def test_configure_applies_resilience_config(self):
        from kueue_oss_tpu.config.configuration import load

        cfg = load({"resilience": {"historyLimit": 9, "enabled": False,
                                   "walRestoreCooldown": 5.0}})
        before = resilience.wal_restore_cooldown_s
        try:
            resilience.configure(cfg.resilience)
            assert resilience.controller.history_limit == 9
            assert resilience.controller.enabled is False
            assert resilience.wal_restore_cooldown_s == 5.0
            obs.recorder.clear()
            resilience.controller.report(
                resilience.SOLVER, "mesh_broken", True)
            # disabled = no recorder events; state + metrics still on
            assert not [e for e in obs.recorder.events()
                        if e.kind == obs.DEGRADATION]
            assert resilience.controller.level(resilience.SOLVER) == 1
        finally:
            resilience.wal_restore_cooldown_s = before

    def test_config_validation(self):
        from kueue_oss_tpu.config.configuration import load, validate

        errs = validate(load({"resilience": {"historyLimit": 0}}))
        assert any("historyLimit" in e for e in errs)
        errs = validate(load({"resilience": {"walRestoreCooldown": -1}}))
        assert any("walRestoreCooldown" in e for e in errs)


# ---------------------------------------------------------------------------
# solver breaker: single half-open probe (satellite b)
# ---------------------------------------------------------------------------


class TestBreakerSingleProbe:
    def _open_breaker(self):
        from kueue_oss_tpu.solver.resilience import SolverHealth

        clk = _Clock()
        h = SolverHealth(failure_threshold=2, cooldown_s=5.0, clock=clk)
        h.record_failure()
        h.record_failure()
        assert h.state == "open"
        assert resilience.controller.active(resilience.SOLVER,
                                            "breaker_open")
        return h, clk

    def test_exactly_one_half_open_probe(self):
        h, clk = self._open_breaker()
        assert not h.allow()  # cooling down
        clk.t = 6.0
        assert h.allow()      # the probe slot
        assert h.state == "half-open"
        assert not h.allow()  # second caller stays shed
        h.record_success()
        assert h.state == "closed"
        assert not resilience.controller.active(resilience.SOLVER,
                                                "breaker_open")
        assert h.allow()

    def test_slow_probe_blocks_concurrent_callers(self):
        """Regression: while one thread's probe call is STILL IN
        FLIGHT (slow sidecar), every other thread must stay on the
        host path — the old breaker granted every post-cooldown caller
        HALF_OPEN passage simultaneously."""
        h, clk = self._open_breaker()
        clk.t = 10.0
        results = []
        got_slot = threading.Event()
        release = threading.Event()

        def prober():
            ok = h.allow()
            results.append(("prober", ok))
            got_slot.set()
            # the probe call is slow: hold the slot
            release.wait(5.0)
            h.record_failure()

        t = threading.Thread(target=prober)
        t.start()
        assert got_slot.wait(5.0)
        # concurrent traffic while the probe is in flight
        for _ in range(4):
            results.append(("other", h.allow()))
        release.set()
        t.join(5.0)
        assert ("prober", True) in results
        assert all(not ok for who, ok in results if who == "other")
        # the failed probe re-opened; the next cooldown gates again
        assert h.state == "open"
        assert not h.allow()
        clk.t = 20.0
        assert h.allow()
        h.record_success()

    def test_failed_probe_releases_slot_and_recools(self):
        h, clk = self._open_breaker()
        clk.t = 6.0
        assert h.allow()
        h.record_failure()
        assert h.state == "open"
        assert not h.probing
        clk.t = 7.0
        assert not h.allow()  # cooldown restarted from the failure
        clk.t = 12.0
        assert h.allow()


# ---------------------------------------------------------------------------
# engine arms report through the controller
# ---------------------------------------------------------------------------


class TestEngineLadder:
    def _engine(self):
        from kueue_oss_tpu.api.types import (
            ClusterQueue, FlavorQuotas, LocalQueue, PodSet,
            ResourceFlavor, ResourceGroup, ResourceQuota, Workload)
        from kueue_oss_tpu.core.queue_manager import QueueManager
        from kueue_oss_tpu.core.store import Store
        from kueue_oss_tpu.solver.engine import SolverEngine

        store = Store()
        store.upsert_resource_flavor(ResourceFlavor(name="f"))
        store.upsert_cluster_queue(ClusterQueue(
            name="cq", resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[FlavorQuotas(name="f", resources=[
                    ResourceQuota(name="cpu", nominal=8)])])]))
        store.upsert_local_queue(LocalQueue(name="lq",
                                            cluster_queue="cq"))
        store.add_workload(Workload(
            name="w", queue_name="lq", uid=1, creation_time=0.0,
            podsets=[PodSet(name="m", count=1, requests={"cpu": 1})]))
        return SolverEngine(store, QueueManager(store))

    def test_mesh_failure_reports_condition_and_shim_roundtrips(self):
        eng = self._engine()
        eng._note_mesh_failure(RuntimeError("chip gone"), "mesh_error")
        ctl = resilience.controller
        assert ctl.active(resilience.SOLVER, "mesh_broken")
        assert eng._mesh_broken  # the property shim reads the controller
        assert eng._mesh_broken_at is not None
        # legacy cooldown-rewind idiom still works through the shim
        eng._mesh_broken_at -= 1000.0
        assert ctl.cooldowns.stamp(
            (resilience.SOLVER, "mesh_broken")) == eng._mesh_broken_at
        eng._mesh_broken = False
        assert not ctl.active(resilience.SOLVER, "mesh_broken")


# ---------------------------------------------------------------------------
# WAL durability ladder
# ---------------------------------------------------------------------------


class TestWalLadder:
    def _wal(self, tmp_path, clk):
        from kueue_oss_tpu.persist.wal import WriteAheadLog

        resilience.controller.clock = clk
        wal = WriteAheadLog(str(tmp_path / "w.log"), fsync="always")
        wal.restore_cooldown_s = 10.0
        return wal

    def test_degrades_one_rung_per_fault_and_probes_back(self, tmp_path):
        clk = _Clock()
        wal = self._wal(tmp_path, clk)
        ctl = resilience.controller
        wal.fsync_fault = 1
        wal.append({"a": 1})
        assert wal.fsync == "batch"
        assert ctl.active(resilience.PERSISTENCE, "fsync_degraded")
        assert ctl.level(resilience.PERSISTENCE) == 1
        wal.fsync_fault = 1
        wal.append({"a": 2}, sync=True)
        assert wal.fsync == "off"
        assert ctl.active(resilience.PERSISTENCE, "wal_off")
        assert ctl.level(resilience.PERSISTENCE) == 2
        # watermark advanced: shipping/group-commit must not wedge
        assert wal.synced_size == wal.size
        # before the cooldown: no restore
        clk.t = 5.0
        wal.sync()
        assert wal.fsync == "off"
        # after the cooldown: one probe fsync restores the config
        clk.t = 20.0
        wal.sync()
        assert wal.fsync == "always"
        assert ctl.level(resilience.PERSISTENCE) == 0
        wal.close()

    def test_failed_probe_restarts_cooldown(self, tmp_path):
        clk = _Clock()
        wal = self._wal(tmp_path, clk)
        wal.fsync_fault = 1
        wal.append({"a": 1})
        assert wal.fsync == "batch"
        clk.t = 20.0
        wal.fsync_fault = 1  # the disk is still sick at probe time
        assert not wal.maybe_restore()
        assert wal.fsync == "batch"
        clk.t = 25.0
        assert not wal.maybe_restore()  # cooldown restarted
        clk.t = 31.0
        assert wal.maybe_restore()
        assert wal.fsync == "always"
        wal.close()

    def test_records_survive_degraded_run(self, tmp_path):
        from kueue_oss_tpu.persist.wal import WriteAheadLog, replay_wal

        clk = _Clock()
        wal = self._wal(tmp_path, clk)
        wal.append({"i": 0})
        wal.fsync_fault = 2
        for i in range(1, 5):
            wal.append({"i": i})
        wal.close()
        records, torn = replay_wal(wal.path)
        assert not torn and [r["i"] for r in records] == list(range(5))


# ---------------------------------------------------------------------------
# farm: backpressure conditions + runtime re-weighting (satellite a)
# ---------------------------------------------------------------------------


class TestFarmLadder:
    def test_throttle_reports_and_service_clears(self):
        from kueue_oss_tpu.federation.farm import FarmScheduler

        fs = FarmScheduler(max_queued=4, clock=_Clock())
        fs.force_throttle("blue", times=1)
        hdr, _ = fs.run("blue", lambda: ({"ok": True}, b""))
        assert hdr["ok"] is False and "backpressure" in hdr["error"]
        ctl = resilience.controller
        assert ctl.active(resilience.FEDERATION, "backpressure")
        assert ctl.level(resilience.FEDERATION) == 1
        hdr, _ = fs.run("blue", lambda: ({"ok": True}, b""))
        assert hdr["ok"] is True
        assert not ctl.active(resilience.FEDERATION, "backpressure")

    @staticmethod
    def _pump(fs, tenants, total, pending):
        """tests.test_federation._drive, but with a caller-owned
        ``pending`` dict so grants can be pumped across a live
        re-weighting without draining the farm's queues."""
        from tests.test_federation import _Ticket

        grants = {t: 0 for t in tenants}
        for _ in range(total):
            with fs._lock:
                for t in tenants:
                    fs._register_locked(t)
                    while len(fs._queues[t]) < 2:
                        tk = _Ticket()
                        fs._queues[t].append(tk)
                        pending[t].append(tk)
                fs._grant_next_locked()
            winner = next(
                t for t in tenants
                for tk in pending[t] if tk.granted.is_set())
            pending[winner].remove(
                next(tk for tk in pending[winner]
                     if tk.granted.is_set()))
            grants[winner] += 1
            fs._complete(winner, 0.01)
        return grants

    def test_set_weights_applies_within_one_ring_walk(self):
        """Satellite: runtime re-weighting takes effect within ONE
        ring walk — the very next grant sequence tracks the new DRR
        shares, no farm restart, no queue drain."""
        from kueue_oss_tpu.federation.farm import FarmScheduler

        fs = FarmScheduler(weights={"a": 1.0, "b": 1.0},
                           quantum_s=0.01, max_queued=64)
        pending = {"a": [], "b": []}
        grants = self._pump(fs, ["a", "b"], 120, pending)
        ratio = grants["a"] / max(1, grants["b"])
        assert 1 / 1.5 <= ratio <= 1.5, grants
        eff = fs.set_weights({"a": 3.0, "b": 1.0})
        assert eff["a"] == 3.0
        grants2 = self._pump(fs, ["a", "b"], 200, pending)
        ratio2 = grants2["a"] / max(1, grants2["b"])
        assert 3.0 / 1.5 <= ratio2 <= 3.0 * 1.5, grants2

    def test_set_weights_validates_and_recaps_deficits(self):
        from kueue_oss_tpu.federation.farm import FarmScheduler

        fs = FarmScheduler(quantum_s=0.01, max_credit_quanta=2.0)
        with fs._lock:
            fs._register_locked("t")
        fs._deficit["t"] = 10.0
        with pytest.raises(ValueError):
            fs.set_weights({"t": 0.0})
        with pytest.raises(ValueError):
            fs.set_weights(default_weight=-1.0)
        fs.set_weights({"t": 1.0})
        cap = fs.quantum_s * 1.0 * fs.max_credit_quanta
        assert fs._deficit["t"] <= cap + 1e-9

    def test_reload_config_updates_drr_knobs(self):
        from kueue_oss_tpu.config.configuration import load
        from kueue_oss_tpu.federation.farm import FarmScheduler

        fs = FarmScheduler()
        cfg = load({"federation": {
            "tenantWeights": {"gold": 4.0}, "defaultWeight": 2.0,
            "quantum": 0.05, "maxQueued": 3, "maxCreditQuanta": 1.5,
        }}).federation
        fs.reload_config(cfg)
        assert fs.weight("gold") == 4.0
        assert fs.weight("anyone") == 2.0
        assert fs.quantum_s == 0.05 and fs.max_queued == 3
        assert fs.max_credit_quanta == 1.5


# ---------------------------------------------------------------------------
# /api surfaces: health rollup, degradation view, farm weights
# ---------------------------------------------------------------------------


class TestApiSurfaces:
    def test_health_rolls_up_degradation(self):
        from kueue_oss_tpu.core.queue_manager import QueueManager
        from kueue_oss_tpu.core.store import Store
        from kueue_oss_tpu.viz import Dashboard

        store = Store()
        dash = Dashboard(store, QueueManager(store))
        h = dash.health_view()
        assert h["degradation"]["degraded"] is False
        resilience.controller.report(resilience.PERSISTENCE, "wal_off",
                                     True, reason="disk sick")
        h = dash.health_view()
        assert h["status"] == "degraded"
        sub = h["degradation"]["subsystems"][resilience.PERSISTENCE]
        assert sub["rung"] == "wal-off-alarm"
        d = dash.degradation_view()
        assert d["maxLevel"] == 2 and d["recentTransitions"]

    def test_farm_weights_get_and_post(self):
        from kueue_oss_tpu.core.queue_manager import QueueManager
        from kueue_oss_tpu.core.store import Store
        from kueue_oss_tpu.federation.farm import FarmScheduler
        from kueue_oss_tpu.viz import Dashboard

        store = Store()
        dash = Dashboard(store, QueueManager(store))
        assert dash.farm_weights_view() == {"attached": False}
        assert dash.set_farm_weights({"weights": {"a": 2.0}})["ok"] \
            is False
        dash.farm = FarmScheduler(weights={"a": 1.0})
        view = dash.farm_weights_view()
        assert view["attached"] and view["weights"] == {"a": 1.0}
        out = dash.set_farm_weights(
            {"weights": {"a": 5.0}, "defaultWeight": 2.0})
        assert out["ok"] and out["weights"]["a"] == 5.0
        assert dash.farm.weight("other") == 2.0
        bad = dash.set_farm_weights({"weights": {"a": -1}})
        assert bad["ok"] is False and "error" in bad

    def test_farm_weights_http_roundtrip(self):
        import json as _json
        import urllib.request

        from kueue_oss_tpu.core.queue_manager import QueueManager
        from kueue_oss_tpu.core.store import Store
        from kueue_oss_tpu.federation.farm import FarmScheduler
        from kueue_oss_tpu.viz import Dashboard, DashboardServer

        store = Store()
        dash = Dashboard(store, QueueManager(store))
        dash.farm = FarmScheduler(weights={"a": 1.0})
        srv = DashboardServer(dash)
        srv.start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            got = _json.loads(urllib.request.urlopen(
                base + "/api/farm/weights", timeout=5).read())
            assert got["weights"] == {"a": 1.0}
            req = urllib.request.Request(
                base + "/api/farm/weights",
                data=_json.dumps({"weights": {"a": 4.0}}).encode(),
                headers={"Content-Type": "application/json"},
                method="POST")
            got = _json.loads(urllib.request.urlopen(
                req, timeout=5).read())
            assert got["ok"] and dash.farm.weight("a") == 4.0
            deg = _json.loads(urllib.request.urlopen(
                base + "/api/degradation", timeout=5).read())
            assert deg["maxLevel"] == 0
        finally:
            srv.stop()
