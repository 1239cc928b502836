"""What-if engine: determinism, vmap parity, replay fidelity.

Acceptance shape (ISSUE 8): same seed + same ScenarioSpec list =>
byte-identical report; S-way vmapped batch plans bit-identical to
solving each scenario alone; journal replay of a live run reproduces
the recorded decision kinds per cycle; the full-sync donation and
atomic-journal satellites.
"""

import json
import os

import numpy as np
import pytest

from kueue_oss_tpu import metrics, obs
from kueue_oss_tpu.api.types import (
    ClusterQueue,
    Cohort,
    FlavorQuotas,
    LocalQueue,
    Node,
    PodSet,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_oss_tpu.core.queue_manager import QueueManager
from kueue_oss_tpu.core.store import Store
from kueue_oss_tpu.perf.generator import GeneratorConfig, generate
from kueue_oss_tpu.sim import (
    FlapEvent,
    ScenarioSpec,
    WhatIfEngine,
    arrival_sweep,
    check_parity,
    cross,
    journal_baseline,
    kind_counts_per_cycle,
    load_events,
    pending_backlog,
    quota_sweep,
    replay,
    simulate_trace,
    solve_scenarios,
    solve_scenarios_sequential,
)
from kueue_oss_tpu.solver.tensors import (
    ExportCache,
    export_problem,
    pad_workloads,
    pow2,
)

pytestmark = pytest.mark.sim


@pytest.fixture(autouse=True)
def _reset():
    metrics.reset_all()
    obs.recorder.clear()
    obs.recorder.enabled = True
    yield
    metrics.reset_all()
    obs.recorder.clear()


def _contended_store(n_cohorts=2, cqs=3, counts=(6, 2, 1)):
    cfg = GeneratorConfig.large_scale(preemption=False)
    cfg.n_cohorts, cfg.cqs_per_cohort = n_cohorts, cqs
    for wc, n in zip(cfg.classes, counts):
        wc.count = n
    store, schedule = generate(cfg)
    for g in schedule:
        store.add_workload(g.workload)
    return store, schedule


def _grid(n):
    specs = cross(quota_sweep((0.25, 0.5, 1.5, 2.0, 3.0)),
                  arrival_sweep((0.5, 0.75, 1.5, 2.0, 2.5)))
    if len(specs) < n:
        specs = specs * (n // len(specs) + 1)
    return specs[:n]


# -- vmap parity (the batched-solve contract) -------------------------------


def test_vmapped_plans_bit_identical_to_sequential_64way():
    store, _ = _contended_store()
    report = WhatIfEngine(store).run(_grid(64), parity=64)
    assert report.parity["checked"] == 64
    assert report.parity["identical"], report.parity["mismatches"]
    assert len(report.scenarios) == 64
    # the sweep must actually explore distinct worlds
    admitted = {s["admitted"] for s in report.scenarios}
    assert len(admitted) > 2


def test_batch_layer_parity_direct():
    """Tensor-level check, independent of the engine plumbing."""
    store, _ = _contended_store(1, 2)
    problem = export_problem(
        store, pending_backlog(store),
        cache=ExportCache(store, subscribe=False))
    problem = pad_workloads(problem, 32)
    specs = _grid(8)
    overlays = [s.overlay(problem) for s in specs]
    batch = solve_scenarios(problem, overlays)
    seq = solve_scenarios_sequential(problem, overlays)
    pr = check_parity(batch, seq, range(len(specs)))
    assert pr.identical and pr.checked == len(specs)
    assert batch.batch_width == 8  # pow2 scenario padding


def test_batched_entry_rejects_unbatchable_fields():
    from kueue_oss_tpu.solver.kernels import solve_backlog_batched

    with pytest.raises(ValueError, match="not ProblemTensors fields"):
        solve_backlog_batched(None, {"nope": np.zeros((2, 3))})
    with pytest.raises(ValueError, match="at least one"):
        solve_backlog_batched(None, {})


# -- determinism ------------------------------------------------------------


def test_report_byte_identical_across_runs():
    store, _ = _contended_store()
    specs = _grid(16)
    for s in specs:
        s.priority_churn_fraction = 0.3
        s.priority_churn_delta = 40
    r1 = WhatIfEngine(store).run(specs, parity=2)
    r2 = WhatIfEngine(store).run(specs, parity=2)
    assert r1.canonical_json() == r2.canonical_json()
    # timing is reported but excluded from the canonical form
    assert "timing" not in json.loads(r1.canonical_json())
    assert "scenarios_per_sec" in r1.timing


def test_validate_rejects_non_finite_factors():
    """NaN compares False against every bound and int-casts to garbage
    cutoffs — it must fail loudly, not run a silently different sweep."""
    with pytest.raises(ValueError, match="finite"):
        ScenarioSpec(name="q", quota_scale={"*": float("nan")}).validate()
    with pytest.raises(ValueError, match="finite"):
        ScenarioSpec(name="a", arrival_scale=float("nan")).validate()
    with pytest.raises(ValueError, match="finite"):
        ScenarioSpec(name="i", arrival_scale=float("inf")).validate()


def test_pending_backlog_paths_agree_on_stopped_cqs():
    store, _ = _contended_store(1, 2)
    name = sorted(store.cluster_queues)[0]
    cq = store.cluster_queues[name]
    cq.stop_policy = "Hold"
    store.upsert_cluster_queue(cq)
    queues = QueueManager(store)
    via_store = pending_backlog(store)
    via_queues = pending_backlog(store, queues)
    assert name not in via_store and name not in via_queues
    assert set(via_store) == set(via_queues)


def test_pending_backlog_queues_path_includes_parked():
    store, _ = _contended_store(1, 2)
    queues = QueueManager(store)
    name = sorted(store.cluster_queues)[0]
    q = queues.queues[name]
    key = next(iter(q._in_heap))
    q.park(key)
    infos = pending_backlog(store, queues)[name]
    assert key in [i.key for i in infos]


def test_scenario_spec_json_roundtrip():
    spec = ScenarioSpec(
        name="x", quota_scale={"cohort-*": 1.5}, arrival_scale=2.0,
        priority_shift={"cq-0-*": 10}, priority_churn_fraction=0.25,
        priority_churn_delta=-5,
        node_flaps=[FlapEvent(at_ms=100.0, down=True, count=2)],
        seed=7)
    back = ScenarioSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
    assert back.to_dict() == spec.to_dict()


# -- scenario semantics -----------------------------------------------------


def test_quota_scaling_cohort_scales_its_subtree():
    store, _ = _contended_store()
    specs = [ScenarioSpec(name="base"),
             ScenarioSpec(name="half", quota_scale={"cohort-0": 0.25}),
             ScenarioSpec(name="boost", quota_scale={"cohort-0": 4.0})]
    rep = WhatIfEngine(store).run(specs, parity=3)
    assert rep.parity["identical"]
    base, half, boost = rep.scenarios
    assert half["admitted"] < base["admitted"]
    assert boost["admitted"] >= base["admitted"]
    # the untouched cohort must be unaffected by cohort-0's factor
    other = [k for k in base.get("admitted_by_cq", {}) if "cq-1-" in k]
    for cq in other:
        assert (half["admitted_by_cq"].get(cq, 0)
                == base["admitted_by_cq"].get(cq, 0))


def test_quota_zero_admits_nothing():
    store, _ = _contended_store(1, 2)
    rep = WhatIfEngine(store).run(
        [ScenarioSpec(name="dead", quota_scale={"*": 0.0})], parity=1)
    assert rep.parity["identical"]
    assert rep.scenarios[0]["admitted"] == 0


def test_arrival_scale_masks_and_replicates():
    store, _ = _contended_store(1, 2, counts=(8, 0, 0))
    specs = [ScenarioSpec(name="base"),
             ScenarioSpec(name="half", arrival_scale=0.5),
             ScenarioSpec(name="double", arrival_scale=2.0)]
    rep = WhatIfEngine(store).run(specs, parity=3)
    assert rep.parity["identical"]
    base, half, double = rep.scenarios
    assert rep.base["arrival_replicas"] == 2
    assert base["workloads"] == 16      # originals only
    assert half["workloads"] == 8       # earlier half arrived
    assert double["workloads"] == 32    # clones materialized
    assert double["admitted"] >= base["admitted"]


def test_priority_shift_moves_admissions_between_cqs():
    """Two CQs contend for one cohort's borrowable pool; raising CQ
    b's priorities must shift admissions toward it."""
    store = Store()
    store.upsert_resource_flavor(ResourceFlavor(name="f"))
    # the contended capacity lives on the COHORT: both CQs borrow from
    # the shared pool, so the per-round entry order (priority) decides
    # who gets it
    # pool of ONE admission: the higher-priority head wins the round's
    # entry order and takes it all
    store.upsert_cohort(Cohort(
        name="root",
        resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name="f", resources=[
                ResourceQuota(name="cpu", nominal=2)])])]))
    for name in ("a", "b"):
        store.upsert_cluster_queue(ClusterQueue(
            name=name, cohort="root",
            resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[FlavorQuotas(name="f", resources=[
                    ResourceQuota(name="cpu", nominal=0,
                                  borrowing_limit=100)])])]))
        store.upsert_local_queue(LocalQueue(name=f"lq-{name}",
                                            cluster_queue=name))
    for i in range(6):
        for name, prio in (("a", 100), ("b", 50)):
            store.add_workload(Workload(
                name=f"wl-{name}-{i}", queue_name=f"lq-{name}",
                priority=prio, creation_time=float(i),
                podsets=[PodSet(count=1, requests={"cpu": 2})]))
    specs = [ScenarioSpec(name="base"),
             ScenarioSpec(name="b-first", priority_shift={"b": 100})]
    rep = WhatIfEngine(store).run(specs, parity=2)
    assert rep.parity["identical"]
    base, shifted = rep.scenarios
    assert (shifted["admitted_by_cq"].get("b", 0)
            > base["admitted_by_cq"].get("b", 0))


# -- journal replay fidelity ------------------------------------------------


def _run_live_and_dump(path):
    from kueue_oss_tpu.perf.runner import Simulator

    cfg = GeneratorConfig.large_scale(preemption=False)
    cfg.n_cohorts, cfg.cqs_per_cohort = 1, 2
    for wc, n in zip(cfg.classes, (4, 2, 1)):
        wc.count = n
    store, schedule = generate(cfg)
    Simulator(store, schedule).run()
    return obs.recorder.dump_jsonl(path)


def test_journal_replay_reproduces_decision_kinds_per_cycle(tmp_path):
    journal = str(tmp_path / "decisions.jsonl")
    n = _run_live_and_dump(journal)
    assert n > 0
    events = load_events(journal)
    recorded = kind_counts_per_cycle(events)
    assert recorded  # the live run produced per-cycle decisions
    replayed = replay(events)
    assert kind_counts_per_cycle(replayed.events()) == recorded
    # virtual time: replayed timestamps AND breaker tags are the
    # recorded ones, not the replaying process's
    src = sorted(events, key=lambda e: e.seq)
    assert [ev.ts for ev in replayed.events()] == [ev.ts for ev in src]
    assert ([ev.breaker for ev in replayed.events()]
            == [ev.breaker for ev in src])
    base = journal_baseline(events)
    assert base["events"] == len(events)
    assert base["admitted"] > 0
    # a recorded breaker-open incident must survive replay verbatim
    # even though the replaying process's breaker is closed
    incident = obs.DecisionEvent(
        seq=1, ts=5.0, cycle=9, kind=obs.SOLVER_FALLBACK,
        workload=obs.CYCLE_SCOPE, breaker="open",
        reason_slug="breaker_open")
    assert replay([incident]).events()[0].breaker == "open"


def test_dump_jsonl_atomic_and_torn_line_tolerant(tmp_path):
    path = str(tmp_path / "j.jsonl")
    obs.recorder.record(obs.ASSIGNED, "ns/a", cycle=1)
    obs.recorder.record(obs.SKIPPED, "ns/b", cycle=1,
                        reason_slug="no_fit")
    n = obs.recorder.dump_jsonl(path)
    assert n == 2
    # atomic: no temp litter next to the journal
    assert os.listdir(tmp_path) == ["j.jsonl"]
    # a crash mid-append tears the tail; later lines may be garbage
    with open(path, "a") as f:
        f.write('{"seq": 3, "kind": "assigned", "workl')  # torn
        f.write("\nnot json at all\n")
        f.write(json.dumps({"seq": 4, "ts": 9.0, "cycle": 2,
                            "kind": "assigned",
                            "workload": "ns/c"}) + "\n")
    events = obs.load_jsonl(path)
    assert [ev.workload for ev in events] == ["ns/a", "ns/b", "ns/c"]
    assert obs.load_jsonl.last_skipped == 2


# -- full-sync donation satellite (DeviceResidentProblem) -------------------


def test_forced_resync_donates_resident_buffers():
    from kueue_oss_tpu.solver.delta import (
        DeviceResidentProblem,
        HostDeltaSession,
    )
    from kueue_oss_tpu.solver.kernels import solve_backlog, to_device

    store, _ = _contended_store(1, 2)
    cache = ExportCache(store)
    sess = HostDeltaSession(cache=cache)
    dev = DeviceResidentProblem()

    def export():
        p = export_problem(store, pending_backlog(store), cache=cache)
        # 12 of the 18 rows churn below: over half of the 20 padded
        # ones (up to PR 31 the pad was 64 and the full sync came from
        # the class space growing, which starts with room since PR 32)
        return pad_workloads(p, 20)

    slotted, frame = sess.advance(export())
    dev.update(slotted, frame, full=False)
    assert dev.full_uploads == 1 and dev.donated_full_syncs == 0

    # churn >50% of rows: the session degrades to a dense-delta full
    # sync at UNCHANGED padded capacity — the donation-eligible case
    for i, wl in enumerate(list(store.workloads.values())):
        if i % 3 != 2:
            wl.priority += 1000 + i
            store.update_workload(wl)
    slotted2, frame2 = sess.advance(export())
    assert frame2.delta is None and frame2.full_reason == "dense_delta"
    t = dev.update(slotted2, frame2, full=False)
    assert dev.donated_full_syncs == 1
    assert dev.avoided_copy_bytes > 0
    # the donated-overwrite tensors must solve identically to a fresh
    # upload of the same problem
    out_resident = [np.asarray(a) for a in solve_backlog(t)]
    out_fresh = [np.asarray(a) for a in solve_backlog(to_device(slotted2))]
    for a, b in zip(out_resident, out_fresh):
        assert np.array_equal(a, b)


# -- trace mode (virtual-time node flaps) -----------------------------------


def _trace_env():
    cfg = GeneratorConfig.large_scale(preemption=False)
    cfg.n_cohorts, cfg.cqs_per_cohort = 1, 2
    for wc, n in zip(cfg.classes, (4, 0, 0)):
        wc.count = n
    store, schedule = generate(cfg)
    for i in range(4):
        store.upsert_node(Node(name=f"node-{i}"))
    return store, schedule


def test_trace_mode_flap_schedule_virtual_time():
    spec = ScenarioSpec(
        name="flappy", arrival_scale=2.0, seed=3,
        node_flaps=[FlapEvent(at_ms=50.0, down=True, count=2),
                    FlapEvent(at_ms=200.0, down=False)])
    store, schedule = _trace_env()
    out1 = simulate_trace(store, schedule, spec)
    store2, schedule2 = _trace_env()
    out2 = simulate_trace(store2, schedule2, spec)
    assert out1["node_flaps"] == out2["node_flaps"]
    assert len(out1["node_flaps"]) == 2
    assert out1["node_flaps"][0]["atMs"] == 50.0
    assert len(out1["node_flaps"][0]["nodes"]) == 2
    assert out1["node_flaps"][1]["down"] is False
    assert out1["admitted"] > 0
    # deterministic end-to-end (real_seconds deliberately not reported)
    assert out1 == out2


# -- surfaces ---------------------------------------------------------------


def test_dashboard_whatif_endpoint():
    import urllib.request

    from kueue_oss_tpu.viz import Dashboard, DashboardServer

    store, _ = _contended_store(1, 2)
    queues = QueueManager(store)
    srv = DashboardServer(Dashboard(store, queues))
    srv.start()
    try:
        url = (f"http://127.0.0.1:{srv.port}/api/whatif"
               "?factors=0.5,2&target=cohort-0")
        rep = json.loads(urllib.request.urlopen(url, timeout=60).read())
        names = [s["name"] for s in rep["scenarios"]]
        assert names[0] == "base" and len(names) == 3
        assert rep["parity"]["identical"]
        assert metrics.whatif_batches_total.total() >= 1
    finally:
        srv.stop()


def test_cli_64_scenario_batch_deterministic(tmp_path, capsys):
    """ISSUE acceptance: tools/simulate.py runs a >=64-scenario batch
    end-to-end on the CPU backend deterministically, with vmapped
    plans bit-identical to the sequential oracle."""
    import importlib

    simulate = importlib.import_module("tools.simulate")
    args = ["--scenarios", "64", "--no-timing", "--compact",
            "--parity", "3"]
    assert simulate.main(args) == 0
    out1 = capsys.readouterr().out
    assert simulate.main(args) == 0
    out2 = capsys.readouterr().out
    assert out1 == out2  # byte-identical rerun
    rep = json.loads(out1)
    assert rep["mode"] == "batched"
    assert len(rep["scenarios"]) == 64
    assert rep["parity"]["identical"] and rep["parity"]["checked"] == 3
    assert "timing" not in rep


def test_cli_journal_anchor(tmp_path, capsys):
    journal = str(tmp_path / "decisions.jsonl")
    _run_live_and_dump(journal)
    import importlib

    simulate = importlib.import_module("tools.simulate")
    assert simulate.main(["--sweep", "quota", "--factors", "0.5",
                          "--journal", journal, "--compact",
                          "--no-timing"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["journal"]["replay_faithful"] is True
    assert rep["journal"]["admitted"] > 0


# -- round-skew bucketing (sim/batch.py solve_scenarios_bucketed) -----------


def test_round_bucketing_bit_identical_with_bucket_stats():
    """Bucketing by predicted round count must change WALL SHAPE only:
    stitched per-scenario plans stay bitwise identical to the single
    unbucketed dispatch, and the bucket stats cover every scenario."""
    from kueue_oss_tpu.sim.batch import (
        predict_rounds,
        solve_scenarios_bucketed,
    )

    store, _ = _contended_store()
    problem = export_problem(
        store, pending_backlog(store),
        cache=ExportCache(store, subscribe=False))
    problem = pad_workloads(problem, pow2(problem.n_workloads))
    # arrival scales spread predicted depths across multiple buckets
    specs = arrival_sweep((0.1, 0.15, 0.2, 1.0, 1.0, 0.9, 0.1, 1.0))
    overlays = [s.overlay(problem, arrival_idx=None) for s in specs]
    preds = predict_rounds(problem, overlays)
    assert len(set(int(p) for p in preds)) > 1

    plain = solve_scenarios(problem, overlays)
    bucketed, stats, dispatches = solve_scenarios_bucketed(
        problem, overlays, min_batch=2)
    assert dispatches >= 2
    assert sum(stats.values()) == len(specs)
    for name in ("admitted", "opt", "admit_round", "parked", "rounds",
                 "usage"):
        assert np.array_equal(getattr(plain, name),
                              getattr(bucketed, name)), name


def test_engine_reports_round_buckets_and_metrics():
    store, _ = _contended_store()
    specs = _grid(16)
    before = {k: v for k, v in
              metrics.whatif_round_buckets_total.collect().items()}
    report = WhatIfEngine(store).run(specs, parity=8)
    assert report.parity["identical"], report.parity["mismatches"]
    buckets = report.timing["round_buckets"]
    assert sum(buckets.values()) == len(specs)
    assert report.timing["batch_dispatches"] >= 1
    after = metrics.whatif_round_buckets_total.collect()
    assert sum(after.values()) - sum(before.values()) == len(specs)


def test_round_bucketing_off_is_single_dispatch():
    from kueue_oss_tpu.config.configuration import SimulatorConfig

    store, _ = _contended_store()
    cfg = SimulatorConfig(round_bucketing=False)
    report = WhatIfEngine(store, config=cfg).run(_grid(12), parity=4)
    assert report.parity["identical"]
    assert report.timing["batch_dispatches"] == 1
    assert report.timing["round_buckets"] == {}
