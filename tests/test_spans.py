"""The one span primitive (obs/spans.py) and what hangs on it: totals,
ledger rows' phases, the Tracer as a sink, JAX's compile events by
span, and the kernels' named scopes."""

import gc
import sys
import time

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
from kueue_oss_tpu.debugger.profiling import Tracer, attach_to_scheduler
from kueue_oss_tpu.obs import spans
from kueue_oss_tpu.scheduler.scheduler import Scheduler


@pytest.fixture(autouse=True)
def _clean_spans():
    obs.cycle_ledger.enabled = True
    obs.cycle_ledger.clear()
    spans.reset()
    yield
    spans.trace_off("test")
    obs.cycle_ledger.enabled = True
    spans.reset()


def _store(n_wl=12, nominal=8):
    store = Store()
    store.upsert_resource_flavor(ResourceFlavor(name="f"))
    store.upsert_cluster_queue(ClusterQueue(
        name="cq0", resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name="f", resources=[
                ResourceQuota(name="cpu", nominal=nominal)])])]))
    store.upsert_local_queue(LocalQueue(name="lq0", cluster_queue="cq0"))
    for i in range(n_wl):
        store.add_workload(Workload(
            name=f"w{i}", queue_name="lq0", uid=i + 1,
            creation_time=float(i),
            podsets=[PodSet(name="main", count=1,
                            requests={"cpu": 1})]))
    return store


def _small_replay(seed=5, cohorts=2, cqs=6):
    from benchmark import deployment, driver

    cfg = deployment.scaled(
        deployment.load_config("upstream-large-scale"), cohorts, cqs, 1)
    replay = driver.Replay(cfg, deployment.schedule(cfg, seed),
                           solver="auto")
    replay.sched.solver_min_backlog = 8
    replay.preload(3.3)
    return replay


# -- the primitive ------------------------------------------------------------


def test_nesting_parent_self_time_and_totals():
    with spans.span("outer", cycle=3) as outer:
        time.sleep(0.002)
        with spans.span("inner") as a:
            assert spans.current() is a and a.parent is outer
            time.sleep(0.003)
        with spans.span("inner"):
            time.sleep(0.001)
    assert spans.current() is None
    t = spans.totals()
    assert t["outer"]["n"] == 1 and t["inner"]["n"] == 2
    assert t["inner"]["s"] >= 0.004
    # a leaf's self time is its time; a parent's is what its children
    # leave
    assert t["inner"]["self_s"] == pytest.approx(t["inner"]["s"])
    assert t["outer"]["self_s"] == pytest.approx(
        t["outer"]["s"] - t["inner"]["s"], abs=1e-9)
    assert t["outer"]["self_s"] >= 0.002
    assert outer.seconds == pytest.approx(t["outer"]["s"])


def test_collector_gathers_descendants_by_name():
    with spans.span("row", collect=True) as row:
        with spans.span("solve"):
            with spans.span("device_put"):
                time.sleep(0.001)
            assert spans.collected() == row.phases
        with spans.span("apply"):
            pass
        with spans.span("apply"):
            pass
    t = spans.totals()
    assert set(row.phases) == {"solve", "device_put", "apply"}
    assert row.phases["solve"] == pytest.approx(t["solve"]["s"])
    assert row.phases["apply"] == pytest.approx(t["apply"]["s"])
    # the child's time lies inside its parent's, as the ledger has it
    assert row.phases["device_put"] <= row.phases["solve"]
    assert spans.collected() == {}


def test_off_is_one_shared_no_op():
    obs.cycle_ledger.enabled = False
    assert not spans.tracing()
    a, b = spans.span("x", cycle=1), spans.span("y")
    assert a is b, "no object per span while nothing records"
    with a as got:
        assert got.phases is None and got.seconds == 0.0
        assert spans.current() is None
    assert spans.start() == 0
    spans.add_since("store.finish", 0)
    assert spans.totals() == {}
    # a duration the caller returns is timed all the same
    with spans.span("solve", force=True) as sp:
        time.sleep(0.001)
    assert sp.seconds >= 0.001
    # the switch alone brings the record back
    spans.trace_on("test")
    with spans.span("x"):
        pass
    assert spans.totals()["x"]["n"] == 1 and len(spans.ring()) == 1


def test_ring_is_bounded_and_only_filled_while_on():
    with spans.span("before"):
        pass
    assert spans.ring() == []
    spans.set_ring_size(8)
    try:
        spans.trace_on("test")
        with spans.span("parent", cycle=9):
            for _ in range(20):
                with spans.span("leaf"):
                    pass
        ring = spans.ring()
        assert len(ring) == 8
        seq, name, t0, t1, parent, cycle, tid = ring[-1]
        assert name == "parent" and cycle == 9 and parent == 0
        assert t1 >= t0 and all(r[4] == seq for r in ring[:-1])
        spans.trace_off("test")
        with spans.span("after"):
            pass
        assert len(spans.ring()) == 8 and spans.ring()[-1][1] == "parent"
    finally:
        spans.set_ring_size(16384)


def test_totals_only_timing_and_counters():
    t0 = spans.start()
    assert t0 > 0
    time.sleep(0.001)
    spans.add_since("store.finish", t0)
    spans.add("store.finish", 0.5)
    spans.count("drain_admitted", 7)
    spans.count("drain_admitted", 3)
    got = spans.totals()["store.finish"]
    assert got["n"] == 2 and got["s"] >= 0.501
    assert spans.counters()["drain_admitted"] == 10
    text = metrics.registry.render()
    assert 'kueue_span_total{span="store.finish"} 2.0' in text
    assert 'kueue_span_seconds_total{span="store.finish"}' in text


# -- ledger rows ---------------------------------------------------------------


def test_host_row_phases_are_the_cycles_spans():
    store = _store()
    sched = Scheduler(store, QueueManager(store))
    sched.schedule(now=1.0)
    row = obs.cycle_ledger.last_row(obs.HOST_CYCLE)
    t = spans.totals()
    assert set(row.phases) == {"requeue", "snapshot", "nominate",
                               "entries", "flush"}
    for name, sec in row.phases.items():
        assert t[name]["n"] == 1
        assert sec == pytest.approx(t[name]["s"], abs=1e-6)
    assert t["schedule"]["n"] == 1
    assert sum(row.phases.values()) <= t["schedule"]["s"] + 1e-5
    # rows and spans come off one clock
    assert 0 < row.mono_ns <= spans.now()
    d = row.to_dict()
    assert obs.ledger.CycleRecord.from_dict(d).mono_ns == row.mono_ns
    # a finish is a per-event total, not a span
    key = next(k for k, w in store.workloads.items()
               if w.is_quota_reserved)
    sched.finish_workload(key, now=2.0)
    assert spans.totals()["store.finish"]["n"] == 1
    assert spans.totals()["store.add"]["n"] == 12


def _check_drain_row(row, kind_spans):
    t = spans.totals()
    assert {"backlog", "export", "encode", "solve", "device_put",
            "dispatch", "wait", "fetch", "apply",
            "apply.commit"} | kind_spans <= set(row.phases)
    for name in ("export", "encode", "solve", "device_put", "dispatch",
                 "wait", "fetch", "apply", "apply.commit"):
        assert row.phases[name] == pytest.approx(t[name]["s"], abs=1e-6)
    # solve still contains device_put, and now its other parts
    inside = sum(row.phases[k] for k in ("device_put", "dispatch", "wait",
                                         "fetch"))
    assert inside <= row.phases["solve"] + 1e-5
    assert inside >= 0.9 * row.phases["solve"]
    assert row.phases["apply.commit"] <= row.phases["apply"]
    # the row's span: the whole drain, the record's own time included
    assert t["solver_drain"]["n"] == 1 and t["record"]["n"] >= 1
    assert t["solver_drain"]["s"] >= sum(
        row.phases[k] for k in ("backlog", "export", "encode", "solve",
                                "apply"))


def test_lean_drain_row_phases_are_the_drains_spans():
    from kueue_oss_tpu.solver.engine import SolverEngine

    store = _store()
    engine = SolverEngine(store, QueueManager(store))
    result = engine.drain(now=100.0, verify=True)
    assert result.admitted == 8
    row = obs.cycle_ledger.last_row(obs.SOLVER_DRAIN)
    _check_drain_row(row, {"apply.decode", "apply.verify", "apply.park"})
    assert result.solver_time_s == pytest.approx(row.phases["solve"],
                                                 abs=1e-6)
    assert result.apply_time_s == pytest.approx(row.phases["apply"],
                                                abs=1e-6)
    assert spans.counters()["drain_admitted"] == 8


def test_full_drain_row_and_a_replays_quiet_adds_up():
    replay = _small_replay()
    replay.run(3.0, max_passes=3)
    assert replay.engine.drain_count >= 1
    row = obs.cycle_ledger.last_row(obs.SOLVER_DRAIN)
    if replay.engine.drain_count == 1:
        _check_drain_row(row, {"apply.evict", "apply.decode",
                               "apply.verify", "apply.park"})
    t = spans.totals()
    # every span's children are timed inside it: the outside is the sum
    # of the inside and of what it does itself
    for parent, children in (
            ("quiet", ("route", "schedule", "quiet.fingerprint")),
            ("route", ("solver_drain",)),
            ("schedule", ("requeue", "snapshot", "nominate", "entries",
                          "flush")),
            ("solver_drain", ("backlog", "export", "encode", "solve",
                              "apply", "record")),
            ("solve", ("device_put", "dispatch", "wait", "fetch"))):
        inside = sum(t[c]["s"] for c in children if c in t)
        # fingerprints also run outside quiet (never here), so <=
        assert inside + t[parent]["self_s"] == pytest.approx(
            t[parent]["s"], rel=0.01), parent
    assert t["schedule"]["n"] == replay.sched.cycle_count
    assert t["solver_drain"]["n"] == replay.engine.drain_count
    assert spans.counters()["drain_admitted"] == sum(
        r.admitted for r in obs.cycle_ledger.rows()
        if r.kind == obs.SOLVER_DRAIN)


# -- the Tracer as a sink --------------------------------------------------------


def test_tracer_is_a_sink_and_patches_nothing():
    store = _store()
    sched = Scheduler(store, QueueManager(store), solver="auto",
                      solver_min_backlog=4)
    schedule, nominate = sched.schedule, sched._nominate
    tracer = Tracer()
    attach_to_scheduler(sched, tracer)
    assert sched.schedule == schedule and sched._nominate == nominate
    assert spans.tracing()
    sched.run_until_quiet(now=0.0, tick=1.0)
    # a trickle under the router's threshold goes to a host cycle
    store.add_workload(Workload(
        name="late", queue_name="lq0", uid=99, creation_time=50.0,
        podsets=[PodSet(name="main", count=1, requests={"cpu": 1})]))
    sched.run_until_quiet(now=60.0, tick=1.0)
    assert tracer.durations_ms("schedule")
    assert tracer.durations_ms("nominate")
    assert tracer.durations_ms("solver_drain")
    names = {s[0] for s in tracer.spans()}
    assert {"quiet", "route", "solve", "dispatch", "wait", "fetch",
            "apply", "apply.commit", "snapshot", "entries"} <= names
    by_name = {s[0]: s for s in tracer.spans()}
    assert by_name["solver_drain"][4]["cycle"] == 1
    assert by_name["nominate"][4]["heads"] >= 1
    # the sink is held weakly: dropping the tracer switches the record
    # off again
    del tracer, by_name
    sched.tracer = None
    gc.collect()
    assert not spans.tracing()


# -- JAX's own events, by span -----------------------------------------------------


def test_compile_events_fall_in_the_innermost_span():
    import jax
    import jax.numpy as jnp

    spans.trace_on("test")
    with spans.span("solve"):
        with spans.span("dispatch"):
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    c = spans.counters()
    assert c["compiles"] >= 1 and c["compile_s"] > 0
    assert c["retrace_s"] > 0
    assert c["jax_by_span"]["dispatch"]["compiles"] >= 1
    assert "solve" not in c["jax_by_span"]
    spans.trace_off("test")
    before = spans.counters()["compiles"]
    with spans.span("dispatch"):
        jax.jit(lambda x: x * 5 + 2)(jnp.arange(7)).block_until_ready()
    assert spans.counters()["compiles"] == before


def test_host_only_import_stays_free_of_jax():
    import subprocess

    code = ("import sys\n"
            "from kueue_oss_tpu.core.store import Store\n"
            "from kueue_oss_tpu.core.queue_manager import QueueManager\n"
            "from kueue_oss_tpu.scheduler.scheduler import Scheduler\n"
            "from kueue_oss_tpu.debugger.profiling import (\n"
            "    Tracer, attach_to_scheduler)\n"
            "from kueue_oss_tpu.obs import spans\n"
            "s = Store(); sc = Scheduler(s, QueueManager(s))\n"
            "tr = Tracer(); attach_to_scheduler(sc, tr)\n"
            "sc.run_until_quiet(now=0.0)\n"
            "assert spans.tracing() and tr.durations_ms('schedule')\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


# -- names on the device -------------------------------------------------------------


def test_lowered_solvers_carry_each_scopes_name():
    from kueue_oss_tpu.solver.full_kernels import (
        make_full_solver,
        to_device_full,
    )
    from kueue_oss_tpu.solver.kernels import solve_backlog, to_device
    from kueue_oss_tpu.solver.tensors import export_problem, pad_workloads

    store = _store()
    queues = QueueManager(store)
    from kueue_oss_tpu.solver.engine import SolverEngine

    engine = SolverEngine(store, queues)
    problem, _ = engine.export()
    problem = pad_workloads(problem, 16)
    lean = solve_backlog.lower(to_device(problem)).as_text(debug_info=True)
    for scope in ("_select_heads", "nominate", "_round_scan", "round"):
        assert f"/{scope}/" in lean or f"{scope}/" in lean, scope

    replay = _small_replay(cohorts=1, cqs=4)
    eng = replay.engine
    pending = eng.pending_backlog()
    assert eng.needs_full_kernel(pending)
    fp = export_problem(replay.store, pending, include_admitted=True,
                        parked={}, afs=replay.queues.afs, now=3.3,
                        cache=eng.export_cache)
    g_max = int(fp.cq_ngroups.max())
    h_max, p_max = eng._size_caps(fp)
    fp = pad_workloads(fp, 256)
    solver = make_full_solver(g_max, h_max, p_max, False)
    full = solver.lower(to_device_full(fp)).as_text(debug_info=True)
    for scope in ("select_heads_full", "nominate_full", "walk_assign",
                  "build_candidate_table", "classical_search",
                  "full_round_scan", "round_body", "compact_victims"):
        # a vmapped stage reads ``vmap(<scope>)`` in the name stack
        assert f"/{scope}/" in full or f"/vmap({scope})/" in full, scope
