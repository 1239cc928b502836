"""The host's time beneath its phase spans: the garbage collector and the
flight recorder as totals of their own, and what nomination and the entry
pass spend per head (``obs/spans.py``, ``obs.FlightRecorder``,
``scheduler.Scheduler``)."""

import gc

import pytest

from kueue_oss_tpu import obs
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
from kueue_oss_tpu.obs import spans
from kueue_oss_tpu.scheduler.scheduler import Scheduler

PER_HEAD = ("nominate.assign", "nominate.targets", "entries.admit",
            "entries.requeue")


@pytest.fixture(autouse=True)
def _clean_spans():
    obs.cycle_ledger.enabled = True
    spans.reset()
    yield
    spans.trace_off("test")
    obs.cycle_ledger.enabled = True
    spans.reset()


@pytest.fixture
def no_auto_gc():
    """Only the test's own ``gc.collect`` calls collect."""
    was = gc.isenabled()
    gc.disable()
    yield
    if was:
        gc.enable()


class _FakeAnnotation:
    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


@pytest.fixture
def fake_annotation():
    spans.trace_on("test")
    before = spans._S.annotation
    spans._S.annotation = _FakeAnnotation
    _FakeAnnotation.log = []
    yield _FakeAnnotation.log
    spans._S.annotation = before
    spans.trace_off("test")


# -- the collector ----------------------------------------------------------------


@pytest.mark.parametrize("on", ["ledger", "switch"])
def test_a_full_collection_adds_gc_and_gc_full(on, no_auto_gc):
    if on == "switch":
        obs.cycle_ledger.enabled = False
        spans.trace_on("test")
    with spans.span("outer"):
        gc.collect(2)
    t = spans.totals()
    assert t["gc"]["n"] == 1 and t["gc.full"]["n"] == 1
    assert t["gc"]["s"] > 0
    assert t["gc.full"]["s"] == pytest.approx(t["gc"]["s"])
    # totals only: the enclosing span keeps the collection in its self time
    assert t["outer"]["self_s"] == pytest.approx(t["outer"]["s"])
    assert t["outer"]["s"] >= t["gc"]["s"]
    # a young collection is a ``gc``, not a ``gc.full``
    gc.collect(0)
    t = spans.totals()
    assert t["gc"]["n"] == 2 and t["gc.full"]["n"] == 1


def test_nothing_recording_adds_nothing(no_auto_gc):
    obs.cycle_ledger.enabled = False
    assert not spans.tracing()
    gc.collect(2)
    assert spans.totals() == {}
    # the hook returns at once: no clock reading, no annotation left open
    spans._on_gc("start", {"generation": 2})
    assert spans._S.gc_t0 == 0 and spans._S.gc_ann is None
    spans._on_gc("stop", {"generation": 2})
    assert spans.totals() == {}


def test_a_traced_collection_of_generation_1_or_2_is_one_annotation(
        no_auto_gc, fake_annotation):
    gc.collect(2)
    assert fake_annotation == [("enter", "kueue:gc"), ("exit", "kueue:gc")]
    fake_annotation.clear()
    gc.collect(0)
    assert fake_annotation == []
    gc.collect(1)
    assert fake_annotation == [("enter", "kueue:gc"), ("exit", "kueue:gc")]
    assert spans._S.gc_ann is None
    # totals only: no ring entry for a collection
    assert all(r[1] != "gc" for r in spans.ring())
    assert spans.totals()["gc"]["n"] == 3


def test_no_annotation_once_the_switch_is_off(no_auto_gc, fake_annotation):
    spans.trace_off("test")
    gc.collect(2)
    assert fake_annotation == []
    assert spans.totals()["gc.full"]["n"] == 1   # the ledger is still on
    assert spans.ring() == []


# -- the flight recorder ------------------------------------------------------------


def test_recorder_counts_the_events_it_stores():
    rec = obs.FlightRecorder(max_events=4)
    for i in range(7):
        rec.record(obs.SKIPPED, f"ns/w{i % 3}", cycle=i,
                   reason="no fit", reason_slug="no_fit")
    rec.record(obs.SOLVER_FALLBACK, obs.CYCLE_SCOPE, cycle=8)
    t = spans.totals()["recorder"]
    assert t["n"] == 8 and t["s"] > 0
    # every event was stored: the ring keeps the newest four of them
    assert [ev.seq for ev in rec.events()] == [5, 6, 7, 8]
    assert sum(len(rec.explain(f"ns/w{i}")) for i in range(3)) == 7


def test_a_disabled_recorder_is_not_timed():
    rec = obs.FlightRecorder()
    rec.enabled = False
    assert rec.record(obs.ASSIGNED, "ns/w") is None
    assert "recorder" not in spans.totals()


# -- host cycles, per head -------------------------------------------------------------


def _two_queues():
    store = Store()
    store.upsert_resource_flavor(ResourceFlavor(name="f"))
    for name, nominal in (("cq0", 4), ("cq1", 2)):
        store.upsert_cluster_queue(ClusterQueue(
            name=name,
            preemption=PreemptionPolicy(
                within_cluster_queue=PreemptionPolicyValue.LOWER_PRIORITY),
            resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[FlavorQuotas(name="f", resources=[
                    ResourceQuota(name="cpu", nominal=nominal)])])]))
        store.upsert_local_queue(LocalQueue(name=f"lq-{name}",
                                            cluster_queue=name))
    return store


def _submit(store, name, lq, cpu, priority, t):
    store.add_workload(Workload(
        name=name, queue_name=lq, priority=priority, creation_time=t,
        podsets=[PodSet(name="main", count=1, requests={"cpu": cpu})]))


def test_per_head_totals_lie_inside_nominate_and_entries():
    store = _two_queues()
    sched = Scheduler(store, QueueManager(store))
    for i in range(4):
        _submit(store, f"low{i}", "lq-cq0", 1, 0, float(i))
    _submit(store, "small", "lq-cq1", 1, 0, 5.0)
    _submit(store, "big", "lq-cq1", 3, 0, 6.0)   # never fits: requeued
    admitted = preempted = 0
    now = 10.0
    for _ in range(6):
        stats = sched.schedule(now=now)
        admitted += stats.admitted
        preempted += stats.preempted
        now += 1.0
    # a head that has to preempt: one of the four
    _submit(store, "high", "lq-cq0", 1, 10, now)
    for _ in range(6):
        stats = sched.schedule(now=now)
        admitted += stats.admitted
        preempted += stats.preempted
        now += 1.0
    assert preempted == 1
    assert store.workloads["default/high"].is_quota_reserved
    t = spans.totals()
    for name in PER_HEAD:
        assert t[name]["n"] >= 1 and t[name]["s"] > 0, name
    assert t["entries.admit"]["n"] == admitted == 6
    assert t["entries.requeue"]["n"] == t["entries"]["n"]
    # totals only: the outside spans' self time keeps them, and the
    # inside adds up
    assert (t["nominate.assign"]["s"] + t["nominate.targets"]["s"]
            <= t["nominate"]["s"])
    assert (t["entries.admit"]["s"] + t["entries.requeue"]["s"]
            <= t["entries"]["s"])
    assert t["nominate"]["self_s"] == pytest.approx(t["nominate"]["s"])
    # the cycle's ledger rows keep their five phases
    row = obs.cycle_ledger.last_row(obs.HOST_CYCLE)
    assert not set(PER_HEAD) & set(row.phases)


def test_per_head_totals_stay_off_while_nothing_records():
    obs.cycle_ledger.enabled = False
    store = _two_queues()
    sched = Scheduler(store, QueueManager(store))
    _submit(store, "w", "lq-cq0", 1, 0, 0.0)
    assert sched.schedule(now=1.0).admitted == 1
    assert spans.totals() == {}
