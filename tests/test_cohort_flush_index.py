"""The capacity-freed flush finds its cohort by an index and visits only
the queues that owe a flush (PR 33, ``core/queue_manager.py``)."""

import pytest

from kueue_oss_tpu import features, metrics
from kueue_oss_tpu.api.types import (
    ClusterQueue,
    Cohort,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_oss_tpu.core.queue_manager import QueueManager, RequeueReason
from kueue_oss_tpu.core.store import Store
from kueue_oss_tpu.obs import spans


@pytest.fixture(autouse=True)
def _reset():
    features.reset()
    metrics.reset_all()
    spans.reset()
    yield
    features.reset()
    metrics.reset_all()
    spans.reset()


def _cq(name, cohort=None):
    return ClusterQueue(
        name=name, cohort=cohort,
        resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name="f", resources=[
                ResourceQuota(name="cpu", nominal=1000)])])])


def _store(cqs, cohorts=()):
    """cqs: name -> cohort or None; cohorts: name -> parent or None."""
    store = Store()
    store.upsert_resource_flavor(ResourceFlavor(name="f"))
    for name, parent in dict(cohorts).items():
        store.upsert_cohort(Cohort(name=name, parent=parent))
    for name, cohort in cqs.items():
        store.upsert_cluster_queue(_cq(name, cohort))
        store.upsert_local_queue(LocalQueue(name=f"lq-{name}",
                                            cluster_queue=name))
    return store


def _submit(store, cq, name, cpu=100):
    wl = Workload(name=name, queue_name=f"lq-{cq}",
                  podsets=[PodSet(name="main", count=1,
                                  requests={"cpu": cpu})])
    store.add_workload(wl)
    return wl


def _park_head(queues, cq):
    """Pop cq's head as a cycle does and requeue it as NoFit."""
    q = queues.queues[cq]
    head = q.pop_head()
    head.pop_cycle = queues.cycle
    assert not queues.requeue_workload(head, RequeueReason.GENERIC)
    return head


def _walked_members(queues, cq_name):
    """What the per-row manager computed on every flush: every
    ClusterQueue of the store whose cohort chain ends where cq_name's
    does (``_cohort_members`` of the parent commit)."""
    store = queues.store
    spec = store.cluster_queues.get(cq_name)
    if spec is None or not spec.cohort:
        return [cq_name]

    def root_of(cohort_name):
        seen, cur = set(), cohort_name
        while cur not in seen:
            seen.add(cur)
            spec_c = store.cohorts.get(cur)
            if spec_c is None or not spec_c.parent:
                break
            cur = spec_c.parent
        return cur

    mine = root_of(spec.cohort)
    return [name for name, other in store.cluster_queues.items()
            if other.cohort and root_of(other.cohort) == mine]


THREE_LEVELS = dict(
    cohorts={"top": None, "mid-a": "top", "mid-b": "top", "leaf": "mid-a",
             "other": None},
    cqs={"a1": "leaf", "a2": "mid-a", "b1": "mid-b", "t1": "top",
         "o1": "other", "o2": "other", "alone": None, "ghost": "nowhere"})


def _check_index(queues):
    for name in list(queues.store.cluster_queues) + ["not-a-queue"]:
        assert sorted(queues._cohort_members(name)) == sorted(
            _walked_members(queues, name)), name


def test_three_level_tree_is_one_root():
    queues = QueueManager(_store(**THREE_LEVELS))
    _check_index(queues)
    assert sorted(queues._cohort_members("a1")) == ["a1", "a2", "b1", "t1"]
    assert queues._cohort_members("alone") == ["alone"]
    assert queues._cohort_members("ghost") == ["ghost"]


@pytest.mark.parametrize("edit", [
    "cq_moves", "cohort_gets_parent", "cq_deleted", "cq_added",
    "cohort_loses_parent", "parent_cycle"])
def test_index_follows_the_specs(edit):
    store = _store(**THREE_LEVELS)
    queues = QueueManager(store)
    _check_index(queues)
    built = queues._root_of
    queues.flush_cohort_for("a1")
    assert queues._root_of is built, "a flush rebuilt the index"
    if edit == "cq_moves":
        store.upsert_cluster_queue(_cq("b1", "other"))
        assert sorted(queues._cohort_members("o1")) == ["b1", "o1", "o2"]
    elif edit == "cohort_gets_parent":
        store.upsert_cohort(Cohort(name="other", parent="mid-b"))
        assert len(queues._cohort_members("o1")) == 6
    elif edit == "cq_deleted":
        store.delete_cluster_queue("a2")
        assert "a2" not in queues._cohort_members("a1")
    elif edit == "cq_added":
        store.upsert_cluster_queue(_cq("a3", "leaf"))
        assert "a3" in queues._cohort_members("t1")
    elif edit == "cohort_loses_parent":
        store.upsert_cohort(Cohort(name="mid-a", parent=None))
        assert sorted(queues._cohort_members("a1")) == ["a1", "a2"]
    elif edit == "parent_cycle":
        # invalid, and answered as the walk always answered it
        store.upsert_cohort(Cohort(name="top", parent="mid-a"))
    assert queues._root_of is not built
    _check_index(queues)


def test_owing_set_holds_who_parked_since_the_last_flush():
    store = _store(**THREE_LEVELS)
    queues = QueueManager(store)
    for cq in ("a1", "b1", "o1"):
        _submit(store, cq, f"w-{cq}")
    queues.cycle = 3
    _park_head(queues, "a1")
    _park_head(queues, "o1")
    root = queues._cohort_index()["a1"]
    assert list(queues._owing[root]) == ["a1"]
    assert list(queues._owing[queues._cohort_index()["o1"]]) == ["o1"]

    queues.flush_cohort_for("t1")  # a finish elsewhere in the tree
    counts = spans.counters()
    assert (counts["flush_requests"], counts["flush_queues"],
            counts["flush_rows"]) == (1, 1, 1)
    assert root not in queues._owing
    assert "default/w-a1" in queues.queues["a1"]._in_heap
    assert "default/w-o1" in queues.queues["o1"].inadmissible  # not its tree

    # a row parked after a flush puts its queue back into the set
    queues.cycle = 4
    _park_head(queues, "a1")
    assert list(queues._owing[root]) == ["a1"]
    queues.flush_cohort_for("b1")
    assert spans.counters()["flush_queues"] == 2
    assert "default/w-a1" in queues.queues["a1"]._in_heap


def test_a_skipped_member_answers_requeue_as_a_flushed_one():
    """b1 holds nothing parked, so the flush passes it by; the head it
    popped before the flush still goes back to the heap, as if b1 had
    been visited (queue_inadmissible_cycle >= pop_cycle)."""
    store = _store(**THREE_LEVELS)
    queues = QueueManager(store)
    _submit(store, "b1", "w-b1")
    _submit(store, "o1", "w-o1")
    queues.cycle = 7
    heads = {cq: queues.queues[cq].pop_head() for cq in ("b1", "o1")}
    for head in heads.values():
        head.pop_cycle = 7
    queues.flush_cohort_for("a1")
    assert spans.counters().get("flush_queues", 0) == 0
    assert queues.queues["b1"].queue_inadmissible_cycle == 7
    assert queues.queues["o1"].queue_inadmissible_cycle == -1
    assert queues.requeue_workload(heads["b1"], RequeueReason.GENERIC)
    assert not queues.requeue_workload(heads["o1"], RequeueReason.GENERIC)
    assert "default/w-b1" in queues.queues["b1"]._in_heap
    assert "default/w-o1" in queues.queues["o1"].inadmissible


def test_what_a_queue_read_of_its_root_stays_when_it_moves():
    store = _store(**THREE_LEVELS)
    queues = QueueManager(store)
    queues.cycle = 5
    queues.flush_cohort_for("a1")
    assert queues.queues["b1"].queue_inadmissible_cycle == 5
    assert queues.queues["o1"].queue_inadmissible_cycle == -1
    store.upsert_cohort(Cohort(name="other", parent="top"))  # roots merge
    assert queues.queues["b1"].queue_inadmissible_cycle == 5
    assert queues.queues["o1"].queue_inadmissible_cycle == -1
    queues.cycle = 6
    queues.flush_cohort_for("o2")
    assert queues.queues["b1"].queue_inadmissible_cycle == 6
    assert queues.queues["o1"].queue_inadmissible_cycle == 6


def test_owing_members_follow_a_changed_tree():
    store = _store(**THREE_LEVELS)
    queues = QueueManager(store)
    _submit(store, "o1", "w-o1")
    _park_head(queues, "o1")
    store.upsert_cohort(Cohort(name="other", parent="top"))
    queues.flush_cohort_for("a1")  # o1's tree now
    assert "default/w-o1" in queues.queues["o1"]._in_heap


@pytest.mark.parametrize("lazy", (False, True), ids=("eager", "lazy"))
def test_a_thousand_finishes_visit_each_owing_queue_once(lazy):
    """A count, not a timing: 1,000 ClusterQueues in 10 cohorts, 49
    parked rows each, 1,000 finishes (one from every queue). The per-row
    manager visited 100 queues a finish; this one visits a queue only
    when it owes a flush."""
    per_cohort, parked = 100, 49
    cqs = {f"cq-{c}-{i}": f"cohort-{c}"
           for c in range(10) for i in range(per_cohort)}
    store = _store(cqs, {f"cohort-{c}": None for c in range(10)})
    queues = QueueManager(store)
    for cq in cqs:
        for j in range(parked):
            _submit(store, cq, f"{cq}-w{j}")
    for cq, q in queues.queues.items():
        for key in list(q._in_heap):
            q.park(key)
    queues.set_lazy_flush(lazy)
    queues._cohort_index()  # built at the first use, from the flags
    assert sum(len(o) for o in queues._owing.values()) == 1000
    spans.reset()

    reparked = 0
    for n, cq in enumerate(cqs):
        queues.flush_cohort_for(cq)
        if n % 50 == 7:
            # a cycle in between parks a head again
            queues.cycle += 1
            q = queues.queues[cq]
            if lazy:
                q.park(next(iter(q._stale)))
            else:
                _park_head(queues, cq)
            reparked += 1
    counts = spans.counters()
    assert counts["flush_requests"] == 1000
    assert counts["flush_queues"] <= 1000 + reparked
    assert counts["flush_queues"] >= 1000
    assert counts["flush_rows"] >= 1000 * parked
    for q in queues.queues.values():
        waiting = q._stale if lazy else q._in_heap
        assert len(waiting) >= parked - 1
