"""The pending queue against the parent commit's (PR 33).

``core/queue_manager.py``'s queue says when it starts to owe a
capacity-freed flush, and reads the cycle of its last one from its
cohort root as well as from itself; everything else it does is what
``tests/pending_queue_reference.py``, the queue of the parent commit,
does. Seeded random sequences drive both with the same operations on
the same ``WorkloadInfo`` objects; after EVERY step the readers' view
has to be the same: the three dicts, the NoFit classes, the totals, the
digest, the flush cycle, each operation's return value and the order in
which ``pop_head`` would return the rows. And the one thing the manager
relies on to pass a queue by: a queue that holds a parked row not yet
owed a retry, or a NoFit class, says that it owes a flush.
"""

import random

import pytest

from kueue_oss_tpu import features
from kueue_oss_tpu.api.types import PodSet, QueueingStrategy, Workload
from kueue_oss_tpu.core.queue_manager import (
    ClusterQueuePendingQueue,
    RequeueReason,
)
from kueue_oss_tpu.core.workload_info import WorkloadInfo
from pending_queue_reference import PerRowPendingQueue

REASONS = (RequeueReason.GENERIC, RequeueReason.FAILED_AFTER_NOMINATION,
           RequeueReason.PENDING_PREEMPTION, RequeueReason.PREEMPTION_FAILED,
           RequeueReason.NAMESPACE_MISMATCH)
#: (cpu, memory or 0, priority): a handful of shapes, so classes have
#: many rows, and two that differ by priority alone
SHAPES = ((1000, 0, 50), (5000, 0, 100), (20000, 0, 200), (1000, 0, 100),
          (2000, 4096, 50), (0, 1024, 50))


@pytest.fixture(autouse=True)
def _gates():
    features.reset()
    yield
    features.reset()


def _info(rng: random.Random, i: int) -> WorkloadInfo:
    cpu, mem, prio = rng.choice(SHAPES)
    requests = {"cpu": cpu}
    if mem:
        requests["memory"] = mem
    wl = Workload(name=f"w{i}", queue_name=f"lq{i % 3}", priority=prio,
                  uid=i + 1, creation_time=float(rng.randrange(50)),
                  podsets=[PodSet(name="main", count=1, requests=requests)])
    return WorkloadInfo(wl, cluster_queue="cq")


class Pair:
    """The two queues, fed alike."""

    def __init__(self, strategy: str, afs: bool) -> None:
        self.changed = ([], [])
        self.ref = PerRowPendingQueue(
            "cq", strategy, on_change=self.changed[0].append)
        self.new = ClusterQueuePendingQueue(
            "cq", strategy, on_change=self.changed[1].append)
        #: decayed usage by LocalQueue: moves between steps, as the AFS
        #: manager's does between cycles
        self.usage = {f"lq{i}": float(i) for i in range(3)}
        if afs:
            key = lambda info: self.usage[info.obj.queue_name]  # noqa: E731
            self.ref.afs_key = self.new.afs_key = key

    def both(self, op: str, *args):
        for c in self.changed:
            c.clear()
        out = (getattr(self.ref, op)(*args), getattr(self.new, op)(*args))
        assert out[0] is out[1] or out[0] == out[1], (op, out)
        assert bool(self.changed[0]) == bool(self.changed[1]), (
            op, "pending-count change reported by one queue only")
        return out[0]

    def same(self, step) -> None:
        ref, new = self.ref, self.new
        for attr in ("_in_heap", "inadmissible", "_stale"):
            a, b = getattr(ref, attr), getattr(new, attr)
            assert type(b) is dict and a.keys() == b.keys(), (step, attr)
            assert all(a[k] is b[k] for k in a), (step, attr)
        for attr in ("no_fit_hashes", "pending_totals", "state_hash",
                     "queue_inadmissible_cycle", "pending_active",
                     "pending_inadmissible"):
            assert getattr(ref, attr) == getattr(new, attr), (step, attr)
        assert ([i.key for i in ref.snapshot_order()]
                == [i.key for i in new.snapshot_order()]), step
        fresh = new.inadmissible.keys() - new._stale.keys()
        assert new.owes_flush or not (fresh or new.no_fit_hashes), (
            step, "fresh rows or NoFit classes, and no flush owed")


def _drive(pair: Pair, rng: random.Random, steps: int) -> None:
    popped: list[WorkloadInfo] = []
    made = 0
    cycle = 0
    for step in range(steps):
        ref = pair.ref
        keys = list(ref._in_heap) + list(ref.inadmissible)
        roll = rng.random()
        if roll < 0.30 or not keys:
            # a new workload, or an update of one (another shape, a new
            # info); through PushOrUpdate's NoFit check or not
            if made and rng.random() < 0.2:
                i = rng.randrange(made)  # queued, popped or long gone
            else:
                i, made = made, made + 1
            op = ("push", _info(rng, i), rng.random() < 0.6)
        elif roll < 0.40:
            op = ("park", rng.choice(keys + ["default/nobody"]))
        elif roll < 0.58:
            head = pair.both("pop_head")
            pair.same((step, "pop_head"))
            if head is None:
                continue
            cycle += 1
            head.pop_cycle = cycle
            popped.append(head)
            continue
        elif roll < 0.76 and popped:
            info = popped.pop(rng.randrange(len(popped)))
            op = ("requeue_if_not_present", info, rng.choice(REASONS),
                  rng.choice((-1, info.pop_cycle, cycle + 1)))
        elif roll < 0.86:
            if rng.random() < 0.35:
                # the router's switch; turning it off hands the stale
                # rows back, as QueueManager.set_lazy_flush does
                lazy = not ref.lazy_flush
                pair.ref.lazy_flush = pair.new.lazy_flush = lazy
                if not lazy and rng.random() < 0.8:
                    pair.both("materialize_stale")
                    pair.same((step, "switch"))
            cycle += 1
            op = ("queue_inadmissible", cycle)
        elif roll < 0.91:
            op = ("materialize_stale",)
        elif roll < 0.97:
            op = ("delete", rng.choice(keys + ["default/nobody"]))
        elif roll < 0.985 and ref.inadmissible:
            # a parked row's priority changed in place, with no event:
            # the next flush orders it by what it reads then
            info = ref.inadmissible[rng.choice(list(ref.inadmissible))]
            info.obj.priority = rng.choice((50, 100, 200))
            continue
        else:
            pair.usage[f"lq{rng.randrange(3)}"] = rng.random() * 4
            continue
        pair.both(*op)
        pair.same((step, op[0]))
    # what is left comes out in the same order
    for q in (pair.ref, pair.new):
        q.lazy_flush = False
    pair.both("queue_inadmissible", cycle + 1)
    pair.same("last flush")
    while pair.both("pop_head") is not None:
        pair.same("drain")


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("afs", (False, True), ids=("heap", "afs"))
@pytest.mark.parametrize("strategy", (QueueingStrategy.BEST_EFFORT_FIFO,
                                      QueueingStrategy.STRICT_FIFO))
def test_queue_equals_the_parent_reference(strategy, afs, seed):
    rng = random.Random(f"{strategy}-{afs}-{seed}")
    _drive(Pair(strategy, afs), rng, 600)


@pytest.mark.parametrize("seed", range(3))
def test_without_equivalence_hashing_no_class_is_parked(seed):
    features.set_gates({"SchedulingEquivalenceHashing": False})
    pair = Pair(QueueingStrategy.BEST_EFFORT_FIFO, afs=False)
    _drive(pair, random.Random(f"gate-off-{seed}"), 400)
    assert not pair.new.no_fit_hashes
