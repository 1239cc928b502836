"""The plain reference of the pending queue: one Python step per row.

``ClusterQueuePendingQueue`` as it stood before PR 33 (commit 84e764a),
copied unchanged but for its name. ``tests/test_pending_queue_reference.py``
drives it beside the queue of ``core/queue_manager.py`` and requires
the same dicts, totals, digest, flush cycle and pop order after every
step: whatever that queue learns to do faster (PR 33 taught it to say
when it owes a flush; moving rows as sets is ROADMAP S3), this is what
it has to keep doing. It is a reference: keep it slow and obvious.
"""

from __future__ import annotations

import heapq
import itertools
from typing import Optional

from kueue_oss_tpu.api.types import QueueingStrategy
from kueue_oss_tpu.core.queue_manager import RequeueReason
from kueue_oss_tpu.core.workload_info import (
    WorkloadInfo,
    effective_priority,
    queue_order_timestamp,
)


def _order_key(info: WorkloadInfo) -> tuple:
    # Higher priority first, then FIFO on the eviction-aware timestamp.
    return (-effective_priority(info.obj), queue_order_timestamp(info.obj),
            info.obj.uid)


class PerRowPendingQueue:
    """Heap + inadmissible parking for one ClusterQueue."""

    def __init__(self, name: str, strategy: str,
                 on_change=None) -> None:
        self.name = name
        self.strategy = strategy
        self._heap: list[tuple[tuple, int, WorkloadInfo]] = []
        self._in_heap: dict[str, WorkloadInfo] = {}
        self._counter = itertools.count()
        self.inadmissible: dict[str, WorkloadInfo] = {}
        #: cycle at which inadmissible workloads were last re-queued
        self.queue_inadmissible_cycle = -1
        self.active = True
        #: called with the CQ name on any pending-count mutation (the
        #: manager uses it to keep a dirty set so metric reporting is
        #: O(changed CQs), not O(all CQs))
        self._on_change = on_change or (lambda name: None)
        #: admission-fair-sharing rank fn (info -> decayed LQ usage);
        #: set by the manager for CQs with UsageBasedAdmissionFairSharing
        self.afs_key = None
        #: scheduling-equivalence classes known NoFit since the last
        #: capacity-freed flush (cluster_queue.go noFitSchedulingHashes)
        self.no_fit_hashes: set = set()
        #: XOR accumulator over (key, heap|inadmissible) membership —
        #: mutated O(1) on every queue transition so run_until_quiet can
        #: detect quiescence without walking queue internals
        self.state_hash = 0
        #: solver-managed mode: capacity-freed flushes mark parked
        #: entries STALE instead of physically re-heaping them (the
        #: eager flush is O(parked) per finish — at flood scale that is
        #: millions of heap pushes per run). Stale entries are exported
        #: to the solver as pending; the host path materializes them
        #: (moves them back into the heap) before it ever schedules.
        self.lazy_flush = False
        #: entries parked before the latest capacity-freed flush
        #: (key -> info); they are schedulable-in-waiting, so they count
        #: in pending_totals like heap members
        self._stale: dict[str, WorkloadInfo] = {}
        #: per-resource request totals over heap + stale members,
        #: maintained O(requests) per transition so the metrics flush
        #: never sorts or walks the backlog
        #: (cluster_queue_resource_pending gauges)
        self.pending_totals: dict[str, int] = {}

    _HEAP, _INADM = 1, 2

    def _hx(self, key: str, state: int) -> None:
        self.state_hash ^= hash((key, state))

    def _tot(self, info: WorkloadInfo, sign: int) -> None:
        for psr in info.total_requests:
            for r, v in psr.requests.items():
                nv = self.pending_totals.get(r, 0) + sign * v
                if nv:
                    self.pending_totals[r] = nv
                else:
                    self.pending_totals.pop(r, None)

    def _stale_pop(self, key: str) -> None:
        info = self._stale.pop(key, None)
        if info is not None:
            self._tot(info, -1)

    def __len__(self) -> int:
        return len(self._heap) + len(self.inadmissible)

    @property
    def pending_active(self) -> int:
        return len(self._in_heap)

    @property
    def pending_inadmissible(self) -> int:
        return len(self.inadmissible)

    def push(self, info: WorkloadInfo, check_no_fit: bool = False) -> None:
        """Insert into the heap. With check_no_fit (the PushOrUpdate path,
        cluster_queue.go:371), a BestEffortFIFO queue parks workloads whose
        scheduling-equivalence class is already known NoFit."""
        from kueue_oss_tpu import features

        if (check_no_fit
                and self.strategy == QueueingStrategy.BEST_EFFORT_FIFO
                and info.key not in self._in_heap
                and self.no_fit_hashes
                and features.enabled("SchedulingEquivalenceHashing")
                and info.scheduling_hash() in self.no_fit_hashes):
            if info.key not in self.inadmissible:
                self._hx(info.key, self._INADM)
            self.inadmissible[info.key] = info
            self._stale_pop(info.key)  # updated shape => freshly parked
            self._on_change(self.name)
            return
        if info.key in self.inadmissible:
            del self.inadmissible[info.key]
            self._stale_pop(info.key)
            self._hx(info.key, self._INADM)
        if info.key in self._in_heap:
            # Re-push with fresh ordering (priority/timestamps may change).
            self.delete(info.key)
        self._in_heap[info.key] = info
        self._tot(info, +1)
        self._hx(info.key, self._HEAP)
        heapq.heappush(self._heap, (_order_key(info), next(self._counter), info))
        self._on_change(self.name)

    def pop_head(self) -> Optional[WorkloadInfo]:
        if self.afs_key is not None and self._in_heap:
            # Admission fair sharing: the head is the entry whose
            # LocalQueue has the lowest decayed usage (KEP-4136); the
            # static heap order is the tie-break. O(n) scan — usage decays
            # between cycles, so the rank can't be baked into the heap.
            info = min(self._in_heap.values(),
                       key=lambda i: (self.afs_key(i), _order_key(i)))
            del self._in_heap[info.key]
            self._tot(info, -1)
            self._hx(info.key, self._HEAP)
            # The AFS path never pops _heap, so stale tuples would pile up
            # forever; rebuild once they dominate (amortized O(1)).
            if len(self._heap) > 2 * len(self._in_heap):
                self._heap = [(k, c, i) for k, c, i in self._heap
                              if self._in_heap.get(i.key) is i]
                heapq.heapify(self._heap)
            self._on_change(self.name)
            return info
        while self._heap:
            _, _, info = heapq.heappop(self._heap)
            if self._in_heap.get(info.key) is info:
                del self._in_heap[info.key]
                self._tot(info, -1)
                self._hx(info.key, self._HEAP)
                self._on_change(self.name)
                return info
        return None

    def delete(self, key: str) -> None:
        live = self._in_heap.pop(key, None)
        if live is not None:
            self._tot(live, -1)
            self._hx(key, self._HEAP)
            self._on_change(self.name)
        if key in self.inadmissible:
            self._hx(key, self._INADM)
            self._on_change(self.name)
        self.inadmissible.pop(key, None)
        self._stale_pop(key)

    def snapshot_order(self) -> list[WorkloadInfo]:
        """Heap contents in pop (rank) order, without consuming them."""
        return sorted(self._in_heap.values(), key=_order_key)

    def park(self, key: str) -> None:
        """Move a heap entry to the inadmissible set (external decision).

        Re-parking an already-parked entry refreshes it: a stale entry
        the solver retried and could not admit is parked *again* (it is
        no longer owed a retry until the next capacity-freed flush)."""
        info = self._in_heap.get(key)
        if info is not None:
            self.delete(key)
            self.inadmissible[key] = info
            self._hx(key, self._INADM)
            self._on_change(self.name)
        elif key in self.inadmissible:
            self._stale_pop(key)

    def requeue_if_not_present(self, info: WorkloadInfo, reason: str,
                               pop_cycle: int = -1) -> bool:
        """Requeue semantics (reference: cluster_queue.go requeueIfNotPresent).

        StrictFIFO always goes back to the heap (the head blocks the queue).
        BestEffortFIFO parks generically-inadmissible workloads until an
        event in the cohort frees capacity; scheduling-affecting reasons go
        straight back to the heap. A capacity-freed flush that fired after
        this workload was popped (queue_inadmissible_cycle >= pop_cycle)
        also sends it to the heap, so mid-cycle events aren't lost.
        """
        if info.key in self._in_heap or info.key in self.inadmissible:
            return False
        if (self.strategy == QueueingStrategy.STRICT_FIFO
                or reason != RequeueReason.GENERIC
                or (pop_cycle >= 0
                    and self.queue_inadmissible_cycle >= pop_cycle)):
            self.push(info)
            return True
        self.inadmissible[info.key] = info
        self._hx(info.key, self._INADM)
        self._on_change(self.name)
        self._handle_inadmissible_hash(info)
        return False

    def _handle_inadmissible_hash(self, info: WorkloadInfo) -> None:
        """Record the parked workload's equivalence class as NoFit and
        bulk-move equivalent heap entries to inadmissible, so the scheduler
        never pays a nomination cycle for a shape it just rejected
        (cluster_queue.go handleInadmissibleHash, :559-575)."""
        from kueue_oss_tpu import features

        if (self.strategy != QueueingStrategy.BEST_EFFORT_FIFO
                or not features.enabled("SchedulingEquivalenceHashing")):
            return
        h = info.scheduling_hash()
        self.no_fit_hashes.add(h)
        equivalent = [k for k, i in self._in_heap.items()
                      if i.scheduling_hash() == h]
        for k in equivalent:
            self.park(k)

    def queue_inadmissible(self, cycle: int) -> bool:
        """Move all parked workloads back into the heap. Known-NoFit
        classes reset: freed capacity may fit them now
        (inadmissible_workloads.go:174).

        In solver-managed (lazy) mode the move is virtual: every parked
        entry becomes STALE in O(parked) set construction — no heap
        pushes. The solver exports stale entries as pending; the host
        path materializes them first (materialize_stale)."""
        self.no_fit_hashes.clear()
        if self.lazy_flush:
            self.queue_inadmissible_cycle = cycle
            if not self.inadmissible:
                return False
            changed = False
            for k, info in self.inadmissible.items():
                if k not in self._stale:
                    self._stale[k] = info
                    self._tot(info, +1)  # schedulable-in-waiting again
                    changed = True
            if changed:
                self._on_change(self.name)
            return True
        if not self.inadmissible:
            self.queue_inadmissible_cycle = cycle
            return False
        parked = list(self.inadmissible.values())
        self.inadmissible.clear()
        for info in parked:
            self._stale_pop(info.key)
            self._hx(info.key, self._INADM)
            self.push(info)
        self.queue_inadmissible_cycle = cycle
        self._on_change(self.name)
        return True

    def stale_infos(self) -> list[WorkloadInfo]:
        """Parked entries owed a retry since the last capacity-freed
        flush (lazy mode)."""
        return list(self._stale.values())

    def materialize_stale(self) -> bool:
        """Physically re-heap stale entries (host-path handoff)."""
        if not self._stale:
            return False
        for k in list(self._stale):
            info = self.inadmissible.pop(k, None)
            self._stale_pop(k)
            if info is not None:
                self._hx(k, self._INADM)
                self.push(info)
        self._on_change(self.name)
        return True
