"""In-memory object store — the control-plane state backing.

The reference persists all state as CRDs in etcd behind an apiserver
(SURVEY.md §5 checkpoint/resume: the store is the only source of truth, and
caches rebuild from watches). Here the store is an in-process dict-of-objects
with the same contract: everything durable lives on the objects' status; the
scheduler and controllers read/write through it, and watchers can subscribe
to change events.
"""

from __future__ import annotations

import threading
from typing import Callable, Iterable, Optional

from kueue_oss_tpu.api.types import (
    AdmissionCheck,
    ClusterQueue,
    Cohort,
    LocalQueue,
    Node,
    ResourceFlavor,
    Topology,
    Workload,
    WorkloadPriorityClass,
)
from kueue_oss_tpu.obs import spans

Event = tuple[str, str, object]  # (verb, kind, obj)


class Store:
    def __init__(self) -> None:
        self._lock = threading.RLock()
        self.cluster_queues: dict[str, ClusterQueue] = {}
        self.cohorts: dict[str, Cohort] = {}
        self.local_queues: dict[str, LocalQueue] = {}  # key "ns/name"
        self.resource_flavors: dict[str, ResourceFlavor] = {}
        self.topologies: dict[str, Topology] = {}
        self.admission_checks: dict[str, AdmissionCheck] = {}
        self.priority_classes: dict[str, WorkloadPriorityClass] = {}
        self.workloads: dict[str, Workload] = {}  # key "ns/name"
        self.nodes: dict[str, Node] = {}
        self.namespaces: dict[str, dict[str, str]] = {"default": {}}
        #: bumped whenever a CQ's quota config changes; invalidates flavor cursors
        self.cq_generation: dict[str, int] = {}
        self._watchers: list[Callable[[Event], None]] = []
        #: index of workloads currently holding quota, maintained on every
        #: workload write so per-cycle snapshot builds are O(admitted), not
        #: O(all workloads) (the reference keeps admitted usage in a
        #: dedicated cache fed by watches, pkg/cache/scheduler/cache.go)
        self._admitted: dict[str, Workload] = {}
        #: cached WorkloadInfo for admitted workloads; invalidated on write
        self._admitted_infos: dict[str, object] = {}
        #: keys whose FINISHED transition was counted into the
        #: retained-finished gauges (see _track_finished)
        self._finished_counted: set[str] = set()
        #: cloned/simulation stores must not touch the process-wide
        #: metric registry (Store.clone sets this False)
        self._metrics_enabled = True
        #: generation of the global request-shaping config (LimitRanges /
        #: resource transformations) the info cache was computed under
        self._info_cache_gen = -1
        #: persist.PersistenceManager wired by attach(); the scheduler
        #: and solver engine write decision intents / cycle-end flushes
        #: through this handle (docs/DURABILITY.md). None = volatile
        #: store (clones, simulations, tests).
        self.persistence = None

    def clone(self) -> "Store":
        """Deep copy of all objects into a fresh Store — no watchers, a
        new lock (dry-run scheduling, restart/rebuild tests)."""
        import copy

        with self._lock:
            out = Store()
            out._metrics_enabled = False
            out.namespaces = copy.deepcopy(self.namespaces)
            for cohort in self.cohorts.values():
                out.upsert_cohort(copy.deepcopy(cohort))
            for rf in self.resource_flavors.values():
                out.upsert_resource_flavor(copy.deepcopy(rf))
            for t in self.topologies.values():
                out.upsert_topology(copy.deepcopy(t))
            for ac in self.admission_checks.values():
                out.upsert_admission_check(copy.deepcopy(ac))
            for pc in self.priority_classes.values():
                out.upsert_priority_class(copy.deepcopy(pc))
            for cq in self.cluster_queues.values():
                out.upsert_cluster_queue(copy.deepcopy(cq))
            for lq in self.local_queues.values():
                out.upsert_local_queue(copy.deepcopy(lq))
            for node in self.nodes.values():
                out.upsert_node(copy.deepcopy(node))
            for wl in self.workloads.values():
                out.add_workload(copy.deepcopy(wl))
            out.cq_generation = dict(self.cq_generation)
            return out

    # -- watch -------------------------------------------------------------

    def watch(self, fn: Callable[[Event], None]) -> None:
        self._watchers.append(fn)

    def _emit(self, verb: str, kind: str, obj: object) -> None:
        for fn in self._watchers:
            fn((verb, kind, obj))

    # -- writers -----------------------------------------------------------

    def upsert_cluster_queue(self, cq: ClusterQueue) -> None:
        with self._lock:
            verb = "update" if cq.name in self.cluster_queues else "add"
            self.cluster_queues[cq.name] = cq
            self.cq_generation[cq.name] = self.cq_generation.get(cq.name, 0) + 1
        self._emit(verb, "ClusterQueue", cq)

    def delete_cluster_queue(self, name: str) -> Optional[ClusterQueue]:
        with self._lock:
            cq = self.cluster_queues.pop(name, None)
            self.cq_generation.pop(name, None)
        if cq is not None:
            self._emit("delete", "ClusterQueue", cq)
        return cq

    def delete_local_queue(self, key: str) -> Optional[LocalQueue]:
        with self._lock:
            lq = self.local_queues.pop(key, None)
        if lq is not None:
            self._emit("delete", "LocalQueue", lq)
        return lq

    def upsert_cohort(self, cohort: Cohort) -> None:
        from kueue_oss_tpu import features

        if cohort.parent and not features.enabled("HierarchicalCohorts"):
            # flat cohorts only when the gate is off (KEP-79); store a
            # flat copy, never mutate the caller's object
            import dataclasses

            cohort = dataclasses.replace(cohort, parent=None)
        with self._lock:
            self.cohorts[cohort.name] = cohort
        self._emit("update", "Cohort", cohort)

    def upsert_local_queue(self, lq: LocalQueue) -> None:
        with self._lock:
            self.local_queues[lq.key] = lq
        self._emit("update", "LocalQueue", lq)

    def upsert_resource_flavor(self, rf: ResourceFlavor) -> None:
        with self._lock:
            self.resource_flavors[rf.name] = rf
        self._emit("update", "ResourceFlavor", rf)

    def upsert_topology(self, t: Topology) -> None:
        with self._lock:
            self.topologies[t.name] = t
        self._emit("update", "Topology", t)

    def upsert_admission_check(self, ac: AdmissionCheck) -> None:
        with self._lock:
            self.admission_checks[ac.name] = ac
        self._emit("update", "AdmissionCheck", ac)

    def upsert_priority_class(self, pc: WorkloadPriorityClass) -> None:
        with self._lock:
            self.priority_classes[pc.name] = pc
        self._emit("update", "WorkloadPriorityClass", pc)

    def upsert_node(self, node: Node) -> None:
        with self._lock:
            self.nodes[node.name] = node
        self._emit("update", "Node", node)

    def delete_node(self, name: str) -> None:
        with self._lock:
            node = self.nodes.pop(name, None)
        if node is not None:
            self._emit("delete", "Node", node)

    def add_workload(self, wl: Workload) -> None:
        t0 = spans.start()   # per event: totals only (obs/spans.py)
        with self._lock:
            if wl.priority_class and wl.priority == 0:
                pc = self.priority_classes.get(wl.priority_class)
                if pc is not None:
                    wl.priority = pc.value
            wl.resource_version += 1
            self.workloads[wl.key] = wl
            self._index_workload(wl)
            self._track_finished(wl)
        # the watchers run here, the queue manager's handler among them
        self._emit("add", "Workload", wl)
        spans.add_since("store.add", t0)

    def update_workload(self, wl: Workload) -> None:
        with self._lock:
            wl.resource_version += 1
            self.workloads[wl.key] = wl
            self._index_workload(wl)
            self._track_finished(wl)
        self._emit("update", "Workload", wl)

    def update_workload_if(self, wl: Workload, expected_rv: int) -> bool:
        """Atomic conditional write: lands only if the stored object
        still exists at exactly `expected_rv` (the apiserver's
        optimistic-concurrency precondition; backs the client's
        merge-patch path). Returns False on conflict or deletion —
        never resurrects a concurrently deleted workload."""
        with self._lock:
            live = self.workloads.get(wl.key)
            if live is None or live.resource_version != expected_rv:
                return False
            wl.resource_version = expected_rv + 1
            self.workloads[wl.key] = wl
            self._index_workload(wl)
            self._track_finished(wl)
        self._emit("update", "Workload", wl)
        return True

    def _track_finished(self, wl: Workload) -> None:
        """The retained-finished gauges count workloads whose FINISHED
        condition is true and that still exist in the store. Tracking
        the transition HERE (the single write choke point) keeps inc/dec
        balanced regardless of which component set the condition
        (scheduler, MultiKueue copy-back, slice replacement)."""
        if wl.is_finished and wl.key not in self._finished_counted:
            self._finished_counted.add(wl.key)
            self._finished_gauges(wl, +1)

    def _finished_gauges(self, wl: Workload, delta: int) -> None:
        if not self._metrics_enabled:
            return
        from kueue_oss_tpu import metrics

        cq = (wl.status.admission.cluster_queue
              if wl.status.admission is not None
              else self.cluster_queue_for(wl))
        if cq:
            metrics.finished_workloads_gauge.inc(cq, by=delta)
            if metrics._lq_metrics_enabled():
                metrics.local_queue_finished_workloads_gauge.inc(
                    wl.queue_name, wl.namespace, by=delta)

    def delete_workload(self, key: str) -> Optional[Workload]:
        with self._lock:
            wl = self.workloads.pop(key, None)
            self._admitted.pop(key, None)
            self._admitted_infos.pop(key, None)
            counted = key in self._finished_counted
            self._finished_counted.discard(key)
        if wl is not None:
            if counted:
                # shed the retained-finished sample on ANY deletion path
                # (retention GC, job deletion, slices)
                self._finished_gauges(wl, -1)
            self._emit("delete", "Workload", wl)
        return wl

    def _index_workload(self, wl: Workload) -> None:
        if wl.is_quota_reserved and not wl.is_finished:
            self._admitted[wl.key] = wl
        else:
            self._admitted.pop(wl.key, None)
        # The cached info reflects pre-write state; rebuild lazily.
        self._admitted_infos.pop(wl.key, None)

    # -- readers -----------------------------------------------------------

    def cluster_queues_using_flavor(self, flavor_name: str) -> list[str]:
        """Sorted ClusterQueues whose resource groups reference the
        flavor (shared by kueuectl describe/list and the dashboard)."""
        return sorted(
            cq.name for cq in self.cluster_queues.values()
            if any(fq.name == flavor_name for rg in cq.resource_groups
                   for fq in rg.flavors))

    def cluster_queue_for(self, wl: Workload) -> Optional[str]:
        lq = self.local_queues.get(f"{wl.namespace}/{wl.queue_name}")
        return lq.cluster_queue if lq is not None else None

    def admitted_workloads(self) -> Iterable[Workload]:
        """Workloads holding quota (reserved and not finished)."""
        return list(self._admitted.values())

    def admitted_infos(self) -> list:
        """Cached WorkloadInfo for every admitted workload.

        The cache is invalidated per workload on write and wholesale when
        the request-shaping config (LimitRanges, transformations) changes,
        so repeated snapshot builds don't recompute effective requests.
        """
        from kueue_oss_tpu.core import workload_info as wli

        with self._lock:
            gen = wli.requests_config_generation()
            if gen != self._info_cache_gen:
                self._admitted_infos.clear()
                self._info_cache_gen = gen
            out = []
            for key, wl in self._admitted.items():
                info = self._admitted_infos.get(key)
                if info is None:
                    # Usage is charged to the CQ recorded in the admission,
                    # not the LocalQueue's current target (workload.go:299).
                    if wl.status.admission is not None:
                        info = wli.WorkloadInfo(
                            wl,
                            cluster_queue=wl.status.admission.cluster_queue)
                        self._admitted_infos[key] = info
                    else:
                        # No recorded admission: the CQ comes from the
                        # LocalQueue, which may be repointed at any time —
                        # resolve fresh every call, never cache.
                        cq_name = self.cluster_queue_for(wl)
                        if cq_name is None:
                            continue
                        info = wli.WorkloadInfo(wl, cluster_queue=cq_name)
                out.append(info)
            return out
