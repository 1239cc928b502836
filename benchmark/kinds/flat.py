"""The kind ``flat``: upstream's generator shape, and its plain reference.

One ResourceFlavor (``default``), one resource, cohorts of equal
ClusterQueues, no fair sharing, no topology; one podset of count 1 per
workload. A configuration file of this kind holds ``cohorts``,
``cqs_per_cohort``, ``nominal``, ``borrowing_limit``,
``reclaim_within_cohort``, ``within_cluster_queue``, ``classes`` (name,
count, request, priority, runtime_ms, creation_interval_ms: all per
ClusterQueue) and ``guarantees``. The interface is ``kinds/__init__``'s.

Copied from ``kueue_oss_tpu/perf/generator.py`` (which has no seed and
lives inside the program) so that a later PR to the program cannot move
the yardstick. What the seed does: the i-th workload of a class is due
somewhere inside its i-th creation interval, and WHICH ClusterQueue
gets which point of that interval is the seed's permutation. Every seed
therefore plays the same multiset of arrival times, sizes, priorities
and runtimes; only their assignment to queues differs.

**The plain reference** (``audit``) imports nothing of the program and
takes nothing it has made: its inputs are the configuration file, the
schedule as plain data (``Arrival``) and the driver's pass log (which
events each pass applied, and which workloads gained or lost a quota
reservation between the end of the pass before and the end of this
one). It keeps its own books (who holds quota, usage per ClusterQueue
and cohort, who waits) and holds every pass to what the configuration
states:

``over_quota``     a ClusterQueue above nominal + borrowingLimit, or a
                   cohort above the sum of its queues' nominal quota,
                   at the end of a pass;
``bad_evictions``  a workload that lost its reservation without
                   finishing, where neither a workload of higher
                   priority got a reservation in its own queue in that
                   pass (withinClusterQueue: LowerPriority) nor its
                   queue could have been above nominal in that pass
                   (reclaimWithinCohort: Any takes from borrowers);
``starved``        a pending workload that fits into the free quota of
                   its queue and cohort when a pass has gone quiet;
``below_nominal``  a pending workload that fits into its own queue's
                   nominal quota when a pass has gone quiet: nominal
                   quota is the queue's to reclaim from borrowers
                   (reclaimWithinCohort: Any), whatever their priority;
``inversions``     a workload seated in a pass while one of higher
                   priority waits in the same queue that would fit in
                   its place (its request within the seated one's plus
                   the free quota);
``ghosts``         a reservation for a workload that has not arrived or
                   already holds one, or a loss by one that held none.

Every count has the limit 0: the kernels are integer, the comparison is
exact.
"""

from __future__ import annotations

from collections import Counter, defaultdict
from dataclasses import dataclass

import numpy as np

REQUIRED = ("cohorts", "cqs_per_cohort", "nominal", "borrowing_limit",
            "classes", "guarantees")


def load(cfg: dict) -> dict:
    for key in REQUIRED:
        if key not in cfg:
            raise ValueError(f"configs/{cfg.get('name')}.json lacks {key!r}")
    return cfg


def scaled(cfg: dict, cohorts: int | None, cqs_per_cohort: int | None,
           count_div: int) -> dict:
    """A smaller copy for the CPU rehearsal and the tests; a measurement
    run never calls this."""
    out = dict(cfg)
    if cohorts:
        out["cohorts"] = cohorts
    if cqs_per_cohort:
        out["cqs_per_cohort"] = cqs_per_cohort
    if count_div > 1:
        out["classes"] = [
            {**c, "count": max(1, c["count"] // count_div)}
            for c in cfg["classes"]]
    return out


@dataclass(frozen=True)
class Arrival:
    """One workload of the schedule, as plain data (the reference reads
    these, the program gets Workload objects built from them)."""

    key: str
    name: str
    cq: str
    cohort: str
    klass: str
    request: int
    priority: int
    runtime_s: float
    due_s: float


def cq_names(cfg: dict) -> list[tuple[str, str]]:
    return [(f"cq-{ci}-{qi}", f"cohort-{ci}")
            for ci in range(cfg["cohorts"])
            for qi in range(cfg["cqs_per_cohort"])]


def schedule(cfg: dict, seed: int) -> list[Arrival]:
    """The arrival schedule, sorted by due time."""
    rng = np.random.default_rng(seed)
    cqs = cq_names(cfg)
    n = len(cqs)
    #: the fixed points of a creation interval that the queues share out
    lattice = (np.arange(n) + 0.5) / n
    out: list[Arrival] = []
    for wc in cfg["classes"]:
        interval_s = wc["creation_interval_ms"] / 1000.0
        for i in range(wc["count"]):
            frac = lattice[rng.permutation(n)]
            for (cq, cohort), u in zip(cqs, frac):
                name = f"{wc['name']}-{cq}-{i}"
                out.append(Arrival(
                    key=f"default/{name}", name=name, cq=cq, cohort=cohort,
                    klass=wc["name"], request=int(wc["request"]),
                    priority=int(wc["priority"]),
                    runtime_s=wc["runtime_ms"] / 1000.0,
                    due_s=float((i + u) * interval_s)))
    out.sort(key=lambda a: (a.due_s, a.key))
    return out


def top_class(cfg: dict) -> str:
    return max(cfg["classes"], key=lambda c: c["priority"])["name"]


def build_store(cfg: dict):
    """The program's Store holding the deployment (no workloads)."""
    from kueue_oss_tpu.api.types import (
        ClusterQueue,
        Cohort,
        FlavorQuotas,
        LocalQueue,
        PreemptionPolicy,
        ResourceFlavor,
        ResourceGroup,
        ResourceQuota,
    )
    from kueue_oss_tpu.core.store import Store

    res = cfg.get("resource", "cpu")
    store = Store()
    store.upsert_resource_flavor(ResourceFlavor(name="default"))
    seen = set()
    for cq, cohort in cq_names(cfg):
        if cohort not in seen:
            seen.add(cohort)
            store.upsert_cohort(Cohort(name=cohort))
        store.upsert_cluster_queue(ClusterQueue(
            name=cq, cohort=cohort,
            preemption=PreemptionPolicy(
                reclaim_within_cohort=cfg["reclaim_within_cohort"],
                within_cluster_queue=cfg["within_cluster_queue"]),
            resource_groups=[ResourceGroup(
                covered_resources=[res],
                flavors=[FlavorQuotas(name="default", resources=[
                    ResourceQuota(
                        name=res,
                        nominal=cfg["nominal"],
                        borrowing_limit=cfg["borrowing_limit"])])])]))
        store.upsert_local_queue(
            LocalQueue(name=f"lq-{cq}", cluster_queue=cq))
    return store


def make_workload(a: Arrival, cfg: dict):
    from kueue_oss_tpu.api.types import PodSet, Workload

    return Workload(
        name=a.name, queue_name=f"lq-{a.cq}", priority=a.priority,
        creation_time=a.due_s,
        podsets=[PodSet(count=1, requests={
            cfg.get("resource", "cpu"): a.request})])


def scheduler_options(cfg: dict) -> dict:
    return {}


class Books:
    def __init__(self, cfg: dict, arrivals) -> None:
        self.nominal = int(cfg["nominal"])
        self.cq_cap = self.nominal + int(cfg["borrowing_limit"])
        self.within_cq = cfg["within_cluster_queue"]
        self.reclaim = cfg["reclaim_within_cohort"]
        self.by_key = {a.key: a for a in arrivals}
        self.cohort_of = {a.cq: a.cohort for a in arrivals}
        n_cq = Counter(self.cohort_of.values())
        self.cohort_cap = {c: n * self.nominal for c, n in n_cq.items()}
        self.cq_use: dict = defaultdict(int)
        self.cohort_use: dict = defaultdict(int)
        self.holding: set = set()
        self.finished: set = set()
        #: cq -> (request, priority) -> how many wait
        self.waiting: dict = defaultdict(Counter)
        self.counts = {"over_quota": 0, "bad_evictions": 0, "starved": 0,
                       "below_nominal": 0, "inversions": 0, "ghosts": 0}
        self.first: dict = {}
        self.passes = 0

    def _note(self, what: str, pass_no: int, detail) -> None:
        self.counts[what] += 1
        self.first.setdefault(what, {"pass": pass_no, "detail": detail})

    def arrive(self, key: str) -> None:
        a = self.by_key[key]
        self.waiting[a.cq][(a.request, a.priority)] += 1

    def _wait(self, a, d: int) -> None:
        c = self.waiting[a.cq]
        k = (a.request, a.priority)
        c[k] += d
        if c[k] <= 0:
            del c[k]

    def apply_pass(self, rec: dict) -> None:
        """``rec``: events [(kind, key, due_s)], added [keys], removed
        [keys]: the pass log's own fields."""
        n = self.passes
        self.passes += 1
        finished_now = set()
        for kind, key, _due in rec["events"]:
            if kind == "arrive":
                self.arrive(key)
            else:
                finished_now.add(key)
        use_before = dict(self.cq_use)
        added = [self.by_key[k] for k in rec["added"] if k in self.by_key]
        if len(added) != len(rec["added"]):
            self._note("ghosts", n, "reservation for an unknown workload")
        gained = defaultdict(int)
        top_added = defaultdict(lambda: -1)
        for a in added:
            gained[a.cq] += a.request
            top_added[a.cq] = max(top_added[a.cq], a.priority)
        for key in rec["removed"]:
            a = self.by_key.get(key)
            if a is None or key not in self.holding:
                self._note("ghosts", n, f"{key} lost what it did not hold")
                continue
            self.holding.discard(key)
            self.cq_use[a.cq] -= a.request
            self.cohort_use[a.cohort] -= a.request
            if key in finished_now:
                self.finished.add(key)
                continue
            # evicted: it waits again, and the policy has to allow it
            self._wait(a, +1)
            in_queue = (self.within_cq == "LowerPriority"
                        and top_added[a.cq] > a.priority)
            borrowed = (self.reclaim == "Any"
                        and use_before.get(a.cq, 0) + gained[a.cq]
                        > self.nominal)
            if not (in_queue or borrowed):
                self._note("bad_evictions", n, key)
        for key in finished_now - self.finished:
            # finished without holding quota at the end of the pass
            # before: it held none (reserved and finished inside one
            # pass cannot happen: a finish is due after the reservation
            # was seen), so it was a finish of a waiting workload
            a = self.by_key.get(key)
            if a is not None:
                self._note("ghosts", n, f"{key} finished while waiting")
        for a in added:
            if a.key in self.holding or a.key in self.finished:
                self._note("ghosts", n, f"{a.key} reserved twice")
                continue
            self.holding.add(a.key)
            self._wait(a, -1)
            self.cq_use[a.cq] += a.request
            self.cohort_use[a.cohort] += a.request
        for cq, used in self.cq_use.items():
            if used > self.cq_cap:
                self._note("over_quota", n, f"{cq}: {used} > {self.cq_cap}")
        for cohort, used in self.cohort_use.items():
            if used > self.cohort_cap[cohort]:
                self._note("over_quota", n,
                           f"{cohort}: {used} > {self.cohort_cap[cohort]}")
        added_in: dict = defaultdict(list)
        for a in added:
            added_in[a.cq].append(a)
        for cq, sizes in self.waiting.items():
            if not sizes:
                continue
            cohort = self.cohort_of[cq]
            free = min(self.cq_cap - self.cq_use[cq],
                       self.cohort_cap[cohort] - self.cohort_use[cohort])
            least = min(r for r, _p in sizes)
            if least <= free:
                self._note("starved", n,
                           f"{cq}: a request of {least} waits, "
                           f"{free} free")
            if (self.reclaim == "Any"
                    and self.cq_use[cq] + least <= self.nominal):
                self._note("below_nominal", n,
                           f"{cq}: a request of {least} waits, the queue "
                           f"uses {self.cq_use[cq]} of {self.nominal}")
            for a in added_in.get(cq, ()):
                if a.key in self.holding and any(
                        p > a.priority and r <= a.request + free
                        for r, p in sizes):
                    self._note("inversions", n,
                               f"{a.key} seated, a higher priority "
                               f"waits in {cq} that fits in its place")

    def result(self) -> dict:
        return {"counts": dict(self.counts), "first": self.first,
                "passes": self.passes, "holding": len(self.holding),
                "finished": len(self.finished)}


def audit(cfg: dict, arrivals, preloaded, pass_log) -> dict:
    """Replay the whole log; ``preloaded`` are the keys put into the
    store in set-up (due before the window opened)."""
    books = Books(cfg, arrivals)
    for key in preloaded:
        books.arrive(key)
    for rec in pass_log:
        books.apply_pass(rec)
    return books.result()


def double_nominal(cfg: dict) -> dict:
    """The program is given twice the nominal quota the configuration
    states, and the reference holds it to the stated one."""
    return {**cfg, "nominal": 2 * cfg["nominal"]}


#: name -> (the deployment the program gets, the count that then has
#: to read above 0)
controls = {"double_nominal": (double_nominal, "over_quota")}
