"""The kind ``tas``: upstream's topology-aware deployment, and its plain
reference.

One topology of three levels (block, rack, hostname), one ResourceFlavor
over it, cohorts of equal ClusterQueues as in ``flat``; every workload
is one podset of several pods (a gang) with a rack-level topology
request. A configuration file of this kind holds ``cohorts``,
``cqs_per_cohort``, ``nominal``, ``borrowing_limit`` (milli-cpu),
``reclaim_within_cohort``, ``within_cluster_queue``, ``topology``
(``blocks``, ``racks_per_block``, ``hosts_per_rack``, ``node_cpu``,
``node_pods``), ``sizes`` (name, count, pods, cpu_per_pod, priority,
runtime_ms, creation_interval_ms: all per ClusterQueue), ``requests``
(the request types a size's workloads take in turn), ``feature_gates``
and ``guarantees``. A workload's class is ``<size>-<request>-rack``,
upstream's name. The interface is ``kinds/__init__``'s; the schedule's
rule is ``flat``'s (the i-th workload of a size is due inside its i-th
creation interval, WHICH queue gets which point of it is the seed's
permutation) and the i-th workload of a size takes the request type
``requests[i % len(requests)]``.

What a request type sends: ``required`` is ``required: <rack level>``;
``preferred`` and ``balanced`` are both ``preferred: <rack level>``,
because upstream's balanced placement has no switch on a request: while
the gate ``TASBalancedPlacement`` is on, every preferred-level request
is placed balanced (tas_flavor_snapshot.go:906-917). The two stay two
classes so that the waits are reported under upstream's names.

**The plain reference** (``audit``) imports nothing of the program. Its
inputs are the configuration file, the schedule as plain data and the
driver's pass log with what every reservation was ``given``; it keeps
quota books as ``flat`` does (a workload's usage is pods x cpu per
pod) and node books of its own (cpu and pods per host, hosts by the
racks the configuration STATES), pass by pass:

``over_quota``, ``bad_evictions``, ``below_nominal``, ``ghosts``
                  as ``flat`` counts them;
``inversions``    as ``flat``: a workload seated in a pass while one of
                  higher priority waits in the same queue that would
                  fit in its place (its request within the seated one's
                  plus the free quota, and the node books can hold its
                  gang). One exemption, for a seat demonstrably got by
                  a reclaim in that pass: where the seated workload's
                  queue ends the pass inside its nominal quota and
                  borrowers of other queues of the cohort were evicted
                  in the pass (the evictions ``bad_evictions`` accepts
                  as reclaims), the quota those evictions freed does not
                  count as free for a waiting workload that would not
                  fit inside the nominal quota in the seated one's
                  place. Such a workload has to borrow, a borrower may
                  preempt nobody (flavorassigner.go:1071-1108), so
                  without the reclaim, which only the seated one could
                  make, there was no room for it. ``flat``'s rule
                  unexempted is counted beside it and reported with the
                  first notes (``inversions_unexempted``), never
                  compared: with gangs of 10 and 100 cpu on queues of 20
                  a ``medium`` reclaims its queue's nominal quota from a
                  borrowing ``large`` of 100 while its own queue's
                  ``large`` waits, and 10 + the 90 left over read as
                  room for that one (PERF.md section 6 has the counts);
``starved``       a pending workload, when a pass has gone quiet, that
                  fits into the free quota of its queue and cohort AND
                  whose gang the node books can hold as its request
                  demands (``required``: inside one rack; else anywhere);
``node_over``     a host above its cpu or its pods at the end of a pass;
``rack_split``    a ``required`` reservation in more than one rack;
``rack_spread``   a ``preferred`` or ``balanced`` reservation in more
                  than one rack although some rack had enough hosts with
                  NOTHING on them to hold the whole gang. What such a
                  request is guaranteed, in words: the fewest racks that
                  could have held it. A pass log can show that only
                  where the fewest is one and the books are certain: the
                  reference charges every reservation of the pass so far
                  and frees only what finished before the pass began
                  (preemptions inside a pass can only have freed more),
                  and asks for hosts that are wholly empty, which
                  balanced placement's pruning (hosts under the balance
                  threshold count for nothing) cannot discount. A count
                  of racks above one is not sound from a log: pruning
                  may lawfully take a rack more than the plain minimum,
                  and WHICH hosts were free at the moment of a placement
                  inside a pass is not in the log. That part, domains
                  and counts, is held by the tier-1 parity tests
                  (tests/test_tas_kernel_balanced.py);
``pods_mismatch`` a reservation that is not one podset of the gang's
                  pods charged pods x cpu per pod to the stated flavor,
                  or whose topology is missing, names a host the
                  configuration has not, or does not add up to its pods.

Every count has the limit 0.
"""

from __future__ import annotations

from collections import Counter, defaultdict
from dataclasses import dataclass

import numpy as np

REQUIRED = ("cohorts", "cqs_per_cohort", "nominal", "borrowing_limit",
            "reclaim_within_cohort", "within_cluster_queue", "topology",
            "sizes", "requests", "guarantees")
REQUESTS = ("required", "preferred", "balanced")
BLOCK = "cloud.provider.com/topology-block"
RACK = "cloud.provider.com/topology-rack"
HOST = "kubernetes.io/hostname"
FLAVOR = "tas-flavor"
TOPOLOGY = "default"
#: every pass-log record lists what each reservation was given
GIVEN = True


def load(cfg: dict) -> dict:
    for key in REQUIRED:
        if key not in cfg:
            raise ValueError(f"configs/{cfg.get('name')}.json lacks {key!r}")
    unknown = sorted(set(cfg["requests"]) - set(REQUESTS))
    if unknown:
        raise ValueError(f"configs/{cfg.get('name')}.json: request types "
                         f"{unknown} (known: {list(REQUESTS)})")
    return cfg


def scaled(cfg: dict, cohorts: int | None, cqs_per_cohort: int | None,
           count_div: int) -> dict:
    """A smaller copy for the CPU rehearsal and the tests: fewer queues
    and workloads as asked, and with ``count_div`` above 1 racks of 8
    hosts (the tree a CPU compiles the placer for); a measurement run
    never calls this."""
    out = dict(cfg)
    if cohorts:
        out["cohorts"] = cohorts
    if cqs_per_cohort:
        out["cqs_per_cohort"] = cqs_per_cohort
    if count_div > 1:
        out["sizes"] = [{**s, "count": max(1, s["count"] // count_div)}
                        for s in cfg["sizes"]]
        out["topology"] = {**cfg["topology"], "hosts_per_rack": min(
            8, cfg["topology"]["hosts_per_rack"])}
    return out


@dataclass(frozen=True)
class Arrival:
    """One workload of the schedule, as plain data."""

    key: str
    name: str
    cq: str
    cohort: str
    klass: str
    mode: str           # required | preferred | balanced
    pods: int
    cpu_per_pod: int
    request: int        # pods x cpu_per_pod: what the quota is charged
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
    lattice = (np.arange(n) + 0.5) / n
    modes = list(cfg["requests"])
    out: list[Arrival] = []
    for size in cfg["sizes"]:
        interval_s = size["creation_interval_ms"] / 1000.0
        for i in range(size["count"]):
            mode = modes[i % len(modes)]
            klass = f"{size['name']}-{mode}-rack"
            frac = lattice[rng.permutation(n)]
            for (cq, cohort), u in zip(cqs, frac):
                name = f"{klass}-{cq}-{i}"
                out.append(Arrival(
                    key=f"default/{name}", name=name, cq=cq, cohort=cohort,
                    klass=klass, mode=mode, pods=int(size["pods"]),
                    cpu_per_pod=int(size["cpu_per_pod"]),
                    request=int(size["pods"]) * int(size["cpu_per_pod"]),
                    priority=int(size["priority"]),
                    runtime_s=size["runtime_ms"] / 1000.0,
                    due_s=float((i + u) * interval_s)))
    out.sort(key=lambda a: (a.due_s, a.key))
    return out


def top_class(cfg: dict) -> str:
    top = max(cfg["sizes"], key=lambda s: s["priority"])["name"]
    return f"{top}-{cfg['requests'][0]}-rack"


def nodes(cfg: dict) -> list[tuple[str, str, str]]:
    """(block, rack, host) of every node the configuration states."""
    t = cfg["topology"]
    return [(f"b{b}", f"b{b}-r{r}", f"b{b}-r{r}-h{h}")
            for b in range(t["blocks"])
            for r in range(t["racks_per_block"])
            for h in range(t["hosts_per_rack"])]


def build_store(cfg: dict):
    """The program's Store holding the deployment (no workloads)."""
    from kueue_oss_tpu.api.types import (
        ClusterQueue,
        Cohort,
        FlavorQuotas,
        LocalQueue,
        Node,
        PreemptionPolicy,
        ResourceFlavor,
        ResourceGroup,
        ResourceQuota,
        Topology,
    )
    from kueue_oss_tpu.core.store import Store

    t = cfg["topology"]
    store = Store()
    store.upsert_topology(Topology(name=TOPOLOGY,
                                   levels=[BLOCK, RACK, HOST]))
    store.upsert_resource_flavor(ResourceFlavor(
        name=FLAVOR, topology_name=TOPOLOGY))
    stated = nodes(cfg)
    racks = [rack for _b, rack, _h in stated]
    if cfg.get("rack_shuffle") is not None:
        # a control's deployment: the same racks, dealt to other hosts
        racks = list(np.random.default_rng(
            cfg["rack_shuffle"]).permutation(racks))
    for (block, _rack, host), rack in zip(stated, racks):
        store.upsert_node(Node(
            name=host, labels={BLOCK: block, RACK: str(rack)},
            allocatable={"cpu": t["node_cpu"], "pods": t["node_pods"]}))
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
                covered_resources=["cpu"],
                flavors=[FlavorQuotas(name=FLAVOR, resources=[
                    ResourceQuota(
                        name="cpu", nominal=cfg["nominal"],
                        borrowing_limit=cfg["borrowing_limit"])])])]))
        store.upsert_local_queue(
            LocalQueue(name=f"lq-{cq}", cluster_queue=cq))
    require_device_placement(store, cfg)
    return store


def require_device_placement(store, cfg: dict) -> None:
    """Refuse, before any set-up, a program whose device drains cannot
    place every request type of this deployment under its gates.

    Such a program exports no queue of it (``engine._tas_device_ready``
    is all or nothing a queue, and every queue holds every type): the
    whole deployment would run in host cycles, which the served path
    does not support (ROADMAP.md: "a deployment that runs on the host
    is not supported"), and a program from before PR 32 runs a reclaim
    between these gangs to ``run_until_quiet``'s limit of 10,000 cycles
    in a pass, minutes of one. That is no measurement of this cell."""
    from kueue_oss_tpu import features
    from kueue_oss_tpu.core.workload_info import WorkloadInfo
    from kueue_oss_tpu.solver.tas_engine import device_tas_supported

    gates = feature_gates(cfg)
    before = {name: features.enabled(name) for name in gates}
    features.set_gates(gates)
    try:
        cq, cohort = cq_names(cfg)[0]
        spec = store.cluster_queues[cq]
        for size in cfg["sizes"]:
            for mode in cfg["requests"]:
                a = Arrival(
                    key=f"default/probe-{size['name']}-{mode}",
                    name=f"probe-{size['name']}-{mode}", cq=cq,
                    cohort=cohort, klass=f"{size['name']}-{mode}-rack",
                    mode=mode, pods=size["pods"],
                    cpu_per_pod=size["cpu_per_pod"],
                    request=size["pods"] * size["cpu_per_pod"],
                    priority=size["priority"], runtime_s=0.0, due_s=0.0)
                info = WorkloadInfo(make_workload(a, cfg), cluster_queue=cq)
                if not device_tas_supported(info, store, spec):
                    raise SystemExit(
                        f"kinds/tas.py: this program's device drains do "
                        f"not place a {a.klass} gang under the gates "
                        f"{gates}: the deployment would run on the host "
                        "alone, which the served path does not support")
    finally:
        features.set_gates(before)


def make_workload(a: Arrival, cfg: dict):
    from kueue_oss_tpu.api.types import (
        PodSet,
        PodSetTopologyRequest,
        Workload,
    )

    request = (PodSetTopologyRequest(required=RACK) if a.mode == "required"
               else PodSetTopologyRequest(preferred=RACK))
    return Workload(
        name=a.name, queue_name=f"lq-{a.cq}", priority=a.priority,
        creation_time=a.due_s,
        podsets=[PodSet(name="main", count=a.pods,
                        requests={"cpu": a.cpu_per_pod},
                        topology_request=request)])


def scheduler_options(cfg: dict) -> dict:
    return {}


def feature_gates(cfg: dict) -> dict:
    return dict(cfg.get("feature_gates", {}))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

COUNTS = ("over_quota", "bad_evictions", "starved", "below_nominal",
          "inversions", "ghosts", "node_over", "rack_split", "rack_spread",
          "pods_mismatch")


class Books:
    def __init__(self, cfg: dict, arrivals) -> None:
        t = cfg["topology"]
        self.nominal = int(cfg["nominal"])
        self.cq_cap = self.nominal + int(cfg["borrowing_limit"])
        self.within_cq = cfg["within_cluster_queue"]
        self.reclaim = cfg["reclaim_within_cohort"]
        self.node_cpu = int(t["node_cpu"])
        self.node_pods = int(t["node_pods"])
        self.by_key = {a.key: a for a in arrivals}
        self.cohort_of = dict(cq_names(cfg))
        n_cq = Counter(self.cohort_of.values())
        self.cohort_cap = {c: n * self.nominal for c, n in n_cq.items()}
        self.cq_use: dict = defaultdict(int)
        self.cohort_use: dict = defaultdict(int)
        self.arrived: set = set()
        self.holding: set = set()
        self.finished: set = set()
        #: cq -> (request, priority, pods, cpu per pod, mode) -> waiting
        self.waiting: dict = defaultdict(Counter)
        #: the racks the configuration states
        self.rack_of = {host: rack for _b, rack, host in nodes(cfg)}
        self.hosts_of: dict = defaultdict(list)
        for host, rack in self.rack_of.items():
            self.hosts_of[rack].append(host)
        #: host -> [cpu, pods] of the reservations charged to it
        self.host_use: dict = defaultdict(lambda: [0, 0])
        #: rack -> hosts with nothing on them
        self.empty = {rack: len(hs) for rack, hs in self.hosts_of.items()}
        #: key -> {host: pods} of those that hold a reservation
        self.placed: dict = {}
        self.counts = dict.fromkeys(COUNTS, 0)
        self.first: dict = {}
        #: ``flat``'s ``inversions`` with no exemption: reported only
        self.inversions_unexempted = 0
        self.passes = 0

    def _note(self, what: str, pass_no: int, detail) -> None:
        self.counts[what] += 1
        self.first.setdefault(what, {"pass": pass_no, "detail": detail})

    # -- waiting ------------------------------------------------------------

    @staticmethod
    def _shape(a) -> tuple:
        return (a.request, a.priority, a.pods, a.cpu_per_pod, a.mode)

    def arrive(self, key: str) -> None:
        a = self.by_key[key]
        self.arrived.add(key)
        self.waiting[a.cq][self._shape(a)] += 1

    def _wait(self, a, d: int) -> None:
        c = self.waiting[a.cq]
        k = self._shape(a)
        c[k] += d
        if c[k] <= 0:
            del c[k]

    # -- node books ---------------------------------------------------------

    def _charge(self, on_host: dict, cpu_per_pod: int, sign: int) -> None:
        for host, pods in on_host.items():
            use = self.host_use[host]
            was_empty = use[1] == 0
            use[0] += sign * pods * cpu_per_pod
            use[1] += sign * pods
            rack = self.rack_of.get(host)
            if rack is not None and was_empty != (use[1] == 0):
                self.empty[rack] += 1 if use[1] == 0 else -1

    def _per_host(self, cpu_per_pod: int) -> int:
        """Pods of this size an empty host holds."""
        return min(self.node_cpu // cpu_per_pod, self.node_pods)

    def _host_room(self, host: str, cpu_per_pod: int) -> int:
        cpu, pods = self.host_use.get(host, (0, 0))
        return max(0, min((self.node_cpu - cpu) // cpu_per_pod,
                          self.node_pods - pods))

    def _rack_holds(self, rack: str, pods: int, cpu_per_pod: int) -> bool:
        if self.empty[rack] * self._per_host(cpu_per_pod) >= pods:
            return True
        return sum(self._host_room(h, cpu_per_pod)
                   for h in self.hosts_of[rack]) >= pods

    def topology_holds(self, pods: int, cpu_per_pod: int,
                       mode: str) -> bool:
        """Whether the node books can hold the gang as its request
        demands: inside one rack for ``required``, anywhere else."""
        if any(self._rack_holds(r, pods, cpu_per_pod)
               for r in self.hosts_of):
            return True
        if mode == "required":
            return False
        return sum(self._host_room(h, cpu_per_pod)
                   for h in self.rack_of) >= pods

    def _read(self, a, entry: dict, pass_no: int) -> dict:
        """One ``given`` entry held to the configuration, against the
        books as they stand (every earlier reservation of the pass
        still charged). Returns {host: pods}."""
        podsets = entry["podsets"] or []
        if len(podsets) != 1 or podsets[0]["count"] != a.pods or (
                podsets[0]["flavors"] != {"cpu": FLAVOR}
                or podsets[0]["usage"] != {"cpu": a.request}):
            self._note("pods_mismatch", pass_no, entry)
        topology = podsets[0]["topology"] if podsets else None
        on_host: dict = {}
        for values, count in (topology or {}).get("domains", []):
            on_host[values[-1]] = on_host.get(values[-1], 0) + count
        unknown = [h for h in on_host if h not in self.rack_of]
        if unknown or sum(on_host.values()) != a.pods:
            self._note("pods_mismatch", pass_no, entry)
            on_host = {h: n for h, n in on_host.items()
                       if h in self.rack_of}
        racks = {self.rack_of[h] for h in on_host}
        if len(racks) > 1:
            if a.mode == "required":
                self._note("rack_split", pass_no, entry)
            elif any(self.empty[r] * self._per_host(a.cpu_per_pod)
                     >= a.pods for r in self.hosts_of):
                self._note("rack_spread", pass_no, entry)
        return on_host

    # -- one pass -------------------------------------------------------------

    def apply_pass(self, rec: dict) -> None:
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
        evicted = []
        #: cohort -> ClusterQueue -> quota that reclaims took from its
        #: borrowers in this pass
        reclaimed: dict = defaultdict(lambda: defaultdict(int))
        for key in rec["removed"]:
            a = self.by_key.get(key)
            if a is None or key not in self.holding:
                self._note("ghosts", n, f"{key} lost what it did not hold")
                continue
            self.holding.discard(key)
            self.cq_use[a.cq] -= a.request
            self.cohort_use[a.cohort] -= a.request
            if key in finished_now:
                # finishes are applied before the pass schedules: their
                # nodes are free at every placement of the pass
                self.finished.add(key)
                self._charge(self.placed.pop(key, {}), a.cpu_per_pod, -1)
                continue
            evicted.append(a)
            self._wait(a, +1)
            in_queue = (self.within_cq == "LowerPriority"
                        and top_added[a.cq] > a.priority)
            borrowed = (self.reclaim == "Any"
                        and use_before.get(a.cq, 0) + gained[a.cq]
                        > self.nominal)
            if not (in_queue or borrowed):
                self._note("bad_evictions", n, key)
            if borrowed:
                reclaimed[a.cohort][a.cq] += a.request
        for key in finished_now - self.finished:
            a = self.by_key.get(key)
            if a is not None:
                self._note("ghosts", n, f"{key} finished while waiting")
        # what every reservation of the pass was given, in order, each
        # against books that still hold all before it (a preemption
        # inside the pass can only have freed more)
        last: dict = {}
        charged = []
        for entry in rec.get("given", ()):
            a = self.by_key.get(entry["key"])
            if a is None:
                continue
            on_host = self._read(a, entry, n)
            self._charge(on_host, a.cpu_per_pod, +1)
            charged.append((on_host, a.cpu_per_pod))
            last[a.key] = on_host
        for on_host, cpu_per_pod in charged:
            self._charge(on_host, cpu_per_pod, -1)
        for a in evicted:
            self._charge(self.placed.pop(a.key, {}), a.cpu_per_pod, -1)
        # the books at the end of the pass: what a workload was given
        # last is what it holds, if it holds anything
        for a in added:
            if a.key in self.holding or a.key in self.finished:
                self._note("ghosts", n, f"{a.key} reserved twice")
                continue
            if a.key not in self.arrived:
                self._note("ghosts", n, f"{a.key} reserved before it came")
                continue
            self.holding.add(a.key)
            self._wait(a, -1)
            self.cq_use[a.cq] += a.request
            self.cohort_use[a.cohort] += a.request
            if a.key not in last:
                self._note("pods_mismatch", n,
                           f"{a.key}: reserved, given nothing")
        touched = set()
        for key, on_host in last.items():
            if key not in self.holding:
                continue    # reserved and lost again inside the pass
            a = self.by_key[key]
            self._charge(self.placed.pop(key, {}), a.cpu_per_pod, -1)
            self.placed[key] = on_host
            self._charge(on_host, a.cpu_per_pod, +1)
            touched.update(on_host)
        for host in sorted(touched):
            cpu, pods = self.host_use[host]
            if cpu > self.node_cpu or pods > self.node_pods:
                self._note("node_over", n,
                           f"{host}: {cpu} cpu, {pods} pods")
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
        for cq, shapes in self.waiting.items():
            if not shapes:
                continue
            cohort = self.cohort_of[cq]
            free = min(self.cq_cap - self.cq_use[cq],
                       self.cohort_cap[cohort] - self.cohort_use[cohort])
            for request, _prio, pods, cpu_per_pod, mode in sorted(shapes):
                if request > free and (
                        self.reclaim != "Any"
                        or self.cq_use[cq] + request > self.nominal):
                    break   # sorted by request: no larger one fits either
                if not self.topology_holds(pods, cpu_per_pod, mode):
                    continue
                if request <= free:
                    self._note("starved", n,
                               f"{cq}: a gang of {pods} x {cpu_per_pod} "
                               f"({mode}) waits, {free} free")
                if (self.reclaim == "Any"
                        and self.cq_use[cq] + request <= self.nominal):
                    self._note("below_nominal", n,
                               f"{cq}: a request of {request} waits, the "
                               f"queue uses {self.cq_use[cq]} of "
                               f"{self.nominal}")
                break       # one note a queue a pass, as ``flat``
            used = self.cq_use[cq]
            # what reclaims took from the borrowers of the cohort's
            # other queues in this pass: room that only a workload
            # inside its queue's nominal quota could make
            took = (sum(q for other, q in reclaimed[cohort].items()
                        if other != cq)
                    if used <= self.nominal else 0)
            for a in added_in.get(cq, ()):
                if a.key not in self.holding:
                    continue
                fits = [(r, used - a.request + r <= self.nominal)
                        for r, p, pods, cpp, mode in shapes
                        if p > a.priority and r <= a.request + free
                        and self.topology_holds(pods, cpp, mode)]
                if fits:
                    self.inversions_unexempted += 1
                if any(inside or r <= a.request + free - took
                       for r, inside in fits):
                    self._note("inversions", n,
                               f"{a.key} seated, a higher priority "
                               f"waits in {cq} that fits in its place")

    def result(self) -> dict:
        if self.inversions_unexempted:
            # reported, never compared (the module's docstring)
            self.first["inversions_unexempted"] = self.inversions_unexempted
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


def shuffled_racks(cfg: dict) -> dict:
    """The program is given the stated racks dealt to other hosts (each
    rack keeps its size), and the reference holds it to the hosts the
    configuration states: a gang the program keeps inside one of ITS
    racks lies across the stated ones as soon as it takes two hosts."""
    return {**cfg, "rack_shuffle": 20261004}


def double_nodes(cfg: dict) -> dict:
    """The program is given nodes of twice the cpu the configuration
    states, and the reference holds it to the stated one. It bites at
    1 % fill because placement packs: best fit and the balanced set's
    tightest capacity both choose the fullest hosts that still fit."""
    return {**cfg, "topology": {
        **cfg["topology"], "node_cpu": 2 * cfg["topology"]["node_cpu"]}}


#: name -> (the deployment the program gets, the count that then has
#: to read above 0)
controls = {"double_nominal": (double_nominal, "over_quota"),
            "double_nodes": (double_nodes, "node_over"),
            "shuffled_racks": (shuffled_racks, "rack_split")}
