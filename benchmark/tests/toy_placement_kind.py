"""A toy kind whose guarantees are about WHICH quota and WHICH nodes, as
the text of the files a ``model_config`` PR would add: ``kinds/racks.py``
(two ResourceFlavors, each over its own racks of one topology; podsets
of several pods that have to sit inside one rack; ``GIVEN``; a feature
gate; a reference that keeps node, rack and per-flavor books from what
each reservation was ``given``; one control), ``configs/racks.json`` and
``traffic/racks-backlog.json``. ``test_given.py`` writes them into a
temporary copy of the benchmark's tree and runs a rehearsal there: no
file the benchmark has is edited.
"""

KIND = '''
"""Kind ``racks``: ``queues`` ClusterQueues, no cohort, each with
``nominal[f]`` cpu on each flavor ``f`` of ``flavors`` (in that order);
every flavor has ``racks`` racks of ``hosts_per_rack`` hosts of
``node_cpu`` cpu of its own. A workload is one podset of 2 to 4 pods of
1 cpu that has to sit inside one rack; it runs ``runtime_s``."""

from dataclasses import dataclass

RACK = "toy/rack"
HOST = "kubernetes.io/hostname"
#: every pass-log record lists what each reservation was given
GIVEN = True


def load(cfg):
    for key in ("queues", "flavors", "nominal", "racks", "hosts_per_rack",
                "node_cpu", "per_queue", "runtime_s", "guarantees"):
        if key not in cfg:
            raise ValueError(f"configs/{cfg.get('name')}.json lacks {key!r}")
    return cfg


def scaled(cfg, cohorts, cqs_per_cohort, count_div):
    return cfg


@dataclass(frozen=True)
class Arrival:
    key: str
    name: str
    cq: str
    klass: str
    runtime_s: float
    due_s: float
    pods: int


def schedule(cfg, seed):
    out = []
    for q in range(cfg["queues"]):
        for i in range(cfg["per_queue"]):
            name = f"gang-{q}-{i}"
            out.append(Arrival(
                key=f"default/{name}", name=name, cq=f"cq-{q}",
                klass="gang", runtime_s=float(cfg["runtime_s"]),
                due_s=((seed + 7 * i + q) % 10) / 100.0,
                pods=2 + (seed + i + q) % 3))
    out.sort(key=lambda a: (a.due_s, a.key))
    return out


def top_class(cfg):
    return "gang"


def nodes(cfg):
    """(flavor, rack, host) of every node the configuration states."""
    return [(f, f"{f}-r{r}", f"{f}-r{r}-h{h}") for f in cfg["flavors"]
            for r in range(cfg["racks"])
            for h in range(cfg["hosts_per_rack"])]


def build_store(cfg):
    from kueue_oss_tpu.api.types import (
        ClusterQueue, FlavorQuotas, LocalQueue, Node, ResourceFlavor,
        ResourceGroup, ResourceQuota, Topology)
    from kueue_oss_tpu.core.store import Store

    store = Store()
    store.upsert_topology(Topology(name="racks", levels=[RACK, HOST]))
    for f in cfg["flavors"]:
        store.upsert_resource_flavor(ResourceFlavor(
            name=f, node_labels={"toy/pool": f}, topology_name="racks"))
    for f, rack, host in nodes(cfg):
        store.upsert_node(Node(
            name=host, labels={"toy/pool": f, RACK: rack},
            allocatable={"cpu": cfg["node_cpu"]}))
    for q in range(cfg["queues"]):
        store.upsert_cluster_queue(ClusterQueue(
            name=f"cq-{q}",
            resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[FlavorQuotas(name=f, resources=[ResourceQuota(
                    name="cpu", nominal=cfg["nominal"][f])])
                    for f in cfg["flavors"]])]))
        store.upsert_local_queue(
            LocalQueue(name=f"lq-cq-{q}", cluster_queue=f"cq-{q}"))
    return store


def make_workload(a, cfg):
    from kueue_oss_tpu.api.types import (
        PodSet, PodSetTopologyRequest, Workload)

    return Workload(
        name=a.name, queue_name=f"lq-{a.cq}", creation_time=a.due_s,
        podsets=[PodSet(name="main", count=a.pods, requests={"cpu": 1},
                        topology_request=PodSetTopologyRequest(
                            required=RACK))])


def scheduler_options(cfg):
    return {}


def feature_gates(cfg):
    return dict(cfg.get("feature_gates", {}))


def breaches(cfg, a, entry, rack_of):
    """What one reservation was given, held to the configuration:
    (names of the guarantees it breaks, flavor, {host: cpu})."""
    broken, flavor, on_host = set(), None, {}
    podsets = entry["podsets"] or []
    if len(podsets) != 1 or podsets[0]["count"] != a.pods:
        return {"rack_split", "flavor_over"}, None, {}
    ps = podsets[0]
    flavor = ps["flavors"].get("cpu")
    if flavor not in cfg["flavors"] or ps["usage"] != {"cpu": a.pods}:
        broken.add("flavor_over")
    domains = (ps["topology"] or {}).get("domains", [])
    for values, count in domains:
        on_host[values[-1]] = on_host.get(values[-1], 0) + count
    racks = {rack_of.get(h) for h in on_host}
    if (sum(on_host.values()) != a.pods or len(racks) != 1 or None in racks
            or any(not h.startswith(f"{flavor}-") for h in on_host)):
        broken.add("rack_split")
    return broken, flavor, on_host


def audit(cfg, arrivals, preloaded, pass_log):
    """Node, rack and per-flavor books, kept from ``given`` alone:
    ``node_over``    a host holds more pods than it has cpu for, at the
                     end of a pass;
    ``rack_split``   a podset that is not whole inside one rack of the
                     flavor it was charged to;
    ``flavor_over``  a queue above its nominal quota of one flavor at
                     the end of a pass, or a reservation charged other
                     than its pods to one stated flavor."""
    by_key = {a.key: a for a in arrivals}
    rack_of = {host: rack for _f, rack, host in nodes(cfg)}
    counts = {"node_over": 0, "rack_split": 0, "flavor_over": 0}
    first = {}
    #: key -> (flavor, {host: cpu}) of those that hold a reservation
    held = {}
    finished = 0

    def note(what, n, detail):
        counts[what] += 1
        first.setdefault(what, {"pass": n, "detail": detail})

    for n, rec in enumerate(pass_log):
        done = {k for kind, k, _d in rec["events"] if kind == "finish"}
        for k in rec["removed"]:
            held.pop(k, None)
            finished += k in done
        #: the last thing a workload was given in this pass is what it
        #: holds at its end, if it holds anything: one that lost and
        #: regained its reservation inside the pass has MOVED
        last = {}
        for entry in rec["given"]:
            last[entry["key"]] = entry
        for k in rec["added"]:
            if k not in last:
                note("rack_split", n, f"{k}: reserved, given nothing")
                held[k] = (None, {})
        for k, entry in last.items():
            if k not in held and k not in rec["added"]:
                continue       # reserved and lost again inside the pass
            broken, flavor, on_host = breaches(cfg, by_key[k], entry,
                                               rack_of)
            for what in sorted(broken):
                note(what, n, entry)
            held[k] = (flavor, on_host)
        node_use, flavor_use = {}, {}
        for k, (flavor, on_host) in held.items():
            cq = by_key[k].cq
            flavor_use[cq, flavor] = (flavor_use.get((cq, flavor), 0)
                                      + by_key[k].pods)
            for host, cpu in on_host.items():
                node_use[host] = node_use.get(host, 0) + cpu
        for host, cpu in sorted(node_use.items()):
            if cpu > cfg["node_cpu"]:
                note("node_over", n, f"{host}: {cpu} > {cfg['node_cpu']}")
        for (cq, flavor), cpu in sorted(flavor_use.items(), key=str):
            if cpu > cfg["nominal"].get(flavor, 0):
                note("flavor_over", n, f"{cq} on {flavor}: {cpu} > "
                                       f"{cfg['nominal'].get(flavor, 0)}")
    return {"counts": counts, "first": first, "holding": len(held),
            "finished": finished}


def double_nodes(cfg):
    """The program is given nodes of twice the cpu the configuration
    states, and the reference holds it to the stated one."""
    return {**cfg, "node_cpu": 2 * cfg["node_cpu"]}


controls = {"double_nodes": (double_nodes, "node_over")}
'''

CONFIG = {
    "name": "racks", "kind": "racks",
    "source": "benchmark/tests/toy_placement_kind.py: a test's deployment",
    "queues": 2, "flavors": ["reserved", "spot"],
    # 'reserved' is bound by quota (2 x 6 cpu on 16), 'spot' by its
    # nodes (2 x 16 cpu of quota on 16 cpu of nodes)
    "nominal": {"reserved": 6, "spot": 16},
    "racks": 2, "hosts_per_rack": 2, "node_cpu": 4,
    "per_queue": 16, "runtime_s": 30,
    "feature_gates": {"TASBalancedPlacement": True},
    "guarantees": [
        "no node holds more pods than it has cpu for",
        "a podset sits inside one rack of the flavor it is charged to",
        "a ClusterQueue never holds more than its nominal quota of a "
        "flavor"],
    "reduced": [],
}

TRAFFIC = {"kind": "replay", "start_at_s": 0.05,
           "warmup": {"passes": 1, "max_seconds": 2}}

CONFIG_ENTRY = {"name": "racks", "source": CONFIG["source"],
                "file": "benchmark/configs/racks.json", "reduced": [],
                "why": "a test's deployment: two flavors over their own "
                       "racks, gangs that have to sit inside one rack"}
CELL = {"name": "racks-backlog", "config": "racks",
        "traffic": "racks-backlog", "chips": 1,
        "why": "32 gangs of 2-4 pods over 2 queues; 2 x 2 racks x 2 hosts "
               "x 4 cpu"}
