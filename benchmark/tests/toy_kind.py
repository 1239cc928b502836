"""A toy configuration kind, as the text of the files a ``model_config``
PR would add: ``kinds/toy.py`` (two ResourceFlavors, fair sharing
switched on through ``scheduler_options``, its own reference with one
count, one control), ``configs/toy.json`` and ``traffic/toy-backlog.json``.
``test_kinds.py`` writes them into a temporary copy of the benchmark's
tree and runs a rehearsal there: no file the benchmark has is edited.
"""

KIND = '''
"""Kind ``toy``: ``queues`` ClusterQueues in one cohort, each with
``nominal`` cpu on each of two flavors and no borrowing; every workload
asks for 1 cpu and runs ``runtime_s``."""

from dataclasses import dataclass

FLAVORS = ("reserved", "spot")


def load(cfg):
    for key in ("queues", "nominal", "per_queue", "runtime_s", "guarantees"):
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
    pods: int          # a field of this kind's own


def schedule(cfg, seed):
    out = []
    for q in range(cfg["queues"]):
        for i in range(cfg["per_queue"]):
            name = f"job-{q}-{i}"
            out.append(Arrival(
                key=f"default/{name}", name=name, cq=f"cq-{q}",
                klass="job", runtime_s=float(cfg["runtime_s"]),
                due_s=((seed + 7 * i + q) % 10) / 100.0, pods=1))
    out.sort(key=lambda a: (a.due_s, a.key))
    return out


def top_class(cfg):
    return "job"


def build_store(cfg):
    from kueue_oss_tpu.api.types import (
        ClusterQueue, Cohort, FlavorQuotas, LocalQueue, ResourceFlavor,
        ResourceGroup, ResourceQuota)
    from kueue_oss_tpu.core.store import Store

    store = Store()
    for f in FLAVORS:
        store.upsert_resource_flavor(ResourceFlavor(name=f))
    store.upsert_cohort(Cohort(name="all"))
    for q in range(cfg["queues"]):
        store.upsert_cluster_queue(ClusterQueue(
            name=f"cq-{q}", cohort="all",
            resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[FlavorQuotas(name=f, resources=[ResourceQuota(
                    name="cpu", nominal=cfg["nominal"],
                    borrowing_limit=0)]) for f in FLAVORS])]))
        store.upsert_local_queue(
            LocalQueue(name=f"lq-cq-{q}", cluster_queue=f"cq-{q}"))
    return store


def make_workload(a, cfg):
    from kueue_oss_tpu.api.types import PodSet, Workload

    return Workload(name=a.name, queue_name=f"lq-{a.cq}",
                    creation_time=a.due_s,
                    podsets=[PodSet(count=a.pods, requests={"cpu": 1})])


def scheduler_options(cfg):
    return dict(cfg.get("scheduler", {}))


def audit(cfg, arrivals, preloaded, pass_log):
    """One guarantee: a queue never holds more than its two flavors'
    nominal quota together (it may not borrow)."""
    by_key = {a.key: a for a in arrivals}
    cap = len(FLAVORS) * cfg["nominal"]
    held, use = set(), {}
    over, first, finished = 0, {}, 0
    for n, rec in enumerate(pass_log):
        done = {k for kind, k, _d in rec["events"] if kind == "finish"}
        for k in rec["removed"]:
            held.discard(k)
            use[by_key[k].cq] -= by_key[k].pods
            finished += k in done
        for k in rec["added"]:
            held.add(k)
            use[by_key[k].cq] = use.get(by_key[k].cq, 0) + by_key[k].pods
        for cq, u in use.items():
            if u > cap:
                over += 1
                first.setdefault("over_both_flavors",
                                 {"pass": n, "detail": f"{cq}: {u} > {cap}"})
    return {"counts": {"over_both_flavors": over}, "first": first,
            "holding": len(held), "finished": finished}


def double_quota(cfg):
    return {**cfg, "nominal": 2 * cfg["nominal"]}


controls = {"double_quota": (double_quota, "over_both_flavors")}
'''

CONFIG = {
    "name": "toy", "kind": "toy",
    "source": "benchmark/tests/toy_kind.py: a test's deployment",
    "queues": 4, "nominal": 3, "per_queue": 16, "runtime_s": 30,
    "scheduler": {"enable_fair_sharing": True},
    "guarantees": ["a ClusterQueue never holds more than the nominal "
                   "quota of its two flavors together"],
    "reduced": [],
}

TRAFFIC = {"kind": "replay", "start_at_s": 0.05,
           "warmup": {"passes": 1, "max_seconds": 2}}

CONFIG_ENTRY = {"name": "toy", "source": CONFIG["source"],
                "file": "benchmark/configs/toy.json", "reduced": [],
                "why": "a test's deployment: two flavors, fair sharing"}
CELL = {"name": "toy-backlog", "config": "toy", "traffic": "toy-backlog",
        "chips": 1, "why": "64 one-cpu jobs over 4 queues of 2 x 3 cpu"}
