"""A cell's deployment and its event schedule, from data files and a seed.

A configuration file (``configs/<name>.json``) holds the upstream
generator's shapes: cohorts x ClusterQueues, quotas, preemption policy
and the workload classes (count, request, priority, runtime, creation
interval per ClusterQueue). A traffic file (``traffic/<name>.json``)
says how the schedule is played. Both are found by the names in
``BENCHMARK.json``; nothing here knows a cell.

Copied from ``kueue_oss_tpu/perf/generator.py`` (which has no seed and
lives inside the program) so that a later PR to the program cannot move
the yardstick. What the seed does: the i-th workload of a class is due
somewhere inside its i-th creation interval, and WHICH ClusterQueue
gets which point of that interval is the seed's permutation. Every seed
therefore plays the same multiset of arrival times, sizes, priorities
and runtimes; only their assignment to queues differs.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    cfg = load_json("configs", f"{name}.json")
    for key in ("cohorts", "cqs_per_cohort", "nominal", "borrowing_limit",
                "classes", "guarantees"):
        if key not in cfg:
            raise ValueError(f"configs/{name}.json lacks {key!r}")
    return cfg


def load_traffic(name: str) -> dict:
    traffic = load_json("traffic", f"{name}.json")
    if traffic.get("kind") != "replay":
        raise ValueError(
            f"traffic/{name}.json: kind {traffic.get('kind')!r} has no "
            "generator (known: replay)")
    return traffic


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


def build_store(cfg: dict, *, nominal: int | None = None):
    """The program's Store holding the deployment (no workloads).
    ``nominal`` overrides the configuration's quota: only the control
    (a deployment that breaks the stated guarantee) passes it."""
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
                        nominal=cfg["nominal"] if nominal is None
                        else nominal,
                        borrowing_limit=cfg["borrowing_limit"])])])]))
        store.upsert_local_queue(
            LocalQueue(name=f"lq-{cq}", cluster_queue=cq))
    return store


def make_workload(a: Arrival, resource: str = "cpu"):
    from kueue_oss_tpu.api.types import PodSet, Workload

    return Workload(
        name=a.name, queue_name=f"lq-{a.cq}", priority=a.priority,
        creation_time=a.due_s,
        podsets=[PodSet(count=1, requests={resource: a.request})])
