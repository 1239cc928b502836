"""Records ``benchmark/data/flat_golden.json``: what the ``flat`` kind's
schedule, store objects, workloads and reference gave at commit 4d96d69,
BEFORE they moved behind the kind seam: this file ran on that tree with
``deployment.load_config`` / ``scaled`` / ``schedule`` / ``build_store``,
``deployment.make_workload(a, cfg.get("resource", "cpu"))`` and
``reference.audit`` in the places of the kind's functions below.
``test_kinds.py`` holds the moved code to it, seed for seed.

    python benchmark/tests/record_flat_golden.py          # prints the JSON

The pass log the reference is given is made here, from the schedule
alone, by a seater that is wrong on purpose now and then (it overfills
a cohort, evicts without cause, seats a small before a large), so that
every count of the reference reads above 0 somewhere.
"""

import dataclasses
import hashlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

CASES = [  # configuration, seed, (cohorts, cqs per cohort, count divisor)
    ("upstream-large-scale", 1, None),
    ("upstream-large-scale", 2147483659, None),
    ("upstream-baseline", 7, None),
    ("upstream-large-scale", 3, (2, 8, 1)),
    ("upstream-baseline", 2147483659, (1, 2, 50)),
    ("upstream-baseline", 11, (2, 3, 10)),
]


def digest(obj) -> str:
    return hashlib.sha256(json.dumps(
        obj, sort_keys=True, default=repr).encode()).hexdigest()


def sloppy_pass_log(cfg: dict, arrivals, n_passes: int = 60,
                    step_s: float = 0.25) -> list:
    """Arrivals by due time, finishes ``runtime_s`` after seating, a
    greedy seater by (priority, due) within the stated caps, and a
    planted mistake in every seventh pass."""
    nominal, cap = cfg["nominal"], cfg["nominal"] + cfg["borrowing_limit"]
    n_cq: dict = {}
    for a in arrivals:
        n_cq.setdefault(a.cohort, set()).add(a.cq)
    cohort_cap = {c: len(q) * nominal for c, q in n_cq.items()}
    cq_use: dict = {}
    co_use: dict = {}
    waiting: list = []
    holding: dict = {}   # key -> (arrival, finish due)
    i = 0
    log = []
    for n in range(n_passes):
        now = (n + 1) * step_s
        events, removed, added = [], [], []
        while i < len(arrivals) and arrivals[i].due_s <= now:
            events.append(("arrive", arrivals[i].key, arrivals[i].due_s))
            waiting.append(arrivals[i])
            i += 1
        for key, (a, due) in sorted(holding.items()):
            if due <= now:
                events.append(("finish", key, due))
                removed.append(key)
        for key in removed:
            a, _due = holding.pop(key)
            cq_use[a.cq] -= a.request
            co_use[a.cohort] -= a.request
        mistake = n % 7 == 6
        if mistake and holding:
            key = sorted(holding)[0]          # evicted for nobody
            a, _due = holding.pop(key)
            cq_use[a.cq] -= a.request
            co_use[a.cohort] -= a.request
            removed.append(key)
            waiting.append(a)
        order = sorted(waiting, key=lambda a: (
            a.priority if mistake else -a.priority, a.due_s, a.key))
        slack = 2 * nominal if mistake else 0   # over the cohort's cap
        for a in order:
            if (cq_use.get(a.cq, 0) + a.request <= cap
                    and co_use.get(a.cohort, 0) + a.request
                    <= cohort_cap[a.cohort] + slack
                    and not (mistake and a.key.endswith("-1"))):
                cq_use[a.cq] = cq_use.get(a.cq, 0) + a.request
                co_use[a.cohort] = co_use.get(a.cohort, 0) + a.request
                holding[a.key] = (a, now + a.runtime_s)
                added.append(a.key)
        seated = set(added)
        waiting = [a for a in waiting if a.key not in seated]
        if n == 20 and added:
            added.append(added[0])            # reserved twice
        events.sort(key=lambda e: e[2])
        log.append({"events": events, "added": sorted(added),
                    "removed": sorted(removed)})
    return log


def record() -> dict:
    from benchmark import deployment

    out = {}
    for name, seed, scale in CASES:
        cfg = deployment.load_config(name)
        kind = deployment.kind_of(cfg)
        if scale:
            cfg = kind.scaled(cfg, *scale)
        arrivals = kind.schedule(cfg, seed)
        case = {"arrivals": len(arrivals), "schedule": digest(
            [dataclasses.astuple(a)[:9] for a in arrivals])}
        if scale or seed == 1:
            store = kind.build_store(cfg)
            case["store"] = digest({
                coll: {k: dataclasses.asdict(v)
                       for k, v in getattr(store, coll).items()}
                for coll in ("cluster_queues", "cohorts", "local_queues",
                             "resource_flavors")})
            case["workloads"] = digest([
                {k: v for k, v in dataclasses.asdict(
                    kind.make_workload(a, cfg)).items() if k != "uid"}
                for a in arrivals[:2000]])
        if scale:
            log = sloppy_pass_log(cfg, arrivals)
            early = [a.key for a in arrivals if a.due_s < 0.1]
            got = kind.audit(cfg, arrivals, early, log)
            case["audit_counts"] = got["counts"]
            case["audit"] = digest(got)
        out[f"{name}:{seed}:{scale}"] = case
    return out


if __name__ == "__main__":
    print(json.dumps(record(), indent=1, sort_keys=True))
