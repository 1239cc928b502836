#!/usr/bin/env python
"""What-if capacity planning from the command line (docs/SIMULATOR.md).

Builds a generated cluster shape (or uses a recorded journal as the
baseline anchor), fans a scenario grid — quota factors x arrival-rate
factors — into ONE vmapped solver dispatch, and prints the per-scenario
KPI report as JSON. Deterministic: same arguments => byte-identical
output with --no-timing.

Usage:
    python tools/simulate.py --scenarios 64                  # 64-way batch
    python tools/simulate.py --sweep quota --factors 0.5,1,2,4
    python tools/simulate.py --target 'cohort-0' --factors 0.25,0.5
    python tools/simulate.py --journal decisions.jsonl       # + baseline
    python tools/simulate.py --trace --flap-at 500 --flap-count 2
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kueue_oss_tpu.config.configuration import SimulatorConfig  # noqa: E402
from kueue_oss_tpu.perf.generator import GeneratorConfig, generate  # noqa: E402
from kueue_oss_tpu.sim import (  # noqa: E402
    FlapEvent,
    ScenarioSpec,
    WhatIfEngine,
    arrival_sweep,
    cross,
    journal_baseline,
    kind_counts_per_cycle,
    load_events,
    quota_sweep,
    replay,
    simulate_trace,
)

#: deterministic default factor ladders for --scenarios N grids
_QUOTA_LADDER = (0.25, 0.5, 0.75, 1.25, 1.5, 2.0, 3.0, 4.0)
_ARRIVAL_LADDER = (0.25, 0.5, 0.75, 1.25, 1.5, 2.0, 2.5, 3.0)


def build_shape(shape: str):
    if shape == "baseline":
        cfg = GeneratorConfig.baseline()
    elif shape == "large-scale":
        cfg = GeneratorConfig.large_scale(preemption=False)
        cfg.nominal_quota = 200
    elif shape == "small":
        cfg = GeneratorConfig.large_scale(preemption=False)
        cfg.n_cohorts, cfg.cqs_per_cohort = 2, 3
        for wc in cfg.classes:
            wc.count = max(2, wc.count // 8)
    else:
        raise SystemExit(f"unknown shape {shape!r}")
    store, schedule = generate(cfg)
    return store, schedule


def build_specs(args) -> list[ScenarioSpec]:
    factors = ([float(f) for f in args.factors.split(",")]
               if args.factors else None)
    if args.sweep == "quota":
        return quota_sweep(factors or _QUOTA_LADDER, target=args.target,
                           seed=args.seed)
    if args.sweep == "arrival":
        return arrival_sweep(factors or _ARRIVAL_LADDER, seed=args.seed)
    # grid: quota x arrival, truncated to --scenarios
    q = quota_sweep(factors or _QUOTA_LADDER, target=args.target,
                    seed=args.seed)
    a = arrival_sweep(_ARRIVAL_LADDER, seed=args.seed)
    specs = cross(q, a)
    if args.scenarios:
        if len(specs) < args.scenarios:
            raise SystemExit(
                f"grid yields only {len(specs)} scenarios; pass more "
                f"--factors to reach {args.scenarios}")
        specs = specs[:args.scenarios]
    return specs


def main(argv=None, out=None) -> int:
    out = out or sys.stdout
    p = argparse.ArgumentParser(
        prog="simulate.py",
        description="Batched what-if simulation & capacity planning.")
    p.add_argument("--shape", default="small",
                   choices=["small", "baseline", "large-scale"],
                   help="generated cluster/backlog shape")
    p.add_argument("--scenarios", type=int, default=0,
                   help="grid size (quota x arrival factors, truncated)")
    p.add_argument("--sweep", default="grid",
                   choices=["grid", "quota", "arrival"])
    p.add_argument("--factors", default="",
                   help="comma-separated factors for the sweep")
    p.add_argument("--target", default="*",
                   help="node-name glob the quota factors apply to "
                        "(CQ or cohort; a cohort scales its subtree)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--parity", type=int, default=None,
                   help="scenarios to cross-check bit-identically "
                        "against the sequential oracle (default: "
                        "simulator config)")
    p.add_argument("--journal",
                   help="flight-recorder journal to anchor the report "
                        "(adds baseline KPIs + replay fidelity)")
    p.add_argument("--trace", action="store_true",
                   help="run ONE virtual-time trace simulation of the "
                        "first PERTURBED scenario (the one after the "
                        "'base' anchor; the report names which) "
                        "instead of the batched sweep")
    p.add_argument("--flap-at", type=float, action="append", default=[],
                   help="trace mode: flap nodes down at this virtual ms")
    p.add_argument("--flap-count", type=int, default=1)
    p.add_argument("--out", help="write the JSON report here instead "
                                 "of stdout")
    p.add_argument("--no-timing", action="store_true",
                   help="omit wall-clock timing (byte-identical reruns)")
    p.add_argument("--compact", action="store_true",
                   help="single-line JSON")
    args = p.parse_args(argv)

    specs = build_specs(args)
    store, schedule = build_shape(args.shape)

    if args.trace:
        spec = specs[1] if len(specs) > 1 else specs[0]
        spec.node_flaps = [
            FlapEvent(at_ms=ms, down=True, count=args.flap_count)
            for ms in args.flap_at]
        result = {"mode": "trace", "trace": simulate_trace(
            store, schedule, spec)}
    else:
        for g in schedule:
            store.add_workload(g.workload)
        cfg = SimulatorConfig(max_scenarios=max(1024, len(specs)))
        engine = WhatIfEngine(store, config=cfg)
        report = engine.run(specs, parity=args.parity)
        result = {"mode": "batched",
                  **report.to_dict(include_timing=not args.no_timing)}

    if args.journal:
        events = load_events(args.journal)
        result["journal"] = journal_baseline(events)
        # replay fidelity: the virtual-time replay must reproduce the
        # recorded decision kinds per cycle, exactly
        replayed = replay(events)
        result["journal"]["replay_faithful"] = (
            kind_counts_per_cycle(events)
            == kind_counts_per_cycle(replayed.events()))

    import jax

    # the report is platform-independent; where it was computed is not
    print(f"solved on {jax.default_backend()}", file=sys.stderr)
    text = (json.dumps(result, sort_keys=True,
                       separators=(",", ":"))
            if args.compact else
            json.dumps(result, sort_keys=True, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"report written to {args.out}", file=sys.stderr)
    else:
        print(text, file=out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
