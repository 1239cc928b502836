#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that owns the chip. Set-up builds the cell's deployment and
schedule from its configuration file, its traffic file and the seed,
warms every program up on a twin deployment built from another seed and
throws the twin away. The window replays the schedule on the real clock
through ``Store -> QueueManager -> Scheduler(solver="auto")`` and
nothing else; the router decides what reaches the device. After the
window the driver's record is held to the configuration's guarantees by
the plain reference of the configuration's kind (``kinds/<kind>.py``).
The last line of standard output is the result.

``--self-test`` checks the trace reduction on the recorded samples.
``--rehearse`` (builder's tool) runs on the CPU at sizes given by
arguments, labels its device ``cpu`` and prints no device metric; a
measurement run that finds no TPU fails.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: what a run leaves behind (the trace, before it is read) goes here
OUT = os.path.join(ROOT, ".bench_out")
PHASES = ("export", "encode", "device_put", "solve", "apply")


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--self-test", action="store_true")
    # builder's tools: none of them is part of a measurement
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: sizes by the arguments below")
    ap.add_argument("--cohorts", type=int, default=None)
    ap.add_argument("--cqs-per-cohort", type=int, default=None)
    ap.add_argument("--count-div", type=int, default=1)
    ap.add_argument("--control", default=None,
                    help="run a control of the configuration's kind (its "
                         "``controls``): the program is given a deployment "
                         "that breaks a stated guarantee, and the "
                         "reference holds it to the configuration")
    ap.add_argument("--twin", action="store_true",
                    help="replay the log through the program's own "
                         "host-only scheduler as well and report where "
                         "the two paths part (a witness, no part of "
                         "correct)")
    return ap.parse_args(argv)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json "
                         f"(cells: {sorted(cells)})")
    cell = cells[name]
    return cell, {c["name"]: c for c in bench["configs"]}[cell["config"]]


def metrics_of(bench: dict, group: str, cell: str) -> list[dict]:
    """The cell's metrics of ``end_to_end`` or ``per_layer``: those
    that list it, and of those that list no cells every end-to-end
    metric and every per-layer metric that moves one the cell reports."""
    def lists(m: dict) -> bool:
        return "workloads" not in m or cell in m["workloads"]

    e2e = {m["name"] for m in bench["end_to_end"] if lists(m)}
    return [m for m in bench[group] if lists(m)
            and ("workloads" in m or m.get("moves", m["name"]) in e2e)]


# ---------------------------------------------------------------------------
# the device, and JAX's own account of its compiles
# ---------------------------------------------------------------------------


def program_key() -> str:
    """A name for the program's solver sources as they stand. The
    persistent cache's key ignores op metadata, so a cache that another
    tree has filled serves executables with THAT tree's
    ``jax.named_scope`` names (or none), and the scope readers then
    find nothing under the names this tree's layer files give. A
    directory per state of ``kueue_oss_tpu/solver/`` cannot: fixed for
    a tree, and new only when a source changes that could rename a
    scope (the program then compiles anew anyway, but for a change of
    names alone)."""
    import hashlib

    h = hashlib.sha256()
    src = os.path.join(ROOT, "kueue_oss_tpu", "solver")
    for name in sorted(os.listdir(src)):
        if name.endswith(".py"):
            h.update(name.encode())
            with open(os.path.join(src, name), "rb") as f:
                h.update(f.read())
    return h.hexdigest()[:16]


def own_the_chip(chips: int, rehearse: bool) -> dict:
    import jax

    from kueue_oss_tpu.util import xla_cache

    cache_dir = None
    if not rehearse:
        # under $JAX_COMPILATION_CACHE_DIR where set, else under the
        # checkout's .xla_cache/; the program's own enable() leaves a
        # directory chosen in jax.config alone
        cache_dir = os.path.join(xla_cache.enable(),
                                 "solver-" + program_key())
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # every program goes to the cache, the small scatter programs
        # too: set-up then does the same work in every run after the
        # first
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if rehearse:
        if device["platform"] != "cpu":
            raise SystemExit("--rehearse is for JAX_PLATFORMS=cpu")
    elif device["platform"] != "tpu" or device["count"] < chips:
        log(f"no accelerator for this cell: {device}, needs {chips} tpu")
        raise SystemExit(3)
    device["cache_dir"] = cache_dir
    return device


class CompileCounter:
    """Backend compile requests as JAX reports them (a load from the
    persistent cache is one too: it stalls the caller as well), and the
    cache hits among them. Copied from ``chip_smoke.XlaCounters``."""

    def __init__(self) -> None:
        import jax

        self.requests = self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(
            self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def snapshot(self) -> dict:
        return {"requests": self.requests, "cache_hits": self.hits,
                "seconds": self.seconds}


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def fallback_counts() -> dict:
    """Every degradation hop the process took (as chip_smoke reads it)."""
    from kueue_oss_tpu import metrics, resilience

    return {
        "solver_fallback_total": int(sum(
            metrics.solver_fallback_total.collect().values())),
        "solver_plan_fallbacks_total": int(
            metrics.solver_plan_fallbacks_total.total()),
        "degradation_levels": int(sum(
            resilience.controller.levels().values())),
    }


def record_caps(engine) -> dict:
    """Note, without changing, the static caps the engine hands its
    solve (as ``chip_smoke.py`` does for its report)."""
    seen: dict = {}
    real = engine._local_solve

    def recording(problem, frame, **kw):
        seen.update({k: v for k, v in kw.items()
                     if k in ("g_max", "h_max", "p_max", "fs_enabled")})
        return real(problem, frame, **kw)

    engine._local_solve = recording
    return seen


def load_solve_variants(engine, caps: dict, p_maxes) -> dict:
    """Trace and load the preemption program for other candidate caps.

    ``SolverEngine._size_caps`` rounds p_max to a power of two of the
    fullest cohort's population, so a stream whose backlog grows through
    the window meets one program per power of two, each ~7 s of Python
    tracing the first time a process needs it. The traffic file lists
    the caps its window can meet; each is run once here on the twin's
    resident tensors, through the program's own jitted entry."""
    import jax

    done = {"loaded": [], "seen": dict(caps)}
    try:
        from kueue_oss_tpu.solver.full_kernels import solve_backlog_full

        tensors = engine._device_states["full"].tensors
        for p in p_maxes:
            if p != caps["p_max"]:
                jax.block_until_ready(solve_backlog_full(
                    tensors, **{**caps, "p_max": int(p)}))
                done["loaded"].append(int(p))
    except (AttributeError, KeyError, TypeError, ValueError) as e:
        done["skipped"] = repr(e)
    return done


def prefill_update_programs(engine) -> dict:
    """Put the engine's small update programs into the compile cache.

    Between drains the engine updates its resident tensors with one
    jitted scatter per (tensor, power-of-two bucket of dirty rows)
    (``solver/delta.py`` ``_scatter``), and on a full sync over resident
    buffers with one overwrite per tensor (``_donated_overwrite``).
    They are built per engine
    from a fresh lambda, so no warm-up can keep the WINDOW's engine from
    asking the backend for them (PERF.md, F6); what set-up can do is see
    that every one of them is in the persistent cache, so that the
    window loads (milliseconds) where a first run would compile
    (seconds, which the router then reads as a slow device). Called on a
    twin that is about to be thrown away, through the program's own
    functions so that the programs are the program's. Where the
    program's internals have moved on, nothing is prefilled and
    ``compiles_in_window`` says so.
    """
    import numpy as np

    done = {"scatter": 0}
    try:
        from kueue_oss_tpu.solver import delta

        for kind, dev in engine._device_states.items():
            row_map = (delta._FULL_ROW_TENSORS if kind.startswith("full")
                       else delta._LEAN_ROW_TENSORS)
            for tname in sorted(set(row_map.values())):
                buf = getattr(dev.tensors, tname, None)
                if buf is None or not buf.ndim:
                    continue
                cap = 1
                # a delta that dirties more than half falls back to a
                # full sync, so larger buckets never run
                while cap <= buf.shape[0] // 2:
                    buf = dev._scatter(
                        buf, np.arange(cap, dtype=np.int32),
                        np.zeros((cap,) + buf.shape[1:], dtype=buf.dtype))
                    done["scatter"] += 1
                    cap *= 2
                dev.tensors = dev.tensors._replace(**{tname: buf})
            # a delta that dirties too much becomes a full sync over
            # the resident buffers: one overwrite program per tensor
            # (it stops at the first 0-d tensor, here as in the
            # program's own ``_full_upload``, which then re-seeds:
            # PERF.md section 7; the programs before it are the ones
            # the window asks for)
            n0 = len(dev._scatter_cache)
            try:
                dev._donated_overwrite(
                    dev.tensors, [np.asarray(x) for x in dev.tensors])
            except ValueError:
                pass
            done["overwrite"] = (done.get("overwrite", 0)
                                 + len(dev._scatter_cache) - n0)
    except (AttributeError, KeyError, TypeError, ValueError) as e:
        done["skipped"] = repr(e)
    return done


# ---------------------------------------------------------------------------
# the window's facts
# ---------------------------------------------------------------------------


class DrainRows:
    """The engine's ledger rows of the window's drains, read after each
    pass in which a drain ran (the ledger is a ring of 4,096 rows)."""

    def __init__(self) -> None:
        from kueue_oss_tpu import obs

        self.obs = obs
        last = obs.cycle_ledger.last_row()
        self.seen = last.seq if last is not None else 0
        self.rows: list = []

    def on_pass(self, rec: dict) -> None:
        if not rec["drains"]:
            return
        for r in self.obs.cycle_ledger.rows(last=1024):
            if r.kind == self.obs.SOLVER_DRAIN and r.seq > self.seen:
                self.rows.append({
                    "phases": dict(r.phases),
                    "rounds": r.rounds, "admitted": r.admitted,
                    "evicted": r.evicted, "frame": r.frame_kind,
                    "arm": r.solver_arm, "pass": rec["n"]})
        last = self.obs.cycle_ledger.last_row()
        self.seen = last.seq if last is not None else self.seen


def window_facts(replay, win: dict, rows: list, compiles: int,
                 totals=None) -> dict:
    from benchmark import endtoend

    t0, end = win["t0"], win["t_end"]
    parts = endtoend.pass_parts(replay.passes, end)
    passes = sum(parts)
    with_drain = 0.0
    span_s = {"run_until_quiet": 0.0, "apply_events": 0.0}
    for p, part in zip(replay.passes, parts):
        if p["drains"]:
            with_drain += part
        span_s["run_until_quiet"] += part * (p["t_end"] - p["t_applied"])
        span_s["apply_events"] += part * (p["t_applied"] - p["t_start"])
    phase_s: dict = {}
    for r in rows:
        for k in PHASES:
            phase_s[k] = (phase_s.get(k, 0.0)
                          + parts[r["pass"]] * r["phases"].get(k, 0.0))
    reservations = sum(1 for _k, t in replay.reservations if t0 <= t <= end)
    evictions = sum(1 for _k, t, _s in replay.evictions if t0 <= t <= end)
    return {
        "passes": passes, "parts": parts, "window_s": end - t0,
        "phase_s": phase_s, "span_s": span_s, "trace": None,
        "program": (totals.window(parts[-1] if parts else 1.0)
                    if totals is not None else None),
        "counters": {"passes": passes, "passes_with_drain": with_drain,
                     "drains": len(rows), "reservations": reservations,
                     "evictions": evictions, "compiles": compiles}}


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------


def check(replay, cfg: dict, fallbacks0: dict, *,
          with_twin: bool) -> tuple[bool, dict, dict]:
    """Every number compared, each beside its limit (all 0: integer
    kernels, exact comparison). ``cfg`` is the configuration as stated
    (``replay.cfg`` is what the program was given: a control's differs);
    the counts of the guarantees are its kind's own, and may be about
    flavors and placement: what the log's reservations were ``given``
    becomes plain data here, after the window."""
    from benchmark import deployment, driver

    t = time.monotonic()
    driver.given_as_data(replay.passes)
    audit = deployment.kind_of(cfg).audit(
        cfg, replay.arrivals, replay.preloaded, replay.passes)
    t_audit = time.monotonic() - t
    compared = {k: {"value": v, "limit": 0}
                for k, v in audit["counts"].items()}
    # nothing the window applied may be lost: every workload that came
    # is waiting, holds quota or has finished, in the store's own words
    store = replay.store
    arrived = set(replay.preloaded) | {
        key for p in replay.passes for kind, key, _d in p["events"]
        if kind == "arrive"}
    lost = sum(1 for k in arrived if k not in store.workloads)
    held_wrong = sum(
        1 for k in arrived if k in store.workloads
        and (store.workloads[k].is_quota_reserved
             and not store.workloads[k].is_finished)
        != (k in replay.holding))
    own = {"lost": lost + held_wrong, "refused": len(replay.failed)}
    own.update((k, max(0, v - fallbacks0.get(k, 0)))
               for k, v in fallback_counts().items())
    if set(own) & set(compared):
        raise ValueError(f"the kind's reference counts "
                         f"{sorted(set(own) & set(compared))}: the "
                         "harness's own names")
    compared.update((k, {"value": v, "limit": 0}) for k, v in own.items())
    t = time.monotonic()
    # the second witness, asked for by --twin and part of no verdict:
    # the program's own host-only scheduler fed the same log. Up to and
    # including the first pass in which a drain ran both sides start
    # from the same state; later passes inherit each side's history.
    twin = None
    if with_twin:
        first_drain = next((n for n, p in enumerate(replay.passes)
                            if p["drains"]), len(replay.passes) - 1)
        twin = {"to_first_drain": driver.replay_log(
                    replay.cfg, replay.arrivals, replay.preloaded,
                    replay.passes[:first_drain + 1]),
                "whole_log": driver.replay_log(
                    replay.cfg, replay.arrivals, replay.preloaded,
                    replay.passes)}
    t_twin = time.monotonic() - t
    ok = all(c["value"] <= c["limit"] for c in compared.values())
    detail = {"audit_first": audit["first"], "twin": twin,
              "audit_s": t_audit, "twin_s": t_twin,
              "holding_at_end": audit["holding"],
              "finished": audit["finished"]}
    return ok, compared, detail


# ---------------------------------------------------------------------------
# self-test of the trace reduction
# ---------------------------------------------------------------------------


def self_test() -> int:
    from benchmark import tracered

    sample = os.path.join(HERE, "data", "sample.xplane.pb")
    r = tracered.reduce_trace(sample)
    # the sample: three rounds of a 14.1 ms while_loop and a 10 us
    # cumsum on one v5e chip, 50 ms sleeps between them, in a 200 ms
    # window (tests/record_sample_trace.py)
    checks = {
        "one_device": r["devices"] == 1,
        "window_s": abs(r["window_s"] - 0.2001) < 0.001,
        "busy_s": 0.0405 < r["busy_s"] < 0.0430,
        "idle_share": 78.0 < 100 * (1 - r["busy_s"] / r["window_s"]) < 80.0,
        "top_op": r["device_ops"][0][0] == "%add_select_fusion.2"
        and 0.040 < r["device_ops"][0][1] < 0.043,
        "while_is_self_time": dict(r["device_ops"])["%while"] < 0.001,
        "gaps_named": [g[0] for g in r["idle_gaps"][:3]] == ["sleep"] * 3
        and all(0.050 < g[1] < 0.054 for g in r["idle_gaps"][:3]),
        "short_gaps": all(g[0] == "host_cycles"
                          for g in r["idle_gaps"][3:]),
        "cut_window": abs(tracered.reduce_trace(
            sample, window_s=0.1)["window_s"] - 0.1) < 1e-9,
        "union": tracered.union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]],
        "self_times": tracered.self_times(
            [(0, 10e9, "a"), (1e9, 4e9, "b"), (2e9, 3e9, "c")])
        == {"c": 1.0, "b": 2.0, "a": 7.0},
        "no_scopes_no_names": r["scope_s"] is None
        and r["program_spans"] == 0,
    }
    # the second sample: three rounds of a 400-step while_loop whose
    # body runs stage_search under vmap and stage_scan, a 30 ms sleep
    # inside the program's span ``entries`` between them, in a 184 ms
    # window (tests/record_sample_spans.py)
    r = tracered.reduce_trace(
        os.path.join(HERE, "data", "sample_spans.xplane.pb"))
    checks.update({
        "spans_busy_s": 0.0215 < r["busy_s"] < 0.0235,
        "program_spans": r["program_spans"] == 51,
        "gaps_named_by_program_span":
        [g[0] for g in r["idle_gaps"][:3]] == ["entries"] * 3,
        "ops_named_by_scope": r["device_ops"][0][0]
        == "round_body/stage_search:add_select_fusion",
        "scopes": set(r["scope_s"]) == {"stage_search", "stage_scan", ""}
        and abs(sum(r["scope_s"].values()) - r["busy_s"])
        < 0.01 * r["busy_s"],
        "scoped_share": 0.80 < r["scoped_share_of_listed"] < 0.85,
    })
    for k, v in checks.items():
        log(f"self-test {k}: {'ok' if v else 'FAILED'}")
    print(json.dumps({"self_test": all(checks.values()), "checks": checks}))
    return 0 if all(checks.values()) else 1


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    a = parse_args(argv)
    if a.self_test:
        return self_test()
    if not a.workload:
        raise SystemExit("--workload is required")
    bench = load_benchmark()
    cell, cfg_entry = find_cell(bench, a.workload)
    seconds = float(a.seconds if a.seconds is not None
                    else bench["run_seconds"])
    if not os.path.isdir(os.path.join(ROOT, "kueue_oss_tpu")):
        log("the program (kueue_oss_tpu/) is not in this checkout")
        return 3
    device = own_the_chip(cell["chips"], a.rehearse)

    import jax

    from kueue_oss_tpu import features
    from kueue_oss_tpu.obs import spans

    from benchmark import (deployment, driver, endtoend, progspans, readers,
                           tracered)

    cfg = deployment.load_config(cfg_entry["name"])
    kind = deployment.kind_of(cfg)
    if a.rehearse:
        cfg = deployment.scaled(cfg, a.cohorts, a.cqs_per_cohort,
                                a.count_div)
    #: what the program is given: the configuration, or a control's
    #: deployment that breaks one guarantee the configuration states
    deployed = cfg
    if a.control is not None:
        if a.control not in kind.controls:
            raise SystemExit(f"--control {a.control!r}: this cell's kind "
                             f"has {sorted(kind.controls)}")
        deployed = kind.controls[a.control][0](cfg)
    traffic = deployment.load_traffic(cell["traffic"])
    start_at = float(traffic["start_at_s"])
    # upstream's featureGates, as the kind states them: once, before
    # anything of the program is built (one process runs one cell)
    gates = driver.feature_gates(deployed)
    features.set_gates(gates)
    compiles = CompileCounter()
    fallbacks0 = fallback_counts()

    # -- set-up: schedule, twin warm-up, the cell's own deployment ------
    t = time.monotonic()
    arrivals = deployment.schedule(cfg, a.seed)
    twin_arrivals = deployment.schedule(cfg, a.seed + 1)
    t_schedule = time.monotonic() - t
    # the twin is replayed on the real clock until its first drain has
    # run: what it traces and loads is what the window then finds loaded
    warm = traffic.get("warmup", {})
    t = time.monotonic()
    twin = driver.Replay(deployed, twin_arrivals, solver="auto")
    caps = record_caps(twin.engine)
    twin.preload(start_at)
    twin.run(float(warm.get("max_seconds", seconds)),
             max_passes=warm.get("passes"),
             until_drains=warm.get("until_drains"))
    warm_info = {"passes": len(twin.passes),
                 "drains": twin.engine.drain_count,
                 "seconds": time.monotonic() - t,
                 "compiles": compiles.snapshot()}
    t = time.monotonic()
    variants = load_solve_variants(twin.engine, caps, warm.get("p_max", []))
    variants["seconds"] = time.monotonic() - t
    t = time.monotonic()
    prefilled = prefill_update_programs(twin.engine)
    prefilled["seconds"] = time.monotonic() - t
    prefilled["compiles"] = compiles.snapshot()
    del twin
    gc.collect()
    t = time.monotonic()
    replay = driver.Replay(deployed, arrivals, solver="auto")
    replay.preload(start_at)
    t_build = time.monotonic() - t
    gc.collect()
    rows = DrainRows()
    on_pass = rows.on_pass
    totals = None
    trace_dir = os.path.join(OUT, "trace")
    if a.trace:
        # the program's own account, in traced runs only: its totals
        # over the window, and its spans in the trace (the switch adds
        # +0.3 % of a pass: PERF.md, Findings, PR 24)
        totals = progspans.TotalsLog(spans)

        def on_pass(rec: dict) -> None:
            rows.on_pass(rec)
            totals.on_pass()

        spans.trace_on("benchmark")
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        replay.span = jax.profiler.TraceAnnotation
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        totals.start()

    c0 = compiles.snapshot()
    setup_s = time.monotonic() - T_PROCESS

    # -- the window -------------------------------------------------------
    win = replay.run(seconds, on_pass=on_pass)

    if a.trace:
        jax.profiler.stop_trace()
        spans.trace_off("benchmark")
    c1 = compiles.snapshot()
    peak = memory_peak_bytes()
    facts = window_facts(replay, win, rows.rows,
                         c1["requests"] - c0["requests"], totals)
    e2e, facts["window"], info = endtoend.measure(
        replay, win, facts["parts"], kind.top_class(cfg), setup_s)
    attempted = sum(len(p["events"]) for p in replay.passes)

    # -- the check, outside the window ------------------------------------
    ok, compared, detail = check(replay, cfg, fallbacks0, with_twin=a.twin)
    failed = int(compared["refused"]["value"] + compared["lost"]["value"])

    # -- the result ---------------------------------------------------------
    metrics: dict = {}
    breakdown = None
    device_out = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"], "memory_peak_bytes": peak}
    if a.trace:
        t = time.monotonic()
        if device["platform"] == "tpu":
            path = tracered.find_xplane(trace_dir)
            trace_bytes = os.path.getsize(path)
            facts["trace"] = tracered.reduce_trace(path, window_s=seconds)
            info["trace_bytes"] = trace_bytes
            tr = facts["trace"]
            if tr is not None:
                device_out["busy_s"] = tr["busy_s"]
                device_out["window_s"] = tr["window_s"]
                breakdown = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
                info["trace"] = {k: tr[k] for k in (
                    "device_events", "program_spans", "scope_s",
                    "scoped_share_of_listed")}
        shutil.rmtree(trace_dir, ignore_errors=True)
        info["trace_read_s"] = time.monotonic() - t
        for m in metrics_of(bench, "per_layer", cell["name"]):
            layer = deployment.load_json("layers", f"{m['name']}.json")
            value = readers.read(layer, facts)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in metrics_of(bench, "end_to_end", cell["name"]):
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    info.update({
        "cell": cell["name"], "seed": a.seed, "seconds": seconds,
        "feature_gates": gates, "e2e": e2e, "window": facts["window"],
        "passes": facts["passes"],
        "counters": facts["counters"], "phase_s": facts["phase_s"],
        "span_s": facts["span_s"], "idle_s": win["idle_s"],
        "overshoot_s": win["closed"] - win["t_end"],
        "drains": [{k: r[k] for k in ("pass", "rounds", "admitted",
                                      "evicted", "frame", "arm")}
                   | {"phases": r["phases"]} for r in rows.rows[:12]],
        "pass_walls_s": [p["t_end"] - p["t_start"]
                         for p in replay.passes[:12]],
        "setup": {"schedule_s": t_schedule, "warmup": warm_info,
                  "variants": variants, "prefilled": prefilled,
                  "build_s": t_build, "compile_cache": device["cache_dir"]},
        "compiles_window": {k: c1[k] - c0[k] for k in c1},
        "program": facts["program"],
        "check": detail, "control": a.control and {
            "name": a.control, "has_to_count": kind.controls[a.control][1]},
        "run_s": time.monotonic() - T_PROCESS})
    log(json.dumps({"info": info}))
    log(f"top class {info['top_class']}: {info['top_still_waiting']} of "
        f"{info['top_due']} due in the window still waited at its end")
    log("compared (value <= limit): " + ", ".join(
        f"{k}={c['value']}<={c['limit']}" for k, c in compared.items()))
    result = {"correct": bool(ok), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device_out}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    if a.rehearse:
        result["rehearsal"] = "cpu: no number here is a device number"
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
