#!/usr/bin/env python3
"""Chip smoke: the served admission path, once, on the TPU.

    python chip_smoke.py              # one chip: lean, served, sidecar, tas
    python chip_smoke.py --chips 4    # four chips: the mesh arm, nothing else

Drives the system's main path through the entry points a user calls
(``Store -> QueueManager -> Scheduler(solver="auto")``, the sidecar as
the deployment manifest starts it, ``SolverEngine.drain``) at the size
of the upstream large-scale deployment (10 cohorts x 100 ClusterQueues,
50,000 workloads) and of the upstream TAS topology (640 nodes), checks
the results against the host-only scheduler on the same data, and
checks that the DEVICE did the work: no degradation hop may hide a
chip whose compiler refused a kernel.

One JSON object per phase is printed; the last line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The exit code is 0 only if every phase passed on a TPU. Sizes are
arguments (defaults: full size) so the script can be rehearsed small on
the CPU, where it runs every check and then fails: no argument and no
environment variable lets it pass without a TPU. These are a smoke's
numbers (one reading each, compile included where it says so), not a
benchmark's.

A chip belongs to one process at a time, so this parent process never
imports jax or ``kueue_oss_tpu.solver``: each phase is a child process
(``--phase``) that owns the chip alone and exits before the next
starts. Nothing is retried on another backend.
"""

from __future__ import annotations

import argparse
import glob
import hashlib
import json
import os
import random
import re
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: phase -> seconds its child may take (cold compile included); the
#: whole run is additionally held to --deadline
PHASE_LIMIT_S = {"lean": 300, "twin": 600, "served": 900,
                 "sidecar": 600, "tas": 400, "mesh": 1500}


def emit(obj: dict) -> None:
    print(json.dumps(obj, sort_keys=True), flush=True)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run the mesh arm against the single-chip "
                         "arm and no other phase")
    ap.add_argument("--cohorts", type=int, default=10)
    ap.add_argument("--cqs-per-cohort", type=int, default=100)
    ap.add_argument("--workload-div", type=int, default=1,
                    help="divide every workload class's count (50 per "
                         "ClusterQueue at 1)")
    ap.add_argument("--churn-rounds", type=int, default=3)
    ap.add_argument("--churn", type=int, default=300,
                    help="workloads finished and submitted per round")
    ap.add_argument("--tas-workloads", type=int, default=15000,
                    help="TAS backlog (upstream: 15,000) over the "
                         "640-node topology")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "chip_smoke"),
        help="logs and per-round result sets land here")
    ap.add_argument("--deadline", type=float, default=1150.0,
                    help="seconds the whole run may take")
    ap.add_argument("--keep-going", action="store_true",
                    help="run the remaining phases after a failed one "
                         "(rehearsal); the result is a failure all the "
                         "same")
    # child mode (set by the parent, not by users)
    ap.add_argument("--phase", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--socket", default=None, help=argparse.SUPPRESS)
    return ap.parse_args(argv)


# ---------------------------------------------------------------------------
# parent: no jax, no kueue_oss_tpu.solver
# ---------------------------------------------------------------------------


class Parent:
    def __init__(self, a: argparse.Namespace) -> None:
        self.a = a
        self.t0 = time.monotonic()
        self.live: list[subprocess.Popen] = []
        os.makedirs(a.out, exist_ok=True)
        #: whether a native verify library was lying in the tree
        #: before any phase could build one
        self.native_prebuilt = bool(glob.glob(os.path.join(
            ROOT, "kueue_oss_tpu", "native", "_oracle-*.so")))

    def remaining(self) -> float:
        return self.a.deadline - (time.monotonic() - self.t0)

    def child_argv(self, phase: str, *extra: str) -> list[str]:
        a = self.a
        return [sys.executable, os.path.abspath(__file__),
                "--phase", phase, "--chips", str(a.chips),
                "--cohorts", str(a.cohorts),
                "--cqs-per-cohort", str(a.cqs_per_cohort),
                "--workload-div", str(a.workload_div),
                "--churn-rounds", str(a.churn_rounds),
                "--churn", str(a.churn),
                "--tas-workloads", str(a.tas_workloads),
                "--seed", str(a.seed), "--out", a.out, *extra]

    def spawn(self, name: str, argv: list[str],
              env: dict | None = None) -> subprocess.Popen:
        """Start a child in its own process group (so a hung one can be
        killed with everything it started); stderr goes to a log."""
        err = open(os.path.join(self.a.out, f"{name}.stderr"), "wb")
        proc = subprocess.Popen(
            argv, cwd=ROOT, env={**os.environ, **(env or {})},
            stdout=subprocess.PIPE, stderr=err, start_new_session=True)
        err.close()
        self.live.append(proc)
        return proc

    def stop(self, proc: subprocess.Popen, grace_s: float = 10.0) -> None:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGTERM)
                proc.wait(timeout=grace_s)
            except (ProcessLookupError, subprocess.TimeoutExpired):
                pass
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
        if proc in self.live:
            self.live.remove(proc)

    def stop_all(self) -> None:
        for proc in list(self.live):
            self.stop(proc)

    def collect(self, name: str, proc: subprocess.Popen,
                limit_s: float) -> dict:
        """Wait for a phase child; its result is the last JSON line of
        its stdout that names the phase. A child that dies, overruns
        or prints no such line failed."""
        limit_s = max(1.0, min(limit_s, self.remaining()))
        t0 = time.monotonic()
        try:
            out, _ = proc.communicate(timeout=limit_s)
        except subprocess.TimeoutExpired:
            self.stop(proc)
            return {"phase": name, "ok": False,
                    "error": f"no result within {limit_s:.0f}s"}
        if proc in self.live:
            self.live.remove(proc)
        result = None
        for line in out.decode("utf-8", "replace").splitlines():
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if isinstance(obj, dict) and obj.get("phase") == name:
                result = obj
        if result is None:
            result = {"phase": name, "ok": False,
                      "error": "child printed no result line"}
        if proc.returncode != 0:
            result["ok"] = False
            result.setdefault("error", f"child exited {proc.returncode}")
        if not result.get("ok"):
            result["stderr_tail"] = self.tail(f"{name}.stderr")
        result["phase_seconds"] = round(time.monotonic() - t0, 1)
        return result

    def tail(self, log: str, n: int = 1500) -> str:
        try:
            with open(os.path.join(self.a.out, log), "rb") as f:
                f.seek(max(0, os.fstat(f.fileno()).st_size - n))
                return f.read().decode("utf-8", "replace")
        except OSError:
            return ""

    def run_phase(self, name: str) -> dict:
        proc = self.spawn(name, self.child_argv(name))
        return self.collect(name, proc, PHASE_LIMIT_S[name])

    # -- the sidecar phase: two children, the manifest's topology ----------

    def run_sidecar(self, served: dict) -> dict:
        """deploy/manifests/base/manager.yaml: the ``solver`` container
        (``python -m kueue_oss_tpu.solver.service <socket>``) owns the
        chip; the ``manager`` container runs with JAX_PLATFORMS=cpu and
        reaches it over the socket."""
        sock = os.path.relpath(os.path.join(self.a.out, "solver.sock"),
                               ROOT)
        if os.path.exists(os.path.join(ROOT, sock)):
            os.unlink(os.path.join(ROOT, sock))
        before = cache_entries(served.get("cache_dir"))
        sidecar = self.spawn(
            "sidecar-solver",
            [sys.executable, "-m", "kueue_oss_tpu.solver.service", sock],
            # JAX's own account of what it compiled and what it loaded
            # from the persistent cache, on the sidecar's stderr
            env={"JAX_LOG_COMPILES": "1",
                 "JAX_DEBUG_LOG_MODULES": "jax._src.compiler"})
        try:
            t0 = time.monotonic()
            while not os.path.exists(os.path.join(ROOT, sock)):
                if sidecar.poll() is not None or (
                        time.monotonic() - t0 > 120):
                    return {"phase": "sidecar", "ok": False,
                            "error": "the sidecar did not open its "
                                     "socket",
                            "stderr_tail": self.tail(
                                "sidecar-solver.stderr")}
                time.sleep(0.2)
            manager = self.spawn(
                "sidecar", self.child_argv("sidecar", "--socket", sock),
                env={"JAX_PLATFORMS": "cpu"})
            result = self.collect("sidecar", manager,
                                  PHASE_LIMIT_S["sidecar"])
            result["sidecar_alive_at_end"] = sidecar.poll() is None
        finally:
            self.stop(sidecar)
        log = self.tail("sidecar-solver.stderr", n=1 << 24)
        hits = log.count("Persistent compilation cache hit for 'jit_solve'")
        misses = log.count(
            "PERSISTENT COMPILATION CACHE MISS for 'jit_solve'")
        after = cache_entries(served.get("cache_dir"))
        result.update(
            sidecar_solve_cache_hits=hits,
            sidecar_solve_cache_misses=misses,
            # compile-or-load seconds of each preemption program, in
            # the sidecar's own words, beside what they cost cold
            sidecar_solve_load_seconds=[float(x) for x in re.findall(
                r"Finished XLA compilation of jit\(solve\) in "
                r"([0-9.]+) sec", log)],
            served_cold_compile_seconds=[
                r["xla"]["seconds"] for r in served.get("rounds", ())
                if r["xla"]["cache_writes"]],
            sidecar_new_cache_entries=len(after - before),
            sidecar_stopped=sidecar.poll() is not None)
        checks = result.setdefault("checks", {})
        # the flagship program must come out of the cache the served
        # phase wrote: a hit for the solve, and no miss
        checks["first_compile_from_cache"] = hits >= 1 and misses == 0
        checks["sidecar_alive_until_stopped"] = bool(
            result.pop("sidecar_alive_at_end", False))
        checks["admitted_equals_served"] = same_rounds(
            self.a.out, "sidecar", "served", result)
        result["ok"] = bool(result.get("ok")) and all(checks.values())
        return result

    # -- orchestration -----------------------------------------------------

    def run(self) -> int:
        a = self.a
        results: list[dict] = []
        device = None

        def record(res: dict) -> bool:
            nonlocal device
            results.append(res)
            if device is None and res.get("device"):
                device = res["device"]
            emit(res)
            return bool(res.get("ok")) or a.keep_going

        try:
            if a.chips == 4:
                record(self.run_phase("mesh"))
            else:
                # cheapest failure first: the device check and the
                # seconds-long lean program before the minutes-long
                # preemption compile
                go = record(self.run_phase("lean"))
                twin = None
                if go:
                    # the plain reference runs beside the chip's
                    # compile: host-only, pinned off the chip
                    twin = self.spawn("twin", self.child_argv("twin"),
                                      env={"JAX_PLATFORMS": "cpu"})
                    served = self.run_phase("served")
                    twin_res = self.collect("twin", twin,
                                            PHASE_LIMIT_S["twin"])
                    emit(twin_res)
                    served.setdefault("native", {})[
                        "built_this_run"] = not self.native_prebuilt
                    checks = served.setdefault("checks", {})
                    checks["twin_ran"] = bool(twin_res.get("ok"))
                    checks["equals_host_twin"] = same_rounds(
                        a.out, "served", "twin", served)
                    served["ok"] = (bool(served.get("ok"))
                                    and all(checks.values()))
                    go = record(served)
                if go:
                    go = record(self.run_sidecar(served))
                if go:
                    record(self.run_phase("tas"))
        finally:
            self.stop_all()

        want = (["mesh"] if a.chips == 4
                else ["lean", "served", "sidecar", "tas"])
        ran = {r["phase"]: bool(r.get("ok")) for r in results}
        device = device or {"platform": "none", "kind": "none", "count": 0}
        ok = (all(ran.get(p) for p in want)
              and device["platform"] == "tpu"
              and device["count"] == a.chips)
        emit({"summary": True, "phases": ran,
              "seconds": round(time.monotonic() - self.t0, 1)})
        emit_last(ok, device)
        return 0 if ok else 1


def emit_last(ok: bool, device: dict) -> None:
    """The line the driver reads; nothing else goes in it."""
    print(json.dumps({"ok": ok, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)


def cache_entries(cache_dir) -> set:
    try:
        return set(os.listdir(cache_dir)) if cache_dir else set()
    except OSError:
        return set()


def same_rounds(out: str, got: str, want: str, into: dict) -> bool:
    """Compare two children's per-round admitted and evicted sets
    (``<phase>.sets.json``); notes the first difference in ``into``."""
    try:
        with open(os.path.join(out, f"{got}.sets.json")) as f:
            g = json.load(f)
        with open(os.path.join(out, f"{want}.sets.json")) as f:
            w = json.load(f)
    except (OSError, ValueError) as e:
        into["compare_error"] = repr(e)
        return False
    if not g or len(g) > len(w):
        into["compare_error"] = (f"{got} ran {len(g)} rounds, "
                                 f"{want} {len(w)}")
        return False
    for rg, rw in zip(g, w):
        for kind in ("admitted", "evicted"):
            sg, sw = set(rg[kind]), set(rw[kind])
            if rg["round"] != rw["round"] or sg != sw:
                into[f"first_difference_vs_{want}"] = {
                    "round": rg["round"], "set": kind,
                    f"only_{got}": sorted(sg - sw)[:3],
                    f"only_{want}": sorted(sw - sg)[:3],
                    "sizes": [len(sg), len(sw)]}
                return False
    return True


# ---------------------------------------------------------------------------
# children: each owns its backend alone
# ---------------------------------------------------------------------------


def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


class XlaCounters:
    """What JAX itself says it compiled: every backend compile request,
    the persistent-cache hits among them, the entries it wrote (a real
    compile that took long enough to be worth keeping), and the
    seconds spent compiling or loading."""

    def __init__(self) -> None:
        import jax

        self.requests = self.hits = self.written = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(
            self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.written += 1

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def snapshot(self) -> dict:
        return {"requests": self.requests, "cache_hits": self.hits,
                "compiled": self.requests - self.hits,
                "cache_writes": self.written,
                "seconds": round(self.seconds, 3)}

    def since(self, before: dict) -> dict:
        now = self.snapshot()
        return {k: round(now[k] - before[k], 3) for k in now}


def own_the_chip() -> tuple:
    """What every chip-owning child does first: place the compile
    cache, touch the device, start counting compiles. Returns
    (cache directory, device as JAX reports it, XlaCounters)."""
    from kueue_oss_tpu.util import xla_cache

    cache_dir = xla_cache.enable()
    device = device_info()
    xla = XlaCounters()
    observe_compiles()
    return cache_dir, device, xla


def observe_compiles() -> None:
    """Switch on the engine's per-drain compile accounting (obs/devtel,
    through its existing config): solver-drain ledger rows then carry
    ``device.compiles``."""
    from kueue_oss_tpu import obs
    from kueue_oss_tpu.config.configuration import (
        DevTelConfig,
        ObservabilityConfig,
    )

    obs.configure(ObservabilityConfig(devtel=DevTelConfig(enabled=True)))


def fallback_counts() -> dict:
    """Every degradation hop the process took: the fallback counter by
    slug (device_error, mesh_error, backend_error, plan_rejected,
    breaker_open, unsupported: whichever moved), the plan
    entries the oracle refused, and the ladder's active levels."""
    from kueue_oss_tpu import metrics, resilience

    by_slug = {"/".join(k): int(v) for k, v in
               metrics.solver_fallback_total.collect().items() if v}
    return {"solver_fallback_total": by_slug,
            "solver_plan_fallbacks_total": int(
                metrics.solver_plan_fallbacks_total.total()),
            "degradation_levels": {
                k: v for k, v in resilience.controller.levels().items()
                if v}}


def no_degradation(fb: dict) -> bool:
    return (not fb["solver_fallback_total"]
            and fb["solver_plan_fallbacks_total"] == 0
            and not fb["degradation_levels"])


def solver_rows() -> list:
    from kueue_oss_tpu import obs

    return [r for r in obs.cycle_ledger.rows()
            if r.kind == obs.SOLVER_DRAIN]


def row_summary(row) -> dict:
    dev = dict(row.device or {})
    # a preemption drain's row says which program it ran (its caps)
    # and whether it had to build it; a lean drain's has no such detail
    detail = dict(row.detail or {})
    return {"cycle": row.cycle, "arm": row.solver_arm,
            "h_max": detail.get("hMax"), "p_max": detail.get("pMax"),
            "program_builds": detail.get("programBuilds"),
            "frame": row.frame_kind, "frame_bytes": row.frame_bytes,
            "frame_reason": row.frame_reason, "rounds": row.rounds,
            "admitted": row.admitted, "evicted": row.evicted,
            "parked": row.parked, "phases": row.phases,
            "compiles": int(dev.get("compiles", 0)),
            "hbm_bytes_in_use": dev.get("hbm_bytes_in_use")}


def resident_platforms(engine) -> dict:
    """Where the tensors the drains used live: platform and device ids
    of every resident buffer, per resident state; and over how many
    devices the workload rows are really split (a buffer whose shards
    are each the whole array is replicated, whatever its sharding is
    called)."""
    import jax

    out = {}
    for kind, dev in engine._device_states.items():
        leaves = jax.tree_util.tree_leaves(dev.tensors)
        devices = set().union(*(leaf.devices() for leaf in leaves))
        split = [leaf for leaf in leaves if leaf.ndim and any(
            s.data.shape[0] < leaf.shape[0]
            for s in leaf.addressable_shards)]
        out[kind] = {"platforms": sorted({d.platform for d in devices}),
                     "device_ids": sorted(d.id for d in devices),
                     "buffers": len(leaves),
                     "row_split_buffers": len(split),
                     "row_split_over": min(
                         (len({s.device.id
                               for s in leaf.addressable_shards})
                          for leaf in split), default=0),
                     "mesh_placed": bool(dev.mesh_placed)}
    return out


def native_library() -> dict:
    """Load (building if absent) the native verify library from the
    oracle.cpp beside it."""
    from kueue_oss_tpu import native

    return {"loaded": native.load() is not None,
            "library": os.path.basename(native.lib_path())}


def quota_violations(store) -> list:
    """Nodes (ClusterQueues and cohorts) whose usage, recomputed from
    the admission records, exceeds what the quota algebra allows."""
    from kueue_oss_tpu.core.quota import QuotaForest
    from kueue_oss_tpu.persist.auditor import recompute_cq_usage

    forest = QuotaForest()
    forest.build(store.cluster_queues.values(), store.cohorts.values(),
                 cq_usage=recompute_cq_usage(store))
    return sorted(
        f"{name}:{fr[0]}/{fr[1]}"
        for name, node in forest.nodes.items()
        for fr, used in node.usage.items()
        if used and node.available(fr) < 0)


def large_scale_store(a, preemption: bool):
    """The upstream large-scale config at the sizes asked for, every
    workload pending; returns (store, workload count). Without
    preemption every ClusterQueue gets quota for its whole demand: the
    lean (fit-only) kernel's shape."""
    from kueue_oss_tpu.perf.generator import GeneratorConfig, generate

    cfg = GeneratorConfig.large_scale(preemption=preemption)
    cfg.n_cohorts, cfg.cqs_per_cohort = a.cohorts, a.cqs_per_cohort
    if not preemption:
        cfg.nominal_quota = 200
    for wc in cfg.classes:
        wc.count = max(1, wc.count // a.workload_div)
    store, schedule = generate(cfg)
    for g in schedule:
        store.add_workload(g.workload)
    return store, len(schedule)


class World:
    """The upstream large-scale deployment, its flood and its churn,
    driven identically on the device path and on the host-only twin:
    the same seed picks the same workloads to finish as long as the
    admitted sets agree, and the per-round sets show where they stop
    agreeing."""

    def __init__(self, a, **scheduler_kw) -> None:
        from kueue_oss_tpu.core.queue_manager import QueueManager
        from kueue_oss_tpu.scheduler.scheduler import Scheduler

        t0 = time.monotonic()
        self.store, self.n_workloads = large_scale_store(a, True)
        self.generate_seconds = round(time.monotonic() - t0, 3)
        self.n_cqs = a.cohorts * a.cqs_per_cohort
        self.queues = QueueManager(self.store)
        self.sched = Scheduler(self.store, self.queues, **scheduler_kw)
        self.rng = random.Random(a.seed)
        self.churn_n = a.churn
        self.now = 0.0
        self.rounds: list[dict] = []
        #: every workload admitted at the end of some round so far
        self.seated: set = set()

    def admitted(self) -> list:
        return sorted(k for k, w in self.store.workloads.items()
                      if w.is_quota_reserved and not w.is_finished)

    def evicted(self) -> list:
        """Victims: workloads that held a seat at the end of some
        earlier round and now carry the Evicted condition. (A workload
        the host admits and preempts again inside one round never held
        a seat the device path, which applies a drain's net plan, would
        have given it; the condition on such a workload is history,
        not state.)"""
        return sorted(k for k in self.seated
                      if self.store.workloads[k].is_evicted
                      and not self.store.workloads[k].is_finished)

    def settle(self, name: str, drain_first=None) -> dict:
        """Run to quiet and record the round. ``drain_first`` drives
        one ``SolverEngine.drain`` ahead of the scheduler's own loop
        (for rounds the router would keep on the host)."""
        t0 = time.monotonic()
        if drain_first is not None:
            drain_first.drain(now=self.now, verify=True)
        cycles = self.sched.run_until_quiet(now=self.now)
        admitted, evicted = self.admitted(), self.evicted()
        self.seated.update(admitted)
        rec = {"round": name, "admitted": admitted,
               "evicted": evicted, "host_cycles": cycles,
               "seconds": round(time.monotonic() - t0, 3)}
        self.rounds.append(rec)
        return rec

    def churn(self, i: int) -> int:
        """Finish ``churn`` admitted workloads and submit as many: two
        in three are the finished workload's shape again in its own
        queue (capacity freed and refilled); the third is an urgent one
        (higher priority, same size) sent to a ClusterQueue whose seat
        is taken, so that it has to preempt."""
        from kueue_oss_tpu.api.types import PodSet, Workload

        admitted = self.admitted()
        n = min(self.churn_n, len(admitted))
        picks = self.rng.sample(admitted, min(n + n // 3, len(admitted)))
        finish, crowd = picks[:n], picks[n:]
        self.now += 10.0
        for key in finish:
            self.sched.finish_workload(key, now=self.now)
        for j, key in enumerate(finish):
            old, boost = self.store.workloads[key], 0
            if j % 3 == 2 and crowd:
                old, boost = self.store.workloads[crowd.pop()], 100
            self.store.add_workload(Workload(
                name=f"churn{i}-{j}", queue_name=old.queue_name,
                priority=old.priority + boost, creation_time=self.now,
                podsets=[PodSet(count=ps.count, requests=dict(ps.requests))
                         for ps in old.podsets]))
        return n

    def dump_sets(self, out: str, phase: str) -> None:
        with open(os.path.join(out, f"{phase}.sets.json"), "w") as f:
            json.dump([{k: r[k] for k in ("round", "admitted", "evicted")}
                       for r in self.rounds], f)

    def summary(self) -> list:
        return [{"round": r["round"], "admitted": len(r["admitted"]),
                 "evicted": len(r["evicted"]),
                 "host_cycles": r["host_cycles"], "seconds": r["seconds"],
                 "digest": hashlib.sha256(
                     "\n".join(r["admitted"]).encode()).hexdigest()[:12]}
                for r in self.rounds]


#: churn rounds driven through ``SolverEngine.drain`` when the router
#: kept every churn round of its own on the host; the twin always runs
#: them too, so the rounds line up
DRIVEN_ROUNDS = 2


def phase_twin(a) -> dict:
    """The plain reference: the host scheduler alone, same data, same
    flood, same churn."""
    w = World(a)
    w.settle("flood")
    for i in range(a.churn_rounds + DRIVEN_ROUNDS):
        w.churn(i)
        w.settle(f"churn-{i}")
    w.dump_sets(a.out, "twin")
    return {"phase": "twin", "ok": True, "rounds": w.summary(),
            "quota_violations": quota_violations(w.store)}


def drive_served(a, w: World, xla: XlaCounters | None = None) -> dict:
    """Flood and churn through ``Scheduler(solver="auto")``; returns
    the facts both the in-process and the sidecar phase check. ``xla``
    counts each round's compiles where this process does the compiling
    (the manager in front of a sidecar has nothing to compile)."""
    per_round = []
    engine = w.sched._solver_engine()

    def settle(name: str, drain_first=None) -> None:
        n_rows = len(solver_rows())
        before = xla.snapshot() if xla else None
        rec = w.settle(name, drain_first)
        # each row names the caps its drain was compiled for: a drain
        # that compiles again shows both sets
        drains = [row_summary(r) for r in solver_rows()[n_rows:]]
        per_round.append({
            "round": name, "seconds": rec["seconds"],
            "driven_by": ("engine.drain" if drain_first is not None
                          else "router"),
            "router_chose": "device" if drains else "host",
            "host_cycles": rec["host_cycles"],
            "admitted_total": len(rec["admitted"]),
            "evicted_now": len(rec["evicted"]),
            "drains": drains,
            "xla": xla.since(before) if xla else None})

    settle("flood")
    for i in range(a.churn_rounds):
        w.churn(i)
        settle(f"churn-{i}")
    driven = not any(r["router_chose"] == "device" for r in per_round[1:])
    if driven:
        # the router kept every churn round on the host: drive the
        # device path directly, as README "Batched TPU drain" shows
        for i in range(a.churn_rounds, a.churn_rounds + DRIVEN_ROUNDS):
            w.churn(i)
            settle(f"churn-{i}", drain_first=engine)
    rows = [d for r in per_round for d in r["drains"]]
    checks = {
        "flood_drained_on_device": per_round[0]["router_chose"] == "device",
        "churn_drained_on_device": any(
            r["drains"] for r in per_round[1:]),
        "first_frame_sync": bool(rows) and rows[0]["frame"] == "sync",
        "later_frame_delta": any(d["frame"] == "delta" for d in rows[1:]),
    }
    return {"rounds": per_round, "checks": checks,
            "churn_rounds_driven_by_engine_drain": driven}


def phase_lean(a) -> dict:
    """The device check, then the cheapest real program: the lean
    (fit-only) drain of the large-scale backlog with quota for
    everything, through ``SolverEngine.drain``."""
    cache_dir, device, xla = own_the_chip()
    from kueue_oss_tpu.core.queue_manager import QueueManager
    from kueue_oss_tpu.solver.engine import SolverEngine

    store, n_workloads = large_scale_store(a, preemption=False)
    engine = SolverEngine(store, QueueManager(store))
    t0 = time.monotonic()
    result = engine.drain(now=0.0, verify=True)
    seconds = round(time.monotonic() - t0, 3)
    rows = [row_summary(r) for r in solver_rows()]
    fb = fallback_counts()
    resident = resident_platforms(engine)
    checks = {
        "device_is_tpu": device["platform"] == "tpu",
        "device_count": device["count"] == a.chips,
        "all_admitted": result.admitted == n_workloads,
        "arm_single": [r["arm"] for r in rows] == ["single"],
        "no_degradation": no_degradation(fb),
        "resident_on_tpu": bool(resident) and all(
            v["platforms"] == ["tpu"] for v in resident.values()),
    }
    return {"phase": "lean", "ok": all(checks.values()), "checks": checks,
            "device": device, "workloads": n_workloads,
            "cluster_queues": a.cohorts * a.cqs_per_cohort,
            "admitted": result.admitted, "rounds": result.rounds,
            "drain_seconds_compile_included": seconds,
            "drains": rows, "xla": xla.snapshot(), "fallbacks": fb,
            "resident": resident, "cache_dir": cache_dir,
            "peak_bytes_in_use": peak_bytes()}


def phase_served(a) -> dict:
    """The main path: the large-scale flood and churn through
    ``Scheduler(store, queues, solver="auto")`` in process."""
    cache_dir, device, xla = own_the_chip()
    native = native_library()
    w = World(a, solver="auto")
    facts = drive_served(a, w, xla)
    w.dump_sets(a.out, "served")
    engine = w.sched._solver_engine()
    fb = fallback_counts()
    resident = resident_platforms(engine)
    violations = quota_violations(w.store)
    drains = [d for r in facts["rounds"] for d in r["drains"]]
    checks = dict(facts["checks"])
    checks.update(
        device_is_tpu=device["platform"] == "tpu",
        device_count=device["count"] == a.chips,
        arm_single=bool(drains) and all(
            d["arm"] == "single" for d in drains),
        no_degradation=no_degradation(fb),
        resident_on_tpu=bool(resident) and all(
            v["platforms"] == ["tpu"] for v in resident.values()),
        native_library_loaded=native["loaded"],
        no_quota_violation=not violations)
    first = facts["rounds"][0]
    return {
        "phase": "served", "ok": all(checks.values()), "checks": checks,
        "device": device, "workloads": w.n_workloads,
        "cluster_queues": w.n_cqs,
        "generate_seconds": w.generate_seconds,
        "compile_seconds": first["xla"]["seconds"],
        "flood_seconds": first["seconds"],
        "rounds": facts["rounds"],
        "churn_rounds_driven_by_engine_drain": facts[
            "churn_rounds_driven_by_engine_drain"],
        "admitted": len(w.admitted()), "evicted": len(w.evicted()),
        "xla": xla.snapshot(), "fallbacks": fb, "resident": resident,
        "native": native, "quota_violations": violations[:5],
        "h_work_budget": engine.h_work_budget,
        "cache_dir": cache_dir, "peak_bytes_in_use": peak_bytes()}


def phase_sidecar(a) -> dict:
    """The manager container's side of the deployed topology: CPU
    pinned, solving through the socket. Must not initialize a backend
    of its own, nor size the sidecar's lanes from one."""
    from kueue_oss_tpu.config.configuration import SolverBackendConfig

    observe_compiles()
    w = World(a, solver="auto",
              solver_config=SolverBackendConfig(socket_path=a.socket))
    engine = w.sched._solver_engine()
    client = engine.remote
    facts = drive_served(a, w)
    w.dump_sets(a.out, "sidecar")
    from jax._src import xla_bridge

    fb = fallback_counts()
    violations = quota_violations(w.store)
    drains = [d for r in facts["rounds"] for d in r["drains"]]
    checks = dict(facts["checks"])
    checks.update(
        arm_remote=bool(drains) and all(
            d["arm"] == "remote" for d in drains),
        sidecar_backend_is_tpu=client.remote_platform == "tpu",
        no_degradation=no_degradation(fb),
        manager_initialized_no_backend=(
            not xla_bridge.backends_are_initialized()),
        manager_chose_no_lane_budget=engine.h_work_budget is None,
        no_quota_violation=not violations)
    first = facts["rounds"][0]
    return {
        "phase": "sidecar", "ok": all(checks.values()), "checks": checks,
        "workloads": w.n_workloads, "cluster_queues": w.n_cqs,
        "sidecar_platform": client.remote_platform,
        "sidecar_mesh_devices": client.remote_mesh_devices,
        "frames": dict(client.frames_by_kind),
        "frame_bytes": dict(client.bytes_by_kind),
        "first_solve_seconds_load_included": (
            first["drains"][0]["phases"].get("solve")
            if first["drains"] else None),
        "rounds": facts["rounds"],
        "churn_rounds_driven_by_engine_drain": facts[
            "churn_rounds_driven_by_engine_drain"],
        "admitted": len(w.admitted()), "evicted": len(w.evicted()),
        "fallbacks": fb, "quota_violations": violations[:5]}


def tas_store(n_workloads: int):
    """The upstream ``tas`` performance config: 640 nodes as 1 block x
    10 racks x 64 hosts (96 cpu each), the baseline's 5 cohorts x 6
    ClusterQueues over the one topology, and a backlog of required /
    preferred / unconstrained rack requests (BASELINE.md's TAS
    config row)."""
    from kueue_oss_tpu.api.types import (
        ClusterQueue,
        Cohort,
        FlavorQuotas,
        LocalQueue,
        Node,
        PodSet,
        PodSetTopologyRequest,
        ResourceFlavor,
        ResourceGroup,
        ResourceQuota,
        Topology,
        Workload,
    )
    from kueue_oss_tpu.core.store import Store

    host = "kubernetes.io/hostname"
    block = "cloud.provider.com/topology-block"
    rack = "cloud.provider.com/topology-rack"
    store = Store()
    store.upsert_topology(Topology(name="default",
                                   levels=[block, rack, host]))
    store.upsert_resource_flavor(ResourceFlavor(
        name="tas", topology_name="default"))
    for r in range(10):
        for h in range(64):
            store.upsert_node(Node(
                name=f"n-{r}-{h}", labels={block: "b0", rack: f"r{r}"},
                allocatable={"cpu": 96}))
    for c in range(5):
        store.upsert_cohort(Cohort(name=f"co{c}"))
        for qi in range(6):
            name = f"cq-{c}-{qi}"
            store.upsert_cluster_queue(ClusterQueue(
                name=name, cohort=f"co{c}",
                resource_groups=[ResourceGroup(
                    covered_resources=["cpu"],
                    flavors=[FlavorQuotas(name="tas", resources=[
                        ResourceQuota(name="cpu", nominal=20,
                                      borrowing_limit=100)])])]))
            store.upsert_local_queue(LocalQueue(
                name=f"lq-{c}-{qi}", cluster_queue=name))
    rng = random.Random(640)
    for i in range(n_workloads):
        cpu = (1, 5, 20)[rng.randrange(3)]
        mode = rng.randrange(3)
        tr = (PodSetTopologyRequest(required=rack) if mode == 0
              else PodSetTopologyRequest(preferred=rack) if mode == 1
              else PodSetTopologyRequest(unconstrained=True))
        c, qi = rng.randrange(5), rng.randrange(6)
        store.add_workload(Workload(
            name=f"w{i}", queue_name=f"lq-{c}-{qi}", uid=i + 1,
            creation_time=float(i),
            podsets=[PodSet(name="main", count=1, requests={"cpu": cpu},
                            topology_request=tr)]))
    return store


def tas_placements(store) -> dict:
    out = {}
    for key, wl in store.workloads.items():
        if not wl.is_quota_reserved:
            continue
        ta = wl.status.admission.podset_assignments[0].topology_assignment
        out[key] = (None if ta is None else sorted(
            (tuple(d.values), d.count) for d in ta.domains))
    return out


def host_tree_placements(store, keys: list) -> dict:
    """The plain reference for placement: the host topology tree
    (tas/snapshot.py) places the same workloads in the same order on an
    empty copy of the cluster, charging each placement before the
    next."""
    from kueue_oss_tpu.core.snapshot import build_snapshot
    from kueue_oss_tpu.core.workload_info import (
        effective_per_pod_requests,
    )
    from kueue_oss_tpu.tas.snapshot import TASPodSetRequest

    tree = build_snapshot(store).tas_flavors["tas"]
    out = {}
    for key in keys:
        wl = store.workloads[key]
        ps = wl.podsets[0]
        per_pod = effective_per_pod_requests(ps, wl.namespace)
        ta = tree.find_topology_assignments([TASPodSetRequest(
            podset=ps, single_pod_requests=per_pod, count=ps.count,
            flavor="tas")])[ps.name].assignment
        if ta is None:
            out[key] = None
            continue
        for d in ta.domains:
            tree.add_tas_usage(d.values, per_pod, d.count)
        out[key] = sorted((tuple(d.values), d.count) for d in ta.domains)
    return out


def phase_tas(a) -> dict:
    """Topology-aware placement on the device: quota through the
    kernel, placement through the sequential placer whose leaf pass is
    the Pallas kernel; placements must equal the host tree's."""
    cache_dir, device, xla = own_the_chip()
    from kueue_oss_tpu.core.queue_manager import QueueManager
    from kueue_oss_tpu.scheduler.scheduler import Scheduler
    from kueue_oss_tpu.solver import tas_engine
    from kueue_oss_tpu.solver.engine import SolverEngine

    # record, without changing, what the drain hands the placement
    # function: the check below lowers that function for those shapes
    calls = []
    placer_for = tas_engine.DeviceTASPlacer._placer_for

    def recording_placer_for(self, levels, bal_cap):
        placer, key = placer_for(self, levels, bal_cap)

        def place(*args):
            calls.append((placer, args))
            return placer(*args)

        return place, key

    tas_engine.DeviceTASPlacer._placer_for = recording_placer_for

    store = tas_store(a.tas_workloads)
    engine = SolverEngine(store, QueueManager(store))
    t0 = time.monotonic()
    result = engine.drain(now=0.0, verify=True)
    drain_seconds = round(time.monotonic() - t0, 3)
    got = tas_placements(store)

    t0 = time.monotonic()
    want = host_tree_placements(tas_store(a.tas_workloads),
                                result.admitted_keys)
    tree_seconds = round(time.monotonic() - t0, 3)
    differing = sorted(k for k in set(got) | set(want)
                       if got.get(k) != want.get(k))

    # the host SCHEDULER on the same backlog, for the record only: its
    # cycle nominates every head against the cycle-start tree and
    # defers the heads whose nominated domain an earlier admission of
    # the same cycle filled, so under cohort contention it lawfully
    # admits a different set than the one-pass sequential placement
    t0 = time.monotonic()
    store_h = tas_store(a.tas_workloads)
    Scheduler(store_h, QueueManager(store_h)).run_until_quiet(now=0.0)
    host_set = set(tas_placements(store_h))
    host_seconds = round(time.monotonic() - t0, 3)

    # "natively" is read from the program, not from the switch
    native_kernel = [
        "tpu_custom_call" in placer.lower(*args).compile().as_text()
        for placer, args in calls]
    fb = fallback_counts()
    rows = [row_summary(r) for r in solver_rows()]
    checks = {
        "device_is_tpu": device["platform"] == "tpu",
        "placer_ran": bool(calls),
        "pallas_leaf_kernel_native": bool(native_kernel) and all(
            native_kernel),
        "placements_equal_host_tree": not differing and bool(got),
        "all_placed_with_topology": bool(got) and all(
            v is not None for v in got.values()),
        "arm_single": bool(rows) and all(
            r["arm"] == "single" for r in rows),
        "no_degradation": no_degradation(fb),
        "no_quota_violation": not quota_violations(store),
    }
    return {"phase": "tas", "ok": all(checks.values()), "checks": checks,
            "device": device, "nodes": len(store.nodes),
            "backlog": a.tas_workloads,
            "backlog_cut_from": 15000 if a.tas_workloads != 15000 else None,
            "admitted": result.admitted,
            "placed": sum(v is not None for v in got.values()),
            "placement_batches": [int(args[2].shape[0])
                                  for _, args in calls],
            "first_difference_vs_host_tree": differing[:3],
            "host_scheduler_admitted": len(host_set),
            "host_scheduler_same_set": host_set == set(got),
            "drain_seconds_compile_included": drain_seconds,
            "host_tree_seconds": tree_seconds,
            "host_scheduler_seconds": host_seconds, "drains": rows,
            "xla": xla.snapshot(), "fallbacks": fb,
            "cache_dir": cache_dir, "peak_bytes_in_use": peak_bytes()}


def phase_mesh(a) -> dict:
    """Four chips: what ``mesh: auto`` gives a user on such a host. The
    lean and the preemption drain of the large-scale problem on the
    mesh arm and on the single-chip arm, in one process; the plans
    must be bit-identical and the resident state spread over every
    chip."""
    cache_dir, device, xla = own_the_chip()
    import numpy as np

    from kueue_oss_tpu import metrics
    from kueue_oss_tpu.core.queue_manager import QueueManager
    from kueue_oss_tpu.solver.engine import SolverEngine

    class RecordingEngine(SolverEngine):
        """Keeps the raw plan arrays each local solve returned."""

        def _local_solve(self, problem, frame, **kw):
            out = super()._local_solve(problem, frame, **kw)
            self.plans = getattr(self, "plans", []) + [out]
            return out

    def build(preemption: bool, arm: str) -> RecordingEngine:
        store, _ = large_scale_store(a, preemption)
        engine = RecordingEngine(store, QueueManager(store))
        if arm == "mesh":
            # the default floor (1,024 live rows) routes this size to
            # the mesh arm by itself; the rehearsal sizes need the pin
            engine.mesh_force = True
        else:
            # same mesh-aligned padding, single-chip arm
            engine.mesh_min_workloads = 1 << 62
        return engine

    report = {}
    checks = {"device_is_tpu": device["platform"] == "tpu",
              "device_count": device["count"] == a.chips}
    for kind, preemption in (("lean", False), ("full", True)):
        runs = {}
        for arm in ("mesh", "single"):
            engine = build(preemption, arm)
            before = xla.snapshot()
            t0 = time.monotonic()
            result = engine.drain(now=0.0, verify=True)
            runs[arm] = {
                "engine": engine, "result": result,
                "seconds_compile_included": round(
                    time.monotonic() - t0, 3),
                "xla": xla.since(before)}
        mesh_e, single_e = runs["mesh"]["engine"], runs["single"]["engine"]
        plan_m, plan_s = mesh_e.plans[-1], single_e.plans[-1]
        identical = (len(plan_m) == len(plan_s) and all(
            np.array_equal(x, y) for x, y in zip(plan_m, plan_s)))
        resident = resident_platforms(mesh_e)
        state = resident.get(kind + "-mesh", {})
        checks[f"{kind}_mesh_arm_served"] = (
            mesh_e.last_drain_arm == "mesh")
        checks[f"{kind}_single_arm_served"] = (
            single_e.last_drain_arm == "single")
        checks[f"{kind}_plans_bit_identical"] = identical
        checks[f"{kind}_same_admissions"] = (
            runs["mesh"]["result"].admitted_keys
            == runs["single"]["result"].admitted_keys
            and runs["mesh"]["result"].evicted_keys
            == runs["single"]["result"].evicted_keys)
        checks[f"{kind}_rows_split_over_{a.chips}_chips"] = (
            state.get("mesh_placed", False)
            and state.get("row_split_buffers", 0) > 0
            and state.get("row_split_over") == a.chips
            and state.get("platforms") == ["tpu"])
        report[kind] = {
            arm: {"admitted": r["result"].admitted,
                  "evicted": r["result"].evicted,
                  "rounds": r["result"].rounds,
                  "seconds_compile_included": r[
                      "seconds_compile_included"],
                  "xla": r["xla"]}
            for arm, r in runs.items()}
        report[kind]["resident"] = resident
        report[kind]["plan_rows"] = int(plan_m[0].shape[0])
    fb = fallback_counts()
    checks["no_mesh_error"] = (
        metrics.solver_fallback_total.value("mesh_error") == 0)
    checks["no_degradation"] = no_degradation(fb)
    return {"phase": "mesh", "ok": all(checks.values()), "checks": checks,
            "device": device, "workloads_per_cq": 50 // a.workload_div,
            "cluster_queues": a.cohorts * a.cqs_per_cohort, **report,
            "drains": [row_summary(r) for r in solver_rows()],
            "fallbacks": fb, "cache_dir": cache_dir,
            "peak_bytes_in_use": peak_bytes()}


PHASES = {"lean": phase_lean, "twin": phase_twin, "served": phase_served,
          "sidecar": phase_sidecar, "tas": phase_tas, "mesh": phase_mesh}


def main(argv=None) -> int:
    a = parse_args(argv)
    if a.phase is None:
        return Parent(a).run()
    sys.path.insert(0, ROOT)
    os.makedirs(a.out, exist_ok=True)
    emit(PHASES[a.phase](a))
    return 0


if __name__ == "__main__":
    sys.exit(main())
