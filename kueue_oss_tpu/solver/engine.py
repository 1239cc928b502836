"""Solver engine: export → jitted drain → apply plan to the store.

The engine is the TPU-native replacement for running the reference's Go
scheduler loop cycle-by-cycle: one invocation computes the admission plan
for the whole backlog. Each admission can optionally be re-verified against
the scalar oracle before committing (mirrors the safety pattern of
verifying solver plans before assuming, SURVEY.md §7 step 4).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from kueue_oss_tpu.api.types import (
    Admission,
    PodSetAssignment,
    PreemptionPolicyValue,
    TopologyAssignment,
    WorkloadConditionType,
)
from kueue_oss_tpu.core.queue_manager import QueueManager
from kueue_oss_tpu.core.store import Store
from kueue_oss_tpu.core.workload_info import WorkloadInfo
from kueue_oss_tpu import metrics, obs, resilience
from kueue_oss_tpu.obs import devtel, spans
from kueue_oss_tpu.solver.delta import (
    DeviceResidentProblem,
    HostDeltaSession,
)
from kueue_oss_tpu.solver.kernels import solve_backlog, to_device
from kueue_oss_tpu.solver.resilience import SolverHealth, SolverUnavailable
from kueue_oss_tpu.solver.tensors import (
    ExportCache,
    SolverProblem,
    UnsupportedProblem,
    export_problem,
    pad_workloads,
    pow2,
)
from kueue_oss_tpu.persist import hooks as persist_hooks


@dataclass
class DrainResult:
    admitted: int = 0
    evicted: int = 0
    rounds: int = 0
    solver_time_s: float = 0.0
    apply_time_s: float = 0.0
    #: workload keys admitted, in (round, entry-order) sequence
    admitted_keys: list[str] = field(default_factory=list)
    #: initially-admitted workload keys preempted by the drain
    evicted_keys: list[str] = field(default_factory=list)
    #: the victim search's liveness gate over the drain's rounds
    #: (full_kernels._run_searches): lanes offered, and lanes that ran
    #: the heavy search; 0 for a lean drain and for a sidecar's, whose
    #: wire carries the plan alone
    search_lanes: int = 0
    search_live_lanes: int = 0
    #: the entry scan's victim branch over the drain's rounds
    #: (full_kernels.full_round_scan): active entries scanned, and
    #: entries with victims to book; 0 where the search counts are
    scan_entries: int = 0
    scan_victim_entries: int = 0
    #: the static caps of the program a full drain ran (_size_caps; a
    #: local drain may run one candidate width up, _full_program), the
    #: preemption programs it had to build (full_kernels.full_solver on
    #: a miss of its cache), and of their executables those it traced
    #: and those it loaded from the program store (solver/
    #: program_store.py): 0 in a drain that reused one, and in a
    #: sidecar's, which builds in its own process; 0 caps in a lean drain
    h_max: int = 0
    p_max: int = 0
    program_builds: int = 0
    program_traces: int = 0
    program_loads: int = 0


class SolverEngine:
    """Drains pending backlogs through the jitted TPU kernel."""

    def __init__(self, store: Store, queues: QueueManager,
                 scheduler=None, enable_fair_sharing: bool = False,
                 remote=None, health: Optional[SolverHealth] = None,
                 mesh_mode: Optional[str] = None) -> None:
        self.store = store
        self.queues = queues
        #: host scheduler whose eviction state machine applies the plan's
        #: preemptions (metrics/backoff parity); built lazily if absent
        self.scheduler = scheduler
        #: fair-sharing mode (KEP-1714): DRS tournament entry ordering +
        #: fair preemption strategies, on-device via
        #: solver/fair_kernels.py. Mirrors Scheduler(enable_fair_sharing).
        self.enable_fair_sharing = enable_fair_sharing
        #: optional solver/service.SolverClient — the solve runs in a
        #: separate sidecar process (SURVEY §2.4); export, verify, and
        #: commit stay in this process
        self.remote = remote
        if remote is None:
            # this process compiles the solver programs: minutes for
            # the flagship drain, paid once per machine
            from kueue_oss_tpu.util import xla_cache

            xla_cache.enable()
        #: circuit breaker over the remote backend: a tripped breaker
        #: short-circuits drains into SolverUnavailable (host-cycle
        #: fallback) instead of re-probing a dead sidecar every pass
        self.health = health if health is not None else SolverHealth()
        #: pad the workload axis to at least this size before solving.
        #: Callers that drain repeatedly while the backlog grows (the
        #: scheduler serve loop, the perf Simulator) set it to the
        #: expected peak so every drain reuses ONE compiled program
        #: instead of recompiling at each power-of-two crossing.
        self.pad_to = 0
        #: cross-drain export memo (event-invalidated); repeated drains
        #: assemble the problem with vectorized gathers instead of
        #: per-workload Python loops
        self.export_cache = ExportCache(store)
        #: (spec_gen, ceilings) memo backing flavor_witness()
        self._flavor_witness_cache: Optional[tuple[int, dict]] = None
        #: sticky pad high-water mark: the padded workload axis never
        #: shrinks, so a backlog oscillating around a power-of-two
        #: boundary (pending + admitted crossing pad_to) can't flap
        #: between two compiled programs — recompiles are monotone
        #: crossings only
        self._pad_hwm = 0
        #: the last export's columnar walk/scatter split and dirty-row
        #: stats, for the drain's ledger row (_note_export_stats)
        self._export_split: dict = {}
        self._export_stats: dict = {}
        #: production device-TAS path: TAS CQs whose backlog shapes the
        #: extended placer supports drain through the quota kernel and
        #: place on device (solver/tas_engine.py); set False to force
        #: the pre-round-5 host-only TAS behavior
        self.device_tas = True
        self._tas_placer = None
        #: placer programs the drain under way had to build
        self._tas_builds = 0
        #: TAS CQs admitted to the device path for the CURRENT drain
        #: (computed by pending_backlog, read by the apply path)
        self._drain_tas_ready: set[str] = set()
        #: victim-search lanes per round, throughput mode: lanes sized
        #: to the CQ count (host-cycle parity — no head deferral) up to
        #: this cap. Narrow lanes lower per-round latency, wide lanes
        #: cut round counts ~10x on park-heavy shapes (see _size_caps).
        self.h_max_cap = 1024
        #: per-round search-work budget in lane-option-group units
        #: (each lane runs K x g victim searches). None = the budget
        #: of the backend that SOLVES (full_kernels.lane_work_budget):
        #: asked of this process at the first local drain, and left to
        #: the sidecar when the solve is remote — the control plane
        #: never initializes a backend to size lanes for a device it
        #: does not own.
        self.h_work_budget = None
        #: total drains started; the obs cycle id for engines used
        #: standalone (no scheduler whose cycle_count anchors the drain)
        self.drain_count = 0
        #: cycle id tagged on this drain's DecisionEvents and spans — the
        #: host cycle the drain serves (scheduler.cycle_count + 1), so a
        #: merged trace groups the drain with the cycle it replaced
        self._drain_cycle = 0
        #: delta-sync sessions (docs/SOLVER_PROTOCOL.md): successive
        #: drains re-encode the padded problem into a stable slot space
        #: and ship only the dirty-row delta; the sidecar (remote) or
        #: the resident device buffers (in-process) hold the rest. One
        #: session per kernel kind — lean and full exports differ.
        import os as _os

        self.use_sessions = _os.environ.get(
            "KUEUE_SOLVER_SESSIONS") != "0"
        self._delta_sessions: dict[str, HostDeltaSession] = {}
        #: in-process resident device tensors keyed by session epoch, so
        #: the non-remote path stops re-uploading the full problem too
        self._device_states: dict[str, DeviceResidentProblem] = {}
        #: single worker for pipelined drain dispatch: the remote solve
        #: round-trip overlaps host-side apply prework
        self._solve_pool = None
        #: apply prework computed during the overlap window (consumed
        #: and cleared by the apply paths)
        self._prework: Optional[dict] = None
        #: mesh-sharded drains (solver/sharded.py): mesh mode string
        #: from SolverBackendConfig.mesh / KUEUE_SOLVER_MESH — "auto"
        #: (default; mesh when jax.device_count() > 1), "off", or an
        #: explicit device count. The mesh itself resolves lazily.
        self.mesh_mode = mesh_mode
        self._mesh_obj = None
        self._mesh_resolved = False
        #: chaos/device-loss cap on mesh width (refresh_mesh)
        self._mesh_max_devices = 0
        #: a mesh drain fault (device loss, compile failure) raises the
        #: ``mesh_broken`` condition on the degradation controller;
        #: drains degrade to single-chip until refresh_mesh() re-probes
        #: or the retry cooldown elapses (timed half-open, owned by the
        #: controller's unified CooldownPolicy — a transient fault must
        #: not disable the mesh for the process lifetime). The
        #: _mesh_broken/_mesh_broken_at names survive as properties
        #: over the controller state.
        self._mesh_broken = False
        self.mesh_retry_cooldown_s = 300.0
        #: backlogs below this stay single-chip: the mesh is the
        #: LARGE-backlog path — tiny problems would pay per-shape SPMD
        #: compiles for collectives they cannot amortize
        self.mesh_min_workloads = 1024
        #: pin drains to the mesh arm regardless of cost estimates
        #: (chip_smoke.py's mesh phase + parity tests; never set in
        #: production — the whole point of the EMA router is measured
        #: routing)
        self.mesh_force = False
        #: adaptive arm routing: measured solve wall PER EXPORTED
        #: WORKLOAD by (kernel kind, arm in {"single", "mesh"}); the
        #: mesh arm engages only while its measured wall beats the
        #: single-chip arm's (each arm is probed once, the losing arm
        #: decays so a regressing winner gets re-measured). The HOST arm
        #: of the triple lives in the scheduler's _drain_cost_ema /
        #: _host_s_per_adm gate, which prices whatever arm ran here
        #: against host cycles.
        self._arm_ema: dict[tuple[str, str], float] = {}
        #: arms whose compile-tainted first sample was discarded: the
        #: probe drain pays one-time SPMD compilation + the full
        #: resident upload, which would inflate the EMA ~100x and latch
        #: the router against the arm; only warm samples are recorded
        self._arm_warm: set[tuple[str, str]] = set()
        #: chaos injection point: called with the arm name ("mesh" /
        #: "single") right before each local solve; raising
        #: simulates a device loss on that arm (kueue_oss_tpu/chaos
        #: MeshFaultInjector)
        self.solve_fault_hook = None
        #: arm that served the most recent local solve (diagnostics)
        self.last_drain_arm: Optional[str] = None
        #: candidate width of the preemption program the last local
        #: full drain ran (_full_program)
        self.last_p_max = 0
        #: streaming micro-batch admitter (scheduler/streaming.py);
        #: every completed full drain re-arms its fences — a full
        #: solve is the oracle-parity baseline boundary
        self.streaming = None

    def supported(self) -> bool:
        """Whether the drain can run on-device.

        The full kernel covers classical preemption, multiple resource
        groups, fair sharing (DRS tournament + S2-a/S2-b), and
        admission fair sharing (KEP-4136: penalty-ordered head
        selection with entry penalties charged on admission). TAS
        shapes are rejected at export (UnsupportedProblem).
        """
        return True

    def needs_full_kernel(
            self,
            pending: Optional[dict[str, list[WorkloadInfo]]] = None,
    ) -> bool:
        """Preemption, multi-RG, fair-sharing, or AFS shapes run the
        unified-axis kernel; the lean fit-only kernel stays for the
        uncontended classical case.

        With `pending` (the drain's backlog), only CQs that are
        actually ADMITTING this drain are consulted: preemption is
        initiated by the admitting CQ under its own policies, so idle
        preemption-enabled CQs elsewhere in the store must not route an
        uncontended flood off the lean fast path (round-4 verdict: the
        store-global check cost uncontended backlogs ~3x)."""
        if self.enable_fair_sharing:
            return True
        if pending is not None:
            cqs = [self.store.cluster_queues[name]
                   for name in pending
                   if name in self.store.cluster_queues]
        else:
            cqs = list(self.store.cluster_queues.values())
        for cq in cqs:
            if cq.preemption.any_enabled:
                return True
            if len(cq.resource_groups) > 1:
                return True
            if (cq.admission_scope is not None
                    and self.queues.afs is not None):
                return True
        return False

    def _is_tas_cq(self, cq_name: str) -> bool:
        """Any flavor with a Topology makes admissions TAS-placed (explicit
        or implied requests — flavor_assigner workload_topology_requests);
        those need the host tree, so the solver leaves them pending."""
        spec = self.store.cluster_queues.get(cq_name)
        if spec is None:
            return False
        for rg in spec.resource_groups:
            for fq in rg.flavors:
                fl = self.store.resource_flavors.get(fq.name)
                if fl is not None and fl.topology_name is not None:
                    return True
        return False

    def flavor_witness(self) -> dict[str, dict]:
        """Per-CQ static flavor-option capacity ceilings for the
        streaming flavor-pick witness, cached by ``ExportCache.spec_gen``
        (any quota/flavor/cohort edit invalidates it together with the
        export tensors it mirrors). The streaming admitter combines
        these with the post-solve window snapshot to decide whether a
        multi-flavor pick could be flipped by a capacity event
        (tensors.flavor_option_ceilings, scheduler/streaming.py)."""
        gen = self.export_cache.spec_gen
        cached = self._flavor_witness_cache
        if cached is not None and cached[0] == gen:
            return cached[1]
        from kueue_oss_tpu.solver.tensors import flavor_option_ceilings

        witness = flavor_option_ceilings(self.store)
        self._flavor_witness_cache = (gen, witness)
        return witness

    def pending_backlog(self) -> dict[str, list[WorkloadInfo]]:
        """Current heap contents per CQ in rank (pop) order, plus stale
        parked entries owed a retry (lazy capacity-freed flushes merge
        into the backlog virtually instead of re-heaping — the rank
        order is the same _order_key sort a physical flush produces).

        TAS-shaped workloads (explicit topology requests, podset groups,
        or any CQ whose flavors carry a Topology) are excluded: the
        kernel admits without computing topology assignments, so those
        stay in their heaps for the host scheduler's mop-up cycles
        (Scheduler.run_until_quiet after _solver_drain), which run the
        full TAS machinery. Stale TAS entries are materialized back into
        their heaps for the same host path."""
        from kueue_oss_tpu.core.queue_manager import _order_key

        out: dict[str, list[WorkloadInfo]] = {}
        self._drain_tas_ready = set()
        for name, q in self.queues.queues.items():
            if not q.active:
                continue
            if self._is_tas_cq(name):
                if not self._tas_device_ready(name, q):
                    q.materialize_stale()
                    continue
                # device-TAS path: quota through the kernel, placement
                # through the sequential device placer at apply time
                self._drain_tas_ready.add(name)
                stale = q.stale_infos() if q._stale else []
                infos = q.snapshot_order()
                if stale:
                    infos = sorted(infos + stale, key=_order_key)
                if infos:
                    out[name] = infos
                continue
            stale = q.stale_infos() if q._stale else []
            if stale and any(ps.topology_request is not None
                             for i in stale for ps in i.obj.podsets):
                # hand topology-requesting stale entries (and their
                # queue-mates, to keep one rank order) to the host path
                q.materialize_stale()
                stale = []
            infos = q.snapshot_order()
            if stale:
                infos = sorted(infos + stale, key=_order_key)
            infos = [i for i in infos
                     if all(ps.topology_request is None
                            for ps in i.obj.podsets)]
            if infos:
                out[name] = infos
        return out

    def _tas_device_ready(self, name: str, q) -> bool:
        """Whether this TAS CQ's ENTIRE backlog (heap + parked) is
        device-placeable. All-or-nothing per CQ keeps StrictFIFO head
        order exact: exporting followers around an unsupported head
        would let the kernel admit past a blocked head."""
        if not self.device_tas:
            return False
        spec = self.store.cluster_queues.get(name)
        if spec is None:
            return False
        from kueue_oss_tpu.solver.tas_engine import device_tas_supported

        for info in list(q._in_heap.values()) + list(
                q.inadmissible.values()):
            if not device_tas_supported(info, self.store, spec):
                return False
        return True

    def _compute_tas_assignments(self, candidates, snapshot=None):
        """Device-place admitted TAS candidates in admission order.

        Returns (kept_candidates, topology_by_workload_key). A
        candidate whose placement failed is not committed: it was
        planned lawfully (the kernel seats by quota, and quota it had),
        so this is no refused plan entry and no fallback; its quota is
        never charged, it stays in its heap for the host cycle after
        the drain, and the rest of the plan stands (counter
        ``tas_place_failed``). ``snapshot`` is the pipelined-dispatch
        prework (lean drains) or the one the full path built after its
        evictions."""
        tas_items = []
        for cand in candidates:
            _wl, cq_name, flavor_of, info, _usage = cand
            if cq_name in self._drain_tas_ready and flavor_of:
                flavor = (next(iter(flavor_of.values()))
                          if isinstance(flavor_of, dict) else flavor_of)
                tas_items.append((info, flavor))
        if not tas_items:
            return candidates, {}
        from kueue_oss_tpu.core.snapshot import build_snapshot
        from kueue_oss_tpu.solver.tas_engine import DeviceTASPlacer

        with spans.span("apply.tas_place"):
            if self._tas_placer is None:
                self._tas_placer = DeviceTASPlacer(self.store)
            if snapshot is None:
                snapshot = build_snapshot(self.store)
            placements = self._tas_placer.place_batch(snapshot, tas_items)
        self._tas_builds += self._tas_placer.last_builds
        # only candidates actually submitted for placement can fail out
        # of the plan; a TAS-CQ candidate with no flavored resources has
        # no TAS request at all (workload_topology_requests skips empty
        # psa.flavors) and commits without an assignment — host parity
        submitted = {info.key for info, _ in tas_items}
        kept = []
        topo_of: dict[str, TopologyAssignment] = {}
        for cand in candidates:
            _wl, cq_name, _f, info, _usage = cand
            if cq_name in self._drain_tas_ready and info.key in submitted:
                ta = placements.get(info.key)
                if ta is None:
                    obs.recorder.record(
                        obs.SKIPPED, info.key,
                        cycle=self._drain_cycle, cluster_queue=cq_name,
                        path=obs.SOLVER,
                        reason="the device placer found no topology "
                               "assignment; the quota is not charged and "
                               "the workload stays queued for the host "
                               "cycle",
                        reason_slug="tas_place_failed")
                    continue  # the host cycle places (or parks) it
                topo_of[info.key] = ta
            kept.append(cand)
        placed, failed = len(topo_of), len(submitted) - len(topo_of)
        spans.count("tas_device_placements", placed)
        spans.count("tas_place_failed", failed)
        spans.count("tas_placements", placed + failed)
        return kept, topo_of

    def export(
            self,
            pending: Optional[dict[str, list[WorkloadInfo]]] = None,
    ) -> tuple[SolverProblem, dict[str, list[WorkloadInfo]]]:
        if pending is None:
            pending = self.pending_backlog()
        problem = export_problem(self.store, pending,
                                 cache=self.export_cache)
        return problem, pending

    def drain(self, now: float = 0.0, verify: bool = False) -> DrainResult:
        """Solve the whole backlog on-device and commit the plan.

        Preemption-capable and multi-resource-group stores route through
        the full kernel (solve_backlog_full) so preemption shapes are
        never silently solved fit-only; the lean kernel keeps the
        uncontended fast path.
        """
        if not self.supported():
            raise UnsupportedProblem(
                "admission-scope or weighted fair-sharing CQs present")
        self.drain_count += 1
        self._drain_cycle = (self.scheduler.cycle_count + 1
                             if self.scheduler is not None
                             else self.drain_count)
        if self.remote is not None and not self.health.allow():
            # open breaker: refuse without touching the socket so the
            # admission round proceeds on the host path immediately
            metrics.solver_fallback_total.inc("breaker_open")
            obs.recorder.record(
                obs.SOLVER_FALLBACK, obs.CYCLE_SCOPE,
                cycle=self._drain_cycle, path=obs.SOLVER,
                reason="solver backend breaker is open (cooling down); "
                       "admissions degrade to the host cycle",
                reason_slug="breaker_open")
            raise SolverUnavailable(
                "solver backend breaker is open (cooling down)")
        # the drain's span collects its children's durations: they are
        # the solver ledger row's ``phases`` (obs/spans.py)
        with spans.span("solver_drain", cycle=self._drain_cycle,
                        collect=True):
            completed = False
            if self.streaming is not None:
                # mark which fences this solve's export can cover:
                # events landing mid-solve keep their subtree fenced
                # past note_full_solve (the solve never saw them)
                self.streaming.note_solve_begin()
            try:
                result = self._drain(now, verify)
                completed = True
                return result
            finally:
                # prework computed for a drain that failed before its
                # apply must never leak into the next drain (stale
                # workload refs would bypass the store lookups)
                self._prework = None
                # durability barrier: a drain's plan applications are
                # group-committed before the scheduler builds on them
                persistence = getattr(self.store, "persistence", None)
                if persistence is not None:
                    with spans.span("record"):
                        persistence.flush()
                if self.streaming is not None:
                    # full-solve boundary: the streaming fences reset
                    # against the post-solve store (a failed drain
                    # keeps them down — host fallback cycles are not
                    # a parity baseline — but must stop attributing
                    # events to the dead solve)
                    if completed:
                        self.streaming.note_full_solve()
                    else:
                        self.streaming.note_solve_abort()

    def _drain(self, now: float, verify: bool) -> DrainResult:
        with spans.span("backlog"):
            pending = self.pending_backlog()
            full = self.needs_full_kernel(pending)
        if full:
            return self._drain_full(now, verify=verify, pending=pending)
        result = DrainResult()
        with spans.span("export"):
            problem, pending = self.export(pending)
            self._note_export_stats()
        if problem.n_workloads == 0:
            return result
        # pad_workloads rebuilds the dataclass, so the columnar hint
        # must be captured off the unpadded export (real-row positions
        # survive padding; the hint's row indices stay valid)
        hint = getattr(problem, "_columnar_hint", None)
        n_live = problem.n_workloads
        self._pad_hwm = max(self._pad_hwm,
                            pow2(max(problem.n_workloads, self.pad_to)))
        problem = pad_workloads(problem, self._pad_target())
        problem, frame = self._session_encode("lean", problem, hint=hint)
        dev0 = self._device_totals()

        # forced: DrainResult returns these two durations
        with spans.span("solve", force=True) as sp:
            if self.remote is not None:
                (admitted, opt, admit_round, parked, rounds,
                 _usage) = self._dispatch_remote(
                    problem, 6, frame, "lean", verify, full=False)
            else:
                (admitted, opt, admit_round, parked, rounds,
                 _usage) = self._local_solve(problem, frame, full=False,
                                             n_live=n_live)
            admitted = np.asarray(admitted)
            opt = np.asarray(opt)
            admit_round = np.asarray(admit_round)
            parked = np.asarray(parked)
            if self.remote is not None:
                # guard IMPORTED plans only: the in-process kernel is
                # trusted (a local bug should fail tests loudly, not
                # silently degrade), and the local hot path stays free
                # of the O(W) validation passes
                self._check_plan(problem, admitted, opt, admit_round,
                                 parked, rounds=rounds, full=False)
            result.rounds = int(rounds)
        result.solver_time_s = sp.seconds

        with spans.span("apply", force=True) as sp:
            self._apply_plan(problem, admitted, opt, admit_round, parked,
                             now, result, verify=verify)
        result.apply_time_s = sp.seconds
        result.program_builds, self._tas_builds = self._tas_builds, 0
        spans.count("drain_admitted", result.admitted)
        spans.count("solver_program_builds", result.program_builds)
        with spans.span("record"):
            self._ledger_record(
                result, frame, "lean", dev0,
                parked_n=int(np.asarray(
                    parked[:problem.n_workloads]).astype(bool).sum()))
        return result

    # -- cycle ledger (obs/ledger.py) --------------------------------------

    def _device_totals(self) -> dict:
        """Cumulative donated-buffer accounting across every resident
        device state (both arms); the ledger records per-drain DELTAS
        of these."""
        totals = {"donated_update_bytes": 0, "avoided_copy_bytes": 0,
                  "full_upload_bytes": 0, "donated_full_syncs": 0}
        for dev in self._device_states.values():
            for k in totals:
                totals[k] += int(getattr(dev, k, 0))
        return totals

    def _resident_bytes(self) -> int:
        """Problem bytes pinned on device right now, summed over every
        resident state (both kernels, both arms) — devtel's portable
        HBM watermark."""
        return sum(int(dev.resident_bytes())
                   for dev in self._device_states.values()
                   if hasattr(dev, "resident_bytes"))

    def _ledger_record(self, result: DrainResult, frame, kind: str,
                       dev0: dict, parked_n: int) -> None:
        """One solver ledger row per drain, keyed by the same cycle id
        the recorder's DecisionEvents carry — solver routing, session
        wire kind/bytes, resident-buffer churn, and (devtel) the
        drain's transfer/HBM/compile/grant-wait telemetry in one
        record."""
        ledger = obs.cycle_ledger
        dev1 = self._device_totals()
        device = {k: dev1[k] - dev0.get(k, 0)
                  for k in dev1 if dev1[k] - dev0.get(k, 0)}
        arm = ("remote" if self.remote is not None
               else (self.last_drain_arm or "single"))
        tenant = getattr(self.remote, "tenant", "")
        dtl = devtel.collector
        if dtl.enabled:
            # unified transfer family + per-drain HBM watermark; the
            # gauges/counters flow even with the ledger disabled (the
            # bench twin's off arm disables the ledger, not devtel)
            dtl.note_transfers(arm, tenant, device)
            if self.remote is None:
                # HBM is the device owner's to report: a control plane
                # in front of a sidecar holds nothing resident and has
                # no backend to ask (asking would initialize one)
                device.update(
                    dtl.sample_residency(self._resident_bytes()))
            events = dtl.compiles.drain_events()
            if events:
                device["compiles"] = len(events)
                device["compile_events"] = events
            dtl.on_drain()
        if not ledger.enabled:
            return
        frame_kind, frame_bytes, frame_reason, session = "legacy", 0, "", {}
        if frame is not None:
            session = dict(frame.stats or {})
            if frame.delta is not None:
                frame_kind = "delta"
                frame_bytes = int(frame.delta.payload_bytes())
            else:
                frame_kind = "sync"
                frame_reason = frame.full_reason or ""
                sess_obj = self._delta_sessions.get(kind)
                if sess_obj is not None:
                    frame_bytes = sess_obj.last_sync_wire_bytes()
        # the durations of the drain's spans so far, by name (backlog,
        # export, encode, solve with device_put / dispatch / wait /
        # fetch inside it, apply and its parts), and the columnar
        # export's own walk/scatter split
        phases = {k: round(v, 6) for k, v in spans.collected().items()}
        for k, v in self._export_split.items():
            phases[k] = round(v, 6)
        session.update(self._export_stats)
        # farm tenancy attribution (docs/FEDERATION.md): ledger rows
        # from a control plane sharing a multi-tenant solver farm carry
        # the tenant id its frames were billed under
        if tenant:
            session["tenant"] = tenant
        # the farm's DRR grant-wait for this drain's solve request,
        # echoed back by the sidecar (0 = dedicated / host / farm idle)
        grant_wait_ms = float(getattr(self.remote, "last_grant_wait_ms",
                                      0.0) or 0.0)
        ledger.record(
            self._drain_cycle, obs.SOLVER_DRAIN,
            breaker=obs.breaker_state_name(),
            duration_s=result.solver_time_s + result.apply_time_s,
            phases=phases,
            admitted=result.admitted, evicted=result.evicted,
            parked=parked_n, rounds=result.rounds, solver_arm=arm,
            frame_kind=frame_kind, frame_bytes=frame_bytes,
            frame_reason=frame_reason, session=session,
            grant_wait_ms=grant_wait_ms, device=device,
            detail=({"searchLanes": result.search_lanes,
                     "searchLiveLanes": result.search_live_lanes,
                     "scanEntries": result.scan_entries,
                     "scanVictimEntries": result.scan_victim_entries,
                     "programBuilds": result.program_builds,
                     "programTraces": result.program_traces,
                     "programLoads": result.program_loads,
                     "hMax": result.h_max, "pMax": result.p_max}
                    if result.p_max else None))

    # -- mesh routing (solver/meshutil.py, solver/sharded.py) --------------

    # The mesh breaker state lives on the process-wide
    # DegradationController (resilience package) — one ladder, one
    # cooldown policy, observable levels. These properties keep the
    # historical private names working for tests and diagnostics.

    @property
    def _mesh_broken(self) -> bool:
        return resilience.controller.active(resilience.SOLVER,
                                            "mesh_broken")

    @_mesh_broken.setter
    def _mesh_broken(self, v: bool) -> None:
        resilience.controller.report(
            resilience.SOLVER, "mesh_broken", bool(v),
            cycle=self._drain_cycle,
            reason=("mesh arm tripped" if v
                    else "mesh re-probed; arm restored"))

    @property
    def _mesh_broken_at(self) -> float:
        return (resilience.controller.cooldowns.stamp(
            (resilience.SOLVER, "mesh_broken")) or 0.0)

    @_mesh_broken_at.setter
    def _mesh_broken_at(self, t: float) -> None:
        resilience.controller.cooldowns.set_stamp(
            (resilience.SOLVER, "mesh_broken"), float(t))

    def _mesh(self):
        """The resolved solver mesh, or None (single device / off /
        tripped by a mesh fault). A tripped mesh self-heals after
        ``mesh_retry_cooldown_s`` (timed half-open via the degradation
        controller's cooldown policy: ONE probe drain re-measures,
        concurrent drains stay single-chip; another fault re-trips and
        restarts the clock)."""
        if self._mesh_broken:
            if not resilience.controller.begin_probe(
                    resilience.SOLVER, "mesh_broken",
                    self.mesh_retry_cooldown_s):
                return None
            self.refresh_mesh(self._mesh_max_devices)
        if not self._mesh_resolved:
            # a remote engine has no mesh of its own: the sidecar owns
            # the devices and advertises its width in session
            # responses (remote_mesh_devices). A control plane must
            # not initialize a backend — on a chip host that would
            # take the sidecar's chip — to look for one
            if self.remote is None:
                from kueue_oss_tpu.solver import meshutil

                try:
                    self._mesh_obj = meshutil.detect_mesh(
                        self.mesh_mode, self._mesh_max_devices)
                except Exception:
                    self._mesh_obj = None  # backend init failure != crash
            self._mesh_resolved = True
        return self._mesh_obj

    def refresh_mesh(self, max_devices: int = 0) -> int:
        """Re-detect the mesh (recovery probe, or the chaos harness's
        mesh-shrink: ``max_devices`` caps the width the way a lost
        device shrinks the usable slice). Drops mesh-resident device
        state and the mesh arm's cost estimate so the new topology is
        re-measured from scratch. Returns the new device count."""
        from kueue_oss_tpu.solver import meshutil

        self._mesh_max_devices = max_devices
        self._mesh_broken = False
        self._mesh_resolved = False
        for kind in ("lean", "full"):
            self._device_states.pop(kind + "-mesh", None)
            self._arm_ema.pop((kind, "mesh"), None)
            self._arm_warm.discard((kind, "mesh"))
            devtel.collector.forget(kind, "mesh")
        return meshutil.mesh_devices(self._mesh())

    def _pick_mesh_arm(self, kind: str, n_workloads: int):
        """The mesh to drain on, or None for single-chip — cost-EMA
        routing with one probe per arm."""
        mesh = self._mesh()
        if mesh is None:
            return None
        if self.mesh_force:
            return mesh
        if n_workloads < self.mesh_min_workloads:
            return None
        e_mesh = self._arm_ema.get((kind, "mesh"))
        e_single = self._arm_ema.get((kind, "single"))
        if e_mesh is None:
            return mesh          # probe the mesh arm first
        if e_single is None:
            return None          # then the single-chip arm
        if e_mesh <= e_single:
            # decay the skipped arm so an out-of-date estimate erodes
            # and the loser eventually re-probes (same rationale as the
            # scheduler's _drain_cost_ema decay)
            self._arm_ema[(kind, "single")] = e_single * 0.98
            return mesh
        self._arm_ema[(kind, "mesh")] = e_mesh * 0.98
        return None

    def _note_arm_wall(self, kind: str, arm: str, wall_s: float,
                       n_workloads: int) -> None:
        key = (kind, arm)
        dtl = devtel.collector
        if dtl.enabled and dtl.compile_enabled:
            # devtel's per-(kernel, arm, shape-bucket) verdict replaces
            # the legacy one-shot warm set: a warm arm re-solving at a
            # new padded width is caught (its compile-tainted wall
            # stays out of the EMA), and a warm arm's first sample is
            # no longer wasted
            if dtl.observe_solve(kind, arm, n_workloads, wall_s):
                return
        elif key not in self._arm_warm:
            # compile-tainted probe sample: discard it (the arm stays
            # unmeasured, so the router probes it once more, warm)
            self._arm_warm.add(key)
            return
        per_wl = wall_s / max(1, n_workloads)
        prev = self._arm_ema.get(key)
        self._arm_ema[key] = (
            per_wl if prev is None else 0.7 * prev + 0.3 * per_wl)

    def _clear_device_error(self) -> None:
        """A local solve landed: the accelerator works again, so the
        device_error rung (host-only) recovers on the ladder."""
        ctl = resilience.controller
        if ctl.active(resilience.SOLVER, "device_error"):
            ctl.report(resilience.SOLVER, "device_error", False,
                       cycle=self._drain_cycle,
                       reason="local solve succeeded; device healthy")

    def _note_mesh_failure(self, e: BaseException, kind: str) -> None:
        """A mesh drain fault (device loss / compile abort / injected):
        count it, drop the possibly-corrupt mesh-resident state, and
        degrade to single-chip until refresh_mesh() or the retry
        cooldown re-probes. Never silent — metered AND journaled."""
        resilience.controller.report(
            resilience.SOLVER, "mesh_broken", True,
            cycle=self._drain_cycle,
            reason=f"mesh drain failed ({e!r}); degrading to the "
                   "single-chip solver arm")
        self._arm_warm.discard((kind, "mesh"))
        devtel.collector.forget(kind, "mesh")
        self._device_states.pop(kind + "-mesh", None)
        metrics.solver_fallback_total.inc("mesh_error")
        metrics.solver_mesh_devices.set(value=0)
        obs.recorder.record(
            obs.SOLVER_FALLBACK, obs.CYCLE_SCOPE, cycle=self._drain_cycle,
            path=obs.SOLVER,
            reason=f"mesh drain failed ({e!r}); degrading to the "
                   "single-chip solver arm",
            reason_slug="mesh_error")

    def _full_program(self, mesh, caps: dict):
        """The preemption program for one arm: at the caps asked, or one
        candidate width up where the process has that program and not
        this one (full_kernels.built_p_max). ``last_p_max`` says which."""
        from kueue_oss_tpu.solver.full_kernels import (
            built_p_max,
            full_solver,
        )

        self.last_p_max = built_p_max(mesh=mesh, **caps)
        return full_solver(mesh=mesh, **{**caps, "p_max": self.last_p_max})

    def _local_solve(self, problem: SolverProblem, frame, *, full: bool,
                     n_live: Optional[int] = None, **caps):
        """In-process solve with the mesh -> single-chip fallback
        chain.

        The mesh arm (when routed) drains the resident mesh-placed
        state through the sharded SPMD program; any fault there is
        counted and the SAME drain re-runs on the single-chip arm. A
        single-chip fault escalates to SolverUnavailable so the
        scheduler completes the admission round on host cycles — the
        full chain is mesh -> single-chip -> host, every hop metered.
        Outputs are materialized to numpy INSIDE each arm's window so
        device faults surface here, not mid-apply.
        """
        import time as _time

        from kueue_oss_tpu.solver import meshutil

        kind = "full" if full else "lean"
        # arm routing keys off the LIVE backlog, not the padded
        # capacity: the sticky pad high-water mark must not keep a
        # 3-workload trickle on the mesh arm after one large flood
        if n_live is None:
            n_live = meshutil.live_rows(problem.wl_cqid, problem.n_cqs)
        W = n_live
        mesh = self._pick_mesh_arm(kind, W)
        if mesh is not None:
            try:
                # ONLY the fault-prone device work lives in the guarded
                # block: bookkeeping below must not turn a metrics
                # hiccup into a discarded plan + tripped mesh
                if self.solve_fault_hook is not None:
                    self.solve_fault_hook("mesh")
                # the router's own cost estimate (behaviour, not a
                # trace): _note_arm_wall
                t0 = _time.monotonic()
                tensors = self._local_tensors(problem, frame, full=full,
                                              mesh=mesh)
                with spans.span("dispatch"):
                    if full:
                        out = self._full_program(mesh, caps)(tensors)
                    else:
                        out = meshutil.lean_mesh_solver(mesh)(tensors)
                out = self._fetch(out)
                wall = _time.monotonic() - t0
            except Exception as e:
                self._note_mesh_failure(e, kind)
            else:
                self._note_arm_wall(kind, "mesh", wall, W)
                self.last_drain_arm = "mesh"
                metrics.solver_mesh_devices.set(
                    value=meshutil.mesh_devices(mesh))
                # both drains row-shard the workload axis now (the
                # full kernel composes lane sharding on top), so both
                # observe block-shard skew
                metrics.solver_shard_imbalance.observe(
                    value=meshutil.shard_imbalance(
                        problem.wl_cqid, problem.n_cqs, mesh))
                self._clear_device_error()
                return out
        try:
            if self.solve_fault_hook is not None:
                self.solve_fault_hook("single")
            t0 = _time.monotonic()   # for _note_arm_wall, as above
            tensors = self._local_tensors(problem, frame, full=full)
            with spans.span("dispatch"):
                if full:
                    out = self._full_program(None, caps)(tensors)
                else:
                    out = solve_backlog(tensors)
            out = self._fetch(out)
        except Exception as e:
            # the single-chip arm died too (whole accelerator gone):
            # degrade the round to host cycles, counted, never silent
            self._device_states.pop(kind, None)
            metrics.solver_fallback_total.inc("device_error")
            metrics.solver_mesh_devices.set(value=0)
            resilience.controller.report(
                resilience.SOLVER, "device_error", True,
                cycle=self._drain_cycle,
                reason=f"local solver backend fault ({e!r}); admissions "
                       "degrade to the host cycle")
            obs.recorder.record(
                obs.SOLVER_FALLBACK, obs.CYCLE_SCOPE,
                cycle=self._drain_cycle, path=obs.SOLVER,
                reason=f"local solver backend fault ({e!r}); admissions "
                       "degrade to the host cycle",
                reason_slug="device_error")
            raise SolverUnavailable(
                f"local solver backend fault: {e!r}") from e
        self._note_arm_wall(kind, "single", _time.monotonic() - t0, W)
        self.last_drain_arm = "single"
        metrics.solver_mesh_devices.set(value=0)
        self._clear_device_error()
        return out

    @staticmethod
    def _fetch(out) -> tuple:
        """The device's work, then the copy to the host, as two spans.
        Waiting first adds no synchronisation: ``np.asarray`` blocked
        on the same buffers already; it only parts the device's time
        (``wait``) from the transfer's (``fetch``)."""
        import jax

        with spans.span("wait"):
            jax.block_until_ready(out)
        with spans.span("fetch"):
            return tuple(np.asarray(a) for a in out)

    # -- delta-sync sessions + pipelined dispatch --------------------------

    def _pad_target(self) -> int:
        """Sticky pad target: the pow2 high-water mark, mesh-aligned
        (meshutil.align_pad_target) so the padded workload axis plus
        the null row block-shards evenly over the mesh. Alignment is
        applied whenever a mesh is AVAILABLE — even on drains routed to
        the single-chip arm — so session slot indices map to stable
        (shard, local-row) coordinates across drains and both arms
        solve the byte-identical padded problem. A remote sidecar's
        advertised mesh width (learned from its session responses —
        the client host may have no accelerators at all) joins the
        alignment via lcm; the one-time capacity change when it is
        first learned rides a counted shape_change full sync."""
        from kueue_oss_tpu.solver.meshutil import align_pad_target

        remote_w = (getattr(self.remote, "remote_mesh_devices", 0)
                    if self.remote is not None else 0)
        return align_pad_target(self._pad_hwm, self._mesh(), remote_w)

    def reset_sessions(self, reason: str = "restart") -> None:
        """Drop delta-sync session and resident-device state so the
        next drain of each kind opens with a full SYNC.

        The recovery path calls this after rebuilding a store
        (docs/DURABILITY.md): resident device buffers and sidecar
        session state are gone by design across a restart, and a
        warmed-by-replay store must never be diffed against slot state
        from before the failover."""
        if self._delta_sessions or self._device_states:
            metrics.solver_resync_total.inc(reason)
        self._delta_sessions.clear()
        self._device_states.clear()

    def _note_export_stats(self) -> None:
        """After an export: the columnar view's own walk/scatter split
        and dirty-row counts for this drain's ledger row (the export's
        wall is the ``export`` span's)."""
        col = getattr(self.export_cache, "columnar", None)
        stats = getattr(col, "last_stats", None) or {}
        if stats:
            self._export_split = {
                "export_walk": stats.get("walk_s", 0.0),
                "export_scatter": stats.get("scatter_s", 0.0)}
            self._export_stats = {
                "export_mode": stats.get("mode", ""),
                "export_dirty_rows": int(stats.get("dirty_rows", 0)),
                "export_rows": int(stats.get("rows", 0))}
        else:
            self._export_split = {}
            self._export_stats = {}

    def _session_encode(self, kind: str, problem: SolverProblem,
                        hint=None):
        """Stable slot/rank re-encoding + the SessionFrame to ship.

        Returns (problem, None) with sessions disabled — the drain then
        behaves exactly like the pre-session engine. A remote client
        configured for legacy frames (sessions_enabled=false) disables
        the whole session layer: there is no point paying the stable
        re-encoding for deltas that would never be sent.
        """
        if not self.use_sessions:
            return problem, None
        if (self.remote is not None
                and not getattr(self.remote, "use_sessions", True)):
            return problem, None
        sess = self._delta_sessions.get(kind)
        if sess is None:
            # the full kernel has no wl_rank tensor (FIFO order rides
            # the timestamp ranks); neutralizing it keeps per-CQ rank
            # ripples off the full session's wire
            neutral = ("wl_rank",) if kind == "full" else ()
            sess = HostDeltaSession(cache=self.export_cache,
                                    neutral_fields=neutral)
            self._delta_sessions[kind] = sess
        # slot->shard interleaving follows whichever mesh the resident
        # tensors will shard over: the remote sidecar's advertised
        # width when a sidecar serves the drains, the local mesh
        # otherwise. A width change is an epoch migration — ONE counted
        # RESYNC re-lays the slots out and rebuilds resident tensors.
        from kueue_oss_tpu.solver.meshutil import mesh_devices

        remote_w = (int(getattr(self.remote, "remote_mesh_devices", 0))
                    if self.remote is not None else 0)
        sess.set_interleave(remote_w if remote_w > 1
                            else mesh_devices(self._mesh()))
        # no sidecar will recompute state_checksum over frames on the
        # local path, so fast-path frames may carry the cheap chained
        # checksum instead of an O(W) crc per drain
        sess.cheap_checksum = self.remote is None
        with spans.span("encode"):
            slotted, frame = sess.advance(problem, hint=hint)
        if frame is not None and frame.full_reason == "interleave_migration":
            metrics.solver_resync_total.inc("interleave_migration")
        return slotted, frame

    def _local_tensors(self, problem: SolverProblem, frame, *,
                       full: bool, mesh=None):
        """In-process path: resident device buffers keyed by session
        epoch — a delta epoch scatters only the dirty rows to the
        device (donated, so no full padded copy materializes) instead
        of re-uploading the padded problem. With a ``mesh`` the lean
        resident state lives sharded over the ``wl`` axis; mesh and
        single-chip arms keep separate resident copies so arm flips
        cannot corrupt each other's donated buffers."""
        with spans.span("device_put"):
            return self._local_tensors_inner(problem, frame, full=full,
                                             mesh=mesh)

    def _local_tensors_inner(self, problem: SolverProblem, frame, *,
                             full: bool, mesh=None):
        if frame is None:
            if full:
                from kueue_oss_tpu.solver.full_kernels import (
                    to_device_full,
                )

                t = to_device_full(problem)
            else:
                t = to_device(problem)
            if mesh is not None:
                # same placement policy as the resident path; routing
                # already cleared the live-row floor for this drain
                if full:
                    from kueue_oss_tpu.solver.sharded import (
                        maybe_place_full,
                    )

                    t, _placed = maybe_place_full(t, problem, mesh)
                else:
                    from kueue_oss_tpu.solver.sharded import (
                        maybe_place_lean,
                    )

                    t, _placed = maybe_place_lean(t, problem, mesh)
            return t
        kind = "full" if full else "lean"
        if mesh is not None:
            kind = kind + "-mesh"
        dev = self._device_states.get(kind)
        if dev is None:
            dev = self._device_states[kind] = DeviceResidentProblem(
                mesh=mesh)
        return dev.update(problem, frame, full)

    def _dispatch_remote(self, problem: SolverProblem, expect: int,
                         frame, session_key: str, verify: bool,
                         **solve_kw):
        """Pipelined drain dispatch: the remote solve round-trip runs on
        a worker thread while this thread computes the apply prework
        (snapshot for the verify/TAS paths, workload-ref prefetch), so
        the wire RTT overlaps host work instead of adding to it."""
        kw = dict(solve_kw)
        if frame is not None and getattr(self.remote,
                                         "supports_sessions", False):
            kw["frame"] = frame
            kw["session_key"] = session_key
        pool = self._solve_executor()
        fut = pool.submit(self._remote_solve, problem, expect, **kw)
        try:
            self._prework = self._build_prework(
                problem, verify, full=bool(solve_kw.get("full")))
        except Exception:
            self._prework = None  # prework is an optimization only
        return fut.result()

    def _solve_executor(self):
        if self._solve_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._solve_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="solver-dispatch")
        return self._solve_pool

    def _build_prework(self, problem: SolverProblem, verify: bool,
                       full: bool) -> dict:
        """Plan-independent apply preparation, safe to compute before
        the plan arrives. The full path cannot pre-build the oracle
        snapshot (its evictions change usage before the verify), so it
        only prefetches workload refs; the lean path pre-builds the
        snapshot its verify/TAS placement would otherwise build after
        the response."""
        pre: dict = {}
        if not full and (verify or self._drain_tas_ready):
            from kueue_oss_tpu.core.snapshot import build_snapshot

            pre["snapshot"] = build_snapshot(self.store)
        pre["wl_of"] = {k: self.store.workloads.get(k)
                        for k in problem.wl_keys if k}
        return pre

    def _take_prework(self) -> dict:
        pre, self._prework = (self._prework or {}), None
        return pre

    # -- backend resilience ------------------------------------------------

    def _remote_solve(self, problem: SolverProblem, expect: int, **kw):
        """One remote solve with breaker accounting.

        Any transport/backend fault (including a malformed result tuple)
        counts against the circuit breaker and surfaces as
        SolverUnavailable so the scheduler degrades to the host cycle.
        Success is NOT recorded here — only a plan that also passes the
        sanity guard counts as a healthy backend response.
        """
        # duck-typed trace propagation: a SolverClient ships the cycle id
        # over the wire so the sidecar's solve span comes back tagged;
        # arbitrary remote stubs without the attribute still work
        if hasattr(self.remote, "trace_cycle"):
            self.remote.trace_cycle = self._drain_cycle
        try:
            out = tuple(self.remote.solve(problem, **kw))
        except SolverUnavailable as e:
            self.health.record_failure()
            metrics.solver_fallback_total.inc("backend_error")
            self._record_backend_fallback(str(e))
            raise
        except (OSError, TimeoutError) as e:
            # custom remote stubs may surface raw socket errors
            self.health.record_failure()
            metrics.solver_fallback_total.inc("backend_error")
            self._record_backend_fallback(repr(e))
            raise SolverUnavailable(f"solver backend fault: {e!r}") from e
        if len(out) != expect:
            self.health.record_failure()
            metrics.solver_fallback_total.inc("backend_error")
            self._record_backend_fallback(
                f"backend returned {len(out)} arrays, expected {expect}")
            raise SolverUnavailable(
                f"solver backend returned {len(out)} arrays, "
                f"expected {expect}")
        self._import_sidecar_spans()
        return out

    def _record_backend_fallback(self, reason: str) -> None:
        obs.recorder.record(
            obs.SOLVER_FALLBACK, obs.CYCLE_SCOPE, cycle=self._drain_cycle,
            path=obs.SOLVER, reason=reason, reason_slug="backend_error")

    def _import_sidecar_spans(self) -> None:
        """Merge the sidecar's solve spans (returned in the response
        header) into the span sinks (obs/spans.py: an attached Tracer).
        The two processes have unrelated perf_counter origins, so spans are END-ALIGNED at the moment the
        response arrived — the duration and the shared cycle id are the
        signal; the sub-millisecond start skew is not."""
        echoed = getattr(self.remote, "last_spans", None)
        if not echoed or not spans.tracing():
            return
        now_us = spans.now() // 1000
        tenant = str(getattr(self.remote, "tenant", "") or "")
        for sp in echoed:
            # span import is best-effort diagnostics: a version-skewed
            # or garbled spans entry must not abort the drain (the plan
            # itself is separately sanity-guarded)
            try:
                dur_us = int(sp.get("dur_us", 0))
                # a span that ended BEFORE the response (the farm's
                # grant-wait precedes the solve) declares the gap so
                # the merged timeline keeps wait -> solve ordering
                skew_us = int(sp.get("end_skew_us", 0))
                args = {str(k): v
                        for k, v in dict(sp.get("args") or {}).items()
                        if k not in ("name", "ts_us", "dur_us", "tid",
                                     "source")}
                args.setdefault("cycle", self._drain_cycle)
                # each remote source gets its own stable synthetic
                # track (tagged with the tenant) instead of the old
                # shared tid=0 pile-up
                src = str(dict(sp.get("args") or {}).get("source", "")
                          or f"sidecar:{tenant or 'solver'}")
                if tenant:
                    args.setdefault("tenant", tenant)
                spans.external(str(sp.get("name", "sidecar_solve")),
                               now_us - skew_us - dur_us, dur_us,
                               source=src, **args)
            except Exception:
                continue

    def _check_plan(self, problem: SolverProblem, admitted, opt,
                    admit_round, parked, victim_reason=None, rounds=None,
                    full: bool = False) -> None:
        """Sanity-guard an imported plan BEFORE any store mutation.

        A divergent plan — wrong shapes/dtypes, out-of-bounds flavor
        options, admissions/parkings of null or padding rows — is a
        backend fault: the whole plan is rejected (store untouched, the
        breaker incremented when remote) rather than committed as
        corrupt state. Committed usage is always recomputed host-side
        from the store's own request vectors, so quota arithmetic can
        never be driven by imported tensors; this guard closes the
        remaining index/flag surface.
        """
        fault = self._plan_fault(problem, admitted, opt, admit_round,
                                 parked, victim_reason, rounds, full)
        if fault is None:
            if self.remote is not None:
                self.health.record_success()
            return
        metrics.solver_plan_rejected_total.inc()
        if self.remote is not None:
            self.health.record_failure()
            metrics.solver_fallback_total.inc("plan_rejected")
        obs.recorder.record(
            obs.SOLVER_FALLBACK, obs.CYCLE_SCOPE, cycle=self._drain_cycle,
            path=obs.SOLVER,
            reason=f"divergent solver plan rejected: {fault}",
            reason_slug="plan_rejected")
        raise SolverUnavailable(f"divergent solver plan rejected: {fault}")

    @staticmethod
    def _plan_fault(problem: SolverProblem, admitted, opt, admit_round,
                    parked, victim_reason, rounds,
                    full: bool) -> Optional[str]:
        """Reason the plan is unusable, or None if it checks out."""
        W1 = problem.wl_cqid.shape[0]
        W = W1 - 1
        C = problem.n_cqs
        for name, arr in (("admitted", admitted), ("parked", parked),
                          ("admit_round", admit_round)):
            if arr.ndim != 1 or arr.shape[0] != W1:
                return f"{name} shape {arr.shape} != ({W1},)"
        if victim_reason is not None:
            if victim_reason.ndim != 1 or victim_reason.shape[0] != W1:
                return (f"victim_reason shape {victim_reason.shape} "
                        f"!= ({W1},)")
            # the eviction loop calls int(victim_reason[w]) BEFORE other
            # guards could fire — a non-integral dtype must fail here,
            # not mid-apply after evictions committed
            if not (victim_reason.dtype == np.bool_
                    or np.issubdtype(victim_reason.dtype, np.integer)):
                return (f"victim_reason dtype {victim_reason.dtype} "
                        "is not integral")
        want_opt_ndim = 2 if full else 1
        if opt.ndim != want_opt_ndim or opt.shape[0] != W1:
            return f"opt shape {opt.shape} incompatible with ({W1}, ...)"
        for name, arr in (("opt", opt), ("admit_round", admit_round)):
            if not np.issubdtype(arr.dtype, np.integer):
                return f"{name} dtype {arr.dtype} is not integral"
        for name, arr in (("admitted", admitted), ("parked", parked)):
            if not (arr.dtype == np.bool_
                    or np.issubdtype(arr.dtype, np.integer)):
                return f"{name} dtype {arr.dtype} is not a flag"
        if rounds is not None:
            r = np.asarray(rounds)
            if r.size != 1 or not (
                    r.dtype == np.bool_
                    or np.issubdtype(r.dtype, np.integer)):
                return f"rounds is not an integer scalar ({r.dtype}, " \
                       f"size {r.size})"
        cq = problem.wl_cqid[:W]
        adm = admitted[:W].astype(bool)
        prk = parked[:W].astype(bool)
        if bool((cq[adm] >= C).any()):
            return "plan admits a null/padding row"
        if bool((cq[prk] >= C).any()):
            return "plan parks a null/padding row"
        if not full and bool((adm & prk).any()):
            return "row both admitted and parked"
        rnd = admit_round[:W]
        floor = -1 if full else 0
        if bool((rnd[adm] < floor).any()):
            return f"admitted row with admit_round below {floor}"
        # flavor-option decode bounds, only for rows the apply path
        # actually decodes (full: newly admitted rows, admit_round >= 0)
        n_opt = np.array(
            [len(problem.cq_option_flavors[name])
             for name in problem.cq_names], dtype=np.int64)
        decode = adm & (rnd >= 0) if full else adm
        if not decode.any():
            return None
        cq_d = cq[decode]
        if full:
            ng = problem.cq_ngroups
            if ng is None:
                ng = np.ones(C, dtype=np.int64)
            need_g = int(ng[cq_d].max())
            if opt.shape[1] < need_g:
                return (f"opt group axis {opt.shape[1]} narrower than "
                        f"{need_g} resource groups")
            rows = opt[:W][decode]
            used = np.arange(opt.shape[1])[None, :] < ng[cq_d][:, None]
            bad = used & ((rows < 0) | (rows >= n_opt[cq_d][:, None]))
            if bool(bad.any()):
                return "flavor option index out of range"
        else:
            o = opt[:W][decode]
            if bool(((o < 0) | (o >= n_opt[cq_d])).any()):
                return "flavor option index out of range"
        return None

    # -- plan application --------------------------------------------------

    def _apply_plan(self, problem: SolverProblem, admitted: np.ndarray,
                    opt: np.ndarray, admit_round: np.ndarray,
                    parked: np.ndarray, now: float,
                    result: DrainResult, verify: bool = False) -> None:
        # Collect the committed plan entries in admission order first, so
        # the optional oracle verification can run as one batched native
        # call (SURVEY.md §7 step 4 verify-then-assume pattern).
        pre = self._take_prework()
        wl_of = pre.get("wl_of")
        with spans.span("apply.decode"):
            adm_ws = np.nonzero(admitted[:-1])[0]
            order = adm_ws[np.argsort(admit_round[adm_ws], kind="stable")]
            candidates = []
            declared_of: dict[str, set] = {}
            for w in order:
                key = problem.wl_keys[w]
                wl = (wl_of.get(key) if wl_of is not None
                      else self.store.workloads.get(key))
                if wl is None or wl.is_quota_reserved or not wl.active:
                    continue
                cq_name = problem.cq_names[problem.wl_cqid[w]]
                flavor = problem.cq_option_flavors[cq_name][opt[w]]
                info = WorkloadInfo(wl, cluster_queue=cq_name)
                declared = declared_of.get(cq_name)
                if declared is None:
                    declared = {
                        r for rg in
                        self.store.cluster_queues[cq_name].resource_groups
                        for r in rg.covered_resources}
                    declared_of[cq_name] = declared
                plan_usage: dict[tuple[str, str], int] = {}
                for psr in info.total_requests:
                    for r, q in psr.requests.items():
                        if r not in declared:
                            continue  # QuotaCheckStrategy=IgnoreUndeclared
                        fr = (flavor, r)
                        plan_usage[fr] = plan_usage.get(fr, 0) + q
                candidates.append((wl, cq_name, flavor, info, plan_usage))

        candidates, topo_of = self._compute_tas_assignments(
            candidates, snapshot=pre.get("snapshot"))

        ok = self._verify_candidates(candidates, verify,
                                     snapshot=pre.get("snapshot"))

        with spans.span("apply.commit"):
            for passed, (wl, cq_name, flavor, info, _) in zip(
                    ok, candidates):
                if not passed:
                    self._record_rejected(wl, cq_name)
                    continue
                flavor_of = {r: flavor for psr in info.total_requests
                             for r in psr.requests}
                self._commit_admission(wl, cq_name, flavor_of, info, now,
                                       result,
                                       topology=topo_of.get(wl.key))
        # Mirror the solver's inadmissible-parking decisions host-side;
        # StrictFIFO blocked heads (not parked) stay in their heaps.
        with spans.span("apply.park"):
            for w in np.nonzero(parked[:problem.n_workloads])[0]:
                cq_name = problem.cq_names[problem.wl_cqid[w]]
                self.queues.queues[cq_name].park(problem.wl_keys[w])
                self._record_parked(problem.wl_keys[w], cq_name)

    def _verify_candidates(self, candidates, verify: bool, snapshot=None):
        """Verify-then-fallback (scheduler.go:427 fits re-check): plan
        entries the oracle rejects are not committed — those workloads
        stay queued for the host scheduler path. The sequential
        fits/add_usage walk runs in native code when the toolchain is
        available (kueue_oss_tpu/native/oracle.cpp). ``snapshot`` comes
        from the pipelined-dispatch prework when it overlapped the
        solve (no mutations since export)."""
        if not (verify and candidates):
            return np.ones(len(candidates), dtype=np.uint8)
        from kueue_oss_tpu.core.snapshot import build_snapshot
        from kueue_oss_tpu.native import BatchOracle

        with spans.span("apply.verify"):
            snapshot = snapshot or build_snapshot(self.store)
            oracle = BatchOracle(snapshot.forest.cqs)
            return oracle.verify_and_apply(
                [(cq_name, usage)
                 for _, cq_name, _, _, usage in candidates])

    def _record_rejected(self, wl, cq_name: str) -> None:
        metrics.solver_plan_fallbacks_total.inc()
        obs.recorder.record(
            obs.SOLVER_FALLBACK, wl.key, cycle=self._drain_cycle,
            cluster_queue=cq_name, path=obs.SOLVER,
            reason="host oracle re-check rejected the plan entry;"
                   " workload stays queued for the host cycle",
            reason_slug="oracle_rejected")

    def _record_parked(self, key: str, cq_name: str) -> None:
        obs.recorder.record(
            obs.SKIPPED, key, cycle=self._drain_cycle,
            cluster_queue=cq_name, path=obs.SOLVER,
            reason="parked inadmissible by the solver plan: no flavor "
                   "option fits at current capacity",
            reason_slug="solver_parked")

    # -- full (preemption-capable) drain -----------------------------------

    def _size_caps(self, problem: SolverProblem) -> tuple[int, int]:
        """Size the full kernel's static caps from the problem.

        h_max bounds victim searches per round: capping it only delays
        later preempt-mode heads a round, so any cap is safe — but the
        host cycle has NO such deferral (every head searches every
        cycle, scheduler.go:286-467), so a cap below the CQ count both
        diverges from host round semantics and throttles NoCandidates
        resolution to h_max classes per round (the round-5 churn
        profile: 49 park-only rounds at h=64 vs 5 at h=1024 on the
        50k x 1k shape). Production drains therefore size lanes to the
        CQ count up to `h_max_cap`, clamped to the solving backend's
        per-round work budget (full_kernels.budgeted_lanes; a remote
        sidecar applies its own budget to the h_max shipped here); the
        stepped serve-loop path can run a narrow-lane variant for
        per-round latency. p_max
        bounds candidates per search and MUST cover the largest possible
        candidate set. Candidates are always CONCURRENTLY-ADMITTED
        workloads with nonzero usage in the preemptor's cohort tree
        (preemption.go:311, candidate_generator.go:34-160), so besides
        the cohort population, p_max is bounded by tree capacity. The
        sound capacity measure is the tree's total quota, NOT the root's
        subtree row: usage bubbling subtracts each child's local quota
        on the way up (resource_node.go:210-217), so with lending
        limits admitted usage can sit entirely below the CQs' local
        quotas and never surface at the root. Inductively
        sum(cq usage) <= sum(local quotas in the tree) + usage[root]
        and usage[root] <= subtree[root], and every admitted candidate
        uses >= the smallest positive request on some FR (partial
        admission can sit below every full-count option, so admitted
        usage joins that minimum).

        Workloads admitted BEFORE the drain hold the same quota, so they
        are not counted on top of it. What they can raise is the ceiling,
        where usage predates a quota reduction (or a removed flavor). At
        every moment of a drain, per FR,
            sum(cq usage) <= max(tree quota, usage held at its start)
        because an eviction lowers the sum, and an admission of v into a
        CQ needs v <= available(cq), which is at most tree quota -
        sum(cq usage): available() is the unused local quota along the
        CQ's path plus subtree[root] - usage[root], NOT clamped at zero
        (quota.py, kernels.available_all), the tree's unused quota is
        the same sum over every node, and what a node uses within its
        local quota never reaches its parent. So an admission lands the
        sum at or under the tree's quota, and
            candidates <= sum over FRs of max(quota, held) // min_req
        which in a tree whose usage is within its quota is its capacity
        whatever it holds when the drain starts: held quota does not
        move the cap. What the drain can newly seat plus the COUNT of
        the holders bounds the same set from the other side (few large
        holders above a reduced quota); the smaller of the two is
        taken. Rounded up to powers of two to reuse compiled kernels.
        The population term still moves the cap, down as a backlog
        drains: _full_program runs a built program one width up rather
        than stall the drain on tracing a narrower one.
        """
        from kueue_oss_tpu.solver.full_kernels import (
            budgeted_lanes,
            lane_work_budget,
        )

        C = problem.n_cqs
        h_max = max(1, pow2(min(C, self.h_max_cap)))
        if self.h_work_budget is None and self.remote is None:
            self.h_work_budget = lane_work_budget()
        if self.h_work_budget is not None:
            K = problem.wl_req.shape[1] if problem.wl_req.ndim == 3 else 1
            g = max(1, int(problem.cq_ngroups.max()) if C else 1)
            h_max = budgeted_lanes(h_max, self.h_work_budget, K, g)
        root_of_cq = problem.cq_root
        wl_root = root_of_cq[np.minimum(problem.wl_cqid[:-1], C - 1)]
        counts = np.bincount(wl_root, minlength=problem.n_nodes + 1)
        pop = int(counts.max()) if counts.size else 1
        # per-FR smallest positive usage a candidate can hold: flavor
        # options plus actual admitted usage (partial admission can sit
        # below every full-count option)
        req = problem.wl_req[:-1].reshape(-1, problem.wl_req.shape[-1])
        if problem.ad_usage is not None:
            req = np.concatenate([req, problem.ad_usage[:-1]], axis=0)
        pos = req > 0
        if pos.any():
            big = np.iinfo(req.dtype).max
            min_req = np.where(pos.any(axis=0),
                               np.where(pos, req, big).min(axis=0), 0)
            # per-node root: last valid entry on the ancestor path
            path = problem.path                       # [N+1, D]
            null = path.shape[0] - 1
            valid = path != null
            last = np.maximum(valid.shape[1] - 1 - np.argmax(
                valid[:, ::-1], axis=1), 0)
            root_of_node = path[np.arange(path.shape[0]), last]
            root_of_node = np.where(valid.any(axis=1), root_of_node, null)
            tree_quota = np.zeros_like(problem.local_quota)
            np.add.at(tree_quota, root_of_node[:-1],
                      problem.local_quota[:-1])
            held = np.zeros(tree_quota.shape, dtype=np.int64)
            adm_counts = np.zeros(problem.n_nodes + 1, dtype=np.int64)
            if problem.ad_usage is not None:
                adm0 = problem.ad_usage[:-1].any(axis=1)
                np.add.at(held, wl_root[adm0], problem.ad_usage[:-1][adm0])
                adm_counts = np.bincount(
                    wl_root[adm0], minlength=problem.n_nodes + 1)
            div, asked = np.maximum(min_req, 1), min_req > 0
            cap = 0
            for rn in np.unique(root_of_cq):
                quota = tree_quota[rn] + problem.subtree[rn]
                cap = max(cap, min(
                    int((quota // div)[asked].sum()) + int(adm_counts[rn]),
                    int((np.maximum(quota, held[rn]) // div)[asked].sum())))
            p_max = min(pop, max(8, cap))
        else:
            p_max = pop
        return h_max, pow2(max(8, p_max))

    def _drain_full(
            self, now: float, verify: bool = False,
            pending: Optional[dict[str, list[WorkloadInfo]]] = None,
    ) -> DrainResult:
        """Drain a preemption-enabled store through solve_backlog_full.

        Reference cycle contract: scheduler.go:286-467 — the kernel
        replays nominate → search → admit/preempt rounds on-device; this
        applies the net plan: evictions first (releasing quota exactly
        like Scheduler._issue_preemptions → evict_workload), then
        admissions in (round, entry-order), then parking decisions.
        """
        result = DrainResult()
        with spans.span("backlog"):
            if pending is None:
                pending = self.pending_backlog()
            parked_map = self._parked_map()
        with spans.span("export"):
            problem = export_problem(self.store, pending,
                                     include_admitted=True,
                                     parked=parked_map,
                                     afs=self.queues.afs, now=now,
                                     cache=self.export_cache)
            self._note_export_stats()
        if problem.n_workloads == 0:
            return result
        hint = getattr(problem, "_columnar_hint", None)
        g_max = int(problem.cq_ngroups.max())
        h_max, p_max = self._size_caps(problem)
        result.h_max, result.p_max = h_max, p_max
        n_live = problem.n_workloads
        self._pad_hwm = max(self._pad_hwm,
                            pow2(max(problem.n_workloads, self.pad_to)))
        problem = pad_workloads(problem, self._pad_target())
        problem, frame = self._session_encode("full", problem, hint=hint)
        dev0 = self._device_totals()

        # forced: DrainResult returns these two durations
        with spans.span("solve", force=True) as sp:
            if self.remote is not None:
                (admitted, opt, admit_round, parked, rounds, _usage,
                 _wl_usage, victim_reason) = self._dispatch_remote(
                    problem, 8, frame, "full", verify, full=True,
                    g_max=g_max, h_max=h_max, p_max=p_max,
                    fs_enabled=self.enable_fair_sharing)
            else:
                from kueue_oss_tpu.solver.full_kernels import (
                    solver_programs)

                programs0 = solver_programs()
                # the program's own return (full_kernels.full_solver):
                # the plan, then the liveness gate's two counts and the
                # entry scan's two
                (admitted, opt, admit_round, parked, rounds, _usage,
                 _wl_usage, victim_reason, search_lanes,
                 search_live_lanes, scan_entries,
                 scan_victim_entries) = self._local_solve(
                    problem, frame, full=True, n_live=n_live,
                    g_max=g_max, h_max=h_max, p_max=p_max,
                    fs_enabled=self.enable_fair_sharing)
                result.search_lanes = int(search_lanes)
                result.search_live_lanes = int(search_live_lanes)
                result.scan_entries = int(scan_entries)
                result.scan_victim_entries = int(scan_victim_entries)
                programs = solver_programs()
                result.program_builds = (programs["builds"]
                                         - programs0["builds"])
                result.program_traces = (programs["traces"]
                                         - programs0["traces"])
                result.program_loads = programs["loads"] - programs0["loads"]
                result.p_max = self.last_p_max
            admitted = np.asarray(admitted)
            opt = np.asarray(opt)
            admit_round = np.asarray(admit_round)
            parked = np.asarray(parked)
            victim_reason = np.asarray(victim_reason)
            if self.remote is not None:
                # imported plans only (see the lean drain's note)
                self._check_plan(problem, admitted, opt, admit_round,
                                 parked, victim_reason=victim_reason,
                                 rounds=rounds, full=True)
            result.rounds = int(rounds)
        result.solver_time_s = sp.seconds

        with spans.span("apply", force=True) as sp:
            self._apply_full_plan(problem, admitted, opt, admit_round,
                                  parked, victim_reason, now, result,
                                  verify=verify)
        result.apply_time_s = sp.seconds
        result.program_builds += self._tas_builds
        self._tas_builds = 0
        spans.count("drain_admitted", result.admitted)
        spans.count("search_lanes", result.search_lanes)
        spans.count("search_live_lanes", result.search_live_lanes)
        spans.count("scan_entries", result.scan_entries)
        spans.count("scan_victim_entries", result.scan_victim_entries)
        spans.count("solver_program_builds", result.program_builds)
        W = problem.n_workloads
        with spans.span("record"):
            self._ledger_record(
                result, frame, "full", dev0,
                parked_n=int((np.asarray(parked[:W]).astype(bool)
                              & ~np.asarray(admitted[:W]).astype(bool)
                              ).sum()))
        return result

    def _parked_map(self) -> dict[str, list[WorkloadInfo]]:
        """Still-parked entries the full export carries as parked0."""
        parked_map: dict[str, list[WorkloadInfo]] = {}
        for name, q in self.queues.queues.items():
            if not q.inadmissible or (
                    self._is_tas_cq(name)
                    and name not in self._drain_tas_ready):
                continue
            # stale entries export as PENDING (pending_backlog); only
            # still-parked (unflushed) entries export as parked0
            infos = [i for k, i in q.inadmissible.items()
                     if k not in q._stale
                     and all(ps.topology_request is None
                             for ps in i.obj.podsets)]
            if infos:
                parked_map[name] = infos
        return parked_map

    def _evictor(self):
        """Host scheduler used purely for its eviction state machine."""
        if self.scheduler is None:
            from kueue_oss_tpu.scheduler.scheduler import Scheduler

            self.scheduler = Scheduler(self.store, self.queues)
        return self.scheduler

    def _apply_full_plan(self, problem: SolverProblem, admitted: np.ndarray,
                         opt: np.ndarray, admit_round: np.ndarray,
                         parked: np.ndarray, victim_reason: np.ndarray,
                         now: float, result: DrainResult,
                         verify: bool = False) -> None:
        from kueue_oss_tpu.scheduler.preemption import (
            _VARIANT_REASON,
            IN_CLUSTER_QUEUE,
            IN_COHORT_FAIR_SHARING,
        )
        from kueue_oss_tpu.solver.fair_kernels import V_FAIR_SHARING

        reason_of = dict(_VARIANT_REASON)
        reason_of[V_FAIR_SHARING] = IN_COHORT_FAIR_SHARING

        pre = self._take_prework()
        wl_of = pre.get("wl_of")

        def lookup(key):
            return (wl_of.get(key) if wl_of is not None
                    else self.store.workloads.get(key))

        W = problem.n_workloads
        wl_admitted0 = problem.wl_admitted0

        # 1) evictions: initially-admitted workloads that lost their
        #    admission, or were evicted mid-drain and re-admitted with a
        #    (possibly different) flavor (admit_round >= 0).
        with spans.span("apply.evict"):
            evictor = self._evictor()
            evict_ws = np.nonzero(
                wl_admitted0[:W]
                & ~(admitted[:W] & (admit_round[:W] < 0)))[0]
            for w in evict_ws:
                key = problem.wl_keys[w]
                wl = lookup(key)
                if wl is None or not wl.is_quota_reserved:
                    continue
                reason = reason_of.get(int(victim_reason[w]),
                                       IN_CLUSTER_QUEUE)
                evictor.evict_workload(
                    key, reason="Preempted",
                    message="Preempted by the solver drain plan",
                    now=now, preemption_reason=reason,
                    decision_path=obs.SOLVER,
                    decision_cycle=self._drain_cycle)
                if not admitted[w]:
                    result.evicted += 1
                    result.evicted_keys.append(key)

        # 2) admissions in (round, entry-order); per-group flavor decode.
        with spans.span("apply.decode"):
            adm_ws = np.nonzero(admitted[:W] & (admit_round[:W] >= 0))[0]
            order = adm_ws[np.argsort(admit_round[adm_ws], kind="stable")]
            candidates = []
            for w in order:
                key = problem.wl_keys[w]
                wl = lookup(key)
                if wl is None or wl.is_quota_reserved or not wl.active:
                    continue
                cq_name = problem.cq_names[problem.wl_cqid[w]]
                rg_of = problem.cq_resource_group[cq_name]
                opts = problem.cq_option_flavors[cq_name]
                info = WorkloadInfo(wl, cluster_queue=cq_name)
                flavor_of = {
                    r: opts[opt[w, g]] for r, g in rg_of.items()}
                plan_usage: dict[tuple[str, str], int] = {}
                for psr in info.total_requests:
                    for r, q in psr.requests.items():
                        if r not in flavor_of:
                            continue  # QuotaCheckStrategy=IgnoreUndeclared
                        fr = (flavor_of[r], r)
                        plan_usage[fr] = plan_usage.get(fr, 0) + q
                candidates.append(
                    (wl, cq_name, flavor_of, info, plan_usage))

        # the evictions above changed usage: the snapshot is built now,
        # once, for the placement and for the verify
        snapshot = None
        if self._drain_tas_ready and any(
                c[1] in self._drain_tas_ready for c in candidates):
            from kueue_oss_tpu.core.snapshot import build_snapshot

            snapshot = build_snapshot(self.store)
        # device-TAS placement in admission order; failed placements
        # drop out of the plan BEFORE the oracle verify so the
        # sequential usage walk matches what actually commits
        candidates, topo_of = self._compute_tas_assignments(
            candidates, snapshot=snapshot)

        ok = self._verify_candidates(candidates, verify, snapshot=snapshot)

        with spans.span("apply.commit"):
            for passed, (wl, cq_name, flavor_of, info, _) in zip(
                    ok, candidates):
                if not passed:
                    self._record_rejected(wl, cq_name)
                    continue
                self._commit_admission(wl, cq_name, flavor_of, info, now,
                                       result,
                                       topology=topo_of.get(wl.key))

        # 3) parking decisions (inadmissible backoff parity).
        with spans.span("apply.park"):
            for w in np.nonzero(parked[:W] & ~admitted[:W])[0]:
                cq_name = problem.cq_names[problem.wl_cqid[w]]
                self.queues.queues[cq_name].park(problem.wl_keys[w])
                self._record_parked(problem.wl_keys[w], cq_name)

    def _commit_admission(self, wl, cq_name: str,
                          flavor_of: dict[str, str], info: WorkloadInfo,
                          now: float, result: DrainResult,
                          topology: Optional[TopologyAssignment] = None,
                          ) -> None:
        key = wl.key
        persistence = getattr(self.store, "persistence", None)
        if persistence is not None:
            # plan-entry intent before the store mutation, fenced like
            # the host path's (scheduler._admit; docs/DURABILITY.md) —
            # a drain killed mid-apply redoes the uncommitted suffix
            # from the recovered backlog
            persistence.intent("admit", key, rv=wl.resource_version,
                               cycle=self._drain_cycle,
                               cluster_queue=cq_name,
                               detail={"path": "solver"})
        admission = Admission(
            cluster_queue=cq_name,
            podset_assignments=[
                PodSetAssignment(
                    name=psr.name,
                    # undeclared resources carry no flavor under
                    # QuotaCheckStrategy=IgnoreUndeclared
                    flavors={r: flavor_of[r] for r in psr.requests
                             if r in flavor_of},
                    resource_usage=dict(psr.requests),
                    count=psr.count,
                    # device-TAS drains carry the placement computed by
                    # the sequential on-device placer (single podset)
                    topology_assignment=topology,
                )
                for psr in info.total_requests
            ],
        )
        wl.status.admission = admission
        wl.set_condition(WorkloadConditionType.QUOTA_RESERVED, True,
                         reason="QuotaReserved", now=now)
        if wl.is_evicted:
            wl.set_condition(WorkloadConditionType.EVICTED, False,
                             reason="QuotaReserved", now=now)
        if wl.status.requeue_state is not None:
            wl.status.requeue_state.requeue_at = None
        cq_spec = self.store.cluster_queues[cq_name]
        # flavors ACTUALLY assigned (host-path parity: scheduler._admit
        # uses admission.assigned_flavors() too) — flavor_of covers every
        # resource the CQ defines, not just the ones this workload uses
        effective_checks = cq_spec.checks_for_flavors(
            admission.assigned_flavors())
        if effective_checks:
            from kueue_oss_tpu.api.types import AdmissionCheckState
            for ac_name in effective_checks:
                wl.status.admission_checks.setdefault(
                    ac_name, AdmissionCheckState(name=ac_name))
        else:
            wl.set_condition(WorkloadConditionType.ADMITTED, True,
                             reason="Admitted", now=now)
        self.store.update_workload(wl)
        persist_hooks.crash_if("mid_drain")
        self.queues.queues[cq_name].delete(key)
        if (self.queues.afs is not None
                and cq_spec.admission_scope is not None
                and cq_spec.admission_scope.admission_mode
                == "UsageBasedAdmissionFairSharing"):
            # keep the host AfsManager in sync with the plan's entry
            # penalties (scheduler._admit record_admission hook)
            by_resource: dict[str, int] = {}
            for psr in info.total_requests:
                for r, q in psr.requests.items():
                    by_resource[r] = by_resource.get(r, 0) + q
            self.queues.afs.record_admission(
                f"{wl.namespace}/{wl.queue_name}", by_resource, now)
        wait_s = max(now - wl.creation_time, 0.0)
        exemplar = {"cycle": self._drain_cycle, "workload": key}
        metrics.quota_reserved_workload(cq_name, wait_s,
                                        lq=wl.queue_name,
                                        namespace=wl.namespace,
                                        exemplar=exemplar)
        if wl.is_admitted:
            metrics.admitted_workload(cq_name, wait_s,
                                      lq=wl.queue_name,
                                      namespace=wl.namespace,
                                      exemplar=exemplar)
        # queue-wait SLI feed (obs/health.py), host-path parity: the
        # solver drain's admissions count against the same objectives;
        # the priority scope keys by WorkloadPriorityClass name
        pclass = obs.priority_class_of(self.store, wl)
        obs.slo_engine.observe_admission(
            cq_name, wait_s, priority=wl.priority,
            priority_class=pclass, now=now,
            cycle=self._drain_cycle, workload=key)
        obs.recorder.record(
            obs.SOLVER_ADMITTED, key, cycle=self._drain_cycle,
            cluster_queue=cq_name, path=obs.SOLVER,
            reason=f"Admitted by the solver drain plan into "
                   f"ClusterQueue {cq_name}",
            detail={
                "flavors": dict(flavor_of),
                "placed_with_topology": topology is not None,
                "admitted": wl.is_admitted,
                "waitSeconds": round(wait_s, 3),
                "priority": wl.priority,
                "priorityClass": pclass,
                # which solver arm produced this plan (mesh / single /
                # remote) — joins the ledger row's solver_arm
                "solver_arm": ("remote" if self.remote is not None
                               else (self.last_drain_arm or "single")),
            })
        result.admitted += 1
        result.admitted_keys.append(key)
