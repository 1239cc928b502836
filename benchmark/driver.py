"""The replay: a schedule played on the real clock through the program.

Upstream's runner (test/performance/scheduler/runner) creates workloads
on the generator's schedule and finishes each one ``runtime_ms`` after
it is admitted, on the wall clock. So does this: one loop, one thread,
through ``Store -> QueueManager -> Scheduler``. With ``solver="auto"``
that is the served path and the router decides what reaches the device;
with ``solver=None`` the same loop fed a recorded log is the host-only
twin (``replay_log``). Copied in spirit from
``kueue_oss_tpu/perf/runner.py`` (``Simulator``), whose clock is
virtual.

A **pass** is: apply every arrival and finish that is due, stamped with
its due time; ``requeue_due``; ``run_until_quiet``; read the clock. The
driver keeps a log of each pass (its ``now``, the events it applied,
and who gained or lost a quota reservation between the end of the pass
before and the end of this one) and stamps every reservation and
eviction with the host clock as the store reports it (a store watch,
as ``Simulator`` has). For a kind that asks (``GIVEN``), a pass's record
also lists what every reservation of the pass was given: which flavor
was charged and which nodes were assigned (``given_as_data``).
"""

from __future__ import annotations

import contextlib
import heapq
import time

from benchmark import deployment


#: what a kind's ``scheduler_options`` may set: deployment settings that
#: upstream's ``Configuration`` API has. A router threshold
#: (``solver_min_backlog``, ``solver_reengage_fraction``,
#: ``solver_config``) is none: the driver changes no router setting
SCHEDULER_OPTIONS = frozenset(("enable_fair_sharing",
                               "enable_partial_admission"))


#: what a kind's ``feature_gates`` may set: the gates of upstream's
#: ``pkg/features/kube_features.go`` (``featureGates`` is a field of the
#: same ``Configuration`` API) that change what the scheduler decides
#: and that the program has. A gate of the program's own
#: (``TASDeviceFillCounts``: what runs where) is no deployment setting
FEATURE_GATES = frozenset((
    "TASBalancedPlacement", "TASProfileMixed", "TASMultiLayerTopology",
    "PartialAdmission", "FlavorFungibility", "PrioritySortingWithinCohort",
    "LendingLimit", "AdmissionFairSharing"))


def _only(cfg: dict, got: dict, allowed: frozenset, where: str) -> dict:
    refused = sorted(set(got) - allowed)
    if refused:
        raise ValueError(
            f"configs/{cfg.get('name')}.json: its kind sets {refused} "
            f"{where}; a kind may set {sorted(allowed)} and nothing else")
    return got


def scheduler_options(cfg: dict) -> dict:
    return _only(cfg, dict(deployment.kind_of(cfg).scheduler_options(cfg)),
                 SCHEDULER_OPTIONS, "on the Scheduler")


def feature_gates(cfg: dict) -> dict:
    """The gates the configuration's kind states (none, for a kind
    without ``feature_gates``)."""
    stated = getattr(deployment.kind_of(cfg), "feature_gates", None)
    return _only(cfg, dict(stated(cfg)) if stated else {}, FEATURE_GATES,
                 "among the feature gates")


class Replay:
    """``cfg`` is the deployment the program is given (the
    configuration as stated, or a control's)."""

    def __init__(self, cfg: dict, arrivals, *, solver) -> None:
        from kueue_oss_tpu.core.queue_manager import QueueManager
        from kueue_oss_tpu.scheduler.scheduler import Scheduler

        kind = deployment.kind_of(cfg)
        self.cfg = cfg
        self.arrivals = arrivals
        self.by_key = {a.key: a for a in arrivals}
        self.workloads = {a.key: kind.make_workload(a, cfg)
                          for a in arrivals}
        self.store = kind.build_store(cfg)
        self.queues = QueueManager(self.store)
        self.sched = Scheduler(self.store, self.queues, solver=solver,
                               **scheduler_options(cfg))
        self.engine = None
        if solver is not None:
            # the program's own provision for one compiled program
            # while a backlog grows (perf/runner.Simulator does the
            # same for a replayed schedule)
            self.engine = self.sched._solver_engine()
            self.engine.pad_to = len(arrivals)
        #: a traced run puts jax.profiler.TraceAnnotation here
        self.span = lambda name: contextlib.nullcontext()
        self.clock = time.monotonic
        self.next_arrival = 0
        self.preloaded: list = []
        # -- the driver's own record --
        self.holding: set = set()
        self.reserved_at: dict = {}
        self.reservations: list = []   # (key, clock)
        self.evictions: list = []      # (key, clock, reserved at clock)
        self.finish_heap: list = []    # (due_s, seq, key, reserved at)
        self._seq = 0
        self._added: set = set()
        self._removed: set = set()
        #: for a kind that asks: (key, the store's Admission) of every
        #: reservation of the pass under way. A reference, no copy: each
        #: reservation gets an Admission of its own and an eviction
        #: drops it whole (``given_as_data`` says what is not covered)
        self._given = [] if getattr(kind, "GIVEN", False) else None
        self.passes: list = []
        self.failed: list = []
        self.t0 = None
        self.start_at = 0.0
        self.store.watch(self._on_event)

    # -- the store's word on who holds quota ----------------------------

    def _on_event(self, event) -> None:
        _verb, kind, wl = event
        if kind != "Workload":
            return
        key = wl.key
        holds = wl.is_quota_reserved and not wl.is_finished
        if holds == (key in self.holding):
            return
        t = self.clock()
        if holds:
            self.holding.add(key)
            self.reserved_at[key] = t
            self.reservations.append((key, t))
            if self._given is not None:
                self._given.append((key, wl.status.admission))
            if self.t0 is not None:
                due = (self.start_at + (t - self.t0)
                       + self.by_key[key].runtime_s)
                self._seq += 1
                heapq.heappush(self.finish_heap, (due, self._seq, key, t))
            if key in self._removed:
                self._removed.discard(key)
            else:
                self._added.add(key)
        else:
            self.holding.discard(key)
            since = self.reserved_at.pop(key, None)
            if not wl.is_finished:
                self.evictions.append((key, t, since))
            if key in self._added:
                self._added.discard(key)
            else:
                self._removed.add(key)

    # -- set-up -----------------------------------------------------------

    def preload(self, start_at: float) -> None:
        """Arrivals due before the window opens go into the store; no
        pass runs on them here."""
        i = self.next_arrival
        while i < len(self.arrivals) and self.arrivals[i].due_s < start_at:
            a = self.arrivals[i]
            self.store.add_workload(self.workloads[a.key])
            self.preloaded.append(a.key)
            i += 1
        self.next_arrival = i
        self.start_at = start_at

    # -- the window -------------------------------------------------------

    def _due_events(self, now: float) -> list:
        events = []
        i = self.next_arrival
        arr = self.arrivals
        while i < len(arr) and arr[i].due_s <= now:
            events.append(("arrive", arr[i].key, arr[i].due_s))
            i += 1
        self.next_arrival = i
        heap = self.finish_heap
        while heap and heap[0][0] <= now:
            due, _seq, key, stamp = heapq.heappop(heap)
            # preempted since: it does not finish, and its next
            # reservation starts a new runtime
            if self.reserved_at.get(key) == stamp:
                events.append(("finish", key, due))
        events.sort(key=lambda e: e[2])
        return events

    def _next_due(self):
        cands = []
        if self.next_arrival < len(self.arrivals):
            cands.append(self.arrivals[self.next_arrival].due_s)
        if self.finish_heap:
            cands.append(self.finish_heap[0][0])
        nxt = self.sched.next_requeue_at()
        if nxt is not None:
            cands.append(nxt)
        return min(cands) if cands else None

    def one_pass(self, now: float, events: list) -> dict:
        sched, store = self.sched, self.store
        t_start = self.clock()
        drains0 = self.engine.drain_count if self.engine else 0
        with self.span("bench:apply_events"):
            for kind, key, due in events:
                try:
                    if kind == "arrive":
                        store.add_workload(self.workloads[key])
                    else:
                        sched.finish_workload(key, now=due)
                except Exception as e:  # refused: counted, run goes on
                    self.failed.append((kind, key, repr(e)))
        t_applied = self.clock()
        with self.span("bench:run_until_quiet"):
            sched.requeue_due(now)
            cycles = sched.run_until_quiet(now=now)
        t_end = self.clock()
        rec = {"n": len(self.passes), "now": now, "events": events,
               "added": sorted(self._added),
               "removed": sorted(self._removed),
               "t_start": t_start, "t_applied": t_applied, "t_end": t_end,
               "cycles": cycles,
               "drains": (self.engine.drain_count - drains0
                          if self.engine else 0)}
        self._added, self._removed = set(), set()
        if self._given is not None:
            rec["given"], self._given = self._given, []
        self.passes.append(rec)
        return rec

    def run(self, seconds: float, *, max_passes: int | None = None,
            until_drains: int | None = None, on_pass=None) -> dict:
        """Play the schedule from ``start_at`` for ``seconds`` on the
        real clock. A pass under way at the end is finished.
        ``max_passes`` and ``until_drains`` end a warm-up early."""
        clock = self.clock
        first = True
        idle_s = 0.0
        with self.span("bench:window"):
            self.t0 = t0 = clock()
            end = t0 + seconds
            while True:
                t = clock()
                if t >= end or (max_passes is not None
                                and len(self.passes) >= max_passes) or (
                        until_drains is not None
                        and self.engine.drain_count >= until_drains):
                    break
                now = self.start_at + (t - t0)
                events = self._due_events(now)
                nxt = self.sched.next_requeue_at()
                if not (events or first
                        or (nxt is not None and nxt <= now)):
                    # nothing due: no pass; sleep to the next due event
                    due = self._next_due()
                    wake = end if due is None else min(
                        end, t0 + (due - self.start_at))
                    with self.span("bench:sleep"):
                        time.sleep(max(0.0, wake - clock()))
                    idle_s += clock() - t
                    continue
                first = False
                with self.span("bench:pass"):
                    rec = self.one_pass(now, events)
                if on_pass is not None:
                    on_pass(rec)
        return {"t0": t0, "t_end": end, "closed": clock(),
                "idle_s": idle_s}


def _podset_as_data(psa) -> dict:
    ta = psa.topology_assignment
    return {"name": psa.name, "count": psa.count,
            "flavors": dict(psa.flavors),
            "usage": dict(psa.resource_usage),
            "topology": None if ta is None else {
                "levels": list(ta.levels),
                "domains": [[list(d.values), d.count]
                            for d in ta.domains]}}


def given_as_data(pass_log: list) -> None:
    """After the window: every ``given`` entry of the log, (key, the
    store's ``Admission``) as the watch kept it, becomes plain data that
    a reference can read without the program's types:
    ``{"key", "podsets": [{"name", "count", "flavors": resource ->
    flavor name, "usage": resource -> quantity, "topology": {"levels",
    "domains": [[values, count], ...]} or None}, ...]}`` (``podsets``
    None where the store reported a reservation with no admission).

    The program writes a new ``Admission`` for every reservation
    (``scheduler._admit``, ``engine._commit_admission``) and changes one
    in place in two places that no replay reaches: the second pass of a
    delayed topology request (an admission check of a provisioning
    request) and ``failure_recovery``'s node replacement. A deployment
    with either would read the later placement under the earlier
    reservation."""
    for rec in pass_log:
        if "given" in rec:
            rec["given"] = [
                {"key": key, "podsets": None if adm is None else [
                    _podset_as_data(psa) for psa in adm.podset_assignments]}
                for key, adm in rec["given"]]


def first_difference(n: int, rec: dict, got: dict) -> dict:
    out = {"pass": n, "drains": rec["drains"]}
    for side in ("added", "removed"):
        a, b = set(rec[side]), set(got[side])
        out[side] = {"program": len(a), "twin": len(b),
                     "only_program": sorted(a - b)[:3],
                     "only_twin": sorted(b - a)[:3]}
    return out


def replay_log(cfg: dict, arrivals, preloaded, pass_log) -> dict:
    """The host-only twin: the program's scheduler with no solver, fed
    the recorded log pass by pass; per pass, whether the same workloads
    (by key) gained and lost a reservation as in the program. What the
    log's reservations were ``given`` is neither read nor changed."""
    twin = Replay(cfg, arrivals, solver=None)
    for key in preloaded:
        twin.store.add_workload(twin.workloads[key])
    differing, first = 0, None
    for n, rec in enumerate(pass_log):
        got = twin.one_pass(rec["now"], rec["events"])
        if got["added"] != rec["added"] or got["removed"] != rec["removed"]:
            differing += 1
            if first is None:
                first = first_difference(n, rec, got)
    return {"passes": len(pass_log), "differing": differing,
            "first": first}
