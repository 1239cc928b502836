"""Deterministic fault-injection harness for the solver backend.

The resilience layer (solver/resilience.py, SolverClient deadlines and
retries, the engine's plan-sanity guard) claims the control plane
survives a crashing, hanging, or garbage-spewing solver sidecar without
stalling admissions. This module *proves* it: a seeded injector decides,
per request, which failure mode the sidecar exhibits, and a chaos
server wraps the real solve path with those faults. The same injector
drives the `chaos`-marked tests (tier-1: fully deterministic, injected
clocks, no sleeps in the fast subset).

Failure modes (FAULTS):

  ok            -- serve the request normally
  crash_pre     -- close the connection before reading the request
  crash         -- read the request, then die without replying
                   (sidecar killed mid-request: client sees EOF)
  hang          -- hold the connection open and never reply (client's
                   per-call deadline is the only way out)
  truncate      -- declare a full frame but send only part of it
  oversize      -- declare a frame above the client's max-frame guard
  garble        -- well-framed response whose npz payload is noise
  corrupt_plan  -- a *decodable* plan with out-of-bounds indices and
                   admitted null/padding rows (exercises the engine's
                   plan-sanity guard, not the transport)
  slow          -- delay the (correct) response by ``slow_s``

Node flap (the non-sidecar failure in the model) is injected by
``NodeFlapInjector`` against the store's node objects. Accelerator
device loss and mesh shrink (the multi-chip failure modes) are
injected by ``MeshFaultInjector`` through the engine's
``solve_fault_hook`` seam, driving the mesh -> single-chip -> host
fallback chain deterministically.

Control-plane crash/restart (the durability failure modes,
docs/DURABILITY.md) is injected by ``CrashPointInjector`` through the
``persist.hooks`` crash points (pre_fsync, torn_tail,
post_fsync_pre_apply, mid_checkpoint, mid_drain); the subprocess
driver ``python -m kueue_oss_tpu.persist.crashtest`` pairs each kill
with a recovery run and asserts byte-identical convergence.
"""

from __future__ import annotations

import io
import json
import random
import socketserver
import struct
import time
from typing import Optional

import numpy as np

from kueue_oss_tpu.solver.service import (
    SolverServer,
    _recv,
    _send,
    deserialize_problem,
    respond,
)

OK = "ok"
CRASH_PRE = "crash_pre"
CRASH = "crash"
HANG = "hang"
TRUNCATE = "truncate"
OVERSIZE = "oversize"
GARBLE = "garble"
CORRUPT_PLAN = "corrupt_plan"
SLOW = "slow"

FAULTS = (OK, CRASH_PRE, CRASH, HANG, TRUNCATE, OVERSIZE, GARBLE,
          CORRUPT_PLAN, SLOW)

#: ceiling on how long a "hang" holds its connection open server-side;
#: the client's deadline fires long before this in any sane config —
#: it only bounds thread lifetime if a test dies mid-hang
_HANG_CAP_S = 30.0


class FaultInjector:
    """Seeded per-request fault decisions, usable two ways.

    - ``schedule``: an explicit fault sequence consumed in order
      (deterministic tests: "crash, then serve"). After the schedule is
      exhausted the injector falls through to the random mode.
    - ``weights``: {fault: weight} sampled with the seeded RNG (chaos
      sweeps). With neither, every request is served.

    ``injected`` counts what was actually injected, for assertions.
    """

    def __init__(self, schedule=(), seed: int = 0,
                 weights: Optional[dict] = None,
                 slow_s: float = 0.01) -> None:
        for f in list(schedule) + list(weights or {}):
            if f not in FAULTS:
                raise ValueError(f"unknown fault {f!r}; one of {FAULTS}")
        self.schedule = list(schedule)
        self._i = 0
        self._rng = random.Random(seed)
        self.weights = dict(weights or {})
        self.slow_s = slow_s
        self.injected: dict[str, int] = {}

    def next_fault(self) -> str:
        if self._i < len(self.schedule):
            fault = self.schedule[self._i]
            self._i += 1
        elif self.weights:
            fault = self._rng.choices(
                list(self.weights), weights=list(self.weights.values()))[0]
        else:
            fault = OK
        self.injected[fault] = self.injected.get(fault, 0) + 1
        return fault

    def faults_injected(self) -> int:
        """Requests that got anything other than normal service."""
        return sum(n for f, n in self.injected.items() if f != OK)


def _corrupt_plan_response(header: dict, blob: bytes,
                           server=None) -> tuple[dict, bytes]:
    """A decodable response whose plan violates every invariant the
    sanity guard checks: all rows (null + padding included) admitted,
    flavor options far out of range.

    Session frames are covered too: a SYNC/legacy request carries the
    problem inline; for a DELTA the workload-axis width comes from the
    server's resident session (no session -> an in-band resync, which
    is itself a valid fault for the client's fallback path)."""
    if header.get("kind") == "delta":
        sess = (server.get_session(str(header.get("sid", "")))
                if server is not None else None)
        if sess is None or sess.kwargs is None:
            return {"ok": False, "resync": "session_missing"}, b""
        W1 = sess.kwargs["wl_cqid"].shape[0]
        return _corrupt_plan_arrays(header, W1)
    problem = deserialize_problem(header["meta"], blob)
    W1 = problem.wl_cqid.shape[0]
    return _corrupt_plan_arrays(header, W1)


def _corrupt_plan_arrays(header: dict, W1: int) -> tuple[dict, bytes]:
    admitted = np.ones(W1, dtype=bool)
    parked = np.zeros(W1, dtype=bool)
    admit_round = np.zeros(W1, dtype=np.int32)
    rounds = np.int32(1)
    if header["full"]:
        g = max(1, int(header.get("g_max", 1)))
        opt = np.full((W1, g), 1 << 20, dtype=np.int32)
        names = ["admitted", "opt", "admit_round", "parked", "rounds",
                 "usage", "wl_usage", "victim_reason"]
        arrays = [admitted, opt, admit_round, parked, rounds,
                  np.zeros(1, np.int32), np.zeros(1, np.int32),
                  np.zeros(W1, np.int32)]
    else:
        opt = np.full((W1,), 1 << 20, dtype=np.int32)
        names = ["admitted", "opt", "admit_round", "parked", "rounds",
                 "usage"]
        arrays = [admitted, opt, admit_round, parked, rounds,
                  np.zeros(1, np.int32)]
    buf = io.BytesIO()
    np.savez(buf, **dict(zip(names, arrays)))
    return {"ok": True, "names": names}, buf.getvalue()


class _ChaosHandler(socketserver.StreamRequestHandler):
    def handle(self) -> None:  # noqa: C901 - one branch per fault
        injector: FaultInjector = self.server.injector
        fault = injector.next_fault()
        if fault == CRASH_PRE:
            return
        try:
            header, blob = _recv(self.request, self.server.max_frame_bytes)
        except ConnectionError:
            return
        if fault == CRASH:
            return
        if fault == HANG:
            try:
                # never reply; unblock (and release the thread) when the
                # client's deadline fires and it closes the socket
                self.request.settimeout(_HANG_CAP_S)
                self.request.recv(1)
            except OSError:
                pass
            return
        if fault == OVERSIZE:
            h = json.dumps({"ok": True, "names": ["admitted"]}).encode()
            try:
                self.request.sendall(
                    struct.pack(">II", len(h), 0xFFFF_FFF0))
                self.request.sendall(h)
            except OSError:
                pass
            return
        if fault == TRUNCATE:
            h = json.dumps({"ok": True, "names": ["admitted"]}).encode()
            try:
                # declare 128 payload bytes, deliver 64, close
                self.request.sendall(struct.pack(">II", len(h), 128))
                self.request.sendall(h)
                self.request.sendall(b"\x00" * 64)
            except OSError:
                pass
            return
        if fault == GARBLE:
            junk = bytes(injector._rng.getrandbits(8) for _ in range(96))
            try:
                _send(self.request,
                      {"ok": True, "names": ["admitted", "opt"]}, junk)
            except OSError:
                pass
            return
        if fault == CORRUPT_PLAN:
            try:
                resp_h, resp_b = _corrupt_plan_response(
                    header, blob, self.server)
                _send(self.request, resp_h, resp_b)
            except OSError:
                pass
            return
        if fault == SLOW:
            time.sleep(injector.slow_s)
        # healthy tail: the production respond path, shared verbatim
        # (session frames included: the chaos server inherits the
        # production session store)
        respond(self.request, header, blob, self.server)


class ChaosSolverServer(SolverServer):
    """A SolverServer whose handler consults a FaultInjector per request.

    Drop-in for the production sidecar in tests and bench runs:
    ``ChaosSolverServer(path, FaultInjector(schedule=["crash", "ok"]))``.
    """

    def __init__(self, socket_path: str, injector: FaultInjector,
                 max_frame_bytes: Optional[int] = None) -> None:
        super().__init__(socket_path, max_frame_bytes=max_frame_bytes)
        self.injector = injector
        self.RequestHandlerClass = _ChaosHandler


class MeshFaultInjector:
    """Deterministic device-loss / mesh-shrink injection for the
    engine's multi-chip drain arms (docs/ROBUSTNESS.md "Mesh faults").

    Wires itself into ``SolverEngine.solve_fault_hook`` — the hook runs
    immediately before each local solve, tagged with the arm about to
    execute, so raising there is indistinguishable from the XLA runtime
    erroring at dispatch time (the closest a virtual-device test rig
    gets to yanking a chip). The engine's contract under test:

      mesh fault   -> the SAME drain re-runs on the single-chip arm
                      (solver_fallback_total{reason="mesh_error"});
      both arms    -> SolverUnavailable, and the scheduler finishes the
                      admission round on host cycles
                      (reason="device_error") — the full
                      mesh -> single-chip -> host chain, never silent;
      mesh shrink  -> refresh_mesh(max_devices=n) re-detects a narrower
                      mesh; the next drain re-pads, the session rides
                      the forced full sync, plans stay bit-identical.
    """

    def __init__(self, engine) -> None:
        self.engine = engine
        self._mesh_faults = 0
        self._all_faults = 0
        self.injected: dict[str, int] = {}
        engine.solve_fault_hook = self._hook

    def _count(self, kind: str) -> None:
        self.injected[kind] = self.injected.get(kind, 0) + 1

    def _hook(self, arm: str) -> None:
        if self._all_faults > 0:
            if arm == "single":
                self._all_faults -= 1  # terminal arm = one drain
            self._count(f"{arm}_lost")
            raise RuntimeError(
                f"injected device loss ({arm} arm unavailable)")
        if arm == "mesh" and self._mesh_faults > 0:
            self._mesh_faults -= 1
            self._count("mesh_lost")
            raise RuntimeError("injected mesh device loss")

    def lose_mesh(self, times: int = 1) -> None:
        """The next ``times`` mesh-arm solves fail (ICI/device loss)."""
        self._mesh_faults += int(times)

    def lose_all(self, times: int = 1) -> None:
        """The next ``times`` drains fail on EVERY local arm — the
        whole accelerator is gone; only host cycles remain."""
        self._all_faults += int(times)

    def shrink(self, n_devices: int) -> int:
        """Shrink the engine's mesh to ``n_devices`` (a partial device
        loss); returns the re-detected width."""
        self._count(f"shrink_{n_devices}")
        return self.engine.refresh_mesh(max_devices=n_devices)

    def restore(self) -> int:
        """Heal: clear pending faults and re-detect the full mesh."""
        self._mesh_faults = 0
        self._all_faults = 0
        return self.engine.refresh_mesh()

    def faults_injected(self) -> int:
        return sum(self.injected.values())


class CrashPointInjector:
    """Kill -9 the control plane at a named durability point
    (docs/DURABILITY.md; docs/ROBUSTNESS.md fault taxonomy).

    Two usage modes:

    - **subprocess** (the restart fault): ``env()`` returns the
      environment that arms the point inside a child control plane —
      ``persist/crashtest.py`` consumes it, SIGKILLs itself at the
      point, and a second invocation with ``--phase recover`` proves
      recovery. This is the production-faithful mode: the process
      really dies, nothing flushes.
    - **in-process** (unit tests): ``arm(mode="raise")`` makes the
      point raise :class:`kueue_oss_tpu.persist.hooks.CrashPoint`
      instead of killing, so a test can assert on the half-written
      state directly.

    Points: pre_fsync, torn_tail, post_fsync_pre_apply,
    mid_checkpoint, mid_drain (``persist.hooks.CRASH_POINTS``).
    """

    def __init__(self, point: str, after: int = 0,
                 mode: str = "kill") -> None:
        from kueue_oss_tpu.persist import hooks

        if point not in hooks.CRASH_POINTS:
            raise ValueError(f"unknown crash point {point!r}; "
                             f"one of {hooks.CRASH_POINTS}")
        self.point = point
        self.after = int(after)
        self.mode = mode

    def arm(self) -> "CrashPointInjector":
        from kueue_oss_tpu.persist import hooks

        hooks.arm(self.point, after=self.after, mode=self.mode)
        return self

    def disarm(self) -> None:
        from kueue_oss_tpu.persist import hooks

        hooks.disarm()

    def __enter__(self) -> "CrashPointInjector":
        return self.arm()

    def __exit__(self, *exc) -> None:
        self.disarm()

    def env(self) -> dict:
        """Environment arming this point in a child process."""
        return {"KUEUE_CRASH_POINT": self.point,
                "KUEUE_CRASH_AFTER": str(self.after),
                "KUEUE_CRASH_MODE": self.mode}


def __getattr__(name: str):
    # campaign composition layer (chaos/campaign.py) — lazy so that
    # importing the injectors never pulls the scheduler stack
    if name in ("ChaosCampaign", "CampaignSpec", "CampaignResult",
                "PROFILES", "PROFILE_SUBSYSTEM", "run_campaign"):
        from kueue_oss_tpu.chaos import campaign

        return getattr(campaign, name)
    raise AttributeError(name)


class NodeFlapInjector:
    """Seeded node-readiness flapping against the store.

    ``flap_down`` marks nodes NotReady (specific names, or a seeded
    sample); ``flap_up`` restores them. Pairing the two inside/outside
    the failure controller's grace period drives the flap-recovery path
    (controllers/failure_recovery.py) deterministically.
    """

    def __init__(self, store, seed: int = 0) -> None:
        self.store = store
        self._rng = random.Random(seed)
        self._down: list[str] = []

    def flap_down(self, count: int = 1,
                  names: Optional[list[str]] = None) -> list[str]:
        if names is None:
            pool = sorted(n for n, node in self.store.nodes.items()
                          if node.ready)
            names = self._rng.sample(pool, min(count, len(pool)))
        for n in names:
            node = self.store.nodes[n]
            node.ready = False
            self.store.upsert_node(node)
        self._down.extend(names)
        return list(names)

    def flap_up(self, names: Optional[list[str]] = None) -> list[str]:
        if names is None:
            names, self._down = self._down, []
        else:
            self._down = [n for n in self._down if n not in names]
        for n in names:
            node = self.store.nodes.get(n)
            if node is not None:
                node.ready = True
                self.store.upsert_node(node)
        return list(names)


class ClusterLossInjector:
    """Federation member-loss faults (docs/FEDERATION.md, ROBUSTNESS.md).

    Drives the three ways a federated fleet loses a member, against a
    live ``MultiKueueController`` (and optionally the shared farm's
    ``SolverServer``):

    - **worker silent-drop**: the worker stops heartbeating
      (``active=False``, ``last_seen`` frozen) without any cleanup —
      the hub must re-dispatch its workloads only after
      ``worker_lost_timeout_s`` elapses (workload.go remote-lost);
    - **farm-tenant eviction**: the shared sidecar drops every
      resident session of one tenant (capacity reclaim / chaos); the
      tenant's next frame must heal through RESYNC with zero impact on
      its neighbors' sessions;
    - **hub-link flap**: a drop/restore pair inside the grace window,
      which must NOT trigger re-dispatch.

    Deterministic: no clocks read here — callers pass ``now`` exactly
    like the controller's reconcile loop, so the grace-window boundary
    is driven, not raced. ``injected`` counts by fault kind.
    """

    def __init__(self, controller, farm_server=None,
                 seed: int = 0) -> None:
        self.controller = controller
        self.farm_server = farm_server
        self._rng = random.Random(seed)
        self.injected: dict[str, int] = {}

    def _count(self, kind: str) -> None:
        self.injected[kind] = self.injected.get(kind, 0) + 1

    def _cluster(self, name: Optional[str]):
        clusters = self.controller.clusters
        if name is None:
            pool = sorted(n for n, c in clusters.items() if c.active)
            if not pool:
                raise ValueError("no active worker to drop")
            name = pool[self._rng.randrange(len(pool))]
        return clusters[name]

    def drop_worker(self, name: Optional[str] = None) -> str:
        """Silent worker loss: stops heartbeating, state intact."""
        cluster = self._cluster(name)
        cluster.active = False
        self._count("worker_drop")
        return cluster.name

    def restore_worker(self, name: str, now: float) -> str:
        """The worker reconnects; its next reconcile marks it seen."""
        cluster = self.controller.clusters[name]
        cluster.active = True
        cluster.mark_seen(now)
        self._count("worker_restore")
        return name

    def flap_worker(self, name: str, now: float) -> str:
        """Drop + immediate restore (a link flap INSIDE the grace
        window when the caller reconciles before the timeout)."""
        self.drop_worker(name)
        self._count("worker_flap")
        return self.restore_worker(name, now)

    def evict_farm_tenant(self, tenant: str) -> int:
        """Drop every resident farm session of one tenant; returns the
        eviction count (metrics count reason=tenant_evicted)."""
        if self.farm_server is None:
            raise ValueError("no farm server wired to this injector")
        self._count("tenant_evict")
        return self.farm_server.drop_tenant(tenant)

    def faults_injected(self) -> int:
        return sum(self.injected.values())
