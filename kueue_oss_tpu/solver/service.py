"""Process-separated solver service (the gRPC-sidecar analog).

SURVEY.md §2.4: the reference's control plane is one Go process; the
TPU-native design adds a sidecar carrying the CQ×FlavorResource usage
tensor + pending-workload request tensor to a separate JAX solver
process, so the control plane never blocks on device compilation and
the solver can sit on the TPU host while the scheduler runs elsewhere.

Wire contract (BASELINE.json: tensor export ≙ Cache.Snapshot, plan
import ≙ assume path; docs/SOLVER_PROTOCOL.md has the full spec):

  legacy (stateless) request:
    header {kind?: "solve", caps, fs_enabled, full} + npz(problem arrays)
    response = header JSON {ok, names, spans} + npz(full plan arrays)

  session frames (delta-sync, the production path):
    SYNC:  header {kind: "sync", sid, epoch, checksum, meta, caps...}
           + npz(problem arrays) — (re)opens session ``sid`` with the
           full padded problem pinned on the sidecar across drains
    DELTA: header {kind: "delta", sid, epoch, base_epoch, checksum,
           meta_delta, caps...} + npz(dirty rows + small replacements)
    responses are COMPACT: header {ok, compact, epoch, spans} + npz of
    decided rows only (admitted/parked/evicted indices), not eight full
    W-sized arrays
    RESYNC: any session/epoch/checksum mismatch answers in-band
    {ok: false, resync: <reason>} and the client falls back to a full
    SYNC (counted in metrics.solver_resync_total — never silently wrong;
    the engine's plan guard still validates every imported plan)

Transport is a length-prefixed unix-domain socket (protocol framing is
what a gRPC stub would generate; no proto toolchain is assumed in the
image). The client side plugs into SolverEngine via `remote=`: the
engine still exports, verifies, and commits — only the solve itself
crosses the process boundary.

Resilience (this layer's failure contract):

- a truncated frame, EOF mid-frame, undecodable header/npz, or a frame
  above ``max_frame_bytes`` raises ``SolverProtocolError`` — never a
  confusing struct/zipfile error, and never an allocation sized by an
  attacker-controlled length prefix;
- ``SolverClient.solve`` runs under a per-call deadline with bounded
  retries (exponential backoff + seeded jitter, fresh connection per
  attempt = automatic reconnect) and collapses exhaustion into
  ``SolverUnavailable`` for the engine/breaker to act on;
- the server catches solve-side exceptions and reports them in-band
  (``{"ok": false}``) so one bad request cannot wedge a handler thread.
"""

from __future__ import annotations

import io
import json
import os
import random
import socket
import socketserver
import struct
import threading
import time
from typing import Optional

import numpy as np

from kueue_oss_tpu import metrics, resilience
from kueue_oss_tpu.persist import hooks as persist_hooks
from kueue_oss_tpu.solver.delta import (
    ARRAY_FIELDS,
    META_FIELDS,
    DeviceResidentProblem,
    SessionFrame,
    apply_delta,
    deserialize_delta,
    serialize_delta,
    state_checksum,
)
from kueue_oss_tpu.solver.resilience import SolverUnavailable
from kueue_oss_tpu.solver.tensors import SolverProblem

#: SolverProblem fields shipped as arrays; the rest go in the header
#: (canonical list lives in solver/delta.py, shared with the delta layer)
_ARRAY_FIELDS = ARRAY_FIELDS
_META_FIELDS = META_FIELDS


class SolverProtocolError(ConnectionError):
    """Garbled wire state: short read/EOF mid-frame, oversized frame, or
    an undecodable header/payload. Distinct from plain ConnectionError so
    callers can tell a *misbehaving* peer from an absent one."""


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        return default


def default_timeout_s() -> float:
    """Per-call deadline; KUEUE_SOLVER_TIMEOUT_S overrides the 600 s
    default (the pre-robustness hardcode) without a code change."""
    return _env_float("KUEUE_SOLVER_TIMEOUT_S", 600.0)


def default_max_frame_bytes() -> int:
    """Frame-size guard; KUEUE_SOLVER_MAX_FRAME_MB overrides 256 MiB.
    Checked BEFORE allocating, on both sides of the wire."""
    return int(_env_float("KUEUE_SOLVER_MAX_FRAME_MB", 256.0) * (1 << 20))


def default_max_sessions() -> int:
    """Resident-session cap; KUEUE_SOLVER_MAX_SESSIONS overrides 4.
    A federated farm (N tenants x ~2 kernel kinds each) must raise this
    or the LRU thrashes — evictions are counted, never silent."""
    return max(1, int(_env_float("KUEUE_SOLVER_MAX_SESSIONS", 4.0)))


def _send(sock: socket.socket, header: dict, blob: bytes) -> None:
    h = json.dumps(header).encode()
    sock.sendall(struct.pack(">II", len(h), len(blob)))
    sock.sendall(h)
    sock.sendall(blob)


def _recv_exact(sock: socket.socket, n: int,
                deadline: Optional[float] = None,
                clock=time.monotonic) -> bytes:
    """Read exactly n bytes; with ``deadline`` (absolute, in ``clock``
    units) the whole read is bounded, not just each recv: a peer
    dripping one byte per op-timeout would otherwise reset the clock on
    every chunk and stall far past the caller's budget."""
    buf = b""
    while len(buf) < n:
        if deadline is not None:
            remaining = deadline - clock()
            if remaining <= 0:
                raise TimeoutError(
                    f"deadline exhausted mid-frame: got {len(buf)} of "
                    f"{n} bytes")
            sock.settimeout(remaining)
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise SolverProtocolError(
                f"peer closed mid-frame: got {len(buf)} of {n} bytes")
        buf += chunk
    return buf


def _recv(sock: socket.socket,
          max_frame_bytes: Optional[int] = None,
          deadline: Optional[float] = None,
          clock=time.monotonic) -> tuple[dict, bytes]:
    if max_frame_bytes is None:
        max_frame_bytes = default_max_frame_bytes()
    hlen, blen = struct.unpack(
        ">II", _recv_exact(sock, 8, deadline, clock))
    if hlen + blen > max_frame_bytes:
        # reject before allocating: the length prefix is peer-controlled
        raise SolverProtocolError(
            f"frame of {hlen + blen} bytes exceeds the "
            f"{max_frame_bytes}-byte limit")
    try:
        header = json.loads(_recv_exact(sock, hlen, deadline, clock))
    except (ValueError, UnicodeDecodeError) as e:
        raise SolverProtocolError(f"undecodable frame header: {e}") from e
    if not isinstance(header, dict):
        raise SolverProtocolError("frame header is not a JSON object")
    return header, _recv_exact(sock, blen, deadline, clock)


def serialize_problem(p: SolverProblem) -> tuple[dict, bytes]:
    arrays = {}
    for name in _ARRAY_FIELDS:
        v = getattr(p, name)
        if v is not None:
            arrays[name] = np.asarray(v)
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    meta = {name: getattr(p, name) for name in _META_FIELDS}
    return meta, buf.getvalue()


def deserialize_problem(meta: dict, blob: bytes) -> SolverProblem:
    data = np.load(io.BytesIO(blob))
    kwargs = {name: (data[name] if name in data else None)
              for name in _ARRAY_FIELDS}
    kwargs.update(meta)
    return SolverProblem(**kwargs)


def _budgeted_h_max(header: dict, K: int) -> int:
    """The request's victim-search lane count under THIS process's
    work budget. The client ships the lanes its problem could use (CQ
    count up to its cap); the budget depends on the backend that runs
    the search, which only the device owner can name — a CPU-pinned
    control plane must not guess it for the sidecar's chip."""
    from kueue_oss_tpu.solver.full_kernels import (
        budgeted_lanes,
        lane_work_budget,
    )

    return budgeted_lanes(int(header["h_max"]), lane_work_budget(), K,
                          int(header["g_max"]))


def _solve_kernel(tensors, header: dict, mesh=None):
    """Run the jitted kernel matching the request params; returns
    (out tuple, legacy array names). With a ``mesh`` BOTH kernels
    block-shard the workload axis over it — the full kernel
    additionally shard_maps its victim-search lanes inside the solve
    (row and lane sharding compose) — and plans stay bit-identical to
    the single-chip kernels either way."""
    if header["full"]:
        from kueue_oss_tpu.solver.full_kernels import solve_backlog_full

        out = solve_backlog_full(
            tensors, header["g_max"],
            _budgeted_h_max(header, tensors.wl_req.shape[1]),
            header["p_max"], fs_enabled=header["fs_enabled"], mesh=mesh)
        names = ["admitted", "opt", "admit_round", "parked",
                 "rounds", "usage", "wl_usage", "victim_reason"]
    else:
        if mesh is not None:
            from kueue_oss_tpu.solver.meshutil import lean_mesh_solver

            out = lean_mesh_solver(mesh)(tensors)
        else:
            from kueue_oss_tpu.solver.kernels import solve_backlog

            out = solve_backlog(tensors)
        names = ["admitted", "opt", "admit_round", "parked",
                 "rounds", "usage"]
    return out, names


def _spans(header: dict, t0: float) -> list[dict]:
    """The response's trace-context spans: the sidecar solve itself
    plus (farmed requests) the DRR grant-wait the handler thread just
    paid. Every span names its ``source`` so the importing host tracer
    lands it on a stable per-process/per-tenant synthetic track."""
    tenant = str(header.get("tenant", ""))
    src_tail = tenant or "solver"
    span_args = {"full": bool(header["full"]),
                 "kind": header.get("kind", "solve"),
                 "source": f"sidecar:{src_tail}"}
    if tenant:
        span_args["tenant"] = tenant
    if header.get("trace_cycle") is not None:
        span_args["cycle"] = header["trace_cycle"]
    solve_dur_us = int((time.perf_counter() - t0) * 1e6)
    spans = [{"name": "sidecar_solve", "dur_us": solve_dur_us,
              "args": span_args}]
    try:
        from kueue_oss_tpu.federation.farm import last_grant_wait_s

        wait_s = last_grant_wait_s()
    except Exception:
        wait_s = 0.0
    if wait_s > 0.0:
        wait_args = {"kind": "grant_wait",
                     "source": f"farm:{src_tail}"}
        if tenant:
            wait_args["tenant"] = tenant
        if header.get("trace_cycle") is not None:
            wait_args["cycle"] = header["trace_cycle"]
        # the wait ENDED when the solve began: end_skew_us lets the
        # importing tracer place it just before the solve span instead
        # of overlapping it (both are end-aligned at response arrival)
        spans.append({"name": "farm_grant_wait",
                      "dur_us": int(wait_s * 1e6),
                      "end_skew_us": solve_dur_us,
                      "args": wait_args})
    return spans


def compact_plan(out, full: bool) -> dict[str, np.ndarray]:
    """Encode a plan as decided rows only: admitted indices (+ their
    flavor options and rounds), parked indices, and nonzero
    victim-reason rows — a few KB instead of eight W-sized arrays."""
    admitted = np.asarray(out[0]).astype(bool)
    opt = np.asarray(out[1])
    admit_round = np.asarray(out[2])
    parked = np.asarray(out[3]).astype(bool)
    adm_idx = np.nonzero(admitted)[0].astype(np.int32)
    arrays = {
        "adm_idx": adm_idx,
        "adm_opt": opt[adm_idx].astype(np.int32),
        "adm_round": admit_round[adm_idx].astype(np.int32),
        "park_idx": np.nonzero(parked)[0].astype(np.int32),
        "rounds": np.asarray(out[4]),
    }
    if full:
        vr = np.asarray(out[7])
        vr_idx = np.nonzero(vr)[0].astype(np.int32)
        arrays["vr_idx"] = vr_idx
        arrays["vr_val"] = vr[vr_idx].astype(np.int32)
    return arrays


def expand_compact_plan(data, W1: int, full: bool, g_max: int):
    """Client-side inverse of compact_plan: rebuild the dense arrays the
    engine's plan guard and apply paths consume. Reconstruction is pure
    scatter — overlaps or out-of-range indices in a corrupt response
    survive into the dense arrays for the sanity guard to reject."""
    adm_idx = np.asarray(data["adm_idx"])
    adm_opt = np.asarray(data["adm_opt"])
    admitted = np.zeros(W1, dtype=bool)
    parked = np.zeros(W1, dtype=bool)
    admitted[adm_idx] = True
    parked[np.asarray(data["park_idx"])] = True
    if full:
        g = adm_opt.shape[1] if adm_opt.ndim == 2 else max(1, g_max)
        opt = np.zeros((W1, g), dtype=np.int32)
        admit_round = np.full(W1, -1, dtype=np.int32)
    else:
        opt = np.zeros(W1, dtype=np.int32)
        admit_round = np.zeros(W1, dtype=np.int32)
    opt[adm_idx] = adm_opt
    admit_round[adm_idx] = np.asarray(data["adm_round"])
    rounds = np.asarray(data["rounds"])
    usage = np.zeros(1, dtype=np.int32)  # engine ignores usage tensors
    if not full:
        return admitted, opt, admit_round, parked, rounds, usage
    victim = np.zeros(W1, dtype=np.int32)
    victim[np.asarray(data["vr_idx"])] = np.asarray(data["vr_val"])
    return (admitted, opt, admit_round, parked, rounds, usage,
            np.zeros(1, dtype=np.int32), victim)


class _SidecarSession:
    """Resident state for one (sid) delta-sync session: the problem's
    numpy mirror + the device tensors pinned across drains (mesh-placed
    over the sidecar's ``wl`` mesh when one is detected and the padded
    axis shards evenly)."""

    def __init__(self, mesh=None) -> None:
        self.lock = threading.Lock()
        self.kwargs: Optional[dict] = None
        self.meta: Optional[dict] = None
        self.epoch = -1
        self.device = DeviceResidentProblem(mesh=mesh)


def _resync(reason: str) -> tuple[dict, bytes]:
    return {"ok": False, "resync": reason}, b""


def _solve_mesh(sess):
    """The mesh this solve should run on, or None. BOTH kernels follow
    the session's resident placement: DeviceResidentProblem row-shards
    the workload axis for lean AND full tensors (the full kernel then
    composes its victim-search lane shard_map on top) when the padded
    axis divides the mesh and the live-row floor clears. A session
    whose tensors stayed replicated solves single-chip — routing a
    replicated resident problem through the mesh solver would silently
    re-place it every drain."""
    return sess.device.mesh if sess.device.mesh_placed else None


def _solve_resilient(server, sess, tensors, header: dict,
                     problem: SolverProblem, frame):
    """Mesh solve with the sidecar-side mesh -> single-chip fallback.

    Mirrors the in-process engine's chain: a mesh fault (device loss,
    SPMD compile abort) trips the SERVER mesh, re-seeds the session's
    resident state unsharded, and serves the same request single-chip —
    one slow request instead of a permanently failing sidecar. Counted
    in this process's solver_fallback_total{mesh_error}; never silent.
    Successful mesh solves report this process's mesh width gauge and
    shard-imbalance histogram, exactly like the in-process engine arm.
    """
    from kueue_oss_tpu.solver import meshutil

    mesh = _solve_mesh(sess)
    if mesh is not None:
        try:
            out = _solve_kernel(tensors, header, mesh)[0]
            metrics.solver_mesh_devices.set(
                value=meshutil.mesh_devices(mesh))
            # both drains row-shard the workload axis now, so both
            # observe the block-shard skew the interleaved session
            # layout is meant to flatten
            metrics.solver_shard_imbalance.observe(
                value=meshutil.shard_imbalance(
                    problem.wl_cqid, problem.n_cqs, mesh))
            return out
        except Exception:
            metrics.solver_fallback_total.inc("mesh_error")
            metrics.solver_mesh_devices.set(value=0)
            if server is not None:
                server.mesh = None
            sess.device.mesh = None
            sess.device.tensors = None  # force an unsharded re-seed
            tensors = sess.device.update(problem, frame,
                                         bool(header["full"]))
    out = _solve_kernel(tensors, header, None)[0]
    metrics.solver_mesh_devices.set(value=0)
    return out


# -- pod-scale (multi-host) sidecar mode -------------------------------------
#
# docs/SOLVER_PROTOCOL.md "Pod-scale sessions": after a jax.distributed
# bootstrap (KUEUE_SOLVER_COORDINATOR / SolverBackendConfig
# coordinator_* fields) the detected mesh spans EVERY process's
# devices, and SPMD solves over it are collective — each process must
# enter the same jitted computation in the same order. The wire
# protocol therefore cannot run independently per host: process 0 (the
# coordinator) owns the unix socket and re-broadcasts each stateless
# request to the followers, which sit in follower_solve_loop() and
# join every solve. Delta-sync sessions are per-process resident state
# and are NOT supported in this mode — a session frame answers an
# in-band error (run pod-scale clients with sessions_enabled=false).


def _bcast_bytes(payload: Optional[bytes]) -> bytes:
    """One coordinator->follower broadcast of a byte blob. Process 0
    passes the payload; followers pass None and receive it. Two
    collectives — the int64 length, then the body — because
    broadcast_one_to_all needs shape agreement on every process.

    The body travels as int32 WORDS (zero-padded to a word boundary,
    the length collective carries the exact byte count): the XLA:CPU
    gloo all-reduce widens sub-32-bit integers on the wire, so a uint8
    body lands int32-strided in the receiver's uint8 buffer — each
    payload byte followed by three zeros, truncated at n.
    """
    from jax.experimental import multihost_utils as mhu

    if payload is None:
        n = int(mhu.broadcast_one_to_all(np.zeros((), np.int64)))
        body = mhu.broadcast_one_to_all(np.zeros((n + 3) // 4, np.int32))
        return np.asarray(body).tobytes()[:n]
    mhu.broadcast_one_to_all(np.int64(len(payload)))
    padded = payload + b"\x00" * (-len(payload) % 4)
    mhu.broadcast_one_to_all(np.frombuffer(padded, np.int32))
    return payload


def _multihost_solve(header: dict, blob: bytes, mesh):
    """The collective body every process of the pod mesh runs for one
    stateless request: deserialize the (identically broadcast)
    problem, pad + row-shard it over the global mesh, solve, and
    materialize the plan host-side everywhere (host_replicated inside
    the sharded entry points). Returns (out tuple, array names)."""
    problem = deserialize_problem(header["meta"], blob)
    if header["full"]:
        from kueue_oss_tpu.solver.sharded import solve_backlog_full_sharded

        out = solve_backlog_full_sharded(
            problem, mesh, header["g_max"],
            _budgeted_h_max(header, problem.wl_req.shape[1]),
            header["p_max"], fs_enabled=header["fs_enabled"])
        names = ["admitted", "opt", "admit_round", "parked",
                 "rounds", "usage", "wl_usage", "victim_reason"]
    else:
        from kueue_oss_tpu.solver.sharded import solve_backlog_sharded

        out = solve_backlog_sharded(problem, mesh)
        names = ["admitted", "opt", "admit_round", "parked",
                 "rounds", "usage"]
    return out, names


def follower_solve_loop(mesh_mode: Optional[str] = None) -> int:
    """Body for every non-coordinator process of a pod-scale sidecar:
    block on the coordinator's broadcast, join each collective solve,
    repeat until the shutdown op arrives. Returns the number of solves
    served (tests assert on it). Call AFTER
    meshutil.bootstrap_distributed — serve_multihost() wires both.

    A solve that raises does so DETERMINISTICALLY on every process of
    the pod (same program, same broadcast inputs), so the coordinator
    reports it in-band to its client while each follower swallows its
    own copy and stays in the loop — the broadcast order never skews.
    """
    from kueue_oss_tpu.solver.meshutil import detect_mesh

    mesh = detect_mesh(mesh_mode)
    if mesh is None:
        raise RuntimeError(
            "follower_solve_loop needs a mesh; a pod-scale sidecar "
            "without one cannot join collective solves")
    served = 0
    while True:
        header = json.loads(_bcast_bytes(None).decode("utf-8"))
        if header.get("op") == "shutdown":
            return served
        blob = _bcast_bytes(None)
        try:
            _multihost_solve(header, blob, mesh)
        except Exception:
            pass  # the coordinator's copy reports in-band
        served += 1


def serve_multihost(socket_path: str,
                    coordinator_address: Optional[str] = None,
                    num_processes: Optional[int] = None,
                    process_id: Optional[int] = None,
                    mesh_mode: Optional[str] = None,
                    **server_kwargs):
    """Pod-scale sidecar entry point.

    Bootstraps jax.distributed from the explicit coordinator args
    (SolverBackendConfig.coordinator_*) or KUEUE_SOLVER_COORDINATOR,
    then splits by rank: process 0 returns a ready ``SolverServer``
    whose stateless solves are re-broadcast to the pod (run
    serve_forever / serve_in_background on it; server_close() releases
    the followers); every other process enters follower_solve_loop and
    returns its served-solve count once the coordinator shuts down.
    """
    from kueue_oss_tpu.solver import meshutil

    n = meshutil.bootstrap_distributed(coordinator_address,
                                       num_processes, process_id)
    metrics.solver_multihost_processes.set(value=n)
    if meshutil.process_index() != 0:
        return follower_solve_loop(mesh_mode)
    server = SolverServer(socket_path, mesh_mode=mesh_mode,
                          **server_kwargs)
    server.multihost = n > 1
    return server


def _session_request(header: dict, blob: bytes,
                     server) -> tuple[dict, bytes]:
    """Handle a SYNC or DELTA frame against the server's session store."""
    t0 = time.perf_counter()
    kind = header["kind"]
    sid = str(header.get("sid", ""))
    tenant = str(header.get("tenant", ""))
    if kind == "sync":
        data = np.load(io.BytesIO(blob))
        kwargs = {name: (np.array(data[name]) if name in data else None)
                  for name in _ARRAY_FIELDS}
        meta = {k: int(v) for k, v in dict(header["meta"]).items()}
        want = header.get("checksum")
        if want is not None and state_checksum(kwargs, meta) != int(want):
            # a sync that decoded but doesn't match its own checksum is
            # transport corruption, not a session-state divergence
            return {"ok": False, "error": "sync frame checksum mismatch"
                    }, b""
        sess = (server.session(sid, tenant) if server is not None
                else _SidecarSession())
        with sess.lock:
            sess.kwargs, sess.meta = kwargs, meta
            sess.epoch = int(header.get("epoch", 0))
            problem = SolverProblem(**kwargs, **meta)
            frame = SessionFrame(epoch=sess.epoch,
                                 checksum=int(want or 0), delta=None)
            tensors = sess.device.update(problem, frame,
                                         bool(header["full"]))
            out = _solve_resilient(server, sess, tensors, header,
                                   problem, frame)
            arrays = compact_plan(out, bool(header["full"]))
            epoch = sess.epoch
    else:  # delta
        sess = (server.get_session(sid, tenant)
                if server is not None else None)
        if sess is None:
            return _resync("session_missing")
        with sess.lock:
            if sess.kwargs is None:
                return _resync("session_missing")
            if int(header["base_epoch"]) != sess.epoch:
                return _resync("epoch_mismatch")
            delta = deserialize_delta(header, blob)
            apply_delta(sess.kwargs, sess.meta, delta)
            # torn-tail kill point (docs/ROBUSTNESS.md): the delta's
            # dirty rows are applied but the epoch has not advanced and
            # the checksum is unverified — a SIGKILL here leaves (or, in
            # raise mode, simulates) torn resident session state that
            # the next drain must detect and heal through RESYNC
            persist_hooks.crash_if("sidecar_session_store")
            sess.epoch = delta.epoch
            if state_checksum(sess.kwargs, sess.meta) != delta.checksum:
                # resident state diverged from the host's: drop the
                # session so the client re-seeds it with a full SYNC
                server.drop_session(sid, tenant)
                return _resync("checksum_mismatch")
            problem = SolverProblem(**sess.kwargs, **sess.meta)
            frame = SessionFrame(epoch=delta.epoch,
                                 checksum=delta.checksum, delta=delta)
            tensors = sess.device.update(problem, frame,
                                         bool(header["full"]))
            out = _solve_resilient(server, sess, tensors, header,
                                   problem, frame)
            arrays = compact_plan(out, bool(header["full"]))
            epoch = sess.epoch
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    import jax

    from kueue_oss_tpu.solver.meshutil import mesh_devices

    # advertise the sidecar's mesh width so a mesh-less client can
    # re-pad its next drains to a shardable axis (engine._pad_target);
    # without this, a CPU-only control plane would ship pow2+1 rows
    # forever and the accelerator sidecar could never shard them. The
    # platform that just solved rides beside it: the control plane has
    # no backend of its own to ask what its plans were computed on.
    return {"ok": True, "compact": True, "epoch": epoch,
            "mesh_devices": mesh_devices(getattr(server, "mesh", None)
                                         if server is not None else None),
            "platform": jax.default_backend(),
            "spans": _spans(header, t0)}, buf.getvalue()


def solve_request(header: dict, blob: bytes,
                  server=None) -> tuple[dict, bytes]:
    """Run one solve for a decoded request; returns (header, npz blob).

    Shared by the production handler and the chaos harness (which wraps
    it to corrupt/delay/drop the response deterministically). ``server``
    carries the session store for SYNC/DELTA frames; without it, SYNC
    degrades to a stateless solve and DELTA answers resync.

    With a solver farm attached (``server.farm``, see
    federation/farm.py), the whole solve body runs under the farm's
    weighted deficit-round-robin admission: the tenant id from the
    frame header picks the queue, and an over-quota tenant gets an
    in-band backpressure error instead of solver time — the client
    collapses that into ``SolverUnavailable`` and the engine degrades
    to host cycles, so a starved tenant never wedges.

    The optional ``trace_cycle`` header field is the host scheduler's
    cycle id: the response carries a ``spans`` list timing the sidecar
    solve, tagged with that cycle, so the engine can merge it into the
    host Tracer's Chrome-trace export as one timeline.
    """
    farm = getattr(server, "farm", None)
    if farm is not None:
        resp, out = farm.run(
            str(header.get("tenant", "")),
            lambda: _solve_request_body(header, blob, server))
        if resp.get("ok"):
            # echo the DRR grant-wait so the client's engine can ledger
            # it per drain (solver_farm_grant_wait_seconds carries the
            # same value farm-side)
            from kueue_oss_tpu.federation.farm import last_grant_wait_s

            resp.setdefault("grant_wait_ms",
                            round(last_grant_wait_s() * 1e3, 3))
        return resp, out
    return _solve_request_body(header, blob, server)


def _solve_request_body(header: dict, blob: bytes,
                        server=None) -> tuple[dict, bytes]:
    kind = header.get("kind", "solve")
    if kind in ("sync", "delta"):
        if server is not None and getattr(server, "multihost", False):
            # sessions are per-process resident state; the pod-scale
            # coordinator serves stateless solves only (run the client
            # with sessions_enabled=false against this sidecar)
            return {"ok": False, "error": "delta-sync sessions are "
                    "unsupported in multihost mode"}, b""
        if kind == "delta" and server is None:
            return _resync("session_unsupported")
        return _session_request(header, blob, server)
    t0 = time.perf_counter()
    if (server is not None and getattr(server, "multihost", False)
            and getattr(server, "mesh", None) is not None):
        # collective pod solve: replay the request to the followers,
        # then join the same SPMD computation they run
        with server._multihost_lock:
            _bcast_bytes(json.dumps(header).encode("utf-8"))
            _bcast_bytes(blob)
            out, names = _multihost_solve(header, blob, server.mesh)
        buf = io.BytesIO()
        np.savez(buf, **{n: np.asarray(v) for n, v in zip(names, out)})
        return {"ok": True, "names": names,
                "spans": _spans(header, t0)}, buf.getvalue()
    problem = deserialize_problem(header["meta"], blob)
    if header["full"]:
        from kueue_oss_tpu.solver.full_kernels import to_device_full

        tensors = to_device_full(problem)
    else:
        from kueue_oss_tpu.solver.kernels import to_device

        tensors = to_device(problem)
    out, names = _solve_kernel(tensors, header)
    buf = io.BytesIO()
    np.savez(buf, **{n: np.asarray(v) for n, v in zip(names, out)})
    return {"ok": True, "names": names,
            "spans": _spans(header, t0)}, buf.getvalue()


def respond(sock: socket.socket, header: dict, blob: bytes,
            server=None) -> None:
    """Solve a decoded request and reply on ``sock``; solve-side
    exceptions are reported in-band, a vanished client is ignored.
    Shared by the production handler and the chaos harness's healthy
    tail, so the two cannot drift apart."""
    try:
        resp_header, resp_blob = solve_request(header, blob, server)
    except Exception as e:  # report in-band; don't wedge the thread
        resp_header, resp_blob = {"ok": False, "error": repr(e)}, b""
    try:
        _send(sock, resp_header, resp_blob)
    except OSError:
        return  # client gave up (deadline) mid-response


class _Handler(socketserver.StreamRequestHandler):
    def handle(self) -> None:
        try:
            # the read is deadline-bounded: a client that stalls
            # mid-frame must not pin this handler thread forever (the
            # server joins handler threads on close)
            header, blob = _recv(
                self.request, self.server.max_frame_bytes,
                deadline=time.monotonic() + self.server.read_timeout_s)
        except (ConnectionError, TimeoutError):
            return  # covers SolverProtocolError: drop the bad request
        respond(self.request, header, blob, self.server)


class SolverServer(socketserver.ThreadingUnixStreamServer):
    """The sidecar process body: `SolverServer(path).serve_forever()`."""

    allow_reuse_address = True
    # handler threads must not block process exit: a wedged client
    # connection would otherwise hang server_close() (block_on_close
    # joins non-daemon handler threads)
    daemon_threads = True

    def __init__(self, socket_path: str,
                 max_frame_bytes: Optional[int] = None,
                 read_timeout_s: Optional[float] = None,
                 max_sessions: Optional[int] = None,
                 mesh_mode: Optional[str] = None,
                 mesh_min_workloads: int = 1024) -> None:
        if os.path.exists(socket_path):
            os.unlink(socket_path)
        super().__init__(socket_path, _Handler)
        self.socket_path = socket_path
        self.max_frame_bytes = (max_frame_bytes if max_frame_bytes
                                is not None else default_max_frame_bytes())
        self.read_timeout_s = (read_timeout_s if read_timeout_s
                               is not None else default_timeout_s())
        #: delta-sync session store ((tenant, sid) -> _SidecarSession),
        #: LRU-capped so abandoned sessions can't accumulate resident
        #: problems. The tenant component namespaces the table: two
        #: control planes reusing a sid can never read each other's
        #: resident state (docs/FEDERATION.md).
        self.sessions: dict[tuple[str, str], _SidecarSession] = {}
        self._sessions_lock = threading.Lock()
        self.max_sessions = (max(1, int(max_sessions))
                             if max_sessions is not None
                             else default_max_sessions())
        #: optional federation/farm.py FarmScheduler; when set, every
        #: decoded request is admitted through its per-tenant DRR queue
        self.farm = None
        from kueue_oss_tpu.util import xla_cache

        xla_cache.enable()
        #: sidecar mesh detection (solver/meshutil.py): sessions place
        #: their resident lean tensors over the mesh and solve via the
        #: sharded SPMD drain; full solves lane-shard. KUEUE_SOLVER_MESH
        #: / mesh_mode governs it exactly like the in-process engine.
        try:
            from kueue_oss_tpu.solver.meshutil import detect_mesh

            self.mesh = detect_mesh(mesh_mode)
        except Exception:
            self.mesh = None
        #: problems narrower than this solve single-chip even with a
        #: mesh (the mesh is the large-backlog path)
        self.mesh_min_workloads = int(mesh_min_workloads)
        #: pod-scale coordinator mode (serve_multihost sets it): every
        #: stateless solve is re-broadcast to the follower processes
        #: and solved collectively over the global mesh; session
        #: frames answer an in-band error. The lock serializes the
        #: broadcast+solve pair — handler threads must not interleave
        #: collectives or the followers would decode skewed frames.
        self.multihost = False
        self._multihost_lock = threading.Lock()

    def session(self, sid: str, tenant: str = "") -> _SidecarSession:
        key = (tenant, sid)
        with self._sessions_lock:
            sess = self.sessions.pop(key, None)
            if sess is None:
                sess = _SidecarSession(mesh=self.mesh)
                sess.device.mesh_min_rows = self.mesh_min_workloads
            self.sessions[key] = sess  # re-insert = LRU touch
            while len(self.sessions) > self.max_sessions:
                self.sessions.pop(next(iter(self.sessions)))
                metrics.solver_session_evictions_total.inc("lru")
            return sess

    def get_session(self, sid: str,
                    tenant: str = "") -> Optional[_SidecarSession]:
        key = (tenant, sid)
        with self._sessions_lock:
            sess = self.sessions.pop(key, None)
            if sess is not None:
                self.sessions[key] = sess
            return sess

    def drop_session(self, sid: str, tenant: str = "") -> None:
        with self._sessions_lock:
            self.sessions.pop((tenant, sid), None)

    def drop_tenant(self, tenant: str) -> int:
        """Evict every resident session of one tenant (farm-side chaos /
        tenant decommission); the tenant's next frame answers
        ``resync: session_missing`` and its client re-seeds with a full
        SYNC — counted, never silent. Returns the eviction count."""
        with self._sessions_lock:
            victims = [k for k in self.sessions if k[0] == tenant]
            for k in victims:
                self.sessions.pop(k, None)
                metrics.solver_session_evictions_total.inc(
                    "tenant_evicted")
            return len(victims)

    def serve_in_background(self) -> threading.Thread:
        t = threading.Thread(target=self.serve_forever, daemon=True)
        t.start()
        return t

    def server_close(self) -> None:
        if self.multihost:
            self.multihost = False
            try:
                with self._multihost_lock:
                    _bcast_bytes(json.dumps({"op": "shutdown"}).encode())
            except Exception:
                pass  # followers already gone; don't wedge shutdown
        super().server_close()


class _ClientSession:
    """Client-side view of one sidecar session (per engine kernel kind)."""

    __slots__ = ("sid", "acked_epoch")

    def __init__(self) -> None:
        self.sid = os.urandom(8).hex()
        self.acked_epoch = -1


class _ResyncRequested(Exception):
    def __init__(self, reason: str) -> None:
        super().__init__(reason)
        self.reason = reason


class SolverClient:
    """Engine-side stub: SolverEngine(remote=SolverClient(path)).

    Every ``solve`` runs under a per-call deadline (``timeout_s``) with
    up to ``max_retries`` re-attempts on transport faults. Each attempt
    opens a fresh connection (automatic reconnect after a sidecar
    restart) and backs off exponentially with seeded jitter between
    attempts. Exhaustion — deadline or retries — raises
    ``SolverUnavailable`` for the engine's circuit breaker.

    With a ``frame`` (a delta-session SessionFrame from the engine's
    HostDeltaSession), the request goes out as a DELTA when the sidecar
    is known to hold the frame's base epoch, else a full SYNC; an
    in-band resync answer falls back to a SYNC within the same call
    (once — a second resync demand is a backend fault). Duplicate
    delivery is safe: the sidecar's epoch guard rejects an already-
    applied delta with a resync, which the SYNC fallback absorbs.

    ``clock``/``sleep`` are injectable so the chaos tests drive the
    deadline/backoff logic without real waiting.
    """

    #: engines check this before routing session frames here
    supports_sessions = True

    def __init__(self, socket_path: str,
                 timeout_s: Optional[float] = None,
                 max_retries: int = 2,
                 backoff_base_s: float = 0.05,
                 backoff_max_s: float = 2.0,
                 max_frame_bytes: Optional[int] = None,
                 jitter_seed: int = 0,
                 clock=time.monotonic,
                 sleep=time.sleep,
                 sessions: Optional[bool] = None,
                 tenant: str = "") -> None:
        self.socket_path = socket_path
        #: federation tenant id; rides EVERY frame header so the farm's
        #: DRR scheduler can bill the request and the sidecar keys the
        #: session under (tenant, sid) — empty = single-tenant sidecar
        self.tenant = str(tenant)
        self.timeout_s = (timeout_s if timeout_s is not None
                          else default_timeout_s())
        self.max_retries = max(0, int(max_retries))
        self.backoff_base_s = backoff_base_s
        self.backoff_max_s = backoff_max_s
        self.max_frame_bytes = (max_frame_bytes if max_frame_bytes
                                is not None else default_max_frame_bytes())
        self._rng = random.Random(jitter_seed)
        self._clock = clock
        self._sleep = sleep
        #: host cycle id shipped in the next request's header (set by
        #: SolverEngine before each solve) so sidecar spans come back
        #: tagged with the cycle they served
        self.trace_cycle: Optional[int] = None
        #: sidecar spans from the LAST successful solve's response header
        self.last_spans: list[dict] = []
        #: the farm's DRR grant-wait echoed in the LAST successful
        #: response (ms; 0 = dedicated sidecar or farm idle)
        self.last_grant_wait_ms = 0.0
        #: the sidecar's advertised mesh width (session responses);
        #: the engine aligns its pad target to it so the sidecar can
        #: shard the resident problem (0 = unknown / no sidecar mesh)
        self.remote_mesh_devices = 0
        #: the platform the sidecar's last session solve ran on
        #: ("tpu" / "cpu" / ...; "" = unknown)
        self.remote_platform = ""
        if sessions is None:
            sessions = os.environ.get("KUEUE_SOLVER_SESSIONS") != "0"
        self.use_sessions = bool(sessions)
        self._sessions: dict[str, _ClientSession] = {}
        #: wire accounting for bench/diagnostics: bytes per frame kind
        #: and the last successful frame's (kind, bytes)
        self.bytes_by_kind: dict[str, int] = {}
        self.frames_by_kind: dict[str, int] = {}
        self.last_frame: Optional[tuple[str, int]] = None

    @classmethod
    def from_config(cls, cfg) -> "SolverClient":
        """Build from a config.SolverBackendConfig."""
        if cfg.socket_path is None:
            raise ValueError("solver.socketPath is required for a remote "
                             "solver backend")
        return cls(cfg.socket_path,
                   timeout_s=cfg.timeout_seconds,
                   max_retries=cfg.max_retries,
                   backoff_base_s=cfg.retry_backoff_base_seconds,
                   backoff_max_s=cfg.retry_backoff_max_seconds,
                   max_frame_bytes=cfg.max_frame_bytes,
                   sessions=getattr(cfg, "sessions_enabled", None),
                   tenant=getattr(cfg, "tenant", "")
                   or os.environ.get("KUEUE_SOLVER_TENANT", ""))

    # -- payload builders --------------------------------------------------

    def _base_params(self, full: bool, g_max: int, h_max: int,
                     p_max: int, fs_enabled: bool) -> dict:
        params = {"full": full, "g_max": g_max, "h_max": h_max,
                  "p_max": p_max, "fs_enabled": fs_enabled}
        if self.tenant:
            params["tenant"] = self.tenant
        if self.trace_cycle is not None:
            params["trace_cycle"] = int(self.trace_cycle)
        return params

    def _build_payload(self, mode: str, problem: SolverProblem,
                       params: dict, frame, st) -> tuple[dict, bytes]:
        if mode == "legacy":
            meta, blob = serialize_problem(problem)
            header = {**params, "meta": meta}
        elif mode == "delta":
            dh, blob = serialize_delta(frame.delta)
            header = {**params, **dh, "kind": "delta", "sid": st.sid}
        else:  # sync / resync
            meta, blob = serialize_problem(problem)
            header = {**params, "meta": meta, "kind": "sync",
                      "sid": st.sid, "epoch": frame.epoch,
                      "checksum": frame.checksum}
        # enforce the frame guard on our OWN request too: a server-side
        # rejection of an oversized frame shows up as a reset/EOF and
        # would be misread as a transient connection fault and retried
        # (deterministically) every drain
        n_frame = len(json.dumps(header).encode()) + len(blob)
        if n_frame > self.max_frame_bytes:
            raise SolverUnavailable(
                f"request frame of {n_frame} bytes exceeds the "
                f"{self.max_frame_bytes}-byte limit (problem too large "
                "for the remote backend)")
        return header, blob

    def _account(self, kind: str, header: dict, blob: bytes) -> None:
        n = len(json.dumps(header).encode()) + len(blob)
        self.bytes_by_kind[kind] = self.bytes_by_kind.get(kind, 0) + n
        self.frames_by_kind[kind] = self.frames_by_kind.get(kind, 0) + 1
        self.last_frame = (kind, n)
        metrics.solver_session_frames_total.inc(kind)
        metrics.solver_session_bytes_total.inc(kind, by=float(n))
        # devtel transfer ledger: request frames are direction "tx"
        from kueue_oss_tpu.obs import devtel

        devtel.collector.note_wire("remote", self.tenant, n)

    # -- the call ----------------------------------------------------------

    def solve(self, problem: SolverProblem, *, full: bool,
              g_max: int = 1, h_max: int = 32, p_max: int = 128,
              fs_enabled: bool = False, frame=None,
              session_key: str = "default"):
        params = self._base_params(full, g_max, h_max, p_max, fs_enabled)
        self.last_spans = []
        self.last_grant_wait_ms = 0.0
        st = None
        mode = "legacy"
        if frame is not None and self.use_sessions:
            st = self._sessions.setdefault(session_key, _ClientSession())
            mode = ("delta" if (frame.delta is not None
                                and st.acked_epoch
                                == frame.delta.base_epoch)
                    else "sync")
        header, blob = self._build_payload(mode, problem, params,
                                           frame, st)
        deadline = self._clock() + self.timeout_s
        attempt = 0
        resynced = False
        last_err: Optional[BaseException] = None
        while True:
            remaining = deadline - self._clock()
            if remaining <= 0:
                metrics.solver_deadline_exceeded_total.inc()
                raise SolverUnavailable(
                    f"solver call deadline ({self.timeout_s}s) exhausted "
                    f"after {attempt} attempt(s): {last_err!r}"
                ) from last_err
            try:
                out = self._solve_once(header, blob, remaining,
                                       problem, params)
                if st is not None:
                    st.acked_epoch = frame.epoch
                self._account("resync" if resynced else mode,
                              header, blob)
                ctl = resilience.controller
                if ctl.active(resilience.FEDERATION, "farm_unavailable"):
                    ctl.report(resilience.FEDERATION, "farm_unavailable",
                               False, reason="solver farm answered; "
                                             "dedicated lane restored")
                return out
            except _ResyncRequested as e:
                # the sidecar lost (or never had) our session state:
                # fall back to a full SYNC within this same call. Does
                # not count against the transport retry budget — the
                # sidecar is demonstrably alive.
                metrics.solver_resync_total.inc(e.reason)
                if mode != "delta" or resynced:
                    raise SolverUnavailable(
                        f"sidecar demanded resync twice: {e.reason}")
                resynced = True
                mode = "sync"
                header, blob = self._build_payload(
                    "sync", problem, params, frame, st)
                continue
            except (TimeoutError, socket.timeout) as e:
                last_err = e
                metrics.solver_remote_failures_total.inc("timeout")
            except SolverProtocolError as e:
                last_err = e
                metrics.solver_remote_failures_total.inc("protocol")
            except OSError as e:  # conn refused/reset, missing socket, …
                last_err = e
                metrics.solver_remote_failures_total.inc("connection")
            attempt += 1
            if attempt > self.max_retries:
                raise SolverUnavailable(
                    f"solver call failed after {attempt} attempt(s): "
                    f"{last_err!r}") from last_err
            metrics.solver_remote_retries_total.inc()
            delay = min(self.backoff_base_s * (2 ** (attempt - 1)),
                        self.backoff_max_s)
            delay += self._rng.uniform(0, delay)  # full jitter
            delay = min(delay, max(0.0, deadline - self._clock()))
            if delay > 0:
                self._sleep(delay)

    def _solve_once(self, header: dict, blob: bytes, budget_s: float,
                    problem: SolverProblem, params: dict):
        sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        sock.settimeout(budget_s)  # bounds connect and the send as ops
        op_deadline = self._clock() + budget_s
        try:
            sock.connect(self.socket_path)
            _send(sock, header, blob)
            # the WHOLE response read shares one deadline — a slow-drip
            # peer must not reset the timer per chunk
            resp, body = _recv(sock, self.max_frame_bytes,
                               deadline=op_deadline, clock=self._clock)
        finally:
            sock.close()
        if not resp.get("ok", False):
            if isinstance(resp.get("resync"), str):
                raise _ResyncRequested(resp["resync"])
            # the sidecar is up but the solve itself failed; a retry
            # would deterministically fail again, so don't burn the
            # deadline on it
            metrics.solver_remote_failures_total.inc("server")
            err = str(resp.get("error", "unknown"))
            if "backpressure" in err:
                # the farm is throttling this whole control plane: the
                # federation ladder degrades past the farm rung and the
                # engine's breaker walks us down to host cycles
                resilience.controller.report(
                    resilience.FEDERATION, "farm_unavailable", True,
                    reason=f"farm refused the solve: {err}")
            raise SolverUnavailable(
                f"solver sidecar reported failure: {err}")
        spans = resp.get("spans")
        self.last_spans = spans if isinstance(spans, list) else []
        try:
            self.last_grant_wait_ms = float(
                resp.get("grant_wait_ms", 0.0) or 0.0)
        except (TypeError, ValueError):
            self.last_grant_wait_ms = 0.0
        try:
            self.remote_mesh_devices = int(resp.get("mesh_devices", 0))
        except (TypeError, ValueError):
            self.remote_mesh_devices = 0
        self.remote_platform = str(resp.get("platform", "") or "")
        try:
            data = np.load(io.BytesIO(body))
            if resp.get("compact"):
                return expand_compact_plan(
                    data, problem.wl_cqid.shape[0],
                    bool(params["full"]), int(params["g_max"]))
            names = resp.get("names")
            if not isinstance(names, list) or not names:
                raise SolverProtocolError(
                    "response header carries no names")
            return tuple(data[n] for n in names)
        except SolverProtocolError:
            raise
        except Exception as e:  # zipfile/np decode errors on corruption
            raise SolverProtocolError(
                f"undecodable plan payload: {e!r}") from e


def main(argv: Optional[list[str]] = None) -> int:
    """``python -m kueue_oss_tpu.solver.service <socket>``: the sidecar
    container's command (deploy/manifests/base/manager.yaml). Serves
    until SIGTERM/SIGINT; with KUEUE_SOLVER_COORDINATOR set, the
    non-coordinator ranks join the pod's collective solves instead."""
    import argparse
    import signal

    ap = argparse.ArgumentParser(
        prog="python -m kueue_oss_tpu.solver.service",
        description="kueue_oss_tpu solver sidecar")
    ap.add_argument("socket_path",
                    help="unix-domain socket to serve on")
    args = ap.parse_args(argv)
    server = serve_multihost(args.socket_path)
    if not isinstance(server, SolverServer):
        return 0  # follower rank: the coordinator shut the pod down

    def stop(*_):
        # shutdown() blocks until serve_forever returns, so it cannot
        # run on the thread that serve_forever is interrupted on
        threading.Thread(target=server.shutdown, daemon=True).start()

    signal.signal(signal.SIGTERM, stop)
    signal.signal(signal.SIGINT, stop)
    try:
        server.serve_forever()
    finally:
        server.server_close()
        if os.path.exists(args.socket_path):
            os.unlink(args.socket_path)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
