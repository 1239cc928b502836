"""Mesh plumbing shared by the engine and the sidecar.

The multi-chip kernels (solver/sharded.py, full_kernels mesh lanes)
need two things every production call site repeats: mesh *detection*
(config / env / device-count auto), and a cache of jitted mesh drains
so every drain of the same (mesh, shape) reuses one compiled SPMD
program. Centralizing them here makes the sidecar's placement
decisions identical to the in-process engine's.

Mesh mode grammar (``SolverBackendConfig.mesh`` / ``KUEUE_SOLVER_MESH``):

- ``auto`` (default): build a 1-D ``wl`` mesh over all local devices
  when ``jax.device_count() > 1``; single-chip otherwise.
- ``off`` / ``none`` / ``0`` / ``1`` — and any unrecognized string —
  never build a mesh (unknown values fail CLOSED: a typo must not
  enable the multi-chip path).
- an integer ``n``: mesh over the first ``n`` local devices; fewer
  available devices means NO mesh, never a silently narrower one.
"""

from __future__ import annotations

from typing import Optional

MESH_AXIS = "wl"

#: KUEUE_SOLVER_COORDINATOR grammar: "host:port,num_processes,process_id"
COORDINATOR_ENV = "KUEUE_SOLVER_COORDINATOR"

#: one-shot jax.distributed bootstrap state (process-wide, like
#: jax.distributed itself); tests reset it between subprocess twins by
#: running each twin in its own interpreter
_distributed = {"initialized": False, "processes": 1, "process_id": 0}


def parse_coordinator(spec: Optional[str]
                      ) -> Optional[tuple[str, int, int]]:
    """Parse a ``host:port,num_processes,process_id`` coordinator spec
    (the KUEUE_SOLVER_COORDINATOR grammar). Returns None for
    absent/empty, and FAILS CLOSED (None + no multi-host init) on any
    malformed value — a typo must degrade to single-host, never
    half-initialize a distributed runtime."""
    if not spec:
        return None
    parts = [p.strip() for p in str(spec).split(",")]
    if len(parts) != 3 or not parts[0]:
        return None
    try:
        n, pid = int(parts[1]), int(parts[2])
    except ValueError:
        return None
    if n < 2 or not (0 <= pid < n):
        return None
    return parts[0], n, pid


def bootstrap_distributed(coordinator_address: Optional[str] = None,
                          num_processes: Optional[int] = None,
                          process_id: Optional[int] = None) -> int:
    """Idempotent multi-host bootstrap: ``jax.distributed.initialize``
    driven by explicit args (SolverBackendConfig.coordinator_*) or the
    ``KUEUE_SOLVER_COORDINATOR`` env ("host:port,num_processes,pid").

    Returns the process count (1 = single-host, nothing initialized).
    After a successful bootstrap ``jax.devices()`` is GLOBAL, so
    :func:`detect_mesh` builds the pod-wide mesh with no further
    changes. On the CPU backend the gloo collectives implementation is
    selected first — the default CPU collectives cannot execute
    cross-process computations at all — and each process should run ONE
    local device: gloo's TCP pairs carry untagged ordered frames, so
    concurrent per-device execution threads issuing collectives inside
    one SPMD program interleave on the pair and abort with a preamble
    size mismatch (real pods run one process per host regardless).
    """
    if _distributed["initialized"]:
        return _distributed["processes"]
    if coordinator_address is None:
        import os

        parsed = parse_coordinator(os.environ.get(COORDINATOR_ENV))
        if parsed is None:
            return 1
        coordinator_address, num_processes, process_id = parsed
    if not num_processes or num_processes < 2:
        return 1
    import jax

    # the platform list this process was started with, not an
    # initialized backend: jax.distributed.initialize must run before
    # any backend exists. Unset means "whatever JAX finds" — on a chip
    # host that is the chip, which needs no CPU collectives.
    if "cpu" in (jax.config.jax_platforms or "").split(","):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        # belt and suspenders for the gloo frame-interleaving hazard
        # above: synchronous dispatch keeps two PROGRAMS from being in
        # flight at once (the one-device-per-process deployment shape
        # handles the within-program case)
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=int(num_processes), process_id=int(process_id))
    _distributed.update(initialized=True,
                        processes=int(num_processes),
                        process_id=int(process_id))
    return int(num_processes)


def process_count() -> int:
    """jax process count AFTER any bootstrap (1 = single-host)."""
    if not _distributed["initialized"]:
        return 1
    import jax

    return int(jax.process_count())


def process_index() -> int:
    if not _distributed["initialized"]:
        return 0
    import jax

    return int(jax.process_index())


def host_replicated(arrays) -> tuple:
    """Materialize global (possibly cross-process sharded) solver
    outputs as full host numpy arrays on EVERY process. Collective —
    all processes of the mesh must call it in the same order. Identity
    (plain np.asarray) on single-process runs."""
    import numpy as np

    if process_count() < 2:
        return tuple(np.asarray(a) for a in arrays)
    from jax.experimental import multihost_utils as mhu

    out = []
    for a in arrays:
        if (getattr(a, "ndim", 1) == 0
                or getattr(a, "is_fully_replicated", False)):
            # replicated values are addressable everywhere already
            out.append(np.asarray(a))
        else:
            out.append(np.asarray(mhu.process_allgather(a, tiled=True)))
    return tuple(out)


def parse_mesh_mode(mode: Optional[str]) -> Optional[int]:
    """Normalize a mesh mode string to a device-count request.

    Returns None for "off", -1 for "auto" (all devices), or a positive
    explicit device count. Unknown strings FAIL CLOSED (off): a typo-ed
    env var intended to disable the multi-chip path must never enable
    it — config-file values are additionally validated at load
    (configuration.validate).
    """
    if mode is None:
        import os

        mode = os.environ.get("KUEUE_SOLVER_MESH") or "auto"
    mode = str(mode).strip().lower()
    if mode in ("auto", "on", "true", ""):
        return -1
    try:
        n = int(mode)
    except ValueError:
        return None  # "off"/"none"/"disabled"/typos: all off
    return n if n > 1 else None


def detect_mesh(mode: Optional[str] = None, max_devices: int = 0):
    """Build the 1-D ``wl`` mesh the mode asks for, or None.

    An explicit device count requires at least that many local devices
    — fewer yields no mesh (fail closed) rather than a silently
    narrower layout. ``max_devices`` (when > 0) caps the mesh width —
    the chaos harness's mesh-shrink injection re-detects with a lower
    cap, the way a real device loss shrinks the usable slice.
    """
    want = parse_mesh_mode(mode)
    if want is None:
        return None
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    if want < 0:
        n = len(devices)
    elif want > len(devices):
        # pinned width unavailable: no mesh, not a silently narrower
        # one (the docstring contract — an explicit count REQUIRES at
        # least that many devices; solver_mesh_devices reports 0)
        return None
    else:
        n = want
    if max_devices > 0:
        n = min(n, max_devices)
    if n < 2:
        return None
    return Mesh(np.array(devices[:n]), (MESH_AXIS,))


def mesh_devices(mesh) -> int:
    return int(mesh.shape[MESH_AXIS]) if mesh is not None else 0


def mesh_divisible(mesh, w1: int) -> bool:
    """Whether a [W+1]-row workload axis block-shards evenly."""
    return mesh is not None and w1 % mesh_devices(mesh) == 0


def align_pad_target(target_w: int, mesh, extra_width: int = 0) -> int:
    """Grow a pad target so the padded axis (target_w + null row)
    splits evenly over the mesh — and over ``extra_width`` when given
    (the REMOTE sidecar's advertised mesh, which need not match the
    client's local device count; lcm covers both). Sticky with a
    monotone pad high-water mark: the same widths always yield the same
    alignment, so session slot coordinates (shard, local row) stay
    stable across drains."""
    import math

    widths = [w for w in (mesh_devices(mesh), int(extra_width)) if w > 1]
    if not widths:
        return target_w
    m = math.lcm(*widths)
    return target_w + (-(target_w + 1)) % m


def live_rows(wl_cqid, n_cqs: int) -> int:
    """Real (non-padding, non-null, non-recycled) workload rows in a
    padded export — the count the mesh floors gate on. ONE definition,
    shared by engine routing, resident placement, and the sidecar."""
    import numpy as np

    return int((np.asarray(wl_cqid[:-1]) < n_cqs).sum())


def shard_imbalance(wl_cqid, n_cqs: int, mesh) -> float:
    """Real-row imbalance across shards: (max - min) / mean occupied
    rows per shard (0.0 = perfectly even). Padding and recycled session
    slots count as empty."""
    import numpy as np

    n = mesh_devices(mesh)
    if n < 2:
        return 0.0
    occ = np.asarray(wl_cqid) < n_cqs
    if occ.shape[0] % n != 0:
        # defense in depth: callers observe row-sharded drains (lean
        # and full), whose padded axis always divides; a non-divisible
        # axis has no block shards to skew
        return 0.0
    per = occ.reshape(n, -1).sum(axis=1).astype(np.float64)
    mean = float(per.mean())
    if mean <= 0:
        return 0.0
    return float((per.max() - per.min()) / mean)


#: jitted lean mesh drains keyed by (mesh, axis); shapes key further
#: inside jit's own cache
_lean_cache: dict = {}


def lean_mesh_solver(mesh, axis: str = MESH_AXIS):
    """Cached jitted production lean drain for ``mesh`` — the full
    solve_backlog contract (admitted, opt, admit_round, parked, rounds,
    usage), bit-identical to the single-chip kernel."""
    import jax

    key = (mesh, axis)
    fn = _lean_cache.get(key)
    if fn is None:
        from kueue_oss_tpu.solver.sharded import make_sharded_drain

        fn = jax.jit(make_sharded_drain(mesh, axis))
        _lean_cache[key] = fn
    return fn
