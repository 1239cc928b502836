"""Multi-chip SPMD drain: workloads sharded over a device mesh.

Scaling model: the workload axis (the dimension that grows — pending
backlogs of 10^5-10^7 entries) is sharded across the mesh's ``wl`` axis;
the node/quota state (10^3 nodes) is replicated. Each round needs three
small collectives, all riding ICI:

  1. per-CQ head rank:   pmin over a [C]-vector of local segment minima
  2. per-CQ head index:  pmin over a [C]-vector (two-pass argmin, int32)
  3. candidate payload:  psum of [C,K,F] request rows + [C] metadata
                         (each head lives on exactly one shard)

The nomination + admission scan then runs replicated (identical on every
device — it only touches [C]- and [N,F]-sized state), and each device
updates the admitted/parked/option/round plan state for its own workload
shard. This keeps per-round collective volume at ~C*K*F ints regardless
of backlog size.

The drain is the PRODUCTION lean path, not a dry-run harness: it
returns the full ``solve_backlog`` contract — (admitted, opt,
admit_round, parked, rounds, usage) — bit-identical to the single-chip
kernel on the same padded problem, so `SolverEngine` and the sidecar
route large backlogs here without changing a byte of the apply path
(engine mesh routing: solver/engine.py; placement + resident state:
solver/delta.py DeviceResidentProblem; detection: solver/meshutil.py).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kueue_oss_tpu.solver.kernels import (
    M_NOFIT,
    ProblemTensors,
    _round_scan,
    available_all,
    nominate,
    potential_available_all,
    refresh_cohort_usage,
)
from kueue_oss_tpu.solver.tensors import BIG, SolverProblem

#: NamedSharding specs for the lean ProblemTensors: workload axis
#: sharded, node/CQ state replicated. Shared by the engine's resident
#: device state and the ad-hoc solve path below.
LEAN_WL_FIELDS = ("wl_cqid", "wl_rank", "wl_prio", "wl_ts", "wl_uid",
                  "wl_req", "wl_valid")


def pad_workloads(p: SolverProblem, multiple: int) -> SolverProblem:
    """Pad the workload axis so (W+1) divides evenly across the mesh.

    Padding rows replicate the null-workload row (rank BIG, null CQ id,
    no options), so they are never selected as heads. Fills must not
    alias real rows: ``wl_uid`` pads with BIG (a real uid-0 row must
    stay distinguishable from padding), every flag with its inert
    value.
    """
    W1 = p.wl_cqid.shape[0]
    target = ((W1 + multiple - 1) // multiple) * multiple
    pad = target - W1
    if pad == 0:
        return p
    C = p.cq_node.shape[0]

    def pad1(a, fill):
        widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        return np.pad(a, widths, constant_values=fill)

    return dataclasses.replace(
        p,
        wl_cqid=pad1(p.wl_cqid, C),
        wl_rank=pad1(p.wl_rank, BIG),
        wl_prio=pad1(p.wl_prio, 0),
        wl_ts=pad1(p.wl_ts, 0),
        wl_uid=pad1(p.wl_uid, BIG),
        wl_req=pad1(p.wl_req, 0),
        wl_valid=pad1(p.wl_valid, False),
    )


def lean_shardings(mesh: Mesh, axis: str = "wl") -> dict:
    """field -> NamedSharding for mesh-placing lean problem tensors."""
    row = NamedSharding(mesh, P(axis))
    rep = NamedSharding(mesh, P())
    return {f: (row if f in LEAN_WL_FIELDS else rep)
            for f in ProblemTensors._fields}


def place_lean_tensors(t: ProblemTensors, mesh: Mesh,
                       axis: str = "wl") -> ProblemTensors:
    """Mesh-place lean tensors: workload rows block-sharded over the
    ``wl`` axis, tree/CQ state replicated. Requires an evenly divisible
    padded axis (meshutil.align_pad_target)."""
    n_dev = mesh.shape[axis]
    W1 = t.wl_cqid.shape[0]
    if W1 % n_dev != 0:
        raise ValueError(
            f"workload axis of {W1} rows does not shard over {n_dev} "
            "devices; pad with meshutil.align_pad_target first")
    sh = lean_shardings(mesh, axis)
    return t._replace(**{
        f: jax.device_put(getattr(t, f), sh[f])
        for f in ProblemTensors._fields})


def maybe_place_lean(t: ProblemTensors, problem: SolverProblem, mesh,
                     min_rows: int = 0,
                     axis: str = "wl") -> tuple[ProblemTensors, bool]:
    """Mesh-place lean tensors when the policy allows: a mesh exists,
    the padded axis divides evenly, and the LIVE row count clears
    ``min_rows``. One placement policy, shared by the resident device
    state and the engine's sessionless path. Returns (tensors,
    placed)."""
    from kueue_oss_tpu.solver.meshutil import live_rows, mesh_divisible

    if (mesh is None
            or not mesh_divisible(mesh, problem.wl_cqid.shape[0])
            or live_rows(problem.wl_cqid, problem.n_cqs) < min_rows):
        return t, False
    return place_lean_tensors(t, mesh, axis), True


def _local_heads(t_local, C, w_offset, admitted, parked):
    """Per-CQ (min rank, head index) over this device's workload shard."""
    W_loc = t_local.wl_rank.shape[0]
    pending = ~admitted & ~parked
    rank_eff = jnp.where(pending, t_local.wl_rank, BIG)
    min_rank = jax.ops.segment_min(
        rank_eff, t_local.wl_cqid, num_segments=C + 1)[:C]
    w_global = jnp.arange(W_loc, dtype=jnp.int32) + w_offset
    is_head = rank_eff == min_rank[jnp.minimum(t_local.wl_cqid, C)]
    head_w = jax.ops.segment_min(
        jnp.where(is_head & pending, w_global, BIG), t_local.wl_cqid,
        num_segments=C + 1)[:C]
    return min_rank, head_w


def make_sharded_drain(mesh: Mesh, axis: str = "wl"):
    """Build the sharded PRODUCTION drain for a mesh.

    Call with mesh-placed (or host) tensors whose padded workload axis
    divides evenly; returns the full solve_backlog tuple (admitted,
    opt, admit_round, parked, rounds, usage), bit-identical to the
    single-chip kernel on the same padded problem.
    """

    n_dev = mesh.shape[axis]

    def drain(t: ProblemTensors):
        C = t.cq_node.shape[0]
        W1 = t.wl_rank.shape[0]
        K = t.wl_req.shape[1]
        F = t.wl_req.shape[2]
        shard = W1 // n_dev

        node_specs = ProblemTensors(
            parent=P(), depth=P(), height=P(), has_parent=P(), is_cq=P(),
            path=P(), subtree=P(), local_quota=P(), nominal=P(),
            has_borrow=P(), borrow_limit=P(), usage0=P(), cq_node=P(),
            cq_strict=P(), cq_try_next=P(), cq_nflavors=P(),
            wl_cqid=P(axis), wl_rank=P(axis), wl_prio=P(axis),
            wl_ts=P(axis), wl_uid=P(axis), wl_req=P(axis), wl_valid=P(axis),
        )

        @partial(
            jax.shard_map, mesh=mesh,
            in_specs=(node_specs,),
            out_specs=(P(axis), P(axis), P(axis), P(axis), P(), P()),
        )
        def run(tl: ProblemTensors):
            my = jax.lax.axis_index(axis)
            w_offset = (my * shard).astype(jnp.int32)
            pot = potential_available_all(tl)

            def cond(state):
                return state[-2] & (state[-1] < W1 + C + 2)

            def body(state):
                (usage, admitted, parked, opt, admit_round, cursor_c,
                 prev_head, _, rounds) = state

                # --- head selection across shards (2x pmin over ICI) ---
                min_rank_l, head_w_l = _local_heads(
                    tl, C, w_offset, admitted, parked)
                min_rank = jax.lax.pmin(min_rank_l, axis)
                head_valid_l = min_rank_l == min_rank
                head_w = jax.lax.pmin(
                    jnp.where(head_valid_l, head_w_l, BIG), axis)
                has_head = min_rank < BIG

                # --- candidate payload: psum of one-hot rows -----------
                local_w = head_w - w_offset
                mine = has_head & (local_w >= 0) & (local_w < shard)
                lw = jnp.clip(local_w, 0, shard - 1)
                payload_req = jnp.where(
                    mine[:, None, None], tl.wl_req[lw], 0)
                payload_valid = jnp.where(mine[:, None], tl.wl_valid[lw],
                                          False)
                payload_prio = jnp.where(mine, tl.wl_prio[lw], 0)
                payload_ts = jnp.where(mine, tl.wl_ts[lw], 0)
                payload_uid = jnp.where(mine, tl.wl_uid[lw], 0)
                req_c = jax.lax.psum(payload_req, axis)
                valid_c = jax.lax.psum(payload_valid.astype(jnp.int32),
                                       axis) > 0
                prio_c = jax.lax.psum(payload_prio, axis)
                ts_c = jax.lax.psum(payload_ts, axis)
                uid_c = jax.lax.psum(payload_uid, axis)

                # --- replicated nomination + scan over candidate rows --
                # Build a candidate-indexed pseudo problem: candidates map
                # 1:1 to CQ slots; reuse the single-chip kernels by
                # substituting gathered arrays.
                t_cand = tl._replace(
                    wl_cqid=jnp.concatenate(
                        [jnp.arange(C, dtype=jnp.int32), jnp.array([C])]),
                    wl_rank=jnp.concatenate(
                        [jnp.where(has_head, min_rank, BIG),
                         jnp.array([BIG], dtype=jnp.int32)]),
                    wl_prio=jnp.concatenate(
                        [prio_c, jnp.array([0], dtype=jnp.int32)]),
                    wl_ts=jnp.concatenate(
                        [ts_c, jnp.array([0], dtype=ts_c.dtype)]),
                    wl_uid=jnp.concatenate(
                        [uid_c, jnp.array([0], dtype=jnp.int32)]),
                    wl_req=jnp.concatenate([req_c, jnp.zeros(
                        (1, K, F), dtype=req_c.dtype)]),
                    wl_valid=jnp.concatenate([valid_c, jnp.zeros(
                        (1, K), dtype=bool)]),
                )
                cand_idx = jnp.where(has_head, jnp.arange(C), C)
                # The flavor cursor belongs to a workload: reset it when a
                # CQ's head changed since last round.
                same_head = head_w == prev_head
                cursor_eff = jnp.concatenate(
                    [jnp.where(same_head, cursor_c[:C], 0),
                     jnp.zeros((1,), dtype=jnp.int32)])
                avail = available_all(tl, usage)
                mode, k_chosen, borrow, next_cursor = nominate(
                    t_cand, usage, avail, pot, cand_idx.astype(jnp.int32),
                    cursor_eff)

                is_head = has_head
                strict_head = tl.cq_strict & is_head
                park_now = is_head & (mode == M_NOFIT) & ~strict_head

                adm_c = jnp.zeros(C + 1, dtype=bool)
                park_c = jnp.zeros(C + 1, dtype=bool)
                park_c = park_c.at[cand_idx].set(park_now)
                cq_usage, adm_c, park_c, any_admitted = _round_scan(
                    t_cand, usage, usage, adm_c, park_c,
                    cand_idx.astype(jnp.int32), mode, k_chosen, borrow)
                usage = refresh_cohort_usage(tl, cq_usage)

                # --- scatter results back to the local shard -----------
                adm_slot = adm_c[:C]
                park_slot = park_c[:C]
                # Scatter-or / scatter-max (duplicate clipped indices
                # from non-owned slots must not clobber owned writes; a
                # row is admitted at most once, so max with the inert
                # fill is exact).
                newly = mine & adm_slot
                admitted = admitted.at[lw].max(newly)
                parked = parked.at[lw].max(mine & park_slot)
                opt = opt.at[lw].max(jnp.where(newly, k_chosen, 0))
                admit_round = admit_round.at[lw].max(
                    jnp.where(newly, rounds, -1))
                keep = is_head & ~adm_slot
                cursor_next = jnp.where(keep, next_cursor, 0)
                cursor_changed = jnp.any(
                    is_head & (cursor_next != cursor_eff[:C]))
                cursor_c = cursor_c.at[:C].set(cursor_next)

                # Progress must be computed from values replicated across
                # devices (heads are never already-parked, so any park
                # this round shows up in park_slot & is_head).
                progress = (any_admitted
                            | jnp.any(park_slot & is_head)
                            | cursor_changed)
                return (usage, admitted, parked, opt, admit_round,
                        cursor_c, head_w, progress, rounds + 1)

            def varying(x):
                return jax.lax.pcast(x, (axis,), to="varying")

            init = (
                tl.usage0,
                # admitted/parked/opt/admit_round are per-shard plan
                # state: mark them varying over the mesh axis so the
                # carry types line up.
                varying(jnp.zeros((shard,), dtype=bool)),
                varying(jnp.zeros((shard,), dtype=bool)),
                varying(jnp.zeros((shard,), dtype=jnp.int32)),
                varying(jnp.full((shard,), -1, dtype=jnp.int32)),
                jnp.zeros((C + 1,), dtype=jnp.int32),
                jnp.full((C,), BIG, dtype=jnp.int32),
                jnp.ones((), dtype=bool),
                jnp.zeros((), dtype=jnp.int32),
            )
            (usage, admitted, parked, opt, admit_round, _, _, _,
             rounds) = jax.lax.while_loop(cond, body, init)
            return admitted, opt, admit_round, parked, rounds, usage

        return run(t)

    return drain


def full_shardings(mesh: Mesh, axis: str = "wl") -> dict:
    """field -> NamedSharding for mesh-placing FULL problem tensors:
    the [W+1] workload-axis fields (full_kernels.FULL_WL_FIELDS)
    block-shard; tree/CQ/flavor state replicates."""
    from kueue_oss_tpu.solver.full_kernels import (
        FULL_WL_FIELDS,
        FullTensors,
    )

    row = NamedSharding(mesh, P(axis))
    rep = NamedSharding(mesh, P())
    return {f: (row if f in FULL_WL_FIELDS else rep)
            for f in FullTensors._fields}


def place_full_tensors(t, mesh: Mesh, axis: str = "wl"):
    """Mesh-place FULL tensors: workload rows block-sharded over the
    ``wl`` axis (cross-shard victim gathers/psums are inserted by the
    partitioner), everything else replicated. Requires an evenly
    divisible padded axis (meshutil.align_pad_target /
    tensors.pad_workloads)."""
    n_dev = mesh.shape[axis]
    W1 = t.wl_cqid.shape[0]
    if W1 % n_dev != 0:
        raise ValueError(
            f"workload axis of {W1} rows does not shard over {n_dev} "
            "devices; pad with meshutil.align_pad_target first")
    sh = full_shardings(mesh, axis)
    return t._replace(**{
        f: jax.device_put(getattr(t, f), sh[f])
        for f in type(t)._fields})


def maybe_place_full(t, problem: SolverProblem, mesh,
                     min_rows: int = 0, axis: str = "wl"):
    """Mesh-place FULL tensors when the policy allows — the same
    gate as maybe_place_lean (mesh present, divisible padded axis,
    live rows clear the floor), shared by the resident device state
    and the engine's sessionless full path. Returns (tensors,
    placed)."""
    from kueue_oss_tpu.solver.meshutil import live_rows, mesh_divisible

    if (mesh is None
            or not mesh_divisible(mesh, problem.wl_cqid.shape[0])
            or live_rows(problem.wl_cqid, problem.n_cqs) < min_rows):
        return t, False
    return place_full_tensors(t, mesh, axis), True


def solve_backlog_full_sharded(problem: SolverProblem, mesh: Mesh,
                               g_max: int, h_max: int = 32,
                               p_max: int = 128, fs_enabled: bool = False,
                               axis: str = "wl", round_cap: int = 0):
    """Multi-chip PREEMPTION-capable drain, row- AND lane-sharded.

    Scaling model: the workload axis block-shards over the mesh with
    NamedSharding (same placement as the lean drain — backlogs of
    10^5-10^7 rows are the growing dimension), and the partitioner
    inserts the cross-shard victim-candidate gathers/psums the round's
    bookkeeping needs. The victim searches — the round's dominant cost
    — additionally shard their LANE axis inside
    full_kernels._run_searches; lane sharding composes with row
    sharding (the search re-gathers the rows it scans), it does not
    replace it. Under a multi-host mesh
    (meshutil.bootstrap_distributed) the same program spans every
    process's devices.

    Padding inserts inert null-row replicas BEFORE the final null row
    (tensors.pad_workloads), so W_null keeps pointing at the real null
    row and every dump scatter lands exactly where the single-chip
    kernel puts it: results match solve_backlog_full bit-for-bit,
    including uneven caller row counts (W+1 not divisible by the
    mesh).
    """
    from kueue_oss_tpu.solver.full_kernels import (
        make_full_solver,
        to_device_full,
    )
    from kueue_oss_tpu.solver.meshutil import host_replicated
    from kueue_oss_tpu.solver.tensors import pad_workloads as _pad_rows

    n_dev = mesh.shape[axis]
    W1 = problem.wl_cqid.shape[0]
    target_w = W1 - 1 + ((-W1) % n_dev)
    padded = _pad_rows(problem, target_w)
    t = place_full_tensors(to_device_full(padded), mesh, axis)
    solver = make_full_solver(g_max, h_max, p_max, fs_enabled,
                              round_cap=round_cap, mesh=mesh, axis=axis)
    # the plan alone: the search counts behind it differ from one
    # device's (the mesh arm's lanes all run, full_kernels._run_searches)
    out = host_replicated(solver(t)[:8])
    if target_w + 1 == W1:
        return out

    def unpad(a):
        # real rows kept their indices; the null row moved to the end
        return np.concatenate([a[: W1 - 1], a[-1:]])

    admitted, opt, admit_round, parked, rounds, usage, wl_usage, vr = out
    return (unpad(admitted), unpad(opt), unpad(admit_round),
            unpad(parked), rounds, usage, unpad(wl_usage), unpad(vr))


def solve_backlog_sharded(problem: SolverProblem, mesh: Mesh,
                          axis: str = "wl"):
    """Shard, place, and drain a problem over the mesh.

    Returns the full plan on host: (admitted [W+1] bool, opt [W+1]
    int32, admit_round [W+1] int32, parked [W+1] bool, rounds int,
    usage [N+1, F]) — the same contract as ``solve_backlog``, sliced
    back to the caller's row count.
    """
    from kueue_oss_tpu.solver.kernels import to_device
    from kueue_oss_tpu.solver.meshutil import (host_replicated,
                                               lean_mesh_solver)

    n_dev = mesh.shape[axis]
    padded = pad_workloads(problem, n_dev)
    t = place_lean_tensors(to_device(padded), mesh, axis)
    # host_replicated is the identity on single-process runs; on a
    # multi-host (pod) mesh it allgathers the cross-process shards so
    # every process slices the same full plan below
    admitted, opt, admit_round, parked, rounds, usage = host_replicated(
        lean_mesh_solver(mesh, axis)(t))
    W1 = problem.wl_cqid.shape[0]
    admitted = np.asarray(admitted)[:W1].copy()
    parked = np.asarray(parked)[:W1].copy()
    opt = np.asarray(opt)[:W1].copy()
    admit_round = np.asarray(admit_round)[:W1].copy()
    admitted[-1] = False
    parked[-1] = False
    return (admitted, opt, admit_round, parked, int(np.asarray(rounds)),
            np.asarray(usage))
