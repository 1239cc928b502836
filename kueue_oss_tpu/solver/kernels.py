"""Jitted admission kernels over dense quota tensors.

The drain kernel reproduces the reference scheduler's cycle semantics
(pkg/scheduler/scheduler.go:286-467) exactly, but runs the whole backlog in
one XLA program:

  round (= one reference cycle, lax.while_loop):
    1. head selection   — per-CQ lowest-rank pending workload (segment min)
    2. nomination       — batched flavor-option classification against the
                          hierarchical availability (level-wise top-down)
    3. entry ordering   — lexsort by (borrow level, -priority, timestamp)
    4. admission scan   — lax.scan in entry order: re-check fit under the
                          current usage, bubble usage up the cohort path;
                          Preempt-mode entries reserve entitled capacity
                          and park (reservations die with the round)
    5. rebuild          — cohort usage recomputed bottom-up from CQ rows,
                          mirroring the reference's fresh per-cycle snapshot

All control flow is lax.* (no data-dependent Python), shapes are static,
quantities are int32 (the exporter guarantees no overflow), so XLA maps the
batched phases onto the VPU and the scan stays on-chip.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from kueue_oss_tpu.solver.tensors import BIG, SolverProblem

# candidate modes
M_NOFIT = 0
M_PREEMPT = 1
M_FIT = 2


class ProblemTensors(NamedTuple):
    """Device-side mirror of SolverProblem (jit pytree)."""

    parent: jnp.ndarray
    depth: jnp.ndarray
    height: jnp.ndarray
    has_parent: jnp.ndarray
    is_cq: jnp.ndarray
    path: jnp.ndarray
    subtree: jnp.ndarray
    local_quota: jnp.ndarray
    nominal: jnp.ndarray
    has_borrow: jnp.ndarray
    borrow_limit: jnp.ndarray
    usage0: jnp.ndarray
    cq_node: jnp.ndarray
    cq_strict: jnp.ndarray
    cq_try_next: jnp.ndarray
    cq_nflavors: jnp.ndarray
    wl_cqid: jnp.ndarray
    wl_rank: jnp.ndarray
    wl_prio: jnp.ndarray
    wl_ts: jnp.ndarray
    wl_uid: jnp.ndarray
    wl_req: jnp.ndarray
    wl_valid: jnp.ndarray


def host_tensors(p: SolverProblem) -> ProblemTensors:
    """The lean kernel's input tensors as HOST (numpy) arrays.

    Split out of :func:`to_device` so callers that reuse resident device
    buffers (DeviceResidentProblem's donated full-sync overwrite) can
    build the new content without first materializing a second full set
    of device buffers."""
    import numpy as np

    is_cq = np.zeros(p.parent.shape[0], dtype=bool)
    is_cq[p.cq_node] = True
    return ProblemTensors(
        parent=p.parent,
        depth=p.depth,
        height=p.height,
        has_parent=p.has_parent,
        is_cq=is_cq,
        path=p.path,
        subtree=p.subtree,
        local_quota=p.local_quota,
        nominal=p.nominal,
        has_borrow=p.has_borrow,
        borrow_limit=p.borrow_limit,
        usage0=p.usage0,
        cq_node=p.cq_node,
        cq_strict=p.cq_strict,
        cq_try_next=p.cq_try_next,
        cq_nflavors=p.cq_nflavors,
        wl_cqid=p.wl_cqid,
        wl_rank=p.wl_rank,
        wl_prio=p.wl_prio,
        wl_ts=p.wl_ts,
        wl_uid=p.wl_uid,
        wl_req=p.wl_req,
        wl_valid=p.wl_valid,
    )


def to_device(p: SolverProblem) -> ProblemTensors:
    return jax.tree_util.tree_map(jnp.asarray, host_tensors(p))


# ---------------------------------------------------------------------------
# Hierarchical quota algebra, tensorized (resource_node.go)
# ---------------------------------------------------------------------------


def refresh_cohort_usage(t: ProblemTensors, usage: jnp.ndarray) -> jnp.ndarray:
    """Recompute cohort rows bottom-up from ClusterQueue rows.

    Mirrors the accumulate step of resource_node.go:210-217: a parent's
    usage is the sum over children of max(0, child_usage - child_local).
    """
    u = jnp.where(t.is_cq[:, None], usage, 0)
    d_max = t.path.shape[1]
    depth_col = t.depth[:, None]
    for d in range(d_max - 1, 0, -1):
        contrib = jnp.where(depth_col == d,
                            jnp.maximum(0, u - t.local_quota), 0)
        u = u.at[t.parent].add(contrib, mode="drop")
    return u


def accumulate_full_charge(parent: jnp.ndarray, depth: jnp.ndarray,
                           values: jnp.ndarray, d_max: int) -> jnp.ndarray:
    """Sum node-row values into every ancestor WITHOUT local-quota
    absorption — refresh_cohort_usage's relaxed cousin.

    The exact algebra absorbs each child's local quota on the way up
    (only the overflow bubbles); the convex relaxation
    (sim/relax.py) instead prices the AGGREGATE load under each
    node against that node's total headroom, which is exactly this
    full-charge accumulation. ``d_max`` is the static ancestor-path
    width (path.shape[1]).
    """
    u = values
    depth_col = depth[:, None]
    for d in range(d_max - 1, 0, -1):
        u = u.at[parent].add(jnp.where(depth_col == d, u, 0),
                             mode="drop")
    return u


def available_all(t: ProblemTensors, usage: jnp.ndarray) -> jnp.ndarray:
    """available() for every node, level-wise from the roots down.

    Mirrors resource_node.go:104-118: root avail = subtree - usage; child
    avail = localAvailable + min(parentAvail, storedInParent - usedInParent
    + borrowingLimit).
    """
    avail = t.subtree - usage  # correct for depth-0 (roots)
    local_avail = jnp.maximum(0, t.local_quota - usage)
    stored = t.subtree - t.local_quota
    used_in_parent = jnp.maximum(0, usage - t.local_quota)
    clamp = jnp.where(t.has_borrow,
                      stored - used_in_parent + t.borrow_limit, BIG)
    depth_col = t.depth[:, None]
    for d in range(1, t.path.shape[1]):
        parent_avail = avail[t.parent]
        cand = local_avail + jnp.minimum(parent_avail, clamp)
        avail = jnp.where(depth_col == d, cand, avail)
    return avail


def potential_available_all(t: ProblemTensors) -> jnp.ndarray:
    """potentialAvailable() for every node (resource_node.go:122-133)."""
    pot = t.subtree  # roots
    cap = jnp.where(t.has_borrow, t.subtree + t.borrow_limit, BIG)
    depth_col = t.depth[:, None]
    for d in range(1, t.path.shape[1]):
        parent_pot = pot[t.parent]
        cand = jnp.minimum(t.local_quota + parent_pot, cap)
        pot = jnp.where(depth_col == d, cand, pot)
    return pot


def borrow_levels(t: ProblemTensors, usage: jnp.ndarray,
                  cand_w: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """FindHeightOfLowestSubtreeThatFits, batched over candidates/options.

    Returns (level [C,K,F] int32, may_reclaim [C,K,F] bool) for each
    candidate workload's request; level is 0 where req == 0.
    Reference parity: classical/hierarchical_preemption.go:221-243.
    """
    null = t.parent.shape[0] - 1
    req = t.wl_req[cand_w]                       # [C,K,F]
    paths = t.path[t.cq_node]                    # [C,D]
    d_max = paths.shape[1]

    level = jnp.zeros_like(req)
    may_reclaim = jnp.zeros(req.shape, dtype=bool)
    found = req == 0
    rem = req
    for d in range(d_max):
        node = paths[:, d]                       # [C]
        node_valid = (node != null)[:, None, None]
        usage_n = usage[node][:, None, :]
        subtree_n = t.subtree[node][:, None, :]
        la_n = jnp.maximum(
            0, t.local_quota[node] - usage[node])[:, None, :]
        not_borrowing = usage_n + rem <= subtree_n
        newly = (~found) & not_borrowing & node_valid
        level = jnp.where(newly, t.height[node][:, None, None], level)
        may_reclaim = jnp.where(
            newly, t.has_parent[node][:, None, None], may_reclaim)
        found = found | newly
        rem = jnp.where(found | ~node_valid, rem, rem - la_n)
    # Not found anywhere: whole-hierarchy height, no proper subtree.
    root_idx = paths[:, d_max - 1]
    for d in range(d_max - 2, -1, -1):
        root_idx = jnp.where(root_idx == null, paths[:, d], root_idx)
    root_h = t.height[root_idx][:, None, None]
    level = jnp.where(found, level, root_h)
    return level, may_reclaim


# ---------------------------------------------------------------------------
# Per-round candidate nomination
# ---------------------------------------------------------------------------


@jax.named_scope("nominate")
def nominate(t: ProblemTensors, usage: jnp.ndarray, avail: jnp.ndarray,
             pot: jnp.ndarray, cand_w: jnp.ndarray, cursor: jnp.ndarray):
    """Classify each CQ's head: (mode, chosen option, borrow level,
    next cursor).

    Mirrors flavorassigner fitsResourceQuota + fungibility option
    selection, including the LastTriedFlavorIdx cursor: the search starts
    at ``cursor[head]`` and the returned next-cursor encodes where a
    re-nomination after a failed re-check must resume
    (flavorassigner.go:843,939-947). Preempt here corresponds to the
    reference's Preempt mode with NoCandidates (the solver path is used
    when no preemption policy is enabled, so SimulatePreemption would
    find no targets).
    """
    req = t.wl_req[cand_w]                        # [C,K,F]
    k_arange = jnp.arange(req.shape[1], dtype=jnp.int32)[None, :]
    cursor_c = cursor[cand_w][:, None]            # [C,1]
    valid = t.wl_valid[cand_w] & (k_arange >= cursor_c)  # [C,K]
    avail_cq = avail[t.cq_node][:, None, :]       # [C,1,F]
    pot_cq = pot[t.cq_node][:, None, :]
    nominal_cq = t.nominal[t.cq_node][:, None, :]

    level, may_reclaim = borrow_levels(t, usage, cand_w)

    nonzero = req > 0
    fit_fr = (~nonzero) | (req <= avail_cq)               # [C,K,F]
    within_cap = (~nonzero) | (req <= pot_cq)
    preemptish_fr = (~nonzero) | (
        within_cap & ((req <= nominal_cq) | may_reclaim))

    opt_fit = valid & jnp.all(fit_fr, axis=-1)            # [C,K]
    opt_preempt = valid & jnp.all(fit_fr | preemptish_fr, axis=-1)
    opt_level = jnp.max(jnp.where(nonzero, level, 0), axis=-1)  # [C,K]

    K = req.shape[1]
    k_idx = jnp.arange(K, dtype=jnp.int32)[None, :]

    def first_true(mask):  # [C,K] -> [C] first index or K
        return jnp.min(jnp.where(mask, k_idx, K), axis=1)

    # default policy (whenCanBorrow=Borrow): first fitting option.
    k_default = first_true(opt_fit)
    # whenCanBorrow=TryNextFlavor: first non-borrowing fit, else the fit
    # with the lowest borrow level (ties -> earliest flavor).
    k_nonborrow = first_true(opt_fit & (opt_level == 0))
    lvl_key = jnp.where(opt_fit, opt_level * K + k_idx, BIG)
    k_bestlvl = jnp.argmin(lvl_key, axis=1).astype(jnp.int32)
    k_try_next = jnp.where(
        k_nonborrow < K, k_nonborrow,
        jnp.where(jnp.any(opt_fit, axis=1), k_bestlvl, K))
    k_fit = jnp.where(t.cq_try_next, k_try_next, k_default)

    any_fit = k_fit < K
    k_preempt = first_true(opt_preempt & ~opt_fit)
    any_preempt = k_preempt < K

    k_chosen = jnp.where(any_fit, k_fit,
                         jnp.where(any_preempt, k_preempt, 0))
    k_chosen = k_chosen.astype(jnp.int32)
    mode = jnp.where(any_fit, M_FIT,
                     jnp.where(any_preempt, M_PREEMPT, M_NOFIT))
    borrow = jnp.take_along_axis(opt_level, k_chosen[:, None], axis=1)[:, 0]

    # Flavor cursor for re-nomination: the search breaks early only at a
    # fit the fungibility policy accepts (default: any fit; TryNextFlavor:
    # a non-borrowing fit); then the next attempt resumes at the following
    # flavor. Walking off the end resets the cursor to 0.
    early_break = jnp.where(t.cq_try_next, k_nonborrow < K, any_fit)
    nfl = t.cq_nflavors
    next_cursor = jnp.where(
        early_break & (k_chosen < nfl - 1), k_chosen + 1, 0)
    return mode, k_chosen, borrow, next_cursor.astype(jnp.int32)


# ---------------------------------------------------------------------------
# In-round admission scan (entry order, usage bubbling)
# ---------------------------------------------------------------------------


def _avail_along_path(t: ProblemTensors, usage: jnp.ndarray,
                      cq_node: jnp.ndarray) -> jnp.ndarray:
    """available() for one CQ under the current usage: walk root -> leaf."""
    path = t.path[cq_node]                        # [D]
    null = t.parent.shape[0] - 1
    avail = jnp.zeros((t.subtree.shape[1],), dtype=jnp.int32)
    started = jnp.zeros((), dtype=bool)
    for d in range(path.shape[0] - 1, -1, -1):
        node = path[d]
        is_valid = node != null
        usage_n = usage[node]
        subtree_n = t.subtree[node]
        local_q = t.local_quota[node]
        local_avail = jnp.maximum(0, local_q - usage_n)
        stored = subtree_n - local_q
        used_in_parent = jnp.maximum(0, usage_n - local_q)
        clamp = jnp.where(t.has_borrow[node],
                          stored - used_in_parent + t.borrow_limit[node], BIG)
        root_avail = subtree_n - usage_n
        child_avail = local_avail + jnp.minimum(avail, clamp)
        cand = jnp.where(started, child_avail, root_avail)
        avail = jnp.where(is_valid, cand, avail)
        started = started | is_valid
    return avail


def _add_usage_along_path(t: ProblemTensors, usage: jnp.ndarray,
                          cq_node: jnp.ndarray,
                          val: jnp.ndarray) -> jnp.ndarray:
    """addUsage with bubbling (resource_node.go:137-145) along one path."""
    path = t.path[cq_node]
    null = t.parent.shape[0] - 1
    for d in range(path.shape[0]):
        node = path[d]
        is_valid = node != null
        usage_n = usage[node]
        local_avail = jnp.maximum(0, t.local_quota[node] - usage_n)
        usage = usage.at[node].add(jnp.where(is_valid, val, 0))
        val = jnp.maximum(0, val - local_avail)
    return usage


@jax.named_scope("_round_scan")
def _round_scan(t: ProblemTensors, usage, cq_usage, admitted, parked,
                cand_w, mode, k_chosen, borrow):
    # strict queues never park (their head keeps blocking the queue)
    """Process this round's nominated heads in entry order.

    ``usage`` is the working tensor (admissions + reservations, bubbled);
    ``cq_usage`` carries only durable CQ-row usage (admissions). Cohort
    rows are rebuilt from it at round end, which also drops reservations —
    exactly like the reference's fresh per-cycle snapshot.
    """
    C = cand_w.shape[0]
    W_null = t.wl_rank.shape[0] - 1

    prio = t.wl_prio[cand_w]
    ts = t.wl_ts[cand_w]
    uid = t.wl_uid[cand_w]
    active = (cand_w != W_null) & (mode != M_NOFIT)
    sort_borrow = jnp.where(active, borrow, BIG)
    order = jnp.lexsort((uid, ts, -prio, sort_borrow))

    def step(carry, slot):
        usage, cq_usage, admitted, parked, any_admitted = carry
        w, cqid, m, k, brw = slot
        cq_node = t.cq_node[cqid]
        req = t.wl_req[w, k]                        # [F]
        is_active = (w != W_null) & (m != M_NOFIT)

        # Preempt mode: reserve entitled capacity and park
        # (scheduler.go reserveCapacityForUnreclaimablePreempt).
        usage_cq = usage[cq_node]
        nominal_cq = t.nominal[cq_node]
        bl = t.borrow_limit[cq_node]
        reserve_borrowing = jnp.where(
            t.has_borrow[cq_node],
            jnp.minimum(req, nominal_cq + bl - usage_cq), req)
        reserve_nominal = jnp.minimum(req, nominal_cq - usage_cq)
        reserve = jnp.maximum(
            0, jnp.where(brw > 0, reserve_borrowing, reserve_nominal))

        is_preempt = is_active & (m == M_PREEMPT)
        usage = _add_usage_along_path(
            t, usage, cq_node, jnp.where(is_preempt, reserve, 0))
        # Preempt-no-targets heads requeue with reason Generic: parked for
        # BestEffortFIFO, pushed back to the heap (still blocking) for
        # StrictFIFO (cluster_queue.go requeueIfNotPresent).
        parked = parked.at[w].set(
            parked[w] | (is_preempt & ~t.cq_strict[cqid]))

        # Fit mode: re-check under current usage, then admit.
        avail_now = _avail_along_path(t, usage, cq_node)
        still_fits = jnp.all((req == 0) | (req <= avail_now))
        do_admit = is_active & (m == M_FIT) & still_fits
        admit_vec = jnp.where(do_admit, req, 0)
        usage = _add_usage_along_path(t, usage, cq_node, admit_vec)
        cq_usage = cq_usage.at[cq_node].add(admit_vec)
        admitted = admitted.at[w].set(admitted[w] | do_admit)
        any_admitted = any_admitted | do_admit
        return (usage, cq_usage, admitted, parked, any_admitted), None

    slots = (cand_w[order], jnp.arange(C, dtype=jnp.int32)[order],
             mode[order], k_chosen[order], borrow[order])
    init = (usage, cq_usage, admitted, parked, jnp.zeros((), dtype=bool))
    (usage, cq_usage, admitted, parked, any_admitted), _ = jax.lax.scan(
        step, init, slots)
    return cq_usage, admitted, parked, any_admitted


# ---------------------------------------------------------------------------
# The drain loop
# ---------------------------------------------------------------------------


@jax.named_scope("_select_heads")
def _select_heads(t: ProblemTensors, admitted, parked):
    """Per-CQ lowest-rank pending workload (two-pass int32 segment min)."""
    C = t.cq_node.shape[0]
    W1 = t.wl_rank.shape[0]
    W_null = W1 - 1
    pending = ~admitted & ~parked
    rank_eff = jnp.where(pending, t.wl_rank, BIG)
    min_rank = jax.ops.segment_min(
        rank_eff[:-1], t.wl_cqid[:-1], num_segments=C + 1)[:C]
    w_idx = jnp.arange(W1 - 1, dtype=jnp.int32)
    is_head = rank_eff[:-1] == min_rank[t.wl_cqid[:-1]]
    head_w = jax.ops.segment_min(
        jnp.where(is_head, w_idx, W_null), t.wl_cqid[:-1],
        num_segments=C + 1)[:C]
    has_head = min_rank < BIG
    return jnp.where(has_head, head_w, W_null).astype(jnp.int32)


def _solve_backlog_impl(t: ProblemTensors):
    """Drain the backlog: run reference-equivalent cycles until quiescent.

    Returns (admitted [W+1] bool, chosen_option [W+1] int32,
    admit_round [W+1] int32, parked [W+1] bool, rounds int32,
    final usage [N+1, F]).
    """
    W1 = t.wl_rank.shape[0]
    C = t.cq_node.shape[0]
    W_null = W1 - 1
    pot = potential_available_all(t)

    def cond(state):
        _, _, _, _, _, _, progress, rounds = state
        return progress & (rounds < W1 + C + 2)

    @jax.named_scope("round")
    def body(state):
        usage, admitted, parked, cursor, opt, admit_round, _, rounds = state
        parked_before = parked
        cursor_before = cursor
        cand_w = _select_heads(t, admitted, parked)
        avail = available_all(t, usage)
        mode, k_chosen, borrow, next_cursor = nominate(
            t, usage, avail, pot, cand_w, cursor)

        # Park NoFit heads of BestEffortFIFO queues; StrictFIFO heads stay
        # and block their queue (inadmissible-parking parity).
        is_head = cand_w != W_null
        strict_head = t.cq_strict & is_head
        park_now = is_head & (mode == M_NOFIT) & ~strict_head
        parked = parked.at[cand_w].set(parked[cand_w] | park_now)

        was_admitted = admitted
        cq_usage, admitted, parked, any_admitted = _round_scan(
            t, usage, usage, admitted, parked, cand_w, mode, k_chosen,
            borrow)
        usage = refresh_cohort_usage(t, cq_usage)

        newly = admitted[cand_w] & ~was_admitted[cand_w]
        opt = opt.at[cand_w].set(jnp.where(newly, k_chosen, opt[cand_w]))
        admit_round = admit_round.at[cand_w].set(
            jnp.where(newly, rounds, admit_round[cand_w]))
        # Record the flavor cursor for heads that stay pending, so their
        # next nomination resumes at the right flavor.
        is_head = cand_w != W_null
        keep = is_head & ~admitted[cand_w]
        cursor = cursor.at[cand_w].set(
            jnp.where(keep, next_cursor, cursor[cand_w]))

        # Progress = any admission, any head parked (NoFit or Preempt
        # mode — the queue advances next round), or any cursor movement
        # (the head will try different flavors next round).
        progress = (any_admitted
                    | jnp.any(parked & ~parked_before)
                    | jnp.any(cursor != cursor_before))
        return (usage, admitted, parked, cursor, opt, admit_round, progress,
                rounds + 1)

    init = (
        t.usage0,
        jnp.zeros(W1, dtype=bool),
        jnp.zeros(W1, dtype=bool),
        jnp.zeros(W1, dtype=jnp.int32),
        jnp.zeros(W1, dtype=jnp.int32),
        jnp.full(W1, -1, dtype=jnp.int32),
        jnp.ones((), dtype=bool),
        jnp.zeros((), dtype=jnp.int32),
    )
    usage, admitted, parked, _cursor, opt, admit_round, _, rounds = (
        jax.lax.while_loop(cond, body, init))
    admitted = admitted.at[W_null].set(False)
    parked = parked.at[W_null].set(False)
    return admitted, opt, admit_round, parked, rounds, usage


solve_backlog = jax.jit(_solve_backlog_impl)


# ---------------------------------------------------------------------------
# Scenario-batched entry (kueue_oss_tpu/sim what-if engine)
# ---------------------------------------------------------------------------

#: ProblemTensors fields a scenario overlay may vary per scenario. The
#: lean drain is pure int/bool arithmetic, so a vmapped batch is
#: bit-identical to solving each scenario alone (the batched while_loop
#: freezes finished lanes with a select, never perturbing their state).
BATCHABLE_FIELDS = frozenset({
    "nominal", "subtree", "local_quota", "has_borrow", "borrow_limit",
    "usage0", "wl_cqid", "wl_rank", "wl_prio", "wl_ts", "wl_valid",
    "wl_req",
})

#: Every ProblemTensors field. The drain body is shape-static pure
#: gather/scatter arithmetic with no host-side dependence on array
#: CONTENT, so any field may carry the scenario axis — the federation
#: dispatcher batches whole canvas-normalized problems from DIFFERENT
#: clusters this way (sim/dispatch.py). BATCHABLE_FIELDS remains the
#: documented subset single-problem overlay sweeps vary.
ALL_PROBLEM_FIELDS = frozenset(ProblemTensors._fields)


@functools.lru_cache(maxsize=None)
def _batched_solver(fields: frozenset):
    """Jitted vmap of the lean drain over a leading scenario axis.

    Only the overlay's ``fields`` carry the [S, ...] axis; everything
    else (notably the large wl_req tensor when quota-only sweeps leave
    it untouched) broadcasts unbatched, so an S-way batch does not cost
    S copies of the whole problem."""
    axes = ProblemTensors(
        **{f: (0 if f in fields else None)
           for f in ProblemTensors._fields})
    return jax.jit(jax.vmap(_solve_backlog_impl, in_axes=(axes,)))


def solve_backlog_batched(t: ProblemTensors, overrides: dict):
    """Solve S counterfactual variants of one padded problem in ONE
    device dispatch.

    ``overrides`` maps BATCHABLE_FIELDS names to stacked [S, ...] arrays
    (scenario variants of the corresponding base array); unnamed fields
    are shared across the batch. Returns the solve_backlog tuple with a
    leading scenario axis on every output.
    """
    if not overrides:
        raise ValueError("batched solve needs at least one scenario-"
                         "varying field (use solve_backlog otherwise)")
    bad = set(overrides) - ALL_PROBLEM_FIELDS
    if bad:
        raise ValueError(
            f"fields {sorted(bad)} are not ProblemTensors fields; "
            f"batchable: {sorted(ALL_PROBLEM_FIELDS)}")
    fn = _batched_solver(frozenset(overrides))
    return fn(t._replace(**{k: jnp.asarray(v)
                            for k, v in overrides.items()}))
