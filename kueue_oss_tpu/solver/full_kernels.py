"""Preemption-capable jitted drain kernel (unified workload axis).

Extends the fit-only drain (kernels.py) with the reference's preemption
semantics, fully on-device:

- batched classical candidate generation: legality masks from the
  within-CQ / reclaim-within-cohort / borrowWithinCohort policies
  (classical/candidate_generator.go:34-160), hierarchical-advantage rings
  (hierarchical_preemption.go collectCandidatesForHierarchicalReclaim),
  and the candidate ordering (common/ordering.go) as lexsort keys;
- the remove-then-fill-back victim search (preemption.go:271-341) as a
  masked lax.scan per preemptor, vmapped over the round's preempt-mode
  heads;
- the cycle contract of scheduler.go:286-467: entry ordering, one
  overlapping-preemption skip, fits re-check under simulated removal of
  already-preempted workloads, reserve-and-park for Preempt/NoCandidates.

Admitted workloads live on the same axis as pending ones: eviction flips
them back to pending (ordered by a per-round eviction timestamp rank,
workload.Ordering semantics) so preemptors re-attempt the next round
against the freed capacity, exactly like the host Simulator.

Static caps (compile-time constants baked into the program):
- H_MAX preempt-mode heads are searched per round; later ones wait a
  round (the reference searches all, but its cycle admits at most one
  conflicting entry anyway, so extra searches mostly re-run next cycle).
- P_MAX candidates considered per search; a victim set needing more
  candidates fails the search (NoCandidates semantics). The engine sizes
  these from the problem.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from kueue_oss_tpu.solver.kernels import (
    M_FIT,
    M_NOFIT,
    M_PREEMPT,
    _add_usage_along_path,
    _avail_along_path,
    available_all,
    borrow_levels,
    potential_available_all,
    refresh_cohort_usage,
)
from kueue_oss_tpu.solver.tensors import (
    BIG,
    POLICY_ANY,
    POLICY_LOWER_OR_NEWER_EQUAL,
    POLICY_LOWER_PRIORITY,
    POLICY_NEVER,
    NO_THRESHOLD,
    SolverProblem,
    pow2,
)

# candidate variants (classical/candidate_generator.go)
V_NEVER = 0
V_WITHIN_CQ = 1
V_HIERARCHICAL_RECLAIM = 2
V_RECLAIM_WITHOUT_BORROWING = 3
V_RECLAIM_WHILE_BORROWING = 4

# preemption-mode lattice (flavorassigner.go:429-437); mirrors the host
# flavor_assigner P_* constants so granular modes compare identically.
P_NOFIT = 0
P_NO_CANDIDATES = 1
P_PREEMPT = 2
P_RECLAIM = 3
P_FIT = 4

#: cap on borrow levels when packing granular modes into one sort key
#: (levels are cohort-tree heights, far below this)
B_CAP = 64


class FullTensors(NamedTuple):
    """Device-side mirror of the extended SolverProblem."""

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
    cq_within_policy: jnp.ndarray
    cq_reclaim_policy: jnp.ndarray
    cq_bwc_forbidden: jnp.ndarray
    cq_bwc_threshold: jnp.ndarray
    cq_preempt_try_next: jnp.ndarray
    cq_pref_pob: jnp.ndarray
    cq_fair_weight: jnp.ndarray
    cq_root: jnp.ndarray
    cq_opt_group: jnp.ndarray    # [C, K]
    cq_opt_pos: jnp.ndarray      # [C, K] position of option within its group
    cq_ngroups: jnp.ndarray
    wl_cqid: jnp.ndarray
    wl_prio: jnp.ndarray
    wl_ts0: jnp.ndarray
    wl_uid: jnp.ndarray
    wl_req: jnp.ndarray
    wl_valid: jnp.ndarray
    wl_parked0: jnp.ndarray
    wl_admitted0: jnp.ndarray
    wl_evicted0: jnp.ndarray
    wl_admit_rank0: jnp.ndarray
    ad_usage: jnp.ndarray
    fr_resource: jnp.ndarray     # [F] int32 resource id per FR column
    res_onehot: jnp.ndarray      # [F, R] int32 one-hot of fr_resource
    node_fair_weight: jnp.ndarray  # [N+1] float32
    wl_class: jnp.ndarray        # [W+1] int32 scheduling-equivalence class
    class_root: jnp.ndarray      # [n_classes+1] int32
    wl_lq: jnp.ndarray           # [W+1] int32 dense LQ id (AFS)
    wl_ts_buf: jnp.ndarray       # [W+1] int32 newer-eq threshold rank
    wl_afs_penalty: jnp.ndarray  # [W+1] float32 admission penalty inc
    lq_penalty0: jnp.ndarray     # [L+1] float32 decayed start penalties
    cq_afs: jnp.ndarray          # [C] bool UsageBasedAdmissionFairSharing
    ts_evict_base: jnp.ndarray   # scalar int32
    admit_rank_base: jnp.ndarray  # scalar int32


#: FullTensors fields carried on the [W+1] workload axis — the set the
#: pod-scale row sharding block-distributes over the mesh ``wl`` axis
#: (sharded.full_shardings); everything else (cohort tree, CQ policy,
#: flavor metadata) replicates. Scatter/gather ops against these fields
#: cross shards under GSPMD; the victim-search lane shard_map
#: (_run_searches) composes with — it re-gathers the rows it scans.
FULL_WL_FIELDS = ("wl_cqid", "wl_prio", "wl_ts0", "wl_uid", "wl_req",
                  "wl_valid", "wl_parked0", "wl_admitted0",
                  "wl_evicted0", "wl_admit_rank0", "ad_usage",
                  "wl_class", "wl_lq", "wl_ts_buf", "wl_afs_penalty")


def host_tensors_full(p: SolverProblem) -> FullTensors:
    """The full kernel's input tensors as HOST (numpy) arrays — see
    kernels.host_tensors for why this is split from the upload."""
    import numpy as np

    is_cq = np.zeros(p.parent.shape[0], dtype=bool)
    is_cq[p.cq_node] = True
    # position of option k within its group, for per-group flavor cursors
    C, K = p.cq_opt_group.shape if p.cq_opt_group is not None else (0, 1)
    opt_pos = np.zeros((C, K), dtype=np.int32)
    for c in range(C):
        counts: dict[int, int] = {}
        for k in range(K):
            g = int(p.cq_opt_group[c, k])
            if g < 0:
                continue
            opt_pos[c, k] = counts.get(g, 0)
            counts[g] = counts.get(g, 0) + 1
    return FullTensors(
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
        cq_within_policy=p.cq_within_policy,
        cq_reclaim_policy=p.cq_reclaim_policy,
        cq_bwc_forbidden=p.cq_bwc_forbidden,
        cq_bwc_threshold=p.cq_bwc_threshold,
        cq_preempt_try_next=p.cq_preempt_try_next,
        cq_pref_pob=p.cq_pref_pob,
        cq_fair_weight=p.cq_fair_weight,
        cq_root=p.cq_root,
        cq_opt_group=p.cq_opt_group,
        cq_opt_pos=opt_pos,
        cq_ngroups=p.cq_ngroups,
        wl_cqid=p.wl_cqid,
        wl_prio=p.wl_prio,
        wl_ts0=p.wl_ts,
        wl_uid=p.wl_uid,
        wl_req=p.wl_req,
        wl_valid=p.wl_valid,
        wl_parked0=p.wl_parked0,
        wl_admitted0=p.wl_admitted0,
        wl_evicted0=p.wl_evicted0,
        wl_admit_rank0=p.wl_admit_rank,
        ad_usage=p.ad_usage,
        fr_resource=p.fr_resource,
        res_onehot=np.eye(p.n_resources, dtype=np.int32)[p.fr_resource],
        node_fair_weight=p.node_fair_weight,
        wl_class=p.wl_class,
        class_root=p.class_root,
        wl_lq=(p.wl_lq if p.wl_lq is not None
               else np.zeros(p.wl_cqid.shape[0], np.int32)),
        wl_ts_buf=(p.wl_ts_buf if p.wl_ts_buf is not None else p.wl_ts),
        wl_afs_penalty=(
            p.wl_afs_penalty if p.wl_afs_penalty is not None
            else np.zeros(p.wl_cqid.shape[0], np.float32)),
        lq_penalty0=(p.lq_penalty0 if p.lq_penalty0 is not None
                     else np.zeros(1, np.float32)),
        cq_afs=(p.cq_afs if p.cq_afs is not None
                else np.zeros(p.cq_node.shape[0], bool)),
        ts_evict_base=np.asarray(p.ts_evict_base, dtype=np.int32),
        admit_rank_base=np.asarray(p.admit_rank_base, dtype=np.int32),
    )


def to_device_full(p: SolverProblem) -> FullTensors:
    return jax.tree_util.tree_map(jnp.asarray, host_tensors_full(p))


# ---------------------------------------------------------------------------
# path helpers
# ---------------------------------------------------------------------------


def _remove_usage_along_path(t, usage: jnp.ndarray, cq_node: jnp.ndarray,
                             val: jnp.ndarray) -> jnp.ndarray:
    """removeUsage with bubbling (resource_node.go:147-158) along one path:
    the parent's share shrinks by min(val, usage stored in parent)."""
    path = t.path[cq_node]
    null = t.parent.shape[0] - 1
    for d in range(path.shape[0]):
        node = path[d]
        is_valid = node != null
        stored = usage[node] - t.local_quota[node]
        usage = usage.at[node].add(jnp.where(is_valid, -val, 0))
        val = jnp.where(stored > 0, jnp.minimum(val, stored), 0)
    return usage


def _height_along_path(t, usage, cq_node, req):
    """FindHeightOfLowestSubtreeThatFits for one CQ under ``usage``.

    Elementwise over the FR axis; returns (level [F] int32,
    may_reclaim [F] bool). Reference parity:
    classical/hierarchical_preemption.go:221-243 — same walk as
    borrow_levels (kernels.py) but along a single CQ path so it can run
    on mid-search usage (simulate_preemption's borrow-after-removal).
    """
    path = t.path[cq_node]
    null = t.parent.shape[0] - 1
    found = req == 0
    level = jnp.zeros_like(req)
    may_reclaim = jnp.zeros(req.shape, dtype=bool)
    rem = req
    root = cq_node
    for d in range(path.shape[0]):
        node = path[d]
        valid = node != null
        root = jnp.where(valid, node, root)
        not_borrowing = usage[node] + rem <= t.subtree[node]
        newly = (~found) & not_borrowing & valid
        level = jnp.where(newly, t.height[node], level)
        may_reclaim = jnp.where(newly, t.has_parent[node], may_reclaim)
        found = found | newly
        la = jnp.maximum(0, t.local_quota[node] - usage[node])
        rem = jnp.where(found | ~valid, rem, rem - la)
    level = jnp.where(found, level, t.height[root])
    return level, may_reclaim


# ---------------------------------------------------------------------------
# head selection: per-CQ min by (-priority, ts, uid) over the pending set
# ---------------------------------------------------------------------------


@jax.named_scope("select_heads_full")
def select_heads_full(t: FullTensors, admitted, parked, ts,
                      lq_penalty=None):
    C = t.cq_node.shape[0]
    W1 = t.wl_cqid.shape[0]
    W_null = W1 - 1
    pending = ~admitted & ~parked
    seg = t.wl_cqid[:-1]
    if lq_penalty is not None:
        # Admission fair sharing (KEP-4136): within a
        # UsageBasedAdmissionFairSharing CQ the head is the entry whose
        # LocalQueue carries the lowest decayed usage; the normal
        # (priority, ts, uid) order is the tie-break
        # (queue_manager.pop_head afs_key).
        is_afs = t.cq_afs[jnp.minimum(seg, C - 1)]
        pen = lq_penalty[t.wl_lq[:-1]]
        pen_eff = jnp.where(pending[:-1] & is_afs, pen, jnp.inf)
        min_pen = jax.ops.segment_min(pen_eff, seg,
                                      num_segments=C + 1)[:C]
        pending_head = pending[:-1] & (
            ~is_afs | (pen == min_pen[seg]))
    else:
        pending_head = pending[:-1]
    prio_eff = jnp.where(pending_head, t.wl_prio[:-1], -BIG)
    max_prio = jax.ops.segment_max(prio_eff, seg, num_segments=C + 1)[:C]
    c1 = pending_head & (t.wl_prio[:-1] == max_prio[seg])
    ts_eff = jnp.where(c1, ts[:-1], BIG)
    min_ts = jax.ops.segment_min(ts_eff, seg, num_segments=C + 1)[:C]
    c2 = c1 & (ts[:-1] == min_ts[seg])
    uid_eff = jnp.where(c2, t.wl_uid[:-1], BIG)
    min_uid = jax.ops.segment_min(uid_eff, seg, num_segments=C + 1)[:C]
    c3 = c2 & (t.wl_uid[:-1] == min_uid[seg])
    w_idx = jnp.arange(W1 - 1, dtype=jnp.int32)
    head_w = jax.ops.segment_min(
        jnp.where(c3, w_idx, W_null), seg, num_segments=C + 1)[:C]
    has_head = max_prio > -BIG
    return jnp.where(has_head, head_w, W_null).astype(jnp.int32)


# ---------------------------------------------------------------------------
# per-group nomination
# ---------------------------------------------------------------------------


@jax.named_scope("nominate_full")
def nominate_full(t: FullTensors, usage, avail, pot, cand_w, cursor,
                  g_max: int, fs_enabled: bool = False):
    """Classify each CQ's head across (group, flavor) options.

    Per resource group the walk mirrors findFlavorForPodSets: start at the
    group's flavor cursor, prefer Fit per the whenCanBorrow policy, fall
    back to Preempt. The entry's mode is the worst group mode; its usage
    is the sum of the chosen options' requests. Returns (mode [C],
    k_chosen [C, G], req_total [C, F], borrow [C], next_cursor [C, G]).
    """
    C, K = t.cq_opt_group.shape
    req = t.wl_req[cand_w]                       # [C,K,F]
    grp = t.cq_opt_group                         # [C,K]
    pos = t.cq_opt_pos                           # [C,K]
    cursor_k = jnp.take_along_axis(
        cursor[cand_w], jnp.maximum(grp, 0), axis=1)  # [C,K]
    valid = (t.wl_valid[cand_w] & (grp >= 0)
             & (pos >= cursor_k))                # [C,K]

    avail_cq = avail[t.cq_node][:, None, :]
    pot_cq = pot[t.cq_node][:, None, :]
    nominal_cq = t.nominal[t.cq_node][:, None, :]
    level, may_reclaim = borrow_levels(t, usage, cand_w)

    nonzero = req > 0
    fit_fr = (~nonzero) | (req <= avail_cq)
    within_cap = (~nonzero) | (req <= pot_cq)
    # flavorassigner.go:1071-1108: preemption is considered when the value
    # is within nominal, a higher subtree could reclaim, or the CQ may
    # preempt while borrowing (borrowWithinCohort enabled; under fair
    # sharing also any reclaimWithinCohort policy —
    # flavor_assigner._can_preempt_while_borrowing)
    can_pwb = (~t.cq_bwc_forbidden
               | (fs_enabled
                  & (t.cq_reclaim_policy != POLICY_NEVER)))[:, None, None]
    preemptish_fr = (~nonzero) | (
        within_cap & ((req <= nominal_cq) | may_reclaim | can_pwb))
    opt_fit = valid & jnp.all(fit_fr, axis=-1)
    opt_preempt = valid & jnp.all(fit_fr | preemptish_fr, axis=-1)
    opt_level = jnp.max(jnp.where(nonzero, level, 0), axis=-1)  # [C,K]

    k_idx = jnp.arange(K, dtype=jnp.int32)[None, :]
    group_active = jnp.zeros((C, g_max), dtype=bool)
    mode = jnp.full((C,), M_FIT, dtype=jnp.int32)
    k_chosen = jnp.zeros((C, g_max), dtype=jnp.int32)
    next_cursor = jnp.zeros((C, g_max), dtype=jnp.int32)
    req_total = jnp.zeros((C, req.shape[2]), dtype=req.dtype)
    borrow = jnp.zeros((C,), dtype=jnp.int32)

    for g in range(g_max):
        in_g = grp == g                          # [C,K]
        has_g = jnp.any(in_g, axis=1)
        active = jnp.any(in_g & jnp.any(nonzero, axis=-1), axis=1)
        group_active = group_active.at[:, g].set(active)
        fit_g = opt_fit & in_g
        pre_g = opt_preempt & in_g & ~opt_fit

        def first_true(mask):
            return jnp.min(jnp.where(mask, k_idx, K), axis=1)

        k_default = first_true(fit_g)
        k_nonborrow = first_true(fit_g & (opt_level == 0))
        lvl_key = jnp.where(fit_g, opt_level * K + k_idx, BIG)
        k_bestlvl = jnp.argmin(lvl_key, axis=1).astype(jnp.int32)
        k_try_next = jnp.where(
            k_nonborrow < K, k_nonborrow,
            jnp.where(jnp.any(fit_g, axis=1), k_bestlvl, K))
        k_fit = jnp.where(t.cq_try_next, k_try_next, k_default)
        any_fit = k_fit < K
        k_preempt = first_true(pre_g)
        any_preempt = k_preempt < K
        k_g = jnp.where(any_fit, k_fit,
                        jnp.where(any_preempt, k_preempt,
                                  first_true(in_g))).astype(jnp.int32)
        k_g = jnp.minimum(k_g, K - 1)
        mode_g = jnp.where(any_fit, M_FIT,
                           jnp.where(any_preempt, M_PREEMPT, M_NOFIT))
        # Inactive groups (no requested resources) are vacuous fits.
        mode_g = jnp.where(active & has_g, mode_g, M_FIT)
        mode = jnp.minimum(mode, mode_g)
        k_chosen = k_chosen.at[:, g].set(jnp.where(active, k_g, 0))
        take = jnp.take_along_axis
        req_g = take(req, k_g[:, None, None], axis=1)[:, 0, :]
        req_total = req_total + jnp.where(active[:, None], req_g, 0)
        borrow_g = take(opt_level, k_g[:, None], axis=1)[:, 0]
        borrow = jnp.maximum(borrow, jnp.where(active, borrow_g, 0))
        # flavor cursor per group (flavorassigner.go:843 LastTriedFlavorIdx)
        early_break = jnp.where(t.cq_try_next, k_nonborrow < K, any_fit)
        pos_g = take(pos, k_g[:, None], axis=1)[:, 0]
        n_in_g = jnp.sum(in_g, axis=1)
        nc = jnp.where(early_break & (pos_g < n_in_g - 1), pos_g + 1, 0)
        next_cursor = next_cursor.at[:, g].set(
            jnp.where(active, nc, 0).astype(jnp.int32))

    return (mode, k_chosen, req_total, borrow, next_cursor,
            opt_fit, opt_preempt, opt_level, group_active, valid)


@jax.named_scope("walk_assign")
def walk_assign(t: FullTensors, head_w, pmode_k, borrow_k, valid_k,
                group_active_row, g_max: int):
    """The assigner's flavor walk over granular modes, for ONE head (vmap).

    Emulates _find_flavor_for_podsets (flavorassigner.go:812-951): per
    resource group, walk options in order; the first option where
    should_try_next_flavor is false wins (early break); otherwise the best
    option by is_preferred — (pmode desc, borrow asc, index asc) under
    BorrowingOverPreemption, (borrow asc, pmode desc, index asc) under
    PreemptionOverBorrowing (flavorassigner.go:439-470). ``pmode_k`` /
    ``borrow_k`` carry the per-option granular modes, with preempt-mode
    options already classified by an actual victim-search simulation
    (P_NO_CANDIDATES / P_PREEMPT / P_RECLAIM with borrow-after levels —
    preemption_oracle.go SimulatePreemption).

    Returns (mode, k_out [G], req [F], borrow, next_cursor [G],
    pmode_sel [G]).
    """
    C = t.cq_node.shape[0]
    K = t.cq_opt_group.shape[1]
    cqi = jnp.minimum(t.wl_cqid[head_w], C - 1)
    grp = t.cq_opt_group[cqi]                    # [K]
    pos = t.cq_opt_pos[cqi]                      # [K]
    req_k = t.wl_req[head_w]                     # [K, F]
    pmode_k = jnp.where(valid_k, pmode_k, P_NOFIT)
    is_pre_pm = (pmode_k == P_PREEMPT) | (pmode_k == P_RECLAIM)
    stn = ((pmode_k == P_NOFIT) | (pmode_k == P_NO_CANDIDATES)
           | (is_pre_pm & t.cq_preempt_try_next[cqi])
           | ((borrow_k != 0) & t.cq_try_next[cqi]))
    brk = valid_k & ~stn
    k_idx = jnp.arange(K, dtype=jnp.int32)
    bor = jnp.minimum(borrow_k, B_CAP - 1)
    key_bop = ((P_FIT - pmode_k) * B_CAP + bor) * K + k_idx
    key_pob = (bor * (P_FIT + 1) + (P_FIT - pmode_k)) * K + k_idx
    key = jnp.where(t.cq_pref_pob[cqi], key_pob, key_bop)
    eligible = valid_k & (pmode_k > P_NOFIT)

    k_out = jnp.zeros((g_max,), dtype=jnp.int32)
    next_cursor = jnp.zeros((g_max,), dtype=jnp.int32)
    req = jnp.zeros((req_k.shape[1],), dtype=req_k.dtype)
    borrow = jnp.zeros((), dtype=jnp.int32)
    mode = jnp.full((), M_FIT, dtype=jnp.int32)
    pmode_sel = jnp.full((g_max,), P_FIT, dtype=jnp.int32)
    for g in range(g_max):
        in_g = grp == g
        has_g = jnp.any(in_g)
        active = group_active_row[g]
        k_brk = jnp.min(jnp.where(brk & in_g, k_idx, K))
        elig_g = eligible & in_g
        any_elig = jnp.any(elig_g)
        k_best = jnp.argmin(jnp.where(elig_g, key, BIG)).astype(jnp.int32)
        k_first = jnp.min(jnp.where(in_g, k_idx, K))
        k_g = jnp.where(k_brk < K, k_brk,
                        jnp.where(any_elig, k_best,
                                  jnp.minimum(k_first, K - 1)))
        k_g = k_g.astype(jnp.int32)
        pm_g = jnp.where((k_brk < K) | any_elig, pmode_k[k_g], P_NOFIT)
        m_g = jnp.where(pm_g == P_FIT, M_FIT,
                        jnp.where(pm_g == P_NOFIT, M_NOFIT, M_PREEMPT))
        # Inactive groups (no requested resources) are vacuous fits.
        m_g = jnp.where(active & has_g, m_g, M_FIT)
        mode = jnp.minimum(mode, m_g)
        k_out = k_out.at[g].set(jnp.where(active, k_g, 0))
        pmode_sel = pmode_sel.at[g].set(
            jnp.where(active & has_g, pm_g, P_FIT))
        req = req + jnp.where(active, req_k[k_g], 0)
        borrow = jnp.maximum(borrow, jnp.where(active, borrow_k[k_g], 0))
        # flavor cursor (flavorassigner.go:843,939-947): next attempt
        # resumes after the break position; walking off the end resets.
        pos_brk = pos[jnp.minimum(k_brk, K - 1)]
        n_in_g = jnp.sum(in_g)
        nc = jnp.where((k_brk < K) & (pos_brk < n_in_g - 1), pos_brk + 1, 0)
        next_cursor = next_cursor.at[g].set(
            jnp.where(active, nc, 0).astype(jnp.int32))
    return mode, k_out, req, borrow, next_cursor, pmode_sel


# ---------------------------------------------------------------------------
# classical preemption search (one preemptor; vmapped over lanes)
# ---------------------------------------------------------------------------


def _within_nominal_frs(t, usage, node, frs_mask):
    """is_within_nominal over the masked FRs at one node."""
    return jnp.all(~frs_mask | (usage[node] <= t.subtree[node]))


def _workload_fits(t, usage, cq_node, req, allow_borrow):
    """_workload_fits (preemption.py:555): every requested fr must fit
    available(), and without allow_borrow must not push the CQ above its
    subtree quota."""
    avail = _avail_along_path(t, usage, cq_node)
    nz = req > 0
    fits_avail = jnp.all(~nz | (req <= avail))
    no_borrow_ok = jnp.all(
        ~nz | (usage[cq_node] + req <= t.subtree[cq_node]))
    return fits_avail & (allow_borrow | no_borrow_ok)


@jax.named_scope("build_candidate_table")
def build_candidate_table(t: FullTensors, admitted, admit_rank, wl_usage,
                          a_max: int):
    """Per-cohort-root admitted-candidate table, [N+1, A] int32.

    Victim candidates are always admitted workloads with nonzero usage in
    the preemptor's cohort tree (candidate_generator.go:34-160), and the
    candidate orderings' lane-independent suffix is shared: (priority
    asc, admit_rank desc = most recently admitted first, uid asc)
    (common/ordering.go). Building one table per round — rows keyed by
    root node, candidates in shared order — lets every victim search run
    on a small capacity-bounded axis instead of re-sorting the whole
    workload axis per lane. Rows pad with W_null.
    """
    W1 = t.wl_cqid.shape[0]
    W_null = W1 - 1
    N1 = t.parent.shape[0]
    C = t.cq_node.shape[0]
    root_of = t.cq_root[jnp.minimum(t.wl_cqid[:-1], C - 1)]   # [W]
    elig = admitted[:-1] & jnp.any(wl_usage[:-1] > 0, axis=1)
    order = jnp.lexsort((t.wl_uid[:-1], -admit_rank[:-1], t.wl_prio[:-1]))
    rank = jnp.zeros((W1 - 1,), dtype=jnp.int32).at[order].set(
        jnp.arange(W1 - 1, dtype=jnp.int32))
    root_eff = jnp.where(elig, root_of, N1)
    sorted_w = jnp.lexsort((rank, root_eff)).astype(jnp.int32)
    elig_s = elig[sorted_w]
    root_s = root_of[sorted_w]
    counts = jax.ops.segment_sum(
        elig.astype(jnp.int32), root_of, num_segments=N1)
    offsets = jnp.cumsum(counts) - counts
    pos = jnp.arange(W1 - 1, dtype=jnp.int32) - offsets[root_s]
    row = jnp.where(elig_s, root_s, N1)               # OOB row -> dropped
    col = jnp.where(elig_s, jnp.minimum(pos, a_max), a_max)
    table = jnp.full((N1, a_max), W_null, dtype=jnp.int32)
    return table.at[row, col].set(sorted_w, mode="drop")


def _frs_needing_preemption(req, avail_cq):
    """[F] bool: the FRs a preemptor requests beyond what its queue has
    left. None: nothing to search for."""
    return (req > 0) & (req > avail_cq)


class _SearchStage1(NamedTuple):
    """What the liveness stage of one lane's victim search hands to the
    walk stage (:func:`_search_stage1` -> :func:`_search_stage2`)."""

    frs_mask: jnp.ndarray      # [F] bool FRs needing preemption
    lca_d: jnp.ndarray         # [P] int32 LCA index on the preemptor's path
    hier_adv: jnp.ndarray      # [P] bool hierarchical advantage at the LCA
    other_legal: jnp.ndarray   # [P] bool legal candidate of another CQ
    legal_all: jnp.ndarray     # [P] bool legal candidate


def _search_lane(t: FullTensors, head_w):
    """(cqid, cqi, cq_node, my_path [D]) of one lane's preemptor."""
    cqid = t.wl_cqid[head_w]
    cqi = jnp.minimum(cqid, t.cq_node.shape[0] - 1)
    cq_node = t.cq_node[cqi]
    return cqid, cqi, cq_node, t.path[cq_node]


def _search_stage1(t: FullTensors, usage0_round, wl_usage, admitted, ts,
                   head_w, req, avail_cq, cands) -> _SearchStage1:
    """Stage 1 of :func:`classical_search`, the cheap half: candidate
    legality, the LCA ring, the hierarchical advantage and the
    within-nominal pruning, all elementwise over the candidate axis. A
    lane whose ``legal_all`` has a true entry is LIVE; any other lane's
    result is already decided (:func:`_dead_search_result`). A lane
    without a head is never live: round_body gives it no request, so no
    FR needs preemption and no candidate uses one."""
    W_null = t.wl_cqid.shape[0] - 1
    C_n = t.cq_node.shape[0]
    null_node = t.parent.shape[0] - 1
    D = t.path.shape[1]
    cqid, cqi, cq_node, my_path = _search_lane(t, head_w)

    frs_mask = _frs_needing_preemption(req, avail_cq)      # [F]

    # ---- candidate legality (candidate_generator.go:34-160) -------------
    present = cands != W_null
    cand_cqid = t.wl_cqid[cands]                 # [P]
    cand_node = t.cq_node[jnp.minimum(cand_cqid, C_n - 1)]
    is_adm = present & admitted[cands] & (cands != head_w)
    uses = jnp.any(wl_usage[cands] * frs_mask[None, :] > 0, axis=1)
    same_cq = cand_cqid == cqid

    prio_p = t.wl_prio[head_w]
    ts_p = ts[head_w]
    prio_c = t.wl_prio[cands]
    lower = prio_p > prio_c
    # newer-equal: candidate rank beyond the preemptor's threshold
    # (wl_ts_buf == own rank normally; the last within-buffer rank under
    # SchedulerTimestampPreemptionBuffer). An in-drain-evicted preemptor
    # (ts re-stamped past ts_evict_base) was evicted "now", so nothing
    # pending can be newer by more than the buffer.
    buf_p = jnp.where(ts_p >= t.ts_evict_base, BIG, t.wl_ts_buf[head_w])
    newer_eq = (prio_p == prio_c) & (ts[cands] > buf_p)
    policy = jnp.where(same_cq, t.cq_within_policy[cqi],
                       t.cq_reclaim_policy[cqi])
    sat = jnp.where(
        policy == POLICY_NEVER, False,
        jnp.where(policy == POLICY_LOWER_PRIORITY, lower,
                  jnp.where(policy == POLICY_LOWER_OR_NEWER_EQUAL,
                            lower | newer_eq, policy == POLICY_ANY)))
    legal = is_adm & uses & sat

    # ---- LCA ring + hierarchical advantage ------------------------------
    # lca_d[a] = first index on MY path that is an ancestor of cand's CQ
    cand_path = t.path[cand_node]                # [P, D]
    anc = (cand_path[:, :, None] == my_path[None, None, :])  # [P, Dc, Dp]
    is_anc = jnp.any(anc, axis=1)                # [P, Dp]
    is_anc = is_anc & (my_path[None, :] != null_node)
    d_idx = jnp.arange(D, dtype=jnp.int32)[None, :]
    lca_d = jnp.min(jnp.where(is_anc, d_idx, D), axis=1)  # [P]
    other_ok = (lca_d >= 1) & (lca_d < D)        # shares a cohort tree

    # advantage chain along my path (hierarchical_preemption.go);
    # QuantitiesFitInQuota iterates the REQUESTED frs only — an unrelated
    # over-subtree column must not kill the advantage
    nz_req = req > 0
    adv_at = jnp.zeros((D,), dtype=bool)
    adv = jnp.all(~nz_req
                  | (usage0_round[cq_node] + req <= t.subtree[cq_node]))
    rem = jnp.maximum(
        0, req - jnp.maximum(0, t.local_quota[cq_node]
                             - usage0_round[cq_node]))
    for d in range(1, D):
        node = my_path[d]
        ok = node != null_node
        adv_at = adv_at.at[d].set(adv)
        fits_d = jnp.all(
            ~nz_req | (usage0_round[node] + rem <= t.subtree[node])) & ok
        rem = jnp.maximum(
            0, rem - jnp.maximum(0, t.local_quota[node]
                                 - usage0_round[node]))
        adv = adv | fits_d
    hier_adv = adv_at[jnp.minimum(lca_d, D - 1)]  # [W]

    # collection-time within-nominal pruning (round-start usage): the
    # candidate's CQ and every cohort strictly below the LCA must be
    # over nominal for some needed fr (_collect_in_subtree)
    cand_over = ~jnp.all(
        ~frs_mask[None, :]
        | (usage0_round[cand_node] <= t.subtree[cand_node]), axis=1)
    # cohorts on cand's path strictly below the LCA: path entries before
    # the one equal to my_path[lca_d]
    lca_node = my_path[jnp.minimum(lca_d, D - 1)]            # [P]
    seen_lca = jnp.cumsum(
        (cand_path == lca_node[:, None]).astype(jnp.int32), axis=1) > 0
    strictly_below = (~seen_lca) & (cand_path != null_node)
    # skip position 0 (the CQ itself, checked via cand_over)
    strictly_below = strictly_below.at[:, 0].set(False)
    path_over = jnp.all(
        ~strictly_below
        | ~jnp.all(~frs_mask[None, None, :]
                   | (usage0_round[cand_path]
                      <= t.subtree[cand_path]), axis=2),
        axis=1)                                   # [P]
    other_legal = legal & ~same_cq & other_ok & cand_over & path_over
    same_legal = legal & same_cq
    legal_all = other_legal | same_legal
    return _SearchStage1(frs_mask, lca_d, hier_adv, other_legal, legal_all)


def _search_stage2(t: FullTensors, usage0_round, wl_usage, evicted_f,
                   head_w, req, cands, s1: _SearchStage1, p_max: int):
    """Stage 2 of :func:`classical_search`, the heavy half: variants,
    the candidate ordering (an argsort over ``p_max``), the two
    remove-until-fits attempts (each opens with a ``p_max``-row
    scatter-add and a cohort refresh), fill-back and ``borrow_after``.
    Only a live lane needs it."""
    W_null = t.wl_cqid.shape[0] - 1
    C_n = t.cq_node.shape[0]
    null_node = t.parent.shape[0] - 1
    D = t.path.shape[1]
    cqid, cqi, cq_node, my_path = _search_lane(t, head_w)
    frs_mask, lca_d, hier_adv, other_legal, legal_all = s1
    same_cq = t.wl_cqid[cands] == cqid
    prio_p = t.wl_prio[head_w]
    prio_c = t.wl_prio[cands]

    # ---- variants & groups ----------------------------------------------
    thr = t.cq_bwc_threshold[cqi]
    above_thr = (prio_c >= prio_p) | (
        (thr != NO_THRESHOLD) & (prio_c > thr))
    variant = jnp.where(
        same_cq, V_WITHIN_CQ,
        jnp.where(hier_adv, V_HIERARCHICAL_RECLAIM,
                  jnp.where(t.cq_bwc_forbidden[cqi] | above_thr,
                            V_RECLAIM_WITHOUT_BORROWING,
                            V_RECLAIM_WHILE_BORROWING)))
    group_rank = jnp.where(same_cq, 2, jnp.where(hier_adv, 0, 1))

    # ---- ordering (common/ordering.go CandidatesOrdering) ---------------
    # ``cands`` already carries the shared (priority, -admit_rank, uid)
    # suffix order, so the full ordering reduces to a stable 7-bucket
    # sort: legal first, evicted first, then candidate group.
    not_evicted = ~evicted_f[cands]
    bucket = jnp.where(
        legal_all,
        jnp.where(not_evicted, 3 + group_rank, group_rank), 6)
    p_idx = jnp.arange(p_max, dtype=jnp.int32)
    perm = jnp.argsort(bucket * p_max + p_idx).astype(jnp.int32)
    cand_ok = bucket[perm] < 6
    cand_w = jnp.where(cand_ok, cands[perm], W_null)
    cand_valid = cand_ok
    cand_variant = jnp.where(cand_valid, variant[perm], V_NEVER)
    cand_lca = jnp.where(cand_valid, lca_d[perm], 0)

    # per-candidate walk state on the permuted axis
    v_cqid = t.wl_cqid[cand_w]
    v_node = t.cq_node[jnp.minimum(v_cqid, C_n - 1)]
    v_path = t.path[v_node]                       # [P, D]
    v_usage = wl_usage[cand_w]                    # [P, F]
    v_same = cand_valid & (v_cqid == cqid)
    v_lnode = my_path[jnp.minimum(cand_lca, D - 1)]
    v_seen = jnp.cumsum((v_path == v_lnode[:, None]).astype(jnp.int32),
                        axis=1) > 0
    v_below = (~v_seen) & (v_path != null_node)
    v_below = v_below.at[:, 0].set(False)

    # ---- attempt schedule (preemption.py:508-515) -----------------------
    no_other = ~jnp.any(other_legal)
    no_hier = ~jnp.any(other_legal & hier_adv)
    under_nominal = jnp.all(
        ~frs_mask | (usage0_round[cq_node] < t.nominal[cq_node]))
    bwc_forbidden = t.cq_bwc_forbidden[cqi]
    single = no_other | (bwc_forbidden & ~under_nominal)
    f_then_t = ~single & bwc_forbidden & no_hier
    first_borrow = jnp.where(single, True, jnp.where(f_then_t, False, True))
    second_borrow = jnp.where(f_then_t, True, False)
    has_second = ~single

    # ---- the remove-until-fits walk (one attempt) -----------------------

    def attempt(allow_borrow, run):
        # Infeasibility precheck: remove EVERY candidate this attempt
        # could ever pop (a superset of what the sequential walk removes).
        # available() is monotone non-increasing in usage, so if the
        # preemptor does not fit even then, no subset of removals can
        # succeed — skip the walk entirely. This is what makes contended
        # large-scale rounds cheap: most searches fail, and they fail
        # here in O(tree) instead of any walk steps.
        vb_all = ~(allow_borrow
                   & (cand_variant == V_RECLAIM_WITHOUT_BORROWING))
        removable = cand_valid & vb_all
        rows0 = jnp.where(t.is_cq[:, None], usage0_round, 0)
        rows_min = rows0.at[v_node].add(
            -jnp.where(removable[:, None], v_usage, 0), mode="drop")
        usage_min = refresh_cohort_usage(t, rows_min)
        could_fit = _workload_fits(t, usage_min, cq_node, req, allow_borrow)
        run = run & could_fit

        def cond(carry):
            usage_l, victims, fitted, cursor = carry
            return run & ~fitted & (cursor < p_max)

        def body(carry):
            usage_l, victims, fitted, cursor = carry
            # bulk pop-time validity (_valid, candidate_generator.go)
            # under the current usage: the over-quota predicates only
            # flip true->false as removals shrink usage, and nothing is
            # removed between the cursor and the next valid slot, so
            # invalid-now candidates are invalid at their sequential pop
            # time too — skip them all in one step and remove exactly
            # one true victim.
            cq_over = jnp.any(
                frs_mask[None, :]
                & (usage_l[v_node] > t.subtree[v_node]), axis=1)
            wn = jnp.all(
                ~frs_mask[None, None, :]
                | (usage_l[v_path] <= t.subtree[v_path]), axis=2)
            path_ok = jnp.all(~v_below | ~wn, axis=1)
            valid_now = removable & (v_same | (cq_over & path_ok))
            j = jnp.min(jnp.where(valid_now & (p_idx >= cursor),
                                  p_idx, p_max))
            has = j < p_max
            jc = jnp.minimum(j, p_max - 1)
            u_row = jnp.where(has, v_usage[jc], 0)
            usage_l = _remove_usage_along_path(t, usage_l, v_node[jc],
                                               u_row)
            victims = victims.at[jc].set(victims[jc] | has)
            fitted = has & _workload_fits(
                t, usage_l, cq_node, req, allow_borrow)
            return (usage_l, victims, fitted, j + 1)

        # fresh init constants derive their type from head_w so the
        # carries stay consistent under shard_map's varying-axes check
        # (a no-op on the unsharded path)
        vzero = head_w.astype(jnp.int32) * 0
        vfalse = vzero != 0
        init = (usage0_round, jnp.zeros((p_max,), dtype=bool) | vfalse,
                vfalse, vzero)
        usage_l, victims, fitted, _cur = jax.lax.while_loop(
            cond, body, init)

        # fillBackWorkloads: re-add earlier victims (excluding the last
        # removed) newest-first while the preemptor still fits. Victims
        # were removed in slot order, so slot rank = removal sequence.
        vseq = jnp.cumsum(victims.astype(jnp.int32)) - 1   # [P]
        nv = jnp.max(jnp.where(victims, vseq + 1, 0))

        def fb_cond(carry):
            usage_l, vcur, s = carry
            return fitted & (s >= 0)

        def fb_body(carry):
            usage_l, vcur, s = carry
            match = victims & (vseq == s)
            slot = jnp.argmax(match).astype(jnp.int32)
            tryit = jnp.any(match)
            u_row = jnp.where(tryit, v_usage[slot], 0)
            usage_l = _add_usage_along_path(t, usage_l, v_node[slot],
                                            u_row)
            still = _workload_fits(t, usage_l, cq_node, req, allow_borrow)
            # fit held -> the candidate stays re-added (not a victim);
            # fit broke -> undo the re-add, it remains a victim
            usage_l = _remove_usage_along_path(
                t, usage_l, v_node[slot],
                jnp.where(tryit & ~still, u_row, 0))
            vcur = vcur.at[slot].set(vcur[slot] & ~(tryit & still))
            return (usage_l, vcur, s - 1)

        usage_l, victims, _ = jax.lax.while_loop(
            fb_cond, fb_body, (usage_l, victims, nv - 2))
        return fitted, victims, usage_l

    ok1, v1, u1 = attempt(first_borrow, jnp.ones((), dtype=bool))
    ok2, v2, u2 = attempt(second_borrow, has_second & ~ok1)
    success = ok1 | ok2
    victims = jnp.where(ok1, v1, jnp.where(ok2, v2, False))
    usage_after = jnp.where(ok1, u1, jnp.where(ok2, u2, usage0_round))
    level_f, _ = _height_along_path(t, usage_after, cq_node, req)
    borrow_after = jnp.max(jnp.where(frs_mask, level_f, 0))
    reason = jnp.where(victims, cand_variant, V_NEVER).astype(jnp.int8)
    victim_same = victims & (t.wl_cqid[cand_w] == cqid)
    any_same_cq = jnp.any(victim_same & cand_valid)
    return success, cand_w, victims, reason, any_same_cq, borrow_after


def _dead_search_result(t: FullTensors, usage0_round, head_w, req,
                        avail_cq, p_max: int):
    """What :func:`classical_search` returns for a lane that is not
    live (no legal candidate), without searching. Exact, not an
    approximation: with ``legal_all`` all false every bucket is 6, so
    ``cand_valid`` is all false and ``cand_w`` all W_null;
    ``removable`` is then empty in both attempts, the walk finds no
    slot to pop and ``fitted`` stays false, so ``success`` is false and
    no slot is a victim; and ``usage_after`` takes its ``usage0_round``
    branch, which leaves ``borrow_after`` as the round-start height
    over the FRs needing preemption."""
    W_null = t.wl_cqid.shape[0] - 1
    _cqid, _cqi, cq_node, _path = _search_lane(t, head_w)
    frs_mask = _frs_needing_preemption(req, avail_cq)
    level_f, _ = _height_along_path(t, usage0_round, cq_node, req)
    false = jnp.zeros((), dtype=bool)
    return (false, jnp.full((p_max,), W_null, dtype=jnp.int32),
            jnp.zeros((p_max,), dtype=bool),
            jnp.full((p_max,), V_NEVER, dtype=jnp.int8), false,
            jnp.max(jnp.where(frs_mask, level_f, 0)))


@jax.named_scope("classical_search")
def classical_search(t: FullTensors, usage0_round, wl_usage, admitted,
                     evicted_f, ts, head_w, req, avail_cq,
                     cands, p_max: int):
    """Victim search for ONE preemptor (vmap over lanes).

    ``cands`` is the preemptor root's row of build_candidate_table:
    round-start admitted workloads in the shared candidate order, W_null
    padded — the only workloads that can ever be victims, on an axis
    bounded by cohort capacity instead of cohort population.

    Returns (success, victim_w [P] int32 (W_null padded), victim_valid [P]
    bool, victim_reason [P] int8, any_same_cq bool, borrow_after int32).
    Mirrors Preemptor._classical_preemptions: candidate generation +
    ordering, two allow-borrowing attempts of the remove-until-fits walk,
    then fillBackWorkloads. The walk is a bulk-skip loop: pop-time
    validity (over-quota predicates, candidate_generator.go _valid) is
    monotone non-increasing under removals, so all currently-invalid
    candidates are skipped in one parallel step and each iteration
    removes exactly one true victim — the loop trips #victims times, not
    p_max times. ``borrow_after`` is the FindHeightOfLowestSubtreeThatFits
    level computed on the usage with the chosen victims removed
    (round-start usage when the search fails), maxed over the FRs needing
    preemption — simulate_preemption's borrow-after that ranks preempt
    flavors in the assigner's granular mode; ``any_same_cq`` distinguishes
    Preempt from Reclaim possibilities (preemption_oracle.go).

    The search is two stages run one after the other:
    :func:`_search_stage1` (legality, cheap) and :func:`_search_stage2`
    (ordering and the walks, heavy). A lane is LIVE when stage 1 finds
    a legal candidate; for any other lane (one without a head among
    them) stage 2 can only return :func:`_dead_search_result` (see there
    for why that is exact), which is what lets the drain run stage 2 on
    the live lanes alone (:func:`_run_searches`). This function runs
    both stages whatever the lane holds, so a caller that vmaps it gets
    every lane's result the long way.
    """
    s1 = _search_stage1(t, usage0_round, wl_usage, admitted, ts,
                        head_w, req, avail_cq, cands)
    return _search_stage2(t, usage0_round, wl_usage, evicted_f,
                          head_w, req, cands, s1, p_max)


# ---------------------------------------------------------------------------
# round scan: entry processing with preemption issue (scheduler.go:337-467)
# ---------------------------------------------------------------------------


def _quota_to_reserve(t, usage, cq_node, req, borrow):
    """scheduler.go quotaResourcesToReserve for Preempt/NoCandidates."""
    usage_cq = usage[cq_node]
    nominal_cq = t.nominal[cq_node]
    bl = t.borrow_limit[cq_node]
    reserve_borrowing = jnp.where(
        t.has_borrow[cq_node],
        jnp.minimum(req, nominal_cq + bl - usage_cq), req)
    reserve_nominal = jnp.minimum(req, nominal_cq - usage_cq)
    return jnp.maximum(
        0, jnp.where(borrow > 0, reserve_borrowing, reserve_nominal))


@jax.named_scope("full_round_scan")
def full_round_scan(t: FullTensors, state, cand_w, mode, k_chosen, req_c,
                    borrow, lane_of_entry, lane_success, lane_cand_w,
                    lane_victims, lane_reason, p_max: int,
                    fs_enabled: bool = False, lendable_r=None):
    """Process the round's entries in order; returns updated state parts.

    Entry order is the classical sort (borrow, -priority, timestamp) or,
    under fair sharing, the dynamic per-pop DRS tournament
    (fair_sharing_iterator.go — each pop re-evaluates shares on the
    mutated usage).

    state: (usage_full, usage_net, cq_rows, admitted, parked, wl_usage,
            victims_all, victim_reason)
    """
    C = cand_w.shape[0]
    W1 = t.wl_cqid.shape[0]
    W_null = W1 - 1

    prio = t.wl_prio[cand_w]
    ts_o = state["ts"][cand_w]
    uid = t.wl_uid[cand_w]
    active = (cand_w != W_null) & (mode != M_NOFIT)
    sort_borrow = jnp.where(active, borrow, BIG)
    order = jnp.lexsort((uid, ts_o, -prio, sort_borrow))
    # the entries that issue preemptions or are refused them: a
    # Preempt-mode entry whose lane's search found targets
    victim_entry = (active & (mode == M_PREEMPT) & (lane_of_entry >= 0)
                    & lane_success[jnp.maximum(lane_of_entry, 0)])

    def step(carry, slot):
        (usage_full, usage_net, cq_rows, admitted, parked, wl_usage,
         victims_all, victim_reason, lq_pen, any_adm, any_evict) = carry
        w, cqid, m, req, brw, lane, is_victim_entry = slot
        cq_node = t.cq_node[jnp.minimum(cqid, C - 1)]
        is_active = (w != W_null) & (m != M_NOFIT)
        searched = lane >= 0
        lane_i = jnp.maximum(lane, 0)
        has_targets = searched & lane_success[lane_i]

        # --- Preempt / NoCandidates: reserve entitled capacity & park ----
        is_reserve = is_active & (m == M_PREEMPT) & searched & ~has_targets
        reserve = jnp.where(
            is_reserve,
            _quota_to_reserve(t, usage_full, cq_node, req, brw), 0)
        usage_full = _add_usage_along_path(t, usage_full, cq_node, reserve)
        usage_net = _add_usage_along_path(t, usage_net, cq_node, reserve)
        parked = parked.at[w].set(
            parked[w] | (is_reserve & ~t.cq_strict[jnp.minimum(cqid, C - 1)]))

        # --- issue preemptions (scheduler.go issuePreemptions) -----------
        # only an entry with targets has victims to book: every other
        # entry skips the P-wide gathers and scatters below, which would
        # leave the carry as it is (their mask is all-false there)
        def preempt(ops):
            (usage_full, usage_net, cq_rows, admitted, victims_all,
             victim_reason) = ops
            # overlap check (one conflicting preemption per cycle)
            vm = lane_victims[lane_i]                   # [P]
            vw = lane_cand_w[lane_i]                    # [P]
            overlap = jnp.any(vm & victims_all[vw])

            # fits re-check under removal of own targets (the preempted
            # set is already excluded from usage_net by earlier steps);
            # the loop is bounded by the lane's last victim slot, not
            # p_max
            n_slots = jnp.max(jnp.where(
                vm, jnp.arange(p_max, dtype=jnp.int32) + 1, 0))

            def rv_cond(carry):
                _, i = carry
                return ~overlap & (i < n_slots)

            def rv_body(carry):
                u_c, i = carry
                a = vw[i]
                a_node = t.cq_node[jnp.minimum(t.wl_cqid[a], C - 1)]
                row = jnp.where(vm[i], wl_usage[a], 0)
                return (_remove_usage_along_path(t, u_c, a_node, row),
                        i + 1)

            usage_probe, _ = jax.lax.while_loop(
                rv_cond, rv_body,
                (usage_net, jnp.zeros((), dtype=jnp.int32)))
            avail_now = _avail_along_path(t, usage_probe, cq_node)
            still_fits = jnp.all((req == 0) | (req <= avail_now))

            do_preempt = ~overlap & still_fits
            usage_net = jnp.where(do_preempt, usage_probe, usage_net)
            evict_now = do_preempt & vm                 # [P]
            victims_all = victims_all.at[vw].max(evict_now, mode="drop")
            victims_all = victims_all.at[W_null].set(False)
            # record each victim's candidate variant (preemption reason)
            victim_reason = victim_reason.at[vw].max(
                jnp.where(evict_now, lane_reason[lane_i], 0), mode="drop")
            victim_reason = victim_reason.at[W_null].set(0)
            admitted = admitted.at[vw].min(~evict_now, mode="drop")
            # durable rows: victims' usage leaves their CQ row (P-sized
            # scatter)
            v_nodes = t.cq_node[jnp.minimum(t.wl_cqid[vw], C - 1)]
            cq_rows = cq_rows.at[v_nodes].add(
                -jnp.where(evict_now[:, None], wl_usage[vw], 0),
                mode="drop")
            # the preemptor charges its assignment usage for the rest of
            # the round (scheduler.go:434 cq.add_usage before
            # issuePreemptions)
            entry_usage = jnp.where(do_preempt, req, 0)
            usage_full = _add_usage_along_path(
                t, usage_full, cq_node, entry_usage)
            usage_net = _add_usage_along_path(
                t, usage_net, cq_node, entry_usage)
            return (usage_full, usage_net, cq_rows, admitted, victims_all,
                    victim_reason, do_preempt)

        (usage_full, usage_net, cq_rows, admitted, victims_all,
         victim_reason, do_preempt) = jax.lax.cond(
            is_victim_entry, preempt,
            lambda ops: (*ops, jnp.zeros((), dtype=bool)),
            (usage_full, usage_net, cq_rows, admitted, victims_all,
             victim_reason))
        any_evict = any_evict | do_preempt

        # --- Fit: re-check then admit ------------------------------------
        avail_fit = _avail_along_path(t, usage_net, cq_node)
        fit_ok = jnp.all((req == 0) | (req <= avail_fit))
        do_admit = is_active & (m == M_FIT) & fit_ok
        admit_vec = jnp.where(do_admit, req, 0)
        usage_full = _add_usage_along_path(t, usage_full, cq_node, admit_vec)
        usage_net = _add_usage_along_path(t, usage_net, cq_node, admit_vec)
        cq_rows = cq_rows.at[cq_node].add(admit_vec)
        admitted = admitted.at[w].set(admitted[w] | do_admit)
        wl_usage = wl_usage.at[w].set(
            jnp.where(do_admit, req, wl_usage[w]))
        # AFS entry penalty: charge the admitted usage to the LocalQueue
        # (afs/entry_penalties.go; scheduler record_admission hook)
        afs_cq = t.cq_afs[jnp.minimum(cqid, C - 1)]
        lq_pen = lq_pen.at[t.wl_lq[w]].add(
            jnp.where(do_admit & afs_cq, t.wl_afs_penalty[w], 0.0))
        any_adm = any_adm | do_admit
        return (usage_full, usage_net, cq_rows, admitted, parked, wl_usage,
                victims_all, victim_reason, lq_pen, any_adm, any_evict), (
            do_admit, do_preempt)

    init = (state["usage_full"], state["usage_net"], state["cq_rows"],
            state["admitted"], state["parked"], state["wl_usage"],
            state["victims_all"], state["victim_reason"],
            state["lq_penalty"],
            jnp.zeros((), dtype=bool), jnp.zeros((), dtype=bool))

    if not fs_enabled:
        slots = (cand_w[order], jnp.arange(C, dtype=jnp.int32)[order],
                 mode[order], req_c[order], borrow[order],
                 lane_of_entry[order], victim_entry[order])
        (usage_full, usage_net, cq_rows, admitted, parked, wl_usage,
         victims_all, victim_reason, lq_pen, any_adm, any_evict), (
            admitted_slot, preempted_slot) = (
            jax.lax.scan(step, init, slots))
        # map per-slot flags back to entry order
        adm_entry = jnp.zeros((C,), dtype=bool).at[order].set(admitted_slot)
        pre_entry = jnp.zeros((C,), dtype=bool).at[order].set(preempted_slot)
    else:
        from kueue_oss_tpu.solver.fair_kernels import fair_entry_pick

        def fs_cond(carry):
            _inner, act, _adm, _pre, i = carry
            return jnp.any(act) & (i < C)

        def fs_body(carry):
            inner, act, adm_e, pre_e, i = carry
            usage_net_cur = inner[1]
            e = fair_entry_pick(t, lendable_r, usage_net_cur, cand_w,
                                req_c, state["ts"], act)
            ec = jnp.minimum(e, C - 1)
            slot = (cand_w[ec], ec, mode[ec], req_c[ec], borrow[ec],
                    lane_of_entry[ec], victim_entry[ec])
            inner2, (da, dp) = step(inner, slot)
            picked = e < C
            inner = jax.tree_util.tree_map(
                lambda a, b: jnp.where(picked, b, a), inner, inner2)
            adm_e = adm_e.at[ec].set(adm_e[ec] | (picked & da))
            pre_e = pre_e.at[ec].set(pre_e[ec] | (picked & dp))
            act = act.at[ec].set(act[ec] & ~picked)
            return (inner, act, adm_e, pre_e, i + 1)

        fs_init = (init, active,
                   jnp.zeros((C,), dtype=bool), jnp.zeros((C,), dtype=bool),
                   jnp.zeros((), dtype=jnp.int32))
        (inner, _act, adm_entry, pre_entry, _i) = jax.lax.while_loop(
            fs_cond, fs_body, fs_init)
        (usage_full, usage_net, cq_rows, admitted, parked, wl_usage,
         victims_all, victim_reason, lq_pen, any_adm, any_evict) = inner

    return {
        "usage_full": usage_full, "usage_net": usage_net,
        "cq_rows": cq_rows, "admitted": admitted, "parked": parked,
        "wl_usage": wl_usage, "victims_all": victims_all,
        "victim_reason": victim_reason, "lq_penalty": lq_pen,
        # every active entry is scanned once, on both paths
        "entries": jnp.sum(active.astype(jnp.int32)),
        "victim_entries": jnp.sum(victim_entry.astype(jnp.int32)),
    }, adm_entry, pre_entry, any_adm, any_evict


# ---------------------------------------------------------------------------
# the drain loop
# ---------------------------------------------------------------------------


#: Lanes a trip of the gated search's loop runs stage 2 on (see
#: :func:`_gated_searches`). A search of no more lanes than one chunk
#: is left ungated (:func:`_run_searches`): the gate could save it one
#: trip at most, and at 32 lanes (``baseline-replay``) a gated program
#: took 2.8 s longer to trace and load than the ungated one on the
#: chip's host, four programs a set-up (+25 % of ``setup_s``).
#: Settled on the chip at 1,024 lanes x 2,048 candidates, 20 victims a
#: live lane (tools/search_chunk_sweep.py): a trip costs ~2.4 ms a lane
#: of the chunk whatever the chunk, so the smallest of 32 / 128 / 256 /
#: 1,024 tried wins with few live lanes (0.31 / 0.52 / 0.88 / 3.68 s
#: with 1 to 32 live) and loses 6 % to 128 with all of them live.
_STAGE2_CHUNK = 32


def _gated_searches(t, usage, wl_usage, admitted, evicted, ts,
                    flat_w, flat_req, flat_avail, flat_cands, p_max):
    """``jax.vmap(classical_search)`` over the lanes, with stage 2 run
    on the LIVE lanes only. Returns (the six per-lane results, the
    number of lanes that ran stage 2).

    Stage 1 runs on every lane; the live lanes (a legal candidate) are
    sorted to the front, stably; stage 2 runs on chunks of ``B`` of
    them in a loop of ``ceil(n_live / B)`` trips and its results
    scatter back to lane order. Every other lane keeps
    :func:`_dead_search_result`, which is what the whole search would
    have returned for it. No live lane, no trip: a round in which
    nobody can be evicted pays for stage 1 alone. And a round in which
    no lane has an FR needing preemption (every head fits, or there are
    no heads) skips stage 1 as well: stage 1 is a gather per candidate
    and lane, 0.18 s at 1,024 x 2,048 on the chip, and with no such FR
    no candidate ``uses`` one, so ``legal_all`` is false throughout.
    """
    L = flat_w.shape[0]
    B = _STAGE2_CHUNK

    def stage1():
        return jax.vmap(
            lambda hw, rq, av, cd: _search_stage1(
                t, usage, wl_usage, admitted, ts, hw, rq, av, cd))(
            flat_w, flat_req, flat_avail, flat_cands)

    P = flat_cands.shape[1]
    no_p = jnp.zeros((L, P), dtype=bool)
    s1 = jax.lax.cond(
        jnp.any(_frs_needing_preemption(flat_req, flat_avail)), stage1,
        lambda: _SearchStage1(
            jnp.zeros(flat_req.shape, dtype=bool),
            jnp.zeros((L, P), dtype=jnp.int32), no_p, no_p, no_p))
    live = jnp.any(s1.legal_all, axis=1)
    n_live = jnp.sum(live.astype(jnp.int32))
    dead = jax.vmap(
        lambda hw, rq, av: _dead_search_result(
            t, usage, hw, rq, av, p_max))(flat_w, flat_req, flat_avail)

    # live lanes first, in lane order; padded to whole chunks with the
    # out-of-range lane L, which gathers clamp and scatters drop
    order = jnp.concatenate([
        jnp.argsort(~live, stable=True).astype(jnp.int32),
        jnp.full(((-L) % B,), L, dtype=jnp.int32)])

    def cond(carry):
        i, _out = carry
        return i * B < n_live

    def body(carry):
        i, out = carry
        lanes = jax.lax.dynamic_slice(order, (i * B,), (B,))
        src = jnp.minimum(lanes, L - 1)
        res = jax.vmap(
            lambda hw, rq, cd, s: _search_stage2(
                t, usage, wl_usage, evicted, hw, rq, cd, s, p_max))(
            flat_w[src], flat_req[src], flat_cands[src],
            jax.tree_util.tree_map(lambda a: a[src], s1))
        # the last chunk's tail holds dead lanes: they keep ``dead``
        dst = jnp.where(
            i * B + jnp.arange(B, dtype=jnp.int32) < n_live, lanes, L)
        return i + 1, tuple(
            o.at[dst].set(r, mode="drop") for o, r in zip(out, res))

    _, out = jax.lax.while_loop(
        cond, body, (jnp.zeros((), dtype=jnp.int32), dead))
    return out, n_live


def _run_searches(t, usage, wl_usage, admitted, evicted, ts,
                  flat_w, flat_req, flat_avail, flat_cands, p_max,
                  fs_enabled, lendable_r, mesh, axis):
    """Run the per-lane victim searches, optionally SPMD over a mesh.
    Returns (the six per-lane results, lanes that ran the heavy search).

    On one device the classical search over more lanes than one chunk
    (``_STAGE2_CHUNK``) is gated by liveness (:func:`_gated_searches`):
    a lane with no legal candidate (no head, or nobody it may evict)
    gets its result without the search. A search that fits one chunk,
    the fair search and the mesh arm keep the ungated call on purpose,
    and every lane counts as run: one chunk has at most one trip to
    save, ``fair_search``'s dead-lane results are not established, and
    sorting the live lanes to the front before a lane-sharded
    ``shard_map`` would pile them onto one device.

    The victim search is the round's dominant cost and lanes are
    independent, so multi-chip scaling shards the LANE axis: each
    device searches its slice of (head, option) lanes against the
    replicated round state, and the [L]-shaped results concatenate
    back. Per-round collective volume is the lane results only
    (L x p_max ints over ICI); the tree/usage state never moves.
    """
    if mesh is None and not fs_enabled and flat_w.shape[0] > _STAGE2_CHUNK:
        # the stages are called bare here, so the scope is opened here:
        # liveness, compaction and the loop all read as the search's cost
        with jax.named_scope("classical_search"):
            return _gated_searches(
                t, usage, wl_usage, admitted, evicted, ts,
                flat_w, flat_req, flat_avail, flat_cands, p_max)
    n_lanes = jnp.asarray(flat_w.shape[0], dtype=jnp.int32)

    def vsearch(hw, rq, av, cd, t_, usage_, wl_usage_, admitted_,
                evicted_, ts_, lendable_):
        if fs_enabled:
            from kueue_oss_tpu.solver.fair_kernels import fair_search

            return jax.vmap(
                lambda a, b, c, d: fair_search(
                    t_, lendable_, usage_, wl_usage_, admitted_,
                    evicted_, ts_, a, b, c, d, p_max))(hw, rq, av, cd)
        return jax.vmap(
            lambda a, b, c, d: classical_search(
                t_, usage_, wl_usage_, admitted_, evicted_, ts_,
                a, b, c, d, p_max))(hw, rq, av, cd)

    if mesh is None:
        return vsearch(flat_w, flat_req, flat_avail, flat_cands, t, usage,
                       wl_usage, admitted, evicted, ts,
                       lendable_r), n_lanes

    from jax.sharding import PartitionSpec as P

    W_null = t.wl_cqid.shape[0] - 1
    n_dev = mesh.shape[axis]
    L = flat_w.shape[0]
    pad = (-L) % n_dev
    if pad:
        flat_w = jnp.concatenate(
            [flat_w, jnp.full((pad,), W_null, dtype=flat_w.dtype)])
        flat_req = jnp.concatenate(
            [flat_req, jnp.zeros((pad,) + flat_req.shape[1:],
                                 dtype=flat_req.dtype)])
        flat_avail = jnp.concatenate(
            [flat_avail, jnp.zeros((pad,) + flat_avail.shape[1:],
                                   dtype=flat_avail.dtype)])
        flat_cands = jnp.concatenate(
            [flat_cands, jnp.full((pad,) + flat_cands.shape[1:], W_null,
                                  dtype=flat_cands.dtype)])
    lend = lendable_r if lendable_r is not None else jnp.zeros((1,))

    def shard_body(hw, rq, av, cd, *rep):
        # mark the replicated state varying-over-mesh so while_loop
        # carries inside the search have consistent manual-axes types
        rep = jax.tree_util.tree_map(
            lambda x: jax.lax.pcast(x, (axis,), to="varying"), rep)
        return vsearch(hw, rq, av, cd, *rep)

    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis),
                  P(), P(), P(), P(), P(), P(), P()),
        out_specs=(P(axis),) * 6,
    )
    out = sharded(flat_w, flat_req, flat_avail, flat_cands, t, usage,
                  wl_usage, admitted, evicted, ts, lend)
    if pad:
        out = tuple(o[:L] for o in out)
    return out, n_lanes


@jax.named_scope("compact_victims")
def _compact_victims(lane_cand_w, lane_victims, lane_reason):
    """Each lane's victims moved to the front of its slot axis, stably.

    The entry scan's removal loop runs `last victim slot + 1`
    iterations, and a victim sitting at slot 3000 of a long candidate
    list would turn it into thousands of sequential steps per entry.
    With no victim in any lane every key is ``p_max`` and the stable
    sort is the identity, so a round without victims skips it."""
    p_max = lane_victims.shape[1]

    def compact(vw_row, vm_row, re_row):
        key = jnp.where(vm_row, jnp.arange(p_max, dtype=jnp.int32), p_max)
        order = jnp.argsort(key)
        return vw_row[order], vm_row[order], re_row[order]

    return jax.lax.cond(
        jnp.any(lane_victims), lambda ops: jax.vmap(compact)(*ops),
        lambda ops: ops, (lane_cand_w, lane_victims, lane_reason))


@jax.named_scope("round_body")
def round_body(t: FullTensors, state, pot, g_max: int, h_max: int,
               p_max: int, fs_enabled: bool = False, lendable_r=None,
               mesh=None, axis: str = "wl"):
    """One reference cycle (shared by the jitted loop and debug_drain)."""
    W1 = t.wl_cqid.shape[0]
    C = t.cq_node.shape[0]
    N1 = t.parent.shape[0]
    W_null = W1 - 1

    rounds = state["rounds"]
    admitted = state["admitted"]
    parked = state["parked"]
    ts = state["ts"]
    usage = state["usage"]          # round-start (victims charged)
    wl_usage = state["wl_usage"]
    class_nofit = state["class_nofit"]
    # scheduling-equivalence dedup (cluster_queue.go:371): anything whose
    # class is known NoFit parks before head selection — this catches
    # evicted workloads re-entering the pending set (the host's
    # push(check_no_fit=True) path)
    parked = parked | (~admitted & class_nofit[t.wl_class])
    parked = parked.at[t.wl_cqid.shape[0] - 1].set(False)
    parked_before = parked
    cursor_before = state["cursor"]

    cand_w = select_heads_full(t, admitted, parked, ts,
                               lq_penalty=state["lq_penalty"])

    # ---- what a preemptor reserved stays reserved until it is its
    # queue's head again (Scheduler._charge_quiet_reservations): the
    # rounds of one drain are one instant, and a victim must not borrow
    # back what its eviction freed before the preemptor gets to it.
    # Charged to the round-start usage only; the durable rows never
    # carry it
    is_head_w = jnp.zeros((W1,), dtype=bool).at[cand_w].set(True)
    resv = state["resv"] & ~is_head_w & ~admitted
    resv = resv.at[W_null].set(False)
    resv_req = state["resv_req"]

    def _with_reservations():
        node = t.cq_node[jnp.minimum(t.wl_cqid, C - 1)]
        rows = state["cq_rows"].at[node].add(
            jnp.where(resv[:, None], resv_req, 0))
        return refresh_cohort_usage(t, rows)

    usage = jax.lax.cond(jnp.any(resv), _with_reservations, lambda: usage)
    avail = available_all(t, usage)
    (mode, k_chosen, req_c, borrow, next_cursor,
     opt_fit, opt_preempt, opt_level, group_active, opt_valid) = (
        nominate_full(t, usage, avail, pot, cand_w, state["cursor"], g_max,
                      fs_enabled))

    is_head = cand_w != W_null
    K = t.cq_opt_group.shape[1]

    # ---- which heads need victim-search simulation? ------------------
    # A head with preempt-capable options needs per-option simulation to
    # pick its flavor (the granular-mode walk depends on NoCandidates /
    # Preempt / Reclaim and borrow-after, preemption_oracle.go) — except
    # when the provisional choice is a Fit under default fungibility
    # (whenCanPreempt=TryNextFlavor, BorrowingOverPreemption): there a
    # fit option always beats every preempt option in the walk.
    any_preemptish = jnp.any(opt_preempt & ~opt_fit, axis=1)  # [C]
    fit_wins = (mode == M_FIT) & t.cq_preempt_try_next & ~t.cq_pref_pob
    needs_search = (is_head & any_preemptish & ~fit_wins
                    & (mode != M_NOFIT))

    # ---- compact searching heads into H_MAX lanes (entry order) ------
    ekey = jnp.lexsort((
        t.wl_uid[cand_w], ts[cand_w], -t.wl_prio[cand_w],
        jnp.where(needs_search, borrow, BIG), ~needs_search))
    pe_sorted = needs_search[ekey]
    pos = jnp.cumsum(pe_sorted.astype(jnp.int32)) - 1
    lane_cq = jnp.full((h_max,), C, dtype=jnp.int32)
    lane_cq = lane_cq.at[jnp.where(pe_sorted, pos, h_max)].set(
        ekey.astype(jnp.int32), mode="drop")
    lane_valid = lane_cq < C
    lane_cqc = jnp.minimum(lane_cq, C - 1)
    lane_w = jnp.where(lane_valid, cand_w[lane_cqc], W_null)
    lane_avail = avail[t.cq_node[lane_cqc]]
    lane_of_entry = jnp.full((C,), -1, dtype=jnp.int32)
    lane_of_entry = lane_of_entry.at[
        jnp.where(lane_valid, lane_cq, C)].set(
        jnp.arange(h_max, dtype=jnp.int32), mode="drop")

    # ---- per-option victim-search simulation over [H, K] -------------
    # One search per (lane, option): SimulatePreemption parity (the host
    # runs _get_targets per flavor during assignment; the Preemptor
    # dispatches to the fair-sharing search when enabled). With a mesh,
    # the lane axis shards across devices (_run_searches). Candidates
    # come from the per-root round-start table (build_candidate_table).
    cand_table = build_candidate_table(t, admitted, state["admit_rank"],
                                       wl_usage, p_max)
    lane_cands = cand_table[t.cq_root[lane_cqc]]   # [H, P]

    def search(hw, rq, av, cd):
        return _run_searches(
            t, usage, wl_usage, admitted, state["evicted"], ts,
            hw, rq, av, cd, p_max, fs_enabled, lendable_r, mesh, axis)

    flat_w = jnp.repeat(lane_w, K)
    flat_req = t.wl_req[lane_w].reshape(h_max * K, -1)
    flat_avail = jnp.repeat(lane_avail, K, axis=0)
    flat_cands = jnp.repeat(lane_cands, K, axis=0)
    (s_succ, s_cand_w, s_victims, s_reason, s_same,
     s_borrow), lanes_run = search(flat_w, flat_req, flat_avail, flat_cands)
    lanes_offered = h_max * K

    # granular-mode table per (lane, option)
    sim_pmode = jnp.where(
        s_succ, jnp.where(s_same, P_PREEMPT, P_RECLAIM),
        P_NO_CANDIDATES).reshape(h_max, K)
    sim_borrow = s_borrow.reshape(h_max, K)
    fit_l = opt_fit[lane_cqc]                     # [H, K]
    pre_l = (opt_preempt & ~opt_fit)[lane_cqc]
    pmode_k = jnp.where(fit_l, P_FIT,
                        jnp.where(pre_l, sim_pmode, P_NOFIT))
    borrow_k = jnp.where(fit_l, opt_level[lane_cqc],
                         jnp.where(pre_l, sim_borrow, 0))

    # ---- the assigner's walk picks each lane's final assignment ------
    walk = jax.vmap(
        lambda hw, pm, bo, va, ga: walk_assign(t, hw, pm, bo, va, ga,
                                               g_max))
    (l_mode, l_k, l_req, l_borrow, l_next_cursor, l_pmode_sel) = walk(
        lane_w, pmode_k, borrow_k, opt_valid[lane_cqc],
        group_active[lane_cqc])
    l_req = jnp.where(lane_valid[:, None], l_req, 0)

    lane_target = jnp.where(lane_valid, lane_cq, C)
    mode = mode.at[lane_target].set(l_mode, mode="drop")
    k_chosen = k_chosen.at[lane_target].set(l_k, mode="drop")
    req_c = req_c.at[lane_target].set(l_req, mode="drop")
    borrow = borrow.at[lane_target].set(l_borrow, mode="drop")
    next_cursor = next_cursor.at[lane_target].set(
        l_next_cursor, mode="drop")

    # ---- final victim set for each preempting lane -------------------
    if g_max == 1:
        # single group: the chosen option's simulation IS the final
        # search (same request vector, same FRs)
        idx = jnp.arange(h_max, dtype=jnp.int32) * K + l_k[:, 0]
        lane_success = s_succ[idx]
        lane_cand_w = s_cand_w[idx]
        lane_victims = s_victims[idx]
        lane_reason = s_reason[idx]
    else:
        # multi-group: GetTargets re-runs on the combined assignment
        # usage (preemption.py get_targets with all preempt-mode frs)
        (lane_success, lane_cand_w, lane_victims, lane_reason,
         _s, _b), run2 = search(lane_w, l_req, lane_avail, lane_cands)
        lanes_run = lanes_run + run2
        lanes_offered += h_max
    lane_success = (lane_success & lane_valid & (l_mode == M_PREEMPT))

    lane_cand_w, lane_victims, lane_reason = _compact_victims(
        lane_cand_w, lane_victims, lane_reason)

    # park NoFit heads of BestEffortFIFO queues (post-walk modes)
    park_now = is_head & (mode == M_NOFIT) & ~t.cq_strict
    parked = parked.at[cand_w].set(parked[cand_w] | park_now)

    # ---- entry scan ---------------------------------------------
    scan_state = {
        "usage_full": usage, "usage_net": usage,
        "cq_rows": state["cq_rows"], "admitted": admitted,
        "parked": parked, "wl_usage": wl_usage,
        "victims_all": jnp.zeros((W1,), dtype=bool),
        "victim_reason": state["victim_reason"], "ts": ts,
        "lq_penalty": state["lq_penalty"],
    }
    out, adm_entry, pre_entry, any_adm, any_evict = full_round_scan(
        t, scan_state, cand_w, mode, k_chosen, req_c, borrow,
        lane_of_entry, lane_success, lane_cand_w, lane_victims,
        lane_reason, p_max, fs_enabled=fs_enabled, lendable_r=lendable_r)
    admitted = out["admitted"]
    parked = out["parked"]
    wl_usage = out["wl_usage"]
    victims = out["victims_all"]

    # an entry that issued preemptions reserves what it was charged
    resv = resv.at[cand_w].max(pre_entry)
    resv_req = resv_req.at[cand_w].set(
        jnp.where(pre_entry[:, None], req_c.astype(resv_req.dtype),
                  resv_req[cand_w]), mode="drop")
    resv = resv.at[W_null].set(False)

    # ---- bookkeeping for evicted victims ------------------------
    ts = jnp.where(victims, t.ts_evict_base + rounds, ts)
    evicted_f = state["evicted"] | victims
    admit_rank = jnp.where(victims, 0, state["admit_rank"])
    # re-admissions: clear Evicted, stamp reservation rank; the ordering
    # timestamp reverts to creation (the host clears the Evicted
    # condition, so queue_order_timestamp falls back to creation_time)
    newly = adm_entry & (cand_w != W_null)
    adm_w = jnp.where(newly, cand_w, W_null)
    ts = ts.at[adm_w].set(
        jnp.where(newly, t.wl_ts0[adm_w], ts[adm_w]), mode="drop")
    evicted_f = evicted_f.at[adm_w].set(
        jnp.where(newly, False, evicted_f[adm_w]), mode="drop")
    admit_rank = admit_rank.at[adm_w].set(
        jnp.where(newly, t.admit_rank_base + rounds,
                  admit_rank[adm_w]), mode="drop")
    evicted_f = evicted_f.at[W_null].set(False)

    # record chosen options + admit round for decode
    opt = state["opt"]
    admit_round = state["admit_round"]
    opt = opt.at[adm_w].set(
        jnp.where(newly[:, None], k_chosen, opt[adm_w]), mode="drop")
    admit_round = admit_round.at[adm_w].set(
        jnp.where(newly, rounds, admit_round[adm_w]), mode="drop")

    # flavor cursors: heads still pending resume their walk; an entry
    # that ISSUED preemptions restarts from flavor 0 next round (the
    # host clears last_assignment in _issue_preemptions,
    # scheduler.go:447 area)
    keep = is_head & ~admitted[cand_w]
    new_cur = jnp.where(pre_entry[:, None], 0, next_cursor)
    cursor = state["cursor"].at[cand_w].set(
        jnp.where(keep[:, None], new_cur,
                  state["cursor"][cand_w]), mode="drop")
    # an evicted workload restarts its flavor walk
    cursor = jnp.where(victims[:, None], 0, cursor)

    # ---- NoFit equivalence classes (handleInadmissibleHash): a head
    # parked this round marks its class NoFit; every pending equivalent
    # parks with it until the capacity-freed flush clears the class
    newly_parked = parked & ~parked_before
    class_nofit = class_nofit.at[
        jnp.where(newly_parked, t.wl_class,
                  class_nofit.shape[0] - 1)].max(newly_parked, mode="drop")
    class_nofit = class_nofit.at[class_nofit.shape[0] - 1].set(False)
    parked = parked | (~admitted & class_nofit[t.wl_class])
    parked = parked.at[W_null].set(False)

    # ---- capacity-freed flush: unpark cohort roots with evictions
    freed_root = jnp.zeros((N1,), dtype=bool)
    victim_roots = t.cq_root[jnp.minimum(t.wl_cqid[:-1], C - 1)]
    freed_root = freed_root.at[victim_roots].max(victims[:-1])
    wl_root = t.cq_root[jnp.minimum(t.wl_cqid, C - 1)]
    parked = parked & ~freed_root[wl_root]
    class_nofit = class_nofit & ~freed_root[t.class_root]

    # ---- durable usage for next round ---------------------------
    usage_next = refresh_cohort_usage(t, out["cq_rows"])

    progress = (any_adm | any_evict
                | jnp.any(parked & ~parked_before)
                | jnp.any(cursor != cursor_before))
    new_state = {
        "usage": usage_next, "cq_rows": out["cq_rows"],
        "admitted": admitted, "parked": parked, "ts": ts,
        "evicted": evicted_f, "admit_rank": admit_rank,
        "wl_usage": wl_usage, "cursor": cursor, "opt": opt,
        "admit_round": admit_round, "class_nofit": class_nofit,
        "victim_reason": out["victim_reason"],
        "lq_penalty": out["lq_penalty"], "progress": progress,
        "rounds": rounds + 1, "resv": resv, "resv_req": resv_req,
        # how often the liveness gate engages (_run_searches): lanes
        # offered to the victim search, and lanes that ran its stage 2
        "search_lanes": state["search_lanes"] + lanes_offered,
        "search_live_lanes": state["search_live_lanes"] + lanes_run,
        # how often the entry scan's victim branch engages
        # (full_round_scan): active entries scanned, and entries that
        # took it
        "scan_entries": state["scan_entries"] + out["entries"],
        "scan_victim_entries": (state["scan_victim_entries"]
                                + out["victim_entries"]),
    }
    debug = {
        "cand_w": cand_w, "mode": mode, "req_c": req_c,
        "victims": victims, "adm_entry": adm_entry, "pre_entry": pre_entry,
        "lane_w": lane_w, "lane_success": lane_success,
        "lane_cand_w": lane_cand_w, "lane_victims": lane_victims,
    }
    return new_state, debug


def _init_state(t: FullTensors, g_max: int):
    W1 = t.wl_cqid.shape[0]
    return {
        "usage": t.usage0,
        "cq_rows": jnp.where(t.is_cq[:, None], t.usage0, 0),
        "admitted": t.wl_admitted0,
        "parked": t.wl_parked0,
        "ts": t.wl_ts0,
        "evicted": t.wl_evicted0,
        "admit_rank": t.wl_admit_rank0,
        "wl_usage": t.ad_usage,
        "cursor": jnp.zeros((W1, g_max), dtype=jnp.int32),
        "opt": jnp.zeros((W1, g_max), dtype=jnp.int32),
        "admit_round": jnp.full((W1,), -1, dtype=jnp.int32),
        "victim_reason": jnp.zeros((W1,), dtype=jnp.int8),
        "lq_penalty": t.lq_penalty0,
        "class_nofit": jnp.zeros((t.class_root.shape[0],), dtype=bool),
        "resv": jnp.zeros((W1,), dtype=bool),
        "resv_req": jnp.zeros_like(t.wl_req[:, 0], dtype=t.usage0.dtype),
        "progress": jnp.ones((), dtype=bool),
        "rounds": jnp.zeros((), dtype=jnp.int32),
        "search_lanes": jnp.zeros((), dtype=jnp.int32),
        "search_live_lanes": jnp.zeros((), dtype=jnp.int32),
        "scan_entries": jnp.zeros((), dtype=jnp.int32),
        "scan_victim_entries": jnp.zeros((), dtype=jnp.int32),
    }


def _solve_full_impl(t: FullTensors, g_max: int, h_max: int, p_max: int,
                     fs_enabled: bool = False, round_cap: int = 0,
                     mesh=None, axis: str = "wl"):
    """The drain body shared by the single-problem jit
    (:func:`make_full_solver`) and the scenario-batched vmap
    (:func:`solve_backlog_full_batched`). Pure traced jnp code — the
    static caps select the program, the tensors are the only inputs."""
    W1 = t.wl_cqid.shape[0]
    C = t.cq_node.shape[0]
    W_null = W1 - 1
    pot = potential_available_all(t)
    if fs_enabled:
        from kueue_oss_tpu.solver.fair_kernels import (
            lendable_by_resource,
        )

        lendable_r = lendable_by_resource(t, pot)
    else:
        lendable_r = None
    bound = 2 * W1 + C + 5
    if round_cap:
        bound = min(bound, round_cap)

    def cond(state):
        return state["progress"] & (state["rounds"] < bound)

    def body(state):
        new_state, _ = round_body(t, state, pot, g_max, h_max, p_max,
                                  fs_enabled, lendable_r, mesh, axis)
        return new_state

    final = jax.lax.while_loop(cond, body, _init_state(t, g_max))
    admitted = final["admitted"].at[W_null].set(False)
    parked = final["parked"].at[W_null].set(False)
    return (admitted, final["opt"], final["admit_round"], parked,
            final["rounds"], final["usage"], final["wl_usage"],
            final["victim_reason"], final["search_lanes"],
            final["search_live_lanes"], final["scan_entries"],
            final["scan_victim_entries"])


def lane_work_budget() -> int:
    """Per-round victim-search work budget, in lane-option-group units
    (each lane runs K x g searches), of the backend THIS process
    solves on: on an accelerator the lanes vectorize so the budget is
    generous; on the CPU they serialize, so multi-flavor/multi-group
    shapes trade lanes for rounds at roughly constant work.

    Only the process that owns the device may ask: a CPU-pinned manager
    in front of a sidecar would get the CPU's answer for the sidecar's
    chip (see :func:`budgeted_lanes`, applied sidecar-side)."""
    return 8192 if jax.default_backend() != "cpu" else 512


def budgeted_lanes(h_max: int, work_budget: int, K: int, g: int) -> int:
    """Clamp ``h_max`` victim-search lanes to ``work_budget``: the
    budgeted lane count rounds DOWN to a power of two so the budget is
    actually enforced; the 64-lane floor overrides it for very wide
    K x g shapes (fewer lanes than that defers too many heads per
    round to ever converge quickly)."""
    lane_cap = pow2(max(1, work_budget // max(K * g, 1)) + 1) // 2
    return min(h_max, max(64, lane_cap))


def make_full_solver(g_max: int, h_max: int, p_max: int,
                     fs_enabled: bool = False, round_cap: int = 0,
                     mesh=None, axis: str = "wl"):
    """Build the jitted preemption-capable drain for static caps.

    ``round_cap`` > 0 bounds the drain's rounds below the quiescence
    bound (benchmarks use it to terminate preemption ping-pong shapes
    the way the reference's wall-clock limits do). ``mesh`` shards the
    victim-search lane axis across devices (see _run_searches)."""

    @jax.jit
    def solve(t: FullTensors):
        return _solve_full_impl(t, g_max, h_max, p_max, fs_enabled,
                                round_cap, mesh, axis)

    return solve


def debug_drain(problem: SolverProblem, g_max: int, h_max: int = 8,
                p_max: int = 32, max_rounds: int = 64, verbose: bool = True,
                fs_enabled: bool = False):
    """Python-loop drain printing per-round events (development aid)."""
    import numpy as np

    t = to_device_full(problem)
    pot = potential_available_all(t)
    if fs_enabled:
        from kueue_oss_tpu.solver.fair_kernels import lendable_by_resource

        lendable_r = lendable_by_resource(t, pot)
    else:
        lendable_r = None
    state = _init_state(t, g_max)
    W_null = t.wl_cqid.shape[0] - 1
    step = jax.jit(lambda tt, st: round_body(tt, st, pot, g_max, h_max,
                                             p_max, fs_enabled, lendable_r))

    def name(w):
        w = int(w)
        return problem.wl_keys[w] if w < W_null else "-"

    for r in range(max_rounds):
        state, dbg = step(t, state)
        if verbose:
            heads = [(name(w), int(m), int(b))
                     for w, m, b in zip(np.asarray(dbg["cand_w"]),
                                        np.asarray(dbg["mode"]),
                                        np.asarray(dbg["req_c"]).sum(1))
                     if int(w) != W_null]
            evs = [name(i) for i, v in
                   enumerate(np.asarray(dbg["victims"])[:-1]) if v]
            adms = [name(w) for w, a in zip(np.asarray(dbg["cand_w"]),
                                            np.asarray(dbg["adm_entry"]))
                    if a and int(w) != W_null]
            print(f"round {r}: heads(mode,req)={heads} "
                  f"admitted={adms} evicted={evs}")
        if not bool(state["progress"]):
            break
    return state


_solver_cache: dict = {}
#: programs :func:`full_solver` has built since the process started: a
#: drain that adds to it met static caps this process had not traced
#: (seconds of Python tracing, and a compile where the persistent cache
#: has none). SolverEngine._drain_full reports each drain's share.
_solver_builds = 0


def solver_builds() -> int:
    return _solver_builds


def _solver_key(g_max: int, h_max: int, p_max: int, fs_enabled: bool,
                mesh, axis: str) -> tuple:
    """The fair-sharing gates are baked in at trace time, so they join
    the cache key — a gate flip must not serve a stale compilation. The
    mesh joins it so single-chip and mesh programs coexist."""
    from kueue_oss_tpu import features

    gates = ()
    if fs_enabled:
        gates = (features.enabled("FairSharingPreemptWithinNominal"),
                 features.enabled("FairSharingPrioritizeNonBorrowing"),
                 features.enabled("PrioritySortingWithinCohort"))
    return (g_max, h_max, p_max, fs_enabled, gates, mesh, axis)


def full_solver(g_max: int, h_max: int = 32, p_max: int = 128,
                fs_enabled: bool = False, mesh=None, axis: str = "wl"):
    """The cached jitted drain for static caps; (g_max, h_max, p_max,
    fs) are compile-time. Called on the tensors it returns the plan's
    eight arrays (:func:`solve_backlog_full`) and, after them, four
    int32 sums over the drain's rounds: of the victim search's liveness
    gate, lanes offered and lanes that ran the heavy search
    (:func:`_run_searches`); of the entry scan, active entries and
    entries that took its victim branch (:func:`full_round_scan`).

    With a ``mesh``, the victim-search lanes shard across its devices
    (_run_searches)."""
    key = _solver_key(g_max, h_max, p_max, fs_enabled, mesh, axis)
    fn = _solver_cache.get(key)
    if fn is None:
        global _solver_builds
        fn = make_full_solver(g_max, h_max, p_max, fs_enabled,
                              mesh=mesh, axis=axis)
        _solver_cache[key] = fn
        _solver_builds += 1
    return fn


#: candidate widths up to this one stand in for any narrower one
_NARROW_P_MAX = 64


def built_p_max(g_max: int, h_max: int, p_max: int,
                fs_enabled: bool = False, mesh=None,
                axis: str = "wl") -> int:
    """The candidate width a drain that NEEDS ``p_max`` should run at:
    twice that where :func:`full_solver` has the next width up and not
    this one (for a width under ``_NARROW_P_MAX``: the narrowest built
    one up to it), else ``p_max`` itself.

    ``p_max`` only pads the candidate axis, so a wider program gives the
    same plan bit for bit; what differs is the cost. Building the exact
    program stalls the drain for seconds of Python tracing and a compile
    or a cache load (8-11 s and ~50 s at 1,024 lanes on a v5e), and a
    cap moves down as easily as up: a cohort whose population falls
    under its capacity is sized by its population (_size_caps). One
    width up costs at most what the power-of-two rounding already
    accepts; further up a drain would pay for a burst long after it, so
    the exact program is built."""
    def have(p):
        return _solver_key(g_max, h_max, p, fs_enabled, mesh,
                           axis) in _solver_cache

    if have(p_max):
        return p_max
    # one width up; and under _NARROW_P_MAX any built width up to it: a
    # candidate axis that narrow is all padding cost-wise, and a stream
    # whose cohorts hold a few large gangs sizes its drains by a
    # population of 8 to 32 rows, a program each where this stopped
    wider = 2 * p_max
    while wider <= max(2 * p_max, _NARROW_P_MAX):
        if have(wider):
            return wider
        wider *= 2
    return p_max


def solve_backlog_full(t: FullTensors, g_max: int, h_max: int = 32,
                       p_max: int = 128, fs_enabled: bool = False,
                       mesh=None, axis: str = "wl"):
    """The plan of :func:`full_solver`'s program for ``t``: (admitted,
    opt, admit_round, parked, rounds, usage, wl_usage, victim_reason).
    The same on one device and over a mesh, which the search counts
    that follow them in the program's return are not."""
    return full_solver(g_max, h_max, p_max, fs_enabled, mesh, axis)(t)[:8]


#: FullTensors fields the scenario overlay layer varies — the FULL
#: twins of kernels.BATCHABLE_FIELDS (lean ``wl_ts`` is ``wl_ts0``
#: here; the lean ``wl_rank`` has no FULL twin: the full kernel
#: selects heads by (priority, ts, uid) and masked rows drop out of
#: the per-CQ segment reductions through ``wl_cqid = C``).
FULL_BATCHABLE_FIELDS = frozenset({
    "nominal", "subtree", "local_quota", "has_borrow", "borrow_limit",
    "usage0", "wl_cqid", "wl_prio", "wl_ts0", "wl_valid", "wl_req",
})

#: Every FullTensors field. Like the lean kernel, the drain body is
#: shape-static gather/scatter arithmetic with no host-side dependence
#: on array content, so any field may carry the scenario axis;
#: FULL_BATCHABLE_FIELDS remains the documented overlay subset.
ALL_FULL_FIELDS = frozenset(FullTensors._fields)


def solve_backlog_full_batched(t: FullTensors, overrides: dict,
                               g_max: int, h_max: int = 32,
                               p_max: int = 128,
                               fs_enabled: bool = False,
                               round_cap: int = 0):
    """Solve S counterfactual variants of one FULL problem in ONE
    device dispatch: ``jit(vmap)`` of the preemption-capable drain.

    ``overrides`` maps FullTensors field names to stacked [S, ...]
    scenario variants; unnamed fields broadcast unbatched (the large
    ``wl_req`` tensor on quota-only sweeps costs one copy, not S).
    Returns the solve_backlog_full 8-tuple and the four counts that
    follow it (:func:`full_solver`), with a leading scenario axis
    on every output. The victim-search lane memory scales as
    S x h_max x K x p_max — callers size S from a
    :class:`~kueue_oss_tpu.sim.batch.LaneBudget`, not from the sweep
    width. Mesh lane-sharding never composes with the scenario axis
    (the batched path is single-program; chunking IS the scale story).
    """
    if not overrides:
        raise ValueError("batched full solve needs at least one "
                         "scenario-varying field (use "
                         "solve_backlog_full otherwise)")
    bad = set(overrides) - ALL_FULL_FIELDS
    if bad:
        raise ValueError(
            f"fields {sorted(bad)} are not FullTensors fields; "
            f"batchable: {sorted(ALL_FULL_FIELDS)}")
    from kueue_oss_tpu import features

    gates = ()
    if fs_enabled:
        gates = (features.enabled("FairSharingPreemptWithinNominal"),
                 features.enabled("FairSharingPrioritizeNonBorrowing"),
                 features.enabled("PrioritySortingWithinCohort"))
    key = ("batched", frozenset(overrides), g_max, h_max, p_max,
           fs_enabled, gates, round_cap)
    fn = _solver_cache.get(key)
    if fn is None:
        axes = FullTensors(
            **{f: (0 if f in overrides else None)
               for f in FullTensors._fields})
        fn = jax.jit(jax.vmap(
            partial(_solve_full_impl, g_max=g_max, h_max=h_max,
                    p_max=p_max, fs_enabled=fs_enabled,
                    round_cap=round_cap),
            in_axes=(axes,)))
        _solver_cache[key] = fn
    return fn(t._replace(**{k: jnp.asarray(v)
                            for k, v in overrides.items()}))
