"""TAS placement on device: dense per-level capacity tensors.

The topology tree (block -> rack -> host) becomes one dense array per
level: `parents[l][d]` indexes level l-1; leaf capacities arrive as a
[D_leaf, R] resource matrix. Placement for one podset runs entirely in
jitted JAX:

  phase 1 (fillInCounts, tas_flavor_snapshot.go:1568):
    leaf state = floor-min over resources of capacity / per-pod request;
    upper levels = one segment_sum per level.

  phase 2 (findLevelWithFitDomains + updateCountsToMinimumGeneric,
  :1236-1469), BestFit profile: at the requested level pick the
  smallest single domain that fits the whole count (ties -> first in
  lexicographic order); preferred requests fall back upward level by
  level, then place greedily (state desc) at the top level taking full
  domains until the remainder fits a single domain, which is then
  chosen best-fit — a sort + prefix-sum + two segment reductions.
  The descent applies the same rule per sibling group at every level.

Scope: the base placer (make_placer) covers single podsets under the
BestFit / LeastFreeCapacity profiles; the extended placer
(make_placer_ext) adds single-layer podset slices, a count-1 leader
podset and, built with ``bal_cap``, BALANCED placement
(tas_balanced_placement.go, gate TASBalancedPlacement) of a
preferred-level request with no slice and no leader: the greedy
evaluation, the balance threshold, the pruning, the choice of the best
sibling group, selectOptimalDomainSetToFit as a dense table over
(domains chosen, capacity left) that keeps the FIRST subset the host's
dict-memoized DP finds, the entropy order from the host's own
fixed-point sums (tas/snapshot.py ``xlog2x_fixed``), and the even
distribution with front-first extras (``_balanced_at_level``). All are
parity-tested against the host tree (tests/test_tas_kernel.py,
test_tas_kernel_ext.py, test_tas_kernel_balanced.py). Still host-only:
balanced placement of sliced or leader-led podsets or of gangs above
``BALANCED_MAX_COUNT`` pods, and nested multi-layer slice constraints.

Reference parity: pkg/cache/scheduler/tas_flavor_snapshot.go (two-phase
algorithm); SURVEY.md §7 step 6 calls this the most TPU-friendly
subproblem.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

BIG = np.int32(1 << 30)


@dataclass
class TASLevels:
    """Dense tree: level l has D_l domains ordered lexicographically by
    their level values; parents[l] maps into level l-1 (parents[0]=0)."""

    parents: list[np.ndarray]          # per level: [D_l] int32
    leaf_capacity: np.ndarray          # [D_leaf, R] int32
    leaf_names: list[tuple[str, ...]]  # decode table
    resources: list[str]
    #: per level: [D_l] int32, a domain's place in the host tree's own
    #: order of that level (``domains_per_level[l]``: creation order),
    #: which orders siblings as ``Domain.children`` does. The balanced
    #: placement breaks ties by it where the host iterates its lists
    ranks: list[np.ndarray] | None = None


def build_levels(snapshot) -> TASLevels:
    """Flatten a host TASFlavorSnapshot's domain tree (lex order per
    level, matching buildAssignment's sort)."""
    levels = []
    for l in range(len(snapshot.levels)):
        doms = sorted(snapshot.domains_per_level[l].values(),
                      key=lambda d: d.level_values)
        levels.append(doms)
    index = [{d.id: i for i, d in enumerate(doms)} for doms in levels]
    parents = []
    for l, doms in enumerate(levels):
        if l == 0:
            parents.append(np.zeros(len(doms), dtype=np.int32))
        else:
            parents.append(np.asarray(
                [index[l - 1][d.id[:-1]] for d in doms], dtype=np.int32))
    ranks = []
    for l, doms in enumerate(levels):
        # children are appended, and a level's dict is filled, in the
        # one order in which the tree was linked: one rank serves both
        created = {d.id: i for i, d in enumerate(
            snapshot.domains_per_level[l].values())}
        ranks.append(np.asarray([created[d.id] for d in doms],
                                dtype=np.int32))
    resources = sorted({r for d in levels[-1] for r in d.free_capacity})
    cap = np.zeros((len(levels[-1]), max(1, len(resources))),
                   dtype=np.int64)
    for i, d in enumerate(levels[-1]):
        for j, r in enumerate(resources):
            cap[i, j] = max(0, d.free_capacity.get(r, 0)
                            - d.tas_usage.get(r, 0))
    return TASLevels(
        parents=parents,
        leaf_capacity=np.minimum(cap, BIG).astype(np.int32),
        leaf_names=[d.id for d in levels[-1]],
        resources=resources,
        ranks=ranks,
    )


def fill_counts(parents, leaf_capacity, per_pod):
    """Phase 1: per-level fit counts, leaves up (segment sums)."""
    nz = per_pod > 0
    per_dom = jnp.where(nz[None, :],
                        leaf_capacity // jnp.maximum(per_pod, 1)[None, :],
                        BIG)
    state = jnp.min(per_dom, axis=1)               # [D_leaf]
    states = [state]
    for l in range(len(parents) - 1, 0, -1):
        n_up = parents[l - 1].shape[0]
        state = jax.ops.segment_sum(state, parents[l], num_segments=n_up)
        states.append(state)
    states.reverse()                                # states[l] = [D_l]
    return states


def _greedy_segment(state, seg, need_of_seg, n_seg, least_free=False):
    """Minimize-domains assignment within each segment (sibling group).

    `state` [D], `seg` [D] segment id, `need_of_seg` [S] pods each
    segment must place (0 = inactive). Take full domains in (state desc,
    index asc) order until the remainder fits one domain, then give the
    remainder to the smallest sufficient domain at or after the
    crossing (updateCountsToMinimumGeneric + findBestFitDomainBy).

    `least_free` (traced bool) flips to the LeastFreeCapacity profile
    (unconstrained podsets under TASProfileMixed,
    tas_flavor_snapshot.go sortedDomains ascending): fill (state asc,
    index asc). In ascending order the best-fit refinement below is a
    no-op — the crossing domain IS the smallest sufficient one — so the
    same formula reproduces the host's sequential consume loop.
    Returns assignment [D].
    """
    D = state.shape[0]
    idx = jnp.arange(D, dtype=jnp.int32)
    sort_state = jnp.where(least_free, state, -state)
    order = jnp.lexsort((idx, sort_state, seg))
    take_sorted = _consume_in_order(state[order], seg[order], need_of_seg,
                                    n_seg, least_free)
    return jnp.zeros_like(state).at[order].set(take_sorted)


def make_placer(parents_np: list[np.ndarray]):
    """Build a jitted placement fn for one tree shape."""
    parents = [jnp.asarray(p) for p in parents_np]
    n_levels = len(parents)

    @jax.jit
    def place(leaf_capacity, per_pod, count, requested_level,
              required, unconstrained, least_free=False):
        states = fill_counts(parents, leaf_capacity, per_pod)

        def single_best(l):
            s = states[l]
            fits = s >= count
            key = jnp.where(fits, s, BIG)
            return jnp.any(fits), jnp.argmin(key).astype(jnp.int32)

        # ---- choose the start level + single-fit domain ---------------
        # preference: requested level first, then upward (preferred
        # requests only) — scan levels deepest-first
        chosen_level = jnp.asarray(-1, dtype=jnp.int32)
        chosen_dom = jnp.asarray(0, dtype=jnp.int32)
        for l in range(n_levels - 1, -1, -1):
            ok, d = single_best(l)
            allowed = jnp.where(
                required | unconstrained, l == requested_level,
                l <= requested_level)
            hit = ok & allowed & (chosen_level < 0) & (
                l <= requested_level)
            chosen_level = jnp.where(hit, l, chosen_level)
            chosen_dom = jnp.where(hit & (chosen_level == l), d,
                                   chosen_dom)
        single_fit = chosen_level >= 0

        # ---- seed the start level ------------------------------------
        sel = [jnp.zeros_like(s) for s in states]
        feasible = jnp.zeros((), dtype=bool)
        # greedy fallback level: top (0) for preferred, requested for
        # unconstrained; required never falls back
        greedy_level = jnp.where(unconstrained, requested_level, 0)
        for l in range(n_levels):
            is_single = single_fit & (chosen_level == l)
            one_hot = (jnp.arange(states[l].shape[0],
                                  dtype=jnp.int32) == chosen_dom)
            seed_single = jnp.where(one_hot, count, 0)
            seg = jnp.zeros_like(states[l])        # one global segment
            g = _greedy_segment(
                states[l], seg,
                jnp.full((1,), count, dtype=states[l].dtype), 1,
                least_free=least_free)
            g_ok = jnp.sum(states[l]) >= count
            use_greedy = (~single_fit) & (greedy_level == l) & ~required
            sel[l] = jnp.where(is_single, seed_single,
                               jnp.where(use_greedy & g_ok, g, sel[l]))
            feasible = feasible | is_single | (use_greedy & g_ok)
        start = jnp.where(single_fit, chosen_level, greedy_level)

        # ---- descend ---------------------------------------------------
        for l in range(n_levels - 1):
            par = parents[l + 1]
            n_par = states[l].shape[0]
            computed = _greedy_segment(states[l + 1], par, sel[l], n_par,
                                       least_free=least_free)
            # best-fit single-child shortcut per sibling group (the
            # least-free profile consumes sequentially without it,
            # _consume_minimum's ascending loop)
            need = sel[l][par]
            fits_whole = (states[l + 1] >= need) & (need > 0) & ~least_free
            key = jnp.where(fits_whole, states[l + 1], BIG)
            m = jax.ops.segment_min(key, par, num_segments=n_par)
            has_single = (m < BIG)[par] & (need > 0) & ~least_free
            cidx = jnp.arange(par.shape[0], dtype=jnp.int32)
            is_best = fits_whole & (states[l + 1] == m[par])
            first_best = jax.ops.segment_min(
                jnp.where(is_best, cidx, BIG), par, num_segments=n_par)
            single_take = jnp.where(
                (cidx == first_best[par]) & has_single, need, 0)
            next_sel = jnp.where(has_single, single_take, computed)
            # levels at or above the start keep their seeded values
            sel[l + 1] = jnp.where(jnp.asarray(l + 1) <= start,
                                   sel[l + 1], next_sel)

        leaf_sel = sel[n_levels - 1]
        feasible = feasible & (jnp.sum(leaf_sel) == count)
        return leaf_sel, feasible

    return place


def make_sequential_placer(parents_np: list[np.ndarray]):
    """Jitted DRAIN of a whole TAS backlog on device: place M podsets
    one after another with the leaf-capacity carry updated in between
    (the perf-shape workload: 15k sequential admissions against one
    640-node tree, configs/tas/generator.yaml). One lax.scan step per
    workload; everything stays on the accelerator.

    Inputs: per-workload arrays [M] — per_pod [M,R], count [M],
    requested level [M], required/unconstrained/least_free flags [M].
    Returns (leaf_sel [M, D_leaf], feasible [M], leaf_capacity_after).
    """
    place = make_placer(parents_np)

    @jax.jit
    def place_all(leaf_capacity, per_pod, count, level, required,
                  unconstrained, least_free):
        def step(cap, xs):
            pp, ct, lv, rq, un, lf = xs
            sel, ok = place(cap, pp, ct, lv, rq, un, lf)
            take = jnp.where(ok, sel, 0)
            cap = cap - take[:, None] * pp[None, :]
            return cap, (sel * ok.astype(sel.dtype), ok)

        cap_after, (sels, oks) = jax.lax.scan(
            step, leaf_capacity,
            (per_pod, count, level, required, unconstrained, least_free))
        return sels, oks, cap_after

    return place_all


def make_sequential_placer_ext(parents_np: list[np.ndarray],
                               ranks_np=None, bal_cap: int = 0):
    """Sequential on-device drain through the slice/leader-capable
    placer: per-workload slice_size/slice_level and an optional count-1
    leader (``has_leader`` [M] bool — explicit, so a leader podset with
    all-zero requests places identically to place_podset_ext). The
    capacity carry subtracts worker pods AND the leader's row. A row of
    ``count`` 0 (a padded or a pre-rejected one) runs no placement at
    all. ``ranks_np`` and ``bal_cap`` as in ``make_placer_ext``;
    ``balanced`` [M] marks the rows that ask for balanced placement."""
    place = make_placer_ext(parents_np, ranks_np, bal_cap)

    @jax.jit
    def place_all(leaf_capacity, per_pod, count, level, required,
                  unconstrained, least_free, slice_size, slice_level,
                  leader_per_pod, has_leader, balanced=None):
        if balanced is None:
            balanced = jnp.zeros(count.shape, dtype=bool)
        n_leaf = leaf_capacity.shape[0]

        def step(cap, xs):
            pp, ct, lv, rq, un, lf, ss, sl, lpp, hl, bal = xs

            def run(_):
                return place(cap, pp, ct, lv, rq, un, lf, ss, sl, lpp,
                             hl, bal)

            def skip(_):
                return (jnp.zeros((n_leaf,), dtype=jnp.int32),
                        jnp.int32(-1), jnp.zeros((), dtype=bool))

            sel, lead_leaf, ok = jax.lax.cond(ct > 0, run, skip, None)
            take = jnp.where(ok, sel, 0)
            cap = cap - take[:, None] * pp[None, :]
            lead_onehot = (jnp.arange(cap.shape[0], dtype=jnp.int32)
                           == lead_leaf) & ok & hl
            cap = cap - jnp.where(lead_onehot[:, None], lpp[None, :], 0)
            return cap, (sel * ok.astype(sel.dtype),
                         jnp.where(ok, lead_leaf, -1), ok)

        with jax.named_scope("tas_place_all"):
            cap_after, (sels, leads, oks) = jax.lax.scan(
                step, leaf_capacity,
                (per_pod, count, level, required, unconstrained,
                 least_free, slice_size, slice_level, leader_per_pod,
                 has_leader, balanced))
        return sels, leads, oks, cap_after

    return place_all


# ---------------------------------------------------------------------------
# extended placer: slices + leaders (tas_flavor_snapshot.go:867-1060,
# 1348-1469)
# ---------------------------------------------------------------------------


def fill_counts_ext(parents, leaf_capacity, per_pod, leader_per_pod,
                    has_leader, slice_size, slice_level):
    """Phase 1 with slice and leader states (fillInCounts +
    fillInCountsHelper, tas_flavor_snapshot.go:1568-1719).

    Returns per level l: dict with st (pods), swl (pods with the leader
    hosted somewhere below), ls (leader capacity 0/1), ss (slices),
    sswl (slices with leader). ``slice_level``/``slice_size`` are traced
    scalars; levels are a static Python loop.
    """
    from kueue_oss_tpu.solver import pallas_tas

    n_levels = len(parents)
    if (pallas_tas.use_pallas()
            and leaf_capacity.shape[1] <= 128):
        # the fused Pallas leaf pass (one tile read for st/swl/ls);
        # non-TPU backends run the same kernel in interpret mode
        st, swl, ls = pallas_tas.leaf_states(
            leaf_capacity, per_pod, leader_per_pod, has_leader,
            interpret=pallas_tas.interpret_mode())
    else:
        st, swl, ls = pallas_tas.leaf_states_reference(
            leaf_capacity, per_pod, leader_per_pod, has_leader)

    leaf_l = n_levels - 1
    at_sl = leaf_l == slice_level
    ss = jnp.where(at_sl, st // jnp.maximum(slice_size, 1), 0)
    sswl = jnp.where(at_sl, swl // jnp.maximum(slice_size, 1), 0)
    out = {leaf_l: dict(st=st, swl=swl, ls=ls, ss=ss, sswl=sswl)}

    for l in range(n_levels - 1, 0, -1):
        n_up = parents[l - 1].shape[0]
        seg = parents[l]
        c = out[l]
        total = jax.ops.segment_sum(c["st"], seg, num_segments=n_up)
        slice_total = jax.ops.segment_sum(c["ss"], seg, num_segments=n_up)
        # leader contributors: children able to host the leader (or no
        # leader requested at all)
        contrib = ~has_leader | (c["ls"] > 0)
        any_contrib = jax.ops.segment_max(
            contrib.astype(jnp.int32), seg, num_segments=n_up) > 0
        state_diff = jnp.where(contrib, c["st"] - c["swl"], BIG)
        slice_diff = jnp.where(contrib, c["ss"] - c["sswl"], BIG)
        min_sd = jax.ops.segment_min(state_diff, seg, num_segments=n_up)
        min_ssd = jax.ops.segment_min(slice_diff, seg, num_segments=n_up)
        ls_up = jax.ops.segment_max(c["ls"], seg, num_segments=n_up)
        swl_up = jnp.where(any_contrib, total - min_sd, 0)
        sswl_up = jnp.where(any_contrib, slice_total - min_ssd, 0)
        at_sl = (l - 1) == slice_level
        ss_up = jnp.where(at_sl, total // jnp.maximum(slice_size, 1),
                          slice_total)
        sswl_up = jnp.where(at_sl, swl_up // jnp.maximum(slice_size, 1),
                            sswl_up)
        out[l - 1] = dict(st=total, swl=swl_up, ls=ls_up, ss=ss_up,
                          sswl=sswl_up)
    return out


def _unit_views(c, l, slice_level):
    """Unit-space (u_state, u_swl) at level l: slices at or above the
    slice level, pods below. The sort keys always use the slice arrays
    (zero below the slice level), mirroring _sorted/_sorted_with_leader
    keying on slice_state at every level."""
    in_slices = jnp.asarray(l, dtype=jnp.int32) <= slice_level
    u_state = jnp.where(in_slices, c["ss"], c["st"])
    u_swl = jnp.where(in_slices, c["sswl"], c["swl"])
    return u_state, u_swl


def _greedy_segment_lead(c, l, slice_level, seg, need_of_seg, lead_of_seg,
                         n_seg, least_free):
    """Per sibling group: route the (0/1) leader, then minimize domains
    (updateCountsToMinimumGeneric + consumeWithLeadersGeneric,
    tas_flavor_snapshot.go:1348-1469). ``need_of_seg`` is in the level's
    units. Returns (take [D] units, lead_take [D] bool).
    """
    u_state, u_swl = _unit_views(c, l, slice_level)
    ss_key = c["ss"]
    st_key = c["st"]
    ls = c["ls"]
    D = u_state.shape[0]
    idx = jnp.arange(D, dtype=jnp.int32)
    need = need_of_seg[seg]
    lead_here = lead_of_seg[seg]                      # [D] bool

    # ---- leader domain (sortedDomainsWithLeader order) ----------------
    # keys: (-leader_state, ±slice_swl, state_swl, idx); only segments
    # with a leader to place participate.
    sswl_key = jnp.where(least_free, c["sswl"], -c["sswl"])
    # lexicographic min via segment reductions
    k1 = -ls
    m1 = jax.ops.segment_min(jnp.where(lead_here, k1, BIG), seg,
                             num_segments=n_seg)
    c1 = lead_here & (k1 == m1[seg])
    m2 = jax.ops.segment_min(jnp.where(c1, sswl_key, BIG), seg,
                             num_segments=n_seg)
    c2 = c1 & (sswl_key == m2[seg])
    m3 = jax.ops.segment_min(jnp.where(c2, c["swl"], BIG), seg,
                             num_segments=n_seg)
    c3 = c2 & (c["swl"] == m3[seg])
    top_lead = jax.ops.segment_min(jnp.where(c3, idx, BIG), seg,
                                   num_segments=n_seg)  # [S]
    top_of = top_lead[seg]
    top_fits = (u_swl[jnp.minimum(top_of, D - 1)] >= need) & (
        ls[jnp.minimum(top_of, D - 1)] > 0)
    # best-fit swap (findBestFitDomainBy over u_swl) when the top fits
    # everything and we are not least-free
    elig_bf = lead_here & (ls > 0) & (u_swl >= need) & top_fits & (
        ~least_free)
    bf_min = jax.ops.segment_min(jnp.where(elig_bf, u_swl, BIG), seg,
                                 num_segments=n_seg)
    is_bf = elig_bf & (u_swl == bf_min[seg])
    bf_first = jax.ops.segment_min(jnp.where(is_bf, idx, BIG), seg,
                                   num_segments=n_seg)
    # least_free keeps the sorted-with-leader top (no best-fit swap)
    lead_dom = jnp.where(bf_first < BIG, bf_first, top_lead)  # [S]
    has_lead_dom = (lead_dom < BIG) & lead_of_seg & (
        jax.ops.segment_max(ls, seg, num_segments=n_seg) > 0)
    lead_dom_c = jnp.minimum(lead_dom, D - 1).astype(jnp.int32)
    is_lead = (idx == lead_dom_c[seg]) & has_lead_dom[seg]
    lead_take_units = jnp.where(is_lead, jnp.minimum(u_swl, need), 0)

    # ---- the rest: normal greedy on remaining need --------------------
    taken = jax.ops.segment_sum(lead_take_units, seg, num_segments=n_seg)
    rest_need = jnp.maximum(need_of_seg - taken, 0)
    state_rest = jnp.where(is_lead, 0, u_state)
    # ordering: (±slice_state, state, idx); leader domain excluded
    ss_sort = jnp.where(least_free, ss_key, -ss_key)
    key = jnp.where(is_lead, BIG, 0)
    order = jnp.lexsort((idx, st_key, ss_sort, key, seg))
    take_sorted = _consume_in_order(state_rest[order], seg[order],
                                    rest_need, n_seg, least_free)
    take = jnp.zeros_like(u_state).at[order].set(take_sorted)
    return take + lead_take_units, is_lead


def _consume_in_order(s_sorted, seg_sorted, need_of_seg, n_seg,
                      least_free):
    """updateCountsToMinimumGeneric on a pre-sorted domain sequence:
    take full domains until the remainder fits one, then best-fit the
    remainder (no-op refinement under least-free ascending order)."""
    D = s_sorted.shape[0]
    idx = jnp.arange(D, dtype=jnp.int32)
    need = need_of_seg[seg_sorted]
    csum = jnp.cumsum(s_sorted)
    is_start = jnp.concatenate([jnp.ones(1, dtype=bool),
                                seg_sorted[1:] != seg_sorted[:-1]])
    base = jnp.where(is_start, csum - s_sorted, 0)
    base = jax.lax.associative_scan(jnp.maximum,
                                    jnp.where(is_start, base, -1))
    prefix_excl = csum - s_sorted - base
    remaining = jnp.maximum(need - prefix_excl, 0)
    covers = (s_sorted >= remaining) & (remaining > 0)
    pos_cover = jnp.where(covers, idx, BIG)
    q = jax.ops.segment_min(pos_cover, seg_sorted, num_segments=n_seg)
    q_of = q[seg_sorted]
    full_take = jnp.where((idx < q_of) & (remaining > 0), s_sorted, 0)
    rem_at_q = jnp.where(idx == q_of, remaining, 0)
    rem_of_seg = jax.ops.segment_max(rem_at_q, seg_sorted,
                                     num_segments=n_seg)
    r = rem_of_seg[seg_sorted]
    elig = (idx >= q_of) & (s_sorted >= r) & (r > 0)
    s_min = jax.ops.segment_min(jnp.where(elig, s_sorted, BIG),
                                seg_sorted, num_segments=n_seg)
    is_best = elig & (s_sorted == s_min[seg_sorted])
    first_best = jax.ops.segment_min(jnp.where(is_best, idx, BIG),
                                     seg_sorted, num_segments=n_seg)
    bf_take = jnp.where(idx == first_best[seg_sorted], r, 0)
    return full_take + bf_take


# ---------------------------------------------------------------------------
# balanced placement (tas_balanced_placement.go; host: tas/snapshot.py
# _find_best_balanced / _apply_balanced), no slices, no leader
# ---------------------------------------------------------------------------

from kueue_oss_tpu.solver.tas_engine import (  # noqa: E402
    BALANCED_MAX_COUNT,
)

#: the entropy table's reach: a leaf that fits more pods than this is
#: left to the host tree (the row reads infeasible)
XLOG_MAX = 4095
_XLOG_LO_BITS = 20


def _xlog_tables():
    """``xlog2x_fixed(s)`` for s in 0..XLOG_MAX as two int32 words (the
    sums of 64 hosts pass 2**31 in one)."""
    from kueue_oss_tpu.tas.snapshot import xlog2x_fixed

    v = np.asarray([xlog2x_fixed(i) for i in range(XLOG_MAX + 1)],
                   dtype=np.int64)
    return ((v >> _XLOG_LO_BITS).astype(np.int32),
            (v & ((1 << _XLOG_LO_BITS) - 1)).astype(np.int32))


def _seg_prefix_excl(s_sorted, seg_sorted):
    """Exclusive running sum inside each run of equal ``seg_sorted``."""
    csum = jnp.cumsum(s_sorted)
    is_start = jnp.concatenate([jnp.ones(1, dtype=bool),
                                seg_sorted[1:] != seg_sorted[:-1]])
    base = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, csum - s_sorted, -1))
    return csum - s_sorted - base


def _greedy_eval(state, seg, n_seg, count):
    """evaluateGreedyAssignment per segment, no leader: domains in
    (state desc, lex) order until ``count`` is covered. Returns per
    segment (fits, domains used, the last used domain's state)."""
    idx = jnp.arange(state.shape[0], dtype=jnp.int32)
    order = jnp.lexsort((idx, -state, seg))
    s, sg = state[order], seg[order]
    taken = (_seg_prefix_excl(s, sg) < count) & (s > 0)
    used = jax.ops.segment_sum(taken.astype(jnp.int32), sg,
                               num_segments=n_seg)
    last = jax.ops.segment_min(jnp.where(taken, s, BIG), sg,
                               num_segments=n_seg)
    fits = jax.ops.segment_sum(state, seg, num_segments=n_seg) >= count
    return fits, used, last


def _first_found_subset(state, order_key, n, count, cap):
    """selectOptimalDomainSetToFit's DP, no leader: of the domains with
    ``state`` > 0, taken in ``order_key`` order, the subset of exactly
    ``n`` whose states cover ``count`` most tightly, and of those with
    one total the FIRST the host's loop stores
    (``placements[i].setdefault``). ``first[i][c]`` is the position of
    the domain whose turn first made (i chosen, c left) reachable; a
    key's subset never changes once stored, so the chosen set is read
    back along those positions. Returns (mask [T], found)."""
    T = state.shape[0]
    perm = jnp.argsort(order_key)
    s = state[perm]
    tpos = jnp.arange(T, dtype=jnp.int32)[:, None]
    caps = jnp.arange(cap + 1, dtype=jnp.int32)[None, :]
    inf = jnp.int32(T + 1)
    live = (s > 0)[:, None]
    src = caps + s[:, None]                 # the key an item extends
    src_ok = live & (src <= cap) & (caps >= 1)
    src_c = jnp.minimum(src, cap)
    table = jnp.full((cap + 1, cap + 1), inf, dtype=jnp.int32)
    table = table.at[0].set(jnp.where(caps[0] == count, -1, inf))

    def level(i, table):
        prev = table[i - 1]
        reach = src_ok & (prev[src_c] < tpos)
        return table.at[i].set(jnp.min(jnp.where(reach, tpos, inf),
                                       axis=0))

    table = jax.lax.fori_loop(1, n, level, table)
    # the n-th domain closes the set: a key <= 0, the largest there is
    prev = table[jnp.maximum(n - 1, 0)]
    key = caps - s[:, None]
    good = live & (caps >= 1) & (prev[None, :] < tpos) & (key <= 0)
    best = jnp.max(jnp.where(good, key, -BIG))
    t_n = jnp.min(jnp.where(good & (key == best), tpos, inf))
    found = jnp.any(good) & (n >= 1)
    t_n = jnp.minimum(t_n, T - 1)
    mask = jnp.zeros((T,), dtype=bool).at[t_n].set(True)

    def back(j, carry):
        mask, c = carry
        t = jnp.clip(table[n - 1 - j, jnp.clip(c, 0, cap)], 0, T - 1)
        return mask.at[t].set(True), c + s[t]

    mask, _ = jax.lax.fori_loop(0, n - 1, back, (mask, best + s[t_n]))
    return jnp.zeros((T,), dtype=bool).at[perm].set(mask) & found, found


def _balanced_distribute(state, mask, thr, count):
    """placeSlicesOnDomainsBalanced's loop: ``thr`` pods to every chosen
    domain, what is left front-first in (state desc, lex) order."""
    idx = jnp.arange(state.shape[0], dtype=jnp.int32)
    order = jnp.lexsort((idx, jnp.where(mask, -state, BIG)))
    s, m = state[order], mask[order]
    room = jnp.where(m, s - thr, 0)
    extra = count - jnp.sum(mask.astype(jnp.int32)) * thr
    take = jnp.clip(extra - (jnp.cumsum(room) - room), 0, room)
    ok = (extra >= 0) & (jnp.sum(room) >= extra)
    given = jnp.where(m, thr + take, 0)
    return jnp.zeros_like(state).at[order].set(given), ok


def _balanced_at_level(parents, ranks, xlog, states, count, l, cap):
    """Balanced placement of ``count`` pods requested at (static) level
    ``l``. ``states[k]`` is level k's fit count (no slices: pods).
    Returns (use, ok, sel per level): ``use`` where some sibling group
    holds the whole gang (threshold > 0: the host then places balanced
    or fails), ``sel`` zero but at the fit level (``l + 1``, or ``l``
    where that is the lowest)."""
    n_levels = len(parents)
    leaf = n_levels - 1
    A = states[l]
    idx_a = jnp.arange(A.shape[0], dtype=jnp.int32)
    if l == 0:
        grp_a = jnp.zeros_like(A)
        n_grp = 1
        grp_rank = jnp.zeros((1,), dtype=jnp.int32)
    else:
        grp_a = parents[l]
        n_grp = states[l - 1].shape[0]
        grp_rank = jnp.asarray(ranks[l - 1])
    low = l < leaf
    B = states[l + 1] if low else A
    par_b = parents[l + 1] if low else idx_a
    grp_b = grp_a[par_b]

    # findBestDomainsForBalancedPlacement, every sibling group at once
    fits, used, last = _greedy_eval(B, grp_b, n_grp, count)
    thr_g = jnp.where(fits, jnp.minimum(count // jnp.maximum(used, 1),
                                        last), 0)
    Bp = jnp.where(B >= thr_g[grp_b], B, 0)          # prune the children
    if low:
        Ap = jax.ops.segment_sum(Bp, par_b, num_segments=A.shape[0])
        Ap = jnp.where(Ap >= thr_g[grp_a], Ap, 0)    # then the domain
        Bp = jnp.where(Ap[par_b] > 0, Bp, 0)
    else:
        Ap = Bp
    ok_g, n_g, _ = _greedy_eval(Ap, grp_a, n_grp, count)
    valid = fits & ok_g & (thr_g > 0)
    top = jnp.max(jnp.where(valid, thr_g, 0))
    c1 = valid & (thr_g == top)
    c2 = c1 & (n_g == jnp.min(jnp.where(c1, n_g, BIG)))
    g = jnp.argmin(jnp.where(c2, grp_rank, BIG)).astype(jnp.int32)
    use = jnp.any(valid)
    thr, n_dom = thr_g[g], n_g[g]
    in_g = grp_a == g
    ok = use

    if low:
        # applyBalancedPlacementAlgorithm one level up: the optimal set
        # of domains by (capacity desc, entropy of the children desc)
        hi, lo = (jnp.asarray(t) for t in xlog)
        ok = ok & (jnp.max(jnp.where(in_g[par_b], Bp, 0)) <= XLOG_MAX)
        bc = jnp.minimum(Bp, XLOG_MAX)
        e_hi = jax.ops.segment_sum(hi[bc], par_b, num_segments=A.shape[0])
        e_lo = jax.ops.segment_sum(lo[bc], par_b, num_segments=A.shape[0])
        e_hi = e_hi + (e_lo >> _XLOG_LO_BITS)
        e_lo = e_lo & ((1 << _XLOG_LO_BITS) - 1)
        cand = jnp.where(in_g, Ap, 0)
        rank_a = jnp.asarray(ranks[l])
        order = jnp.lexsort((rank_a, e_lo, e_hi, -cand))
        pos = jnp.zeros_like(idx_a).at[order].set(idx_a)
        chosen, found = _first_found_subset(cand, pos, n_dom, count, cap)
        ok = ok & found
        items = jnp.where(chosen[par_b], Bp, 0)
        item_key = pos[par_b] * B.shape[0] + jnp.asarray(ranks[l + 1])
        fit_level = l + 1
    else:
        items = jnp.where(in_g, Ap, 0)
        item_key = jnp.asarray(ranks[l])
        fit_level = l
    # placeSlicesOnDomainsBalanced on the fit level's domains
    zero = jnp.zeros_like(items)
    fits2, n2, _ = _greedy_eval(items, zero, 1, count)
    mask, found2 = _first_found_subset(items, item_key, n2[0], count, cap)
    given, ok3 = _balanced_distribute(items, mask, thr, count)
    ok = ok & fits2[0] & found2 & ok3 & (count <= cap)
    sel = tuple(jnp.where(use & ok, given, 0) if k == fit_level
                else jnp.zeros_like(states[k]) for k in range(n_levels))
    return use, ok, sel



def make_placer_ext(parents_np: list[np.ndarray], ranks_np=None,
                    bal_cap: int = 0):
    """Jitted placer with slice + leader support for one tree shape.

    ``place(leaf_capacity, per_pod, count, requested_level, required,
    unconstrained, least_free, slice_size, slice_level, leader_per_pod,
    has_leader, balanced)`` returns (worker_leaf_sel [D_leaf] pods,
    leader_leaf int32 (-1 when none), feasible bool). Covers
    findTopologyAssignment for single-layer slices and a count-1 leader
    podset (tas_flavor_snapshot.go:804-999) and, built with the tree's
    ``ranks_np`` (``TASLevels.ranks``) and ``bal_cap`` > 0, the balanced
    placement of a row whose ``balanced`` flag is set (a preferred-level
    request while TASBalancedPlacement is on; no slice, no leader, at
    most ``bal_cap`` pods); nested slice layers stay on the host tree.
    """
    parents = [jnp.asarray(p) for p in parents_np]
    n_levels = len(parents)
    xlog = _xlog_tables() if bal_cap else None

    def balanced_seed(cs, count, requested_level, on):
        """(use, ok, sel per level) of the balanced placement, all
        zero where the row asks for none."""
        states = [cs[l]["st"] for l in range(n_levels)]
        off = (jnp.zeros((), dtype=bool), jnp.zeros((), dtype=bool),
               tuple(jnp.zeros_like(st) for st in states))
        if not bal_cap:
            return off

        def run(_):
            with jax.named_scope("tas_balanced"):
                return jax.lax.switch(
                    jnp.clip(requested_level, 0, n_levels - 1),
                    [lambda l=l: _balanced_at_level(
                        parents, ranks_np, xlog, states, count, l,
                        bal_cap) for l in range(n_levels)])

        return jax.lax.cond(on, run, lambda _: off, None)

    @jax.jit
    def place(leaf_capacity, per_pod, count, requested_level, required,
              unconstrained, least_free, slice_size, slice_level,
              leader_per_pod, has_leader, balanced=False):
        with jax.named_scope("tas_fill_counts"):
            cs = fill_counts_ext(parents, leaf_capacity, per_pod,
                                 leader_per_pod, has_leader, slice_size,
                                 slice_level)
        with jax.named_scope("tas_select_domains"):
            return select(cs, count, requested_level, required,
                          unconstrained, least_free, slice_size,
                          slice_level, has_leader, balanced)

    def select(cs, count, requested_level, required, unconstrained,
               least_free, slice_size, slice_level, has_leader, balanced):
        slice_count = count // jnp.maximum(slice_size, 1)
        use_bal, ok_bal, sel_bal = balanced_seed(
            cs, count, requested_level,
            jnp.asarray(balanced) & ~required & ~unconstrained
            & ~has_leader & (slice_size == 1)
            & (slice_level == n_levels - 1))

        def units_at(l):
            # placement units at level l (need conversions cross SL)
            return jnp.where(jnp.asarray(l, jnp.int32) <= slice_level,
                             slice_count, count)

        # ---- findLevelWithFitDomains at the requested level, walking
        # up for preferred requests ------------------------------------
        chosen_level = jnp.asarray(-1, dtype=jnp.int32)
        chosen_dom = jnp.asarray(0, dtype=jnp.int32)
        for l in range(n_levels - 1, -1, -1):
            c = cs[l]
            u_state, u_swl = _unit_views(c, l, slice_level)
            nd = units_at(l)
            ok_lead = (c["ls"] > 0) | ~has_leader
            # least-free (host: first sorted domain with slice_state >=
            # need) still must hold the leader when one exists — the
            # host's own least-free walk skips that check only because
            # mixed-profile unconstrained podsets never carry leaders;
            # without it the sequential drain's capacity carry would go
            # negative on the leader row
            fits = jnp.where(least_free & ~has_leader, u_state >= nd,
                             (u_swl >= nd) & ok_lead)
            # least-free: first in (-ls, sswl, swl, idx) order with
            # slice_state >= need; normal: best-fit by u_swl
            key_lf = jnp.where(fits, jnp.arange(u_state.shape[0]), BIG)
            key_bf = jnp.where(fits, u_swl, BIG)
            d_lf = jnp.argmin(key_lf).astype(jnp.int32)
            d_bf = jnp.argmin(key_bf).astype(jnp.int32)
            d = jnp.where(least_free, d_lf, d_bf)
            okl = jnp.any(fits)
            allowed = jnp.where(
                required | unconstrained, l == requested_level,
                l <= requested_level)
            hit = okl & allowed & (chosen_level < 0) & (
                l <= requested_level)
            chosen_level = jnp.where(hit, l, chosen_level)
            chosen_dom = jnp.where(hit & (chosen_level == l), d,
                                   chosen_dom)
        single_fit = chosen_level >= 0

        # ---- seed: single domain, or greedy multi-domain -------------
        sel = [jnp.zeros_like(cs[l]["st"]) for l in range(n_levels)]
        lead = [jnp.zeros(cs[l]["st"].shape, dtype=bool)
                for l in range(n_levels)]
        feasible = jnp.zeros((), dtype=bool)
        greedy_level = jnp.where(unconstrained, requested_level, 0)
        for l in range(n_levels):
            c = cs[l]
            is_single = single_fit & (chosen_level == l)
            one_hot = (jnp.arange(c["st"].shape[0],
                                  dtype=jnp.int32) == chosen_dom)
            seed_single = jnp.where(one_hot, units_at(l), 0)
            seed_lead = one_hot & has_leader
            seg = jnp.zeros_like(c["st"])
            g, gl = _greedy_segment_lead(
                c, l, slice_level, seg,
                jnp.full((1,), units_at(l), dtype=c["st"].dtype),
                jnp.full((1,), True) & has_leader, 1, least_free)
            u_state, u_swl = _unit_views(c, l, slice_level)
            cap_ok = jnp.where(
                has_leader,
                (jnp.sum(jnp.where(gl, u_swl, u_state)) >= units_at(l))
                & (jnp.any(gl) | ~has_leader),
                jnp.sum(u_state) >= units_at(l))
            use_greedy = (~single_fit) & (greedy_level == l) & ~required
            sel[l] = jnp.where(is_single, seed_single,
                               jnp.where(use_greedy & cap_ok, g, sel[l]))
            lead[l] = jnp.where(is_single, seed_lead & has_leader,
                                jnp.where(use_greedy & cap_ok,
                                          gl & has_leader, lead[l]))
            feasible = feasible | is_single | (use_greedy & cap_ok)
        start = jnp.where(single_fit, chosen_level, greedy_level)
        # balanced placement seeds its own fit level: one below the
        # requested one, or the requested one where that is the lowest
        for l in range(n_levels):
            sel[l] = jnp.where(use_bal, sel_bal[l], sel[l])
            lead[l] = lead[l] & ~use_bal
        feasible = jnp.where(use_bal, ok_bal, feasible)
        start = jnp.where(
            use_bal, jnp.minimum(requested_level + 1, n_levels - 1), start)

        # ---- descend --------------------------------------------------
        for l in range(n_levels - 1):
            par = parents[l + 1]
            n_par = cs[l]["st"].shape[0]
            # need conversion when crossing the slice level: parents at
            # or above SL hold slices, children below hold pods
            below_sl = jnp.asarray(l + 1, jnp.int32) > slice_level
            need_par = jnp.where(
                below_sl & (jnp.asarray(l, jnp.int32) <= slice_level),
                sel[l] * jnp.maximum(slice_size, 1), sel[l])
            computed, comp_lead = _greedy_segment_lead(
                cs[l + 1], l + 1, slice_level, par, need_par, lead[l],
                n_par, least_free)
            # down to the slice level the host pools the children of
            # ALL chosen domains and places the whole count on them
            # anew (findTopologyAssignment's first descent); a balanced
            # placement and the levels below the slices go parent by
            # parent
            chosen = ((sel[l] > 0) | lead[l])[par]
            pool = {k: jnp.where(chosen, v, 0)
                    for k, v in cs[l + 1].items()}
            pooled, pooled_lead = _greedy_segment_lead(
                pool, l + 1, slice_level, jnp.zeros_like(par),
                jnp.full((1,), units_at(l + 1), dtype=sel[l].dtype),
                jnp.full((1,), True) & has_leader, 1, least_free)
            in_pool = ~below_sl & ~use_bal
            computed = jnp.where(in_pool, pooled, computed)
            comp_lead = jnp.where(in_pool, pooled_lead, comp_lead)
            keep = jnp.asarray(l + 1) <= start
            sel[l + 1] = jnp.where(keep, sel[l + 1], computed)
            lead[l + 1] = jnp.where(keep, lead[l + 1], comp_lead)

        leaf = n_levels - 1
        # leaf units -> pods
        leaf_pods = jnp.where(
            jnp.asarray(leaf, jnp.int32) <= slice_level,
            sel[leaf] * jnp.maximum(slice_size, 1), sel[leaf])
        total_ok = jnp.sum(leaf_pods) == count
        feasible = feasible & total_ok & (
            ~has_leader | jnp.any(lead[leaf]))
        leader_leaf = jnp.where(
            has_leader & feasible,
            jnp.argmax(lead[leaf]).astype(jnp.int32), -1)
        return leaf_pods, leader_leaf, feasible

    return place


_placer_cache: dict = {}


def place_podset(snapshot, per_pod: dict, count: int,
                 requested_level_idx: int, required: bool = False,
                 unconstrained: bool = False):
    """Host wrapper: flatten the tree, run the kernel, decode leaves.
    Returns {leaf domain id: count} or None when infeasible."""
    levels = build_levels(snapshot)
    key = tuple(tuple(p.tolist()) for p in levels.parents)
    placer = _placer_cache.get(key)
    if placer is None:
        placer = make_placer(levels.parents)
        _placer_cache[key] = placer
    req = np.zeros(max(1, len(levels.resources)), dtype=np.int32)
    for j, r in enumerate(levels.resources):
        req[j] = per_pod.get(r, 0)
    least_free = unconstrained and getattr(snapshot, "profile_mixed", False)
    leaf_sel, feasible = placer(
        jnp.asarray(levels.leaf_capacity), jnp.asarray(req),
        jnp.asarray(count, dtype=jnp.int32),
        jnp.asarray(requested_level_idx, dtype=jnp.int32),
        jnp.asarray(required), jnp.asarray(unconstrained),
        jnp.asarray(least_free))
    if not bool(feasible):
        return None
    leaf_sel = np.asarray(leaf_sel)
    return {levels.leaf_names[i]: int(leaf_sel[i])
            for i in range(len(levels.leaf_names)) if leaf_sel[i] > 0}


_placer_ext_cache: dict = {}
_sequential_ext_cache: dict = {}


def tree_key(levels: TASLevels, bal_cap: int) -> tuple:
    """What a placer is compiled for: the FULL parent structure (the
    placer bakes parents in at trace time, so any relabeled domain must
    miss the cache) and, for the balanced placement, the host tree's
    own order of every level."""
    return (tuple(np.asarray(p, dtype=np.int32).tobytes()
                  for p in levels.parents),
            tuple(np.asarray(r, dtype=np.int32).tobytes()
                  for r in levels.ranks) if bal_cap else (), bal_cap)


def sequential_placer_for(levels: TASLevels, bal_cap: int = 0):
    """The process's sequential placer of one tree: one jitted function
    for every engine of the process, so a program it has traced for a
    batch size is traced once (a benchmark's twin deployment and its
    cell share it). Returns (placer, its tree key, built now)."""
    key = tree_key(levels, bal_cap)
    placer = _sequential_ext_cache.get(key)
    if placer is not None:
        return placer, key, False
    placer = make_sequential_placer_ext(levels.parents, levels.ranks,
                                        bal_cap)
    _sequential_ext_cache[key] = placer
    return placer, key, True


def place_podset_ext(snapshot, per_pod: dict, count: int,
                     requested_level_idx: int, required: bool = False,
                     unconstrained: bool = False, slice_size: int = 1,
                     slice_level_idx: int | None = None,
                     leader_per_pod: dict | None = None,
                     balanced: bool = False):
    """Host wrapper for the slice/leader-capable placer.

    Returns (worker {leaf id: pods}, leader leaf id or None) or None
    when infeasible. Single slice layer + count-1 leader podset, and
    with ``balanced`` the balanced placement of a preferred-level
    request (no slice, no leader, up to BALANCED_MAX_COUNT pods); nested
    slice layers stay on the host tree
    (tas_flavor_snapshot.go:804-999 scope notes in make_placer_ext).
    """
    levels = build_levels(snapshot)
    bal_cap = BALANCED_MAX_COUNT if balanced else 0
    key = tree_key(levels, bal_cap)
    placer = _placer_ext_cache.get(key)
    if placer is None:
        placer = make_placer_ext(levels.parents, levels.ranks, bal_cap)
        _placer_ext_cache[key] = placer
    R = max(1, len(levels.resources))
    req = np.zeros(R, dtype=np.int32)
    for j, r in enumerate(levels.resources):
        req[j] = per_pod.get(r, 0)
    lead = np.zeros(R, dtype=np.int32)
    has_leader = leader_per_pod is not None
    if has_leader:
        for j, r in enumerate(levels.resources):
            lead[j] = leader_per_pod.get(r, 0)
    if slice_level_idx is None:
        slice_level_idx = len(levels.parents) - 1
    if count % max(slice_size, 1) != 0:
        return None
    least_free = unconstrained and getattr(snapshot, "profile_mixed", False)
    worker_sel, leader_leaf, feasible = placer(
        jnp.asarray(levels.leaf_capacity), jnp.asarray(req),
        jnp.asarray(count, dtype=jnp.int32),
        jnp.asarray(requested_level_idx, dtype=jnp.int32),
        jnp.asarray(required), jnp.asarray(unconstrained),
        jnp.asarray(least_free),
        jnp.asarray(max(slice_size, 1), dtype=jnp.int32),
        jnp.asarray(slice_level_idx, dtype=jnp.int32),
        jnp.asarray(lead), jnp.asarray(has_leader),
        jnp.asarray(balanced))
    if not bool(feasible):
        return None
    worker_sel = np.asarray(worker_sel)
    workers = {levels.leaf_names[i]: int(worker_sel[i])
               for i in range(len(levels.leaf_names)) if worker_sel[i] > 0}
    leader = (levels.leaf_names[int(leader_leaf)]
              if has_leader and int(leader_leaf) >= 0 else None)
    return workers, leader
