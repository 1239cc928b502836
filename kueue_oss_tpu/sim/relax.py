"""The what-if simulator's declared-approximate tier: a relaxed LP.

Scenarios the lane budget cannot run through the FULL kernel
(sim/batch.plan_full_sweep) are answered by ``solve_scenarios_relax``
with rows labelled ``tier="relax"`` (docs/SIMULATOR.md). This module is
that tier's arithmetic, and the simulator is its only user: nothing on
the served path (solver/, scheduler/) imports it, so "an approximate
answer is allowed" is a decision that lives in sim/ alone. After
CvxCluster (arXiv 2605.01614): large granular allocation problems admit
convex relaxations solved as dense matrix iterations.

1. **Relaxation** — the admission LP over a fractional admit vector
   x ∈ [0, 1]^W maximizing priority-weighted admission subject to one
   capacity row per (hierarchy node, flavor-resource):

       max  Σ_w s_w x_w
       s.t. Σ_{w under n}  req_w,f · x_w  ≤  slack_n,f      ∀ (n, f)

   ``slack`` is the node's aggregate headroom: subtree quota plus its
   borrowing allowance, minus the full-charge total of current CQ
   usage. Solved by fixed-iteration projected gradient ascent on a
   quadratic penalty (pure ``jax.numpy``: one fori_loop of segment-sum
   + ancestor-accumulate + clip per iteration, vmapped over scenarios).

2. **Rounding** — deterministic support selection on the host: rows
   with x above the threshold, per-CQ slack rows by relaxed score
   (ties broken by FIFO rank, so symmetric contention rounds to the
   exact kernel's FIFO prefix), every live row of StrictFIFO CQs
   (their heads may never be skipped), and a per-CQ allowance sized by
   the CQ's fractional mass so the repair pass can fill capacity the
   threshold underestimated.

3. **Repair** — the EXACT lean kernel, run on the support rows
   compacted into a small padded subproblem (same node/CQ tensors,
   gathered workload rows). Whatever it admits is exactly feasible by
   construction; results scatter back to full workload indices. Rows
   outside the support park (BestEffortFIFO) exactly like the exact
   kernel's quiescent state; StrictFIFO rows never park.

The plan is therefore ALWAYS exactly feasible — approximation error
can only show up as a different admitted set, never as overcommitted
quota.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import numpy as np

from kueue_oss_tpu.solver.tensors import SolverProblem, pow2

#: projected-gradient constants: step size, score (objective) weight,
#: and the quadratic-penalty ramp rho0 * (1 + growth * i / iters). The
#: LP only has to CONCENTRATE mass and ORDER candidates — the repair
#: pass is exact — so these favor robustness over last-digit optimality.
ETA = 0.5
ALPHA = 0.05
RHO0 = 1.0
RHO_GROWTH = 3.0

#: effectively-unbounded capacity for constraint rows that only bind at
#: an ancestor (non-root nodes without a borrowing limit)
UNBOUNDED = np.float32(1 << 30)


class RelaxLP(NamedTuple):
    """Device inputs of the relaxed admission LP (jit pytree)."""

    r: np.ndarray        # [W+1, F] float32 request under the first valid option
    s: np.ndarray        # [W+1] float32 priority-major, FIFO-minor score
    live: np.ndarray     # [W+1] bool
    wl_cqid: np.ndarray  # [W+1] int32
    cq_node: np.ndarray  # [C] int32
    path_cq: np.ndarray  # [C, D] int32 ancestor chain of each CQ's node
    parent: np.ndarray   # [N+1] int32
    depth: np.ndarray    # [N+1] int32
    slack: np.ndarray    # [N+1, F] float32 aggregate headroom per node
    scale: np.ndarray    # [N+1, F] float32 max(slack, 1) normalizer


def lp_step_body(lp: RelaxLP, x, i, iters: int):
    """One projected-gradient iteration."""
    import jax
    import jax.numpy as jnp

    from kueue_oss_tpu.solver.kernels import accumulate_full_charge

    C = lp.cq_node.shape[0]
    N1 = lp.parent.shape[0]
    F = lp.r.shape[1]
    d_max = lp.path_cq.shape[1]
    load_cq = jax.ops.segment_sum(lp.r * x[:, None], lp.wl_cqid,
                                  num_segments=C + 1)[:C]
    u = jnp.zeros((N1, F), lp.r.dtype).at[lp.cq_node].add(load_cq)
    u = accumulate_full_charge(lp.parent, lp.depth, u, d_max)
    # RELATIVE violation, clipped: scale-invariant pricing. Normalizing
    # by scale**2 (the literal quadratic-penalty gradient) crushes the
    # price on large-capacity rows (a cohort with slack ~10^3 would
    # price a 5x oversubscription below the score term) — relative
    # overflow prices a 2x-oversubscribed 8-cpu CQ and a 2x
    # oversubscribed 2000-cpu cohort identically.
    over = jnp.clip((u - lp.slack) / lp.scale, 0.0, 1.0)
    price = over[lp.path_cq].sum(axis=1)              # [C, F]
    rho = RHO0 * (1.0 + RHO_GROWTH * i / iters)
    # per-row request normalized by its own largest component, so the
    # downstep stays O(rho) for any request magnitude (no overshoot
    # for 100-unit rows, no stall for 1-unit rows)
    rnorm = lp.r / jnp.maximum(lp.r.max(axis=1, keepdims=True), 1.0)
    g = ALPHA * lp.s - rho * (rnorm * price[lp.wl_cqid]).sum(axis=1)
    x = jnp.clip(x + ETA * g, 0.0, 1.0)
    return jnp.where(lp.live, x, 0.0)


def lp_loop(lp: RelaxLP, iters: int):
    """The full fixed-iteration LP solve (trace-time body)."""
    import jax
    import jax.numpy as jnp

    x0 = jnp.where(lp.live, jnp.float32(0.5), jnp.float32(0.0))
    return jax.lax.fori_loop(
        0, iters,
        lambda i, x: lp_step_body(lp, x, i, iters), x0)


# ---------------------------------------------------------------------------
# LP assembly (host)
# ---------------------------------------------------------------------------


def _full_charge_np(parent: np.ndarray, depth: np.ndarray,
                    values: np.ndarray, d_max: int) -> np.ndarray:
    """Numpy twin of kernels.accumulate_full_charge for the
    per-scenario constant headroom tensors."""
    u = values.copy()
    for d in range(d_max - 1, 0, -1):
        rows = depth == d
        np.add.at(u, parent[rows], u[rows])
    return u


def build_lp(problem: SolverProblem) -> RelaxLP:
    """Assemble the LP tensors from a (padded) lean export."""
    C = problem.n_cqs
    W1 = problem.wl_cqid.shape[0]
    cqid = np.asarray(problem.wl_cqid)
    valid = np.asarray(problem.wl_valid)
    live = np.zeros(W1, dtype=bool)
    live[:-1] = (cqid[:-1] < C) & valid[:-1].any(axis=1)

    # request under the FIRST valid flavor option; the repair pass
    # re-runs the exact fungibility policy, so the relaxation only
    # needs one representative request vector per row
    k0 = np.argmax(valid, axis=1).astype(np.int64)
    r = np.asarray(problem.wl_req)[np.arange(W1), k0].astype(np.float32)
    r[~live] = 0.0

    prio = np.asarray(problem.wl_prio).astype(np.float32)
    ts = np.asarray(problem.wl_ts).astype(np.float32)
    p_lo = float(prio[live].min()) if live.any() else 0.0
    p_hi = float(prio[live].max()) if live.any() else 0.0
    t_hi = float(ts[live].max()) if live.any() else 0.0
    s = ((prio - p_lo) / max(1.0, p_hi - p_lo)
         + 0.25 * (1.0 - ts / max(1.0, t_hi))).astype(np.float32)
    s[~live] = 0.0

    # capacity rows: subtree quota + borrowing allowance (non-root
    # nodes without a limit only bind at their ancestors), minus the
    # full-charge total of current CQ usage under the node
    subtree = np.asarray(problem.subtree).astype(np.float32)
    extra = np.where(
        np.asarray(problem.has_borrow),
        np.asarray(problem.borrow_limit).astype(np.float32),
        np.where(np.asarray(problem.has_parent)[:, None],
                 UNBOUNDED, np.float32(0.0)))
    cap = np.minimum(subtree + extra, UNBOUNDED)
    is_cq = np.zeros(problem.parent.shape[0], dtype=bool)
    is_cq[problem.cq_node] = True
    usage_cq = np.where(is_cq[:, None],
                        np.asarray(problem.usage0), 0).astype(np.float32)
    d_max = problem.path.shape[1]
    base = _full_charge_np(np.asarray(problem.parent),
                           np.asarray(problem.depth), usage_cq, d_max)
    slack = np.maximum(cap - base, 0.0).astype(np.float32)
    scale = np.maximum(slack, 1.0).astype(np.float32)

    return RelaxLP(
        r=r, s=s, live=live, wl_cqid=cqid.astype(np.int32),
        cq_node=np.asarray(problem.cq_node).astype(np.int32),
        path_cq=np.asarray(problem.path)[problem.cq_node].astype(np.int32),
        parent=np.asarray(problem.parent).astype(np.int32),
        depth=np.asarray(problem.depth).astype(np.int32),
        slack=slack, scale=scale)


# ---------------------------------------------------------------------------
# Rounding: deterministic support selection (host)
# ---------------------------------------------------------------------------


def strict_rows(problem: SolverProblem) -> np.ndarray:
    """[W+1] mask of rows whose CQ is StrictFIFO — the ONE definition
    of the strict-semantics rule both the rounding (strict rows always
    join the support) and the plan assembly (strict rows never park)
    share."""
    cq = np.asarray(problem.wl_cqid)
    strict = np.zeros(cq.shape[0], dtype=bool)
    m = cq < problem.n_cqs
    strict[m] = np.asarray(problem.cq_strict)[cq[m]].astype(bool)
    return strict


def rounded_support(x: np.ndarray, problem: SolverProblem,
                    live: np.ndarray, threshold: float = 0.5,
                    slack_frac: float = 0.25,
                    slack_min: int = 4) -> np.ndarray:
    """Boolean support mask over the real workload rows [W].

    Selected: live rows with x >= threshold; every live StrictFIFO row
    (a strict head must never be skipped — admitting past it would
    diverge from the reference's blocking semantics); and per CQ, extra
    rows by (-x, FIFO rank) up to an allowance of
    ``slack_min + ceil(slack_frac * selected + unselected fractional
    mass)`` — the mass term sizes the allowance to the capacity the LP
    thinks is still fillable, so a diffuse symmetric solution still
    rounds to the exact kernel's FIFO prefix.
    """
    C = problem.n_cqs
    W = problem.wl_cqid.shape[0] - 1
    cq = np.asarray(problem.wl_cqid)[:W]
    livew = np.asarray(live)[:W]
    xw = np.asarray(x)[:W]
    sel = livew & ((xw >= threshold) | strict_rows(problem)[:W])
    cand = np.nonzero(livew & ~sel)[0]
    if cand.size:
        rank = np.asarray(problem.wl_rank)[:W]
        order = cand[np.lexsort((rank[cand], -xw[cand], cq[cand]))]
        cqs = cq[order]
        starts = np.r_[True, cqs[1:] != cqs[:-1]]
        idx = np.arange(order.size)
        gi = idx - np.maximum.accumulate(np.where(starts, idx, 0))
        n_sel = np.bincount(cq[sel], minlength=C + 1)
        mass = np.bincount(cq[cand], weights=xw[cand], minlength=C + 1)
        allow = (slack_min
                 + np.ceil(slack_frac * n_sel + mass)).astype(np.int64)
        sel[order[gi < allow[cqs]]] = True
    return sel


# ---------------------------------------------------------------------------
# Repair: the exact lean kernel on the compacted support
# ---------------------------------------------------------------------------


def restrict_problem(problem: SolverProblem, sel_idx: np.ndarray,
                     target_w: int) -> SolverProblem:
    """Compact a padded lean problem to the support rows (+ inert null
    fills up to ``target_w`` and the trailing null row). Node/CQ
    tensors are untouched; per-CQ FIFO rank ORDER is preserved because
    the gather keeps ascending row order and ranks ride along."""
    W1 = problem.wl_cqid.shape[0]
    rows = np.concatenate([
        np.asarray(sel_idx, dtype=np.int64),
        np.full(target_w + 1 - len(sel_idx), W1 - 1, dtype=np.int64),
    ])
    return dataclasses.replace(
        problem,
        wl_cqid=np.ascontiguousarray(problem.wl_cqid[rows]),
        wl_rank=np.ascontiguousarray(problem.wl_rank[rows]),
        wl_prio=np.ascontiguousarray(problem.wl_prio[rows]),
        wl_ts=np.ascontiguousarray(problem.wl_ts[rows]),
        wl_uid=np.ascontiguousarray(problem.wl_uid[rows]),
        wl_req=np.ascontiguousarray(problem.wl_req[rows]),
        wl_valid=np.ascontiguousarray(problem.wl_valid[rows]),
    )


def repair(problem: SolverProblem, sel: np.ndarray,
           live: np.ndarray) -> tuple:
    """Run the exact lean kernel on the rounded support and scatter the
    plan back to full workload indices.

    Returns the full ``solve_backlog`` contract — (admitted, opt,
    admit_round, parked, rounds, usage), numpy, [W+1]-shaped.
    """
    from kueue_oss_tpu.solver.kernels import solve_backlog, to_device

    W1 = problem.wl_cqid.shape[0]
    sel_idx = np.nonzero(sel)[0]
    S = len(sel_idx)
    sub = restrict_problem(problem, sel_idx, pow2(S + 1) - 1)
    adm_s, opt_s, round_s, _, rounds, usage = (
        np.asarray(a) for a in solve_backlog(to_device(sub)))

    admitted = np.zeros(W1, dtype=bool)
    opt = np.zeros(W1, dtype=np.int32)
    admit_round = np.zeros(W1, dtype=np.int32)
    admitted[sel_idx] = adm_s[:S].astype(bool)
    opt[sel_idx] = opt_s[:S]
    admit_round[sel_idx] = np.where(adm_s[:S].astype(bool),
                                    round_s[:S], 0)
    # rows the plan leaves unadmitted park exactly like the exact
    # kernel's quiescent state: every live BestEffortFIFO row; never a
    # StrictFIFO row (their heads block in place)
    parked = (np.asarray(live, dtype=bool) & ~admitted
              & ~strict_rows(problem))
    parked[-1] = False
    admitted[-1] = False
    return admitted, opt, admit_round, parked, rounds, usage
