"""The what-if simulator's declared-approximate tier (sim/relax.py,
sim/batch.solve_scenarios_relax; docs/SIMULATOR.md).

The tier answers a scenario with a relaxed LP, a deterministic
rounding to a support, and the EXACT lean kernel on that support. Each
case runs the tier with one scenario (``solve_scenarios_relax(problem,
[{}])``) and compares its plan row with the exact lean kernel's:

1. exact feasibility — every plan row is a lean-kernel plan over the
   rounded support and passes the engine's plan guard, whatever the LP
   did;
2. rounding-and-repair parity (randomized property) — the row is
   BIT-IDENTICAL to independently running the exact lean kernel on the
   compacted support problem and scattering the results back;
3. symmetric contention rounds to the exact kernel's FIFO prefix (the
   support's rank tie-break);
4. StrictFIFO rows are always in the support and never park.
"""

import functools

import jax
import numpy as np
import pytest

from kueue_oss_tpu.api.types import (
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_oss_tpu.core.queue_manager import QueueManager
from kueue_oss_tpu.core.store import Store
from kueue_oss_tpu.sim import relax
from kueue_oss_tpu.sim.batch import RELAX_TIER, solve_scenarios_relax
from kueue_oss_tpu.solver.engine import SolverEngine
from kueue_oss_tpu.solver.kernels import solve_backlog, to_device
from kueue_oss_tpu.solver.tensors import pad_workloads, pow2

pytestmark = pytest.mark.sim


def _store(n_cqs=4, quota=8, strict=()):
    store = Store()
    store.upsert_resource_flavor(ResourceFlavor(name="f"))
    for i in range(n_cqs):
        store.upsert_cluster_queue(ClusterQueue(
            name=f"cq{i}",
            queueing_strategy=("StrictFIFO" if i in strict
                               else "BestEffortFIFO"),
            resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[FlavorQuotas(name="f", resources=[
                    ResourceQuota(name="cpu", nominal=quota)])])]))
        store.upsert_local_queue(LocalQueue(
            name=f"lq{i}", cluster_queue=f"cq{i}"))
    return store


def _add(store, i, cpu=1, prio=0, n_cqs=4):
    store.add_workload(Workload(
        name=f"w{i}", queue_name=f"lq{i % n_cqs}", uid=i + 1,
        priority=prio, creation_time=float(i),
        podsets=[PodSet(name="main", count=1, requests={"cpu": cpu})]))


def _padded_problem(store):
    engine = SolverEngine(store, QueueManager(store))
    problem, _ = engine.export()
    return pad_workloads(problem, pow2(problem.n_workloads))


def _exact(problem):
    return tuple(np.asarray(a) for a in solve_backlog(to_device(problem)))


def _tier_row(problem):
    """The tier's plan for the one unmodified scenario, in the lean
    kernel's tuple order (admitted, opt, admit_round, parked, rounds)."""
    res = solve_scenarios_relax(problem, [{}])
    assert res.tier == [RELAX_TIER]
    return (res.admitted[0], res.opt[0], res.admit_round[0],
            res.parked[0], res.rounds[0])


def _agree(row, exact, n_workloads):
    """Same admitted set, same parked set, same flavor option per
    admitted row. Round numbers are not compared: the repair runs over
    a compacted axis, so its numbering differs while the decisions do
    not."""
    W = n_workloads
    adm = row[0][:W].astype(bool)
    return (np.array_equal(adm, exact[0][:W].astype(bool))
            and np.array_equal(row[3][:W].astype(bool),
                               exact[3][:W].astype(bool))
            and np.array_equal(row[1][:W][adm], exact[1][:W][adm]))


def _plan_fault(problem, row):
    return SolverEngine._plan_fault(
        problem, row[0], row[1], row[2], row[3], None, row[4], False)


def test_symmetric_contention_matches_exact_and_passes_guard():
    """Uniform contended FIFO backlog: the tier's plan must equal the
    exact kernel's (the support's rank tie-break rounds a symmetric
    fractional solution to the FIFO prefix) and pass the plan guard."""
    store = _store(n_cqs=4, quota=8)
    for i in range(64):
        _add(store, i)
    problem = _padded_problem(store)
    row = _tier_row(problem)
    assert _agree(row, _exact(problem), problem.n_workloads)
    assert _plan_fault(problem, row) is None


def test_priority_ordering_survives_relaxation():
    """High-priority rows must win the contended seats, exactly like
    the exact kernel (the LP's score term orders the support)."""
    store = _store(n_cqs=1, quota=4)
    for i in range(16):
        _add(store, i, prio=(2 if i >= 12 else 0), n_cqs=1)
    problem = _padded_problem(store)
    row = _tier_row(problem)
    assert _agree(row, _exact(problem), problem.n_workloads)
    admitted = np.nonzero(row[0][:problem.n_workloads])[0]
    # all four priority-2 workloads (w12..w15) hold the four seats
    names = {problem.wl_keys[w].rsplit("/", 1)[-1] for w in admitted}
    assert names == {"w12", "w13", "w14", "w15"}


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_repair_is_bit_identical_to_lean_kernel_on_support(seed):
    """Randomized property: the tier's row == the exact lean kernel run
    on restrict_problem(rounded support), scattered back. The emitted
    plan IS a lean-kernel plan — approximation can only pick the
    support, never bend feasibility."""
    rng = np.random.default_rng(seed)
    n_cqs = int(rng.integers(2, 6))
    store = _store(n_cqs=n_cqs, quota=int(rng.integers(3, 12)))
    for i in range(int(rng.integers(24, 72))):
        _add(store, i, cpu=int(rng.integers(1, 4)),
             prio=int(rng.integers(0, 3)), n_cqs=n_cqs)
    problem = _padded_problem(store)
    row = _tier_row(problem)

    # independent reconstruction from the same fractional solution:
    # the tier's own program (vmapped LP, one scenario)
    lp = relax.build_lp(problem)
    stacked = relax.RelaxLP(*[np.asarray(f)[None] for f in lp])
    x = np.asarray(jax.jit(jax.vmap(functools.partial(
        relax.lp_loop, iters=32)))(stacked))[0]
    sel = relax.rounded_support(x, problem, lp.live)
    sel_idx = np.nonzero(sel)[0]
    assert 0 < len(sel_idx) <= int(lp.live.sum())
    sub = relax.restrict_problem(problem, sel_idx,
                                 pow2(len(sel_idx) + 1) - 1)
    ref = _exact(sub)
    W1 = problem.wl_cqid.shape[0]
    adm = np.zeros(W1, dtype=bool)
    adm[sel_idx] = ref[0][:len(sel_idx)].astype(bool)
    assert np.array_equal(row[0], adm)
    opt = np.zeros(W1, dtype=np.int32)
    opt[sel_idx] = ref[1][:len(sel_idx)]
    assert np.array_equal(row[1][adm], opt[adm])
    assert int(row[4]) == int(ref[4])
    # feasibility guard holds for every seed
    assert _plan_fault(problem, row) is None
    # parked is exactly: live, unadmitted, BestEffortFIFO
    assert not (row[3] & row[0]).any()
    assert not row[3][~np.asarray(lp.live)].any()


def test_strict_fifo_rows_ride_the_support_and_never_park():
    """StrictFIFO heads block in place: every live strict row joins the
    support, none parks, and the plan equals the exact kernel's."""
    store = _store(n_cqs=2, quota=4, strict=(0,))
    # strict cq0's head does NOT fit; followers must stay blocked
    _add(store, 0, cpu=6, n_cqs=2)
    for i in range(2, 20):
        _add(store, i, cpu=1, n_cqs=2)
    problem = _padded_problem(store)
    row = _tier_row(problem)
    assert _agree(row, _exact(problem), problem.n_workloads)
    W = problem.n_workloads
    strict = relax.strict_rows(problem)[:W]
    assert strict.any()
    assert not row[3][:W][strict].any()
    # the blocked strict queue admitted nothing past its stuck head
    assert not row[0][:W][strict].any()


def test_zero_backlog_cq_and_empty_support_are_inert():
    """A CQ with zero quota parks everything (BestEffortFIFO) without
    faulting the guard, matching the exact kernel."""
    store = _store(n_cqs=2, quota=0)
    for i in range(12):
        _add(store, i, n_cqs=2)
    problem = _padded_problem(store)
    row = _tier_row(problem)
    assert _agree(row, _exact(problem), problem.n_workloads)
    assert int(row[0].sum()) == 0
    assert int(row[3][:problem.n_workloads].sum()) == 12
