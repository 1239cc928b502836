"""The candidate cap of the preemption program (SolverEngine._size_caps).

``p_max`` is the width of the candidate axis: a row of
``build_candidate_table`` past it is DROPPED, so the cap has to cover
every cohort tree's admitted workloads at every round of a drain, and a
cap that does gives the plan of any wider one bit for bit (the rows past
the candidate count are padding). The cap follows the tree's capacity,
or what it already holds where that is more, and not the moment a drain
starts: held quota is not counted on top of the quota it holds.

Held to that here, over generated stores with victims: (i) the plan at
the cap equals the plan at the cohort's whole population; (ii) no round
of those drains has more eligible candidates in a tree than the cap;
(iii) the cap is never above the bound it replaces (kept below as the
reference); (iv) ``upstream-large-scale`` asks for the same program
before anything is admitted and after a host cycle has filled the
cohorts; (v) the drain says which program it ran and whether it had to
build it; (vi) a drain whose cap came out one width under a program the
process has (a cohort's population fell under its capacity) runs that
program, and builds its own only where nothing near covers it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kueue_oss_tpu.api.types import (
    ClusterQueue,
    Cohort,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    PreemptionPolicy,
    PreemptionPolicyValue,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_oss_tpu.core.queue_manager import QueueManager
from kueue_oss_tpu.core.store import Store
from kueue_oss_tpu.scheduler.scheduler import Scheduler
from kueue_oss_tpu.solver import full_kernels as fk
from kueue_oss_tpu.solver.engine import SolverEngine
from kueue_oss_tpu.solver.tensors import export_problem, pad_workloads, pow2

#: every problem of a store is padded to this many rows, so that the
#: drains of one store at one pair of caps share a compiled program
PAD = 128

# -- the stores ---------------------------------------------------------------

#: name -> (resources a workload asks, cohort parents, per queue
#: (cohort, nominal, borrowing limit, lending limit, its weight among
#: the holders, its backlog), a holder's size); quantities in units of
#: the smallest request
TREES = {
    # one cohort of four queues, everything lendable, borrowing capped
    "flat": (("cpu",), {"co": None},
             [("co", 4, 12, None, 1, 11)] * 4, 1),
    # the same held by a few workloads of a queue's whole quota
    "big-holders": (("cpu",), {"co": None},
                    [("co", 4, 12, None, 1, 11)] * 4, 4),
    # the same with two resources in the group: two FRs
    "two-fr": (("cpu", "memory"), {"co": None},
               [("co", 4, 12, None, 1, 11)] * 4, 1),
    # root -> two cohorts -> queues that lend only part of their quota
    "lending": (("cpu",), {"root": None, "left": "root", "right": "root"},
                [("left", 4, 8, 2, 1, 11), ("left", 4, 8, 1, 1, 11),
                 ("right", 4, 8, 2, 1, 11), ("right", 4, None, 0, 1, 11)], 1),
    # two queues that lend nothing: the first holds its whole quota and
    # has no backlog, the second holds nothing and has all of it
    "guaranteed": (("cpu",), {"co": None},
                   [("co", 20, None, 0, 1, 0), ("co", 14, None, 0, 0, 60)], 1),
}


def _cq(name, cohort, resources, nominal, borrow, lend):
    return ClusterQueue(
        name=name, cohort=cohort,
        preemption=PreemptionPolicy(
            within_cluster_queue=PreemptionPolicyValue.LOWER_PRIORITY,
            reclaim_within_cohort=PreemptionPolicyValue.ANY),
        resource_groups=[ResourceGroup(
            covered_resources=list(resources),
            flavors=[FlavorQuotas(name="f", resources=[
                ResourceQuota(name=r, nominal=nominal * 1000,
                              borrowing_limit=(None if borrow is None
                                               else borrow * 1000),
                              lending_limit=(None if lend is None
                                             else lend * 1000))
                for r in resources])])])


def _wl(name, queue, priority, t, uid, resources, units=1):
    return Workload(
        name=name, queue_name=queue, priority=priority, creation_time=t,
        uid=uid, podsets=[PodSet(name="main", count=1, requests={
            r: units * 1000 for r in resources})])


def build(tree: str, fill: float, reduce_to=None, seed=0):
    """A store whose holding queues hold ``fill`` of their quota in
    workloads of priority 0 (one unit each, or the tree's size), admitted by the host before the
    drain (a queue may borrow what another has not taken); then, with
    ``reduce_to`` (queue index -> units), those queues' nominal quota
    cut with the holders left in place; then each queue's backlog of
    one-unit workloads of priority 1 and 2, every fifth of three units:
    more than the tree can seat. Returns (store, queues)."""
    rng = np.random.default_rng(seed)
    resources, cohorts, cqs, holder_units = TREES[tree]
    store = Store()
    store.upsert_resource_flavor(ResourceFlavor(name="f"))
    for name, parent in cohorts.items():
        store.upsert_cohort(Cohort(name=name, parent=parent))
    for i, cq in enumerate(cqs):
        store.upsert_cluster_queue(_cq(f"cq{i}", cq[0], resources, *cq[1:4]))
        store.upsert_local_queue(
            LocalQueue(name=f"lq{i}", cluster_queue=f"cq{i}"))
    queues = QueueManager(store)
    sched = Scheduler(store, queues)
    uid = 1
    # holders: unevenly spread, so that some queues borrow
    weights = np.array([float(cq[4]) for cq in cqs])
    capacity = sum(cq[1] for cq in cqs if cq[4])
    spread = rng.dirichlet(np.where(weights > 0, 0.7, 1e-9))
    for i, n in enumerate(rng.multinomial(
            int(fill * capacity) // holder_units, spread)):
        for _ in range(n):
            store.add_workload(_wl(f"held{uid}", f"lq{i}", 0, float(uid),
                                   uid, resources, units=holder_units))
            uid += 1
    sched.run_until_quiet(now=50.0, tick=1.0)
    for i, units in (reduce_to or {}).items():
        cohort, _nominal, borrow, lend = cqs[i][:4]
        store.upsert_cluster_queue(_cq(
            f"cq{i}", cohort, resources, units, borrow,
            None if lend is None else min(lend, units)))
    for i, cq in enumerate(cqs):
        for k in range(cq[5]):
            store.add_workload(_wl(
                f"new{uid}", f"lq{i}", int(rng.integers(1, 3)),
                100.0 + uid, uid, resources,
                units=3 if k % 5 == 4 else 1))
            uid += 1
    return store, queues


def export(store, queues):
    """(an engine on the store, the problem its full drain would export)."""
    engine = SolverEngine(store, queues)
    return engine, export_problem(
        store, engine.pending_backlog(), include_admitted=True,
        parked=engine._parked_map())


# -- the bound this PR replaced, and the tree's population ----------------------


def _tree_sums(problem):
    """(roots, tree quota [N+1, F], smallest positive request [F], workload
    -> root) as ``_size_caps`` derives them."""
    C = problem.n_cqs
    wl_root = problem.cq_root[np.minimum(problem.wl_cqid[:-1], C - 1)]
    req = np.concatenate([
        problem.wl_req[:-1].reshape(-1, problem.wl_req.shape[-1]),
        problem.ad_usage[:-1]], axis=0)
    pos = req > 0
    min_req = np.where(pos.any(axis=0), np.where(
        pos, req, np.iinfo(req.dtype).max).min(axis=0), 0)
    path = problem.path
    null = path.shape[0] - 1
    root_of_node = np.full(path.shape[0], null)
    for n in range(null):
        root_of_node[n] = [x for x in path[n] if x != null][-1]
    tree_quota = np.zeros_like(problem.local_quota)
    np.add.at(tree_quota, root_of_node[:-1], problem.local_quota[:-1])
    return np.unique(problem.cq_root), tree_quota, min_req, wl_root


def population(problem) -> int:
    wl_root = _tree_sums(problem)[3]
    return int(np.bincount(wl_root).max())


def old_cap(problem) -> int:
    """The bound up to PR 30: the tree's quota over the smallest request,
    plus the count of workloads admitted before the drain."""
    roots, tree_quota, min_req, wl_root = _tree_sums(problem)
    adm0 = problem.ad_usage[:-1].any(axis=1)
    counts = np.bincount(wl_root[adm0], minlength=problem.n_nodes + 1)
    cap = 0
    for rn in roots:
        quota = tree_quota[rn] + problem.subtree[rn]
        per_fr = quota // np.maximum(min_req, 1)
        cap = max(cap, int(per_fr[min_req > 0].sum()) + int(counts[rn]))
    return pow2(max(8, min(population(problem), max(8, cap))))


def held_cap(problem) -> int:
    """The bound ISSUE 31 proposed: per FR the larger of the tree's
    quota and the usage its admitted workloads hold. Sound in a tree
    that lends everything; with lending limits usage inside a queue's
    own quota never reaches the root, so new workloads can be seated
    beside holders that are above a reduced quota (the case below)."""
    roots, tree_quota, min_req, wl_root = _tree_sums(problem)
    held = np.zeros_like(problem.local_quota)
    np.add.at(held, wl_root, problem.ad_usage[:-1])
    cap = 0
    for rn in roots:
        per_fr = np.maximum(tree_quota[rn] + problem.subtree[rn],
                            held[rn]) // np.maximum(min_req, 1)
        cap = max(cap, int(per_fr[min_req > 0].sum()))
    return pow2(max(8, min(population(problem), max(8, cap))))


# -- drains under a spy ------------------------------------------------------------


@pytest.fixture(scope="module", autouse=True)
def seen():
    """Every ``build_candidate_table`` of the module's drains leaves the
    largest count of eligible candidates of any tree in that round."""
    counts: list = []
    table = fk.build_candidate_table

    def spy(t, admitted, admit_rank, wl_usage, a_max):
        C = t.cq_node.shape[0]
        root_of = t.cq_root[jnp.minimum(t.wl_cqid[:-1], C - 1)]
        elig = admitted[:-1] & jnp.any(wl_usage[:-1] > 0, axis=1)
        most = jax.ops.segment_sum(elig.astype(jnp.int32), root_of,
                                   num_segments=t.parent.shape[0]).max()
        jax.debug.callback(lambda m: counts.append(int(m)), most,
                           ordered=True)
        return table(t, admitted, admit_rank, wl_usage, a_max)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fk, "build_candidate_table", spy)
        yield counts
    # a program traced under the spy must not be served to another file
    _SOLVERS.clear()
    fk._solver_cache.clear()
    jax.clear_caches()


_SOLVERS: dict = {}


def drain(problem, h_max, p_max, seen):
    """(the plan's eight arrays, the most candidates any round had)."""
    g_max = int(problem.cq_ngroups.max())
    key = (g_max, h_max, p_max)
    if key not in _SOLVERS:
        _SOLVERS[key] = fk.make_full_solver(*key)
    del seen[:]
    out = _SOLVERS[key](fk.to_device_full(pad_workloads(problem, PAD)))
    jax.effects_barrier()
    return tuple(np.asarray(a) for a in out[:8]), max(seen)


CASES = [
    # tree, share of the quota held when the drain starts, quotas cut to
    ("flat", 0.0, None), ("flat", 0.5, None), ("flat", 1.0, None),
    ("two-fr", 0.5, None), ("two-fr", 1.0, None),
    ("lending", 0.5, None), ("lending", 1.0, None),
    ("flat", 1.0, dict.fromkeys(range(4), 2)),
    ("two-fr", 1.0, dict.fromkeys(range(4), 1)),
    ("lending", 1.0, dict.fromkeys(range(4), 2)),
    ("lending", 1.0, {0: 1, 1: 1}),
    ("guaranteed", 1.0, {0: 2}),
    ("big-holders", 1.0, dict.fromkeys(range(4), 1)),
]


@pytest.mark.parametrize("tree,fill,reduce_to", CASES, ids=[
    f"{tree}-{int(100 * fill)}" + (
        "-cut" + "".join(map(str, cut.values())) if cut else "")
    for tree, fill, cut in CASES])
def test_plan_at_the_cap_is_the_plan_at_the_population(
        tree, fill, reduce_to, seen):
    store, queues = build(tree, fill, reduce_to)
    engine, problem = export(store, queues)
    h_max, p_max = engine._size_caps(problem)
    wide = pow2(population(problem))
    # (iii) never wider than the bound it replaces, which was sound
    assert p_max <= old_cap(problem) <= wide
    assert p_max < wide, "the case does not tell the cap from no cap"
    held = int(problem.ad_usage[:-1].any(axis=1).sum())
    assert (held > 0) == (fill > 0)
    at_cap, most = drain(problem, h_max, p_max, seen)
    at_wide, most_wide = drain(problem, h_max, wide, seen)
    # (ii) no tree ever had more candidates than the cap has columns
    assert most == most_wide <= p_max
    # (i) array for array
    for i, (a, b) in enumerate(zip(at_cap, at_wide)):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), i
    # the drain did evict and seat: the candidates were real
    admitted, admit_round = at_cap[0], at_cap[2]
    was = problem.wl_admitted0[:problem.n_workloads]
    assert (admit_round[:problem.n_workloads][admitted[
        :problem.n_workloads].astype(bool)] >= 0).any()
    if fill >= 0.5:
        assert (was & ~admitted[:problem.n_workloads].astype(bool)).any()


def test_usage_above_a_reduced_quota_raises_the_ceiling_and_no_more(seen):
    """Holders above a quota that was cut stay candidates, so the cap
    takes what they hold; and nothing is seated beside them, even in a
    queue whose own quota nobody may borrow and nobody uses: the
    availability a queue sees goes negative with its tree (quota.py
    ``available``), which is what the cap's proof rests on."""
    store, queues = build("guaranteed", 1.0, {0: 2})
    engine, problem = export(store, queues)
    h_max, p_max = engine._size_caps(problem)
    # 20 held against a tree quota of 2 + 14: the 20 decide
    assert p_max == held_cap(problem) == 32 < old_cap(problem) == 64
    plan, most = drain(problem, h_max, pow2(population(problem)), seen)
    assert most == 20
    assert int(plan[4]) > 14    # long enough to fill the idle queue


def test_few_large_holders_are_counted_not_measured():
    """Four holders of four units above a quota cut to four: what the
    drain can seat plus their count (8) is under what they hold (16)."""
    store, queues = build("big-holders", 1.0, dict.fromkeys(range(4), 1))
    engine, problem = export(store, queues)
    _h, p_max = engine._size_caps(problem)
    assert p_max == old_cap(problem) == 8 < held_cap(problem) == 16


# -- (iv) the cell's configuration ---------------------------------------------------


@pytest.mark.parametrize("cohorts,cqs", [(2, 16), (10, 100)],
                         ids=["2x16", "full-width"])
def test_large_scale_asks_one_program_held_or_not(cohorts, cqs):
    """``upstream-large-scale``: one host cycle seats a ``large`` (20 cpu
    of a queue's 20) in every queue, so every cohort holds its whole
    quota; the drain that starts then asks the caps of the flood."""
    from test_chip_compile import large_scale

    engine, flood = large_scale(True, cohorts, cqs)
    before = engine._size_caps(flood)
    Scheduler(engine.store, engine.queues).schedule(now=1.0)
    held = export_problem(engine.store, engine.pending_backlog(),
                          include_admitted=True)
    assert int(held.ad_usage[:-1].any(axis=1).sum()) == cohorts * cqs
    root = held.cq_root[0]
    assert (held.usage0[root] == held.subtree[root]).all()
    assert engine._size_caps(held) == before
    if cqs == 100:
        assert before == (1024, 2048)
        # up to PR 30: 2,000 cpu over 1 cpu, plus the 100 that hold it
        assert old_cap(held) == 4096


# -- (v) the drain says which program it ran -----------------------------------------


def test_a_drain_counts_the_programs_it_built():
    from kueue_oss_tpu import obs
    from kueue_oss_tpu.obs import spans

    store, queues = build("flat", 0.5)
    engine = SolverEngine(store, queues)
    fk._solver_cache.clear()

    def drain_once(now):
        before = spans.counters().get("solver_program_builds", 0)
        result = engine.drain(now=now)
        assert result.rounds >= 1
        counted = spans.counters()["solver_program_builds"] - before
        row = obs.cycle_ledger.last_row(obs.SOLVER_DRAIN)
        assert row.detail["programBuilds"] == result.program_builds
        assert (row.detail["hMax"], row.detail["pMax"]) == (
            result.h_max, result.p_max)
        assert counted == result.program_builds
        return result

    first = drain_once(200.0)
    assert (first.h_max, first.p_max, first.program_builds) == (4, 16, 1)
    assert first.evicted and first.admitted
    # the cohort now holds its whole quota: the same caps, no new program
    uid = 10_000
    for i in range(4):
        store.add_workload(_wl(f"late{i}", f"lq{i}", 3, 300.0 + i,
                               uid + i, ("cpu",)))
    second = drain_once(400.0)
    assert (second.h_max, second.p_max) == (4, 16)
    assert second.program_builds == 0 and second.evicted
    # other caps are another program
    engine.h_max_cap = 2
    for i in range(4):
        store.add_workload(_wl(f"later{i}", f"lq{i}", 4, 500.0 + i,
                               uid + 10 + i, ("cpu",)))
    third = drain_once(600.0)
    assert (third.h_max, third.p_max, third.program_builds) == (2, 16, 1)


# -- (vi) a built width stands in: one up, or any up to 64 ------------------------


def test_built_p_max_takes_a_built_width_one_up_or_any_up_to_64():
    """The invariant since PR 32 (PR 31's was "one width up and no
    further" at every width): a drain that needs ``p_max`` runs the
    exact program where it is built; else the next width up where that
    is built; else, ONLY for a need under 64, the narrowest built width
    up to 64 (a cohort of a few large gangs sizes its drains by 8 to 32
    rows, and a program each is seconds of building inside a window:
    upstream-tas); else it builds its own. Above 64 two widths up stay
    too far, as in PR 31."""
    fk._solver_cache.clear()
    assert fk.built_p_max(1, 4, 16) == 16           # nothing built
    fk.full_solver(1, 4, 32)
    assert fk.built_p_max(1, 4, 16) == 32           # one width up
    assert fk.built_p_max(1, 4, 8) == 32            # two up, under 64
    fk.full_solver(1, 4, 512)
    assert fk.built_p_max(1, 4, 128) == 128         # two up, over 64: its own
    assert fk.built_p_max(1, 4, 256) == 512         # one up
    assert fk.built_p_max(1, 4, 64) == 64           # 128 is not built
    assert fk.built_p_max(1, 4, 32) == 32
    assert fk.built_p_max(1, 8, 16) == 16           # other lanes: other program
    assert fk.built_p_max(1, 4, 16, fs_enabled=True) == 16
    fk.full_solver(1, 4, 16)
    assert fk.built_p_max(1, 4, 16) == 16           # the exact one, once built
    assert fk.built_p_max(1, 4, 8) == 16            # the narrowest built one
    fk._solver_cache.clear()
    fk.full_solver(1, 4, 128)
    assert fk.built_p_max(1, 4, 8) == 8             # 128 is over 64
    assert fk.built_p_max(1, 4, 64) == 128          # one up


def test_a_shrunken_population_runs_the_program_the_flood_built():
    """The cell's third drain in small: the first drain is sized by the
    cohort's capacity (16), the next by a population that has fallen
    under it (8). It runs the program the process has, and the plan is
    the plan of its own width."""
    from kueue_oss_tpu import obs

    def world():
        store, queues = build("flat", 0.5)
        engine = SolverEngine(store, queues)
        first = engine.drain(now=200.0)
        assert (first.p_max, first.rounds > 0) == (16, True)
        # everything leaves but what one queue still holds
        for key, wl in list(store.workloads.items()):
            if not (wl.is_quota_reserved and wl.queue_name == "lq0"):
                store.delete_workload(key)
        for i in range(3):
            store.add_workload(_wl(f"late{i}", "lq1", 3, 300.0 + i,
                                   10_000 + i, ("cpu",)))
        return store, queues, engine

    fk._solver_cache.clear()
    store, queues, engine = world()
    _engine, problem = export(store, queues)
    assert engine._size_caps(problem) == (4, 8)
    reused = engine.drain(now=400.0)
    assert (reused.p_max, reused.program_builds) == (16, 0)
    assert obs.cycle_ledger.last_row(obs.SOLVER_DRAIN).detail["pMax"] == 16
    # the same world where the process has no such program: its own width
    store2, queues2, engine2 = world()
    fk._solver_cache.clear()
    own = engine2.drain(now=400.0)
    assert (own.p_max, own.program_builds) == (8, 1)
    assert own.admitted_keys == reused.admitted_keys
    assert own.evicted_keys == reused.evicted_keys
    assert own.admitted == 3
