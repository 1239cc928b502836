"""The entry scan books victims only where an entry has targets.

A step of the preemption drain's entry scan (full_kernels.full_round_scan)
runs the victim bookkeeping (the overlap check, the removal loop, the
P-wide gathers and scatters, the preemptor's charge) under a
``lax.cond`` whose predicate is the entry's own: Preempt mode and a
lane whose search found targets. The victim compaction of a round
(full_kernels._compact_victims) runs its sort only where some lane
holds a victim. These tests hold both to what the drain did without
them: the host's plan, the counts the program returns, and the
compaction's own order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kueue_oss_tpu.solver import full_kernels as fk

from test_full_kernel_parity_hard import (
    _mk_wl as _mk_hard_wl,
    _run_host,
    _run_kernel,
    build_hard_scenario,
)
from test_search_liveness import _drain, _export, _flood, _p_max

H_MAX = 8


@pytest.fixture(autouse=True)
def _clear_caches_each_test():
    """As tests/test_full_kernel_parity_hard.py: the XLA:CPU backend
    aborts after enough large compilations in one process, and a solver
    traced under a spy must not be served to the next test."""
    yield
    jax.clear_caches()
    fk._solver_cache.clear()


def _rounds(problem, g_max, h_max, p_max):
    """The drain one round at a time, as debug_drain steps it: the final
    state and every round's debug arrays."""
    t = fk.to_device_full(problem)
    pot = fk.potential_available_all(t)
    state = fk._init_state(t, g_max)
    step = jax.jit(lambda tt, st: fk.round_body(tt, st, pot, g_max, h_max,
                                                p_max))
    rounds = []
    while True:
        state, dbg = step(t, state)
        rounds.append({k: np.asarray(v) for k, v in dbg.items()})
        if not bool(state["progress"]):
            return state, rounds


def _active(problem, dbg):
    W_null = problem.wl_cqid.shape[0] - 1
    return (dbg["cand_w"] != W_null) & (dbg["mode"] != fk.M_NOFIT)


def _victim_sets(dbg):
    """The workloads each lane with targets would evict."""
    return [set(dbg["lane_cand_w"][lane][dbg["lane_victims"][lane]].tolist())
            for lane in np.nonzero(dbg["lane_success"])[0]]


# -- (a) the cell's flood: nobody has a victim ---------------------------------


def _host_flood(cohorts, cqs, seed):
    """The flood of ``_flood`` drained by host cycles: the keys that
    hold quota when they go quiet."""
    from benchmark import deployment, driver

    cfg = deployment.scaled(
        deployment.load_config("upstream-large-scale"), cohorts, cqs, 1)
    replay = driver.Replay(cfg, deployment.schedule(cfg, seed),
                           solver=None)
    replay.preload(3.3)
    replay.sched.run_until_quiet(now=3.3)
    return {k for k, w in replay.store.workloads.items()
            if w.is_quota_reserved}


@pytest.mark.parametrize("cohorts,cqs,seed", [(2, 8, 5), (1, 6, 11)])
def test_flood_books_no_victim_and_matches_the_host(cohorts, cqs, seed):
    problem, (h_max, p_max) = _flood(cohorts, cqs, seed)
    out = _drain(problem, 1, h_max, p_max)
    assert len(out) == 12
    rounds, scan_entries, scan_victim_entries = (
        int(out[4]), int(out[10]), int(out[11]))
    assert rounds >= 2
    assert scan_victim_entries == 0
    assert scan_entries >= problem.n_cqs
    assert not out[7].any()                         # no victim reason
    admitted = {problem.wl_keys[w] for w in range(problem.n_workloads)
                if out[0][w]}
    assert admitted and admitted == _host_flood(cohorts, cqs, seed)


# -- (b) a round that mixes preemptors, an overlap and fits ---------------------


# hard seeds with a round in which an entry fits and two entries whose
# searches found targets share a victim (found by stepping every seed)
@pytest.mark.parametrize("seed", [8, 13])
def test_mixed_round_counts_the_entries_that_book_victims(seed):
    problem = _export(build_hard_scenario, _mk_hard_wl, seed)
    g_max = int(problem.cq_ngroups.max())
    p_max = _p_max(problem)
    state, rounds = _rounds(problem, g_max, H_MAX, p_max)

    mixed = []
    for r, dbg in enumerate(rounds):
        sets = _victim_sets(dbg)
        shared = any(a & b for i, a in enumerate(sets) for b in sets[i + 1:])
        if shared and (dbg["adm_entry"] & _active(problem, dbg)).any():
            mixed.append(r)
            # the later of two entries that share a victim, or the earlier
            # where it is refused for another reason, books nothing
            assert dbg["pre_entry"].sum() < dbg["lane_success"].sum()
    assert mixed, f"hard seed {seed}: no mixed round with a shared victim"

    # a Preempt-mode entry with a lane whose search found targets is what
    # the counter counts: every lane with targets is one such entry
    want_victim = sum(int(d["lane_success"].sum()) for d in rounds)
    want_entries = sum(int(_active(problem, d).sum()) for d in rounds)
    assert int(state["scan_victim_entries"]) == want_victim
    assert int(state["scan_entries"]) == want_entries
    assert 0 < want_victim < want_entries

    out = _drain(problem, g_max, H_MAX, p_max)
    assert int(out[4]) == len(rounds)
    assert (int(out[10]), int(out[11])) == (want_entries, want_victim)
    assert int(out[11]) >= sum(int(d["pre_entry"].sum()) for d in rounds)
    # the plan is the host's, as the parity test compares it
    init_h, admitted_h, flavors_h = _run_host(seed)
    init_k, admitted_k, flavors_k, _rounds_k = _run_kernel(seed)
    assert init_k == init_h and admitted_k == admitted_h
    assert all(flavors_k.get(k) == flavors_h.get(k) for k in admitted_h)


# -- (c) the compaction skips its sort where no lane holds a victim ------------


def _reference_compact(vw, vm, re):
    p_max = vm.shape[1]
    key = np.where(vm, np.arange(p_max), p_max)
    order = np.argsort(key, axis=1, kind="stable")
    return tuple(np.take_along_axis(a, order, axis=1) for a in (vw, vm, re))


def _spy_compaction(monkeypatch):
    calls = []
    compact = fk._compact_victims

    def spy(vw, vm, re):
        out = compact(vw, vm, re)
        jax.debug.callback(
            lambda *a: calls.append([np.asarray(x) for x in a]),
            vw, vm, re, *out, ordered=True)
        return out

    monkeypatch.setattr(fk, "_compact_victims", spy)
    return calls


@pytest.mark.parametrize("case", ["flood", "hard seed 13"])
def test_debug_drain_lanes_keep_the_searchs_order_without_victims(
        case, monkeypatch):
    if case == "flood":
        problem, (h_max, p_max) = _flood()
        g_max = 1
    else:
        problem = _export(build_hard_scenario, _mk_hard_wl, 13)
        g_max, h_max, p_max = (int(problem.cq_ngroups.max()), H_MAX,
                               _p_max(problem))
    calls = _spy_compaction(monkeypatch)
    _state, rounds = _rounds(problem, g_max, h_max, p_max)
    jax.effects_barrier()
    assert len(calls) == len(rounds)
    with_victims = 0
    for (vw, vm, re, *got), dbg in zip(calls, rounds):
        for g, w in zip(got, _reference_compact(vw, vm, re)):
            assert g.tobytes() == w.tobytes()
        # debug_drain's lanes are what the compaction handed on
        assert dbg["lane_cand_w"].tobytes() == got[0].tobytes()
        assert dbg["lane_victims"].tobytes() == got[1].tobytes()
        if not vm.any():
            # the search's own order, slot for slot
            assert got[0].tobytes() == vw.tobytes()
            assert got[1].tobytes() == vm.tobytes()
            assert got[2].tobytes() == re.tobytes()
        with_victims += bool(vm.any())
    if case == "flood":
        assert with_victims == 0
    else:
        assert 0 < with_victims < len(rounds)


@pytest.mark.parametrize("share", [0.0, 0.05, 1.0])
def test_compaction_equals_the_stable_sort(share):
    rng = np.random.default_rng(int(share * 100))
    L, P = 16, 64
    vw = rng.integers(0, 1000, size=(L, P)).astype(np.int32)
    vm = rng.random((L, P)) < share
    re = rng.integers(0, 4, size=(L, P)).astype(np.int8)
    got = jax.jit(fk._compact_victims)(
        jnp.asarray(vw), jnp.asarray(vm), jnp.asarray(re))
    for g, w in zip(got, _reference_compact(vw, vm, re)):
        assert np.asarray(g).tobytes() == w.tobytes()
    if share == 0.0:
        assert np.asarray(got[0]).tobytes() == vw.tobytes()

