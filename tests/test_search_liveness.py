"""The victim search's liveness gate (full_kernels._gated_searches).

A lane of the preemption drain's victim search is LIVE when its head is
a workload and stage 1 of the search finds a legal candidate; stage 2
(the ordering and the walks) runs on the live lanes alone, in chunks,
and every other lane gets ``_dead_search_result``. These tests hold the
gated search to ``jax.vmap(classical_search)`` over all lanes: lane for
lane and array for array at every round of a drain, with nobody to
evict, on both sides of a chunk boundary, through a whole drain's
outputs and counts, and with two resource groups (the second search).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kueue_oss_tpu.core.queue_manager import QueueManager
from kueue_oss_tpu.scheduler.scheduler import Scheduler
from kueue_oss_tpu.solver import full_kernels as fk
from kueue_oss_tpu.solver.tensors import export_problem

from test_full_kernel_parity import _mk_wl, build_scenario
from test_full_kernel_parity_hard import (
    _mk_wl as _mk_hard_wl,
    _run_host,
    _run_kernel,
    build_hard_scenario,
)

RESULTS = ("success", "cand_w", "victims", "reason", "any_same_cq",
           "borrow_after")
H_MAX = 8


@pytest.fixture(autouse=True)
def _clear_caches_each_test():
    """As tests/test_full_kernel_parity_hard.py: the XLA:CPU backend
    aborts after enough large compilations in one process, and a solver
    traced under a spy must not be served to the next test."""
    yield
    jax.clear_caches()
    fk._solver_cache.clear()


def _export(build, mk_wl, seed):
    """The drain's problem as the parity tests build it: phase 1 admitted
    by the host, phase 2 pending on top of it."""
    store, phase1, phase2 = build(seed)
    queues = QueueManager(store)
    sched = Scheduler(store, queues)
    uid = 1
    for spec in phase1:
        store.add_workload(mk_wl(spec, uid))
        uid += 1
    sched.run_until_quiet(now=50.0, tick=1.0)
    for spec in phase2:
        store.add_workload(mk_wl(spec, uid))
        uid += 1
    pending, parked = {}, {}
    for name, q in queues.queues.items():
        infos = q.snapshot_order()
        if infos:
            pending[name] = infos
        if q.inadmissible:
            parked[name] = list(q.inadmissible.values())
    return export_problem(store, pending, include_admitted=True,
                          parked=parked)


def _flood(cohorts=2, cqs=8, seed=5):
    """``large-scale-replay``'s own configuration cut to 2 x 8 queues,
    all of it pending: the flood drain's problem and its caps."""
    from benchmark import deployment, driver

    cfg = deployment.scaled(
        deployment.load_config("upstream-large-scale"), cohorts, cqs, 1)
    replay = driver.Replay(cfg, deployment.schedule(cfg, seed),
                           solver="auto")
    replay.preload(3.3)
    eng = replay.engine
    pending = eng.pending_backlog()
    assert eng.needs_full_kernel(pending)
    problem = export_problem(replay.store, pending, include_admitted=True,
                             parked={}, afs=replay.queues.afs, now=3.3)
    return problem, eng._size_caps(problem)


def _p_max(problem):
    C = problem.n_cqs
    wl_root = problem.cq_root[np.minimum(problem.wl_cqid[:-1], C - 1)]
    counts = np.bincount(wl_root, minlength=problem.n_nodes + 1)
    return fk.pow2(max(8, int(counts.max())))


def _ungated(t, usage, wl_usage, admitted, evicted, ts, fw, fr, fa, fc,
             p_max):
    """What the gate has to reproduce: the whole search on every lane."""
    out = jax.vmap(lambda a, b, c, d: fk.classical_search(
        t, usage, wl_usage, admitted, evicted, ts, a, b, c, d, p_max))(
        fw, fr, fa, fc)
    return out, jnp.asarray(fw.shape[0], dtype=jnp.int32)


def _live(t, usage, wl_usage, admitted, ts, fw, fr, fa, fc):
    s1 = jax.vmap(lambda a, b, c, d: fk._search_stage1(
        t, usage, wl_usage, admitted, ts, a, b, c, d))(fw, fr, fa, fc)
    return jnp.any(s1.legal_all, axis=1)


def _spy(monkeypatch):
    """Wrap the gated search so that every call of a drain leaves, in
    call order: its inputs, its results, the ungated results and the
    liveness mask."""
    calls = []
    gated = fk._gated_searches

    def spy(t, usage, wl_usage, admitted, evicted, ts, fw, fr, fa, fc,
            p_max):
        args = (usage, wl_usage, admitted, evicted, ts, fw, fr, fa, fc)
        out, n_run = gated(t, *args, p_max)
        ref, _ = _ungated(t, *args, p_max)
        live = _live(t, usage, wl_usage, admitted, ts, fw, fr, fa, fc)
        jax.debug.callback(
            lambda *a: calls.append(dict(
                args=a[:9], out=a[9:15], ref=a[15:21], n_run=int(a[21]),
                live=np.asarray(a[22]))),
            *args, *out, *ref, n_run, live, ordered=True)
        return out, n_run

    monkeypatch.setattr(fk, "_gated_searches", spy)
    return calls


def _drain(problem, g_max, h_max, p_max):
    t = fk.to_device_full(problem)
    out = fk.make_full_solver(g_max, h_max, p_max)(t)
    jax.effects_barrier()
    return tuple(np.asarray(a) for a in out)


def _assert_lanes_equal(got, want, what):
    for name, g, w in zip(RESULTS, got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, (what, name)
        bad = np.nonzero((g != w).reshape(g.shape[0], -1).any(axis=1))[0]
        assert bad.size == 0, f"{what}: {name} differs in lanes {bad}"


def _check_calls(calls, what):
    assert calls, what
    for i, c in enumerate(calls):
        _assert_lanes_equal(c["out"], c["ref"], f"{what}, search {i}")
        assert c["n_run"] == int(c["live"].sum()), (what, i)


# -- (a) every round of a drain with victims ----------------------------------


# the tests' searches have 8 or 16 lanes: chunks of 4 (whole chunks) and of
# 3 (the last one padded) put them over one chunk, so the gate engages
@pytest.mark.parametrize("seed,chunk", [(0, 4), (3, 3), (6, 4), (9, 3)])
def test_gated_search_equals_every_lanes_search(seed, chunk, monkeypatch):
    monkeypatch.setattr(fk, "_STAGE2_CHUNK", chunk)
    problem = _export(build_scenario, _mk_wl, seed)
    calls = _spy(monkeypatch)
    out = _drain(problem, int(problem.cq_ngroups.max()), H_MAX, 32)
    _check_calls(calls, f"seed {seed}")
    assert len(calls) == int(out[4])        # one search a round
    # these seeds have victims: the comparison is not of dead lanes alone
    assert sum(c["n_run"] for c in calls) >= 2
    assert any(np.asarray(c["out"][0]).any() for c in calls)
    # a dead lane's results are the constants, whatever the lane held
    W_null = problem.wl_cqid.shape[0] - 1
    for c in calls:
        dead = ~c["live"]
        succ, cand_w, victims, reason, same, _b = c["out"]
        assert not succ[dead].any() and not same[dead].any()
        assert (cand_w[dead] == W_null).all()
        assert not victims[dead].any() and not reason[dead].any()


# -- (b) nobody to evict --------------------------------------------------------


def test_flood_of_the_cell_has_lanes_and_no_live_lane(monkeypatch):
    """The cell's flood at 2 x 8 queues: rounds in which every queue's
    head is a valid lane (the second ``large``, then ``medium``, then
    ``small``: none fits, none may evict) and rounds with no lane."""
    monkeypatch.setattr(fk, "_STAGE2_CHUNK", 4)
    problem, (h_max, p_max) = _flood()
    calls = _spy(monkeypatch)
    out = _drain(problem, 1, h_max, p_max)
    _check_calls(calls, "flood")
    W_null = problem.wl_cqid.shape[0] - 1
    valid = [int((np.asarray(c["args"][5]) != W_null).sum())
             for c in calls]
    assert max(valid) == problem.n_cqs and min(valid) == 0, valid
    assert sum(c["n_run"] for c in calls) == 0
    assert int(out[9]) == 0
    assert int(out[8]) == int(out[4]) * h_max \
        * problem.cq_opt_group.shape[1]


# -- (c) both sides of a chunk boundary -----------------------------------------


_ROUND = {}


def _a_round_with_live_lanes():
    """The inputs and the liveness mask of the round of seed 6's drain
    that has the most live lanes (taken once for the cases below)."""
    if not _ROUND:
        problem = _export(build_scenario, _mk_wl, 6)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(fk, "_STAGE2_CHUNK", 4)
            calls = _spy(mp)
            _drain(problem, int(problem.cq_ngroups.max()), H_MAX, 32)
        c = max(calls, key=lambda c: int(c["live"].sum()))
        _ROUND.update(problem=problem, args=c["args"], live=c["live"])
    return _ROUND["problem"], _ROUND["args"], _ROUND["live"]


@pytest.mark.parametrize("n_live", [0, 3, 4, 5, 8, 13, 16])
def test_chunk_boundaries(n_live, monkeypatch):
    """16 lanes in chunks of 4: live lanes taken from a real round and
    spread among dead ones, ``n_live`` below, on and above a multiple of
    the chunk, none and all."""
    problem, args, live = _a_round_with_live_lanes()
    live_ix, dead_ix = np.nonzero(live)[0], np.nonzero(~live)[0]
    assert live_ix.size and dead_ix.size
    L, B = 16, 4
    rng = np.random.default_rng(n_live)
    is_live = np.zeros(L, dtype=bool)
    is_live[rng.choice(L, size=n_live, replace=False)] = True
    pick = np.where(is_live, rng.choice(live_ix, size=L),
                    rng.choice(dead_ix, size=L))
    state = tuple(jnp.asarray(a) for a in args[:5])
    lanes = tuple(jnp.asarray(np.asarray(a)[pick]) for a in args[5:])
    t = fk.to_device_full(problem)
    monkeypatch.setattr(fk, "_STAGE2_CHUNK", B)
    got, n_run = jax.jit(lambda *a: fk._gated_searches(
        t, *a, 32))(*state, *lanes)
    want, _ = jax.jit(lambda *a: _ungated(t, *a, 32))(*state, *lanes)
    _assert_lanes_equal(got, want, f"n_live {n_live}")
    assert int(n_run) == n_live
    # the picked lanes are live where they were meant to be
    assert (np.asarray(got[1])[is_live] != (
        problem.wl_cqid.shape[0] - 1)).any(axis=1).all()


# -- (d) a whole drain ---------------------------------------------------------


@pytest.mark.parametrize("seed,chunk", [(3, 4), (9, 3)])
def test_whole_drain_and_its_counts(seed, chunk, monkeypatch):
    monkeypatch.setattr(fk, "_STAGE2_CHUNK", chunk)
    problem = _export(build_scenario, _mk_wl, seed)
    g_max = int(problem.cq_ngroups.max())
    K = problem.cq_opt_group.shape[1]
    with pytest.MonkeyPatch.context() as mp:
        calls = _spy(mp)
        gated = _drain(problem, g_max, H_MAX, 32)
    jax.clear_caches()
    monkeypatch.setattr(fk, "_gated_searches", _ungated)
    plain = _drain(problem, g_max, H_MAX, 32)
    assert len(gated) == len(plain) == 12
    for i in range(8):
        assert gated[i].tobytes() == plain[i].tobytes(), i
    rounds = int(gated[4])
    assert int(gated[8]) == int(plain[8]) == rounds * H_MAX * K
    assert int(plain[9]) == rounds * H_MAX * K
    assert int(gated[9]) == sum(int(c["live"].sum()) for c in calls)
    assert 0 < int(gated[9]) < int(gated[8])


def test_a_search_that_fits_one_chunk_is_left_ungated():
    """8 x K lanes against a chunk of 32: every lane runs, as on the
    fair-sharing and mesh arms, and the plan is the gated drain's."""
    problem = _export(build_scenario, _mk_wl, 3)
    g_max = int(problem.cq_ngroups.max())
    plain = _drain(problem, g_max, H_MAX, 32)
    assert int(plain[9]) == int(plain[8]) > 0
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fk, "_STAGE2_CHUNK", 4)
        gated = _drain(problem, g_max, H_MAX, 32)
    assert 0 < int(gated[9]) < int(gated[8]) == int(plain[8])
    for i in range(8):
        assert gated[i].tobytes() == plain[i].tobytes(), i


def test_engine_counts_the_drains_lanes(monkeypatch):
    """The counts leave the program with the plan: the drain's result,
    the program's counters and the solver's ledger row."""
    monkeypatch.setattr(fk, "_STAGE2_CHUNK", 4)
    from kueue_oss_tpu import obs
    from kueue_oss_tpu.obs import spans
    from kueue_oss_tpu.solver.engine import SolverEngine

    store, phase1, phase2 = build_scenario(3)
    queues = QueueManager(store)
    sched = Scheduler(store, queues)
    for uid, spec in enumerate(phase1, 1):
        store.add_workload(_mk_wl(spec, uid))
    sched.run_until_quiet(now=50.0, tick=1.0)
    for uid, spec in enumerate(phase2, len(phase1) + 1):
        store.add_workload(_mk_wl(spec, uid))
    before = spans.counters()
    result = SolverEngine(store, queues).drain(now=200.0)
    assert result.rounds >= 1
    assert 0 < result.search_live_lanes < result.search_lanes
    after = spans.counters()
    for name in ("search_lanes", "search_live_lanes"):
        assert after[name] - before.get(name, 0) == getattr(result, name)
    row = obs.cycle_ledger.last_row(obs.SOLVER_DRAIN)
    assert row.detail == {"searchLanes": result.search_lanes,
                          "searchLiveLanes": result.search_live_lanes,
                          "scanEntries": result.scan_entries,
                          "scanVictimEntries": result.scan_victim_entries,
                          "programBuilds": result.program_builds,
                          "programTraces": result.program_traces,
                          "programLoads": result.program_loads,
                          "hMax": result.h_max, "pMax": result.p_max}


# -- (e) two resource groups: the second search ----------------------------------


@pytest.mark.parametrize("seed,chunk", [(2, 4), (3, 3)])
def test_second_search_of_a_multi_group_head_is_gated(seed, chunk,
                                                      monkeypatch):
    monkeypatch.setattr(fk, "_STAGE2_CHUNK", chunk)
    problem = _export(build_hard_scenario, _mk_hard_wl, seed)
    g_max = int(problem.cq_ngroups.max())
    K = problem.cq_opt_group.shape[1]
    assert g_max == 2
    with pytest.MonkeyPatch.context() as mp:
        calls = _spy(mp)
        out = _drain(problem, g_max, H_MAX, _p_max(problem))
    jax.clear_caches()
    # two searches a round: every option, then the chosen assignment
    assert len(calls) == 2 * int(out[4])
    assert [c["live"].shape[0] for c in calls[:2]] == [H_MAX * K, H_MAX]
    _check_calls(calls, f"hard seed {seed}")
    assert sum(c["n_run"] for c in calls[1::2]) >= 4
    assert any(np.asarray(c["out"][0]).any() for c in calls[1::2])
    assert int(out[8]) == int(out[4]) * H_MAX * (K + 1)
    assert int(out[9]) == sum(c["n_run"] for c in calls)
    # and the plan is the host's, as the parity test compares it
    init_h, admitted_h, flavors_h = _run_host(seed)
    init_k, admitted_k, flavors_k, _rounds = _run_kernel(seed)
    assert init_k == init_h and admitted_k == admitted_h
    assert init_k - admitted_k == init_h - admitted_h
    assert all(flavors_k.get(k) == flavors_h.get(k) for k in admitted_h)
