"""Delta-sync solver sessions (docs/SOLVER_PROTOCOL.md).

Correctness contract under test:

1. property-style replay — randomized store event sequences (create /
   admit / evict / finish / delete / quota-edit); after every event
   batch, the delta applied to a shadow sidecar state must be
   BIT-IDENTICAL to a fresh full sync of the same export (checksums and
   arrays both), whatever mix of deltas and full syncs the session
   chose to emit;
2. the wire path — a real sidecar serves SYNC then DELTA frames, plans
   match a sessionless engine exactly, and steady-state frames are
   deltas, not syncs;
3. forced desync — a dropped DELTA (sidecar crash mid-cycle) leaves the
   sidecar behind; the next drain must recover through an in-band
   RESYNC (counted in metrics), re-seed bit-identical sidecar state,
   and still produce the host-parity plan;
4. the in-process resident device path reuses buffers across drains
   (delta scatter updates, not full re-uploads) without changing plans.
"""

import os
import random
import tempfile

import numpy as np
import pytest

from kueue_oss_tpu import metrics
from kueue_oss_tpu.api.types import (
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    PreemptionPolicy,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_oss_tpu.core.queue_manager import QueueManager
from kueue_oss_tpu.core.store import Store
from kueue_oss_tpu.scheduler.scheduler import Scheduler
from kueue_oss_tpu.solver.delta import (
    HostDeltaSession,
    StableRanker,
    apply_delta,
    deserialize_delta,
    problem_wire_state,
    serialize_delta,
    state_checksum,
)
from kueue_oss_tpu.solver.engine import SolverEngine
from kueue_oss_tpu.solver.service import (
    SolverClient,
    SolverServer,
    expand_compact_plan,
)
from kueue_oss_tpu.solver.tensors import pad_workloads


def _store(n_cqs=4, quota=8, preemption=True):
    store = Store()
    store.upsert_resource_flavor(ResourceFlavor(name="f"))
    for i in range(n_cqs):
        store.upsert_cluster_queue(ClusterQueue(
            name=f"cq{i}",
            preemption=(PreemptionPolicy(
                within_cluster_queue="LowerPriority")
                if preemption else PreemptionPolicy()),
            resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[FlavorQuotas(name="f", resources=[
                    ResourceQuota(name="cpu", nominal=quota)])])]))
        store.upsert_local_queue(LocalQueue(
            name=f"lq{i}", cluster_queue=f"cq{i}"))
    return store


def _wl(i, prio=0, cpu=1):
    return Workload(
        name=f"w{i}", queue_name=f"lq{i % 4}", uid=i + 1, priority=prio,
        creation_time=float(i),
        podsets=[PodSet(name="main", count=1, requests={"cpu": cpu})])


def _sock_path():
    return os.path.join(tempfile.mkdtemp(), "solver.sock")


def _admitted(store):
    return {k for k, w in store.workloads.items() if w.is_quota_reserved}


# ---------------------------------------------------------------------------
# stable ranker unit behavior
# ---------------------------------------------------------------------------


def test_stable_ranker_preserves_order_and_identity():
    r = StableRanker(gap=8)
    vals = np.asarray([3.0, 1.0, 2.0])
    r.update(vals)
    first = {v: int(x) for v, x in zip(vals, r.rank(vals))}
    assert first[1.0] < first[2.0] < first[3.0]
    # appends keep existing ranks; order still strict
    r.update(np.asarray([10.0, 2.5]))
    after = {v: int(x) for v, x in
             zip([1.0, 2.0, 2.5, 3.0, 10.0],
                 r.rank(np.asarray([1.0, 2.0, 2.5, 3.0, 10.0])))}
    for v in (1.0, 2.0, 3.0):
        assert after[v] == first[v], "existing ranks must not move"
    assert (after[1.0] < after[2.0] < after[2.5] < after[3.0]
            < after[10.0])


def test_stable_ranker_renumbers_on_gap_exhaustion():
    r = StableRanker(gap=2)
    r.update(np.asarray([0.0, 1.0]))
    # repeated midpoint inserts exhaust a gap of 2 quickly
    renumbered = False
    for k in range(4):
        renumbered |= r.update(np.asarray([0.1 + k * 0.01]))
    assert renumbered, "exhausted gap must report a renumber"
    vals = np.asarray(sorted([0.0, 1.0, 0.1, 0.11, 0.12, 0.13]))
    ranks = r.rank(vals)
    assert (np.diff(ranks) > 0).all(), "order survives the renumber"


# ---------------------------------------------------------------------------
# property-style replay: delta-applied state == fresh full sync
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 7, 23])
def test_delta_replay_bit_identical_over_random_event_sequences(seed):
    rng = random.Random(seed)
    store = _store(quota=6)
    queues = QueueManager(store)
    sched = Scheduler(store, queues)
    engine = SolverEngine(store, queues, scheduler=sched)
    session = HostDeltaSession(cache=engine.export_cache,
                               neutral_fields=("wl_rank",))
    next_uid = [0]

    def submit(n):
        for _ in range(n):
            i = next_uid[0]
            next_uid[0] += 1
            store.add_workload(_wl(i, prio=rng.randrange(3)))

    submit(16)
    sidecar = None  # (kwargs, meta) shadow of the remote state
    syncs = deltas = 0
    for step in range(14):
        # one random event batch: the store/queue churn mix of the
        # acceptance criteria (create/admit/evict/finish/delete and a
        # quota edit, which flows through the node-axis repl path)
        op = rng.randrange(5)
        if op == 0:
            submit(rng.randrange(1, 4))
        elif op == 1:
            engine.drain(now=float(step))  # admissions (solver path)
        elif op == 2:
            admitted = sorted(_admitted(store))
            for k in admitted[:rng.randrange(0, 3)]:
                sched.finish_workload(k, now=float(step))
        elif op == 3:
            admitted = sorted(_admitted(store))
            if admitted:
                sched.evict_workload(
                    admitted[rng.randrange(len(admitted))],
                    reason="Preempted", message="chaos", now=float(step))
        else:
            cq = store.cluster_queues[f"cq{rng.randrange(4)}"]
            cq.resource_groups[0].flavors[0].resources[0].nominal = (
                rng.randrange(4, 9))
            store.upsert_cluster_queue(cq)

        problem = _export_full_problem(engine, now=float(step))
        if problem is None:
            continue
        problem = pad_workloads(problem, 64)
        slotted, frame = session.advance(problem)
        kwargs, meta = problem_wire_state(slotted)
        assert state_checksum(kwargs, meta) == frame.checksum
        if frame.delta is None or sidecar is None:
            syncs += 1
            sidecar = ({k: (None if v is None else v.copy())
                        for k, v in kwargs.items()}, dict(meta))
        else:
            deltas += 1
            # full wire roundtrip of the delta, then replay
            dh, blob = serialize_delta(frame.delta)
            delta = deserialize_delta(dh, blob)
            apply_delta(sidecar[0], sidecar[1], delta)
        # BIT-IDENTICAL: checksum and every array
        assert state_checksum(*sidecar) == frame.checksum
        for name, arr in kwargs.items():
            if arr is None:
                assert sidecar[0][name] is None
            else:
                assert np.array_equal(sidecar[0][name], arr), name
    assert deltas > 0, "the sequence must exercise the delta path"


# helper used by the replay test: one full-kernel export of the
# current backlog exactly as _drain_full would build it
def _export_full_problem(engine, now=0.0):
    pending = engine.pending_backlog()
    parked_map = {}
    for name, q in engine.queues.queues.items():
        if not q.inadmissible:
            continue
        infos = [i for k, i in q.inadmissible.items()
                 if k not in q._stale]
        if infos:
            parked_map[name] = infos
    from kueue_oss_tpu.solver.tensors import export_problem

    problem = export_problem(engine.store, pending,
                             include_admitted=True, parked=parked_map,
                             now=now, cache=engine.export_cache)
    return problem if problem.n_workloads else None


# ---------------------------------------------------------------------------
# wire path: sync -> deltas, parity, resident device reuse
# ---------------------------------------------------------------------------


@pytest.fixture()
def server():
    path = _sock_path()
    srv = SolverServer(path)
    srv.serve_in_background()
    yield path, srv
    srv.shutdown()
    srv.server_close()


def _churn_run(engine, store, sched, cycles=4, churn=2):
    uid = [100]
    for cyc in range(1, cycles + 1):
        admitted = sorted(k for k, w in store.workloads.items()
                          if w.is_quota_reserved and not w.is_finished)
        for k in admitted[:churn]:
            sched.finish_workload(k, now=float(cyc))
        for _ in range(churn):
            store.add_workload(_wl(uid[0]))
            uid[0] += 1
        engine.drain(now=float(cyc))


def test_remote_session_ships_deltas_with_exact_parity(server):
    path, srv = server
    store = _store()
    for i in range(48):
        store.add_workload(_wl(i))
    queues = QueueManager(store)
    sched = Scheduler(store, queues)
    engine = SolverEngine(store, queues, scheduler=sched,
                          remote=SolverClient(path))
    engine.pad_to = 128
    engine.drain(now=0.0)
    assert engine.remote.frames_by_kind.get("sync") == 1
    _churn_run(engine, store, sched, cycles=4)
    assert engine.remote.frames_by_kind.get("delta", 0) >= 2, \
        "steady-state churn cycles must ship DELTA frames"
    # the sidecar session is resident: one full upload + delta scatters
    sess = next(iter(srv.sessions.values()))
    assert sess.device.delta_updates >= 2

    # parity: identical run with sessions disabled
    store2 = _store()
    for i in range(48):
        store2.add_workload(_wl(i))
    queues2 = QueueManager(store2)
    sched2 = Scheduler(store2, queues2)
    engine2 = SolverEngine(store2, queues2, scheduler=sched2)
    engine2.use_sessions = False
    engine2.pad_to = 128
    engine2.drain(now=0.0)
    _churn_run(engine2, store2, sched2, cycles=4)
    assert _admitted(store) == _admitted(store2)


def test_dropped_delta_forces_resync_and_recovers(server):
    """A DELTA the sidecar never saw (lost mid-transport / sidecar
    wiped) must resolve through RESYNC: counted, bit-identical state
    re-seeded, plan unchanged vs the host cycle."""
    path, srv = server
    store = _store()
    for i in range(48):
        store.add_workload(_wl(i))
    queues = QueueManager(store)
    sched = Scheduler(store, queues)
    engine = SolverEngine(store, queues, scheduler=sched,
                          remote=SolverClient(path))
    engine.pad_to = 128
    engine.drain(now=0.0)
    _churn_run(engine, store, sched, cycles=2)
    assert engine.remote.frames_by_kind.get("delta", 0) >= 1

    # simulate the sidecar losing the session (restart/crash): the next
    # delta must come back resync=session_missing and recover in-call
    resyncs0 = metrics.solver_resync_total.total()
    with srv._sessions_lock:
        srv.sessions.clear()
    _churn_run(engine, store, sched, cycles=1)
    assert metrics.solver_resync_total.total() == resyncs0 + 1
    assert metrics.solver_resync_total.collect().get(
        ("session_missing",), 0) >= 1
    assert engine.remote.frames_by_kind.get("resync", 0) >= 1

    # re-seeded sidecar state is bit-identical to the host session's
    sess_host = engine._delta_sessions["full"]
    sidecar = next(iter(srv.sessions.values()))
    assert sidecar.epoch == sess_host.epoch
    host_kwargs, host_meta = sess_host._last
    assert (state_checksum(sidecar.kwargs, sidecar.meta)
            == state_checksum(host_kwargs, host_meta))

    # and the overall plan still matches the host-only path
    store_h = _store()
    for i in range(48):
        store_h.add_workload(_wl(i))
    queues_h = QueueManager(store_h)
    sched_h = Scheduler(store_h, queues_h)
    engine_h = SolverEngine(store_h, queues_h, scheduler=sched_h)
    engine_h.use_sessions = False
    engine_h.pad_to = 128
    engine_h.drain(now=0.0)
    _churn_run(engine_h, store_h, sched_h, cycles=3)
    assert _admitted(store) == _admitted(store_h)


def test_checksum_mismatch_drops_session_and_resyncs(server):
    """Corrupted resident sidecar state (bit-flip) must be caught by the
    DELTA checksum, answered with RESYNC, and healed by the SYNC."""
    path, srv = server
    store = _store()
    for i in range(48):
        store.add_workload(_wl(i))
    queues = QueueManager(store)
    sched = Scheduler(store, queues)
    engine = SolverEngine(store, queues, scheduler=sched,
                          remote=SolverClient(path))
    engine.pad_to = 128
    engine.drain(now=0.0)
    _churn_run(engine, store, sched, cycles=2)
    sidecar = next(iter(srv.sessions.values()))
    with sidecar.lock:
        sidecar.kwargs["wl_prio"][0] += 1  # silent divergence
    resyncs0 = metrics.solver_resync_total.collect().get(
        ("checksum_mismatch",), 0)
    _churn_run(engine, store, sched, cycles=1)
    assert metrics.solver_resync_total.collect().get(
        ("checksum_mismatch",), 0) == resyncs0 + 1
    sidecar2 = next(iter(srv.sessions.values()))
    host_kwargs, host_meta = engine._delta_sessions["full"]._last
    assert (state_checksum(sidecar2.kwargs, sidecar2.meta)
            == state_checksum(host_kwargs, host_meta))


def test_local_resident_device_reuses_buffers_with_same_plans():
    store = _store()
    for i in range(48):
        store.add_workload(_wl(i))
    queues = QueueManager(store)
    sched = Scheduler(store, queues)
    engine = SolverEngine(store, queues, scheduler=sched)
    engine.pad_to = 128
    engine.drain(now=0.0)
    _churn_run(engine, store, sched, cycles=3)
    dev = engine._device_states["full"]
    assert dev.delta_updates >= 2, \
        "steady-state local drains must scatter deltas, not re-upload"

    store2 = _store()
    for i in range(48):
        store2.add_workload(_wl(i))
    queues2 = QueueManager(store2)
    sched2 = Scheduler(store2, queues2)
    engine2 = SolverEngine(store2, queues2, scheduler=sched2)
    engine2.use_sessions = False
    engine2.pad_to = 128
    engine2.drain(now=0.0)
    _churn_run(engine2, store2, sched2, cycles=3)
    assert _admitted(store) == _admitted(store2)


def test_compact_plan_roundtrip_preserves_guard_visible_corruption():
    """expand_compact_plan is pure scatter: a compact response that
    admits padding rows or overlaps admitted/parked must survive into
    the dense arrays so the engine's sanity guard can reject it."""
    data = {
        "adm_idx": np.asarray([0, 5], dtype=np.int32),   # 5 = padding
        "adm_opt": np.asarray([0, 3], dtype=np.int32),
        "adm_round": np.asarray([0, 1], dtype=np.int32),
        "park_idx": np.asarray([0], dtype=np.int32),     # overlaps
        "rounds": np.int32(1),
    }
    admitted, opt, admit_round, parked, rounds, _usage = (
        expand_compact_plan(data, 7, full=False, g_max=1))
    assert admitted[5] and admitted[0] and parked[0]
    assert opt[5] == 3 and int(rounds) == 1
    assert bool((admitted & parked).any())


def test_session_prunes_oversized_rankers():
    """Rankers must not hold dead timestamps forever: once the registry
    dwarfs the live problem, the session resets them and rides the full
    sync it forces (reason=ranker_prune)."""
    store = _store()
    for i in range(8):
        store.add_workload(_wl(i))
    queues = QueueManager(store)
    engine = SolverEngine(store, queues)
    session = HostDeltaSession(cache=engine.export_cache)
    problem = _export_full_problem(engine)
    problem = pad_workloads(problem, 16)
    session.advance(problem)
    session._ts.update(np.arange(5000, dtype=np.float64) + 1e6)
    assert session._ts.size > 4096
    _slotted, frame = session.advance(problem)
    assert frame.full_reason == "ranker_prune"
    assert session._ts.size < 4096, "rankers rebuilt from live rows only"


# ---------------------------------------------------------------------------
# mesh-resident sessions (docs/SOLVER_PROTOCOL.md "Mesh-resident sessions")
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [5, 19])
def test_mesh_resident_replay_bit_identical_uneven_shards(
        seed, eight_devices):
    """The randomized event-replay property, extended to the mesh:
    delta-applied MESH-resident device state must stay bit-identical to
    a fresh full sync AND to the single-chip resident path after every
    event batch — with a padded axis whose real rows do NOT divide
    evenly over the 8 shards (W % n_dev != 0)."""
    import jax
    from jax.sharding import Mesh

    from kueue_oss_tpu.solver.delta import DeviceResidentProblem
    from kueue_oss_tpu.solver.kernels import solve_backlog, to_device
    from kueue_oss_tpu.solver.meshutil import (
        align_pad_target,
        lean_mesh_solver,
    )

    mesh = Mesh(np.asarray(eight_devices), ("wl",))
    rng = random.Random(seed)
    store = _store(quota=6, preemption=False)
    queues = QueueManager(store)
    sched = Scheduler(store, queues)
    engine = SolverEngine(store, queues, scheduler=sched,
                          mesh_mode="off")
    session = HostDeltaSession(cache=engine.export_cache)
    dev_mesh = DeviceResidentProblem(mesh=mesh)
    dev_one = DeviceResidentProblem()
    # padded so W1 = 56 shards evenly over 8 devices while the REAL
    # row count (<= ~30) never does (uneven shard occupancy every step)
    target = align_pad_target(48, mesh)
    assert (target + 1) % 8 == 0
    next_uid = [0]

    def submit(n):
        for _ in range(n):
            i = next_uid[0]
            next_uid[0] += 1
            store.add_workload(_wl(i, prio=rng.randrange(3)))

    submit(12)
    deltas = 0
    for step in range(10):
        op = rng.randrange(4)
        if op == 0:
            submit(rng.randrange(1, 3))
        elif op == 1:
            engine.drain(now=float(step))
        elif op == 2:
            admitted = sorted(_admitted(store))
            for k in admitted[:rng.randrange(0, 3)]:
                sched.finish_workload(k, now=float(step))
        else:
            cq = store.cluster_queues[f"cq{rng.randrange(4)}"]
            cq.resource_groups[0].flavors[0].resources[0].nominal = (
                rng.randrange(4, 9))
            store.upsert_cluster_queue(cq)
        problem, _ = engine.export()
        if problem.n_workloads == 0:
            continue
        problem = pad_workloads(problem, target)
        slotted, frame = session.advance(problem)
        tm = dev_mesh.update(slotted, frame, False)
        t1 = dev_one.update(slotted, frame, False)
        assert dev_mesh.mesh_placed
        if frame.delta is not None:
            deltas += 1
        fresh = to_device(slotted)
        for f in fresh._fields:
            assert np.array_equal(np.asarray(getattr(tm, f)),
                                  np.asarray(getattr(fresh, f))), f
            assert np.array_equal(np.asarray(getattr(t1, f)),
                                  np.asarray(getattr(fresh, f))), f
        # and the PLANS from the resident states are bit-identical
        out_m = lean_mesh_solver(mesh)(tm)
        out_s = solve_backlog(t1)
        for a, b in zip(out_m, out_s):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    assert deltas > 0, "the sequence must exercise the delta path"
    assert dev_mesh.delta_updates > 0
    assert dev_mesh.donated_update_bytes > 0
    assert dev_mesh.avoided_copy_bytes > dev_mesh.donated_update_bytes


def test_mesh_single_host_churn_plans_bit_identical(eight_devices):
    """Acceptance: randomized churn replays produce bit-identical
    admitted/parked/victim plans across the host (sessionless fresh
    sync), single-chip resident, and mesh-resident session paths —
    preemption shapes included (full kernel, lane-sharded)."""
    rng = random.Random(77)

    def build():
        store = _store(quota=6, preemption=True)
        for i in range(24):
            store.add_workload(_wl(i, prio=i % 3))
        queues = QueueManager(store)
        sched = Scheduler(store, queues)
        return store, queues, sched

    store_h, q_h, s_h = build()
    e_h = SolverEngine(store_h, q_h, scheduler=s_h, mesh_mode="off")
    e_h.use_sessions = False
    store_s, q_s, s_s = build()
    e_s = SolverEngine(store_s, q_s, scheduler=s_s, mesh_mode="off")
    store_m, q_m, s_m = build()
    e_m = SolverEngine(store_m, q_m, scheduler=s_m)
    e_m.mesh_min_workloads = 0
    e_m.mesh_force = True
    for e in (e_h, e_s, e_m):
        e.pad_to = 64
    uid = [1000]
    for cyc in range(4):
        results = []
        for store, sched, engine in ((store_h, s_h, e_h),
                                     (store_s, s_s, e_s),
                                     (store_m, s_m, e_m)):
            admitted = sorted(k for k, w in store.workloads.items()
                              if w.is_quota_reserved
                              and not w.is_finished)
            finish = admitted[:2]
            for k in finish:
                sched.finish_workload(k, now=float(cyc))
            for j in range(2):
                store.add_workload(_wl(uid[0] + j, prio=(cyc + j) % 3))
            results.append(engine.drain(now=float(cyc)))
        uid[0] += 2
        # single-chip resident vs mesh-resident: the same PLAN (sets,
        # victims). The two engines' sessions no longer share one slot
        # layout — the mesh engine interleaves slots across block
        # shards (HostDeltaSession.set_interleave) while the mesh-off
        # twin keeps the classic smallest-slot packing — so key ORDER
        # within an admit round may legally differ between the twins.
        # Cross-ARM bit-identity still holds inside one engine: both
        # its arms drain the byte-identical session encoding
        # (test_sharded_full.py proves kernel-level bit-identity).
        assert (sorted(results[1].admitted_keys)
                == sorted(results[2].admitted_keys)), cyc
        assert (sorted(results[1].evicted_keys)
                == sorted(results[2].evicted_keys)), cyc
        # vs the sessionless fresh-sync path the PLAN (sets, victims)
        # matches; within one admit round the apply tie-break is slot
        # order vs export order, so key order may legally differ there
        assert (set(results[0].admitted_keys)
                == set(results[1].admitted_keys)), cyc
        assert (results[0].evicted_keys == results[1].evicted_keys), cyc
        assert (_admitted(store_h) == _admitted(store_s)
                == _admitted(store_m)), cyc
    assert e_m.last_drain_arm == "mesh"
    dev = e_m._device_states.get("full-mesh") or e_m._device_states.get(
        "lean-mesh")
    assert dev is not None and dev.delta_updates > 0


def test_mesh_sidecar_session_resync_recovery(server, eight_devices):
    """Mesh-resident sessions over the WIRE: the sidecar shards its
    resident lean state over the virtual mesh, ships compact plans,
    and a forced session loss recovers through RESYNC with plans still
    matching the mesh-less host path bit-for-bit."""
    path, srv = server
    srv.mesh_min_workloads = 0
    for sess in list(srv.sessions.values()):
        sess.device.mesh_min_rows = 0
    store = _store(preemption=False)
    for i in range(48):
        store.add_workload(_wl(i))
    queues = QueueManager(store)
    sched = Scheduler(store, queues)
    engine = SolverEngine(store, queues, scheduler=sched,
                          remote=SolverClient(path))
    engine.pad_to = 64
    engine.drain(now=0.0)
    assert engine.remote.frames_by_kind.get("sync") == 1
    _churn_run(engine, store, sched, cycles=2)
    assert engine.remote.frames_by_kind.get("delta", 0) >= 1
    sidecar = next(iter(srv.sessions.values()))
    if srv.mesh is not None:
        assert sidecar.device.mesh_placed, \
            "sidecar lean resident state must shard over the mesh"
    # forced desync: the sidecar loses the session mid-churn
    resyncs0 = metrics.solver_resync_total.total()
    with srv._sessions_lock:
        srv.sessions.clear()
    _churn_run(engine, store, sched, cycles=1)
    assert metrics.solver_resync_total.total() == resyncs0 + 1
    # re-seeded mesh-resident state serves deltas again
    _churn_run(engine, store, sched, cycles=1)
    sidecar2 = next(iter(srv.sessions.values()))
    assert sidecar2.device.delta_updates >= 1
    # parity vs the sessionless, mesh-less path
    store_h = _store(preemption=False)
    for i in range(48):
        store_h.add_workload(_wl(i))
    queues_h = QueueManager(store_h)
    sched_h = Scheduler(store_h, queues_h)
    engine_h = SolverEngine(store_h, queues_h, scheduler=sched_h,
                            mesh_mode="off")
    engine_h.use_sessions = False
    engine_h.pad_to = 64
    engine_h.drain(now=0.0)
    _churn_run(engine_h, store_h, sched_h, cycles=4)
    assert _admitted(store) == _admitted(store_h)


def test_sidecar_mesh_fault_serves_single_chip_and_trips(
        server, monkeypatch, eight_devices):
    """A sidecar-side mesh fault (device loss / SPMD compile abort)
    must not wedge the sidecar: the SAME request is served single-chip,
    the server mesh trips off (no per-request flapping), and the
    resident session state re-seeds unsharded."""
    path, srv = server
    if srv.mesh is None:
        pytest.skip("no sidecar mesh detected")
    srv.mesh_min_workloads = 0

    from kueue_oss_tpu.solver import meshutil

    calls = {"n": 0}

    def boom(mesh, axis="wl"):
        calls["n"] += 1
        raise RuntimeError("injected sidecar mesh loss")

    store = _store(preemption=False)
    for i in range(48):
        store.add_workload(_wl(i))
    queues = QueueManager(store)
    sched = Scheduler(store, queues)
    engine = SolverEngine(store, queues, scheduler=sched,
                          remote=SolverClient(path))
    engine.pad_to = 64
    # a control plane looks for no mesh of its own: the first response
    # teaches it the sidecar's width, the next drain ships a shardable
    # axis, and THAT solve meets the mesh fault
    result = engine.drain(now=0.0)
    assert result.admitted == 32
    assert engine.remote.remote_mesh_devices == 8
    monkeypatch.setattr(meshutil, "lean_mesh_solver", boom)
    _churn_run(engine, store, sched, cycles=1)  # served despite the fault
    assert calls["n"] == 1
    # both seats the churn freed were refilled by the faulted solve
    assert sum(w.is_quota_reserved and not w.is_finished
               for w in store.workloads.values()) == 32
    assert srv.mesh is None, "sidecar mesh must trip off, not flap"
    sess = next(iter(srv.sessions.values()))
    assert not sess.device.mesh_placed
    # subsequent drains stay single-chip and never touch the mesh again
    _churn_run(engine, store, sched, cycles=1)
    assert calls["n"] == 1


def test_meshless_client_learns_sidecar_width_and_repads(server):
    """A control plane with NO local mesh (CPU-only host) must still
    let the accelerator sidecar shard: the session response advertises
    the sidecar's mesh width, the client records it, and the next
    drain re-pads to a shardable axis (one counted shape_change sync),
    after which the sidecar's resident state is mesh-placed."""
    path, srv = server
    if srv.mesh is None:
        pytest.skip("no sidecar mesh detected")
    srv.mesh_min_workloads = 0
    store = _store(preemption=False)
    for i in range(48):
        store.add_workload(_wl(i))
    queues = QueueManager(store)
    sched = Scheduler(store, queues)
    engine = SolverEngine(store, queues, scheduler=sched,
                          remote=SolverClient(path), mesh_mode="off")
    engine.pad_to = 64
    engine.drain(now=0.0)
    # first drain shipped an unaligned pow2+1 axis; the response taught
    # the client the sidecar's width
    assert engine.remote.remote_mesh_devices == 8
    sess0 = next(iter(srv.sessions.values()))
    assert not sess0.device.mesh_placed
    _churn_run(engine, store, sched, cycles=1)
    # second drain re-padded to a shardable axis: sidecar now sharded
    sess = next(iter(srv.sessions.values()))
    assert sess.device.mesh_placed
    assert sess.kwargs["wl_cqid"].shape[0] % 8 == 0


# ---------------------------------------------------------------------------
# sidecar session-store torn-tail kill point (persist/hooks.py
# "sidecar_session_store"; docs/ROBUSTNESS.md)
# ---------------------------------------------------------------------------


def test_sidecar_torn_delta_crash_point_heals_byte_identical(server):
    """RAISE-mode torn tail: the crash point fires after a DELTA's
    dirty rows were applied to the sidecar's resident session but
    before the epoch advanced — torn state the next drain must heal
    with a full SYNC that rebuilds BYTE-IDENTICAL session state."""
    from kueue_oss_tpu.persist import hooks as persist_hooks
    from kueue_oss_tpu.solver.resilience import SolverUnavailable

    path, srv = server
    store = _store()
    for i in range(48):
        store.add_workload(_wl(i))
    queues = QueueManager(store)
    sched = Scheduler(store, queues)
    engine = SolverEngine(store, queues, scheduler=sched,
                          remote=SolverClient(path))
    engine.pad_to = 128
    engine.drain(now=0.0)
    _churn_run(engine, store, sched, cycles=2)
    assert engine.remote.frames_by_kind.get("delta", 0) >= 1

    persist_hooks.arm("sidecar_session_store", mode=persist_hooks.RAISE)
    try:
        with pytest.raises(SolverUnavailable):
            _churn_run(engine, store, sched, cycles=1)
    finally:
        persist_hooks.disarm()
    # torn: the delta's rows were applied but the epoch never advanced
    # — the session records an epoch whose state it no longer holds, so
    # the next DELTA against it cannot apply cleanly
    sidecar = next(iter(srv.sessions.values()))
    host_sess = engine._delta_sessions["full"]
    assert sidecar.epoch < host_sess.epoch

    # the next drain heals through a full SYNC (stale-epoch client
    # fallback); the rebuilt state is byte-identical to the host's
    _churn_run(engine, store, sched, cycles=1)
    sidecar = next(iter(srv.sessions.values()))
    host_kwargs, host_meta = engine._delta_sessions["full"]._last
    assert sidecar.meta == host_meta
    for name, arr in host_kwargs.items():
        if arr is None:
            assert sidecar.kwargs[name] is None, name
        else:
            assert np.array_equal(sidecar.kwargs[name], arr), name
    assert (state_checksum(sidecar.kwargs, sidecar.meta)
            == state_checksum(host_kwargs, host_meta))
    # steady state resumes on deltas against the healed base
    deltas0 = engine.remote.frames_by_kind.get("delta", 0)
    _churn_run(engine, store, sched, cycles=1)
    assert engine.remote.frames_by_kind.get("delta", 0) == deltas0 + 1


def _spawn_sidecar(path, crash_env=None):
    """A real sidecar subprocess (arming crash points from its env),
    ready once its socket accepts."""
    import socket as socket_mod
    import subprocess
    import sys
    import time as time_mod

    code = (
        "import os\n"
        "from kueue_oss_tpu.persist import hooks\n"
        "hooks.arm_from_env()\n"
        "from kueue_oss_tpu.solver.service import SolverServer\n"
        f"SolverServer({path!r}, mesh_mode='off').serve_forever()\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(crash_env or {})
    proc = subprocess.Popen([sys.executable, "-c", code], env=env)
    deadline = time_mod.monotonic() + 60
    while time_mod.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError("sidecar subprocess died during startup")
        try:
            s = socket_mod.socket(socket_mod.AF_UNIX,
                                  socket_mod.SOCK_STREAM)
            s.connect(path)
            s.close()
            return proc
        except OSError:
            time_mod.sleep(0.1)
    proc.kill()
    raise RuntimeError("sidecar subprocess never came up")


def test_sidecar_sigkill_torn_session_resync_rebuilds(tmp_path):
    """Real SIGKILL torn tail + session_missing RESYNC, end to end:

    1. the armed crash point SIGKILLs the sidecar mid-DELTA (rows
       applied, epoch not advanced — the torn state dies with the
       process, exactly like a power cut);
    2. a restarted sidecar is rebuilt through a full SYNC, and the
       NEXT delta applying cleanly (server-side state_checksum
       verified) proves the rebuilt session state is byte-identical
       to the host's mirror;
    3. a second SIGKILL between drains leaves the client in delta
       mode against an empty session store: the sidecar answers
       session_missing, the client RESYNCs in-call (counted), and
       steady-state deltas resume against the rebuilt state."""
    import signal

    from kueue_oss_tpu.solver.resilience import SolverUnavailable

    path = str(tmp_path / "sidecar.sock")
    store = _store(preemption=False)  # lean kernel: cheap subprocess
    for i in range(16):
        store.add_workload(_wl(i))
    queues = QueueManager(store)
    sched = Scheduler(store, queues)
    engine = SolverEngine(store, queues, scheduler=sched,
                          remote=SolverClient(path))
    engine.pad_to = 32

    proc = _spawn_sidecar(
        path, crash_env={"KUEUE_CRASH_POINT": "sidecar_session_store"})
    try:
        engine.drain(now=0.0)  # SYNC seeds the session
        assert engine.remote.frames_by_kind.get("sync") == 1
        # the first DELTA trips the kill point mid-apply: the sidecar
        # dies with torn session state and the drain degrades
        with pytest.raises(SolverUnavailable):
            _churn_run(engine, store, sched, cycles=1, churn=1)
        proc.wait(timeout=30)
        assert proc.returncode == -signal.SIGKILL
    finally:
        if proc.poll() is None:
            proc.kill()

    proc = _spawn_sidecar(path)
    try:
        # stale-epoch client -> full SYNC rebuild on the fresh sidecar
        syncs0 = engine.remote.frames_by_kind.get("sync", 0)
        _churn_run(engine, store, sched, cycles=1, churn=1)
        assert engine.remote.frames_by_kind.get("sync", 0) == syncs0 + 1
        # a DELTA applying cleanly against the rebuilt base (the
        # sidecar verifies state_checksum over EVERY array) proves the
        # rebuilt session state is byte-identical to the host's
        deltas0 = engine.remote.frames_by_kind.get("delta", 0)
        resyncs0 = metrics.solver_resync_total.total()
        _churn_run(engine, store, sched, cycles=1, churn=1)
        assert engine.remote.frames_by_kind.get("delta", 0) == deltas0 + 1
        assert metrics.solver_resync_total.total() == resyncs0

        # plain SIGKILL between drains: client stays in delta mode,
        # the fresh sidecar has no session -> in-band RESYNC
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()

    proc = _spawn_sidecar(path)
    try:
        missing0 = metrics.solver_resync_total.collect().get(
            ("session_missing",), 0)
        resync_frames0 = engine.remote.frames_by_kind.get("resync", 0)
        _churn_run(engine, store, sched, cycles=1, churn=1)
        assert metrics.solver_resync_total.collect().get(
            ("session_missing",), 0) == missing0 + 1
        assert engine.remote.frames_by_kind.get(
            "resync", 0) == resync_frames0 + 1
        # and deltas resume against the RESYNC-rebuilt state
        deltas0 = engine.remote.frames_by_kind.get("delta", 0)
        _churn_run(engine, store, sched, cycles=1, churn=1)
        assert engine.remote.frames_by_kind.get("delta", 0) == deltas0 + 1
    finally:
        proc.kill()
        proc.wait(timeout=30)
