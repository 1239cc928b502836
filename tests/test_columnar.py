"""Columnar export path (solver/columnar.py): bit-identity + scale.

The ColumnarStore keeps the export tensors as incrementally-maintained
flat columns, updated in place from ExportCache invalidation events, so
an unchanged-store re-export is an O(dirty) refresh instead of the
classic O(W) per-row dict walk. Its contract is strict bit-identity:
every export it serves must equal the classic walk's output field for
field — dtype, shape, and content.

Covered here:
- randomized churn replay: arrivals, touches, priority/timestamp
  edits, finishes, quota edits and node flaps in random order, with a
  classic-twin comparison after every event batch;
- the delta-session fast path: HostDeltaSession.advance with a
  columnar hint vs the classic content-diff advance, and the emitted
  DELTA frames replayed onto a wire-state mirror;
- scale: the 50k x 1k smoke (tier-1) and the 1M x 10k megascale
  variant (slow lane), both asserting the unchanged-store re-export
  beats the classic walk by the documented margin.
"""

import copy
import dataclasses
import random
import time

import numpy as np
import pytest

from kueue_oss_tpu.api.types import (
    Admission,
    ClusterQueue,
    Cohort,
    FlavorQuotas,
    LocalQueue,
    Node,
    PodSet,
    PodSetAssignment,
    PreemptionPolicy,
    QueueingStrategy,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
    WorkloadConditionType,
)
from kueue_oss_tpu.core.queue_manager import QueueManager
from kueue_oss_tpu.core.store import Store
from kueue_oss_tpu.solver.delta import (
    HostDeltaSession,
    apply_delta,
    problem_wire_state,
)
from kueue_oss_tpu.solver.tensors import (
    ExportCache,
    export_problem,
    pad_workloads,
)


def make_cq(name, nominal, cohort=None, bl=None, flavors=None):
    fqs = flavors or [FlavorQuotas(name="default", resources=[
        ResourceQuota(name="cpu", nominal=nominal, borrowing_limit=bl)])]
    return ClusterQueue(
        name=name, cohort=cohort,
        resource_groups=[ResourceGroup(
            covered_resources=["cpu"], flavors=fqs)],
        queueing_strategy=QueueingStrategy.BEST_EFFORT_FIFO,
        preemption=PreemptionPolicy())


def build_store():
    store = Store()
    for f in ("default", "small", "large"):
        store.upsert_resource_flavor(ResourceFlavor(name=f))
    store.upsert_node(Node(name="n1", allocatable={"cpu": 100000}))
    store.upsert_cohort(Cohort(name="co"))
    for cq in (make_cq("a", 2000, cohort="co"),
               make_cq("b", 1000, cohort="co", bl=0),
               make_cq("c", 3000),
               make_cq("m", 0, flavors=[
                   FlavorQuotas(name="small", resources=[
                       ResourceQuota(name="cpu", nominal=1500)]),
                   FlavorQuotas(name="large", resources=[
                       ResourceQuota(name="cpu", nominal=4000)])])):
        store.upsert_cluster_queue(cq)
        store.upsert_local_queue(
            LocalQueue(name=f"lq-{cq.name}", cluster_queue=cq.name))
    return store


def submit(store, name, cq, t, uid, cpu=500, prio=0):
    store.add_workload(Workload(
        name=name, queue_name=f"lq-{cq}", priority=prio,
        creation_time=t, uid=uid,
        podsets=[PodSet(count=1, requests={"cpu": cpu})]))


def backlog(qm):
    return {name: q.snapshot_order()
            for name, q in sorted(qm.queues.items())}


def assert_problems_equal(classic, col, label):
    for f in dataclasses.fields(classic):
        a, b = getattr(classic, f.name), getattr(col, f.name)
        if isinstance(a, np.ndarray):
            assert a.dtype == b.dtype, (label, f.name, a.dtype, b.dtype)
            assert a.shape == b.shape, (label, f.name, a.shape, b.shape)
            assert np.array_equal(a, b), (label, f.name)
        else:
            assert a == b, (label, f.name, a, b)


class TestChurnReplay:
    """Randomized event-batch replay: after every batch the columnar
    export must be bit-identical to the classic walk on the SAME cache
    (shared rows, so the comparison isolates the assembly path)."""

    @pytest.mark.parametrize("seed", [3, 17, 29])
    def test_columnar_matches_classic_under_churn(self, seed):
        rng = random.Random(seed)
        store = build_store()
        qm = QueueManager(store)
        cache = ExportCache(store)
        assert cache.columnar is not None
        uid = [100]
        live = []
        for _ in range(16):
            uid[0] += 1
            name = f"w{uid[0]}"
            submit(store, name, rng.choice("abcm"), float(uid[0]),
                   uid[0], cpu=100 * (1 + uid[0] % 4))
            live.append(f"default/{name}")

        def arrival():
            uid[0] += 1
            name = f"w{uid[0]}"
            submit(store, name, rng.choice("abcm"), float(uid[0]),
                   uid[0], cpu=100 * (1 + uid[0] % 4),
                   prio=rng.choice([0, 0, 3]))
            live.append(f"default/{name}")

        def touch():
            if live:
                store.update_workload(
                    store.workloads[rng.choice(live)])

        def prio_change():
            if live:
                wl = store.workloads[rng.choice(live)]
                wl.priority = rng.randint(0, 5)
                store.update_workload(wl)

        def ts_change():
            if live:
                wl = store.workloads[rng.choice(live)]
                wl.creation_time = rng.uniform(0.0, 500.0)
                store.update_workload(wl)

        def req_change():
            if live:
                wl = store.workloads[rng.choice(live)]
                wl.podsets[0].requests["cpu"] = rng.choice(
                    [100, 250, 400, 900])
                store.update_workload(wl)

        def finish():
            if len(live) > 4:
                store.delete_workload(
                    live.pop(rng.randrange(len(live))))

        def quota_edit():
            store.upsert_cluster_queue(make_cq(
                "a", rng.choice([1800, 2000, 2400]), cohort="co"))

        def node_flap():
            store.upsert_node(Node(
                name="n1",
                allocatable={"cpu": rng.choice([80000, 100000])}))

        ops = [arrival, arrival, touch, prio_change, ts_change,
               req_change, finish, quota_edit, node_flap]
        modes = set()
        for batch in range(25):
            # some batches are empty: the unchanged-store re-export
            # (cached mode) must hold bit-identity too
            for _ in range(rng.randint(0, 4)):
                rng.choice(ops)()
            pending = backlog(qm)
            col = export_problem(store, pending, cache=cache, now=1.0)
            hint = getattr(col, "_columnar_hint", None)
            if hint is not None:
                modes.add(hint.mode)
            classic = export_problem(store, pending, cache=cache,
                                     now=1.0, columnar=False)
            assert_problems_equal(classic, col, f"seed{seed}/b{batch}")
        # the replay must have exercised the interesting paths, not
        # just fall back to full rebuilds every batch
        assert "cached" in modes or "scatter" in modes, modes


class TestSessionFastPath:
    """HostDeltaSession.advance with a columnar hint vs the classic
    content-diff advance: identical slotted problems, and the emitted
    DELTA frames must replay a wire-state mirror to the same tensors."""

    def test_hint_advance_matches_classic_and_replays(self):
        store = build_store()
        qm = QueueManager(store)
        cache = ExportCache(store)
        for i in range(12):
            submit(store, f"wl-{i}", "abcm"[i % 4], float(i), 1000 + i,
                   cpu=100 + (i % 3) * 50, prio=i % 2)

        sess_fast = HostDeltaSession(cache=cache)
        sess_classic = HostDeltaSession(cache=None)
        mirror = {}

        def step(label, mutate=None):
            if mutate is not None:
                mutate()
            pending = backlog(qm)
            prob = export_problem(store, pending, cache=cache, now=1.0)
            hint = getattr(prob, "_columnar_hint", None)
            padded = pad_workloads(prob, 32)
            twin = dataclasses.replace(padded, **{
                f.name: (np.array(getattr(padded, f.name))
                         if isinstance(getattr(padded, f.name),
                                       np.ndarray)
                         else copy.deepcopy(getattr(padded, f.name)))
                for f in dataclasses.fields(padded)})
            sa, fa = sess_fast.advance(padded, hint=hint)
            sb, fb = sess_classic.advance(twin)
            assert_problems_equal(sb, sa, label)
            if fa.delta is None:
                kw, meta = problem_wire_state(sa)
                mirror["kw"] = copy.deepcopy(kw)
                mirror["meta"] = dict(meta)
            else:
                apply_delta(mirror["kw"], mirror["meta"], fa.delta)
                kb, mb = problem_wire_state(sb)
                for name, arr in kb.items():
                    if arr is not None:
                        assert np.array_equal(mirror["kw"][name],
                                              arr), (label, name)
                assert mirror["meta"] == mb, label

        step("first")
        step("unchanged")
        step("touch", lambda: store.update_workload(
            store.workloads["default/wl-3"]))
        step("unchanged2")

        def prio():
            wl = store.workloads["default/wl-5"]
            wl.priority = 9
            store.update_workload(wl)
        step("prio", prio)
        step("arrival", lambda: submit(
            store, "wl-new", "a", 99.0, 9999, cpu=200))
        step("unchanged3")

        def ts():
            wl = store.workloads["default/wl-7"]
            wl.creation_time = 55.5
            store.update_workload(wl)
        step("ts", ts)
        step("unchanged4")
        assert sess_fast.fast_advances >= 3, sess_fast.fast_advances


def _scale_harness(n_wl, n_cqs, min_speedup, identity_fields):
    """Flat n_wl x n_cqs store: classic-walk vs columnar-cached
    re-export wall + bit-identity on the given field subset."""
    store = Store()
    store.upsert_resource_flavor(ResourceFlavor(name="default"))
    store.upsert_node(Node(name="n1", allocatable={"cpu": 10 ** 12}))
    for c in range(n_cqs):
        store.upsert_cluster_queue(make_cq(f"cq{c:05d}", 10_000_000))
        store.upsert_local_queue(LocalQueue(
            name=f"lq-cq{c:05d}", cluster_queue=f"cq{c:05d}"))
    per_cq = max(1, n_wl // n_cqs)
    for i in range(n_wl):
        c = min(i // per_cq, n_cqs - 1)
        submit(store, f"w{i}", f"cq{c:05d}", float(i) * 1e-3, i + 1,
               cpu=100 + (i % 5) * 50)
    qm = QueueManager(store)
    cache = ExportCache(store)
    assert cache.columnar is not None
    pending = backlog(qm)

    # classic walk, warmed rows (the steadier, stricter baseline)
    export_problem(store, pending, cache=cache, now=1.0,
                   columnar=False)
    t0 = time.perf_counter()
    classic = export_problem(store, pending, cache=cache, now=1.0,
                             columnar=False)
    walk_s = time.perf_counter() - t0

    export_problem(store, pending, cache=cache, now=1.0)  # build
    t0 = time.perf_counter()
    col = export_problem(store, pending, cache=cache, now=1.0)
    cached_s = time.perf_counter() - t0
    hint = getattr(col, "_columnar_hint", None)
    assert hint is not None and hint.mode == "cached", hint

    assert col.n_workloads == classic.n_workloads == n_wl
    assert col.wl_keys == classic.wl_keys
    for f in identity_fields:
        assert np.array_equal(getattr(col, f), getattr(classic, f)), f
    speedup = walk_s / max(cached_s, 1e-9)
    assert speedup >= min_speedup, (
        f"unchanged-store columnar re-export only {speedup:.1f}x the "
        f"classic walk (walk {walk_s * 1000:.1f}ms, cached "
        f"{cached_s * 1000:.2f}ms)")
    return speedup


IDENTITY_FIELDS = ("wl_cqid", "wl_rank", "wl_prio", "wl_ts", "wl_uid",
                   "wl_req", "wl_valid", "nominal", "usage0")


@pytest.mark.megascale
def test_smoke_50k_1k_cached_reexport_beats_walk():
    # tier-1 smoke: loose 2x bar — the CI margin, not the headline
    # (the 20x acceptance at 1M x 10k is the slow test below)
    _scale_harness(50_000, 1_000, 2.0, IDENTITY_FIELDS)


@pytest.mark.slow
@pytest.mark.megascale
def test_megascale_1m_10k_cached_reexport_beats_walk():
    _scale_harness(1_000_000, 10_000, 20.0, IDENTITY_FIELDS)


def test_afs_bailout_is_counted_and_stamped():
    """A columnar export that hands back to the classic walk must be
    ACCOUNTED: counted by reason in columnar_bailouts_total and stamped
    into last_stats (mode="bailout:<reason>") so the engine's export
    phase surfaces it in the cycle ledger — a silent per-cycle walk at
    megascale is a regression, not a fallback."""
    from kueue_oss_tpu import metrics
    from kueue_oss_tpu.api.types import AdmissionScope
    from kueue_oss_tpu.config.configuration import (
        AdmissionFairSharingConfig,
    )
    from kueue_oss_tpu.core.afs import AfsManager

    store = build_store()
    cq = store.cluster_queues["a"]
    cq.admission_scope = AdmissionScope()
    store.upsert_cluster_queue(cq)
    afs = AfsManager(AdmissionFairSharingConfig())
    qm = QueueManager(store, afs=afs)
    cache = ExportCache(store)
    for i in range(4):
        submit(store, f"w{i}", "a", float(i), 100 + i)
    before = metrics.columnar_bailouts_total.collect().get(
        ("afs_active",), 0)
    problem = export_problem(store, backlog(qm), cache=cache,
                             afs=afs, now=1.0)
    assert problem is not None, "the classic walk still serves the export"
    assert metrics.columnar_bailouts_total.collect().get(
        ("afs_active",), 0) == before + 1
    stats = cache.columnar.last_stats
    assert stats["mode"] == "bailout:afs_active"
    assert stats["rows"] == 0 and stats["dirty_rows"] == 0


class TestAdmittedRowGranular:
    """Admitted-section churn must ride the scatter path: content
    edits to admitted workloads (priority, requests/usage, admission
    timestamp) patch O(dirty) rows instead of retiring the whole
    section, and unrelated pending events must not rebuild it either —
    all while staying bit-identical to the classic walk."""

    def _admit(self, store, name, cq, t, uid, cpu=500):
        submit(store, name, cq, t, uid, cpu=cpu)
        wl = store.workloads[f"default/{name}"]
        wl.status.admission = Admission(
            cluster_queue=cq,
            podset_assignments=[PodSetAssignment(
                name="main", flavors={"cpu": "default"},
                resource_usage=dict(wl.podsets[0].total_requests()),
                count=1)])
        wl.set_condition(WorkloadConditionType.QUOTA_RESERVED, True,
                         reason="QuotaReserved", now=t)
        store.update_workload(wl)
        return wl

    def _setup(self):
        store = build_store()
        qm = QueueManager(store)
        cache = ExportCache(store)
        for i in range(6):
            submit(store, f"p{i}", "abcm"[i % 4], float(i), 200 + i,
                   cpu=100 * (1 + i % 3))
        for i in range(8):
            self._admit(store, f"ad{i}", "abc"[i % 3], 50.0 + i,
                        300 + i, cpu=250 * (1 + i % 3))
        pending = backlog(qm)
        warm = export_problem(store, pending, cache=cache, now=1.0,
                              include_admitted=True)
        assert warm is not None
        return store, qm, cache

    def _export_both(self, store, qm, cache, label):
        pending = backlog(qm)
        col = export_problem(store, pending, cache=cache, now=1.0,
                             include_admitted=True)
        classic = export_problem(store, pending, cache=cache, now=1.0,
                                 include_admitted=True, columnar=False)
        assert_problems_equal(classic, col, label)
        return col

    def test_admitted_content_churn_scatters(self):
        store, qm, cache = self._setup()
        wl = store.workloads["default/ad3"]
        wl.priority = 7
        wl.podsets[0].requests["cpu"] = 950
        wl.status.admission.podset_assignments[0].resource_usage = (
            dict(wl.podsets[0].total_requests()))
        cond = wl.status.conditions[
            WorkloadConditionType.QUOTA_RESERVED]
        cond.last_transition_time = 321.0
        store.update_workload(wl)
        col = self._export_both(store, qm, cache, "admitted-churn")
        stats = cache.columnar.last_stats
        assert stats["mode"] == "scatter", stats
        assert stats["dirty_rows"] == 1, stats
        assert stats["blocks_rebuilt"] == 0, stats
        # the patched row actually landed: admitted usage & admit rank
        # reflect the edit (sanity on top of the twin compare)
        pos = col.wl_keys.index("default/ad3")
        assert col.wl_raw_admit_ts[pos] == 321.0
        assert col.wl_prio[pos] == 7

    def test_pending_churn_keeps_admitted_block(self):
        store, qm, cache = self._setup()
        wl = store.workloads["default/p2"]
        wl.priority = 4
        store.update_workload(wl)
        self._export_both(store, qm, cache, "pending-churn")
        stats = cache.columnar.last_stats
        # the pending block's content-only rebuild is expected (its
        # infos were re-wrapped); the admitted section must NOT be
        # rebuilt, which is what keeps this on the scatter path —
        # before row-granular revalidation this forced an assemble
        assert stats["mode"] == "scatter", stats
        assert stats["blocks_rebuilt"] == 1, stats

    def test_admitted_membership_change_assembles(self):
        store, qm, cache = self._setup()
        self._admit(store, "ad-new", "b", 99.0, 400)
        self._export_both(store, qm, cache, "admitted-join")
        stats = cache.columnar.last_stats
        assert stats["mode"] == "assemble", stats
        # release one: membership shrinks, still bit-identical
        store.delete_workload("default/ad1")
        self._export_both(store, qm, cache, "admitted-release")
        assert cache.columnar.last_stats["mode"] == "assemble"

    def test_admitted_churn_burst_random(self):
        rng = random.Random(11)
        store, qm, cache = self._setup()
        for batch in range(12):
            for _ in range(rng.randint(1, 3)):
                name = f"ad{rng.randrange(8)}"
                wl = store.workloads.get(f"default/{name}")
                if wl is None:
                    continue
                roll = rng.random()
                if roll < 0.4:
                    wl.priority = rng.randint(0, 9)
                elif roll < 0.8:
                    wl.podsets[0].requests["cpu"] = rng.choice(
                        [250, 500, 750, 950])
                    psa = wl.status.admission.podset_assignments[0]
                    psa.resource_usage = dict(
                        wl.podsets[0].total_requests())
                else:
                    wl.status.conditions[
                        WorkloadConditionType.QUOTA_RESERVED
                    ].last_transition_time = rng.uniform(10.0, 400.0)
                store.update_workload(wl)
            self._export_both(store, qm, cache, f"burst-b{batch}")
            assert cache.columnar.last_stats["mode"] == "scatter"
