"""Balanced placement on the device against the host tree.

With ``TASBalancedPlacement`` on, a preferred-level request goes through
tas_balanced_placement.go's path on the host (tas/snapshot.py
``_find_best_balanced`` / ``_apply_balanced``). The dense port
(solver/tas_kernels.py ``_balanced_at_level``) has to give the same
domains and counts, tie-breaks included, at every step of a sequential
drain: seeded random trees (upstream's 1 x 10 x 64 at a reduced cpu and
two smaller shapes, nodes in a shuffled order so that the host's list
orders differ from the lexicographic one), random fills, gang sizes and
levels. Then the drain's batch: padded to its bucket it places as the
unpadded one does, two batch sizes of one bucket build one program, and
a placement that fails is no refused plan entry.
"""

import random

import numpy as np
import pytest

from kueue_oss_tpu import features, metrics
from kueue_oss_tpu.api.types import (
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    Node,
    PodSet,
    PodSetTopologyRequest,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Topology,
    Workload,
)
from kueue_oss_tpu.solver.tas_kernels import place_podset_ext
from kueue_oss_tpu.tas.snapshot import (
    TASFlavorSnapshot,
    TASPodSetRequest,
    build_tas_flavor_snapshot,
    xlog2x_fixed,
)

HOST = "kubernetes.io/hostname"
BLOCK = "cloud/block"
RACK = "cloud/rack"
LEVELS = [BLOCK, RACK, HOST]


@pytest.fixture(autouse=True)
def _gate():
    features.set_gates({"TASBalancedPlacement": True})
    yield
    features.reset()


def make_nodes(blocks, racks, hosts, cpu):
    nodes = [Node(name=f"n-{b}-{r}-{h}",
                  labels={BLOCK: f"b{b}", RACK: f"b{b}-r{r}"},
                  allocatable={"cpu": cpu})
             for b in range(blocks) for r in range(racks)
             for h in range(hosts)]
    # one order per shape: the host's children and level orders then
    # differ from the lexicographic one, and a shape compiles once
    random.Random(blocks * 1000 + racks * 10 + hosts).shuffle(nodes)
    return nodes


def host_place(snap, count, per_pod, level):
    ps = PodSet(name="main", count=count, requests=dict(per_pod),
                topology_request=PodSetTopologyRequest(preferred=level))
    res = snap.find_topology_assignments([TASPodSetRequest(
        podset=ps, single_pod_requests=dict(per_pod), count=count,
        flavor="default")])
    ta = res["main"].assignment
    return None if ta is None else {
        tuple(d.values): d.count for d in ta.domains}


def kernel_place(snap, count, per_pod, level):
    out = place_podset_ext(snap, {**per_pod, "pods": 1}, count,
                           LEVELS.index(level), balanced=True)
    if out is None:
        return None
    return {(leaf[-1],): c for leaf, c in out[0].items()}


#: (blocks, racks, hosts, cpu per host in milli-cpu, steps of a drain)
SHAPES = {"1x10x64": (1, 10, 64, 12_000, 10),
          "2x3x4": (2, 3, 4, 8_000, 14),
          "1x4x6": (1, 4, 6, 8_000, 14)}


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_balanced_sequential_drain_matches_host(shape, seed):
    blocks, racks, hosts, cpu, steps = SHAPES[shape]
    rng = random.Random(7000 + seed)
    nodes = make_nodes(blocks, racks, hosts, cpu)
    trees = [build_tas_flavor_snapshot("default", LEVELS, list(nodes))
             for _ in range(2)]
    fill = rng.choice([0.1, 0.5, 0.8])
    for n in nodes:
        if rng.random() < fill:
            used = rng.randint(1, cpu // 1000 - 1)
            for t in trees:
                t.add_tas_usage((n.labels[BLOCK], n.labels[RACK], n.name),
                                {"cpu": 1000}, used)
    balanced_steps = 0
    for step in range(steps):
        count = rng.randint(1, 24)
        per_pod = {"cpu": rng.choice([500, 1000, 2000])}
        level = rng.choice([RACK, RACK, HOST, BLOCK])
        h = host_place(trees[0], count, per_pod, level)
        k = kernel_place(trees[1], count, per_pod, level)
        assert k == h, (shape, seed, step, count, per_pod, level)
        if h is None:
            continue
        balanced_steps += 1
        for values, c in h.items():
            for t in trees:
                t.add_tas_usage(values, per_pod, c)
    assert balanced_steps


def test_balanced_spreads_where_best_fit_packs():
    """The arithmetic is the balanced one, not a best fit that happens
    to be lawful: 6 pods asked at rack level over racks of three 4-cpu
    hosts sit 3 + 3 under balanced placement (two hosts cover the gang,
    so the threshold is 6 // 2) and 4 + 2 under best fit."""
    nodes = make_nodes(1, 2, 3, 4000)
    snap = build_tas_flavor_snapshot("default", LEVELS, list(nodes))
    k = kernel_place(snap, 6, {"cpu": 1000}, RACK)
    assert k == host_place(snap, 6, {"cpu": 1000}, RACK)
    assert sorted(k.values()) == [3, 3]
    assert len({host.split("-")[2] for (host,) in k}) == 1   # one rack
    features.set_gates({"TASBalancedPlacement": False})
    assert sorted(host_place(snap, 6, {"cpu": 1000}, RACK).values()) == [2, 4]


@pytest.mark.parametrize("sizes", [[3, 3, 2], [2, 3, 3], [4, 4], [8],
                                   [1, 1, 1, 1, 1, 1, 1, 1]])
def test_entropy_is_one_fixed_point_sum_on_both_sides(sizes):
    """The host's entropy is log2(T) - sum(xlog2x_fixed) / (T * 2**22):
    splits of one total order by an integer, whatever their order."""
    from kueue_oss_tpu.tas.snapshot import XLOG_SHIFT

    import math

    e = TASFlavorSnapshot._entropy(sizes)
    assert e == TASFlavorSnapshot._entropy(sorted(sizes))
    total = sum(sizes)
    exact = -sum(s / total * math.log2(s / total) for s in sizes if s)
    assert abs(e - exact) < 1e-5
    assert e == math.log2(total) - sum(map(xlog2x_fixed, sizes)) / (
        total * (1 << XLOG_SHIFT))


def _float_entropy(sizes):
    """The host tree's entropy before PR 32 (upstream's float sum, in
    list order)."""
    import math

    total = sum(sizes)
    e = 0.0
    for s in sizes:
        if s > 0:
            p = s / total
            e += -p * math.log2(p)
    return e


def _sign(x):
    return (x > 0) - (x < 0)


def test_fixed_point_entropy_orders_as_the_float_wherever_that_is_clear():
    """Entropy only breaks ties between domains of ONE total capacity
    (``_select_optimal_set``'s sort key comes after the capacity). For
    every pair of different splits of one total, up to 4 children of up
    to 20 pods, the fixed-point order IS the float order: no two of them
    lie closer than the float's own noise. Where the two differ: a
    permutation of one split, which the float sums in list order (so it
    may differ in its last bits and then orders by them) and the fixed
    point ties exactly, leaving the order to the next key, the tree's
    list order; and an exact tie of two different splits, which both
    sides order arbitrarily."""
    import itertools
    import math

    by_total = {}
    for k in range(1, 5):
        for split in itertools.combinations_with_replacement(
                range(1, 21), k):
            by_total.setdefault(sum(split), []).append(split)
    entropy = TASFlavorSnapshot._entropy
    pairs = exact_ties = 0
    for splits in by_total.values():
        fixed = [entropy(list(sp)) for sp in splits]
        flt = [_float_entropy(sp) for sp in splits]
        for i, j in itertools.combinations(range(len(splits)), 2):
            pairs += 1
            d = flt[i] - flt[j]
            if abs(d) > 1e-12:
                assert _sign(fixed[i] - fixed[j]) == _sign(d), (
                    splits[i], splits[j])
            else:
                # the float cannot tell them apart because the two are
                # an exact tie (3,3,4 and 1,1,2,6: 3**3 * 3**3 * 4**4
                # == 6**6 * 2**2): the float orders such a pair by its
                # last bits, the fixed point by the rounding of its
                # terms, both arbitrarily, the fixed point the same way
                # on host and device
                assert (math.prod(s ** s for s in splits[i])
                        == math.prod(s ** s for s in splits[j]))
                assert abs(fixed[i] - fixed[j]) < 1e-6
                exact_ties += 1
    assert pairs > 1_000_000 and 0 < exact_ties < pairs // 1000
    # the named difference: one split in two orders
    a, b = [7, 3, 5, 1, 9], [9, 1, 5, 3, 7]
    assert entropy(a) == entropy(b)


@pytest.mark.parametrize("children, top", [(64, 110), (64, 19), (10, 32)])
def test_fixed_point_entropy_at_the_trees_sizes(children, top):
    """At the sizes in use (upstream's rack: 64 hosts of up to 110 small,
    48 medium or 19 large pods; 10 racks) near-ties are made on purpose:
    one pod moved between two children. The fixed-point order equals
    the float order wherever the float difference exceeds the fixed
    point's rounding (children x 2**-(XLOG_SHIFT + 1) bits over the
    total), and no pair under that bound was met."""
    import random

    from kueue_oss_tpu.tas.snapshot import XLOG_SHIFT

    rng = random.Random(children * 1000 + top)
    entropy = TASFlavorSnapshot._entropy
    under = 0
    for _ in range(3000):
        a = [rng.randint(0, top) for _ in range(children)]
        b = list(a)
        for _move in range(rng.randint(1, 3)):
            i, j = rng.sample(range(children), 2)
            if b[i] > 0 and b[j] < top:
                b[i] -= 1
                b[j] += 1
        if sorted(a) == sorted(b) or sum(a) == 0:
            continue
        d = _float_entropy(a) - _float_entropy(b)
        bound = children * 2.0 ** -(XLOG_SHIFT + 1) / sum(a) * 2
        if abs(d) <= bound:
            under += 1
            continue
        assert _sign(entropy(a) - entropy(b)) == _sign(d), (a, b)
    assert under == 0


# ---------------------------------------------------------------------------
# the drain's batch
# ---------------------------------------------------------------------------


def tas_store(racks=2, hosts=2, cpu=4000, quota=100_000):
    from kueue_oss_tpu.core.store import Store

    store = Store()
    store.upsert_topology(Topology(name="default",
                                   levels=[BLOCK, RACK, HOST]))
    store.upsert_resource_flavor(ResourceFlavor(
        name="tas", topology_name="default"))
    for r in range(racks):
        for h in range(hosts):
            store.upsert_node(Node(
                name=f"n-{r}-{h}", labels={BLOCK: "b0", RACK: f"r{r}"},
                allocatable={"cpu": cpu}))
    store.upsert_cluster_queue(ClusterQueue(
        name="cq", resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name="tas", resources=[
                ResourceQuota(name="cpu", nominal=quota)])])]))
    store.upsert_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    return store


def gang(i, pods, mode="preferred", cpu=1000):
    request = (PodSetTopologyRequest(required=RACK) if mode == "required"
               else PodSetTopologyRequest(preferred=RACK))
    return Workload(name=f"g{i}", queue_name="lq", uid=i + 1,
                    creation_time=float(i),
                    podsets=[PodSet(name="main", count=pods,
                                    requests={"cpu": cpu},
                                    topology_request=request)])


def batch_items(store, n):
    from kueue_oss_tpu.core.workload_info import WorkloadInfo

    rng = random.Random(n)
    items = []
    for i in range(n):
        wl = gang(i, rng.randint(1, 6),
                  rng.choice(["required", "preferred"]))
        store.add_workload(wl)
        items.append((WorkloadInfo(wl, cluster_queue="cq"), "tas"))
    return items


def as_sets(placements):
    return {k: None if ta is None else sorted(
        (tuple(d.values), d.count) for d in ta.domains)
        for k, ta in placements.items()}


def test_padded_batch_places_as_the_unpadded_one():
    """Five rows padded to the bucket of 16 give what the placer gives
    on exactly five rows (the padded rows have ``count`` 0 and place
    nothing), and what the host tree gives row by row."""
    import jax.numpy as jnp

    from kueue_oss_tpu.core.snapshot import build_snapshot
    from kueue_oss_tpu.solver import tas_engine
    from kueue_oss_tpu.solver.tas_kernels import (
        build_levels,
        make_sequential_placer_ext,
    )

    store = tas_store(racks=3, hosts=4, cpu=6000)
    items = batch_items(store, 5)
    snapshot = build_snapshot(store)
    placer = tas_engine.DeviceTASPlacer(store)
    got = as_sets(placer.place_batch(snapshot, items))

    levels = build_levels(snapshot.tas_flavors["tas"])
    exact = make_sequential_placer_ext(
        levels.parents, levels.ranks, tas_engine.BALANCED_MAX_COUNT)
    rows = placer._rows(levels, 5)
    ridx = {r: j for j, r in enumerate(levels.resources)}
    for m, (info, _f) in enumerate(items):
        ps = info.obj.podsets[0]
        rows["per_pod"][m, ridx["cpu"]] = ps.requests["cpu"]
        rows["per_pod"][m, ridx["pods"]] = 1
        rows["count"][m] = ps.count
        rows["level"][m] = 1
        rows["required"][m] = ps.topology_request.required is not None
        rows["balanced"][m] = not rows["required"][m]
    sels, _lead, oks, _cap = exact(
        jnp.asarray(levels.leaf_capacity), rows["per_pod"], rows["count"],
        rows["level"], rows["required"], rows["unconstrained"],
        rows["least_free"], rows["sl_size"], rows["sl_level"],
        jnp.zeros_like(rows["per_pod"]), jnp.zeros((5,), dtype=bool),
        rows["balanced"])
    sels, oks = np.asarray(sels), np.asarray(oks)
    tree = build_snapshot(store).tas_flavors["tas"]
    for m, (info, _f) in enumerate(items):
        want = None if not oks[m] else sorted(
            ((levels.leaf_names[d][-1],), int(sels[m, d]))
            for d in np.nonzero(sels[m])[0])
        assert got[info.key] == want, m
        ps = info.obj.podsets[0]
        ta = tree.find_topology_assignments([TASPodSetRequest(
            podset=ps, single_pod_requests=dict(ps.requests),
            count=ps.count, flavor="tas")])["main"].assignment
        assert got[info.key] == (None if ta is None else sorted(
            (tuple(d.values), d.count) for d in ta.domains)), m
        if ta is not None:
            for d in ta.domains:
                tree.add_tas_usage(d.values, dict(ps.requests), d.count)


def test_two_batch_sizes_of_one_bucket_build_one_program():
    from kueue_oss_tpu.core.snapshot import build_snapshot
    from kueue_oss_tpu.solver import tas_engine
    from kueue_oss_tpu.solver.tas_kernels import (
        build_levels,
        sequential_placer_for,
    )

    assert [tas_engine.bucket_of(n) for n in (1, 16, 17, 64, 65, 5000)] == [
        16, 16, 64, 64, 256, 1024]
    store = tas_store(racks=3, hosts=5, cpu=6000)   # a tree of its own
    items = batch_items(store, 12)
    snapshot = build_snapshot(store)
    placer = tas_engine.DeviceTASPlacer(store)
    placer.place_batch(snapshot, items[:3])
    # the tree's first build traces the warm buckets at once
    assert placer.last_builds == len(tas_engine.WARM_BUCKETS)
    fn, _key, new = sequential_placer_for(
        build_levels(snapshot.tas_flavors["tas"]),
        tas_engine.BALANCED_MAX_COUNT)
    assert not new
    traced = fn._cache_size()
    assert traced == len(tas_engine.WARM_BUCKETS)
    placer.place_batch(snapshot, items[:7])
    placer.place_batch(snapshot, items)
    assert placer.last_builds == 0 and fn._cache_size() == traced
    # another engine's placer of the same tree: the process's programs
    other = tas_engine.DeviceTASPlacer(store)
    other.place_batch(snapshot, items[:9])
    assert other.last_builds == 0 and fn._cache_size() == traced


def test_a_failed_placement_is_no_refused_plan_entry():
    """Quota for all three gangs, nodes for one: the drain commits what
    it could place, leaves the others pending with their quota never
    charged, and counts no plan fallback."""
    from kueue_oss_tpu import obs
    from kueue_oss_tpu.core.queue_manager import QueueManager
    from kueue_oss_tpu.obs import spans
    from kueue_oss_tpu.solver.engine import SolverEngine

    store = tas_store(racks=1, hosts=1, cpu=4000)
    for i in range(3):
        store.add_workload(gang(i, 3, "required"))
    queues = QueueManager(store)
    engine = SolverEngine(store, queues)
    fallbacks0 = metrics.solver_plan_fallbacks_total.total()
    c0 = dict(spans.counters())
    result = engine.drain(now=0.0, verify=True)
    c1 = spans.counters()

    def moved(name):
        return c1.get(name, 0) - c0.get(name, 0)

    assert result.admitted == 1
    assert metrics.solver_plan_fallbacks_total.total() == fallbacks0
    assert moved("tas_device_placements") == 1
    assert moved("tas_place_failed") == 2
    assert moved("tas_placements") == 3
    held = [wl for wl in store.workloads.values() if wl.is_quota_reserved]
    assert len(held) == 1
    ta = held[0].status.admission.podset_assignments[0].topology_assignment
    assert [(d.values, d.count) for d in ta.domains] == [(["n-0-0"], 3)]
    # the books: one gang's quota, and the two others still pending
    from kueue_oss_tpu.core.snapshot import build_snapshot

    usage = build_snapshot(store).cluster_queues["cq"].node.usage
    assert dict(usage) == {("tas", "cpu"): 3000}
    pending = [k for k, wl in store.workloads.items()
               if not wl.is_quota_reserved]
    q = queues.queues["cq"]
    assert sorted(pending) == sorted(
        set(q._in_heap) | set(q.inadmissible))
    for key in pending:
        kinds = [ev.kind for ev in obs.recorder.explain(key)]
        assert obs.SOLVER_FALLBACK not in kinds
        assert obs.SKIPPED in kinds


def test_support_gate_keeps_what_the_port_does_not_cover_on_the_host():
    from kueue_oss_tpu.core.workload_info import WorkloadInfo
    from kueue_oss_tpu.solver import tas_engine

    store = tas_store()
    spec = store.cluster_queues["cq"]

    def supported(wl):
        return tas_engine.device_tas_supported(
            WorkloadInfo(wl, cluster_queue="cq"), store, spec)

    assert supported(gang(0, 20, "preferred"))
    assert supported(gang(1, 20, "required"))
    assert supported(gang(2, tas_engine.BALANCED_MAX_COUNT, "preferred"))
    assert not supported(gang(3, tas_engine.BALANCED_MAX_COUNT + 1,
                              "preferred"))
    assert supported(gang(4, tas_engine.BALANCED_MAX_COUNT + 1, "required"))
    sliced = gang(5, 4, "preferred")
    sliced.podsets[0].topology_request.podset_slice_required_topology = HOST
    sliced.podsets[0].topology_request.podset_slice_size = 2
    assert not supported(sliced)
    features.set_gates({"TASBalancedPlacement": False})
    assert supported(sliced)
    assert supported(gang(6, tas_engine.BALANCED_MAX_COUNT + 1, "preferred"))
