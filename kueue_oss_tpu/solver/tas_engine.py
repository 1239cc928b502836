"""Production device-TAS placement for the solver engine.

TAS workloads whose shapes the extended placer (solver/tas_kernels.py)
reproduces exactly are admitted by the quota kernel like any other
workload, then placed ON DEVICE by the sequential placer in admission
order: a single podset; required, preferred or unconstrained levels;
single-layer podset slices; the BestFit / LeastFreeCapacity profiles;
and, while TASBalancedPlacement is on, the balanced placement of a
preferred-level request with no slice and at most
``tas_kernels.BALANCED_MAX_COUNT`` pods (upstream's ``tas`` performance
deployment: gangs of 2 to 20 pods with rack-level requests). The host
tree remains the path of everything else: multi-layer slice
constraints, podset groups and leaders, partial admission, node
replacement, and balanced placement of sliced or larger gangs; one such
workload in a ClusterQueue's backlog keeps the whole queue on the host
(``SolverEngine._tas_device_ready``).

The batch of a drain is padded to a bucket (``bucket_of``: 16, 64, 256,
1024 rows, larger batches in chunks of 1024 with the capacity carried
on), the padded rows with ``count`` 0 as the pre-rejected rows already
are, so a process meets a handful of placer programs per tree; the
placer of a tree is the process's (``tas_kernels.sequential_placer_for``)
and its first build traces the buckets of ``WARM_BUCKETS`` at once, so
a stream's drains compile nothing.

A placement that fails is no refused plan entry: nothing unlawful was
planned (the kernel seats by quota, which the workload had), so the
admission simply is not committed, its quota is never charged, the
workload stays in its heap for the host cycle after the drain, and the
rest of the plan stands (``tas_place_failed``; an under-consumed plan
keeps every later entry valid).

Reference parity: scheduler.go:759-783 (TAS assignment after quota),
tas_flavor_snapshot.go:804-999 (findTopologyAssignment — the placer's
contract), tas_balanced_placement.go, clusterqueue_snapshot.go:191.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from kueue_oss_tpu.api.types import (
    TopologyAssignment,
    TopologyDomainAssignment,
)
from kueue_oss_tpu.core.workload_info import (
    WorkloadInfo,
    effective_per_pod_requests,
)

#: the largest gang the device places balanced (the table of
#: ``tas_kernels._first_found_subset`` is (count + 1)^2 and a level of
#: it costs O(domains x count)); a larger one keeps its queue on the host
BALANCED_MAX_COUNT = 32
#: batch sizes the sequential placer is compiled for; a larger batch
#: runs in chunks of the last
BUCKETS = (16, 64, 256, 1024)
#: the buckets a tree's first placer build traces at once (a stream's
#: drains seat tens of gangs, its first up to a cohort's seats)
WARM_BUCKETS = (16, 64, 256)


def bucket_of(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    return BUCKETS[-1]


def _topology_of_cq(store, spec) -> Optional[str]:
    """The single topology name shared by EVERY flavor of the CQ, or
    None when the CQ mixes TAS and non-TAS flavors (or topologies) —
    those keep the host path so the chosen option always needs the same
    tree."""
    topo = None
    for rg in spec.resource_groups:
        for fq in rg.flavors:
            fl = store.resource_flavors.get(fq.name)
            if fl is None or fl.topology_name is None:
                return None
            if topo is None:
                topo = fl.topology_name
            elif fl.topology_name != topo:
                return None
    return topo


def _is_unconstrained(ps) -> bool:
    """Host's unconstrained test (tas/snapshot.py _place:662-666),
    including the implied and slice-only forms."""
    tr = ps.topology_request
    if tr is None:
        return True  # implied request on a TAS-only CQ
    if tr.unconstrained:
        return True
    return (tr.podset_slice_required_topology is not None
            and tr.required is None and tr.preferred is None)


def device_tas_supported(info: WorkloadInfo, store, spec) -> bool:
    """Shape gate: can the extended device placer reproduce the host
    placement for this workload exactly?"""
    from kueue_oss_tpu import features

    if _topology_of_cq(store, spec) is None:
        return False
    if len(info.obj.podsets) != 1:
        return False  # leaders / groups / multi-podset: host path
    if info.obj.status.unhealthy_nodes:
        return False  # node-replacement machinery is host-only
    if info.can_be_partially_admitted():
        return False  # PodSetReducer search is host-only
    ps = info.obj.podsets[0]
    tr = ps.topology_request
    if tr is not None:
        if tr.podset_group_name:
            return False
        if tr.podset_slice_constraints and len(
                tr.podset_slice_constraints) > 1:
            return False  # nested multi-layer slices: host DP
        required = tr.required is not None
        if (features.enabled("TASBalancedPlacement") and not required
                and not _is_unconstrained(ps)):
            # balanced placement: the dense port covers a gang with no
            # slice, up to the table's size
            if (tr.podset_slice_required_topology is not None
                    or ps.count > BALANCED_MAX_COUNT):
                return False
    return True


class DeviceTASPlacer:
    """Places kernel-admitted TAS workloads via the on-device
    sequential placer, one lax.scan step per admission with the
    leaf-capacity carry between them."""

    #: (tree, bucket) programs this process has traced
    _built: set = set()

    def __init__(self, store) -> None:
        self.store = store
        #: placer programs the last ``place_batch`` had to build
        self.last_builds = 0

    def _placer_for(self, levels, bal_cap: int):
        from kueue_oss_tpu.solver.tas_kernels import sequential_placer_for

        placer, key, new = sequential_placer_for(levels, bal_cap)
        if new:
            for b in WARM_BUCKETS:
                self._run(placer, key, levels, self._rows(levels, b))
        return placer, key

    @staticmethod
    def _rows(levels, n: int) -> dict:
        """``n`` rows that place nothing (``count`` 0)."""
        R = max(1, len(levels.resources))
        leaf_l = len(levels.parents) - 1
        return dict(
            per_pod=np.zeros((n, R), dtype=np.int32),
            count=np.zeros((n,), dtype=np.int32),
            level=np.zeros((n,), dtype=np.int32),
            required=np.zeros((n,), dtype=bool),
            unconstrained=np.zeros((n,), dtype=bool),
            least_free=np.zeros((n,), dtype=bool),
            sl_size=np.ones((n,), dtype=np.int32),
            sl_level=np.full((n,), leaf_l, dtype=np.int32),
            balanced=np.zeros((n,), dtype=bool))

    def _run(self, placer, key, levels, rows: dict, cap=None):
        """One call of the placer on rows padded to their bucket.
        Returns (leaf selections, oks, capacity after) for the rows
        given."""
        import jax.numpy as jnp

        n = len(rows["count"])
        b = bucket_of(n)
        if (key, b) not in self._built:
            self._built.add((key, b))
            self.last_builds += 1
        pad = self._rows(levels, b - n)
        a = {k: jnp.asarray(np.concatenate([v, pad[k]]))
             for k, v in rows.items()}
        R = a["per_pod"].shape[1]
        sels, _leads, oks, cap = placer(
            jnp.asarray(levels.leaf_capacity) if cap is None else cap,
            a["per_pod"], a["count"], a["level"], a["required"],
            a["unconstrained"], a["least_free"], a["sl_size"],
            a["sl_level"], jnp.zeros((b, R), dtype=jnp.int32),
            jnp.zeros((b,), dtype=bool), a["balanced"])
        return np.asarray(sels)[:n], np.asarray(oks)[:n], cap

    def place_batch(self, snapshot, items):
        """Place ``items`` (admission-ordered list of (info, flavor))
        on device. Returns {workload key: TopologyAssignment | None} —
        None marks a placement failure (the workload stays pending for
        the host cycle)."""
        from kueue_oss_tpu import features
        from kueue_oss_tpu.solver.tas_kernels import build_levels

        self.last_builds = 0
        gate = features.enabled("TASBalancedPlacement")
        out: dict[str, Optional[TopologyAssignment]] = {}
        by_flavor: dict[str, list] = {}
        for info, flavor in items:
            by_flavor.setdefault(flavor, []).append(info)

        for flavor, infos in by_flavor.items():
            snap = snapshot.tas_flavors.get(flavor)
            if snap is None:
                for info in infos:
                    out[info.key] = None
                continue
            levels = build_levels(snap)
            res_idx = {r: j for j, r in enumerate(levels.resources)}
            leaf_l = len(levels.parents) - 1
            M = len(infos)
            rows = self._rows(levels, M)
            per_pod, count, level = (rows["per_pod"], rows["count"],
                                     rows["level"])
            feasible = np.ones((M,), dtype=bool)
            for m, info in enumerate(infos):
                ps = info.obj.podsets[0]
                tr = ps.topology_request
                reqs = dict(effective_per_pod_requests(
                    ps, info.obj.namespace))
                # the host counts a pod against the node's pod limit
                # (fillInCounts: req["pods"] += 1)
                reqs["pods"] = reqs.get("pods", 0) + 1
                for r, v in reqs.items():
                    j = res_idx.get(r)
                    if j is None:
                        if v > 0:
                            feasible[m] = False  # resource absent from tree
                    else:
                        per_pod[m, j] = v
                count[m] = info.total_requests[0].count
                unc = _is_unconstrained(ps)
                rows["unconstrained"][m] = unc
                rows["least_free"][m] = unc and snap.profile_mixed
                key_level = None
                if tr is not None and tr.required is not None:
                    rows["required"][m] = True
                    key_level = tr.required
                elif tr is not None and tr.preferred is not None:
                    key_level = tr.preferred
                    rows["balanced"][m] = gate and not unc
                if unc or key_level is None:
                    level[m] = leaf_l
                else:
                    idx = snap.level_index(key_level)
                    if idx is None:
                        feasible[m] = False
                        idx = leaf_l
                    level[m] = idx
                if (tr is not None
                        and tr.podset_slice_required_topology is not None):
                    sidx = snap.level_index(
                        tr.podset_slice_required_topology)
                    if (sidx is None or tr.podset_slice_size is None
                            or level[m] > sidx
                            or count[m] % max(tr.podset_slice_size, 1)):
                        feasible[m] = False
                    else:
                        rows["sl_level"][m] = sidx
                        rows["sl_size"][m] = tr.podset_slice_size

            # rows the host pre-check rejected must not consume capacity
            # inside the scan (later rows would see a smaller tree)
            bad = ~feasible
            count[bad] = 0
            per_pod[bad] = 0
            rows["sl_size"][bad] = 1
            placer, key = self._placer_for(
                levels, BALANCED_MAX_COUNT if gate else 0)
            sels, oks, cap = [], [], None
            for lo in range(0, M, BUCKETS[-1]):
                part = {k: v[lo:lo + BUCKETS[-1]] for k, v in rows.items()}
                s, o, cap = self._run(placer, key, levels, part, cap)
                sels.append(s)
                oks.append(o)
            sels = np.concatenate(sels)
            oks = np.concatenate(oks) & feasible
            # buildAssignment parity (tas_flavor_snapshot.go:1490-1501):
            # hostname-only values when the lowest level is the hostname
            lvl0 = (len(snap.levels) - 1 if snap.is_lowest_level_node
                    else 0)
            for m, info in enumerate(infos):
                if not oks[m]:
                    out[info.key] = None
                    continue
                domains = [
                    TopologyDomainAssignment(
                        values=list(levels.leaf_names[d][lvl0:]),
                        count=int(sels[m, d]))
                    for d in np.nonzero(sels[m])[0]
                ]
                out[info.key] = TopologyAssignment(
                    levels=list(snap.levels[lvl0:]),
                    domains=domains,
                )
        return out
