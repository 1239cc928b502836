"""Delta-sync solver sessions: stable row encodings, problem deltas,
and resident device state.

Without sessions every drain re-serializes and ships the full padded
50k x 1k problem (several MB) to the sidecar, which re-uploads it to
the device. Aryl
(arxiv 2202.07896) and CvxCluster (arxiv 2605.01614) both keep the
allocation problem resident and re-solve incrementally; this module is
that move for the export -> upload -> solve -> download cycle:

- ``HostDeltaSession`` re-encodes each padded export into a **stable
  slot space** (a workload keeps its row for the life of the session;
  freed rows are recycled as inert padding) with **order-preserving
  stable ranks** for timestamps/admit-ranks and **stable class tokens**
  — so a churn cycle dirties only the rows whose workloads actually
  changed, not every row behind a dense re-ranking.
- ``compute_delta``/``apply_delta`` diff two consecutive encodings into
  a ``ProblemDelta`` (changed rows + small-array replacements + scalar
  meta updates) and replay it bit-identically on the other side.
- ``state_checksum`` is the cheap content checksum both sides compare
  after every DELTA application: any mismatch forces a full RESYNC
  (counted in metrics, never silently wrong).
- ``DeviceResidentProblem`` pins the padded problem tensors on device
  across drains and applies row deltas with ``.at[rows].set`` scatter
  updates, so neither the sidecar nor the in-process path re-uploads
  the full problem per cycle.

Correctness posture: the delta layer is *content-based* — deltas are
computed by comparing the actual encoded arrays, with the event-driven
dirty sets from ``ExportCache`` serving as statistics and fast-path
hints, so delta-applied state is bit-identical to a fresh full sync by
construction (property-tested in tests/test_solver_delta.py). Anything
the delta cannot express cheaply (shape growth, scale flips, renumber
events, >50% dirty rows) degrades to a full sync, and the engine's
plan-sanity guard still validates every imported plan.

Streaming interplay (scheduler/streaming.py): a sub-cycle
micro-admission is an ordinary store event — it dirties its
ExportCache row, the workload leaves the next export's pending set,
and its session slot recycles like any other departure. The content
diff ships exactly those rows at the next full solve, so resident
device tensors stay valid across arbitrarily many micro-drains with
no session reset and no full re-upload.
"""

from __future__ import annotations

import dataclasses
import io
import json
import zlib
from bisect import bisect_left
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from kueue_oss_tpu.solver.tensors import BIG, SolverProblem, pow2

#: SolverProblem fields that ride the wire as arrays. Host-only decode
#: tables (fr_list, wl_keys, ...) and the raw stable-encoding inputs
#: (wl_raw_ts, ...) stay on the host.
HOST_ONLY_FIELDS = (
    "fr_list", "node_names", "cq_names", "wl_keys", "cq_option_flavors",
    "cq_resource_group", "scale", "n_resources", "ts_evict_base",
    "admit_rank_base", "n_classes",
    "wl_raw_ts", "wl_raw_admit_ts", "wl_class_tok", "class_tok_root",
)
ARRAY_FIELDS = [
    f.name for f in dataclasses.fields(SolverProblem)
    if f.name not in HOST_ONLY_FIELDS
]
META_FIELDS = ["n_resources", "ts_evict_base", "admit_rank_base", "scale"]

#: workload-axis arrays ([W+1] leading dim): delta'd row-wise
W_AXIS_FIELDS = (
    "wl_cqid", "wl_rank", "wl_prio", "wl_ts", "wl_uid", "wl_req",
    "wl_valid", "wl_parked0", "wl_admitted0", "wl_evicted0",
    "wl_admit_rank", "ad_usage", "wl_class", "wl_lq", "wl_afs_penalty",
    "wl_ts_buf",
)
NON_W_FIELDS = tuple(f for f in ARRAY_FIELDS if f not in W_AXIS_FIELDS)

#: a delta dirtying more than this fraction of rows costs more than a
#: full sync saves; degrade (counted as reason="dense_delta")
DENSE_DELTA_FRACTION = 0.5


# ---------------------------------------------------------------------------
# content checksum
# ---------------------------------------------------------------------------


def state_checksum(kwargs: dict, meta: dict) -> int:
    """Cheap content checksum over the wire-visible problem state.

    crc32 chained over every present array's (name, dtype, shape,
    bytes) in canonical field order plus the meta scalars — both sides
    compute it over their own state after every sync/delta, so any
    divergence (a garbled frame that still decoded, an apply bug, a
    version skew) is caught before the next plan is trusted.
    """
    crc = 0
    for name in ARRAY_FIELDS:
        arr = kwargs.get(name)
        if arr is None:
            continue
        arr = np.ascontiguousarray(arr)
        head = f"{name}|{arr.dtype.str}|{arr.shape}".encode()
        crc = zlib.crc32(head, crc)
        crc = zlib.crc32(arr.tobytes(), crc)
    crc = zlib.crc32(json.dumps(
        {k: int(meta[k]) for k in META_FIELDS}, sort_keys=True).encode(),
        crc)
    return crc & 0xFFFFFFFF


def problem_wire_state(problem: SolverProblem) -> tuple[dict, dict]:
    """Split a problem into (array kwargs, meta) in wire form."""
    kwargs = {name: getattr(problem, name) for name in ARRAY_FIELDS}
    meta = {name: int(getattr(problem, name)) for name in META_FIELDS}
    return kwargs, meta


# ---------------------------------------------------------------------------
# ProblemDelta
# ---------------------------------------------------------------------------


@dataclass
class ProblemDelta:
    """Row-sparse diff between two consecutive session epochs."""

    epoch: int
    base_epoch: int
    #: checksum of the FULL post-apply state (not of the delta)
    checksum: int
    #: per W-axis array: (dirty row indices, new content at those rows).
    #: Per-array rows, not a union: one widely-dirty one-byte flag array
    #: (parked bits toggling as capacity-freed wakes ripple) must not
    #: drag every other array's bytes along with it.
    row_updates: dict[str, tuple[np.ndarray, np.ndarray]] = field(
        default_factory=dict)
    #: full replacements for changed non-workload arrays (node/CQ axes
    #: are small; usage/quota updates ride here)
    repl: dict[str, np.ndarray] = field(default_factory=dict)
    #: changed meta scalars (ts_evict_base and friends)
    meta_delta: dict[str, int] = field(default_factory=dict)
    #: emit statistics (dirty workloads/CQs seen, removed keys, ...)
    stats: dict = field(default_factory=dict)

    def payload_bytes(self) -> int:
        n = 0
        for idx, vals in self.row_updates.values():
            n += idx.nbytes + vals.nbytes
        for arr in self.repl.values():
            n += arr.nbytes
        return n


def compute_delta(prev_kwargs: dict, prev_meta: dict,
                  new_kwargs: dict, new_meta: dict,
                  epoch: int, base_epoch: int,
                  checksum: int) -> Optional[ProblemDelta]:
    """Diff two wire states; None means "too different — full sync".

    Incompatible = any array appearing/disappearing, any shape change
    (covers pad growth, vocabulary growth, class-space growth), a scale
    or resource-vocabulary flip (column meaning changes wholesale), or
    a dirty-row fraction above DENSE_DELTA_FRACTION.
    """
    for name in ARRAY_FIELDS:
        a, b = prev_kwargs.get(name), new_kwargs.get(name)
        if (a is None) != (b is None):
            return None
        if a is not None and (a.shape != b.shape or a.dtype != b.dtype):
            return None
    if (prev_meta["scale"] != new_meta["scale"]
            or prev_meta["n_resources"] != new_meta["n_resources"]):
        return None

    W1 = new_kwargs["wl_cqid"].shape[0]
    mask = np.zeros(W1, dtype=bool)
    row_updates: dict[str, tuple[np.ndarray, np.ndarray]] = {}
    for name in W_AXIS_FIELDS:
        a, b = prev_kwargs.get(name), new_kwargs.get(name)
        if a is None:
            continue
        neq = a != b
        if neq.ndim > 1:
            neq = neq.reshape(W1, -1).any(axis=1)
        if neq.any():
            idx = np.nonzero(neq)[0].astype(np.int32)
            row_updates[name] = (idx, np.ascontiguousarray(b[idx]))
            mask |= neq
    if int(mask.sum()) > W1 * DENSE_DELTA_FRACTION:
        return None
    repl = {}
    for name in NON_W_FIELDS:
        a, b = prev_kwargs.get(name), new_kwargs.get(name)
        if a is None:
            continue
        if not np.array_equal(a, b):
            repl[name] = np.ascontiguousarray(b)
    meta_delta = {k: int(new_meta[k]) for k in META_FIELDS
                  if prev_meta[k] != new_meta[k]}
    return ProblemDelta(epoch=epoch, base_epoch=base_epoch,
                        checksum=checksum, row_updates=row_updates,
                        repl=repl, meta_delta=meta_delta)


def apply_delta(kwargs: dict, meta: dict, delta: ProblemDelta) -> None:
    """Replay a delta onto (kwargs, meta) in place — the sidecar's (and
    the tests') reconstruction path. Bit-identical by construction with
    the state compute_delta diffed against; verified via checksum."""
    for name, (idx, vals) in delta.row_updates.items():
        kwargs[name][idx] = vals
    for name, arr in delta.repl.items():
        kwargs[name] = arr
    meta.update(delta.meta_delta)


def serialize_delta(delta: ProblemDelta) -> tuple[dict, bytes]:
    arrays = {}
    for name, (idx, vals) in delta.row_updates.items():
        arrays[f"ri__{name}"] = idx
        arrays[f"rv__{name}"] = vals
    for name, arr in delta.repl.items():
        arrays[f"a__{name}"] = arr
    buf = io.BytesIO()
    # deltas are small and highly structured (runs of consecutive row
    # indices, uniform flag toggles), so deflate pays for itself many
    # times over; the bulk SYNC frame stays uncompressed — it is the
    # once-per-session latency-critical upload
    np.savez_compressed(buf, **arrays)
    header = {"epoch": delta.epoch, "base_epoch": delta.base_epoch,
              "checksum": delta.checksum,
              "meta_delta": {k: int(v)
                             for k, v in delta.meta_delta.items()},
              "stats": delta.stats}
    return header, buf.getvalue()


def deserialize_delta(header: dict, blob: bytes) -> ProblemDelta:
    data = np.load(io.BytesIO(blob))
    row_updates, repl = {}, {}
    for name in data.files:
        if name.startswith("ri__"):
            row_updates[name[4:]] = (data[name], data["rv__" + name[4:]])
        elif name.startswith("a__"):
            repl[name[3:]] = data[name]
    return ProblemDelta(
        epoch=int(header["epoch"]), base_epoch=int(header["base_epoch"]),
        checksum=int(header["checksum"]), row_updates=row_updates,
        repl=repl,
        meta_delta={k: int(v)
                    for k, v in (header.get("meta_delta") or {}).items()},
        stats=dict(header.get("stats") or {}))


# ---------------------------------------------------------------------------
# order-preserving stable ranks
# ---------------------------------------------------------------------------


class StableRanker:
    """Order-preserving integer ranks for a growing set of floats.

    Dense ``np.unique`` ranks shift wholesale when an early value
    leaves the set — one finished workload would dirty every later
    row's timestamp rank. Stable ranks preserve order AND identity:
    once a value has a rank it keeps it; new values get gap midpoints
    (appends, the common churn case, get max+GAP). The kernels only
    compare ranks, so any order-embedding is semantically identical to
    the dense encoding. Gap exhaustion or int32-headroom overflow
    renumbers everything (``renumbers`` counts it; the session turns a
    renumber into a full sync).
    """

    def __init__(self, gap: int = 1 << 10,
                 max_rank: int = 1 << 29) -> None:
        self.gap = gap
        self.max_rank = max_rank
        self._values = np.zeros(0, dtype=np.float64)
        self._ranks = np.zeros(0, dtype=np.int64)
        self.renumbers = 0

    def update(self, values: np.ndarray) -> bool:
        """Register values; True if a renumber changed existing ranks."""
        distinct = np.unique(np.asarray(values, dtype=np.float64))
        if distinct.size == 0:
            return False
        if self._values.size == 0:
            self._values = distinct
            self._ranks = (np.arange(distinct.size, dtype=np.int64)
                           + 1) * self.gap
            return self._maybe_renumber(False)
        idx = np.searchsorted(self._values, distinct)
        present = np.zeros(distinct.size, dtype=bool)
        in_range = idx < self._values.size
        present[in_range] = (
            self._values[idx[in_range]] == distinct[in_range])
        new = distinct[~present]
        if new.size == 0:
            return False
        renumber = False
        tail = new[new > self._values[-1]]
        mid = new[new <= self._values[-1]]
        if mid.size:
            vals = self._values.tolist()
            ranks = self._ranks.tolist()
            for v in mid.tolist():
                i = bisect_left(vals, v)
                lo = ranks[i - 1] if i else 0
                hi = ranks[i]
                r = (lo + hi) // 2
                if r <= lo or r >= hi:
                    renumber = True  # gap exhausted at this position
                    r = lo
                vals.insert(i, v)
                ranks.insert(i, r)
            self._values = np.asarray(vals, dtype=np.float64)
            self._ranks = np.asarray(ranks, dtype=np.int64)
        if tail.size:
            base = int(self._ranks[-1]) if self._ranks.size else 0
            self._values = np.concatenate([self._values, tail])
            self._ranks = np.concatenate([
                self._ranks,
                base + (np.arange(tail.size, dtype=np.int64) + 1)
                * self.gap])
        return self._maybe_renumber(renumber)

    def _maybe_renumber(self, force: bool) -> bool:
        over = self._ranks.size and int(self._ranks[-1]) > self.max_rank
        if not (force or over):
            return False
        gap = self.gap
        while self._values.size * gap > self.max_rank and gap > 1:
            gap //= 2
        self._ranks = (np.arange(self._values.size, dtype=np.int64)
                       + 1) * gap
        self.renumbers += 1
        return True

    def rank(self, values: np.ndarray) -> np.ndarray:
        return self._ranks[np.searchsorted(self._values, values)]

    def rank_before(self, thresholds: np.ndarray) -> np.ndarray:
        """Rank of the largest registered value <= each threshold
        (callers guarantee at least one exists — each row's own value
        is registered)."""
        pos = np.searchsorted(self._values, thresholds, side="right") - 1
        return self._ranks[np.maximum(pos, 0)]

    @property
    def size(self) -> int:
        return int(self._values.size)

    @property
    def max(self) -> int:
        return int(self._ranks[-1]) if self._ranks.size else 0


# ---------------------------------------------------------------------------
# host-side session: slots + stable encodings + delta emission
# ---------------------------------------------------------------------------


@dataclass
class SessionFrame:
    """What one drain ships: a delta when possible, else a full sync."""

    epoch: int
    checksum: int
    delta: Optional[ProblemDelta]  # None => full SYNC required
    full_reason: Optional[str] = None  # why a sync (None when delta)
    stats: dict = field(default_factory=dict)


#: pad_workloads-equivalent inert fill per W-axis array; wl_cqid/wl_rank
#: fills are resolved at slot time (C / BIG). wl_uid fills with BIG so
#: a recycled slot can never alias a legitimate uid-0 workload.
_ROW_FILL = {
    "wl_prio": 0, "wl_ts": 0, "wl_uid": BIG, "wl_req": 0,
    "wl_valid": False, "wl_parked0": False, "wl_admitted0": False,
    "wl_evicted0": False, "wl_admit_rank": 0, "ad_usage": 0,
    "wl_lq": 0, "wl_afs_penalty": 0.0, "wl_ts_buf": 0,
    "wl_raw_ts": 0.0, "wl_raw_admit_ts": 0.0,
}


class HostDeltaSession:
    """Per-kind (lean/full) session state on the scheduler host.

    ``advance(padded_problem)`` returns the slot-stable, rank-stable
    re-encoding of the problem plus the SessionFrame to ship. One
    instance per kernel kind — the lean and full exports differ in
    content, so they are separate sessions on the wire too.
    """

    #: W-axis fields copied straight from the export row in the hint
    #: fast path — everything except the session-stable re-derivations
    #: (wl_ts/wl_ts_buf/wl_admit_rank/wl_class come from the rankers)
    _FAST_DIRECT = (
        "wl_cqid", "wl_rank", "wl_prio", "wl_uid", "wl_req", "wl_valid",
        "wl_parked0", "wl_admitted0", "wl_evicted0", "ad_usage",
        "wl_lq", "wl_afs_penalty")

    def __init__(self, cache=None,
                 neutral_fields: tuple[str, ...] = ()) -> None:
        #: optional ExportCache: per-workload/per-CQ dirty sets feed the
        #: frame stats and the no-change fast path
        self.cache = cache
        #: W-axis arrays this kernel kind never reads (the full kernel
        #: has no wl_rank — FIFO order rides the timestamp ranks), held
        #: at their inert fill so rank churn can't dirty the wire
        self.neutral_fields = tuple(neutral_fields)
        self.epoch = 0
        self._last: Optional[tuple[dict, dict]] = None
        self._last_keys: list[str] = []
        self._slots: dict[str, int] = {}
        self._free: list[int] = []
        self._capacity = -1
        self._ts = StableRanker()
        self._admit = StableRanker()
        self._class_cs = 2  # sticky pow2 class-space (>= max token + 2)
        self._event_mark = 0
        self.full_syncs = 0
        self.delta_syncs = 0
        #: slot->shard interleave width (1 = the classic smallest-slot
        #: policy). With a row-sharded mesh, smallest-slot packs every
        #: churn-era arrival into the low shards while departures
        #: hollow out the high ones — shard_imbalance drifts > 1 on
        #: long-lived sessions. Interleaving assigns new slots round-
        #: robin across the mesh's block shards instead.
        self._interleave = 1
        self._pending_interleave: Optional[int] = None
        #: interleave-change RESYNCs actually taken (epoch migrations)
        self.migrations = 0
        self._rr_cursor = 0
        #: columnar-hint fast path state: the previous slotted problem
        #: (its arrays alias ``_last``'s, so in-place row scatters keep
        #: both views coherent), the last consumed assembly seq, and
        #: the chained cheap checksum
        self._last_slotted: Optional[SolverProblem] = None
        self._hint_seq: Optional[int] = None
        #: when True (engine sets it on the LOCAL path only — no remote
        #: sidecar will recompute state_checksum), fast-path frames
        #: carry a chained checksum over the delta payload instead of
        #: an O(W) crc over the full state
        self.cheap_checksum = False
        self._fast_crc = 0
        self.fast_advances = 0

    # -- slot assignment ---------------------------------------------------

    def set_interleave(self, n_shards: int) -> None:
        """Request slot->shard interleaving over ``n_shards`` block
        shards. A width CHANGE is an epoch migration: the next advance
        re-lays every slot out (one full RESYNC, full_reason
        "interleave_migration", counted in ``migrations``) and resident
        device tensors rebuild once. Width 1 restores the classic
        smallest-slot policy byte-for-byte."""
        n = max(1, int(n_shards))
        if n != self._interleave:
            self._pending_interleave = n

    def _shard_of(self, slot: int) -> int:
        # block sharding over the PADDED axis (capacity + null row),
        # mirroring NamedSharding's layout; the null row rides the last
        # shard
        block = (self._capacity + 1) // self._interleave
        return min(slot // max(1, block), self._interleave - 1)

    def _assign_slots(self, keys: list[str]) -> Optional[np.ndarray]:
        """dst[i] = slot for exported row i (or None on capacity reset)."""
        present = {k for k in keys if k}
        for k in [k for k in self._slots if k not in present]:
            self._free.append(self._slots.pop(k))
        self._free.sort(reverse=True)  # pop() yields the smallest slot
        n = self._interleave
        if n > 1:
            by_shard: list[list[int]] = [[] for _ in range(n)]
            for s in self._free:  # descending, so pop() = smallest
                by_shard[self._shard_of(s)].append(s)
        dst = np.full(len(keys), -1, dtype=np.int64)
        for i, k in enumerate(keys):
            if not k:
                continue
            s = self._slots.get(k)
            if s is None:
                if not self._free:
                    return None  # capacity exhausted: reset + full sync
                if n > 1:
                    # round-robin shard choice; fall through occupied
                    # shards so capacity, not balance, is the only
                    # reset trigger
                    s = None
                    for d in range(n):
                        bucket = by_shard[(self._rr_cursor + d) % n]
                        if bucket:
                            s = bucket.pop()
                            break
                    self._rr_cursor = (self._rr_cursor + 1) % n
                    self._free.remove(s)
                else:
                    s = self._free.pop()
                self._slots[k] = s
            dst[i] = s
        return dst

    def _reset_slots(self, keys: list[str]) -> np.ndarray:
        self._slots = {}
        self._free = []
        dst = np.full(len(keys), -1, dtype=np.int64)
        n = self._interleave
        if n > 1:
            # striped re-layout: row i of the export lands in shard
            # i % n, at that shard's next sequential slot
            block = (len(keys) + 1) // n
            bounds = [min((s + 1) * block, len(keys)) for s in range(n)]
            cursor = [s * block for s in range(n)]
            live = 0
            for i, k in enumerate(keys):
                if not k:
                    continue
                s = None
                for d in range(n):
                    sh = (live + d) % n
                    if cursor[sh] < bounds[sh]:
                        s = cursor[sh]
                        cursor[sh] += 1
                        break
                live += 1
                if s is None:
                    continue  # > capacity: caller's pad guarantees room
                self._slots[k] = s
                dst[i] = s
            taken = set(self._slots.values())
            self._free = sorted(
                (s for s in range(len(keys)) if s not in taken),
                reverse=True)
            return dst
        nxt = 0
        for i, k in enumerate(keys):
            if k:
                self._slots[k] = nxt
                dst[i] = nxt
                nxt += 1
        self._free = list(range(len(keys) - 1, nxt - 1, -1))
        return dst

    # -- the per-drain step ------------------------------------------------

    def advance(self, problem: SolverProblem, hint=None
                ) -> tuple[SolverProblem, SessionFrame]:
        """Re-encode ``problem`` into slot space and emit its frame.

        ``hint`` is the export's ``ColumnarHint`` (solver/columnar.py)
        when the problem came off the columnar scatter/cached path: a
        contiguous-seq hint whose membership did not change lets the
        session scatter just the changed rows into the previous slotted
        encoding — O(dirty) instead of the O(W) permute + content diff.
        Every precondition failure falls back to the classic path,
        which diffs actual array content and is therefore always
        correct regardless of how far the fast path got.
        """
        if hint is not None and not hint.membership_changed:
            fast = self._advance_fast(problem, hint)
            if fast is not None:
                self._hint_seq = hint.seq
                return fast
        out = self._advance_classic(problem)
        self._hint_seq = hint.seq if hint is not None else None
        return out

    def _advance_classic(self, problem: SolverProblem
                         ) -> tuple[SolverProblem, SessionFrame]:
        full_reason = None
        W = problem.n_workloads
        keys = list(problem.wl_keys)
        if W != self._capacity:
            # padded capacity changed => compiled shapes changed anyway
            # (a pending interleave change rides along for free)
            self._capacity = W
            if self._pending_interleave is not None:
                self._interleave = self._pending_interleave
                self._pending_interleave = None
            dst = self._reset_slots(keys)
            full_reason = "shape_change" if self.epoch else "first_sync"
        elif self._pending_interleave is not None:
            # epoch migration: re-lay every slot out under the new
            # interleave width; ONE full RESYNC, resident device
            # tensors rebuild once on the other side
            self._interleave = self._pending_interleave
            self._pending_interleave = None
            self.migrations += 1
            dst = self._reset_slots(keys)
            full_reason = "interleave_migration"
        else:
            dst = self._assign_slots(keys)
            if dst is None:
                dst = self._reset_slots(keys)
                full_reason = "slot_reset"

        # rankers keep every timestamp ever seen so existing ranks never
        # move; once the dead fraction dominates (long-running sessions,
        # finished workloads' timestamps linger), reset them — the
        # wholesale rank change rides the full sync this forces, and the
        # memory/lookup cost stays proportional to the live problem
        active = sum(1 for k in keys if k)
        cap = max(4096, 4 * active)
        if self._ts.size > cap or self._admit.size > cap:
            self._ts = StableRanker()
            self._admit = StableRanker()
            full_reason = full_reason or "ranker_prune"

        slotted = self._permute(problem, dst)
        if self._restamp(slotted):
            full_reason = full_reason or "rank_renumber"

        kwargs, meta = problem_wire_state(slotted)
        checksum = state_checksum(kwargs, meta)
        self.epoch += 1
        stats = self._drain_stats(keys)
        delta = None
        if full_reason is None and self._last is not None:
            delta = compute_delta(self._last[0], self._last[1],
                                  kwargs, meta, epoch=self.epoch,
                                  base_epoch=self.epoch - 1,
                                  checksum=checksum)
            if delta is None:
                full_reason = "dense_delta"
            else:
                delta.stats = stats
        elif full_reason is None:
            full_reason = "first_sync"
        self._last = (kwargs, meta)
        self._last_keys = keys
        self._last_slotted = slotted
        if delta is None:
            self.full_syncs += 1
        else:
            self.delta_syncs += 1
        return slotted, SessionFrame(epoch=self.epoch, checksum=checksum,
                                     delta=delta,
                                     full_reason=full_reason, stats=stats)

    # -- columnar-hint O(dirty) advance ------------------------------------

    def _advance_fast(self, problem: SolverProblem, hint
                      ) -> Optional[tuple[SolverProblem, SessionFrame]]:
        """Scatter the hint's changed rows straight into the previous
        slotted encoding. Returns None when any precondition fails; the
        ranker registrations it may have done before bailing are
        harmless (the classic path re-registers idempotently and diffs
        actual content, so a renumber mid-bail just rides the diff)."""
        from kueue_oss_tpu import features
        from kueue_oss_tpu.scheduler.preemption import (
            TIMESTAMP_PREEMPTION_BUFFER_S,
        )

        prev = self._last_slotted
        if (prev is None or self._last is None or not self.epoch
                or self._hint_seq is None
                or hint.base_seq != self._hint_seq
                or problem.n_workloads != self._capacity
                or self._pending_interleave is not None):
            return None
        active = len(self._slots)
        cap = max(4096, 4 * active)
        if self._ts.size > cap or self._admit.size > cap:
            return None  # classic path prunes the rankers (full sync)
        kwargs, meta = self._last
        if (int(problem.scale) != meta["scale"]
                or int(problem.n_resources) != meta["n_resources"]):
            return None
        ckeys = list(hint.changed)
        slots = np.empty(len(ckeys), dtype=np.int64)
        rows = np.empty(len(ckeys), dtype=np.int64)
        for i, k in enumerate(ckeys):
            s = self._slots.get(k)
            if s is None:
                return None
            slots[i] = s
            rows[i] = hint.changed[k]
        if rows.size and int(rows.max()) >= problem.n_workloads:
            return None

        # new raw timestamps register into the rankers before anything
        # mutates: a renumber moves OTHER rows' ranks, and under the
        # preemption-buffer gate even a plain registry growth can move
        # other rows' buffered ranks — both degrade to classic
        new_raw = np.ascontiguousarray(problem.wl_raw_ts[rows])
        gate = features.enabled("SchedulerTimestampPreemptionBuffer")
        ts_size0 = self._ts.size
        if self._ts.update(new_raw):
            return None
        if gate and active and self._ts.size != ts_size0:
            return None
        new_adm = np.ascontiguousarray(problem.wl_admitted0[rows])
        new_raw_admit = np.ascontiguousarray(
            problem.wl_raw_admit_ts[rows])
        if new_adm.any() and self._admit.update(new_raw_admit[new_adm]):
            return None
        new_tok = np.ascontiguousarray(problem.wl_class_tok[rows])
        root = problem.class_tok_root
        max_tok = int(new_tok.max()) if new_tok.size else -1
        if root is not None:
            max_tok = max(max_tok, len(root) - 1)
        if pow2(max_tok + 2) > self._class_cs:
            return None  # class space must grow: shapes change
        for name in NON_W_FIELDS:
            if name == "class_root":
                continue  # session-derived, handled below
            a, b = kwargs.get(name), getattr(problem, name)
            if (a is None) != (b is None):
                return None
            if a is not None and (a.shape != np.shape(b)
                                  or a.dtype != np.asarray(b).dtype):
                return None

        # -- all preconditions hold; mutate the resident encoding. The
        # kwargs arrays alias the slotted problem's, so one scatter
        # updates the wire state and the returned problem together.
        row_updates: dict[str, tuple[np.ndarray, np.ndarray]] = {}

        def scatter(name: str, new_vals: np.ndarray) -> None:
            arr = kwargs.get(name)
            if arr is None or not slots.size:
                return
            old_vals = arr[slots]
            neq = old_vals != new_vals
            if neq.ndim > 1:
                neq = neq.reshape(len(ckeys), -1).any(axis=1)
            if not neq.any():
                return
            sub = np.nonzero(neq)[0]
            arr[slots[sub]] = new_vals[sub]
            row_updates[name] = (slots[sub].astype(np.int32),
                                 np.ascontiguousarray(new_vals[sub]))

        for name in self._FAST_DIRECT:
            if name in self.neutral_fields:
                continue
            src = getattr(problem, name)
            if src is None:
                continue
            scatter(name, np.ascontiguousarray(src[rows]))

        if slots.size:
            new_ts = self._ts.rank(new_raw).astype(np.int32)
            scatter("wl_ts", new_ts)
            if gate:
                scatter("wl_ts_buf", self._ts.rank_before(
                    new_raw
                    + TIMESTAMP_PREEMPTION_BUFFER_S).astype(np.int32))
            else:
                scatter("wl_ts_buf", new_ts)
            ar = np.zeros(len(ckeys), dtype=np.int32)
            if new_adm.any():
                ar[new_adm] = (self._admit.rank(new_raw_admit[new_adm])
                               + 1).astype(np.int32)
            scatter("wl_admit_rank", ar)
            scatter("wl_class", np.where(
                new_tok >= 0, new_tok,
                self._class_cs - 1).astype(np.int32))
            prev.wl_raw_ts[slots] = new_raw
            prev.wl_raw_admit_ts[slots] = new_raw_admit
            prev.wl_class_tok[slots] = new_tok

        repl: dict[str, np.ndarray] = {}
        cs = self._class_cs
        class_root = np.full(cs, problem.n_nodes, dtype=np.int32)
        if root is not None and len(root):
            class_root[:len(root)] = root
        if not np.array_equal(kwargs["class_root"], class_root):
            repl["class_root"] = class_root
            kwargs["class_root"] = class_root
            prev.class_root = class_root
        for name in NON_W_FIELDS:
            if name == "class_root":
                continue
            a, b = kwargs.get(name), getattr(problem, name)
            if a is None or np.array_equal(a, b):
                continue
            repl[name] = np.ascontiguousarray(b)
            kwargs[name] = repl[name]
            setattr(prev, name, repl[name])
        if root is not None:
            prev.class_tok_root = root

        meta_delta: dict[str, int] = {}
        new_meta = {"n_resources": int(problem.n_resources),
                    "scale": int(problem.scale),
                    "ts_evict_base": self._ts.max + 1,
                    "admit_rank_base": self._admit.max + 2}
        for k in META_FIELDS:
            if meta[k] != new_meta[k]:
                meta_delta[k] = new_meta[k]
                meta[k] = new_meta[k]
        prev.ts_evict_base = new_meta["ts_evict_base"]
        prev.admit_rank_base = new_meta["admit_rank_base"]
        # host-only scalars ride the export (n_classes and friends can
        # move without any wire array changing); the session-derived
        # rank bases above are the only scalars the session owns
        for f in dataclasses.fields(problem):
            if f.name in ("ts_evict_base", "admit_rank_base"):
                continue
            val = getattr(problem, f.name)
            if isinstance(val, (bool, int, float, np.integer,
                                np.floating)):
                setattr(prev, f.name, val)

        self.epoch += 1
        if self.cheap_checksum:
            checksum = self._delta_checksum(row_updates, repl,
                                            meta_delta)
        else:
            checksum = state_checksum(kwargs, meta)
        stats = self._drain_stats_fast(len(ckeys))
        delta = ProblemDelta(epoch=self.epoch, base_epoch=self.epoch - 1,
                             checksum=checksum, row_updates=row_updates,
                             repl=repl, meta_delta=meta_delta,
                             stats=stats)
        self.delta_syncs += 1
        self.fast_advances += 1
        return prev, SessionFrame(epoch=self.epoch, checksum=checksum,
                                  delta=delta, full_reason=None,
                                  stats=stats)

    def _delta_checksum(self, row_updates: dict, repl: dict,
                        meta_delta: dict) -> int:
        """Chained cheap checksum over the delta payload (local-path
        only): NOT comparable with ``state_checksum`` — the engine
        enables it only when no remote sidecar will verify frames, so a
        1M-row session does not pay an O(W) crc per drain."""
        crc = zlib.crc32(f"{self.epoch}|{self._fast_crc}".encode())
        for name in sorted(row_updates):
            idx, vals = row_updates[name]
            crc = zlib.crc32(name.encode(), crc)
            crc = zlib.crc32(np.ascontiguousarray(idx).tobytes(), crc)
            crc = zlib.crc32(np.ascontiguousarray(vals).tobytes(), crc)
        for name in sorted(repl):
            crc = zlib.crc32(name.encode(), crc)
            crc = zlib.crc32(
                np.ascontiguousarray(repl[name]).tobytes(), crc)
        crc = zlib.crc32(json.dumps(
            {k: int(v) for k, v in sorted(meta_delta.items())}).encode(),
            crc)
        self._fast_crc = crc & 0xFFFFFFFF
        return self._fast_crc

    def _drain_stats_fast(self, n_changed: int) -> dict:
        stats = {"removed_keys": 0, "added_keys": 0,
                 "fast_rows": n_changed}
        if self.cache is not None:
            stats["dirty_workloads"] = len(self.cache.dirty_keys)
            stats["dirty_cqs"] = len(self.cache.dirty_cqs)
            stats["events"] = self.cache.events_seen - self._event_mark
            self._event_mark = self.cache.events_seen
            self.cache.consume_dirty()
        return stats

    def last_sync_wire_bytes(self) -> int:
        """Wire payload of the most recent full-sync state — the
        byte-accounting counterpart of ``ProblemDelta.payload_bytes``
        for sync frames, owned here so ledger consumers (engine and
        streaming drains) never reach into ``_last`` internals."""
        if self._last is None:
            return 0
        return sum(int(getattr(a, "nbytes", 0))
                   for a in self._last[0].values())

    def _drain_stats(self, keys: list[str]) -> dict:
        prev = {k for k in self._last_keys if k}
        cur = {k for k in keys if k}
        stats = {"removed_keys": len(prev - cur),
                 "added_keys": len(cur - prev)}
        if self.cache is not None:
            stats["dirty_workloads"] = len(self.cache.dirty_keys)
            stats["dirty_cqs"] = len(self.cache.dirty_cqs)
            stats["events"] = self.cache.events_seen - self._event_mark
            self._event_mark = self.cache.events_seen
            self.cache.consume_dirty()
        return stats

    def _permute(self, problem: SolverProblem,
                 dst: np.ndarray) -> SolverProblem:
        """Rewrite the workload axis into slot space: out[slot] = row,
        free slots filled with the pad_workloads inert row."""
        W = problem.n_workloads
        C = problem.n_cqs
        occupied = dst >= 0
        src = np.nonzero(occupied)[0]
        slots = dst[occupied]
        updates: dict = {}
        for name in W_AXIS_FIELDS + ("wl_raw_ts", "wl_raw_admit_ts",
                                     "wl_class_tok"):
            arr = getattr(problem, name)
            if arr is None:
                continue
            if name == "wl_cqid":
                fill = C
            elif name == "wl_rank":
                fill = BIG
            elif name == "wl_class":
                fill = problem.n_classes
            elif name == "wl_class_tok":
                fill = -1
            else:
                fill = _ROW_FILL[name]
            out = np.full_like(arr, fill)
            if name not in self.neutral_fields:
                out[-1] = arr[-1]  # the null row stays last
                out[slots] = arr[src]
            updates[name] = out
        out_keys = [""] * W
        for i, s in zip(src, slots):
            out_keys[s] = problem.wl_keys[i]
        updates["wl_keys"] = out_keys
        return dataclasses.replace(problem, **updates)

    def _restamp(self, p: SolverProblem) -> bool:
        """Replace the dense per-export encodings (timestamp ranks,
        admit ranks, scheduling-class ids) with session-stable ones, in
        place on the slotted problem. Returns True when a ranker
        renumber invalidated previous ranks (forces a full sync).

        The kernels only *compare* these values (entry ordering, the
        newer-equal preemption test, candidate recency), so any
        order-preserving embedding is behaviorally identical to the
        dense ``np.unique`` ranks export_problem produces.
        """
        from kueue_oss_tpu import features
        from kueue_oss_tpu.scheduler.preemption import (
            TIMESTAMP_PREEMPTION_BUFFER_S,
        )

        W = p.n_workloads
        occ = p.wl_cqid[:W] < p.n_cqs
        renumbered = False
        raw_ts = p.wl_raw_ts[:W][occ]
        renumbered |= self._ts.update(raw_ts)
        p.wl_ts[:W][occ] = self._ts.rank(raw_ts).astype(np.int32)
        p.wl_ts[:W][~occ] = 0
        if features.enabled("SchedulerTimestampPreemptionBuffer"):
            p.wl_ts_buf[:W][occ] = self._ts.rank_before(
                raw_ts + TIMESTAMP_PREEMPTION_BUFFER_S).astype(np.int32)
        else:
            p.wl_ts_buf[:W][occ] = p.wl_ts[:W][occ]
        p.wl_ts_buf[:W][~occ] = 0
        p.ts_evict_base = self._ts.max + 1

        adm = occ & p.wl_admitted0[:W]
        if adm.any():
            raw_admit = p.wl_raw_admit_ts[:W][adm]
            renumbered |= self._admit.update(raw_admit)
            p.wl_admit_rank[:W] = 0
            p.wl_admit_rank[:W][adm] = (
                self._admit.rank(raw_admit) + 1).astype(np.int32)
        else:
            p.wl_admit_rank[:W] = 0
        p.admit_rank_base = self._admit.max + 2

        # stable scheduling-equivalence classes: raw interned tokens in
        # a sticky pow2 class space (sentinel = CS-1, shared by strict
        # and gate-off rows exactly like the dense sentinel n_classes)
        toks = p.wl_class_tok[:W]
        max_tok = int(toks.max()) if toks.size else -1
        if p.class_tok_root is not None:
            max_tok = max(max_tok, len(p.class_tok_root) - 1)
        # room for 16 shapes a queue from the first drain on (up to
        # 1,024 classes): a stream whose classes arrive over its first
        # seconds (upstream tas: 9 a queue, the large ones last) would
        # otherwise grow the space inside the window, and a new shape of
        # the program is seconds of tracing even where its compile is
        # cached
        self._class_cs = max(self._class_cs, pow2(max_tok + 2),
                             min(pow2(16 * p.n_cqs), 1024))
        cs = self._class_cs
        wl_class = np.full(W + 1, cs - 1, dtype=np.int32)
        pos = toks >= 0
        wl_class[:W][pos] = toks[pos]
        p.wl_class = wl_class
        class_root = np.full(cs, p.n_nodes, dtype=np.int32)
        if p.class_tok_root is not None and len(p.class_tok_root):
            class_root[:len(p.class_tok_root)] = p.class_tok_root
        p.class_root = class_root
        return bool(renumbered)


# ---------------------------------------------------------------------------
# resident device tensors (shared by the sidecar and the local path)
# ---------------------------------------------------------------------------

#: problem W-axis field -> ProblemTensors field (lean kernel)
_LEAN_ROW_TENSORS = {n: n for n in (
    "wl_cqid", "wl_rank", "wl_prio", "wl_ts", "wl_uid", "wl_req",
    "wl_valid")}
#: problem W-axis field -> FullTensors field
_FULL_ROW_TENSORS = {
    "wl_cqid": "wl_cqid", "wl_prio": "wl_prio", "wl_ts": "wl_ts0",
    "wl_uid": "wl_uid", "wl_req": "wl_req", "wl_valid": "wl_valid",
    "wl_parked0": "wl_parked0", "wl_admitted0": "wl_admitted0",
    "wl_evicted0": "wl_evicted0", "wl_admit_rank": "wl_admit_rank0",
    "ad_usage": "ad_usage", "wl_class": "wl_class", "wl_lq": "wl_lq",
    "wl_afs_penalty": "wl_afs_penalty", "wl_ts_buf": "wl_ts_buf",
}


def _tree_nbytes(t) -> int:
    return sum(int(getattr(a, "nbytes", 0)) for a in t)


def delta_scatter(buf, idx, vals):
    """The donated row scatter of a delta epoch, under its own name in
    a device trace (program ``jit_delta_scatter``, scope
    ``delta_scatter``)."""
    import jax

    with jax.named_scope("delta_scatter"):
        return buf.at[idx].set(vals)


def delta_overwrite(buf, vals):
    """The donated whole-array rewrite of a full sync over resident
    buffers (program ``jit_delta_overwrite``)."""
    import jax

    with jax.named_scope("delta_overwrite"):
        return buf.at[...].set(vals)


class DeviceResidentProblem:
    """Padded problem tensors pinned on device across drains.

    A full sync uploads everything once; each delta epoch then updates
    only the dirty rows with a **donated** ``.at[rows].set`` scatter
    (plus the small node/CQ replacement arrays), so steady-state drains
    ship a few KB to the device instead of the whole padded problem —
    and the scatter itself reuses the resident buffer (XLA input/output
    aliasing) instead of materializing a second full padded copy.

    With a ``mesh``, BOTH kernels' workload-axis tensors live
    block-sharded over the mesh's ``wl`` axis (tree/CQ state
    replicated) whenever the padded axis divides evenly; donated
    scatters preserve the placement, so delta rows land directly on
    their owning shard. The full kernel additionally lane-shards its
    victim searches inside the solve — row and lane sharding compose
    (full_kernels._run_searches).
    """

    def __init__(self, mesh=None, axis: str = "wl") -> None:
        self.mesh = mesh
        self.axis = axis
        #: problems narrower than this stay unsharded even with a mesh
        #: (the mesh is the large-backlog path; callers set it from
        #: their mesh_min_workloads policy)
        self.mesh_min_rows = 0
        self.kind: Optional[str] = None
        self.epoch = -1
        self.tensors = None
        self.full_uploads = 0
        self.delta_updates = 0
        #: whether the CURRENT resident tensors are mesh-placed
        self.mesh_placed = False
        #: donated-scatter accounting for bench/diagnostics: bytes
        #: actually shipped by row updates vs the full-problem bytes a
        #: per-drain re-upload (or a non-donated scatter's output copy)
        #: would have materialized
        self.donated_update_bytes = 0
        self.avoided_copy_bytes = 0
        self.full_upload_bytes = 0
        #: full syncs that reused (donated) the previous epoch's
        #: resident buffers instead of allocating a second full set —
        #: forced-resync storms stop double-allocating device memory
        self.donated_full_syncs = 0
        #: _apply faults healed by a fresh full upload (never silent —
        #: the engine's mesh-fault accounting reads this)
        self.apply_faults = 0
        self._scatter_cache: dict = {}

    def resident_bytes(self) -> int:
        """Bytes of problem state currently pinned on device — the
        portable HBM-watermark bookkeeping obs/devtel.py gauges when
        the backend exposes no allocator stats (0 = nothing resident)."""
        return _tree_nbytes(self.tensors) if self.tensors is not None \
            else 0

    def update(self, problem: SolverProblem, frame: Optional[SessionFrame],
               full: bool):
        kind = "full" if full else "lean"
        delta = frame.delta if frame is not None else None
        if (delta is None or self.tensors is None or self.kind != kind
                or delta.base_epoch != self.epoch):
            self.tensors = self._full_upload(problem, full)
        else:
            try:
                self._apply(problem, delta, full)
            except Exception:
                # a partially-applied donated update leaves consumed
                # buffers behind; drop the resident state (so the heal
                # can never donate FROM consumed buffers) and re-seed
                # from the authoritative host problem
                self.apply_faults += 1
                self.tensors = None
                self.tensors = self._full_upload(problem, full)
        self.kind = kind
        self.epoch = frame.epoch if frame is not None else self.epoch + 1
        return self.tensors

    def _full_upload(self, problem: SolverProblem, full: bool):
        import jax
        import jax.numpy as jnp

        if full:
            from kueue_oss_tpu.solver.full_kernels import host_tensors_full

            host = host_tensors_full(problem)
        else:
            from kueue_oss_tpu.solver.kernels import host_tensors

            host = host_tensors(problem)
        kind = "full" if full else "lean"
        prev = self.tensors if self.kind == kind else None
        if prev is not None and self._donation_compatible(prev, host):
            # ROADMAP open item: a forced resync (shape-stable session
            # reset, checksum heal, chaos storm) used to allocate a
            # SECOND full set of resident buffers while the previous
            # epoch's set was still live. Donating the old buffers
            # rewrites them in place — same placement, no double
            # allocation — and rides the existing donated/avoided-copy
            # accounting. mesh_placed is preserved: identical shapes
            # keep the divisibility the original placement required.
            du, ac = self.donated_update_bytes, self.avoided_copy_bytes
            try:
                t = self._donated_overwrite(prev, host)
            except Exception:
                # roll back the per-buffer byte accounting of a
                # donation that did not complete, then re-seed fresh
                self.donated_update_bytes = du
                self.avoided_copy_bytes = ac
                self.apply_faults += 1
                self.mesh_placed = False
                t = jax.tree_util.tree_map(jnp.asarray, host)
            else:
                self.donated_full_syncs += 1
                self.full_uploads += 1
                self.full_upload_bytes += _tree_nbytes(t)
                return t
        else:
            self.mesh_placed = False
            t = jax.tree_util.tree_map(jnp.asarray, host)
        if self.mesh is not None:
            if full:
                from kueue_oss_tpu.solver.sharded import maybe_place_full

                t, self.mesh_placed = maybe_place_full(
                    t, problem, self.mesh, self.mesh_min_rows, self.axis)
            else:
                from kueue_oss_tpu.solver.sharded import maybe_place_lean

                t, self.mesh_placed = maybe_place_lean(
                    t, problem, self.mesh, self.mesh_min_rows, self.axis)
        self.full_uploads += 1
        self.full_upload_bytes += _tree_nbytes(t)
        return t

    @staticmethod
    def _donation_compatible(prev, host) -> bool:
        """Every resident buffer must match its replacement's shape and
        dtype exactly — XLA aliases donated inputs to outputs only then,
        and a mismatch means the compiled shapes changed anyway."""
        import numpy as np

        for old, new in zip(prev, host):
            new = np.asarray(new)
            if (tuple(old.shape) != tuple(new.shape)
                    or old.dtype != new.dtype):
                return False
        return True

    def _donated_overwrite(self, prev, host):
        """Rewrite every resident buffer in place with the new epoch's
        content (donated whole-array set; output aliases the donated
        input, preserving each buffer's sharding)."""
        import jax
        import numpy as np

        out = []
        for old, new in zip(prev, host):
            new = np.ascontiguousarray(new)
            self.donated_update_bytes += int(new.nbytes)
            self.avoided_copy_bytes += int(old.nbytes)
            sharding = getattr(old, "sharding", None)
            key = ("overwrite", old.shape, str(old.dtype), sharding)
            fn = self._scatter_cache.get(key)
            if fn is None:
                kw = {}
                if self.mesh_placed and sharding is not None:
                    kw["out_shardings"] = sharding
                fn = jax.jit(delta_overwrite, donate_argnums=0, **kw)
                self._scatter_cache[key] = fn
            out.append(fn(old, new))
        return type(prev)(*out)

    def _replicated(self, arr: np.ndarray):
        """Place a small replacement array consistently with the
        resident tensors (replicated over the mesh when sharded)."""
        import jax
        import jax.numpy as jnp

        if not self.mesh_placed:
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(
            np.ascontiguousarray(arr),
            NamedSharding(self.mesh, PartitionSpec()))

    def _scatter(self, buf, idx: np.ndarray, vals: np.ndarray):
        """Donated row scatter: out aliases ``buf``, so no full padded
        copy is materialized per delta epoch. The dirty-row count is
        bucketed to a power of two (padded with idempotent repeats of
        the last row) so one jitted program per (shape, dtype, bucket,
        sharding) serves every epoch."""
        import jax

        self.donated_update_bytes += int(idx.nbytes) + int(vals.nbytes)
        self.avoided_copy_bytes += int(buf.nbytes)
        n = idx.shape[0]
        cap = pow2(max(1, n))
        if cap != n:
            idx = np.concatenate([idx, np.repeat(idx[-1:], cap - n)])
            vals = np.concatenate(
                [vals, np.repeat(vals[-1:], cap - n, axis=0)])
        sharding = getattr(buf, "sharding", None)
        key = (buf.shape, str(buf.dtype), cap, sharding)
        fn = self._scatter_cache.get(key)
        if fn is None:
            kw = {}
            if self.mesh_placed and sharding is not None:
                kw["out_shardings"] = sharding
            fn = jax.jit(delta_scatter, donate_argnums=0, **kw)
            self._scatter_cache[key] = fn
        return fn(buf, idx, vals)

    def _apply(self, problem: SolverProblem, delta: ProblemDelta,
               full: bool) -> None:
        import jax.numpy as jnp

        t = self.tensors
        tensor_fields = set(t._fields)
        row_map = _FULL_ROW_TENSORS if full else _LEAN_ROW_TENSORS
        updates: dict = {}
        for name, (idx, vals) in delta.row_updates.items():
            tname = row_map.get(name)
            if tname is None:
                continue
            updates[tname] = self._scatter(
                getattr(t, tname), np.asarray(idx),
                np.ascontiguousarray(vals))
        for name, arr in delta.repl.items():
            if name in tensor_fields:
                updates[name] = self._replicated(arr)
        # derived fields whose inputs changed
        if "cq_node" in delta.repl or "parent" in delta.repl:
            is_cq = np.zeros(problem.parent.shape[0], dtype=bool)
            is_cq[problem.cq_node] = True
            updates["is_cq"] = self._replicated(is_cq)
        if full:
            if "cq_opt_group" in delta.repl:
                C, K = problem.cq_opt_group.shape
                opt_pos = np.zeros((C, K), dtype=np.int32)
                for c in range(C):
                    counts: dict[int, int] = {}
                    for k in range(K):
                        g = int(problem.cq_opt_group[c, k])
                        if g < 0:
                            continue
                        opt_pos[c, k] = counts.get(g, 0)
                        counts[g] = counts.get(g, 0) + 1
                updates["cq_opt_pos"] = jnp.asarray(opt_pos)
            if "fr_resource" in delta.repl:
                updates["res_onehot"] = jnp.asarray(np.eye(
                    problem.n_resources,
                    dtype=np.int32)[problem.fr_resource])
            if "ts_evict_base" in delta.meta_delta:
                updates["ts_evict_base"] = jnp.asarray(
                    problem.ts_evict_base, dtype=jnp.int32)
            if "admit_rank_base" in delta.meta_delta:
                updates["admit_rank_base"] = jnp.asarray(
                    problem.admit_rank_base, dtype=jnp.int32)
        if updates:
            self.tensors = t._replace(**updates)
        self.delta_updates += 1
