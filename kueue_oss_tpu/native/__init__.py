"""Native (C++) host-runtime components, loaded via ctypes.

The compute path is JAX/XLA; these are the *host-side* hot loops around
it — currently the sequential quota-oracle verify used when committing
solver plans (oracle.cpp). The library is compiled on first use with the
system toolchain and cached next to the source under a name keyed on the
source's CONTENT (file times do not survive a copy of the tree), so what
runs is always built from the oracle.cpp beside it; every entry point
has a pure-Python fallback so the framework works without a compiler.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from kueue_oss_tpu.api.types import FlavorResource
from kueue_oss_tpu.core.quota import QuotaNode

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "oracle.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def lib_path() -> str:
    """Where the library built from the CURRENT oracle.cpp lives."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"_oracle-{digest}.so")


def _compile(lib: str) -> bool:
    # build beside the target and rename: a concurrent loader (xdist
    # workers, a sidecar next to its manager) never maps a half-written
    # library
    tmp = f"{lib}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
             "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib)
        return True
    except (OSError, subprocess.SubprocessError):
        return False
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def load() -> Optional[ctypes.CDLL]:
    """The compiled library, building it if absent; None if unavailable."""
    global _lib, _load_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _load_failed:
            return None
        path = lib_path()
        if not os.path.exists(path) and not _compile(path):
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            _load_failed = True
            return None
        lib.verify_plan.restype = ctypes.c_int64
        lib.verify_plan.argtypes = [
            ctypes.c_int32, ctypes.c_int32,
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
            ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
        ]
        _lib = lib
        return _lib


class BatchOracle:
    """Flattened quota forest for batch verify-and-charge.

    Built once per drain from the oracle forest; `verify_and_apply`
    checks a sequence of (cq_name, {FlavorResource: qty}) admissions in
    order, charging the ones that fit — semantically identical to calling
    QuotaNode.fits + add_usage per admission, but against the oracle's
    OWN flattened state. Neither the native nor the Python path mutates
    the QuotaNode objects passed to __init__; callers needing the charged
    state read it from the oracle (or re-apply to their forest).
    """

    def __init__(self, cqs: dict[str, QuotaNode]) -> None:
        # Collect every node reachable from the CQ leaves, parents-first.
        roots = []
        seen = set()
        for node in cqs.values():
            root = node.root()
            if id(root) not in seen:
                seen.add(id(root))
                roots.append(root)
        nodes: list[QuotaNode] = []
        for root in roots:
            stack = [root]
            while stack:
                n = stack.pop()
                nodes.append(n)
                stack.extend(n.children.values())
        self._nodes = nodes
        self._index = {id(n): i for i, n in enumerate(nodes)}
        self._cq_node = {name: self._index[id(n)] for name, n in cqs.items()}
        self._cqs = cqs

        frs: set[FlavorResource] = set()
        for n in nodes:
            frs.update(n.quotas)
            frs.update(n.subtree_quota)
            frs.update(n.usage)
        self._fr_list = sorted(frs)
        self._fr_index = {fr: i for i, fr in enumerate(self._fr_list)}

        N, F = len(nodes), max(1, len(self._fr_list))
        self.F = F
        self.parent = np.full(N, -1, dtype=np.int32)
        self.local_quota = np.zeros((N, F), dtype=np.int64)
        self.subtree = np.zeros((N, F), dtype=np.int64)
        self.has_borrow = np.zeros((N, F), dtype=np.uint8)
        self.borrow_limit = np.zeros((N, F), dtype=np.int64)
        self.usage = np.zeros((N, F), dtype=np.int64)
        for i, n in enumerate(nodes):
            if n.parent is not None:
                self.parent[i] = self._index[id(n.parent)]
            for fr, q in n.quotas.items():
                j = self._fr_index[fr]
                if q.borrowing_limit is not None:
                    self.has_borrow[i, j] = 1
                    self.borrow_limit[i, j] = q.borrowing_limit
            for fr, val in n.subtree_quota.items():
                self.subtree[i, self._fr_index[fr]] = val
            for fr, val in n.usage.items():
                self.usage[i, self._fr_index[fr]] = val
            for j, fr in enumerate(self._fr_list):
                self.local_quota[i, j] = n.local_quota(fr)

    def verify_and_apply(
        self, admissions: list[tuple[str, dict[FlavorResource, int]]],
        force_python: bool = False,
    ) -> np.ndarray:
        """ok[i] per admission; fitting admissions charge usage in order."""
        ok = np.zeros(len(admissions), dtype=np.uint8)
        lib = None if force_python else load()
        if lib is None:
            return self._python_verify(admissions, ok)
        # Admissions naming a (flavor, resource) with no quota anywhere can
        # never fit (available() over an unknown fr is <= 0), and a CQ
        # absent from the forest (deleted since plan construction) cannot
        # be charged; reject both up front instead of indexing them into
        # the CSR arrays — mirrored by _python_verify.
        valid = [i for i, (cq_name, usage) in enumerate(admissions)
                 if cq_name in self._cq_node
                 and all(q <= 0 or fr in self._fr_index
                         for fr, q in usage.items())]
        node_idx = np.zeros(len(valid), dtype=np.int32)
        ptr = np.zeros(len(valid) + 1, dtype=np.int64)
        fr_l: list[int] = []
        qty_l: list[int] = []
        for j, i in enumerate(valid):
            cq_name, usage = admissions[i]
            node_idx[j] = self._cq_node[cq_name]
            for fr, q in usage.items():
                if q <= 0:
                    continue
                fr_l.append(self._fr_index[fr])
                qty_l.append(q)
            ptr[j + 1] = len(fr_l)
        ok_valid = np.zeros(len(valid), dtype=np.uint8)
        lib.verify_plan(
            np.int32(len(self._nodes)), np.int32(self.F),
            self.parent, self.local_quota.ravel(), self.subtree.ravel(),
            self.has_borrow.ravel(), self.borrow_limit.ravel(),
            self.usage.ravel(),
            np.int64(len(valid)), node_idx, ptr,
            np.asarray(fr_l, dtype=np.int32),
            np.asarray(qty_l, dtype=np.int64), ok_valid)
        ok[valid] = ok_valid
        return ok

    def _python_verify(self, admissions, ok: np.ndarray) -> np.ndarray:
        """Pure-Python mirror of oracle.cpp verify_plan over the same
        flattened arrays — both paths charge ONLY the oracle's internal
        state, never the QuotaNode objects passed to __init__ (callers that
        reuse the forest after verification see identical state either way).
        """
        for i, (cq_name, usage) in enumerate(admissions):
            n = self._cq_node.get(cq_name)
            if n is None:
                continue
            items = [(self._fr_index[fr], q) for fr, q in usage.items()
                     if q > 0 and fr in self._fr_index]
            if any(q > 0 and fr not in self._fr_index
                   for fr, q in usage.items()):
                continue  # unknown fr can never fit (available() <= 0)
            if all(q <= self._available(n, j) for j, q in items):
                ok[i] = 1
                for j, q in items:
                    self._add_usage(n, j, q)
        return ok

    def _available(self, n: int, f: int) -> int:
        """quota.py QuotaNode.available over the flattened arrays
        (resource_node.go:104-118)."""
        if self.parent[n] < 0:
            return int(self.subtree[n, f] - self.usage[n, f])
        parent_avail = self._available(int(self.parent[n]), f)
        if self.has_borrow[n, f]:
            stored_in_parent = int(self.subtree[n, f] - self.local_quota[n, f])
            used_in_parent = max(
                0, int(self.usage[n, f] - self.local_quota[n, f]))
            with_max = (stored_in_parent - used_in_parent
                        + int(self.borrow_limit[n, f]))
            parent_avail = min(with_max, parent_avail)
        local_avail = max(0, int(self.local_quota[n, f] - self.usage[n, f]))
        return local_avail + parent_avail

    def _add_usage(self, n: int, f: int, val: int) -> None:
        """quota.py QuotaNode.add_usage bubbling (resource_node.go:137-146)."""
        while True:
            local_avail = max(
                0, int(self.local_quota[n, f] - self.usage[n, f]))
            self.usage[n, f] += val
            p = int(self.parent[n])
            if p < 0 or val <= local_avail:
                return
            val -= local_avail
            n = p
