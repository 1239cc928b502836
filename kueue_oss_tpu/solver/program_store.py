"""Compiled preemption programs, kept beside the compile cache.

JAX's persistent compilation cache is keyed by the lowered module, so a
process that finds its program there has already traced and lowered it:
the cache saves the compile and never the trace, seconds of Python for
each preemption drain a process builds (PERF.md, section 5). This store
keeps the compiled executable itself, serialized, under a key that is
computed without tracing: the program's static caps, every feature gate,
the inputs' avals and shardings, the package's sources, the JAX versions,
the backend, the compiler's flags and the settings of JAX that change a
trace. A process that finds its key loads the executable and
calls it; one that does not traces, lowers and compiles as before (the
compile still goes through the persistent cache) and writes the entry
(:func:`compile_and_store`).

The store lives in ``programs/`` under the persistent cache's directory
(:func:`kueue_oss_tpu.util.xla_cache.enable`) and is off wherever that
cache is off. An entry is written to a temporary name and renamed into
place, so readers see a whole entry or none, and nothing here removes an
entry: processes of other trees keep theirs beside this one's. An entry
that fails to load or to run its first call (truncated, corrupt, built
for other devices) is counted in ``solver_program_load_failures``; the
caller then traces as it would without the store and writes the entry
anew. A key that cannot be computed (a JAX without an attribute it
reads) leaves the program unstored: the store never fails a drain.
"""

from __future__ import annotations

import functools
import hashlib
import logging
import os
import pickle
import uuid
from typing import Optional

import jax
import numpy as np

#: the package whose sources join the key (a change to any of them may
#: change what a program computes, or the names its operations carry)
_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_MAGIC = b"kueue-program-1\n"
#: what else decides the executable a trace gives or the compile makes:
#: the compiler's flags, and the settings of JAX that change a trace
_FLAG_VARIABLES = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")
_TRACE_SETTINGS = (
    "jax_enable_x64", "jax_default_matmul_precision",
    "jax_numpy_dtype_promotion", "jax_numpy_rank_promotion",
    "jax_default_prng_impl", "jax_threefry_partitionable",
    "jax_use_shardy_partitioner")
_log = logging.getLogger(__name__)


def directory() -> Optional[str]:
    """Where entries live: ``programs/`` under the persistent compile
    cache's directory, or None where that cache is off."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    root = jax.config.jax_compilation_cache_dir
    return os.path.join(root, "programs") if root else None


@functools.lru_cache(maxsize=None)
def sources_digest(package: str = _PACKAGE) -> str:
    """A digest of every ``.py`` file under ``package``, names and bytes."""
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(package):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for name in sorted(f for f in filenames if f.endswith(".py")):
            path = os.path.join(dirpath, name)
            h.update(os.path.relpath(path, package).encode() + b"\0")
            with open(path, "rb") as f:
                h.update(f.read())
            h.update(b"\0")
    return h.hexdigest()


def runtime() -> tuple:
    """The JAX versions, the backend, the compiler's flags and the
    trace's settings an executable was built under."""
    import jaxlib

    dev = jax.devices()[0]
    return (jax.__version__, jaxlib.__version__, dev.platform,
            dev.client.platform_version, dev.device_kind,
            jax.device_count(),
            tuple(os.environ.get(v) for v in _FLAG_VARIABLES),
            tuple(jax.config.values.get(k) for k in _TRACE_SETTINGS))


def _plain(part):
    """A key part with its meshes spelled out (a Mesh's repr names its
    shape and axes, not its devices)."""
    if isinstance(part, jax.sharding.Mesh):
        return ("mesh", part.axis_names, part.devices.shape,
                tuple(d.id for d in part.devices.flat))
    return part


def _leaf(x) -> tuple:
    aval = jax.typeof(x)
    sharding = getattr(x, "sharding", None)
    if sharding is None:
        return (aval.shape, str(aval.dtype), aval.weak_type)
    return (aval.shape, str(aval.dtype), aval.weak_type, repr(sharding),
            _plain(getattr(sharding, "mesh", None)),
            tuple(sorted(d.id for d in sharding.device_set)))


def key(static: tuple, args) -> Optional[str]:
    """The entry's name for the program of ``static`` called on ``args``;
    None (and a warning) where it cannot be computed: the program is
    then traced and not stored."""
    from kueue_oss_tpu import features

    try:
        leaves, tree = jax.tree_util.tree_flatten(args)
        parts = (tuple(_plain(p) for p in static),
                 tuple(sorted(features.all_gates().items())),
                 str(tree), tuple(_leaf(x) for x in leaves),
                 sources_digest(), runtime())
    except Exception:  # noqa: BLE001 - the program runs all the same
        _log.warning("no program store key; tracing unstored",
                     exc_info=True)
        return None
    return hashlib.sha256(repr(parts).encode()).hexdigest()


def _count(name: str) -> None:
    from kueue_oss_tpu import metrics
    from kueue_oss_tpu.obs import spans

    spans.count("solver_program_" + name)
    metrics.solver_program_store_total.inc(name)


def _loaded(payload: bytes):
    """The executable a payload of :func:`_serialized` holds, loaded
    onto the devices it was built for."""
    from jax.experimental import serialize_executable

    exe, in_tree, out_tree, device_ids = pickle.loads(payload)
    by_id = {d.id: d for d in jax.devices()}
    devices = [by_id[i] for i in device_ids]
    return serialize_executable.deserialize_and_load(
        exe, in_tree, out_tree, backend=devices[0].client,
        execution_devices=devices)


def load(name: str, args):
    """The stored executable under ``name``, loaded onto the devices it
    was built for, and its output on ``args``; None where there is none,
    or where it does not load or does not run (a failure is counted).
    The first run is waited for, so that an entry that loads and cannot
    run fails here and not in the drain that called it."""
    where = directory()
    path = where and os.path.join(where, name)
    if not path or not os.path.exists(path):
        return None
    try:
        with open(path, "rb") as f:
            blob = f.read()
        head = len(_MAGIC) + 32
        if (blob[:len(_MAGIC)] != _MAGIC or hashlib.sha256(
                blob[head:]).digest() != blob[len(_MAGIC):head]):
            raise ValueError("a truncated or corrupt entry")
        compiled = _loaded(blob[head:])
        out = jax.block_until_ready(compiled(args))
    except Exception:  # noqa: BLE001 - the caller traces instead
        _count("load_failures")
        _log.warning("program %s did not load; tracing it instead", name,
                     exc_info=True)
        return None
    _count("loads")
    _log.info("program %s loaded from the program store", name)
    return compiled, out


#: compiles the persistent cache has served in this process
_cache_hits = [0]


@functools.lru_cache(maxsize=None)
def _count_cache_hits() -> None:
    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            _cache_hits[0] += 1

    jax.monitoring.register_event_listener(on_event)


def compile_and_store(lowered, name: Optional[str], args):
    """``lowered`` compiled, through the persistent cache as ever, and
    its output on ``args``; stored under ``name`` where that is given.
    An executable the persistent cache served was itself deserialized,
    and not every backend serializes such a one whole (XLA:CPU keeps no
    object code for it, and the entry would fail to run in every
    process after this one): it is stored only where its entry, loaded
    here, gives this output bit for bit."""
    _count_cache_hits()
    hits = _cache_hits[0]
    compiled = lowered.compile()
    served = _cache_hits[0] != hits
    # serialized before it first runs: XLA:CPU's executable then adds
    # functions it cannot serialize (a sort's comparator)
    payload = name and _serialized(name, compiled)
    out = compiled(args)
    if payload:
        _write(name, payload, check=(args, out) if served else None)
    return compiled, out


def _serialized(name: str, compiled) -> Optional[bytes]:
    """``compiled`` as an entry holds it; None (and a warning) where it
    cannot be serialized: the program then runs unstored."""
    from jax.experimental import serialize_executable

    try:
        exe, in_tree, out_tree = serialize_executable.serialize(compiled)
        devices = compiled._executable._unloaded_executable.device_list
        return pickle.dumps((exe, in_tree, out_tree, [d.id for d in devices]),
                            protocol=pickle.HIGHEST_PROTOCOL)
    except Exception:  # noqa: BLE001 - the program runs all the same
        _log.warning("program %s was not stored", name, exc_info=True)
        return None


def _same(a, b) -> bool:
    """Whether two outputs hold the same arrays, bit for bit."""
    x, y = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(x) == len(y) and all(
        u.dtype == v.dtype and u.tobytes() == v.tobytes()
        for u, v in zip(map(np.asarray, x), map(np.asarray, y)))


def _write(name: str, payload: bytes, check=None) -> bool:
    """Write ``payload`` under ``name``, to a temporary name and then
    renamed into place. With ``check``, an ``(args, output)`` pair, only
    where the payload loads and gives that output on those arguments. A
    store that cannot be written leaves no entry and costs the caller
    nothing but the attempt."""
    where = directory()
    if not where:
        return False
    tmp = os.path.join(where, f".{name}.{uuid.uuid4().hex}.tmp")
    try:
        if check is not None and not _same(
                _loaded(payload)(check[0]), check[1]):
            raise ValueError("its entry gives another output")
        os.makedirs(where, exist_ok=True)
        with open(tmp, "wb") as f:
            f.write(_MAGIC + hashlib.sha256(payload).digest() + payload)
        os.replace(tmp, os.path.join(where, name))
    except Exception:  # noqa: BLE001 - the program runs all the same
        _log.warning("program %s was not stored", name, exc_info=True)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False
    _count("stores")
    return True
