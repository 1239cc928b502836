"""The program store: compiled preemption programs kept beside the
compile cache and loaded without tracing (solver/program_store.py).

``full_kernels.full_solver`` calls its program through one executable
per input signature; the first call with a signature loads it from the
store where the store has it, and else traces, compiles and stores it.
These tests hold a loaded program to the traced one bit for bit, the
key to every input it names, a damaged entry to a trace and a rewrite,
a second process to no trace at all, and racing writers to one whole
entry.
"""

import contextlib
import hashlib
import json
import os
import subprocess
import sys
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kueue_oss_tpu import features, metrics
from kueue_oss_tpu.obs import spans
from kueue_oss_tpu.solver import full_kernels as fk
from kueue_oss_tpu.solver import program_store

from test_full_kernel_parity_hard import _mk_wl as _mk_hard_wl
from test_full_kernel_parity_hard import build_hard_scenario
from test_search_liveness import _export, _flood, _p_max

H_MAX = 8
TESTS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(TESTS)
#: the digest of a tree of sources, unmemoized
_digest_of = program_store.sources_digest.__wrapped__


@pytest.fixture(autouse=True)
def _clear_caches_each_test():
    """As tests/test_scan_victim_gate.py: a program must not be served
    to the next test, and XLA:CPU tires of many large compilations."""
    yield
    jax.clear_caches()
    fk._solver_cache.clear()


@pytest.fixture
def store(tmp_path, monkeypatch):
    """A store of the test's own (the tests' persistent cache is off,
    and so is the store wherever it is)."""
    where = tmp_path / "programs"
    monkeypatch.setattr(program_store, "directory", lambda: str(where))
    return where


def _flood_case():
    problem, (h_max, p_max) = _flood(1, 6, 11)
    return problem, 1, h_max, p_max


def _hard_case(seed):
    problem = _export(build_hard_scenario, _mk_hard_wl, seed)
    return problem, int(problem.cq_ngroups.max()), H_MAX, _p_max(problem)


def _counts():
    """Programs built, and executables traced and loaded, in this
    process (full_kernels); entries written and failed loads (the
    store's spans counters)."""
    c = spans.counters()
    return {**fk.solver_programs(),
            **{k: c.get("solver_program_" + k, 0)
               for k in ("stores", "load_failures")}}


def _same(got, want):
    assert len(got) == len(want) == 12
    for i, (g, w) in enumerate(zip(got, want)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, i
        assert g.tobytes() == w.tobytes(), i


def _entries(where):
    return sorted(os.listdir(where)) if os.path.isdir(where) else []


# -- a loaded program is the traced one ---------------------------------------


@pytest.mark.parametrize("case", ["flood", "hard-8", "hard-13"])
def test_loaded_program_gives_the_traced_plan_bit_for_bit(case, store):
    problem, g_max, h_max, p_max = (
        _flood_case() if case == "flood"
        else _hard_case(int(case.split("-")[1])))
    t = fk.to_device_full(problem)
    _stored_then_loaded(lambda: fk.full_solver(g_max, h_max, p_max), t,
                        store)


def _stored_then_loaded(program, t, store):
    """The program's plan traced (and stored), then loaded in what is to
    the store a new process; the two bit for bit."""
    c0 = _counts()
    traced = [np.asarray(a) for a in program()(t)]
    c1 = _counts()
    assert (c1["builds"], c1["traces"]) == (c0["builds"] + 1,
                                            c0["traces"] + 1)
    assert c1["stores"] == c0["stores"] + 1
    assert c1["loads"] == c0["loads"]
    assert len(_entries(store)) == 1

    # a process that has not built it: the program's memo is gone
    fk._solver_cache.clear()
    spans0 = spans.counters().get("solver_program_loads", 0)
    metric0 = metrics.solver_program_store_total.value("loads")
    loaded = program()(t)
    c2 = _counts()
    # new to the process all the same: built, and loaded and not traced
    assert (c2["builds"], c2["traces"]) == (c1["builds"] + 1, c1["traces"])
    assert c2["loads"] == c1["loads"] + 1
    assert spans.counters()["solver_program_loads"] == spans0 + 1
    assert metrics.solver_program_store_total.value("loads") == metric0 + 1
    _same(loaded, traced)
    assert int(traced[4]) >= 1          # the drain ran rounds


def test_a_mesh_program_is_stored_and_loaded_bit_for_bit(store):
    """The mesh arm's program, its rows over four devices: the entry
    loads onto the devices it was built for and gives the same plan."""
    from kueue_oss_tpu.solver.sharded import place_full_tensors
    from kueue_oss_tpu.solver.tensors import pad_workloads

    problem, g_max, h_max, p_max = _hard_case(8)
    mesh = _mesh()
    w1 = problem.wl_cqid.shape[0]
    padded = pad_workloads(problem, w1 - 1 + (-w1) % 4)
    t = place_full_tensors(fk.to_device_full(padded), mesh, "wl")
    assert len(t.wl_cqid.sharding.device_set) == 4
    _stored_then_loaded(
        lambda: fk.full_solver(g_max, h_max, p_max, mesh=mesh), t, store)


def test_a_drain_reports_the_programs_it_loaded(store):
    """The drain's ledger row names traced and loaded programs apart:
    a warm process traces none, and builds the program all the same
    (new to the process, the count behind cold_program_share)."""
    from kueue_oss_tpu import obs
    from kueue_oss_tpu.solver.engine import SolverEngine

    from test_size_caps import build

    def drain():
        s, q = build("flat", 0.5)
        result = SolverEngine(s, q).drain(now=200.0)
        row = obs.cycle_ledger.last_row(obs.SOLVER_DRAIN)
        assert (row.detail["programBuilds"], row.detail["programTraces"],
                row.detail["programLoads"]) == (
            result.program_builds, result.program_traces,
            result.program_loads)
        return result

    fk._solver_cache.clear()
    cold = drain()
    assert (cold.program_builds, cold.program_traces,
            cold.program_loads) == (1, 1, 0)
    fk._solver_cache.clear()
    warm = drain()
    assert (warm.program_builds, warm.program_traces,
            warm.program_loads) == (1, 0, 1)
    assert warm.admitted_keys == cold.admitted_keys
    assert warm.evicted_keys == cold.evicted_keys


# -- the key ------------------------------------------------------------------


def _args(w=8, device=0):
    dev = jax.devices()[device]
    return fk.FullTensors(*[None] * len(fk.FullTensors._fields))._replace(
        wl_cqid=jax.device_put(jnp.zeros(w, jnp.int32), dev),
        wl_req=jax.device_put(jnp.zeros((w, 2), jnp.float32), dev))


def _source_tree(tmp_path, flip: bool) -> str:
    pkg = tmp_path / ("flipped" if flip else "plain")
    (pkg / "sub").mkdir(parents=True)
    (pkg / "a.py").write_bytes(b"x = 1\n")
    (pkg / "sub" / "b.py").write_bytes(b"y = 2\n" if not flip else b"y = 3\n")
    return str(pkg)


def _mesh():
    return jax.sharding.Mesh(np.array(jax.devices()[:4]), ("wl",))


# each changes one input of the key and nothing else
KEY_CHANGES = {
    "caps": lambda mp, tmp: dict(static=fk._solver_key(
        1, 8, 128, False, None, "wl")),
    "fair-sharing": lambda mp, tmp: dict(static=fk._solver_key(
        1, 8, 64, True, None, "wl")),
    "gate": lambda mp, tmp: mp.setattr(
        features, "_overrides", {"TASBalancedPlacement": True}),
    "mesh": lambda mp, tmp: dict(static=fk._solver_key(
        1, 8, 64, False, _mesh(), "wl")),
    "axis": lambda mp, tmp: dict(static=fk._solver_key(
        1, 8, 64, False, None, "rows")),
    "W": lambda mp, tmp: dict(args=_args(w=16)),
    "sharding": lambda mp, tmp: dict(args=_args(device=3)),
    "source-byte": lambda mp, tmp: mp.setattr(
        program_store, "sources_digest",
        lambda: _digest_of(_source_tree(tmp, flip=True))),
    "jax-version": lambda mp, tmp: mp.setattr(
        jax, "__version__", jax.__version__ + ".dev-other"),
    "platform-version": lambda mp, tmp: mp.setattr(
        jax, "devices", _other_platform_version(jax.devices)),
    "xla-flags": lambda mp, tmp: mp.setenv(
        "XLA_FLAGS", os.environ.get("XLA_FLAGS", "")
        + " --xla_cpu_enable_fast_math=false"),
    "libtpu-args": lambda mp, tmp: mp.setenv(
        "LIBTPU_INIT_ARGS", "--xla_tpu_enable_latency_hiding_scheduler"),
    "matmul-precision": lambda mp, tmp: mp.enter_context(
        jax.default_matmul_precision("highest")),
    "x64": lambda mp, tmp: mp.enter_context(jax.enable_x64(True)),
}


def _other_platform_version(devices):
    def fake(*a, **k):
        d = devices(*a, **k)[0]
        return [types.SimpleNamespace(
            platform=d.platform, device_kind=d.device_kind, id=d.id,
            client=types.SimpleNamespace(
                platform_version=d.client.platform_version + " other"))]
    return fake


class _Changes:
    """monkeypatch, and the settings of JAX that live in contexts."""

    def __init__(self, mp, stack):
        self.mp, self.stack = mp, stack

    def __getattr__(self, name):
        return getattr(self.mp, name)

    def enter_context(self, cm):
        self.stack.enter_context(cm)


@pytest.mark.parametrize("change", sorted(KEY_CHANGES))
def test_key_changes_with_each_of_its_inputs(change, tmp_path, monkeypatch):
    monkeypatch.setattr(features, "_overrides", {})
    plain_sources = _digest_of(_source_tree(tmp_path, flip=False))
    monkeypatch.setattr(program_store, "sources_digest",
                        lambda: plain_sources)
    base = dict(static=fk._solver_key(1, 8, 64, False, None, "wl"),
                args=_args())
    key0 = program_store.key(**base)
    assert key0 is not None
    assert program_store.key(**base) == key0        # fixed for its inputs
    with contextlib.ExitStack() as stack:
        changed = KEY_CHANGES[change](
            _Changes(monkeypatch, stack), tmp_path) or {}
        assert program_store.key(**{**base, **changed}) != key0


def test_a_key_that_cannot_be_computed_leaves_the_program_unstored(
        store, monkeypatch):
    """A JAX without an attribute the key reads: the drain runs all
    the same, traced and not stored."""
    def missing():
        raise AttributeError("an attribute this JAX does not have")

    monkeypatch.setattr(program_store, "runtime", missing)
    problem, g_max, h_max, p_max = _flood_case()
    t = fk.to_device_full(problem)
    counts0 = _counts()
    out = fk.full_solver(g_max, h_max, p_max)(t)
    counts1 = _counts()
    assert counts1["traces"] == counts0["traces"] + 1
    assert counts1["stores"] == counts0["stores"]
    assert _entries(store) == []
    assert int(out[4]) >= 1


def test_sources_digest_reads_every_source_file(tmp_path):
    plain = _digest_of(_source_tree(tmp_path, flip=False))
    flipped = _digest_of(_source_tree(tmp_path, flip=True))
    assert plain != flipped
    assert len(program_store.sources_digest()) == 64


# -- a damaged entry ----------------------------------------------------------


def _truncate(path, monkeypatch):
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 2)


def _flip_a_byte(path, monkeypatch):
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) // 2)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))


def _fails_to_run(path, monkeypatch):
    """An entry that loads and cannot run (as XLA:CPU's does when it was
    serialized from an executable the persistent cache had served)."""
    from jax.experimental import serialize_executable

    def broken(*a, **k):
        def run(*a, **k):
            raise RuntimeError("a function of the executable is missing")
        return run

    monkeypatch.setattr(serialize_executable, "deserialize_and_load", broken)


@pytest.mark.parametrize("damage", [_truncate, _flip_a_byte, _fails_to_run],
                         ids=["truncated", "corrupt", "fails-to-run"])
def test_a_damaged_entry_is_traced_counted_and_rewritten(damage, store,
                                                         monkeypatch):
    problem, g_max, h_max, p_max = _flood_case()
    t = fk.to_device_full(problem)
    first = fk.full_solver(g_max, h_max, p_max)(t)
    (name,) = _entries(store)
    with monkeypatch.context() as mp:
        damage(store / name, mp)
        fk._solver_cache.clear()
        counts0 = _counts()
        metric0 = metrics.solver_program_store_total.value("load_failures")
        again = fk.full_solver(g_max, h_max, p_max)(t)
        counts1 = _counts()
    assert counts1["traces"] == counts0["traces"] + 1   # traced as before
    assert counts1["load_failures"] == counts0["load_failures"] + 1
    assert (metrics.solver_program_store_total.value("load_failures")
            == metric0 + 1)
    assert counts1["loads"] == counts0["loads"]
    assert counts1["stores"] == counts0["stores"] + 1   # and rewritten
    _same(again, first)
    assert _entries(store) == [name]
    assert program_store.load(name, t) is not None


def test_a_program_the_compile_cache_served_is_stored_only_if_it_round_trips(
        store, tmp_path):
    """What JAX's persistent cache serves was itself deserialized, and
    XLA:CPU's does not serialize whole (its entry would fail to run):
    such a program is stored only where its entry, loaded back, gives
    the output it gave."""
    from jax.experimental.compilation_cache import compilation_cache

    problem, g_max, h_max, p_max = _flood_case()
    t = fk.to_device_full(problem)
    saved = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir",
                          str(tmp_path / "xla_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        compilation_cache.reset_cache()
        first = fk.full_solver(g_max, h_max, p_max)(t)  # compiled: stored
        assert len(_entries(store)) == 1
        (store / _entries(store)[0]).unlink()
        fk._solver_cache.clear()
        counts0 = _counts()
        again = fk.full_solver(g_max, h_max, p_max)(t)  # the cache serves it
        counts1 = _counts()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert counts1["traces"] == counts0["traces"] + 1
    assert (counts1["stores"], counts1["loads"], counts1["load_failures"]) \
        == (counts0["stores"], counts0["loads"], counts0["load_failures"])
    assert _entries(store) == []
    _same(again, first)


def test_a_served_program_whose_entry_round_trips_is_stored(store,
                                                            monkeypatch):
    x = jnp.arange(8, dtype=jnp.int32)
    lowered = jax.jit(lambda v: v * 2 + 1).lower(x)

    class Served:
        """A lowering whose compile the persistent cache serves."""

        def compile(self):
            program_store._cache_hits[0] += 1
            return lowered.compile()

    checked = []
    real = program_store._loaded
    monkeypatch.setattr(program_store, "_loaded",
                        lambda p: checked.append(1) or real(p))
    stores0 = _counts()["stores"]
    compiled, out = program_store.compile_and_store(Served(), "e" * 64, x)
    assert checked == [1]                           # loaded back first
    assert _counts()["stores"] == stores0 + 1
    assert _entries(store) == ["e" * 64]
    assert (np.asarray(program_store.load("e" * 64, x)[1])
            == np.asarray(out)).all()


# -- a second process ---------------------------------------------------------


_SECOND = """
import hashlib, json, sys
sys.path[:0] = [{tests!r}, {root!r}]
from kueue_oss_tpu.obs import spans
from kueue_oss_tpu.solver import full_kernels as fk
from test_search_liveness import _flood

def no_trace(*a, **k):
    raise AssertionError("the program was traced")

fk._solve_full_impl = no_trace
problem, (h_max, p_max) = _flood(1, 6, 11)
out = fk.full_solver(1, h_max, p_max)(fk.to_device_full(problem))
h = hashlib.sha256()
for a in out:
    h.update(bytes(memoryview(__import__("numpy").asarray(a))))
c = spans.counters()
print(json.dumps({{"programs": fk.solver_programs(),
                   "store": [c.get("solver_program_" + k, 0)
                             for k in ("loads", "stores", "load_failures")],
                   "plan": h.hexdigest()}}))
"""


def test_a_second_process_that_finds_the_entry_never_traces(tmp_path,
                                                            monkeypatch):
    """The store as a restarted process meets it: JAX's own cache
    variables name the directory, and the program is never traced."""
    monkeypatch.setattr(features, "_overrides", {})
    where = tmp_path / "programs"
    monkeypatch.setattr(program_store, "directory", lambda: str(where))
    problem, g_max, h_max, p_max = _flood_case()
    out = fk.full_solver(g_max, h_max, p_max)(fk.to_device_full(problem))
    assert len(_entries(where)) == 1
    h = hashlib.sha256()
    for a in out:
        h.update(bytes(memoryview(np.asarray(a))))

    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_ENABLE_COMPILATION_CACHE="true")
    proc = subprocess.run(
        [sys.executable, "-c", _SECOND.format(tests=TESTS, root=ROOT)],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["programs"] == {"builds": 1, "traces": 0, "loads": 1}
    assert got["store"] == [1, 0, 0]
    assert got["plan"] == h.hexdigest()
    assert len(_entries(where)) == 1


# -- writers that race --------------------------------------------------------


def test_two_writers_leave_one_readable_entry(store):
    x = jnp.arange(8, dtype=jnp.int32)
    compiled = jax.jit(lambda v: v * 2 + 1).lower(x).compile()
    name = "f" * 64
    start = threading.Barrier(3)
    failures0 = _counts()["load_failures"]
    read = []

    payload = program_store._serialized(name, compiled)

    def write():
        start.wait()
        for _ in range(5):
            assert program_store._write(name, payload)

    def reader():
        start.wait()
        for _ in range(20):
            got = program_store.load(name, x)
            read.append(None if got is None else np.asarray(got[1]))

    threads = [threading.Thread(target=f) for f in (write, write, reader)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert _entries(store) == [name]                    # no temporary left
    assert _counts()["load_failures"] == failures0
    want = np.asarray(compiled(x))
    assert all(r is None or (r == want).all() for r in read)
    assert (np.asarray(program_store.load(name, x)[1]) == want).all()


def test_the_store_is_off_with_the_compile_cache():
    """The tests' configuration switches the persistent cache off; the
    store follows it and the program runs as it did without one."""
    assert not jax.config.jax_enable_compilation_cache
    assert program_store.directory() is None
