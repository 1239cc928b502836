"""The main path's programs, compiled for the chip without the chip.

The TPU's compiler is installed wherever these tests run and compiles
for a v5e that is described, not attached
(/opt/skills/guides/on-chip-measurement, section 2.3): what it refuses
here — a slice not aligned to the tiling, too much fast memory, a
program that does not fit 16 GB, a kernel that cannot be partitioned —
it would refuse on the chip, at chip-time prices. Nothing runs, so these
say nothing about results or speed; chip_smoke.py does that on the chip.

The topology is described inside a fixture, never while a module is
imported: only one process may hold the TPU library, and every xdist
worker imports every test file. All such compiles live in THIS file so
one worker takes the library once.

The file also holds the CPU rehearsal of chip_smoke.py itself: at a
tiny size it must run every phase and still fail, because no size and
no switch lets it pass without a TPU.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from kueue_oss_tpu.core.queue_manager import QueueManager
from kueue_oss_tpu.perf.generator import GeneratorConfig, generate
from kueue_oss_tpu.solver import meshutil
from kueue_oss_tpu.solver.engine import SolverEngine
from kueue_oss_tpu.solver.tensors import export_problem, pad_workloads, pow2

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices), (meshutil.MESH_AXIS,))


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip; the next one would
    warn and compile again."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def large_scale(preemption, cohorts=10, cqs_per_cohort=100):
    """The upstream large-scale config as the served path exports it."""
    cfg = GeneratorConfig.large_scale(preemption=preemption)
    cfg.n_cohorts, cfg.cqs_per_cohort = cohorts, cqs_per_cohort
    if not preemption:
        cfg.nominal_quota = 200  # fit-only: the lean kernel's shape
    store, schedule = generate(cfg)
    for g in schedule:
        store.add_workload(g.workload)
    engine = SolverEngine(store, QueueManager(store))
    # the chip's lane budget: left alone, _size_caps asks this
    # process's backend and takes the CPU's
    engine.h_work_budget = 8192
    if not preemption:
        return engine, engine.export()[0]
    return engine, export_problem(store, engine.pending_backlog(),
                                  include_admitted=True)


@pytest.fixture(scope="module")
def lean_problem():
    return large_scale(preemption=False)[1]


def shapes(host, sharding_of):
    """The kernel's input tensors as shapes placed on the described
    devices (there is no device to hold an array)."""
    return type(host)(**{
        f: jax.ShapeDtypeStruct(np.asarray(getattr(host, f)).shape,
                                np.asarray(getattr(host, f)).dtype,
                                sharding=sharding_of(f))
        for f in host._fields})


def compile_full(problem, engine, one_chip, fs_enabled=False):
    from kueue_oss_tpu.solver.full_kernels import (
        host_tensors_full,
        make_full_solver,
    )

    h_max, p_max = engine._size_caps(problem)
    padded = pad_workloads(problem, pow2(problem.n_workloads))
    compiled = make_full_solver(
        int(problem.cq_ngroups.max()), h_max, p_max, fs_enabled).lower(
        shapes(host_tensors_full(padded), lambda f: one_chip)).compile()
    return compiled, h_max, p_max


def test_lean_drain_50k_by_1k_compiles_for_one_chip(lean_problem, one_chip):
    from kueue_oss_tpu.solver.kernels import host_tensors, solve_backlog

    assert lean_problem.n_workloads == 50_000
    assert lean_problem.n_cqs == 1_000
    padded = pad_workloads(lean_problem, pow2(lean_problem.n_workloads))
    compiled = solve_backlog.lower(
        shapes(host_tensors(padded), lambda f: one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def test_sharded_lean_drain_50k_by_1k_compiles_for_four_chips(
        lean_problem, four_chips):
    from kueue_oss_tpu.solver.kernels import host_tensors
    from kueue_oss_tpu.solver.sharded import lean_shardings

    padded = pad_workloads(lean_problem, meshutil.align_pad_target(
        pow2(lean_problem.n_workloads), four_chips))
    assert padded.wl_cqid.shape[0] % 4 == 0
    placed = lean_shardings(four_chips)
    compiled = meshutil.lean_mesh_solver(four_chips).lower(
        shapes(host_tensors(padded), lambda f: placed[f])).compile()
    # per-CQ head selection crosses the shards every round
    assert "all-reduce" in compiled.as_text()
    # a quarter of the rows on each chip, not all of them on the first
    whole = sum(np.asarray(getattr(host_tensors(padded), f)).nbytes
                for f in ("wl_req", "wl_cqid", "wl_rank"))
    assert compiled.memory_analysis().argument_size_in_bytes < whole


def test_pallas_leaf_kernel_lowers_natively_at_640_leaves(one_chip):
    from kueue_oss_tpu.solver import pallas_tas

    args = (jax.ShapeDtypeStruct((640, 1), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.bool_, sharding=one_chip))
    compiled = pallas_tas.leaf_states.lower(
        *args, interpret=False).compile()
    # Mosaic took the kernel: neither interpret mode nor the jnp form
    assert "tpu_custom_call" in compiled.as_text()


def test_sequential_placer_with_balanced_compiles_for_one_chip(
        one_chip, monkeypatch):
    """The drain's placer for upstream's ``tas`` tree (1 x 10 x 64) with
    the balanced placement, at its first batch bucket: the scan, the
    per-row conditionals, the dense subset table and the Pallas leaf
    pass in one program the chip's compiler takes."""
    from kueue_oss_tpu.api.types import Node
    from kueue_oss_tpu.solver import pallas_tas, tas_engine
    from kueue_oss_tpu.solver.tas_kernels import (
        build_levels,
        make_sequential_placer_ext,
    )
    from kueue_oss_tpu.tas.snapshot import build_tas_flavor_snapshot

    monkeypatch.setattr(pallas_tas, "use_pallas", lambda: True)
    monkeypatch.setattr(pallas_tas, "interpret_mode", lambda: False)
    nodes = [Node(name=f"r{r}-h{h}", labels={"b": "b0", "r": f"r{r}"},
                  allocatable={"cpu": 96_000})
             for r in range(10) for h in range(64)]
    levels = build_levels(build_tas_flavor_snapshot(
        "default", ["b", "r", "kubernetes.io/hostname"], nodes))
    placer = make_sequential_placer_ext(
        levels.parents, levels.ranks, tas_engine.BALANCED_MAX_COUNT)
    m, r = tas_engine.BUCKETS[0], len(levels.resources)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = placer.lower(
        arg((640, r), jnp.int32), arg((m, r), jnp.int32),
        arg((m,), jnp.int32), arg((m,), jnp.int32), arg((m,), jnp.bool_),
        arg((m,), jnp.bool_), arg((m,), jnp.bool_), arg((m,), jnp.int32),
        arg((m,), jnp.int32), arg((m, r), jnp.int32), arg((m,), jnp.bool_),
        arg((m,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text      # the leaf pass is Mosaic's
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


def test_full_drain_compiles_for_one_chip(one_chip):
    """The preemption drain through the same code path as the flagship
    program, at a width the chip's compiler takes in about ten
    seconds."""
    engine, problem = large_scale(preemption=True, cohorts=2,
                                  cqs_per_cohort=16)
    compiled, h_max, p_max = compile_full(problem, engine, one_chip)
    assert (h_max, p_max) == (32, 512)
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9


@pytest.mark.slow
@pytest.mark.parametrize("fs_enabled", [False, True],
                         ids=["classical", "fair-sharing"])
def test_full_drain_50k_by_1k_compiles_for_one_chip(one_chip, fs_enabled):
    """The flagship program at the caps the chip gets (h_max=1024):
    about four minutes of compiling each."""
    engine, problem = large_scale(preemption=True)
    compiled, h_max, p_max = compile_full(problem, engine, one_chip,
                                          fs_enabled)
    assert (h_max, p_max) == (1024, 2048)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


# -- chip_smoke.py, rehearsed on the CPU ------------------------------------

#: a few ClusterQueues, a backlog just past the router's flood floor
TINY = ["--cohorts", "2", "--cqs-per-cohort", "6", "--churn", "12",
        "--churn-rounds", "1", "--tas-workloads", "120"]


def test_chip_smoke_runs_every_phase_on_the_cpu_and_fails(tmp_path):
    # one CPU device, as a user's shell has it (conftest asks for eight)
    flags = " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *TINY,
         "--keep-going", "--out", str(tmp_path)],
        # a cache of its own, placed from outside and on (conftest
        # switches it off for what tests spawn): the sidecar phase
        # checks that its programs come out of the one `served` wrote
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": flags,
             "JAX_ENABLE_COMPILATION_CACHE": "true",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla_cache")},
        capture_output=True, text=True, timeout=280)
    lines = [json.loads(line) for line in proc.stdout.splitlines()]
    phases = {o["phase"]: o for o in lines if "phase" in o}
    assert set(phases) == {"lean", "twin", "served", "sidecar", "tas"}, (
        proc.stdout[-2000:], proc.stderr[-2000:])
    # every phase got as far as its checks; what fails is the device
    for name in ("lean", "served", "tas"):
        checks = phases[name]["checks"]
        assert checks.pop("device_is_tpu") is False
        failed = {k for k, v in checks.items() if not v}
        assert failed <= {"resident_on_tpu", "pallas_leaf_kernel_native"}, (
            name, failed)
    sidecar = phases["sidecar"]["checks"]
    assert {k for k, v in sidecar.items() if not v} == {
        "sidecar_backend_is_tpu"}
    assert phases["served"]["checks"]["equals_host_twin"]
    assert phases["served"]["cache_dir"] == str(tmp_path / "xla_cache")
    assert sidecar["manager_initialized_no_backend"]
    assert proc.returncode != 0
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}


def test_chip_smoke_fails_when_the_host_hides_a_refused_program():
    """A single-chip arm that raises degrades the round to host cycles:
    every workload the twin admits is admitted and nothing crashes —
    and the smoke's checks fail all the same."""
    import chip_smoke
    from kueue_oss_tpu import metrics, obs

    refused = []

    def refuse_the_flood(arm):
        if not refused:
            refused.append(arm)
            raise RuntimeError(f"injected: the {arm} arm's program was "
                               "refused")

    metrics.reset_all()
    obs.cycle_ledger.clear()
    try:
        a = chip_smoke.parse_args(TINY)
        twin = chip_smoke.World(a)
        twin.settle("flood")
        w = chip_smoke.World(a, solver="auto")
        w.sched._solver_engine().solve_fault_hook = refuse_the_flood
        facts = chip_smoke.drive_served(a, w)
        fallbacks = chip_smoke.fallback_counts()
    finally:
        metrics.reset_all()
        obs.cycle_ledger.clear()
    assert refused == ["single"]
    # the host finished the round the device was refused...
    assert w.rounds[0]["admitted"] == twin.rounds[0]["admitted"] != []
    assert facts["rounds"][0]["router_chose"] == "host"
    # ...and the smoke does not let that pass
    assert not facts["checks"]["flood_drained_on_device"]
    assert fallbacks["solver_fallback_total"] == {"device_error": 1}
    assert not chip_smoke.no_degradation(fallbacks)


# -- the compile cache: one place decides -----------------------------------

_COMPILE_ONCE = """
import os, jax
from kueue_oss_tpu.util import xla_cache
where = xla_cache.enable()
before = set(os.listdir(where)) if os.path.isdir(where) else set()
jax.jit(lambda x: x * 2 + {salt})(jax.numpy.arange(8)).block_until_ready()
print(where)
print(len(set(os.listdir(where)) - before))
"""


@pytest.mark.parametrize("from_outside", [True, False],
                         ids=["env-var", "checkout"])
def test_compile_cache_goes_where_the_environment_says_else_the_checkout(
        tmp_path, from_outside):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    # persist even a program this small
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="true",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    if from_outside:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "elsewhere")
    proc = subprocess.run(
        [sys.executable, "-c",
         _COMPILE_ONCE.format(salt=int.from_bytes(os.urandom(3), "big"))],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    where, new_entries = proc.stdout.split()
    assert where == (str(tmp_path / "elsewhere") if from_outside
                     else os.path.join(ROOT, ".xla_cache"))
    assert int(new_entries) >= 1
