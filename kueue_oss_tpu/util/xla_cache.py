"""Persistent XLA compilation cache: one function decides where it is.

The solver's programs are compiled per (padded-shape, caps) key, and
the flagship preemption drain takes minutes to compile; no process on
a machine should pay that twice. Everything that compiles a solver
program — ``SolverEngine``, ``SolverServer``, ``chip_smoke.py``, the
bench scenarios — calls :func:`enable` before its first compile.

Where ``JAX_COMPILATION_CACHE_DIR`` is set (the deployment manifests
set it), JAX reads the variable itself and this module sets nothing.
Where it is not, the cache goes to ONE fixed directory inside the
checkout: the directory is part of the cache key, so a temporary,
per-process or per-run name would never hit. The compiled preemption
programs themselves are kept in ``programs/`` beneath it
(``solver/program_store.py``): the cache saves their compile, the store
their trace as well.

Reference analog: the reference amortizes scheduling-logic cost by
being a long-lived controller process (cmd/kueue main.go); our
device programs amortize through this cache plus long-lived serve()
loops.
"""

from __future__ import annotations

import os

#: the checkout root (the directory holding the ``kueue_oss_tpu``
#: package); the default cache lives directly under it, git-ignored
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".xla_cache")


def enable() -> str:
    """Make sure JAX's persistent compilation cache has a directory;
    returns the directory in use. Idempotent, and never overrides a
    directory chosen from outside (environment or ``jax.config``)."""
    import jax

    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return jax.config.jax_compilation_cache_dir
