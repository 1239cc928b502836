"""Time the preemption drain's victim search alone, on the chip, for
candidate values of ``full_kernels._STAGE2_CHUNK``.

The benchmark's cells cannot settle the chunk: ``large-scale-replay``
never has a live lane and ``baseline-replay`` has 32 lanes. This builds
``upstream-large-scale``'s shape (``--cohorts`` x ``--cqs-per-cohort``
queues, nominal 20) with victims at every lane: every other queue holds
35 ``small`` and one ``medium`` (40 cpu: its nominal and as much
borrowed, so the cohort is full), and every queue's head is a ``large``
(20 cpu, priority 200). A borrowing queue's head may evict its own
queue's work (LowerPriority), the others reclaim from the borrowers
(Any). The first ``n_live`` lanes keep their heads, the rest get none.

    python tools/search_chunk_sweep.py                 # on the chip
    JAX_PLATFORMS=cpu python tools/search_chunk_sweep.py \\
        --cohorts 2 --cqs-per-cohort 8 --chunks 4,16   # rehearsal

One JSON line a (chunk, n_live), to stdout and to
``chiprun_out/search_chunk_sweep.jsonl``; chunk 0 is the ungated
``jax.vmap(classical_search)``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def problem_with_victims(cohorts: int, cqs: int, seed: int):
    """The flood's export with the borrowers' work marked admitted."""
    import numpy as np

    from benchmark import deployment, driver
    from kueue_oss_tpu.solver.tensors import (
        export_problem,
        pad_workloads,
        pow2,
    )

    cfg = deployment.scaled(
        deployment.load_config("upstream-large-scale"), cohorts, cqs, 1)
    replay = driver.Replay(cfg, deployment.schedule(cfg, seed),
                           solver="auto")
    replay.preload(3.3)
    eng = replay.engine
    p = export_problem(replay.store, eng.pending_backlog(),
                       include_admitted=True, parked={},
                       afs=replay.queues.afs, now=3.3)
    h_max, p_max = eng._size_caps(p)
    W = p.n_workloads
    small = np.nonzero(p.wl_prio[:W] == 50)[0]
    medium = np.nonzero(p.wl_prio[:W] == 100)[0]
    borrower = p.wl_cqid[:W] % 2 == 0
    admitted = np.zeros(p.wl_cqid.shape[0], dtype=bool)
    admitted[small[borrower[small]]] = True
    for c in range(0, p.n_cqs, 2):
        admitted[medium[p.wl_cqid[medium] == c][0]] = True
    p.wl_admitted0 = admitted
    p.ad_usage = np.where(admitted[:, None], p.wl_req[:, 0, :], 0).astype(
        p.ad_usage.dtype)
    p.wl_admit_rank = np.where(
        admitted, 1 + np.arange(admitted.shape[0]), 0).astype(
        p.wl_admit_rank.dtype)
    heads = np.full(p.n_cqs, W, dtype=np.int32)
    large = np.nonzero(p.wl_prio[:W] == 200)[0]
    heads[p.wl_cqid[large][::-1]] = large[::-1]    # a queue's first large
    p = pad_workloads(p, pow2(W))
    # the most a cohort's candidate row has to hold
    p_max = max(p_max, pow2(int(admitted.sum()) // cohorts + 1))
    W_null = p.wl_cqid.shape[0] - 1
    return p, np.where(heads == W, W_null, heads), h_max, p_max


def lane_inputs(t, heads, h_max: int, p_max: int):
    """Round 0's search inputs for ``heads`` ([C], W_null for none)."""
    import jax.numpy as jnp

    from kueue_oss_tpu.solver import full_kernels as fk
    from kueue_oss_tpu.solver.kernels import (
        available_all,
        refresh_cohort_usage,
    )

    W_null = t.wl_cqid.shape[0] - 1
    cq_rows = jnp.zeros_like(t.usage0).at[
        t.cq_node[jnp.minimum(t.wl_cqid, t.cq_node.shape[0] - 1)]].add(
        jnp.where(t.wl_admitted0[:, None], t.ad_usage, 0))
    usage = refresh_cohort_usage(t, cq_rows)
    table = fk.build_candidate_table(t, t.wl_admitted0, t.wl_admit_rank0,
                                     t.ad_usage, p_max)
    C = heads.shape[0]
    lane_cq = jnp.minimum(jnp.arange(h_max), C - 1)
    flat_w = jnp.where(jnp.arange(h_max) < C,
                       jnp.asarray(heads)[lane_cq], W_null)
    state = (usage, t.ad_usage, t.wl_admitted0, t.wl_evicted0, t.wl_ts0)
    return state, (flat_w, t.wl_req[flat_w][:, 0, :],
                   available_all(t, usage)[t.cq_node[lane_cq]],
                   table[t.cq_root[lane_cq]])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cohorts", type=int, default=10)
    ap.add_argument("--cqs-per-cohort", type=int, default=100)
    ap.add_argument("--chunks", default="0,32,128,256,1024")
    ap.add_argument("--n-live", default="0,1,8,64,512,100000")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kueue_oss_tpu.solver import full_kernels as fk

    p, heads, h_max, p_max = problem_with_victims(
        args.cohorts, args.cqs_per_cohort, args.seed)
    t = fk.to_device_full(p)
    state, lanes = jax.jit(
        lambda t_: lane_inputs(t_, heads, h_max, p_max))(t)
    W_null = p.wl_cqid.shape[0] - 1
    dev = jax.devices()[0]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out_path = os.path.join(ROOT, "chiprun_out", "search_chunk_sweep.jsonl")
    ref = {}
    with open(out_path, "a") as f:
        for chunk in (int(c) for c in args.chunks.split(",")):
            if chunk:
                fk._STAGE2_CHUNK = chunk
                fn = jax.jit(lambda t_, s, ln: fk._gated_searches(
                    t_, *s, *ln, p_max))
            else:
                fn = jax.jit(lambda t_, s, ln: (jax.vmap(
                    lambda a, b, c, d: fk.classical_search(
                        t_, *s[:4], s[4], a, b, c, d, p_max))(*ln),
                    jnp.asarray(h_max)))
            t0 = time.monotonic()
            compiled = fn.lower(t, state, lanes).compile()
            compile_s = time.monotonic() - t0
            for n in (min(int(x), h_max) for x in args.n_live.split(",")):
                # as round_body leaves a lane without a head: no request
                keep = jnp.arange(h_max) < n
                ln = (jnp.where(keep, lanes[0], W_null),
                      jnp.where(keep[:, None], lanes[1], 0)) + lanes[2:]
                secs = []
                for _ in range(args.reps + 1):
                    t0 = time.monotonic()
                    out, ran = jax.block_until_ready(
                        compiled(t, state, ln))
                    secs.append(time.monotonic() - t0)
                got = [np.asarray(a) for a in out]
                want = ref.setdefault(n, got)
                line = {
                    "chunk": chunk, "lanes": h_max, "p_max": p_max,
                    "n_live": n, "ran": int(ran),
                    "succeeded": int(got[0].sum()),
                    "victims": int(got[2].sum()),
                    "equal_to_first": all(
                        (a == b).all() for a, b in zip(got, want)),
                    "search_s": sorted(secs[1:])[len(secs[1:]) // 2],
                    "search_s_all": secs[1:], "compile_s": compile_s,
                    "platform": dev.platform,
                    "device_kind": dev.device_kind}
                print(json.dumps(line), flush=True)
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
