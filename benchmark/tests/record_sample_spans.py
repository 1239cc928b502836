"""Records the second small device trace, ``benchmark/data/sample_spans.xplane.pb``.

Run on the chip (``python benchmark/tests/record_sample_spans.py <dir>``).
Like ``record_sample_trace.py``'s, with what ``progspans.py`` reads on top:

* the jitted program carries ``jax.named_scope`` names as the solver's
  kernels do: a ``lax.while_loop`` whose body (scope ``round_body``) runs
  a stage ``stage_search`` under ``vmap`` and a stage ``stage_scan``;
* the host's work is wrapped in the PROGRAM's span primitive
  (``kueue_oss_tpu.obs.spans``, switched on), so the trace holds
  ``kueue:<name>`` annotations nested as the served path nests them:
  ``quiet`` > ``route`` > ``solver_drain`` > ``solve`` > ``dispatch`` /
  ``wait`` / ``fetch``, then ``schedule`` > ``entries`` (a 30 ms sleep: host
  work with the device idle), inside the benchmark's own ``bench:`` spans.

Prints what the trace holds, every stat of the first device operations
included: that is where the op-name metadata was found by hand.
"""

import glob
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from kueue_oss_tpu.obs import spans

    @jax.named_scope("stage_search")
    def stage_search(row, i):
        return (row * 3 + i) % 1000003

    @jax.named_scope("stage_scan")
    def stage_scan(v):
        return jnp.cumsum(v, axis=1) % 1000003

    @jax.named_scope("round_body")
    def round_body(c):
        i, v = c
        v = jax.vmap(stage_search, in_axes=(0, None))(v, i)
        return i + 1, stage_scan(v)

    @jax.jit
    def solve(x):
        return lax.while_loop(lambda c: c[0] < 400, round_body, (0, x))[1]

    x = jnp.arange(1 << 20, dtype=jnp.int32).reshape(1024, 1024)
    jax.block_until_ready(solve(x))  # compile outside
    tmp = os.path.join(out_dir, "trace_tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    spans.trace_on("sample")
    t0 = time.monotonic()
    with jax.profiler.TraceAnnotation("bench:window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench:pass"):
                with jax.profiler.TraceAnnotation("bench:run_until_quiet"):
                    with spans.span("quiet", cycle=i + 1):
                        with spans.span("route"):
                            with spans.span("solver_drain"):
                                with spans.span("solve"):
                                    with spans.span("dispatch"):
                                        out = solve(x)
                                    with spans.span("wait"):
                                        jax.block_until_ready(out)
                                    with spans.span("fetch"):
                                        np.asarray(out)
                        with spans.span("schedule"):
                            with spans.span("entries"):
                                time.sleep(0.03)
            with jax.profiler.TraceAnnotation("bench:sleep"):
                time.sleep(0.02)
    window_s = time.monotonic() - t0
    spans.trace_off("sample")
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    dst = os.path.join(out_dir, "sample_spans.xplane.pb")
    shutil.copyfile(found[0], dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print("window_s", window_s, "bytes", os.path.getsize(dst))
    print("totals", {k: v for k, v in spans.totals().items()})
    pd = jax.profiler.ProfileData.from_file(dst)
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for e in evs[:8]:
                print("     ", repr(e.name), e.start_ns, e.duration_ns)
                if plane.name.startswith("/device:"):
                    for k, v in e.stats:
                        print("          stat", repr(k), repr(v)[:300])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1
                  else "chiprun_out/sample_trace"))
