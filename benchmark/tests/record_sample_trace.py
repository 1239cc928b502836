"""Records the small device trace kept as ``benchmark/data/sample.xplane.pb``.

Run on the chip (``python benchmark/tests/record_sample_trace.py <dir>``):
a few jitted integer programs, one of them a ``lax.while_loop`` as the
solver's kernels are, separated by sleeps of known length and wrapped in
the benchmark's own ``TraceAnnotation`` spans, so that the reduction's
self-test (``run.py --self-test``) has busy time, idle gaps and spans of
known shape to find. Prints what the reduction reads from it.
"""

import glob
import os
import shutil
import sys
import time


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def loop(x):
        def body(c):
            i, v = c
            return i + 1, (v * 3 + i) % 1000003
        return lax.while_loop(lambda c: c[0] < 2000, body, (0, x))[1]

    @jax.jit
    def scan_sum(x):
        return jnp.cumsum(x, axis=0).sum()

    x = jnp.arange(1 << 20, dtype=jnp.int32).reshape(1024, 1024)
    jax.block_until_ready((loop(x), scan_sum(x)))  # compile outside
    tmp = os.path.join(out_dir, "trace_tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    t0 = time.monotonic()
    with jax.profiler.TraceAnnotation("bench:window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench:pass"):
                with jax.profiler.TraceAnnotation("bench:run_until_quiet"):
                    jax.block_until_ready(loop(x))
                    jax.block_until_ready(scan_sum(x))
            with jax.profiler.TraceAnnotation("bench:sleep"):
                time.sleep(0.05)
    window_s = time.monotonic() - t0
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    dst = os.path.join(out_dir, "sample.xplane.pb")
    shutil.copyfile(found[0], dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print("window_s", window_s, "bytes", os.path.getsize(dst))
    pd = jax.profiler.ProfileData.from_file(dst)
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for e in evs[:6]:
                print("     ", repr(e.name), e.start_ns, e.duration_ns)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1
                  else "chiprun_out/sample_trace"))
