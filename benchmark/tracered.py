"""From a profiler trace (``.xplane.pb``) to device busy time, idle
share, the operations that took most device time and the longest idle
gaps by what the host was doing.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. What
it relies on, as one v5e trace showed (``data/sample.xplane.pb``, read
by ``run.py --self-test``):

* a plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules``
  (one event per program run) and ``XLA Ops`` (one event per operation,
  a ``while`` spanning its body's); busy is the union of both lines'
  intervals, so an operation the tracer dropped is still covered by its
  module;
* the benchmark's own ``TraceAnnotation`` spans (``bench:...``) on the
  host plane's ``python`` line, on the same time base (the two clocks
  were ~1 ms apart in the sample);
* the window is the ``bench:window`` span.

An operation's time is its SELF time: its interval less what the
operations nested in it cover. Names are the trace's own, cut at " = ".
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
BUSY_LINES = ("XLA Modules", "XLA Ops")
SPAN_PREFIX = "bench:"
WINDOW = "bench:window"
#: spans that only group others; a gap is named by what is inside them
GROUPS = (WINDOW, "bench:pass")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: list) -> list:
    """Merged [start, end) intervals, sorted."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def self_times(events: list) -> dict:
    """events: (start, end, name) on one line. name -> self seconds."""
    out: dict = {}
    stack: list = []   # [end, name, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            _end, name, own = stack.pop()
            out[name] = out.get(name, 0.0) + own

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return {k: v / 1e9 for k, v in out.items()}


def short(name: str) -> str:
    return name.split(" = ", 1)[0][:120]


def reduce_trace(path: str, extra_spans=None, window_s: float | None = None,
                 top: int = 10) -> dict | None:
    """``extra_spans(window_start_ns)`` gives (name, start_ns, end_ns) on
    the trace's clock for host work that has no annotation of its own
    (the engine's phases, placed from its ledger); they lie inside
    ``bench:run_until_quiet``. ``window_s`` cuts the window to its
    stated length (the ``bench:window`` span also holds the pass that
    was under way when the window ended). Returns None where the trace
    has no device plane or no window span: nothing to read."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    device_lines: dict = {}
    spans: list = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = device_lines.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in BUSY_LINES:
                    lines[line.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    window = [s for s in spans if s[0] == WINDOW]
    if not device_lines or not window:
        return None
    lo, hi = window[0][1], window[0][2]
    if window_s is not None:
        hi = min(hi, lo + window_s * 1e9)
    extra = list(extra_spans(lo)) if extra_spans is not None else []
    busy_ns, n_events = [], 0
    merged_all: list = []
    ops: dict = {}
    for lines in device_lines.values():
        ivs = [(s, e) for evs in lines.values() for s, e, _n in evs]
        n_events += len(ivs)
        merged = clip(union(ivs), lo, hi)
        busy_ns.append(sum(e - s for s, e in merged))
        merged_all.extend(merged)
        op_line = lines.get("XLA Ops") or lines.get("XLA Modules") or []
        for name, sec in self_times(
                [(max(s, lo), min(e, hi), n) for s, e, n in op_line
                 if e > lo and s < hi]).items():
            ops[short(name)] = ops.get(short(name), 0.0) + sec
    n_dev = len(device_lines)
    # gaps: where NO chip ran anything
    gaps, at = [], lo
    for s, e in union(merged_all):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    named = [(n[len(SPAN_PREFIX):], s, e) for n, s, e in spans
             if n not in GROUPS] + extra
    inner = {n for n, _s, _e in extra}

    def label(g0: float, g1: float) -> str:
        """What the host did for most of the gap."""
        cover: dict = {}
        for name, s, e in named:
            ov = min(e, g1) - max(s, g0)
            if ov > 0:
                cover[name] = cover.get(name, 0.0) + ov
        if "run_until_quiet" in cover:
            # its own time: the host cycles, less the drain's phases
            cover["host_cycles"] = cover.pop("run_until_quiet") - sum(
                v for k, v in cover.items() if k in inner)
        return max(cover, key=cover.get) if cover else "unattributed"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": n_dev,
        "device_events": n_events,
        "device_ops": [[k, v] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label(s, e), (e - s) / 1e9] for s, e in longest],
    }
