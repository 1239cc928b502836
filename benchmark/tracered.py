"""From a profiler trace (``.xplane.pb``) to device busy time, idle
share, the operations that took most device time by the program's scope
and the longest idle gaps by what the host was doing. The ONE reducer.

Reads the file with ``jax.profiler.ProfileData`` and, for the one thing
that does not hand out, the protobuf's wire format (``op_names``). What
it relies on, as two v5e traces showed (``data/sample.xplane.pb`` and
``data/sample_spans.xplane.pb``, read by ``run.py --self-test``):

* a plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules``
  (one event per program run) and ``XLA Ops`` (one event per operation,
  a ``while`` spanning its body's); busy is the union of both lines'
  intervals, so an operation the tracer dropped is still covered by its
  module;
* the benchmark's own ``TraceAnnotation`` spans (``bench:...``) and,
  while the program's trace switch is on, the program's
  (``kueue:<name>``, ``kueue_oss_tpu/obs/spans.py``) on the host plane,
  on the same time base (the two clocks were ~1 ms apart in the
  sample). ``self_pieces`` cuts the program's nested spans into pieces
  that do not overlap, each named by the innermost span open there: a
  gap is named by the piece (or the benchmark's span) that covers most
  of it;
* the window is the ``bench:window`` span;
* an operation's op-name path, e.g. ``jit(solve)/while/body/round_body/
  vmap(classical_search)/while/body/add:``, is not among an event's own
  stats (all that ``ProfileData`` hands out) but in the stat ``tf_op``
  of the event's METADATA record on the device plane. ``reduce_scopes``
  sums the operations' self time by the innermost ``jax.named_scope``
  of the program in that path. A fusion carries its root's name.
  Operations XLA's own passes made (a ``cumsum``'s reduce-windows) keep
  only ``jit(f)/while:`` and count as unscoped. Scope names show only
  in executables compiled from a tree that has them: the persistent
  cache's key ignores op metadata (``run.own_the_chip`` keys the cache
  directory by the program's solver sources for that reason).

An operation's time is its SELF time: its interval less what the
operations nested in it cover. Names are the trace's own, cut at " = ".
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
BUSY_LINES = ("XLA Modules", "XLA Ops")
SPAN_PREFIX = "bench:"
#: the program's annotations (``obs/spans.py`` ``TRACE_PREFIX``)
PROGRAM_PREFIX = "kueue:"
WINDOW = "bench:window"
#: spans that only group others; a gap is named by what is inside them
GROUPS = (WINDOW, "bench:pass")
#: name-stack components that are the program's structure, not its names
STRUCTURAL = frozenset((
    "while", "body", "cond", "body_pred", "scan", "closed_call",
    "checkpoint", "remat", "custom_jvp_call", "custom_vjp_call",
    "core_call", "shard_map"))
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_BRANCH = re.compile(r"^branch_\d+_fun$")


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


def self_pieces(spans: list) -> list:
    """Cut nested spans (one thread's, or several threads' whose spans
    do not interleave) into pieces that do not overlap: each stretch is
    named by the innermost span open there."""
    out: list = []
    stack: list = []   # [name, end, cursor]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, end, cur = stack.pop()
            if end > cur:
                out.append((name, cur, end))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        close(s)
        if stack:
            top = stack[-1]
            if s > top[2]:
                out.append((top[0], top[2], s))
            top[2] = max(top[2], s)
        stack.append([name, e, s])
    close(float("inf"))
    return out


def _varint(buf, i: int):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if not b & 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one protobuf message: ints
    for varints, memoryviews for length-delimited fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 1:
            v, i = None, i + 8
        elif wt == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"xplane: wire type {wt}")
        yield field, wt, v


def _map_entry(buf):
    key, val = 0, None
    for f, _wt, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def op_names(path: str, stat: str = "tf_op") -> dict:
    """event name -> the op-name path XLA kept for it (stat ``tf_op`` of
    the event's metadata record), over the ``/device:TPU:n`` planes. The
    events themselves (``lines``) are skipped: ``ProfileData`` reads
    those."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for f_, wt, plane in _fields(space):
        if f_ != 1 or wt != 2:
            continue
        name, event_md, stat_md = "", [], {}
        for f2, wt2, v in _fields(plane):
            if f2 == 2 and wt2 == 2:
                name = bytes(v).decode("utf-8", "replace")
            elif f2 == 4 and wt2 == 2:
                event_md.append(v)
            elif f2 == 5 and wt2 == 2:
                key, val = _map_entry(v)
                if val is not None:
                    for f3, wt3, v3 in _fields(val):
                        if f3 == 2 and wt3 == 2:
                            stat_md[key] = bytes(v3).decode("utf-8",
                                                            "replace")
        if not name.startswith(DEVICE_PREFIX):
            continue
        wanted = {k for k, v in stat_md.items() if v == stat}
        for entry in event_md:
            _key, md = _map_entry(entry)
            if md is None:
                continue
            ev_name, op = "", None
            for f3, wt3, v3 in _fields(md):
                if f3 == 2 and wt3 == 2:
                    ev_name = bytes(v3).decode("utf-8", "replace")
                elif f3 == 5 and wt3 == 2:
                    sid, sval = 0, None
                    for f4, wt4, v4 in _fields(v3):
                        if f4 == 1:
                            sid = v4
                        elif f4 == 5 and wt4 == 2:
                            sval = bytes(v4).decode("utf-8", "replace")
                        elif f4 == 7 and wt4 == 0:
                            sval = stat_md.get(v4)
                    if sid in wanted and sval:
                        op = sval
            if ev_name and op:
                out[ev_name] = op
    return out


def scope_path(op_name: str) -> tuple:
    """The program's scopes in an op-name path, outermost first:
    ``jit(solve)/while/body/round_body/vmap(classical_search)/while/
    body/add:`` -> ("round_body", "classical_search"). The last
    component is the primitive; ``jit(...)`` marks a program or one of
    jax.numpy's own; ``vmap(x)`` and its like wrap a scope."""
    parts = op_name.rstrip(":").split("/")[:-1]
    out = []
    for part in parts:
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            if m.group(1) in ("jit", "pjit"):
                part = ""
                break
            part = m.group(2)
        if part and part not in STRUCTURAL and not _BRANCH.match(part):
            out.append(part)
    return tuple(out)


def op_kind(name: str) -> str:
    """``%add_select_fusion.4 = ...`` -> ``add_select_fusion``."""
    short = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", short)


def by_scope(op_s: dict, names: dict, top: int = 10) -> dict:
    """``op_s``: operation -> self seconds. By innermost program scope
    (``scope_s``, "" for unscoped), the ``top`` operations named
    ``<scope path>:<kind>`` where they have a scope (``device_ops``,
    summed over the numbered copies a recompile renumbers), and the
    scoped share of that list. Where no operation has a scope (an
    executable compiled without the names) ``scope_s`` is None and the
    operations keep the trace's names."""
    scope_s: dict = {}
    ops: dict = {}
    for name, sec in op_s.items():
        path = scope_path(names[name]) if name in names else ()
        inner = path[-1] if path else ""
        scope_s[inner] = scope_s.get(inner, 0.0) + sec
        label = ("/".join(path) + ":" + op_kind(name) if path
                 else short(name))
        ops.setdefault(label, [0.0, bool(path)])[0] += sec
    listed = sorted(ops.items(), key=lambda kv: -kv[1][0])[:top]
    listed_s = sum(v[0] for _k, v in listed)
    scoped = any(v for k, v in scope_s.items() if k)
    return {
        "scope_s": scope_s if scoped else None,
        "device_ops": [[k, v[0]] for k, v in listed],
        "scoped_share_of_listed": (
            sum(v[0] for _k, v in listed if v[1]) / listed_s
            if scoped and listed_s else None)}


def reduce_trace(path: str, window_s: float | None = None,
                 top: int = 10) -> dict | None:
    """``window_s`` cuts the window to its stated length (the
    ``bench:window`` span also holds the pass that was under way when
    the window ended). Returns None where the trace has no device plane
    or no window span: nothing to read."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    device_lines: dict = {}
    spans: list = []      # the benchmark's, by their full name
    program: list = []    # the program's, prefix cut
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
                    end = e.start_ns + e.duration_ns
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, end))
                    elif e.name.startswith(PROGRAM_PREFIX):
                        program.append((e.name[len(PROGRAM_PREFIX):],
                                        e.start_ns, end))
    window = [s for s in spans if s[0] == WINDOW]
    if not device_lines or not window:
        return None
    lo, hi = window[0][1], window[0][2]
    if window_s is not None:
        hi = min(hi, lo + window_s * 1e9)
    busy_ns, n_events = [], 0
    merged_all: list = []
    op_s: dict = {}
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
            op_s[name] = op_s.get(name, 0.0) + sec
    n_dev = len(device_lines)
    # gaps: where NO chip ran anything
    gaps, at = [], lo
    for s, e in union(merged_all):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    pieces = self_pieces(program)
    named = [(n[len(SPAN_PREFIX):], s, e) for n, s, e in spans
             if n not in GROUPS] + pieces
    inner = {n for n, _s, _e in pieces}

    def label(g0: float, g1: float) -> str:
        """What the host did for most of the gap."""
        cover: dict = {}
        for name, s, e in named:
            ov = min(e, g1) - max(s, g0)
            if ov > 0:
                cover[name] = cover.get(name, 0.0) + ov
        if "run_until_quiet" in cover:
            # its own time: what no span of the program's covers
            cover["host_cycles"] = cover.pop("run_until_quiet") - sum(
                v for k, v in cover.items() if k in inner)
        return max(cover, key=cover.get) if cover else "unattributed"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": n_dev,
        "device_events": n_events,
        "idle_gaps": [[label(s, e), (e - s) / 1e9] for s, e in longest],
        "program_spans": len(pieces),
        **by_scope(op_s, op_names(path), top),
    }
