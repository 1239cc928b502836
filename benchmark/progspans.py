#!/usr/bin/env python3
"""What the program's own spans and scopes tell the benchmark.

The program times its phases with one primitive (``kueue_oss_tpu/obs/
spans.py``): process-wide totals by span name, ``kueue:<name>``
annotations in a profiler trace while its switch is on, and
``jax.named_scope`` names on the kernels' stages. This file reads all
three and nothing else of the program:

(a) ``TotalsLog`` snapshots ``spans.totals()`` and ``spans.counters()``
    at the window's start and after every pass and weights the pass
    under way at the window's end by its part, exactly as
    ``run.window_facts`` weights ledger rows: ``facts["program"]``;
(b) ``host_spans`` reads the ``kueue:`` events of the host plane from the
    ``.xplane.pb`` and ``self_pieces`` cuts them into pieces that do not
    overlap, each named by the innermost span open there (a leaf span
    is one piece; a parent gives the pieces its children leave), on the
    trace's clock: ``tracered.reduce_trace`` takes them as its
    ``extra_spans`` in place of phases laid back from a ledger row;
(c) ``reduce_scopes`` sums the self time of the device plane's
    ``XLA Ops`` events by the innermost program scope of the operation.
    One v5e trace, looked at by hand (``data/sample_spans.xplane.pb``),
    showed where the name is: not among an event's own stats (all that
    ``jax.profiler.ProfileData`` hands out) but in the stat ``tf_op`` of
    the event's METADATA record on the ``/device:TPU:n`` plane, e.g.
    ``jit(solve)/while/body/round_body/vmap(classical_search)/while/
    body/add:``; the few fields needed are read from the protobuf's wire
    format here (``op_names``), and joined to the events by name. A
    fusion carries its root's name. Operations XLA's own passes made
    (a ``cumsum``'s reduce-windows) keep only ``jit(f)/while:`` and
    count as unscoped.

Two reader kinds for ``layers/<name>.json`` (``READERS``): ``program``
(``spans`` summed, ``minus`` subtracted, or a ``count``; ``per``
``passes`` / ``window_s`` / a count's or a span's name; ``scale``) and
``trace_scope`` (``scopes``, as a share of ``busy_s``). Both take names
as arguments, so a later span becomes a metric by a data file alone,
and both return None where the program has no such span (the parent
commit): the metric is then left out of the line.

``run.py`` cannot take a reader kind, a fact or other ``extra_spans``
from a new file (``readers.READERS``, ``run.window_facts``,
``run.PHASES`` and ``tracered.SPAN_PREFIX`` are closed lists), and this
PR may not edit it. Until a benchmark PR makes the hook (PERF.md section
7 says which lines), ``python3 benchmark/progspans.py <run.py's
arguments>`` is the builder's tool: it runs ``run.main`` with the hook
put in at run time (``install``) and with the metrics that
``progspans_metrics.json`` lists added to the cell's, and prints the
same result line. No line of ``run.py`` that computes a metric, a
bound, ``correct`` or the window is touched by it.
"""

from __future__ import annotations

import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import tracered  # noqa: E402  (imports no jax itself)

PREFIX = "kueue:"
DEVICE_PREFIX = "/device:TPU:"
WINDOW = "bench:window"
#: name-stack components that are the program's structure, not its names
STRUCTURAL = frozenset((
    "while", "body", "cond", "body_pred", "scan", "closed_call",
    "checkpoint", "remat", "custom_jvp_call", "custom_vjp_call",
    "core_call", "shard_map"))
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_BRANCH = re.compile(r"^branch_\d+_fun$")


# ---------------------------------------------------------------------------
# (a) the program's totals over a window
# ---------------------------------------------------------------------------


def _flat(spans_mod) -> dict:
    out = {}
    for name, v in spans_mod.totals().items():
        out[("s", name)] = v["s"]
        out[("n", name)] = v["n"]
        out[("self_s", name)] = v["self_s"]
    for name, v in spans_mod.counters().items():
        if isinstance(v, (int, float)):
            out[("c", name)] = v
    return out


class TotalsLog:
    """Snapshots of the program's totals: one at ``start`` and one after
    every pass. Only the last pass of a window can be a part pass, so a
    running sum and the last pass's own delta are all that is kept."""

    def __init__(self) -> None:
        try:
            from kueue_oss_tpu.obs import spans
        except ImportError:   # the parent commit: nothing to read
            spans = None
        self.spans = spans
        self.prev: dict = {}
        self.before_last: dict = {}
        self.last: dict = {}
        self.passes = 0

    def start(self) -> None:
        if self.spans is not None:
            self.prev = _flat(self.spans)
        self.before_last, self.last, self.passes = {}, {}, 0

    def on_pass(self, _rec=None) -> None:
        if self.spans is None:
            return
        cur = _flat(self.spans)
        for k, v in self.last.items():
            self.before_last[k] = self.before_last.get(k, 0.0) + v
        self.last = {k: v - self.prev.get(k, 0.0) for k, v in cur.items()
                     if v != self.prev.get(k, 0.0)}
        self.prev = cur
        self.passes += 1

    def window(self, last_part: float = 1.0) -> dict | None:
        """``facts["program"]``: {"spans": name -> {"s", "n", "self_s"},
        "counts": name -> n}; None where the program has no spans."""
        if self.spans is None:
            return None
        total = dict(self.before_last)
        for k, v in self.last.items():
            total[k] = total.get(k, 0.0) + last_part * v
        out: dict = {"spans": {}, "counts": {}}
        for (field, name), v in total.items():
            if field == "c":
                out["counts"][name] = v
            else:
                out["spans"].setdefault(
                    name, {"s": 0.0, "n": 0.0, "self_s": 0.0})[field] = v
        return out


def last_part(passes: list, win: dict) -> float:
    """The part of the window's last pass that lies inside the window
    (``run.window_facts``'s rule)."""
    if not passes:
        return 1.0
    p = passes[-1]
    if p["t_end"] <= win["t_end"]:
        return 1.0
    return (win["t_end"] - p["t_start"]) / (p["t_end"] - p["t_start"])


# ---------------------------------------------------------------------------
# the two reader kinds
# ---------------------------------------------------------------------------


def _count_of(prog: dict, name: str):
    if name in prog["counts"]:
        return prog["counts"][name]
    if name in prog["spans"]:
        return prog["spans"][name]["n"]
    return None


def program(facts: dict, spans=(), minus=(), count=None, per=None,
            scale=1.0):
    """Seconds of ``spans`` less seconds of ``minus`` (or the count
    ``count``: a counter's or a span's), over ``per`` (``passes``,
    ``window_s``, or a count's or a span's name), times ``scale``."""
    prog = facts.get("program")
    if not prog:
        return None
    sp = prog["spans"]
    if count is not None:
        value = _count_of(prog, count)
        if value is None:
            return None
    else:
        if not any(s in sp for s in spans):
            return None
        value = (sum(sp[s]["s"] for s in spans if s in sp)
                 - sum(sp[s]["s"] for s in minus if s in sp))
    if per is not None:
        den = facts.get(per) if per in ("passes", "window_s") else (
            _count_of(prog, per))
        if not den:
            return None
        value = value / den
    return scale * value


def trace_scope(facts: dict, scopes):
    """Self time of the device operations whose innermost program scope
    is one of ``scopes``, as a share (%) of the device's busy time."""
    tr = facts.get("trace")
    if not tr or tr.get("busy_s", 0) <= 0 or not tr.get("scope_s"):
        return None
    return 100.0 * sum(tr["scope_s"].get(s, 0.0)
                       for s in scopes) / tr["busy_s"]


READERS = {"program": program, "trace_scope": trace_scope}


# ---------------------------------------------------------------------------
# (b) the program's spans in the trace
# ---------------------------------------------------------------------------


def host_spans(pd, prefix: str = PREFIX) -> list:
    """(name, start_ns, end_ns) of every ``kueue:`` event of the host
    plane, prefix cut; one list per thread line, concatenated."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name[len(prefix):], e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


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


# ---------------------------------------------------------------------------
# (c) the device's operations by program scope
# ---------------------------------------------------------------------------


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


def reduce_scopes(pd, names: dict, lo: float, hi: float,
                  top: int = 10) -> dict:
    """Self seconds of the ``XLA Ops`` events inside [lo, hi) by
    innermost program scope (``scope_s``, "" for unscoped), the ``top``
    operations by self time named ``<scope path>:<kind>`` where they
    have a scope (``device_ops``), and the scoped share of that list."""
    scope_s: dict = {}
    ops: dict = {}
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            events = [(max(e.start_ns, lo),
                       min(e.start_ns + e.duration_ns, hi), e.name)
                      for e in line.events
                      if e.start_ns + e.duration_ns > lo
                      and e.start_ns < hi]
            for name, sec in tracered.self_times(events).items():
                path = scope_path(names[name]) if name in names else ()
                inner = path[-1] if path else ""
                scope_s[inner] = scope_s.get(inner, 0.0) + sec
                label = ("/".join(path) + ":" + op_kind(name) if path
                         else tracered.short(name))
                ops[label] = ops.get(label, [0.0, bool(path)])
                ops[label][0] += sec
    listed = sorted(ops.items(), key=lambda kv: -kv[1][0])[:top]
    listed_s = sum(v[0] for _k, v in listed)
    return {
        "scope_s": scope_s,
        "device_ops": [[k, v[0]] for k, v in listed],
        "scoped_share_of_listed": (
            sum(v[0] for _k, v in listed if v[1]) / listed_s
            if listed_s else None)}


def window_of(pd, window_s: float | None):
    """[start, end) of the ``bench:window`` span, cut to ``window_s``."""
    for name, s, e in host_spans(pd, prefix=WINDOW):
        if not name:
            return s, (e if window_s is None
                       else min(e, s + window_s * 1e9))
    return None


def reduce_trace(path: str, extra_spans=None, window_s=None,
                 top: int = 10) -> dict | None:
    """``tracered.reduce_trace`` with the program's own spans as
    ``extra_spans`` when the trace holds any (else the caller's), plus
    ``scope_s``, ``program_spans`` and ``device_ops`` named by scope
    where the trace's operations have one."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    pieces = self_pieces(host_spans(pd))
    out = tracered.reduce_trace(
        path, (lambda _lo: pieces) if pieces else extra_spans,
        window_s=window_s, top=top)
    if out is None:
        return None
    out["program_spans"] = len(pieces)
    win = window_of(pd, window_s)
    names = op_names(path)
    if win is not None and names:
        got = reduce_scopes(pd, names, win[0], win[1], top)
        if any(got["scope_s"].get(k) for k in got["scope_s"] if k):
            out["scope_s"] = got["scope_s"]
            out["device_ops"] = got["device_ops"]
            out["scoped_share_of_listed"] = got["scoped_share_of_listed"]
    return out


# ---------------------------------------------------------------------------
# the hook, put in at run time until run.py has it
# ---------------------------------------------------------------------------


def proposed_metrics() -> list:
    with open(os.path.join(HERE, "progspans_metrics.json")) as f:
        return json.load(f)["per_layer"]


def install(run, mode: str) -> dict:
    """Put the hook into a loaded ``run`` module: the two reader kinds,
    ``facts["program"]``, the program's spans as ``extra_spans``, the
    scope names, and the proposed metrics beside the cell's own.
    ``mode``: ``on`` holds the program's trace switch on for the
    window, ``totals`` leaves it off, ``off`` also disables the cycle
    ledger (the program then records nothing: the cost's zero)."""
    import benchmark
    from benchmark import readers

    readers.READERS.update(READERS)
    log = TotalsLog()
    state = {"log": log}

    rows_init = run.DrainRows.__init__
    rows_on_pass = run.DrainRows.on_pass

    def init(self) -> None:     # built right before the window opens
        rows_init(self)
        if mode == "off":
            self.obs.cycle_ledger.enabled = False
        if mode == "on" and log.spans is not None:
            log.spans.trace_on("benchmark")
        log.start()

    def on_pass(self, rec: dict) -> None:
        rows_on_pass(self, rec)
        log.on_pass(rec)

    run.DrainRows.__init__ = init
    run.DrainRows.on_pass = on_pass

    facts_of = run.window_facts

    def window_facts(replay, win, rows, compiles):
        if log.spans is not None:
            log.spans.trace_off("benchmark")
        facts = facts_of(replay, win, rows, compiles)
        facts["program"] = log.window(last_part(replay.passes, win))
        state["facts"] = facts
        return facts

    run.window_facts = window_facts

    class Tracered:             # run.py calls tracered.reduce_trace
        def __getattr__(self, name):
            return getattr(tracered, name)

        @staticmethod
        def reduce_trace(path, extra_spans=None, window_s=None, top=10):
            out = reduce_trace(path, extra_spans, window_s, top)
            state["trace"] = out
            return out

    # run.main does ``from benchmark import tracered`` when it runs
    benchmark.tracered = Tracered()

    metrics_of = run.metrics_of

    def with_proposed(bench, group, cell):
        out = metrics_of(bench, group, cell)
        if group == "per_layer":
            e2e = {m["name"] for m in metrics_of(bench, "end_to_end", cell)}
            have = {m["name"] for m in out}
            out = out + [m for m in proposed_metrics()
                         if m["name"] not in have and m["moves"] in e2e]
        return out

    run.metrics_of = with_proposed
    return state


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--spans", choices=("auto", "on", "totals", "off"),
                    default="auto")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    own, rest = ap.parse_known_args(argv)
    mode = own.spans if own.spans != "auto" else (
        "on" if own.trace else "totals")
    from benchmark import run

    state = install(run, mode)
    rc = run.main(rest + ["--trace", str(own.trace)])
    facts = state.get("facts") or {}
    tr = state.get("trace") or {}
    print(json.dumps({"progspans": {
        "mode": mode, "program": facts.get("program"),
        "scope_s": tr.get("scope_s"),
        "scoped_share_of_listed": tr.get("scoped_share_of_listed"),
        "program_spans_in_trace": tr.get("program_spans")}}),
        file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
