"""One span primitive for the served path.

``with spans.span(name, cycle=...)`` times a PHASE (never a workload) on
one clock, ``time.perf_counter_ns``, the clock ``debugger.Tracer`` has
always used and the one every ledger row is stamped with (``mono_ns``).
A per-thread stack gives a span its parent; the cycle id is the one the
flight recorder and the cycle ledger already join on. On exit a span

(a) adds its duration, a count of 1 and its self time (duration less
    its children's) to process-wide **totals** by name: ``totals()`` is
    O(names) to read, what an operator's ``rate()`` takes
    (``kueue_span_seconds_total`` / ``kueue_span_total``) and what the
    benchmark snapshots around a window;
(b) hands its duration to the nearest enclosing span opened with
    ``collect=True``, whose ``phases`` dict becomes a ledger row's
    ``phases`` (name -> seconds, summed where a name repeats);
(c) **only while the trace switch is on**, keeps (seq, name, start,
    end, parent seq, cycle, thread) in a bounded ring, feeds every
    registered sink (a ``debugger.Tracer``) and lies inside a
    ``jax.profiler.TraceAnnotation`` named ``kueue:<name>``, so that a
    profiler trace holds the host's spans on the clock the device plane
    shares.

The switch has no configuration field: it is held on by whoever wants
the record (``trace_on(holder)`` / ``trace_off(holder)``: the
benchmark's traced run, ``devtel.DeepCapture``) and by any live sink
(``add_sink``; sinks are held weakly, so a Tracer that is dropped lets
the switch fall back). With the cycle ledger disabled and the switch off
``span()`` returns one shared no-op after two attribute reads.

``jax`` is never imported here: an annotation is opened only if some
other module has already loaded jax (no profiler can be running
otherwise), so a host-only scheduler stays free of it. While the switch
is on a ``jax.monitoring`` listener attributes JAX's own compile events
to the innermost open span: seconds of jaxpr tracing and lowering
(``retrace_s``) and count and seconds of backend compiles, cache loads
included (``compiles``, ``compile_s``), by span name.

Per-event work (``finish_workload``, the store's add) gets totals only:
``t0 = spans.start()`` ... ``spans.add_since(name, t0)``: two clock
reads, no object, no annotation.
"""

from __future__ import annotations

import sys
import threading
import time
import weakref
from collections import deque
from typing import Optional

from kueue_oss_tpu.obs.ledger import ledger as _ledger

#: the one clock (ns, monotonic, process-local)
now = time.perf_counter_ns

#: annotation prefix in a profiler trace
TRACE_PREFIX = "kueue:"

_RETRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _State:
    """Process-wide switch, ring and books (one instance, ``_S``)."""

    def __init__(self) -> None:
        self.on = False
        self.holders: set = set()
        self.sinks: list = []          # weakref.ref(sink)
        self.ring: deque = deque(maxlen=16384)
        self.seq = 0
        #: name -> [total ns, count, self ns]
        self.totals: dict = {}
        #: name -> count (``count()``)
        self.counts: dict = {}
        #: span name -> [retrace ns, compiles, compile ns]
        self.jax_events: dict = {}
        self.annotation = None         # jax.profiler.TraceAnnotation
        self.listening = False
        #: reentrant: a dying sink's weakref callback may fire inside
        #: a section that already holds it
        self.lock = threading.RLock()


_S = _State()


class _Local(threading.local):
    top = None   # innermost open span of this thread


_L = _Local()


class _NullSpan:
    """What ``span()`` returns when nothing is recording."""

    __slots__ = ()
    phases = None
    seconds = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL = _NullSpan()


class Span:
    """One open phase. Use through ``span()``."""

    __slots__ = ("name", "cycle", "args", "phases", "t0", "t1",
                 "child_ns", "parent", "seq", "ann")

    def __init__(self, name: str, cycle: int, collect: bool,
                 args: Optional[dict]) -> None:
        self.name = name
        self.cycle = cycle
        self.args = args
        self.phases = {} if collect else None
        self.child_ns = 0
        self.seq = 0
        self.ann = None
        self.t0 = self.t1 = 0

    @property
    def seconds(self) -> float:
        """The span's duration, once it has ended."""
        return (self.t1 - self.t0) * 1e-9

    def __enter__(self) -> "Span":
        self.parent = _L.top
        _L.top = self
        if _S.on:
            _S.seq = self.seq = _S.seq + 1
            cls = _S.annotation or _find_jax()
            if cls is not None:
                self.ann = cls(TRACE_PREFIX + self.name)
                self.ann.__enter__()
        self.t0 = now()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = t1 = now()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        dur = t1 - self.t0
        parent = self.parent
        _L.top = parent
        _bump(self.name, dur, 1, dur - self.child_ns)
        if parent is not None:
            parent.child_ns += dur
        while parent is not None:
            if parent.phases is not None:
                ph = parent.phases
                ph[self.name] = ph.get(self.name, 0.0) + dur * 1e-9
                break
            parent = parent.parent
        if self.seq:
            _record(self, t1)
        return False


def _bump(name: str, ns: int, n: int, self_ns: int) -> None:
    rec = _S.totals.get(name)
    if rec is None:
        rec = _S.totals.setdefault(name, [0, 0, 0])
    rec[0] += ns
    rec[1] += n
    rec[2] += self_ns


def _live_sinks():
    for ref in _S.sinks:
        sink = ref()
        if sink is not None and sink.enabled:
            yield sink


def span(name: str, cycle: int = 0, collect: bool = False,
         force: bool = False, **args):
    """Context manager timing one phase. ``collect=True`` makes the span
    the collector of a ledger row: its ``phases`` gathers every
    descendant span's seconds by name. ``force=True`` times the span
    even while nothing records: for a duration its caller returns
    (``DrainResult.solver_time_s``). ``args`` ride to the sinks (a
    Chrome-trace event's ``args``) and cost nothing while off."""
    if not (_ledger.enabled or _S.on or force):
        return _NULL
    return Span(name, cycle, collect, args or None)


def _record(sp: Span, t1: int) -> None:
    """Switch on: the ring and the sinks."""
    parent = sp.parent
    tid = threading.get_ident()
    _S.ring.append((sp.seq, sp.name, sp.t0, t1,
                    parent.seq if parent is not None else 0,
                    sp.cycle, tid))
    if _S.sinks:
        args = dict(sp.args) if sp.args else {}
        if sp.cycle:
            args["cycle"] = sp.cycle
        for sink in _live_sinks():
            sink.add_span(sp.name, sp.t0 // 1000, (t1 - sp.t0) // 1000,
                          tid=tid, **args)


# -- totals-only timing for per-event work -----------------------------------


def start() -> int:
    """Clock reading for ``add_since``; 0 while nothing records."""
    return now() if (_ledger.enabled or _S.on) else 0


def add_since(name: str, t0: int) -> None:
    if t0:
        add(name, (now() - t0) * 1e-9)


def add(name: str, seconds: float, n: int = 1) -> None:
    """Totals only: no object, no stack, no annotation."""
    ns = int(seconds * 1e9)
    _bump(name, ns, n, ns)


def count(name: str, n: int = 1) -> None:
    """A counter at a span boundary (admissions a drain committed)."""
    _S.counts[name] = _S.counts.get(name, 0) + n


# -- reading -------------------------------------------------------------------


def totals() -> dict:
    """name -> {"s": seconds, "n": count, "self_s": seconds not spent
    in child spans}, since process start (or ``reset``)."""
    return {k: {"s": v[0] * 1e-9, "n": v[1], "self_s": v[2] * 1e-9}
            for k, v in list(_S.totals.items())}


def counters() -> dict:
    """The boundary counters, and JAX's compile events by the span
    they fell in: ``retrace_s``, ``compiles``, ``compile_s`` (overall)
    and ``jax_by_span`` (span name -> the same three)."""
    by_span = {k: {"retrace_s": v[0] * 1e-9, "compiles": v[1],
                   "compile_s": v[2] * 1e-9}
               for k, v in list(_S.jax_events.items())}
    out = dict(_S.counts)
    for key in ("retrace_s", "compiles", "compile_s"):
        out[key] = sum(v[key] for v in by_span.values())
    out["jax_by_span"] = by_span
    return out


def ring() -> list:
    """Recorded spans, oldest first: (seq, name, start ns, end ns,
    parent seq or 0, cycle, thread id). Filled only while on."""
    return list(_S.ring)


def current() -> Optional[Span]:
    return _L.top


def collected() -> dict:
    """What the nearest collecting span of this thread has gathered so
    far (a copy): the ledger row's ``phases``."""
    sp = _L.top
    while sp is not None:
        if sp.phases is not None:
            return dict(sp.phases)
        sp = sp.parent
    return {}


def tracing() -> bool:
    return _S.on


# -- the switch ------------------------------------------------------------------


def _refresh() -> None:
    _S.sinks = [r for r in _S.sinks if r() is not None]
    _S.on = bool(_S.holders or _S.sinks)
    if _S.on:
        _find_jax()


def trace_on(holder: str) -> None:
    with _S.lock:
        _S.holders.add(holder)
        _refresh()


def trace_off(holder: str) -> None:
    with _S.lock:
        _S.holders.discard(holder)
        _refresh()


def add_sink(sink) -> None:
    """Register a ``debugger.Tracer`` (anything with ``enabled`` and
    ``add_span(name, ts_us, dur_us, tid=, **args)``) as a sink of every
    span; held weakly. Holds the switch on while it lives."""
    def gone(_ref) -> None:
        with _S.lock:
            _refresh()

    with _S.lock:
        if not any(r() is sink for r in _S.sinks):
            _S.sinks = _S.sinks + [weakref.ref(sink, gone)]
        _refresh()


def remove_sink(sink) -> None:
    with _S.lock:
        _S.sinks = [r for r in _S.sinks if r() is not sink]
        _refresh()


def external(name: str, ts_us: int, dur_us: int, source: str,
             **args) -> None:
    """A span timed elsewhere (a sidecar's solve, echoed over the wire):
    no totals, straight to the sinks on the source's own track."""
    for sink in _live_sinks():
        sink.add_span(name, ts_us, dur_us, source=source, **args)


def set_ring_size(n: int) -> None:
    with _S.lock:
        _S.ring = deque(_S.ring, maxlen=int(n))


def reset() -> None:
    """Forget totals, counters and the ring (tests, a benchmark's
    window start). Open spans and the switch are left alone."""
    with _S.lock:
        _S.totals = {}
        _S.counts = {}
        _S.jax_events = {}
        _S.ring.clear()


# -- jax, only where someone else has loaded it --------------------------------


def _find_jax():
    """``jax.profiler.TraceAnnotation`` if jax is already imported by
    the process, else None. Also registers the monitoring listener."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        _S.annotation = jax.profiler.TraceAnnotation
        if not _S.listening:
            # listeners cannot be unregistered: one, for the process,
            # that does nothing while the switch is off
            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_duration)
            _S.listening = True
    except Exception:   # a partial import: try again at the next span
        return None
    return _S.annotation


def _on_jax_duration(event: str, secs: float, **_kw) -> None:
    if not _S.on:
        return
    if event == _COMPILE_EVENT:
        slot = 1
    elif event in _RETRACE_EVENTS:
        slot = 0
    else:
        return
    top = _L.top
    name = top.name if top is not None else ""
    rec = _S.jax_events.get(name)
    if rec is None:
        rec = _S.jax_events.setdefault(name, [0, 0, 0])
    ns = int(secs * 1e9)
    if slot:
        rec[1] += 1
        rec[2] += ns
    else:
        rec[0] += ns
