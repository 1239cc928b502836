"""Per-layer metrics: one small reader per KIND, one data file per metric.

A metric is ``layers/<name>.json``: its layer, unit, the end-to-end
metric it should move, a reader kind and the reader's arguments. The
harness finds the file by the name in ``BENCHMARK.json`` and hands the
reader the window's ``facts``:

``passes``    passes completed in the window (a part pass as its part)
``window_s``  the window's seconds
``counters``  counts over the window (passes, passes_with_drain, drains,
              reservations, evictions, compiles)
``phase_s``   engine ledger phase -> seconds, summed over the window's
              solver-drain rows
``span_s``    the benchmark's own spans -> seconds
``window``    what the driver's record of the window says of its
              workloads (the top class's waits)
``program``   the program's own spans and counters over the window
              (``progspans.TotalsLog``): {"spans": name -> {"s", "n",
              "self_s"}, "counts": name -> n}
``trace``     the trace reduction's result, or None without a device
              trace

Seven kinds. ``program`` and ``trace_scope`` take the program's span,
counter and ``jax.named_scope`` names as arguments, so a new span or
scope in the program is read by a data file alone.

A reader that finds nothing to read returns None and the metric is left
out of the line.
"""

from __future__ import annotations


def _per(value: float, facts: dict, per: str | None):
    if per is None:
        return value
    den = facts.get(per) or facts["counters"].get(per)
    return value / den if den else None


def ledger_phase(facts: dict, phases, minus_phases=(), per=None,
                 scale=1.0):
    if not facts.get("passes"):
        return None
    ph = facts["phase_s"]
    value = _per(sum(ph.get(p, 0.0) for p in phases)
                 - sum(ph.get(p, 0.0) for p in minus_phases), facts, per)
    return None if value is None else scale * value


def counter(facts: dict, num, den=None, scale=1.0):
    c = facts["counters"]
    if num not in c:
        return None
    if den is None:
        return scale * c[num]
    return scale * c[num] / c[den] if c.get(den) else None


def span(facts: dict, span, minus_phases=(), per=None, scale=1.0):
    if span not in facts["span_s"]:
        return None
    value = _per(facts["span_s"][span] - sum(
        facts["phase_s"].get(p, 0.0) for p in minus_phases), facts, per)
    return None if value is None else scale * value


def window(facts: dict, value):
    return facts.get("window", {}).get(value)


def trace_busy(facts: dict, value):
    tr = facts.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    if value == "idle_share":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    if value == "busy_per_pass":
        # passes of the traced window: the trace spans the whole window
        return _per(tr["busy_s"], facts, "passes")
    raise ValueError(f"trace_busy: unknown value {value!r}")


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


READERS = {"ledger_phase": ledger_phase, "counter": counter, "span": span,
           "window": window, "trace_busy": trace_busy, "program": program,
           "trace_scope": trace_scope}


def read(layer: dict, facts: dict):
    kind = layer["reader"]
    if kind not in READERS:
        raise ValueError(f"layers/{layer['name']}.json: reader kind "
                         f"{kind!r} is not one of {sorted(READERS)}")
    return READERS[kind](facts, **layer.get("args", {}))
