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
``trace``     the trace reduction's result, or None without a device
              trace

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


READERS = {"ledger_phase": ledger_phase, "counter": counter, "span": span,
           "window": window, "trace_busy": trace_busy}


def read(layer: dict, facts: dict):
    kind = layer["reader"]
    if kind not in READERS:
        raise ValueError(f"layers/{layer['name']}.json: reader kind "
                         f"{kind!r} is not one of {sorted(READERS)}")
    return READERS[kind](facts, **layer.get("args", {}))
