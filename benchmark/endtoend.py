"""The end-to-end metrics, from the driver's record of a window.

What a user of the system sees, on the host's clock: the code of a
``benchmark`` PR (a later PR brings cells, configurations, kinds and
per-layer metrics as files, and is judged on these as they stand).

``adm_per_s``      quota reservations that were kept, a pass's counted
                   as ``passes`` counts the pass, over the window's
                   seconds: sum over passes of ``part(p)`` x the kept
                   reservations stamped inside pass ``p``, with
                   ``part`` = 1 for a pass that ended inside the window
                   and the inside fraction for the pass under way at
                   its end (which the driver finishes). A reservation
                   preempted away before the window's end is not kept.
                   A drain stamps its whole plan inside one commit, so
                   a count by the stamp alone against the window's end
                   reads a step of thousands for 0.2 s of timing
                   (PERF.md section 2); by the part it moves as the
                   passes do
``pass_s``         the window's seconds over the passes in it
``tta_top_p95_s``  95th percentile, over every workload of the top class
                   due in the window, of due creation (or the window's
                   start) to its first quota reservation as the store
                   reports it; one still waiting at the window's end
                   counts the time it had waited
``setup_s``        process start to window start

Beside them, for the reader kind ``window`` and no end-to-end metric:
``wait_mean_s.<klass>`` and ``wait_p95_s.<klass>`` for every ``klass``
of the schedule, by the tail's rule (upstream's ``rangespec.yaml`` files
state the average time to admission by class).
"""

from __future__ import annotations

import bisect
import math


def pass_parts(passes: list, end: float) -> list:
    """The part of each pass that lies inside the window: the pass under
    way when the window ends counts as its part, and so does everything
    inside it."""
    return [1.0 if p["t_end"] <= end else max(0.0, (
        (end - p["t_start"]) / (p["t_end"] - p["t_start"])))
        for p in passes]


def p95(values: list) -> float:
    s = sorted(values)
    return s[max(0, math.ceil(0.95 * len(s)) - 1)]


def kept_stamps(reservations: list, evictions: list, t0: float,
                end: float) -> list:
    """The stamps, sorted, of the reservations from the window's start
    on that no eviction up to the window's end took away."""
    lost = {(k, since) for k, t, since in evictions if t <= end}
    return sorted(t for k, t in reservations
                  if t >= t0 and (k, t) not in lost)


def by_pass(passes: list, stamps: list) -> list:
    """Per pass, how many of the sorted ``stamps`` lie inside it."""
    return [bisect.bisect_right(stamps, p["t_end"])
            - bisect.bisect_left(stamps, p["t_start"]) for p in passes]


def measure(replay, win: dict, parts: list, top: str,
            setup_s: float) -> tuple[dict, dict, dict]:
    """(metric -> value or None; ``facts["window"]`` for the reader kind
    ``window``; what else the record says)."""
    t0, end = win["t0"], win["t_end"]
    seconds = end - t0
    stamps = kept_stamps(replay.reservations, replay.evictions, t0, end)
    kept = by_pass(replay.passes, stamps)
    admitted = sum(part * n for part, n in zip(parts, kept))
    passes = sum(parts)
    start_at = replay.start_at
    first_reserved: dict = {}
    for k, t in replay.reservations:
        first_reserved.setdefault(k, t)
    #: klass -> the wait of every workload of it due in the window
    waits_of: dict = {}
    still = 0
    for a in replay.arrivals:
        if a.due_s >= start_at + seconds:
            continue
        due = max(a.due_s, start_at)
        t = first_reserved.get(a.key)
        if t is None or t > end:
            still += a.klass == top
            wait = start_at + seconds - due
        else:
            wait = max(0.0, start_at + (t - t0) - due)
        waits_of.setdefault(a.klass, []).append(wait)
    waits = waits_of.get(top, [])
    out = {
        "adm_per_s": admitted / seconds,
        "pass_s": seconds / passes if passes else None,
        "tta_top_p95_s": p95(waits) if waits else None,
        "setup_s": setup_s,
    }
    info = {"kept_reservations": sum(kept),
            "kept_in_last_pass": kept[-1] if kept else 0,
            "last_part": parts[-1] if parts else None,
            # the count by the stamp alone, as the harness read
            # adm_per_s up to PR 25: beside the metric, never in it
            "adm_per_s_by_stamp": bisect.bisect_right(stamps, end) / seconds,
            "top_class": top,
            "top_due": len(waits), "top_still_waiting": still,
            "top_wait_median_s": (sorted(waits)[len(waits) // 2]
                                  if waits else None),
            "top_wait_mean_s": (sum(waits) / len(waits)
                                if waits else None)}
    window = {"top_wait_p95_s": out["tta_top_p95_s"]}
    for klass, w in waits_of.items():
        window[f"wait_mean_s.{klass}"] = sum(w) / len(w)
        window[f"wait_p95_s.{klass}"] = p95(w)
    return out, window, info
