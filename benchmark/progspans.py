"""The program's own totals over a window: ``facts["program"]``.

The program times its phases with one primitive (``kueue_oss_tpu/obs/
spans.py``): process-wide totals by span name (seconds, count, self
seconds) and counters. ``TotalsLog`` snapshots ``spans.totals()`` and
``spans.counters()`` at the window's start and after every pass and
weights the pass under way at the window's end by its part, exactly as
``run.window_facts`` weights ledger rows. The reader kind ``program``
(``readers.py``) takes span and counter names as arguments, so a later
span becomes a metric by a ``layers/<name>.json`` alone. The spans in a
trace and the kernels' ``jax.named_scope`` names are ``tracered.py``'s.
"""

from __future__ import annotations


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

    def __init__(self, spans) -> None:
        #: the program's ``obs.spans`` module (a test plants a fake)
        self.spans = spans
        self.prev: dict = {}
        self.before_last: dict = {}
        self.last: dict = {}

    def start(self) -> None:
        self.prev = _flat(self.spans)
        self.before_last, self.last = {}, {}

    def on_pass(self, _rec=None) -> None:
        cur = _flat(self.spans)
        for k, v in self.last.items():
            self.before_last[k] = self.before_last.get(k, 0.0) + v
        self.last = {k: v - self.prev.get(k, 0.0) for k, v in cur.items()
                     if v != self.prev.get(k, 0.0)}
        self.prev = cur

    def window(self, last_part: float = 1.0) -> dict:
        """``facts["program"]``: {"spans": name -> {"s", "n", "self_s"},
        "counts": name -> n}."""
        # every span and counter the program has, one that did not move
        # in the window with 0: a reader tells "nothing happened" from
        # "the program has no such span"
        total = {**dict.fromkeys(self.prev, 0.0), **self.before_last}
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
