"""The program's spans and scopes as the driver reads them: the reader
kinds ``program`` and ``trace_scope`` on planted facts, the totals of a
window, the ONE trace reduction on both recorded samples
(``data/sample.xplane.pb``, ``data/sample_spans.xplane.pb``: v5e,
``record_sample_trace.py`` / ``record_sample_spans.py``), and the whole
of it through ``run.main``'s own path."""

import json
import os

import pytest

from benchmark import deployment, progspans, readers, run, tracered

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "data")
SAMPLE = os.path.join(DATA, "sample_spans.xplane.pb")

FACTS = {
    "passes": 4.0, "window_s": 8.0, "trace": None,
    "program": {
        "spans": {
            "quiet": {"s": 6.0, "n": 4, "self_s": 0.2},
            "route": {"s": 2.5, "n": 4, "self_s": 0.1},
            "solver_drain": {"s": 2.4, "n": 1, "self_s": 0.3},
            "solve": {"s": 1.6, "n": 1, "self_s": 0.0},
            "dispatch": {"s": 0.4, "n": 1, "self_s": 0.4},
            "apply.commit": {"s": 0.3, "n": 1, "self_s": 0.3},
            "schedule": {"s": 3.2, "n": 10, "self_s": 0.2},
            "entries": {"s": 2.0, "n": 8, "self_s": 2.0},
            "store.finish": {"s": 0.002, "n": 1000, "self_s": 0.002}},
        "counts": {"drain_admitted": 600, "retrace_s": 0.08}}}


def test_program_reader_sums_subtracts_counts_and_divides():
    rd = readers.program
    assert rd(FACTS, ["entries"], per="window_s", scale=100.0) == 25.0
    assert rd(FACTS, ["route"], ["solver_drain"], per="window_s",
              scale=100.0) == pytest.approx(1.25)
    assert rd(FACTS, count="schedule", per="passes") == 2.5
    assert rd(FACTS, count="retrace_s", per="window_s",
              scale=100.0) == pytest.approx(1.0)
    assert rd(FACTS, ["apply.commit"], per="drain_admitted",
              scale=1e6) == pytest.approx(500.0)
    assert rd(FACTS, ["store.finish"], per="store.finish",
              scale=1e6) == pytest.approx(2.0)
    # a span the program does not have: nothing, and never a made-up 0
    assert rd(FACTS, ["no_such_span"], per="window_s") is None
    assert rd(FACTS, count="no_such_count") is None
    assert rd(FACTS, ["entries"], per="no_such_count") is None
    # an untraced run keeps no totals
    assert rd({"passes": 1.0, "window_s": 1.0}, ["entries"]) is None
    assert rd({**FACTS, "program": None}, ["entries"]) is None


def test_trace_scope_reader_is_a_share_of_busy_time():
    rd = readers.trace_scope
    assert rd(FACTS, ["classical_search"]) is None
    facts = {**FACTS, "trace": {"busy_s": 4.0, "window_s": 8.0,
                                "scope_s": None}}
    assert rd(facts, ["classical_search"]) is None    # no scopes read
    facts["trace"]["scope_s"] = {"classical_search": 1.0,
                                 "walk_assign": 0.5, "": 0.2}
    assert rd(facts, ["classical_search"]) == 25.0
    assert rd(facts, ["nominate_full", "walk_assign"]) == 12.5


class FakeSpans:
    def __init__(self):
        self.t = {}
        self.c = {}

    def totals(self):
        return {k: dict(v) for k, v in self.t.items()}

    def counters(self):
        return {**self.c, "jax_by_span": {}}


def test_totals_log_weights_the_last_pass_by_its_part():
    fake = FakeSpans()
    log = progspans.TotalsLog(fake)
    fake.t = {"quiet": {"s": 100.0, "n": 7, "self_s": 1.0},   # set-up's
              "flush": {"s": 9.0, "n": 3, "self_s": 9.0}}
    fake.c = {"retrace_s": 0.0}
    log.start()
    for i in range(3):
        fake.t["quiet"]["s"] += 2.0
        fake.t["quiet"]["n"] += 1
        fake.c["drain_admitted"] = 10 * (i + 1)
        log.on_pass()
    prog = log.window(last_part=0.25)
    assert prog["spans"]["quiet"]["s"] == pytest.approx(4.5)
    assert prog["spans"]["quiet"]["n"] == pytest.approx(2.25)
    assert prog["counts"]["drain_admitted"] == pytest.approx(22.5)
    # what the program has but did not move in the window reads 0, not
    # nothing: the reader then reports a 0 share, and the line keeps it
    assert prog["spans"]["flush"] == {"s": 0.0, "n": 0.0, "self_s": 0.0}
    assert prog["counts"]["retrace_s"] == 0.0
    facts = {"program": prog, "window_s": 2.0, "passes": 2.25}
    assert readers.program(facts, count="retrace_s", per="window_s") == 0.0
    assert readers.program(facts, ["flush"], per="window_s") == 0.0
    assert readers.program(facts, ["no_such_span"]) is None


def test_self_pieces_names_every_stretch_by_the_innermost_span():
    got = tracered.self_pieces([
        ("quiet", 0, 100), ("route", 10, 40), ("solve", 15, 35),
        ("schedule", 50, 90), ("entries", 60, 80)])
    assert sorted(got, key=lambda p: p[1]) == [
        ("quiet", 0, 10), ("route", 10, 15), ("solve", 15, 35),
        ("route", 35, 40), ("quiet", 40, 50), ("schedule", 50, 60),
        ("entries", 60, 80), ("schedule", 80, 90), ("quiet", 90, 100)]
    assert sum(e - s for _n, s, e in got) == 100


def test_scope_path_keeps_the_programs_names_only():
    sp = tracered.scope_path
    assert sp("jit(solve)/while/body/round_body/vmap(classical_search)"
              "/while/body/add:") == ("round_body", "classical_search")
    assert sp("jit(solve)/while/body/round_body/vmap(stage_search)/"
              "jit(remainder)/jit(_where)/select_n:") == (
        "round_body", "stage_search")
    assert sp("jit(solve)/while:") == ()
    assert sp("jit(delta_scatter)/delta_scatter/scatter:") == (
        "delta_scatter",)
    assert tracered.op_kind(
        "%add_select_fusion.4 = s32[8]{0} fusion(...)") == (
        "add_select_fusion")
    assert tracered.op_kind("%while") == "while"


def test_reduction_of_the_recorded_sample_with_spans_and_scopes():
    # three rounds of a 400-step while_loop (7.8 ms each) whose body
    # runs stage_search under vmap and stage_scan, a 30 ms sleep inside
    # the program's span ``entries`` and a 20 ms sleep of the
    # benchmark's between them, in a 184 ms window
    names = tracered.op_names(SAMPLE)
    assert len(names) >= 8
    assert any(v.endswith("vmap(stage_search)/jit(remainder)/select_n:")
               for v in names.values())
    r = tracered.reduce_trace(SAMPLE)
    assert r["devices"] == 1 and abs(r["window_s"] - 0.1841) < 0.001
    assert 0.0215 < r["busy_s"] < 0.0235
    assert r["program_spans"] == 51
    # the gaps carry the name of the program's span the host was in
    assert [g[0] for g in r["idle_gaps"][:3]] == ["entries"] * 3
    assert all(0.052 < g[1] < 0.056 for g in r["idle_gaps"][:3])
    # the operations stand under their scope's name, summed over the
    # numbered copies a recompile renumbers
    top = dict(r["device_ops"])
    assert r["device_ops"][0][0] == (
        "round_body/stage_search:add_select_fusion")
    assert 0.0070 < top["round_body/stage_search:add_select_fusion"] < 0.0077
    assert "round_body/stage_scan:add_remainder_fusion" in top
    # what XLA's own passes made of the cumsum keeps no scope
    assert "%fusion.5" in top
    assert 0.80 < r["scoped_share_of_listed"] < 0.85
    assert set(r["scope_s"]) == {"stage_search", "stage_scan", ""}
    assert sum(r["scope_s"].values()) == pytest.approx(r["busy_s"],
                                                       rel=0.01)
    facts = {"trace": r}
    assert 31.0 < readers.trace_scope(facts, ["stage_search"]) < 34.5
    assert 48.0 < readers.trace_scope(facts, ["stage_scan"]) < 51.5


def test_first_sample_has_no_scopes_and_keeps_the_traces_names():
    r = tracered.reduce_trace(os.path.join(DATA, "sample.xplane.pb"))
    assert r["program_spans"] == 0 and r["scope_s"] is None
    assert r["scoped_share_of_listed"] is None
    assert r["device_ops"][0][0] == "%add_select_fusion.2"
    assert [g[0] for g in r["idle_gaps"][:3]] == ["sleep"] * 3
    assert readers.trace_scope({"trace": r}, ["stage_search"]) is None


def test_self_test_passes():
    assert run.self_test() == 0


def test_traced_run_reports_the_programs_spans_through_run_main(capsys):
    """``run.main`` itself (``--rehearse``: the CPU, no device trace to
    read): every accepted metric of kind ``program`` is on the line, the
    scope shares are not (no trace), and the inside adds up to the
    outside."""
    from kueue_oss_tpu.obs import spans

    assert run.main(["--workload", "large-scale-replay", "--seed", "8",
                     "--seconds", "4", "--trace", "1", "--rehearse",
                     "--cohorts", "2", "--cqs-per-cohort", "8"]) == 0
    assert not spans.tracing()        # the switch is handed back
    cap = capsys.readouterr()
    r = json.loads(cap.out.strip().splitlines()[-1])
    info = next(json.loads(line)["info"] for line in cap.err.splitlines()
                if line.startswith('{"info"'))
    assert r["correct"], r["compared"]
    bench = run.load_benchmark()
    want = set()
    for m in run.metrics_of(bench, "per_layer", "large-scale-replay"):
        layer = deployment.load_json("layers", f"{m['name']}.json")
        if layer["reader"] == "program":
            want.add(m["name"])
        elif layer["reader"] in ("trace_scope", "trace_busy"):
            assert m["name"] not in r["metrics"]
    assert len(want) == 18 and want <= set(r["metrics"])
    v = {k: m["value"] for k, m in r["metrics"].items()}
    assert v["cycles_per_pass"] >= 1.0
    # parking is a part of apply: a share of the window like its siblings
    assert 0.0 <= v["apply_park_share"] < 100.0 * (
        v["apply_s_per_pass"] * info["passes"] / 4.0)
    # PERF.md section 5's identity: the cycle's parts, the router and
    # the drain's untimed rest are the host cycles (the benchmark's span
    # around run_until_quiet less the drains' phases), in % of the window
    inside = sum(v[k] for k in (
        "cycle_requeue_share", "cycle_snapshot_share",
        "cycle_nominate_share", "cycle_entries_share", "cycle_flush_share",
        "quiet_overhead_share", "route_share", "drain_untimed_share"))
    outside = 100.0 * v["host_cycle_s_per_pass"] * info["passes"] / 4.0
    assert inside == pytest.approx(outside, rel=0.1)
    assert (v["solve_dispatch_share"] + v["solve_wait_share"]
            + v["solve_fetch_share"]) == pytest.approx(
        100.0 * v["solve_wall_s_per_pass"] * info["passes"] / 4.0, rel=0.1)


def test_cache_directory_is_named_by_the_solver_sources(tmp_path, monkeypatch):
    key = run.program_key()
    assert len(key) == 16 and key == run.program_key()
    src = tmp_path / "kueue_oss_tpu" / "solver"
    src.mkdir(parents=True)
    (src / "kernels.py").write_text("@jax.named_scope('round')\n")
    (src / "notes.txt").write_text("not a source")
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    first = run.program_key()
    (src / "notes.txt").write_text("changed")
    assert run.program_key() == first != key
    # a renamed scope is a changed source: another directory
    (src / "kernels.py").write_text("@jax.named_scope('round2')\n")
    assert run.program_key() != first
