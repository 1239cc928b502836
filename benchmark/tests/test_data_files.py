"""``BENCHMARK.json`` and the data files under ``benchmark/`` agree.

What the harness finds by name has to be there, and every cell has to
get the metrics the contract asks of it: ``setup_s``, one more
end-to-end metric and a per-layer metric, each per-layer metric moving
an end-to-end metric that the cell reports.
"""

import pytest

from benchmark import deployment, readers, run

BENCH = run.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def names(group: str, cell: str) -> set:
    return {m["name"] for m in run.metrics_of(BENCH, group, cell)}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_finds_its_files_and_metrics(cell):
    entry, cfg_entry = run.find_cell(BENCH, cell)
    cfg = deployment.load_config(cfg_entry["name"])
    assert cfg["name"] == cfg_entry["name"]
    assert cfg["source"] == cfg_entry["source"]
    assert cfg["reduced"] == cfg_entry["reduced"]
    assert deployment.load_traffic(entry["traffic"])["kind"] == "replay"
    e2e = names("end_to_end", cell)
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = run.metrics_of(BENCH, "per_layer", cell)
    assert per_layer
    for m in per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_layer_file_matches_its_entry(metric):
    layer = deployment.load_json("layers", f"{metric['name']}.json")
    for key in ("name", "layer", "unit", "better", "source", "moves"):
        assert layer[key] == metric[key], key
    assert layer["reader"] in readers.READERS
    # with nothing to read (no spans, no trace) a reader of the program's
    # spans or scopes gives nothing, and never a made-up 0
    if layer["reader"] in ("program", "trace_scope"):
        empty = {"passes": 1.0, "window_s": 1.0, "trace": None,
                 "program": None}
        assert readers.read(layer, empty) is None


def test_an_unknown_reader_kind_is_refused():
    with pytest.raises(ValueError, match="no_such_kind"):
        readers.read({"name": "x", "reader": "no_such_kind"}, {})


def test_readers_read_facts_and_leave_out_what_is_not_there():
    facts = {"passes": 4.0, "window_s": 8.0,
             "phase_s": {"solve": 2.0, "device_put": 0.5, "apply": 1.0},
             "span_s": {"run_until_quiet": 5.0},
             "counters": {"passes": 4.0, "passes_with_drain": 1.0,
                          "evictions": 0, "reservations": 0},
             "window": {"top_wait_p95_s": 1.5, "wait_mean_s.large": 0.5},
             "trace": None}
    assert readers.ledger_phase(facts, ["solve"], per="passes") == 0.5
    assert readers.ledger_phase(facts, ["solve"], per="window_s",
                                scale=100.0) == 25.0
    # the engine times device_put inside solve
    assert readers.ledger_phase(facts, ["solve"], ["device_put"],
                                per="passes") == 0.375
    assert readers.span(facts, "run_until_quiet", ["solve", "apply"],
                        per="passes") == 0.5
    assert readers.span(facts, "apply_events") is None
    assert readers.counter(facts, "passes_with_drain", "passes",
                           100.0) == 25.0
    # nothing reserved: no share to report, and never a made-up 0
    assert readers.counter(facts, "evictions", "reservations") is None
    assert readers.window(facts, "top_wait_p95_s") == 1.5
    # a class's waits, and a class with nobody due in the window
    assert readers.window(facts, "wait_mean_s.large") == 0.5
    assert readers.window(facts, "wait_mean_s.absent") is None
    assert readers.trace_busy(facts, "idle_share") is None
    facts["trace"] = {"busy_s": 2.0, "window_s": 8.0}
    assert readers.trace_busy(facts, "idle_share") == 75.0
    assert readers.trace_busy(facts, "busy_per_pass") == 0.5
