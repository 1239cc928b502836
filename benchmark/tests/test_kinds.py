"""A configuration's kind is a module found by the name in its file.

* a toy kind (``toy_kind.py``) is ADDED, as files, to a temporary copy
  of the benchmark's tree and run end to end through ``run.main``: its
  own count is what ``correct`` compares, its control reads the count
  above 0, its ``scheduler_options`` reach the ``Scheduler``;
* a kind that hands the ``Scheduler`` a router setting is refused;
* ``flat`` through the seam gives, seed for seed, what the code gave
  before it moved (``data/flat_golden.json``, recorded at commit 4d96d69
  by ``record_flat_golden.py``).
"""

import json

import pytest

from benchmark import deployment, driver, run
from benchmark.tests import record_flat_golden, toy_kind
from benchmark.tests.kind_tree import add_kind, rehearse


@pytest.fixture
def toy_tree(tmp_path, monkeypatch):
    """The benchmark's data directories copied as they are, and the toy's
    files added beside them."""
    add_kind(tmp_path, monkeypatch, toy_kind)
    return tmp_path


def drive(capsys, *extra) -> dict:
    return rehearse(capsys, "toy-backlog", *extra)[0]


def test_toy_kind_runs_as_added_files(toy_tree, capsys, monkeypatch):
    from kueue_oss_tpu.scheduler.scheduler import Scheduler

    seen = []
    real = Scheduler.__init__

    def init(self, *a, **kw):
        seen.append(kw)
        real(self, *a, **kw)

    monkeypatch.setattr(Scheduler, "__init__", init)
    r = drive(capsys, "--seed", "3")
    assert r["correct"], r["compared"]
    # the counts are the kind's own, beside the harness's
    assert "over_both_flavors" in r["compared"]
    assert "over_quota" not in r["compared"]
    assert r["compared"]["lost"] == {"value": 0, "limit": 0}
    # (the arrivals due before start_at_s are put in by set-up)
    assert 0 < r["attempted"] < 64 and r["failed"] == 0
    # 4 queues x 2 flavors x 3 cpu seated and kept: the second flavor
    # is used, and nobody finishes inside the window
    assert r["metrics"]["adm_per_s"]["value"] == pytest.approx(24 / 1.5)
    assert set(r["metrics"]) == {"adm_per_s", "setup_s"}
    assert seen and all(kw.get("enable_fair_sharing") is True
                        and kw["solver"] == "auto" for kw in seen)


def test_toy_control_breaks_its_guarantee(toy_tree, capsys):
    r = drive(capsys, "--seed", "4", "--control", "double_quota")
    assert not r["correct"]
    assert r["compared"]["over_both_flavors"]["value"] > 0
    # a control of another kind is no control of this cell
    with pytest.raises(SystemExit, match="double_quota"):
        run.main(["--workload", "toy-backlog", "--seconds", "1",
                  "--rehearse", "--control", "double_nominal"])


@pytest.mark.parametrize("key", ["solver_min_backlog", "solver_config",
                                 "solver_reengage_fraction", "solver",
                                 "streaming"])
def test_router_setting_from_a_kind_is_refused(toy_tree, key):
    cfg = deployment.load_config("toy")
    cfg["scheduler"] = {"enable_fair_sharing": True, key: 1}
    with pytest.raises(ValueError, match=key):
        driver.Replay(cfg, [], solver=None)
    cfg["scheduler"] = {"enable_partial_admission": False}
    assert driver.scheduler_options(cfg) == {
        "enable_partial_admission": False}


def test_default_kind_and_its_interface():
    for name in ("upstream-large-scale", "upstream-baseline"):
        cfg = deployment.load_config(name)
        assert "kind" not in cfg          # the accepted files name none
        kind = deployment.kind_of(cfg)
        assert kind.__name__ == f"benchmark.kinds.{deployment.DEFAULT_KIND}"
        assert driver.scheduler_options(cfg) == {}
        assert kind.top_class(cfg) == "large"
        for control, (deploy, count) in kind.controls.items():
            stated = json.dumps(cfg, sort_keys=True)
            assert deploy(cfg) != cfg
            assert json.dumps(cfg, sort_keys=True) == stated
            assert count in kind.audit(cfg, [], [], [])["counts"]
    with pytest.raises(ValueError, match="lacks 'nominal'"):
        deployment.kind_of({}).load({"name": "x", "cohorts": 1,
                                     "cqs_per_cohort": 1})
    with pytest.raises(ModuleNotFoundError):
        deployment.kind_of({"kind": "no_such_kind"})


GOLDEN = deployment.load_json("data", "flat_golden.json")


def test_flat_through_the_seam_is_what_it_was_before_the_move():
    got = record_flat_golden.record()
    assert set(got) == set(GOLDEN)
    for case, want in GOLDEN.items():
        assert got[case] == want, case
