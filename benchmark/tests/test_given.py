"""Guarantees about WHICH quota and WHICH nodes reach the reference.

* a toy kind with a topology and two flavors (``toy_placement_kind.py``)
  is ADDED, as files, to a temporary copy of the benchmark's tree and run
  end to end through ``run.main``: its reference keeps node, rack and
  per-flavor books from what each reservation was ``given`` and from
  nothing else; a sound run reads its three counts 0, on the host's path
  and on the device's; its control (nodes of twice the stated cpu) and a
  placement altered where it is produced read ``correct`` false;
* a workload that loses and regains its reservation inside one pass
  stands in neither ``added`` nor ``removed``, and the reference sees it
  move all the same;
* the feature gates a kind states reach the program, and any gate that
  is not upstream's scheduling behaviour is refused;
* a kind that does not ask has no ``given`` in its records.
"""

import dataclasses
import json

import pytest

from benchmark import deployment, driver, run
from benchmark.tests import toy_placement_kind
from benchmark.tests.kind_tree import add_kind, rehearse


@pytest.fixture
def racks_tree(tmp_path, monkeypatch):
    from kueue_oss_tpu import features

    add_kind(tmp_path, monkeypatch, toy_placement_kind)
    yield tmp_path
    features.reset()


#: the toy as its file states it (32 gangs: the router keeps the host's
#: path), and wide enough for the router to drain on the device (640
#: gangs over 8 queues, 2 x 4 racks x 8 hosts). There quota binds in both
#: flavors (8 x 16 cpu of 'spot' on 128 cpu of nodes): a device drain
#: seats by quota and places afterwards, so where the nodes bind first
#: its plan is refused in part (``solver_plan_fallbacks_total`` 43 at
#: 4 hosts a rack), which the harness counts
SIZES = {"host": {}, "device": {"queues": 8, "per_queue": 80, "racks": 4,
                                "hosts_per_rack": 8}}


def resize(tree, size: str) -> None:
    cfg = {**toy_placement_kind.CONFIG, **SIZES[size]}
    (tree / "configs" / "racks.json").write_text(json.dumps(cfg))


def drive(capsys, *extra) -> tuple[dict, dict]:
    return rehearse(capsys, "racks-backlog", *extra)


def values(result: dict) -> dict:
    return {k: c["value"] for k, c in result["compared"].items()}


@pytest.mark.parametrize("size", sorted(SIZES))
def test_placement_kind_runs_as_added_files(racks_tree, capsys, monkeypatch,
                                            size):
    from kueue_oss_tpu import features

    resize(racks_tree, size)
    logs, gates_seen = [], []
    real = driver.given_as_data

    def keep(pass_log):
        gates_seen.append(features.enabled("TASBalancedPlacement"))
        real(pass_log)
        logs.append(pass_log)

    monkeypatch.setattr(driver, "given_as_data", keep)
    assert not features.enabled("TASBalancedPlacement")
    r, info = drive(capsys, "--seed", "3")
    assert r["correct"], r["compared"]
    c = values(r)
    assert c["node_over"] == c["rack_split"] == c["flavor_over"] == 0
    assert (info["counters"]["drains"] > 0) == (size == "device")
    # the gate the kind states reached the program and the info line
    assert gates_seen == [True]
    assert info["feature_gates"] == {"TASBalancedPlacement": True}
    # every reservation of the window is in ``given`` (nobody loses one
    # in this toy), as plain data; both flavors are in use
    given = [g for rec in logs[0] for g in rec["given"]]
    assert sorted(g["key"] for g in given) == sorted(
        k for rec in logs[0] for k in rec["added"])
    assert json.loads(json.dumps(given)) == given
    assert len(given) == info["counters"]["reservations"] > 0
    assert {g["podsets"][0]["flavors"]["cpu"] for g in given} == {
        "reserved", "spot"}
    ps = given[0]["podsets"][0]
    assert set(ps) == {"name", "count", "flavors", "usage", "topology"}
    assert ps["usage"] == {"cpu": ps["count"]} and ps["count"] > 1
    assert sum(n for _v, n in ps["topology"]["domains"]) == ps["count"]
    # the window's waits by class, for a reader of kind ``window``
    assert info["window"]["wait_mean_s.gang"] > 0
    assert info["window"]["wait_p95_s.gang"] == info["window"][
        "top_wait_p95_s"]


@pytest.mark.parametrize("size, seed", [("host", 4), ("host", 5),
                                        ("host", 6), ("device", 4)])
def test_control_double_nodes_is_not_correct(racks_tree, capsys, size, seed):
    resize(racks_tree, size)
    r, _info = drive(capsys, "--seed", str(seed), "--control",
                     "double_nodes")
    assert not r["correct"]
    c = values(r)
    assert c["node_over"] > 0
    # the program itself was sound on the deployment it was given
    assert c["rack_split"] == c["flavor_over"] == c["lost"] == 0


def test_altered_placement_is_not_correct(racks_tree, capsys, monkeypatch):
    """An answer altered where it is produced: the host's ``_admit``
    puts one pod of every gang on a host of the flavor's other rack."""
    from kueue_oss_tpu.api.types import TopologyDomainAssignment
    from kueue_oss_tpu.scheduler.scheduler import Scheduler

    real = Scheduler._admit

    def admit_elsewhere(self, e, now):
        real(self, e, now)
        wl = self.store.workloads.get(e.info.key)
        if wl is not None and wl.status.admission is not None:
            ta = wl.status.admission.podset_assignments[
                0].topology_assignment
            flavor, rack, _host = ta.domains[0].values[-1].split("-")
            ta.domains[0].count -= 1
            ta.domains.append(TopologyDomainAssignment(
                [f"{flavor}-r{1 - int(rack[1:])}-h0"], 1))

    monkeypatch.setattr(Scheduler, "_admit", admit_elsewhere)
    r, _info = drive(capsys, "--seed", "7")
    assert not r["correct"]
    assert values(r)["rack_split"] > 0


def reserve(store, wl, flavor: str, hosts: dict) -> None:
    """What ``scheduler._admit`` writes, by hand."""
    from kueue_oss_tpu.api.types import (
        Admission, PodSetAssignment, TopologyAssignment,
        TopologyDomainAssignment, WorkloadConditionType)

    count = wl.podsets[0].count
    wl.status.admission = Admission(
        cluster_queue=wl.queue_name[3:], podset_assignments=[
            PodSetAssignment(
                name="main", flavors={"cpu": flavor},
                resource_usage={"cpu": count}, count=count,
                topology_assignment=TopologyAssignment(
                    levels=["kubernetes.io/hostname"],
                    domains=[TopologyDomainAssignment([h], n)
                             for h, n in hosts.items()]))])
    wl.set_condition(WorkloadConditionType.QUOTA_RESERVED, True)
    store.update_workload(wl)


def evict(store, wl) -> None:
    from kueue_oss_tpu.api.types import WorkloadConditionType

    wl.set_condition(WorkloadConditionType.QUOTA_RESERVED, False)
    wl.status.admission = None
    store.update_workload(wl)


def test_lost_and_regained_inside_one_pass(racks_tree):
    """Pass 0 seats ``a`` on ``reserved`` and ``b`` on a ``spot`` host;
    in pass 1 ``a`` loses its reservation and regains one on ``b``'s
    host. ``a`` stands in neither ``added`` nor ``removed`` of pass 1;
    its two ``given`` entries say where it was and where it went, and
    the reference, which has nothing else, finds the host over."""
    cfg = deployment.load_config("racks")
    kind = deployment.kind_of(cfg)
    arrivals = [x for x in deployment.schedule(cfg, 1) if x.pods == 2][:2]
    # ``b`` fills its host alone
    arrivals[1] = dataclasses.replace(arrivals[1], pods=4)
    replay = driver.Replay(cfg, arrivals, solver=None)
    store = replay.store
    a, b = (replay.workloads[x.key] for x in arrivals)
    script = [
        lambda: (reserve(store, a, "reserved", {"reserved-r0-h0": 2}),
                 reserve(store, b, "spot", {"spot-r1-h1": 4})),
        lambda: (evict(store, a),
                 reserve(store, a, "spot", {"spot-r1-h1": 2})),
        lambda: (evict(store, a), reserve(store, a, "spot",
                                          {"spot-r0-h0": 1,
                                           "spot-r0-h1": 1}),
                 evict(store, a)),
    ]
    replay.sched.requeue_due = lambda now: None
    replay.sched.run_until_quiet = lambda now: script.pop(0)() and 0
    replay.one_pass(0.1, [("arrive", x.key, x.due_s) for x in arrivals])
    replay.one_pass(0.2, [])
    replay.one_pass(0.3, [])
    p0, p1, p2 = replay.passes
    assert p0["added"] == sorted([a.key, b.key]) and p0["removed"] == []
    assert p1["added"] == [] and p1["removed"] == []
    # regained and lost again inside pass 2: lost, and one entry more
    assert p2["added"] == [] and p2["removed"] == [a.key]
    driver.given_as_data(replay.passes)
    mine = [g for p in replay.passes for g in p["given"]
            if g["key"] == a.key]
    assert [len(p["given"]) for p in replay.passes] == [2, 1, 1]
    assert [g["podsets"][0]["flavors"]["cpu"] for g in mine[:2]] == [
        "reserved", "spot"]
    assert mine[1]["podsets"][0]["topology"] == {
        "levels": ["kubernetes.io/hostname"],
        "domains": [[["spot-r1-h1"], 2]]}
    got = kind.audit(cfg, arrivals, [], replay.passes)
    assert got["counts"] == {"node_over": 1, "rack_split": 0,
                             "flavor_over": 0}
    assert got["first"]["node_over"] == {
        "pass": 1, "detail": "spot-r1-h1: 6 > 4"}
    assert got["holding"] == 1
    # the twin's witness reads ``added`` and ``removed`` and leaves
    # ``given`` as it found it
    before = json.dumps([p["given"] for p in replay.passes])
    driver.replay_log(cfg, arrivals, [], replay.passes)
    assert json.dumps([p["given"] for p in replay.passes]) == before


def test_reference_counts_each_guarantee(racks_tree):
    cfg = deployment.load_config("racks")
    kind = deployment.kind_of(cfg)
    arrivals = deployment.schedule(cfg, 2)
    first = {}
    for x in arrivals:
        first.setdefault((x.cq, x.pods), x)

    def given(x, flavor, hosts, **over):
        return {"key": x.key, "podsets": [{
            "name": "main", "count": x.pods, "flavors": {"cpu": flavor},
            "usage": {"cpu": x.pods},
            "topology": {"levels": ["kubernetes.io/hostname"],
                         "domains": [[[h], n] for h, n in hosts.items()]},
            **over}]}

    def audit(*entries):
        return kind.audit(cfg, arrivals, [], [{
            "events": [], "added": sorted(e["key"] for e in entries),
            "removed": [], "given": list(entries)}])["counts"]

    four, three = first["cq-0", 4], first["cq-0", 3]
    sound = given(four, "spot", {"spot-r0-h0": 3, "spot-r0-h1": 1})
    assert not any(audit(sound).values())
    # a gang over two racks; on a host of another flavor; with no
    # placement at all; with a pod too few placed
    for hosts in ({"spot-r0-h0": 2, "spot-r1-h0": 2}, {"reserved-r0-h0": 4},
                  {}, {"spot-r0-h0": 3}):
        assert audit(given(four, "spot", hosts)) == {
            "node_over": 0, "rack_split": 1, "flavor_over": 0}
    assert audit(given(four, "spot", {}, topology=None))["rack_split"] == 1
    # a fifth pod where the host has 4 cpu
    assert audit(sound, given(three, "spot", {"spot-r0-h0": 2,
                                              "spot-r0-h1": 1})) == {
        "node_over": 1, "rack_split": 0, "flavor_over": 0}
    # 7 cpu of 'reserved' where the queue's nominal quota is 6
    assert audit(given(four, "reserved", {"reserved-r0-h0": 4}),
                 given(three, "reserved", {"reserved-r1-h0": 3})) == {
        "node_over": 0, "rack_split": 0, "flavor_over": 1}
    # charged to no stated flavor; charged less than its pods
    assert audit(given(four, "gold", {"spot-r0-h0": 4}))["flavor_over"] > 0
    assert audit(given(four, "spot", {"spot-r0-h0": 4},
                       usage={"cpu": 1}))["flavor_over"] == 1


@pytest.mark.parametrize("gate", ["TASDeviceFillCounts", "NoSuchGate",
                                  "TopologyAwareScheduling"])
def test_a_gate_that_is_no_deployment_setting_is_refused(racks_tree, gate):
    from kueue_oss_tpu import features

    cfg = deployment.load_config("racks")
    assert driver.feature_gates(cfg) == {"TASBalancedPlacement": True}
    cfg["feature_gates"] = {"TASBalancedPlacement": True, gate: True}
    with pytest.raises(ValueError, match=f"{gate}.*nothing else"):
        driver.feature_gates(cfg)
    (racks_tree / "configs" / "racks.json").write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match=gate):
        run.main(["--workload", "racks-backlog", "--seconds", "1",
                  "--rehearse"])
    # refused before any gate was set
    assert not features.enabled("TASBalancedPlacement")


def test_every_gate_a_kind_may_state_is_the_programs():
    from kueue_oss_tpu import features

    assert driver.FEATURE_GATES <= set(features.all_gates())
    assert "TASDeviceFillCounts" not in driver.FEATURE_GATES


def test_a_kind_that_does_not_ask_is_given_nothing():
    """``flat`` states no gates and its records have no ``given``
    (``test_kinds.py`` holds it to ``data/flat_golden.json`` through the
    same code)."""
    cfg = deployment.scaled(deployment.load_config("upstream-baseline"),
                            1, 2, 50)
    assert driver.feature_gates(cfg) == {}
    arrivals = deployment.schedule(cfg, 1)
    replay = driver.Replay(cfg, arrivals, solver=None)
    rec = replay.one_pass(1.0, [("arrive", a.key, a.due_s)
                                for a in arrivals[:8]])
    assert rec["added"] and "given" not in rec
    driver.given_as_data(replay.passes)
    assert "given" not in replay.passes[0]
