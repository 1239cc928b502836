"""The benchmark's kind ``tas`` (benchmark/kinds/tas.py): its plain
reference on hand-made pass logs, and a rehearsal of ``tas-replay``.

The reference imports nothing of the program; here each of its counts
is made to read exactly what a hand-made ``given`` entry breaks. The
rehearsals run the cell through ``benchmark/run.py``'s ``main`` on the
CPU at a scaled size (``--rehearse``): on the host's path as the router
leaves it, and through device drains with the router's backlog
threshold lowered FOR THE TEST (a CPU holds no backlog of 256 gangs in
a few seconds; the measurement changes no router setting), each
control reading ``correct`` false with its count above 0.
"""

import json

import pytest

from benchmark import deployment, driver, run
from benchmark.kinds import tas

CFG = tas.scaled(deployment.load_config("upstream-tas"), 1, 6, 10)
ARRIVALS = tas.schedule(CFG, 1)


def test_schedule_is_upstreams_shape():
    cfg = deployment.load_config("upstream-tas")
    arrivals = tas.schedule(cfg, 7)
    assert len(arrivals) == 15_000 and len(tas.nodes(cfg)) == 640
    per_queue = {}
    for a in arrivals:
        if a.cq == "cq-0-0":
            per_queue[a.klass] = per_queue.get(a.klass, 0) + 1
    assert per_queue == {
        "small-required-rack": 117, "small-preferred-rack": 117,
        "small-balanced-rack": 116, "medium-required-rack": 34,
        "medium-preferred-rack": 33, "medium-balanced-rack": 33,
        "large-required-rack": 17, "large-preferred-rack": 17,
        "large-balanced-rack": 16}
    shapes = {(a.klass.split("-")[0], a.pods, a.cpu_per_pod)
              for a in arrivals}
    assert shapes == {("small", 2, 500), ("medium", 5, 2000),
                      ("large", 20, 5000)}
    assert tas.top_class(cfg) == "large-required-rack"
    assert tas.feature_gates(cfg) == {"TASBalancedPlacement": True}
    assert tas.schedule(cfg, 7) == arrivals != tas.schedule(cfg, 8)
    # the largest gang fits a queue's nominal + borrowingLimit
    assert max(a.request for a in arrivals) <= (
        cfg["nominal"] + cfg["borrowing_limit"])


def first(mode, size):
    return next(a for a in ARRIVALS
                if a.mode == mode and a.klass.startswith(size))


def entry(a, on_host, *, count=None, usage=None, flavor=tas.FLAVOR,
          topology=True):
    return {"key": a.key, "podsets": [{
        "name": "main", "count": a.pods if count is None else count,
        "flavors": {"cpu": flavor},
        "usage": {"cpu": a.request if usage is None else usage},
        "topology": {"levels": [tas.HOST], "domains": [
            [[h], n] for h, n in on_host.items()]} if topology else None}]}


def one_pass(given, *, arrive=(), added=None, removed=(), finish=()):
    return {"events": [("arrive", a.key, a.due_s) for a in arrive]
            + [("finish", k, 0.0) for k in finish],
            "added": sorted(e["key"] for e in given)
            if added is None else added,
            "removed": list(removed), "given": given}


def counts(*passes):
    out = tas.audit(CFG, ARRIVALS, [], list(passes))
    return {k: v for k, v in out["counts"].items() if v}


LARGE_REQ = first("required", "large")
LARGE_PREF = first("preferred", "large")
LARGE_BAL = first("balanced", "large")
MEDIUM = first("required", "medium")
SMALL = first("preferred", "small")
H = "b0-r0-h0", "b0-r0-h1", "b0-r1-h0"


@pytest.mark.parametrize("name, passes, want", [
    ("sound", [one_pass([entry(LARGE_REQ, {H[0]: 19, H[1]: 1})],
                        arrive=[LARGE_REQ])], {}),
    ("rack_split", [one_pass([entry(LARGE_REQ, {H[0]: 19, H[2]: 1})],
                             arrive=[LARGE_REQ])], {"rack_split": 1}),
    ("rack_spread_preferred",
     [one_pass([entry(LARGE_PREF, {H[0]: 10, H[2]: 10})],
               arrive=[LARGE_PREF])], {"rack_spread": 1}),
    ("rack_spread_balanced",
     [one_pass([entry(LARGE_BAL, {H[0]: 10, H[2]: 10})],
               arrive=[LARGE_BAL])], {"rack_spread": 1}),
    ("one_rack_balanced",
     [one_pass([entry(LARGE_BAL, {H[0]: 10, H[1]: 10})],
               arrive=[LARGE_BAL])], {}),
    ("domains_do_not_add_up",
     [one_pass([entry(MEDIUM, {H[0]: 4})], arrive=[MEDIUM])],
     {"pods_mismatch": 1}),
    ("no_topology",
     [one_pass([entry(MEDIUM, {}, topology=False)], arrive=[MEDIUM])],
     {"pods_mismatch": 1}),
    ("unknown_host",
     [one_pass([entry(MEDIUM, {"elsewhere": 5})], arrive=[MEDIUM])],
     {"pods_mismatch": 1}),
    ("usage_is_pods_x_cpu",
     [one_pass([entry(MEDIUM, {H[0]: 5}, usage=2000)], arrive=[MEDIUM])],
     {"pods_mismatch": 1}),
    ("another_flavor",
     [one_pass([entry(MEDIUM, {H[0]: 5}, flavor="default")],
               arrive=[MEDIUM])], {"pods_mismatch": 1}),
    ("reserved_given_nothing",
     [one_pass([], arrive=[MEDIUM], added=[MEDIUM.key])],
     {"pods_mismatch": 1}),
    ("ghost", [one_pass([entry(MEDIUM, {H[0]: 5})])], {"ghosts": 1}),
])
def test_reference_counts_what_a_given_entry_breaks(name, passes, want):
    assert counts(*passes) == want, name


def test_reference_keeps_node_books_from_given():
    """96 cpu a host: five large gangs' 19 + 1 pods of 5 cpu on one host
    pair overfill it; a finish frees the nodes before the pass places."""
    larges = [a for a in ARRIVALS if a.klass.startswith("large")][:2]
    a, b = larges

    def run_log(*passes):
        out = tas.audit(CFG, larges, [], list(passes))
        return {k: v for k, v in out["counts"].items() if v}

    both = [one_pass([entry(a, {H[0]: 19, H[1]: 1})], arrive=[a, b]),
            one_pass([entry(b, {H[0]: 19, H[1]: 1})])]
    got = run_log(*both)
    assert got.pop("node_over") == 1
    # two larges of 100 cpu in one cohort of 120: the quota books too
    assert got.pop("over_quota") >= 1 and not got
    moved = [one_pass([entry(a, {H[0]: 19, H[1]: 1})], arrive=[a, b]),
             one_pass([entry(b, {H[0]: 19, H[1]: 1})],
                      removed=[a.key], finish=[a.key])]
    assert run_log(*moved) == {}


def test_reference_counts_a_gang_that_waits_with_room():
    """``starved`` asks for quota AND nodes: a small gang waits while
    its queue is empty; with every host full it is not starved."""
    assert counts(one_pass([], arrive=[SMALL], added=[])) == {
        "starved": 1, "below_nominal": 1}
    books = tas.Books(CFG, ARRIVALS)
    for host in books.rack_of:
        books._charge({host: 96}, 1000, +1)
    books.arrive(SMALL.key)
    books.apply_pass(one_pass([], added=[]))
    assert not any(books.counts.values())
    # a required gang needs ONE rack to hold it
    books = tas.Books(CFG, ARRIVALS)
    hosts = sorted(books.rack_of)
    for host in hosts:
        books._charge({host: 18}, 5000, +1)    # 1 pod of 5 cpu a host left
    assert books.topology_holds(8, 5000, "preferred")
    assert books.topology_holds(8, 5000, "required")
    assert not books.topology_holds(9, 5000, "required")
    assert books.topology_holds(9, 5000, "balanced")


def in_queue(cq, size):
    return [a for a in ARRIVALS
            if a.cq == cq and a.klass.startswith(size)]


def test_inversions_are_flats_but_for_a_seat_got_by_a_reclaim():
    """``flat``'s rule holds as it stands; the one seat exempted is one
    inside the queue's nominal quota in a pass that reclaimed from a
    borrower of another queue, against a waiting workload that would
    have to borrow (it could not have made that room)."""
    qa, qb = sorted({a.cq for a in ARRIVALS})[:2]
    large, medium, small = (in_queue(qa, s)[0]
                            for s in ("large", "medium", "small"))
    medium2 = in_queue(qa, "medium")[1]
    b_large, b_medium, b_small = (in_queue(qb, s)[0]
                                  for s in ("large", "medium", "small"))

    def seat(a, host):
        return entry(a, {host: a.pods})

    # no reclaim anywhere: the medium is seated while its queue's large
    # waits with room for it (it is starved too)
    assert counts(one_pass([seat(medium, H[0])],
                           arrive=[large, medium])) == {
        "inversions": 1, "starved": 1}
    # the medium's seat was reclaimed from the other queue's borrowing
    # large; what is left free (99 of 120 cpu) only reads as room for
    # the large: exempt, and counted by flat's rule unexempted
    before = one_pass([entry(b_large, {H[0]: 19, H[1]: 1}),
                       seat(b_medium, H[1]), seat(b_small, H[1])],
                      arrive=[b_large, b_medium, b_small])
    reclaim = one_pass([seat(medium, H[2])], arrive=[large, medium],
                       removed=[b_large.key])
    out = tas.audit(CFG, ARRIVALS, [], [before, reclaim])
    assert not any(out["counts"].values()), out
    assert out["first"] == {"inversions_unexempted": 1}
    # the same reclaim does not cover a seat against a waiter that fits
    # the nominal quota in its place: that one could have reclaimed
    reclaim = one_pass([seat(small, H[2])], arrive=[medium2, small],
                       removed=[b_large.key])
    assert counts(before, reclaim)["inversions"] == 1


def test_a_program_that_cannot_place_the_deployment_is_refused(monkeypatch):
    """The kind builds no store for a program whose device drains do
    not cover one of the deployment's request types (the parent of
    PR 32 under this benchmark): the run ends at once, not in host
    cycles."""
    from kueue_oss_tpu.solver import tas_engine

    assert len(tas.build_store(CFG).nodes) == len(tas.nodes(CFG))
    monkeypatch.setattr(
        tas_engine, "device_tas_supported",
        lambda info, store, spec:
            info.obj.podsets[0].topology_request.required is not None)
    with pytest.raises(SystemExit, match="preferred-rack"):
        tas.build_store(CFG)


def test_controls_deploy_what_breaks_a_guarantee():
    cfg = deployment.load_config("upstream-tas")
    assert set(tas.controls) >= {"double_nominal", "double_nodes"}
    assert tas.controls["double_nominal"][0](cfg)["nominal"] == 40_000
    assert tas.controls["double_nodes"][0](cfg)["topology"][
        "node_cpu"] == 192_000
    shuffled = tas.controls["shuffled_racks"][0](cfg)
    assert tas.nodes(shuffled) == tas.nodes(cfg)   # the reference's view


# ---------------------------------------------------------------------------
# rehearsals of the cell
# ---------------------------------------------------------------------------


def rehearse(capsys, *extra):
    assert run.main(["--workload", "tas-replay", "--trace", "0",
                     "--rehearse", "--cohorts", "1", "--cqs-per-cohort",
                     "6", "--count-div", "10", *extra]) == 0
    cap = capsys.readouterr()
    info = next(json.loads(line)["info"] for line in cap.err.splitlines()
                if line.startswith('{"info"'))
    return json.loads(cap.out.strip().splitlines()[-1]), info


@pytest.fixture
def cell(monkeypatch):
    """The cell's rehearsal, short: the twin's warm-up cut to 2 s and no
    other candidate caps traced (minutes on a CPU, nothing to a check of
    ``correct``)."""
    from kueue_oss_tpu import features

    real = deployment.load_traffic

    def short(name):
        traffic = dict(real(name))
        traffic["warmup"] = {**traffic["warmup"], "max_seconds": 2,
                             "p_max": []}
        return traffic

    monkeypatch.setattr(deployment, "load_traffic", short)
    yield
    features.reset()


@pytest.fixture
def device_path(monkeypatch, cell):
    """Drains at a size a CPU holds: the router's backlog threshold at
    16 for the test's length."""
    init = driver.Replay.__init__

    def lowered(self, *a, **kw):
        init(self, *a, **kw)
        self.sched.solver_min_backlog = 16

    monkeypatch.setattr(driver.Replay, "__init__", lowered)


def test_rehearsal_on_the_hosts_path(cell, capsys):
    r, info = rehearse(capsys, "--seed", "3", "--seconds", "3")
    assert r["correct"], r["compared"]
    assert info["counters"]["drains"] == 0
    assert info["counters"]["reservations"] > 20
    assert info["feature_gates"] == {"TASBalancedPlacement": True}
    assert set(r["metrics"]) == {"adm_per_s", "setup_s"}
    for mode in ("required", "preferred", "balanced"):
        assert f"wait_mean_s.small-{mode}-rack" in info["window"]


def test_rehearsal_through_device_drains(device_path, capsys):
    from kueue_oss_tpu.obs import spans

    c0 = dict(spans.counters())
    r, info = rehearse(capsys, "--seed", "4", "--seconds", "4")
    c1 = spans.counters()
    assert r["correct"], r["compared"]
    assert info["counters"]["drains"] > 0
    # how many a drain seats hangs on the clock (the deterministic
    # count is test_a_backlog_of_the_cell_drains_on_the_device's)
    assert c1.get("tas_device_placements", 0) >= c0.get(
        "tas_device_placements", 0)
    assert c1.get("tas_place_failed", 0) == c0.get("tas_place_failed", 0)
    assert r["compared"]["solver_plan_fallbacks_total"]["value"] == 0


def test_a_backlog_of_the_cell_drains_on_the_device(device_path):
    """The first 400 gangs of a scaled cell as one backlog, one pass, no clock: the
    router drains, the device placer places required and preferred requests,
    and the reference finds nothing in what they were given."""
    from kueue_oss_tpu import features
    from kueue_oss_tpu.obs import spans

    features.set_gates(driver.feature_gates(CFG))
    arrivals = ARRIVALS[:400]
    replay = driver.Replay(CFG, arrivals, solver="auto")
    c0 = dict(spans.counters())
    rec = replay.one_pass(1.0, [("arrive", a.key, a.due_s)
                                for a in arrivals])
    c1 = spans.counters()
    assert rec["drains"] == 1
    placed = c1["tas_device_placements"] - c0.get("tas_device_placements", 0)
    assert placed >= 1
    assert c1.get("tas_place_failed", 0) == c0.get("tas_place_failed", 0)
    driver.given_as_data(replay.passes)
    by_key = {a.key: a for a in arrivals}
    modes = {by_key[g["key"]].mode for g in rec["given"]}
    # (a balanced-rack gang sends what a preferred-rack one sends)
    assert {"required", "preferred"} <= modes
    assert all(g["podsets"][0]["topology"]["domains"] for g in rec["given"])
    out = tas.audit(CFG, arrivals, [], replay.passes)
    assert not any(out["counts"].values()), out["first"]
    assert out["holding"] == len(rec["added"]) >= placed


@pytest.mark.parametrize("control, seconds", [("double_nominal", "3"),
                                              ("double_nodes", "6")])
def test_rehearsal_of_a_control_is_not_correct(device_path, capsys,
                                               control, seconds):
    r, info = rehearse(capsys, "--seed", "5", "--seconds", seconds,
                       "--control", control)
    count = tas.controls[control][1]
    assert info["control"] == {"name": control, "has_to_count": count}
    assert not r["correct"]
    assert r["compared"][count]["value"] > 0
