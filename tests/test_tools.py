"""Visibility API, debugger dump, kueuectl CLI, and importer tests.

Scenario shapes mirror pkg/visibility tests, pkg/debugger, the kueuectl
command tests (cmd/kueuectl), and cmd/importer's check/import phases.
"""

import json
import urllib.request

import pytest

from kueue_oss_tpu.api.types import (
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_oss_tpu.cli import CliError, Kueuectl
from kueue_oss_tpu.core.queue_manager import QueueManager
from kueue_oss_tpu.core.store import Store
from kueue_oss_tpu.debugger import Dumper
from kueue_oss_tpu.importer import QUEUE_LABEL, ExistingPod, Importer
from kueue_oss_tpu.scheduler.scheduler import Scheduler
from kueue_oss_tpu.visibility import VisibilityServer, VisibilityService


def make_env(nominal=2000):
    store = Store()
    store.upsert_resource_flavor(ResourceFlavor(name="default"))
    store.upsert_cluster_queue(ClusterQueue(
        name="cq", resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name="default", resources=[
                ResourceQuota(name="cpu", nominal=nominal)])])]))
    for lq in ("lq-a", "lq-b"):
        store.upsert_local_queue(LocalQueue(name=lq, cluster_queue="cq"))
    queues = QueueManager(store)
    sched = Scheduler(store, queues)
    return store, queues, sched


def submit(store, name, lq, cpu=1000, priority=0, t=0.0):
    store.add_workload(Workload(
        name=name, queue_name=lq, priority=priority, creation_time=t,
        podsets=[PodSet(count=1, requests={"cpu": cpu})]))


# -- visibility --------------------------------------------------------------


def test_pending_workloads_positions():
    store, queues, sched = make_env(nominal=1000)
    submit(store, "w1", "lq-a", t=1.0)
    submit(store, "w2", "lq-a", t=2.0)
    submit(store, "w3", "lq-b", t=3.0, priority=5)  # admitted (priority)
    submit(store, "w4", "lq-a", t=4.0)
    sched.schedule(5.0)
    svc = VisibilityService(queues)
    summary = svc.pending_workloads_in_cq("cq")
    names = [i.name for i in summary.items]
    assert names == ["w1", "w2", "w4"], "FIFO among equal priorities"
    w4 = next(i for i in summary.items if i.name == "w4")
    assert w4.local_queue_name == "lq-a"
    assert w4.position_in_local_queue == 2
    assert w4.position_in_cluster_queue == 2

    lq_summary = svc.pending_workloads_in_lq("default", "lq-a")
    assert [i.name for i in lq_summary.items] == ["w1", "w2", "w4"]


def test_visibility_http_server():
    store, queues, sched = make_env(nominal=0)
    submit(store, "w1", "lq-a")
    sched.schedule(1.0)
    srv = VisibilityServer(VisibilityService(queues))
    srv.start()
    try:
        url = (f"http://127.0.0.1:{srv.port}/apis/visibility/v1beta2/"
               f"clusterqueues/cq/pendingworkloads")
        data = json.loads(urllib.request.urlopen(url, timeout=5).read())
        assert [i["name"] for i in data["items"]] == ["w1"]
        url2 = (f"http://127.0.0.1:{srv.port}/apis/visibility/v1beta2/"
                f"namespaces/default/localqueues/lq-a/pendingworkloads")
        data2 = json.loads(urllib.request.urlopen(url2, timeout=5).read())
        assert len(data2["items"]) == 1
    finally:
        srv.stop()


# -- debugger ----------------------------------------------------------------


def test_dumper_snapshot():
    store, queues, sched = make_env(nominal=1000)
    submit(store, "running", "lq-a", t=1.0)
    submit(store, "waiting", "lq-b", t=2.0)
    sched.schedule(3.0)
    d = Dumper(store, queues).dump()
    assert d["cluster_queues"] == ["cq"]
    assert [w["workload"] for w in d["admitted_workloads"]["cq"]] == [
        "default/running"]
    pend = d["pending_workloads"]["cq"]
    assert pend["active"] == ["default/waiting"] or \
        pend["inadmissible"] == ["default/waiting"]
    text = Dumper(store, queues).dump_text(out=open("/dev/null", "w"))
    assert "ClusterQueue cq" in text


# -- kueuectl ----------------------------------------------------------------


def test_cli_create_list_stop_resume_delete():
    store = Store()
    store.upsert_resource_flavor(ResourceFlavor(name="default"))
    ctl = Kueuectl(store)
    out = ctl.run(["create", "clusterqueue", "team-a",
                   "--nominal-quota", "default:cpu=4000"])
    assert "created" in out
    assert store.cluster_queues["team-a"].quota_for(
        ("default", "cpu")).nominal == 4000
    ctl.run(["create", "localqueue", "lq", "-c", "team-a"])
    assert "default/lq" in store.local_queues

    submit(store, "w1", "lq")
    listing = ctl.run(["list", "workload"])
    assert "w1" in listing and "Pending" in listing
    listing = ctl.run(["list", "clusterqueue"])
    assert "team-a" in listing

    assert "stopped" in ctl.run(["stop", "clusterqueue", "team-a"])
    assert store.cluster_queues["team-a"].stop_policy == "HoldAndDrain"
    assert "resumed" in ctl.run(["resume", "clusterqueue", "team-a"])
    assert store.cluster_queues["team-a"].stop_policy == "None"

    assert "stopped" in ctl.run(["stop", "workload", "w1"])
    assert not store.workloads["default/w1"].active
    assert "resumed" in ctl.run(["resume", "workload", "w1"])

    assert "deleted" in ctl.run(["delete", "workload", "w1"])
    assert "deleted" in ctl.run(["delete", "localqueue", "lq"])
    assert "deleted" in ctl.run(["delete", "clusterqueue", "team-a"])
    assert store.cluster_queues == {}


def test_cli_errors():
    store = Store()
    ctl = Kueuectl(store)
    with pytest.raises(CliError):
        ctl.run(["create", "localqueue", "lq", "-c", "missing"])
    with pytest.raises(CliError):
        ctl.run(["delete", "clusterqueue", "nope"])
    with pytest.raises(CliError):
        ctl.run(["create", "clusterqueue", "Bad_Name"])
    assert "version" in ctl.run(["version"])


def test_cli_stop_keep_already_running_maps_to_hold():
    store = Store()
    store.upsert_cluster_queue(ClusterQueue(name="cq"))
    ctl = Kueuectl(store)
    ctl.run(["stop", "clusterqueue", "cq", "--keep-already-running"])
    assert store.cluster_queues["cq"].stop_policy == "Hold"


# -- importer ----------------------------------------------------------------


def test_importer_check_and_import():
    store, queues, sched = make_env(nominal=4000)
    pods = [
        ExistingPod(name="p1", labels={QUEUE_LABEL: "lq-a"},
                    requests={"cpu": 1000}),
        ExistingPod(name="p2", labels={QUEUE_LABEL: "lq-b"},
                    requests={"cpu": 500}, priority=3),
    ]
    imp = Importer(store)
    res = imp.run(pods, now=1.0)
    assert res.imported == 2 and not res.errors
    wl = store.workloads["default/pod-p1"]
    assert wl.is_admitted
    assert wl.status.admission.cluster_queue == "cq"
    # imported usage is charged: only 2500 of 4000 left
    submit(store, "newcomer", "lq-a", cpu=3000)
    sched.schedule(2.0)
    assert not store.workloads["default/newcomer"].is_quota_reserved


def test_importer_rejects_unmapped_pods():
    store, *_ = make_env()
    imp = Importer(store)
    res = imp.run([
        ExistingPod(name="ok", labels={QUEUE_LABEL: "lq-a"},
                    requests={"cpu": 100}),
        ExistingPod(name="orphan", labels={}, requests={"cpu": 100}),
        ExistingPod(name="badq", labels={QUEUE_LABEL: "ghost"},
                    requests={"cpu": 100}),
        ExistingPod(name="badres", labels={QUEUE_LABEL: "lq-a"},
                    requests={"tpu": 4}),
    ])
    assert res.imported == 0, "check phase failures abort the import"
    assert len(res.errors) == 3


# -- populator + kueueviz dashboard ------------------------------------------


def test_populator_creates_matching_local_queues():
    from kueue_oss_tpu.populator import Populator

    store = Store()
    store.namespaces["team-a"] = {"team": "a"}
    store.namespaces["team-b"] = {"team": "b"}
    store.upsert_cluster_queue(ClusterQueue(
        name="cq-a", namespace_selector={"team": "a"}))
    pop = Populator(store)
    res = pop.reconcile()
    assert res.created == ["team-a/default"]
    assert store.local_queues["team-a/default"].cluster_queue == "cq-a"
    # idempotent
    res2 = pop.reconcile()
    assert res2.created == [] and res2.skipped == ["team-a/default"]
    # no selector -> no auto-creation
    store.upsert_cluster_queue(ClusterQueue(name="cq-all"))
    assert pop.reconcile().created == []


def test_dashboard_views_and_server():
    from kueue_oss_tpu.viz import Dashboard, DashboardServer

    store, queues, sched = make_env(nominal=1000)
    submit(store, "running", "lq-a", t=1.0)
    submit(store, "waiting", "lq-b", t=2.0)
    sched.schedule(3.0)
    dash = Dashboard(store, queues)
    cqs = dash.cluster_queues_view()
    assert cqs[0]["name"] == "cq"
    assert cqs[0]["admitted"] == 1
    assert cqs[0]["pending"] + cqs[0]["inadmissible"] == 1
    assert cqs[0]["usage"] == {"default/cpu": 1000}
    wls = dash.workloads_view()
    statuses = {w["name"]: w["status"] for w in wls}
    assert statuses == {"running": "Admitted", "waiting": "Pending"}

    srv = DashboardServer(dash)
    srv.start()
    try:
        data = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/api/overview", timeout=5).read())
        assert data["clusterQueues"][0]["name"] == "cq"
        assert len(data["workloads"]) == 2
        # the static HTML frontend serves at /
        html = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/", timeout=5).read().decode()
        assert "<title>kueue-oss-tpu dashboard</title>" in html
        assert "/api/overview" in html
        # cohort tree + usage-bar rendering (kueueviz frontend analog)
        assert "renderTree" in html and "usageBar" in html
    finally:
        srv.stop()


def test_cli_create_resourceflavor_get_dryrun_completion():
    from kueue_oss_tpu.api.types import Topology

    store = Store()
    ctl = Kueuectl(store)
    out = ctl.run(["create", "resourceflavor", "tpu",
                   "--node-labels", "pool=tpu,zone=a",
                   "--node-taints", "dedicated=ml:NoSchedule"])
    assert "created" in out
    rf = store.resource_flavors["tpu"]
    assert rf.node_labels == {"pool": "tpu", "zone": "a"}
    assert rf.node_taints[0].effect == "NoSchedule"

    # the tainted flavor rejects untolerated workloads, so the schedulable
    # queue uses a second, untainted flavor
    ctl.run(["create", "resourceflavor", "plain"])
    ctl.run(["create", "clusterqueue", "cq",
             "--nominal-quota", "plain:cpu=4000"])
    ctl.run(["create", "localqueue", "lq", "-c", "cq"])

    # passthrough get over kinds without dedicated commands
    store.upsert_topology(Topology(name="dc", levels=["rack", "host"]))
    assert "dc" in ctl.run(["get", "topology"])
    assert "levels" in ctl.run(["get", "topology", "dc"])

    # dryrun simulates on a clone: reports would-be admissions, commits
    # nothing
    submit(store, "w1", "lq")
    out = ctl.run(["dryrun"])
    assert "1 workload(s) would be admitted" in out
    assert "default/w1" in out and "cq" in out
    assert not store.workloads["default/w1"].is_quota_reserved

    assert "complete -F _kueuectl_completions" in ctl.run(["completion"])


def test_store_clone_is_independent():
    store = Store()
    store.upsert_resource_flavor(ResourceFlavor(name="default"))
    ctl = Kueuectl(store)
    ctl.run(["create", "clusterqueue", "cq",
             "--nominal-quota", "default:cpu=4000"])
    ctl.run(["create", "localqueue", "lq", "-c", "cq"])
    submit(store, "w1", "lq")
    clone = store.clone()
    clone.workloads["default/w1"].priority = 99
    assert store.workloads["default/w1"].priority != 99
    clone.delete_workload("default/w1")
    assert "default/w1" in store.workloads


def test_dryrun_clears_eviction_backoff():
    """A live eviction backoff must not gate the simulation
    (kueuectl dryrun asks 'could it admit')."""
    from kueue_oss_tpu.api.types import RequeueState

    store = Store()
    store.upsert_resource_flavor(ResourceFlavor(name="default"))
    ctl = Kueuectl(store)
    ctl.run(["create", "clusterqueue", "cq",
             "--nominal-quota", "default:cpu=4000"])
    ctl.run(["create", "localqueue", "lq", "-c", "cq"])
    submit(store, "w1", "lq")
    store.workloads["default/w1"].status.requeue_state = RequeueState(
        count=3, requeue_at=10_000.0)
    out = ctl.run(["dryrun"])
    assert "1 workload(s) would be admitted" in out, out


def test_cli_selectors_json_and_topology_views():
    from kueue_oss_tpu.api.types import Node, Topology, Workload, PodSet

    store, queues, sched = make_env(nominal=1000)
    store.upsert_topology(Topology(
        name="dc", levels=["cloud/rack", "kubernetes.io/hostname"]))
    for r in range(2):
        for h in range(2):
            store.upsert_node(Node(
                name=f"n-{r}-{h}", labels={"cloud/rack": f"r{r}"},
                allocatable={"cpu": 4000}))
    store.add_workload(Workload(
        name="labeled", queue_name="lq-a", labels={"team": "ml"},
        podsets=[PodSet(count=1, requests={"cpu": 100})]))
    store.add_workload(Workload(
        name="other", queue_name="lq-a", labels={"team": "web"},
        podsets=[PodSet(count=1, requests={"cpu": 100})]))
    ctl = Kueuectl(store, queues=queues)

    out = ctl.run(["list", "workload", "-l", "team=ml"])
    assert "labeled" in out and "other" not in out
    out = ctl.run(["list", "workload", "-l", "team!=ml"])
    assert "other" in out and "labeled" not in out

    data = json.loads(ctl.run(["list", "workload", "-o", "json"]))
    assert {w["name"] for w in data} >= {"labeled", "other"}
    data = json.loads(ctl.run(["list", "localqueue", "-o", "json"]))
    assert all("clusterqueue" in row for row in data)

    out = ctl.run(["list", "topology"])
    assert "dc" in out and "2/4" in out
    out = ctl.run(["describe", "topology", "dc"])
    assert "Level 0 (cloud/rack): 2 domains" in out
    assert "cpu=16000" in out


def test_cli_round5_option_breadth():
    """-o yaml|wide, -A, --field-selector, create flag matrix,
    delete --all (cmd/kueuectl list/create/delete flag parity)."""
    import yaml as _yaml

    store = Store()
    store.upsert_resource_flavor(ResourceFlavor(name="default"))
    ctl = Kueuectl(store)
    out = ctl.run([
        "create", "clusterqueue", "team-a",
        "--nominal-quota", "default:cpu=4000",
        "--borrowing-limit", "default:cpu=1000",
        "--lending-limit", "default:cpu=500",
        "--queuing-strategy", "StrictFIFO",
        "--reclaim-within-cohort", "Any",
        "--preemption-within-cluster-queue", "LowerPriority",
        "--namespace-selector", "team=a"])
    assert "created" in out
    cq = store.cluster_queues["team-a"]
    assert cq.queueing_strategy == "StrictFIFO"
    assert cq.preemption.reclaim_within_cohort == "Any"
    assert cq.preemption.within_cluster_queue == "LowerPriority"
    assert cq.namespace_selector == {"team": "a"}
    q = cq.quota_for(("default", "cpu"))
    assert (q.nominal, q.borrowing_limit, q.lending_limit) == (
        4000, 1000, 500)

    ctl.run(["create", "localqueue", "lq", "-c", "team-a"])
    ctl.run(["create", "localqueue", "lq2", "-c", "team-a",
             "-n", "other"])
    submit(store, "w1", "lq")
    store.add_workload(Workload(
        name="w2", namespace="other", queue_name="lq2",
        podsets=[PodSet(count=1, requests={"cpu": 1000})]))

    # -A spans namespaces; -n restricts
    both = ctl.run(["list", "workload", "-A"])
    assert "w1" in both and "w2" in both
    one = ctl.run(["list", "workload", "-n", "other"])
    assert "w2" in one and "w1" not in one

    # field selector on rendered fields
    sel = ctl.run(["list", "workload", "-A",
                   "--field-selector", "spec.queueName=lq2"])
    assert "w2" in sel and "w1" not in sel
    sel = ctl.run(["list", "workload", "-A",
                   "--field-selector", "status.phase!=Pending"])
    assert "w1" not in sel and "w2" not in sel

    # -o yaml round-trips; -o wide appends columns
    docs = _yaml.safe_load(ctl.run(["list", "workload", "-A",
                                    "-o", "yaml"]))
    assert {d["name"] for d in docs} == {"w1", "w2"}
    wide = ctl.run(["list", "clusterqueue", "-o", "wide"])
    assert "FLAVORS" in wide and "default" in wide and "Any" in wide
    wide_wl = ctl.run(["list", "workload", "-A", "-o", "wide"])
    assert "ADMITTED BY" in wide_wl and "UID" in wide_wl
    lqs = ctl.run(["list", "localqueue", "-A"])
    assert "lq2" in lqs

    # delete --all in one namespace only
    out = ctl.run(["delete", "workload", "--all", "-n", "default"])
    assert "w1 deleted" in out
    assert "default/w1" not in store.workloads
    assert "other/w2" in store.workloads


def test_dashboard_detail_views_and_sse():
    """Per-resource detail endpoints + SSE live stream (kueueviz
    WorkloadDetail.jsx / useWebSocket.js analogs)."""
    import http.client
    import time as _time

    from kueue_oss_tpu.viz import Dashboard, DashboardServer

    store, queues, sched = make_env(nominal=1000)
    submit(store, "running", "lq-a", t=1.0)
    submit(store, "waiting", "lq-b", t=2.0)
    sched.schedule(3.0)
    dash = Dashboard(store, queues)

    wd = dash.workload_detail("default", "running")
    assert wd["status"] == "Admitted"
    assert wd["admission"]["clusterQueue"] == "cq"
    assert wd["podSets"][0]["requests"] == {"cpu": 1000}
    assert wd["conditions"], "conditions must be present"
    assert dash.workload_detail("default", "nope") is None

    cqd = dash.cluster_queue_detail("cq")
    assert {w["name"] for w in cqd["admittedWorkloads"]} == {"running"}
    assert any(p["name"] == "waiting" for p in cqd["pendingWorkloads"])
    assert cqd["preemption"]["withinClusterQueue"] in (
        "Never", "LowerPriority", "LowerOrNewerEqualPriority", "Any")

    srv = DashboardServer(dash)
    srv.start()
    try:
        wd2 = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/api/workloads/default/running",
            timeout=5).read())
        assert wd2["admission"]["clusterQueue"] == "cq"
        cqd2 = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/api/clusterqueues/cq",
            timeout=5).read())
        assert cqd2["name"] == "cq"
        # missing resources 404 (urllib.error is loaded by
        # urllib.request at import time)
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/api/clusterqueues/nope",
                timeout=5)
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404

        # SSE: a store change pushes a data event
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
        conn.request("GET", "/api/stream")
        resp = conn.getresponse()
        assert resp.headers["Content-Type"] == "text/event-stream"
        submit(store, "late", "lq-a", t=4.0)  # triggers a store event
        deadline = _time.monotonic() + 10
        saw_data = False
        while _time.monotonic() < deadline:
            line = resp.fp.readline().decode()
            if line.startswith("data:"):
                payload = json.loads(line[5:])
                names = {w["name"] for w in payload["workloads"]}
                if "late" in names:
                    saw_data = True
                    break
        assert saw_data, "SSE stream never delivered the store change"
        conn.close()
    finally:
        srv.stop()


def test_cli_round5_option_breadth():
    """--status / --active / -c filters, describe localqueue and
    resourceflavor, -i ignore-unknown-cq (round-5 verb options)."""
    store, queues, sched = make_env()
    ctl = Kueuectl(store, queues=queues)
    for i, lq in enumerate(("lq-a", "lq-b")):
        store.add_workload(Workload(
            name=f"w{i}", queue_name=lq,
            podsets=[PodSet(name="main", count=1, requests={"cpu": 100})]))
    sched.run_until_quiet(now=0.0)
    # everything fits: admitted filter sees both, pending sees none
    admitted = ctl.run(["list", "workload", "-A", "--status", "admitted"])
    assert "w0" in admitted and "w1" in admitted
    pending = ctl.run(["list", "workload", "-A", "--status", "pending"])
    assert "w0" not in pending and "w1" not in pending
    both = ctl.run(["list", "workload", "-A", "--status", "pending",
                    "--status", "admitted"])
    assert "w0" in both

    # localqueue filter by cluster queue
    out = ctl.run(["list", "localqueue", "-A", "-c", "cq"])
    assert "lq-a" in out
    assert "lq-a" not in ctl.run(["list", "localqueue", "-A", "-c", "no"])

    # active filter: a stopped CQ is inactive
    ctl.run(["stop", "clusterqueue", "cq"])
    assert "cq" not in ctl.run(["list", "clusterqueue", "--active", "true"])
    assert "cq" in ctl.run(["list", "clusterqueue", "--active", "false"])
    ctl.run(["resume", "clusterqueue", "cq"])

    # describe localqueue / resourceflavor
    desc = ctl.run(["describe", "localqueue", "lq-a"])
    assert "ClusterQueue: cq" in desc and "Admitted Workloads: 1" in desc
    rf = ctl.run(["describe", "resourceflavor", "default"])
    assert "Used By ClusterQueues: cq" in rf

    # ignore-unknown-cq creates a dangling LocalQueue without error
    out = ctl.run(["create", "localqueue", "lq-x", "-c", "ghost", "-i"])
    assert "created" in out
    with pytest.raises(CliError):
        ctl.run(["create", "localqueue", "lq-y", "-c", "ghost"])

    # resourceflavor list output modes include wide
    wide = ctl.run(["list", "resourceflavor", "-o", "wide"])
    assert "TAINTS" in wide


def test_viz_round5_resource_views():
    """LocalQueue / ResourceFlavor / Topology / AdmissionCheck API views
    (kueueviz per-resource pages analog)."""
    import urllib.request

    from kueue_oss_tpu.api.types import AdmissionCheck, Node, Topology
    from kueue_oss_tpu.viz import Dashboard, DashboardServer

    store, queues, sched = make_env()
    store.upsert_topology(Topology(name="tp", levels=["rack", "host"]))
    store.upsert_node(Node(name="n1", labels={"rack": "r1", "host": "n1"},
                           allocatable={"cpu": 8}))
    store.upsert_admission_check(AdmissionCheck(
        name="prov", controller_name="kueue.x-k8s.io/provisioning-request"))
    store.add_workload(Workload(
        name="w", queue_name="lq-a",
        podsets=[PodSet(name="main", count=1, requests={"cpu": 1})]))
    sched.run_until_quiet(now=0.0)
    dash = Dashboard(store, queues)
    lqs = {q["name"]: q for q in dash.local_queues_view()}
    assert lqs["lq-a"]["admitted"] == 1
    assert lqs["lq-a"]["clusterQueue"] == "cq"
    rfs = dash.resource_flavors_view()
    assert rfs[0]["name"] == "default" and rfs[0]["usedBy"] == ["cq"]
    tps = dash.topologies_view()
    assert tps[0]["levels"] == ["rack", "host"]
    assert tps[0]["domainsPerLevel"] == [1, 1]
    acs = dash.admission_checks_view()
    assert acs[0]["name"] == "prov" and acs[0]["active"]

    srv = DashboardServer(dash, port=0)
    srv.start()
    try:
        for path in ("localqueues", "resourceflavors", "topologies",
                     "admissionchecks"):
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/api/{path}").read()
            assert body.startswith(b"[")
        overview = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/api/overview").read())
        assert "resourceFlavors" in overview
        html = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/").read().decode()
        assert "AdmissionChecks" in html and "Topologies" in html
    finally:
        srv.stop()
