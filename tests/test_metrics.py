"""Metrics registry + scheduler wiring tests.

Reference parity: pkg/metrics/metrics_test.go (series semantics) and the
perf runner's metric scraping of admitted/evicted counters.
"""

import pytest

from kueue_oss_tpu import metrics
from kueue_oss_tpu.api.types import (
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
    iter_quotas,
)
from kueue_oss_tpu.core.queue_manager import QueueManager
from kueue_oss_tpu.core.store import Store
from kueue_oss_tpu.scheduler.scheduler import Scheduler


@pytest.fixture(autouse=True)
def _reset_metrics():
    metrics.reset_all()
    yield
    metrics.reset_all()


def _mk_env(nominal=4000):
    store = Store()
    store.upsert_resource_flavor(ResourceFlavor(name="default"))
    store.upsert_cluster_queue(ClusterQueue(
        name="cq", resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name="default", resources=[
                ResourceQuota(name="cpu", nominal=nominal)])])]))
    store.upsert_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    queues = QueueManager(store)
    sched = Scheduler(store, queues)
    return store, queues, sched


def test_counter_gauge_histogram_basics():
    c = metrics.Counter("t_total", "t", ("a",))
    c.inc("x")
    c.inc("x", by=2)
    assert c.value("x") == 3
    g = metrics.Gauge("t_g", "t", ("a",))
    g.set("x", value=7)
    assert g.value("x") == 7
    h = metrics.Histogram("t_h", "t", buckets=(1.0, 10.0))
    h.observe(value=0.5)
    h.observe(value=5.0)
    h.observe(value=50.0)
    assert h.count() == 3
    assert h.sum() == 55.5


def test_label_arity_enforced():
    c = metrics.Counter("t2_total", "t", ("a", "b"))
    with pytest.raises(ValueError):
        c.inc("only-one")


def test_scheduler_records_admission_metrics():
    store, queues, sched = _mk_env()
    store.add_workload(Workload(
        name="w1", queue_name="lq",
        podsets=[PodSet(count=1, requests={"cpu": 1000})]))
    sched.schedule(now=10.0)
    assert metrics.admitted_workloads_total.value("cq") == 1
    assert metrics.quota_reserved_workloads_total.value("cq") == 1
    assert metrics.admission_attempts_total.value("success") == 1
    assert metrics.admission_wait_time_seconds.count("cq") == 1
    # usage gauge reflects the assumed admission
    assert metrics.cluster_queue_resource_usage.value(
        "cq", "default", "cpu") == 1000


def test_eviction_and_finish_metrics():
    store, queues, sched = _mk_env()
    store.add_workload(Workload(
        name="w1", queue_name="lq",
        podsets=[PodSet(count=1, requests={"cpu": 1000})]))
    sched.schedule(now=0.0)
    sched.evict_workload("default/w1", reason="Preempted", message="m",
                         now=1.0, preemption_reason="InClusterQueue")
    assert metrics.evicted_workloads_total.value("cq", "Preempted") == 1
    assert metrics.preempted_workloads_total.value("cq", "InClusterQueue") == 1
    sched.schedule(now=2.0)  # re-admits
    sched.finish_workload("default/w1", now=3.0)
    assert metrics.finished_workloads_total.value("cq") == 1


def test_pending_gauge_reports_inadmissible():
    store, queues, sched = _mk_env(nominal=500)
    store.add_workload(Workload(
        name="big", queue_name="lq",
        podsets=[PodSet(count=1, requests={"cpu": 1000})]))
    sched.schedule(now=0.0)
    assert metrics.admission_attempts_total.value("inadmissible") == 1
    active = metrics.pending_workloads.value("cq", "active")
    inadmissible = metrics.pending_workloads.value("cq", "inadmissible")
    assert active + inadmissible == 1


def test_quota_gauges_and_clear():
    store, _, _ = _mk_env()
    cq = store.cluster_queues["cq"]
    metrics.report_cluster_queue_quotas("cq", iter_quotas(cq.resource_groups))
    assert metrics.cluster_queue_nominal_quota.value(
        "cq", "default", "cpu") == 4000
    metrics.clear_cluster_queue_metrics("cq")
    assert metrics.cluster_queue_nominal_quota.value(
        "cq", "default", "cpu") == 0


def test_collect_race_with_concurrent_writes():
    """A dashboard scrape (render/collect) racing inc/observe must not
    raise 'dictionary changed size during iteration': collect() now
    copies under the series lock. Hammer with a writer thread churning
    NEW label values (each insert grows the dict) while readers render.
    Extended past collect() to the remaining read surface: Histogram
    count/sum/total_count reads, Registry.register/get racing a full
    render, and exemplar-carrying observes."""
    import threading

    c = metrics.Counter("t_race_total", "t", ("a",))
    h = metrics.Histogram("t_race_h", "t", ("a",), buckets=(1.0, 10.0))
    reg = metrics.Registry()
    reg.register(c)
    reg.register(h)
    stop = threading.Event()
    errors = []

    def guard(fn):
        def run():
            try:
                while not stop.is_set():
                    fn()
            except Exception as e:  # pragma: no cover - the bug
                errors.append(e)
        return run

    def writer(state={"i": 0}):
        i = state["i"] = state["i"] + 1
        c.inc(f"lbl{i}")
        h.observe(f"lbl{i}", value=float(i % 20),
                  exemplar={"cycle": str(i)})

    def reader():
        c.collect()
        h.collect()

    def histo_reader(state={"i": 0}):
        i = state["i"] = state["i"] + 1
        h.count(f"lbl{i % 50}")
        h.sum(f"lbl{i % 50}")
        h.total_count()
        h.exemplars(f"lbl{i % 50}")

    def registrar(state={"i": 0}):
        # late registration racing a scrape grows the series dict
        i = state["i"] = state["i"] + 1
        reg.register(metrics.Gauge(f"t_race_g{i % 200}", "t"))
        reg.get(f"t_race_g{(i * 7) % 200}")

    def renderer():
        reg.render()
        reg.render(openmetrics=True)

    threads = [threading.Thread(target=guard(fn)) for fn in
               (writer, reader, reader, histo_reader, registrar,
                renderer)]
    for t in threads:
        t.start()
    import time

    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join()
    assert not errors, f"a read raced a concurrent write: {errors[0]!r}"


def test_label_values_escaped_in_exposition():
    """Recorder reason strings and CQ names flow into labels verbatim;
    backslash, double-quote, and newline must render escaped or the
    whole exposition corrupts for every scraper."""
    hostile = 'he said "no fit"\nfor C:\\cluster\\cq'
    c = metrics.Counter("t_esc_total", "t", ("reason",))
    c.inc(hostile)
    h = metrics.Histogram("t_esc_h", "t", ("reason",), buckets=(1.0,))
    h.observe(hostile, value=0.5,
              exemplar={"workload": 'ns/"w"\n'})
    r = metrics.Registry()
    r.register(c)
    r.register(h)
    for text in (r.render(), r.render(openmetrics=True)):
        assert ('t_esc_total{reason="he said \\"no fit\\"\\nfor '
                'C:\\\\cluster\\\\cq"} 1') in text
        # no raw newline may survive inside any sample line
        for line in text.splitlines():
            assert line.count('"') % 2 == 0 or "#" in line
        assert '\nfor C:' not in text.replace("\\n", "")
    om = r.render(openmetrics=True)
    assert '# {workload="ns/\\"w\\"\\n"}' in om
    # the raw value is still queryable under its unescaped key
    assert c.value(hostile) == 1


def test_gauge_replace_prefix_zero_fill_then_drop():
    """A drained sample first reports one scrape of 0, then drops off
    entirely; churned label sets must not accumulate forever."""
    g = metrics.Gauge("t_rp", "t", ("lq", "resource"))
    g.replace_prefix(("a",), {("cpu",): 5.0, ("mem",): 3.0})
    assert g.value("a", "cpu") == 5.0
    assert g.value("a", "mem") == 3.0
    # mem leaves the update set: one zero-fill scrape...
    g.replace_prefix(("a",), {("cpu",): 7.0})
    assert g.value("a", "cpu") == 7.0
    assert g.collect()[("a", "mem")] == 0.0
    # ...then the stale sample drops off entirely
    g.replace_prefix(("a",), {("cpu",): 7.0})
    assert ("a", "mem") not in g.collect()
    # other prefixes are never touched
    g.replace_prefix(("b",), {("cpu",): 1.0})
    g.replace_prefix(("a",), {})
    assert g.value("b", "cpu") == 1.0
    # an empty update zero-fills, then clears, the whole prefix
    assert g.collect()[("a", "cpu")] == 0.0
    g.replace_prefix(("a",), {})
    assert all(k[0] != "a" for k in g.collect())


class _ParentGauge(metrics.Gauge):
    """The parent commit's ``Gauge`` kept as the plain reference: one
    flat dict, and a ``replace_prefix`` that walks every sample of the
    gauge. The indexed gauge has to read the same after every call."""

    def set(self, *label_values, value):
        key = self._key(label_values)
        with self._lock:
            self._values[key] = float(value)

    def inc(self, *label_values, by=1.0):
        key = self._key(label_values)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + by

    def delete_matching(self, **by_label):
        idx = {self.labels.index(k): v for k, v in by_label.items()}
        with self._lock:
            for key in [k for k in self._values
                        if all(k[i] == v for i, v in idx.items())]:
                del self._values[key]

    def reset(self):
        self._values = {}

    def replace_prefix(self, prefix, updates):
        n = len(prefix)
        with self._lock:
            for key in list(self._values):
                if key[:n] == prefix and key[n:] not in updates:
                    if self._values[key] == 0.0:
                        del self._values[key]
                    else:
                        self._values[key] = 0.0
        for suffix, v in updates.items():
            self.set(*(prefix + tuple(suffix)), value=v)


#: the label sets of the gauges that ``replace_prefix`` is called on:
#: the cycle-end flush (prefix lengths 2 and 1) and obs/health.py's
#: starvation gauge (the empty prefix)
_PREFIXED_GAUGES = [
    metrics.local_queue_resource_usage,
    metrics.cluster_queue_resource_pending,
    metrics.starvation_oldest_pending_seconds,
]


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("like", _PREFIXED_GAUGES, ids=lambda g: g.name)
def test_gauge_prefix_index_equals_parent_sweep(like, seed, monkeypatch):
    """The same random sequence of every call that adds or removes a
    key, driven through the indexed gauge and through the parent's:
    ``collect()``, the order of ``_values`` and the rendered text are
    equal after every step."""
    import random

    rng = random.Random(seed)
    width = len(like.labels)
    sides = []
    for cls in (metrics.Gauge, _ParentGauge):
        reg = metrics.Registry()
        sides.append((reg, reg.register(cls(like.name, like.help,
                                            like.labels))))
    names = ["a", "b", "c", "d"]

    def key(n):
        return tuple(rng.choice(names) for _ in range(n))

    def step(call):
        for reg, g in sides:
            call(reg, g)
        (reg_new, new), (reg_old, old) = sides
        assert new.collect() == old.collect()
        assert list(new._values) == list(old._values)
        assert reg_new.render() == reg_old.render()

    def reset_all(reg, g):
        monkeypatch.setattr(metrics, "registry", reg)
        metrics.reset_all()

    for _ in range(600):
        op = rng.random()
        if op < 0.25:
            k, v = key(width), rng.choice([0.0, 1.0, 2.5, 7.0])
            step(lambda reg, g: g.set(*k, value=v))
        elif op < 0.35:
            k, by = key(width), rng.choice([-1.0, 1.0])
            step(lambda reg, g: g.inc(*k, by=by))
        elif op < 0.85:
            n = rng.randrange(0, min(width, 2) + 1)
            prefix = key(n)
            updates = {key(width - n): rng.choice([0.0, 1.0, 3.0])
                       for _ in range(rng.randrange(0, 4))}
            # the same call again is what turns a 0 into a dropped key
            for _ in range(rng.choice([1, 1, 2, 3])):
                step(lambda reg, g: g.replace_prefix(prefix, updates))
        elif op < 0.97:
            label, v = rng.choice(like.labels), rng.choice(names)
            step(lambda reg, g: g.delete_matching(**{label: v}))
        else:
            step(reset_all)


def _fill(g, prefixes):
    for i in range(prefixes):
        g.replace_prefix((f"lq-{i}", "ns"), {("f", "cpu"): 1.0,
                                             ("f", "mem"): 2.0})


class _CountingDict(dict):
    """``_values`` with its key visits counted: one for each key an
    iteration yields, one for each lookup, store and delete."""

    visits = 0

    def __iter__(self):
        for k in super().__iter__():
            self.visits += 1
            yield k

    def __getitem__(self, k):
        self.visits += 1
        return super().__getitem__(k)

    def __setitem__(self, k, v):
        self.visits += 1
        super().__setitem__(k, v)

    def __delitem__(self, k):
        self.visits += 1
        super().__delitem__(k)


def test_replace_prefix_work_independent_of_other_prefixes():
    """One call touches the samples under its prefix and its updates,
    however many other prefixes the gauge holds (the flush at 1,000
    ClusterQueues made 3,000 calls that each walked ~1,000 keys)."""
    visits = {}
    for others in (10, 1000):
        g = metrics.Gauge("t_scale", "t", ("lq", "ns", "flavor", "resource"))
        _fill(g, others)
        g.replace_prefix(("mine", "ns"), {("f", "cpu"): 1.0,
                                          ("f", "mem"): 2.0,
                                          ("f", "gpu"): 3.0})
        g._values = _CountingDict(g._values)
        # one stale sample zeroed, two rewritten, one new
        g.replace_prefix(("mine", "ns"), {("f", "cpu"): 4.0,
                                          ("f", "mem"): 5.0,
                                          ("f", "tpu"): 6.0})
        visits[others] = g._values.visits
        assert g.collect()[("mine", "ns", "f", "gpu")] == 0.0
        assert len(g.collect()) == 2 * others + 4
    assert visits[10] == visits[1000] <= 8


def test_reset_all_leaves_no_ghost_under_a_prefix(monkeypatch):
    """After ``reset_all`` the index is as empty as ``_values``: a
    ``replace_prefix`` on a gauge that had been full finds nothing
    stale, raises nothing and brings no old sample back."""
    reg = metrics.Registry()
    g = reg.register(metrics.Gauge(
        "t_ghost", "t", ("lq", "ns", "flavor", "resource")))
    monkeypatch.setattr(metrics, "registry", reg)
    _fill(g, 50)
    metrics.reset_all()
    assert g.collect() == {}
    g.replace_prefix(("lq-3", "ns"), {("f", "gpu"): 9.0})
    g.replace_prefix(("lq-4", "ns"), {})
    assert g.collect() == {("lq-3", "ns", "f", "gpu"): 9.0}
    # and the old samples under the prefix are not zero-filled either
    g.replace_prefix(("lq-3", "ns"), {})
    assert g.collect() == {("lq-3", "ns", "f", "gpu"): 0.0}
    g.replace_prefix(("lq-3", "ns"), {})
    assert g.collect() == {}


def test_histogram_bucket_edge_values_inclusive():
    """Prometheus le buckets are INCLUSIVE upper bounds: an observation
    exactly on a bucket edge counts in that bucket (and all above)."""
    h = metrics.Histogram("t_edge", "t", buckets=(1.0, 5.0, 10.0))
    h.observe(value=1.0)   # == first edge
    h.observe(value=5.0)   # == middle edge
    h.observe(value=10.0)  # == last edge
    counts, total, n = h.collect()[()]
    assert counts == [1, 2, 3]
    assert n == 3 and total == 16.0
    r = metrics.Registry()
    r.register(h)
    rendered = r.render()
    assert 't_edge_bucket{le="1.0"} 1' in rendered
    assert 't_edge_bucket{le="5.0"} 2' in rendered
    assert 't_edge_bucket{le="10.0"} 3' in rendered
    assert 't_edge_bucket{le="+Inf"} 3' in rendered


def test_render_exposition_format():
    store, queues, sched = _mk_env()
    store.add_workload(Workload(
        name="w1", queue_name="lq",
        podsets=[PodSet(count=1, requests={"cpu": 1000})]))
    sched.schedule(now=0.0)
    text = metrics.registry.render()
    assert '# TYPE kueue_admitted_workloads_total counter' in text
    assert 'kueue_admitted_workloads_total{cluster_queue="cq"} 1' in text
    assert 'kueue_admission_attempt_duration_seconds_count{result="success"} 1' in text


def test_solver_mesh_devices_gauge_tracks_active_mesh():
    """kueue_tpu_solver_mesh_devices: drain-scoped mesh width; 0 means
    single-chip / host path (the fallback chain resets it)."""
    g = metrics.solver_mesh_devices
    assert g.value() == 0  # reset state: nothing reported yet
    g.set(value=8)
    assert g.value() == 8
    g.set(value=0)  # mesh fault / single-chip drain zeroes it
    assert g.value() == 0
    rendered = metrics.registry.render()
    assert "# TYPE kueue_tpu_solver_mesh_devices gauge" in rendered
    assert "kueue_tpu_solver_mesh_devices 0" in rendered


def test_solver_shard_imbalance_histogram_buckets():
    """kueue_tpu_solver_shard_imbalance: (max-min)/mean occupied rows
    per mesh drain; perfectly-even drains land in every bucket
    (value 0), pathological skew only in +Inf."""
    h = metrics.solver_shard_imbalance
    h.observe(value=0.0)    # perfectly even
    h.observe(value=0.3)    # mild skew
    h.observe(value=100.0)  # pathological: beyond the top bucket
    counts, total, n = h.collect()[()]
    assert n == 3 and total == 100.3
    by_edge = dict(zip(h.buckets, counts))
    assert by_edge[0.01] == 1          # only the even drain
    assert by_edge[0.5] == 2           # even + mild
    assert by_edge[8.0] == 2           # 100.0 exceeds every edge
    rendered = metrics.registry.render()
    assert ('kueue_tpu_solver_shard_imbalance_bucket{le="+Inf"} 3'
            in rendered)


def test_mesh_drain_reports_mesh_metrics():
    """A production engine drain routed to the mesh arm must report the
    mesh width gauge and one imbalance observation (tests the engine
    wiring, not just the series)."""
    store, queues, sched = _mk_env()
    for i in range(8):
        store.add_workload(Workload(
            name=f"mw{i}", queue_name="lq", uid=i + 1,
            creation_time=float(i),
            podsets=[PodSet(count=1, requests={"cpu": 100})]))
    from kueue_oss_tpu.solver.engine import SolverEngine

    engine = SolverEngine(store, queues, scheduler=sched)
    engine.mesh_min_workloads = 0
    engine.mesh_force = True
    n0 = metrics.solver_shard_imbalance.total_count()
    result = engine.drain(now=0.0)
    assert result.admitted == 8
    assert engine.last_drain_arm == "mesh"
    assert metrics.solver_mesh_devices.value() >= 2
    assert metrics.solver_shard_imbalance.total_count() == n0 + 1
