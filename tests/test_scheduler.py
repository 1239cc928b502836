"""Scheduler cycle tests: admission, queueing strategies, borrowing,
flavor fungibility, preemption, fair sharing, partial admission.

Scenario shapes mirror the reference's pkg/scheduler/scheduler_test.go and
preemption_test.go fixtures.
"""

from kueue_oss_tpu.api.types import (
    BorrowWithinCohort,
    ClusterQueue,
    Cohort,
    FairSharing,
    FlavorFungibility,
    FlavorFungibilityPolicy,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    PreemptionPolicy,
    PreemptionPolicyValue,
    QueueingStrategy,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_oss_tpu.core.queue_manager import QueueManager
from kueue_oss_tpu.core.store import Store
from kueue_oss_tpu.scheduler.scheduler import Scheduler


def make_cq(name, nominal, cohort=None, flavors=None, resource="cpu", **kw):
    """flavors: list of (flavor_name, nominal) preserving order."""
    flavors = flavors or [("default", nominal)]
    return ClusterQueue(
        name=name,
        cohort=cohort,
        resource_groups=[
            ResourceGroup(
                covered_resources=[resource],
                flavors=[
                    FlavorQuotas(name=f, resources=[
                        ResourceQuota(name=resource, nominal=n,
                                      borrowing_limit=kw.get("borrowing_limit"),
                                      lending_limit=kw.get("lending_limit"))])
                    for f, n in flavors
                ],
            )
        ],
        queueing_strategy=kw.get("strategy", QueueingStrategy.BEST_EFFORT_FIFO),
        preemption=kw.get("preemption", PreemptionPolicy()),
        flavor_fungibility=kw.get("fungibility", FlavorFungibility()),
        fair_sharing=kw.get("fair_sharing", FairSharing()),
    )


class Harness:
    def __init__(self, cqs, cohorts=(), flavors=("default",),
                 fair_sharing=False):
        self.store = Store()
        for f in flavors:
            self.store.upsert_resource_flavor(
                f if isinstance(f, ResourceFlavor) else ResourceFlavor(name=f))
        for c in cohorts:
            self.store.upsert_cohort(c)
        for cq in cqs:
            self.store.upsert_cluster_queue(cq)
            self.store.upsert_local_queue(
                LocalQueue(name=f"lq-{cq.name}", cluster_queue=cq.name))
        self.queues = QueueManager(self.store)
        self.scheduler = Scheduler(self.store, self.queues,
                                   enable_fair_sharing=fair_sharing)
        self._t = 0.0

    def submit(self, name, cq, cpu=1000, count=1, priority=0, min_count=None,
               resource="cpu"):
        self._t += 1.0
        wl = Workload(
            name=name,
            queue_name=f"lq-{cq}",
            priority=priority,
            creation_time=self._t,
            podsets=[PodSet(count=count, requests={resource: cpu},
                            min_count=min_count)],
        )
        self.store.add_workload(wl)
        return wl

    def cycle(self, n=1):
        stats = None
        for _ in range(n):
            self._t += 1.0
            self.scheduler.requeue_due(self._t)
            stats = self.scheduler.schedule(now=self._t)
        return stats

    def settle(self, max_cycles=50):
        idle = 0
        for _ in range(max_cycles):
            pre = self.scheduler._queue_fingerprint()
            self._t += 1.0
            self.scheduler.requeue_due(self._t)
            stats = self.scheduler.schedule(now=self._t)
            if stats.heads == 0 and self.scheduler.next_requeue_at() is None:
                break
            if (stats.admitted == 0 and stats.preempted == 0
                    and self.scheduler._queue_fingerprint() == pre):
                idle += 1
                # allow pending eviction backoffs to expire before giving up
                if idle > 3 and self.scheduler.next_requeue_at() is None:
                    break
                nxt = self.scheduler.next_requeue_at()
                if nxt is not None:
                    self._t = max(self._t, nxt)
            else:
                idle = 0

    def finish(self, key):
        self._t += 1.0
        self.scheduler.finish_workload(key if "/" in key else f"default/{key}",
                                       now=self._t)

    def admitted(self):
        return sorted(w.name for w in self.store.workloads.values()
                      if w.is_admitted and not w.is_finished)

    def wl(self, name):
        return self.store.workloads[f"default/{name}"]


class TestBasicAdmission:
    def test_admits_within_quota(self):
        h = Harness([make_cq("cq", 4000)])
        h.submit("a", "cq", cpu=2000)
        h.submit("b", "cq", cpu=2000)
        h.settle()
        assert h.admitted() == ["a", "b"]
        adm = h.wl("a").status.admission
        assert adm.cluster_queue == "cq"
        assert adm.podset_assignments[0].flavors == {"cpu": "default"}

    def test_over_quota_waits_then_admits_after_finish(self):
        h = Harness([make_cq("cq", 3000)])
        h.submit("a", "cq", cpu=2000)
        h.submit("b", "cq", cpu=2000)
        h.settle()
        assert h.admitted() == ["a"]
        h.finish("a")
        h.settle()
        assert h.admitted() == ["b"]

    def test_priority_order(self):
        h = Harness([make_cq("cq", 2000)])
        h.submit("low", "cq", cpu=2000, priority=1)
        h.submit("high", "cq", cpu=2000, priority=10)
        h.settle()
        assert h.admitted() == ["high"]

    def test_fifo_within_priority(self):
        h = Harness([make_cq("cq", 2000)])
        h.submit("first", "cq", cpu=2000)
        h.submit("second", "cq", cpu=2000)
        h.settle()
        assert h.admitted() == ["first"]

    def test_strict_fifo_blocks_behind_head(self):
        # BestEffortFIFO admits the small workload around the big head;
        # StrictFIFO must not.
        for strategy, expect in [
            (QueueingStrategy.BEST_EFFORT_FIFO, ["small"]),
            (QueueingStrategy.STRICT_FIFO, []),
        ]:
            h = Harness([make_cq("cq", 3000, strategy=strategy)])
            h.submit("big", "cq", cpu=4000)   # never fits
            h.submit("small", "cq", cpu=1000)
            h.settle()
            assert h.admitted() == expect, strategy

    def test_multi_podset_workload(self):
        h = Harness([make_cq("cq", 10000)])
        wl = Workload(
            name="mp", queue_name="lq-cq", creation_time=1.0,
            podsets=[PodSet(name="driver", count=1, requests={"cpu": 1000}),
                     PodSet(name="workers", count=4, requests={"cpu": 2000})])
        h.store.add_workload(wl)
        h.settle()
        assert h.admitted() == ["mp"]
        psa = h.wl("mp").status.admission.podset_assignments
        assert [p.name for p in psa] == ["driver", "workers"]
        assert psa[1].resource_usage == {"cpu": 8000}

    def test_inadmissible_parked_not_retried(self):
        h = Harness([make_cq("cq", 1000)])
        h.submit("big", "cq", cpu=5000)
        h.settle()
        q = h.queues.queues["cq"]
        assert q.pending_inadmissible == 1
        assert q.pending_active == 0


class TestCohortBorrowing:
    def test_borrow_idle_sibling_quota(self):
        h = Harness(
            [make_cq("a", 2000, "co"), make_cq("b", 2000, "co")],
            cohorts=[Cohort(name="co")],
        )
        h.submit("w1", "a", cpu=3000)
        h.settle()
        assert h.admitted() == ["w1"]

    def test_borrowing_limit_respected(self):
        h = Harness(
            [make_cq("a", 2000, "co", borrowing_limit=500),
             make_cq("b", 2000, "co")],
            cohorts=[Cohort(name="co")],
        )
        h.submit("w1", "a", cpu=3000)
        h.settle()
        assert h.admitted() == []

    def test_one_borrowing_admission_per_cohort_per_cycle(self):
        # Two CQs both want to borrow the same idle capacity; only one can
        # win, the other must see "no longer fits" and retry.
        h = Harness(
            [make_cq("a", 0, "co"), make_cq("b", 0, "co"),
             make_cq("idle", 3000, "co")],
            cohorts=[Cohort(name="co")],
        )
        h.submit("wa", "a", cpu=2000)
        h.submit("wb", "b", cpu=2000)
        stats = h.cycle()
        assert stats.admitted == 1
        h.settle()
        assert len(h.admitted()) == 1  # second can never fit (only 1000 left)

    def test_non_borrowing_admissions_can_share_cycle(self):
        h = Harness(
            [make_cq("a", 2000, "co"), make_cq("b", 2000, "co")],
            cohorts=[Cohort(name="co")],
        )
        h.submit("wa", "a", cpu=2000)
        h.submit("wb", "b", cpu=2000)
        stats = h.cycle()
        assert stats.admitted == 2


class TestFlavorFungibility:
    def flavors_cq(self, **kw):
        return make_cq("cq", 0, flavors=[("on-demand", 2000), ("spot", 5000)],
                       **kw)

    def test_falls_through_to_second_flavor(self):
        h = Harness([self.flavors_cq()], flavors=("on-demand", "spot"))
        h.submit("big", "cq", cpu=4000)
        h.settle()
        assert h.admitted() == ["big"]
        psa = h.wl("big").status.admission.podset_assignments[0]
        assert psa.flavors == {"cpu": "spot"}

    def test_prefers_first_fitting_flavor(self):
        h = Harness([self.flavors_cq()], flavors=("on-demand", "spot"))
        h.submit("small", "cq", cpu=1000)
        h.settle()
        psa = h.wl("small").status.admission.podset_assignments[0]
        assert psa.flavors == {"cpu": "on-demand"}

    def test_taint_untolerated_skips_flavor(self):
        flavors = (
            ResourceFlavor(name="on-demand"),
            ResourceFlavor(name="spot", node_taints=[
                __import__("kueue_oss_tpu.api.types", fromlist=["Taint"])
                .Taint(key="spot", effect="NoSchedule")]),
        )
        h = Harness([self.flavors_cq()], flavors=flavors)
        h.submit("big", "cq", cpu=4000)  # only fits spot, but untolerated
        h.settle()
        assert h.admitted() == []

    def test_when_can_borrow_try_next_flavor(self):
        # With whenCanBorrow=TryNextFlavor, a workload that would need to
        # borrow on flavor 1 moves to flavor 2 instead.
        cq_a = ClusterQueue(
            name="a", cohort="co",
            resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[
                    FlavorQuotas(name="on-demand", resources=[
                        ResourceQuota(name="cpu", nominal=1000)]),
                    FlavorQuotas(name="spot", resources=[
                        ResourceQuota(name="cpu", nominal=5000)]),
                ])],
            flavor_fungibility=FlavorFungibility(
                when_can_borrow=FlavorFungibilityPolicy.TRY_NEXT_FLAVOR),
        )
        cq_b = ClusterQueue(
            name="b", cohort="co",
            resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[FlavorQuotas(name="on-demand", resources=[
                    ResourceQuota(name="cpu", nominal=3000)])])],
        )
        h = Harness([cq_a, cq_b], cohorts=[Cohort(name="co")],
                    flavors=("on-demand", "spot"))
        h.submit("w", "a", cpu=2000)
        h.settle()
        psa = h.wl("w").status.admission.podset_assignments[0]
        assert psa.flavors == {"cpu": "spot"}


PREEMPT_LOWER = PreemptionPolicy(
    within_cluster_queue=PreemptionPolicyValue.LOWER_PRIORITY)
RECLAIM_ANY = PreemptionPolicy(
    reclaim_within_cohort=PreemptionPolicyValue.ANY)


class TestPreemption:
    def test_preempts_lower_priority_in_cq(self):
        h = Harness([make_cq("cq", 2000, preemption=PREEMPT_LOWER)])
        h.submit("low", "cq", cpu=2000, priority=0)
        h.settle()
        assert h.admitted() == ["low"]
        h.submit("high", "cq", cpu=2000, priority=10)
        h.settle()
        assert h.admitted() == ["high"]
        assert h.wl("low").is_evicted
        assert h.wl("low").condition("Preempted").reason == "InClusterQueue"

    def test_no_preemption_when_policy_never(self):
        h = Harness([make_cq("cq", 2000)])
        h.submit("low", "cq", cpu=2000, priority=0)
        h.settle()
        h.submit("high", "cq", cpu=2000, priority=10)
        h.settle()
        assert h.admitted() == ["low"]

    def test_preempts_minimal_set(self):
        h = Harness([make_cq("cq", 3000, preemption=PREEMPT_LOWER)])
        h.submit("v1", "cq", cpu=1000, priority=0)
        h.submit("v2", "cq", cpu=1000, priority=1)
        h.submit("v3", "cq", cpu=1000, priority=2)
        h.settle()
        assert len(h.admitted()) == 3
        h.submit("high", "cq", cpu=1000, priority=10)
        h.settle()
        assert "high" in h.admitted()
        # only the lowest-priority victim should have been evicted
        assert h.wl("v1").is_evicted
        assert not h.wl("v2").is_evicted
        assert not h.wl("v3").is_evicted

    def test_reclaim_within_cohort(self):
        # b borrows a's idle quota; a's workload then reclaims it.
        h = Harness(
            [make_cq("a", 2000, "co", preemption=RECLAIM_ANY),
             make_cq("b", 2000, "co")],
            cohorts=[Cohort(name="co")],
        )
        h.submit("borrower", "b", cpu=4000)
        h.settle()
        assert h.admitted() == ["borrower"]
        h.submit("owner", "a", cpu=2000)
        h.settle()
        assert h.admitted() == ["owner"]
        assert h.wl("borrower").is_evicted
        assert (h.wl("borrower").condition("Preempted").reason
                == "InCohortReclamation")

    def test_reclaim_does_not_preempt_non_borrowers(self):
        h = Harness(
            [make_cq("a", 2000, "co", preemption=RECLAIM_ANY),
             make_cq("b", 2000, "co")],
            cohorts=[Cohort(name="co")],
        )
        h.submit("rightful", "b", cpu=2000)
        h.settle()
        h.submit("wants", "a", cpu=4000)  # needs to borrow, b not borrowing
        h.settle()
        assert h.admitted() == ["rightful"]


class TestFairSharing:
    def cqs(self):
        return [
            make_cq("a", 2000, "co", preemption=RECLAIM_ANY),
            make_cq("b", 2000, "co", preemption=RECLAIM_ANY),
            make_cq("c", 2000, "co"),
        ]

    def test_tournament_prefers_lower_share(self):
        h = Harness(self.cqs(), cohorts=[Cohort(name="co")], fair_sharing=True)
        # a has high usage (borrowing), b none; b's workload should win the
        # tournament and admit first.
        h.submit("a-pre", "a", cpu=3000)
        h.settle()
        h.submit("a-next", "a", cpu=1500)
        h.submit("b-next", "b", cpu=1500)
        stats = h.cycle()
        assert stats.admitted >= 1
        assert "b-next" in h.admitted()

    def test_fair_preemption_rebalances(self):
        h = Harness(self.cqs(), cohorts=[Cohort(name="co")], fair_sharing=True)
        for i in range(6):
            h.submit(f"hog-{i}", "a", cpu=1000)
        h.settle()
        assert len(h.admitted()) == 6  # a uses all 6000 in the cohort
        h.submit("claim", "b", cpu=2000)
        h.settle()
        assert "claim" in h.admitted()
        evicted = [w.name for w in h.store.workloads.values() if w.is_evicted]
        assert len(evicted) >= 1
        assert all(n.startswith("hog-") for n in evicted)
        # the claimant stays within nominal on the contested resource,
        # so FairSharingPreemptWithinNominal (GA default) classifies the
        # eviction as entitlement reclamation, not fair sharing
        assert (h.wl(evicted[0]).condition("Preempted").reason
                == "InCohortReclamation")


class TestQueueManagerEvents:
    def test_reactivated_workload_requeues_via_update_event(self):
        h = Harness([make_cq("cq", 2000)])
        wl = h.submit("w", "cq", cpu=1000)
        wl.active = False
        h.store.update_workload(wl)
        h.settle()
        assert h.admitted() == []
        wl.active = True
        h.store.update_workload(wl)
        h.settle()
        assert h.admitted() == ["w"]

    def test_mid_cycle_capacity_flush_not_lost(self):
        # A head popped before a same-cycle eviction frees capacity must go
        # back to the heap, not be parked forever.
        h = Harness(
            [make_cq("a", 2000, "co", preemption=PREEMPT_LOWER),
             make_cq("b", 0, "co")],
            cohorts=[Cohort(name="co")],
        )
        h.submit("low", "a", cpu=2000, priority=0)
        h.settle()
        # b's workload needs the capacity currently held by "low"; a's
        # high-priority workload preempts "low" in the same cycle b's head
        # is processed and fails.
        h.submit("high", "a", cpu=2000, priority=10)
        h.submit("b-wl", "b", cpu=2000)
        h.cycle()  # preemption of "low" fires; b-wl fails this cycle
        q = h.queues.queues["b"]
        assert q.pending_active == 1, "b-wl must be back in the heap"


class TestPartialAdmission:
    def test_reduces_count_to_fit(self):
        h = Harness([make_cq("cq", 3000)])
        h.submit("elastic", "cq", cpu=1000, count=5, min_count=1)
        h.settle()
        assert h.admitted() == ["elastic"]
        psa = h.wl("elastic").status.admission.podset_assignments[0]
        assert psa.count == 3
        assert psa.resource_usage == {"cpu": 3000}

    def test_no_reduction_below_min_count(self):
        h = Harness([make_cq("cq", 500)])
        h.submit("elastic", "cq", cpu=1000, count=5, min_count=2)
        h.settle()
        assert h.admitted() == []


class TestCycleEndGaugeFlush:
    """The cycle-end flush over several hundred ClusterQueues writes the
    per-LocalQueue usage and per-ClusterQueue pending gauges through
    ``Gauge.replace_prefix``. The samples are held to a plain model
    kept here: what the store holds, and for a sample that went stale
    one flush of 0 and then none."""

    N = 300
    NS = "default"

    def _harness(self):
        cqs = []
        for i in range(self.N):
            cqs.append(ClusterQueue(
                name=f"cq-{i}",
                resource_groups=[ResourceGroup(
                    covered_resources=["cpu", "memory"],
                    flavors=[FlavorQuotas(name="default", resources=[
                        ResourceQuota(name="cpu", nominal=16000),
                        ResourceQuota(name="memory", nominal=16000)])])]))
        h = Harness(cqs)
        for cq in cqs:
            h.store.upsert_local_queue(
                LocalQueue(name=f"lq2-{cq.name}", cluster_queue=cq.name))
        return h

    def _submit(self, h):
        for i in range(self.N):
            for j in range(2 + i % 3):
                requests = {"cpu": 1000 * (1 + j % 2)}
                if (i + j) % 3 == 0:
                    requests["memory"] = 500
                h._t += 1.0
                h.store.add_workload(Workload(
                    name=f"w-{i}-{j}", creation_time=h._t,
                    queue_name=f"lq{'2' if j % 2 else ''}-cq-{i}",
                    podsets=[PodSet(count=1, requests=requests)]))

    @staticmethod
    def _replace(samples, prefix, updates):
        """``replace_prefix`` as a sweep over every sample."""
        n = len(prefix)
        for key in [k for k in samples if k[:n] == prefix]:
            if key[n:] not in updates:
                if samples[key] == 0.0:
                    del samples[key]
                else:
                    samples[key] = 0.0
        for suffix, v in updates.items():
            samples[prefix + suffix] = float(v)

    def _state(self, h):
        """(ClusterQueue -> LocalQueue -> (flavor, resource) -> quantity
        reserved, ClusterQueue -> resource -> quantity pending)."""
        usage, pending = {}, {}
        for wl in h.store.workloads.values():
            if wl.is_finished:
                continue
            cq = h.store.cluster_queue_for(wl)
            requests = wl.podsets[0].requests
            if wl.is_quota_reserved:
                by_fr = usage.setdefault(cq, {}).setdefault(
                    wl.queue_name, {})
                for r, v in requests.items():
                    by_fr[("default", r)] = by_fr.get(("default", r), 0) + v
            else:
                by_r = pending.setdefault(cq, {})
                for r, v in requests.items():
                    by_r[r] = by_r.get(r, 0) + v
        return usage, pending

    def test_samples_equal_the_model_through_drain_and_refill(self):
        from kueue_oss_tpu import metrics

        metrics.reset_all()
        h = self._harness()
        self._submit(h)
        lq_usage, cq_pending, reported = {}, {}, {}
        touched = set()

        def cycle():
            # usage is the cycle's snapshot's, taken before it admits;
            # pending is the queues' own count after it
            usage, pending = self._state(h)
            touched.update(pending)          # a head each: all of them fit
            h.cycle()
            _, pending = self._state(h)
            for cq in touched:
                active = usage.get(cq, {})
                for lq, by_fr in active.items():
                    self._replace(lq_usage, (lq, self.NS), by_fr)
                for lq in reported.get(cq, set()) - set(active):
                    self._replace(lq_usage, (lq, self.NS), {})
                reported[cq] = set(active)
                self._replace(cq_pending, (cq,), {
                    (r,): v for r, v in pending.get(cq, {}).items()})
            touched.clear()
            assert metrics.local_queue_resource_usage.collect() == lq_usage
            assert (metrics.local_queue_resource_reservation.collect()
                    == lq_usage)
            assert (metrics.cluster_queue_resource_pending.collect()
                    == cq_pending)

        def nth(wl):
            return int(wl.queue_name.rsplit("-", 1)[1])

        def finish(keep):
            for wl in list(h.store.workloads.values()):
                if (wl.is_quota_reserved and not wl.is_finished
                        and not keep(wl)):
                    touched.add(h.store.cluster_queue_for(wl))
                    h.finish(wl.key)

        try:
            for _ in range(3):
                cycle()
            assert len(lq_usage) > 2 * self.N
            # a LocalQueue drains in every 5th ClusterQueue, a resource
            # leaves a LocalQueue that stays busy in every 7th
            finish(lambda wl: not (
                (wl.queue_name.startswith("lq2-") and nth(wl) % 5 == 0)
                or ("memory" in wl.podsets[0].requests
                    and nth(wl) % 7 == 0)))
            cycle()
            assert 0.0 in lq_usage.values()
            cycle()
            cycle()
            assert not any(cq_pending.values())
            # every 4th ClusterQueue drains whole, then fills again
            finish(lambda wl: nth(wl) % 4)
            cycle()
            self._submit_again(h)
            cycle()
            assert any(cq_pending.values())
            cycle()
            cycle()
        finally:
            metrics.reset_all()

    def _submit_again(self, h):
        for i in range(0, self.N, 2):
            h._t += 1.0
            h.store.add_workload(Workload(
                name=f"again-{i}", creation_time=h._t,
                queue_name=f"lq2-cq-{i}",
                podsets=[PodSet(count=1, requests={"cpu": 250,
                                                   "memory": 250})]))
            h._t += 1.0
            h.store.add_workload(Workload(
                name=f"again2-{i}", creation_time=h._t,
                queue_name=f"lq-cq-{i}",
                podsets=[PodSet(count=1, requests={"cpu": 250})]))
