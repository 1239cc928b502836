"""Feature gates.

Reference parity: pkg/features/kube_features.go:30-386 — a named-gate
registry with per-gate defaults, overridable from the Configuration file
(featureGates map) or a --feature-gates-style dict. Only gates that guard
behavior implemented in this framework are registered; unknown gates are
rejected the way the reference's featuregate library rejects them.
"""

from __future__ import annotations

import threading

#: gate name -> default enabled. Every registered gate is read at a use
#: site — a gate with no enforcing code must NOT be listed here (it would
#: silently no-op); new features register their gate when they wire it in.
#: Reference defaults as of v1beta2.
_DEFAULTS: dict[str, bool] = {
    # queueing / admission
    "PartialAdmission": True,          # scheduler podset reduction
    "ObjectRetentionPolicies": True,   # workload controller GC
    "FlavorFungibility": True,         # flavor_assigner honors custom policy
    "PrioritySortingWithinCohort": True,  # classical iterator priority key
    "LendingLimit": True,              # quota algebra lending limits
    "HierarchicalCohorts": True,       # store cohort parent edges
    "ReclaimablePods": True,           # workload_info + reconciler sync
    "AdmissionFairSharing": True,      # queue_manager AFS ordering key
    # multi-cluster
    "MultiKueue": True,                # multikueue controller sync
    # hub check waits for worker ADMITTED, not just quota-reserved (GA)
    "MultiKueueWaitForWorkloadAdmitted": True,  # controller race check
    # worker eviction triggers hub re-dispatch instead of waiting (GA)
    "MultiKueueRedoAdmissionOnEvictionInWorker": True,  # _sync_winner
    # jobs managedBy the multikueue controller never start locally (GA)
    "MultiKueueBatchJobWithManagedBy": True,  # jobframework run gate
    # observability
    "VisibilityOnDemand": True,        # visibility pending-workloads API
    "LocalQueueMetrics": True,         # local_queue_* metric series
    # DRA (reference default: alpha, off)
    "DynamicResourceAllocation": False,  # dra device-class mapping
    # extended resources resolved through DeviceClasses (alpha, off)
    "DRAExtendedResources": False,     # dra.resolve_extended_resources
    # TAS replacement triggers
    "TASReplaceNodeOnNodeTaints": True,     # failure_recovery taint path
    "TASReplaceNodeOnPodTermination": True,  # failure_recovery term path
    "TASProfileMixed": True,           # LeastFreeCapacity for unconstrained
    # topology-aware scheduling
    "TopologyAwareScheduling": True,   # core/snapshot.py TAS snapshot build
    "TASFailedNodeReplacement": True,  # tas/snapshot.py replacement path
    "TASFailedNodeReplacementFailFast": False,  # failure_recovery eviction
    "TASBalancedPlacement": False,     # tas/snapshot.py balanced algorithm
    "TASMultiLayerTopology": False,    # tas/snapshot.py nested slice layers
    # misc controllers
    "WaitForPodsReady": True,          # workload controller PodsReady path
    # elastic jobs (KEP-77; reference default off)
    "ElasticJobsViaWorkloadSlices": False,  # workloadslicing + scheduler hooks
    # slices for TAS-placed jobs (alpha, off)
    "ElasticJobsViaWorkloadSlicesWithTAS": False,  # workloadslicing.enabled
    # concurrent admission variants (KEP-8691; reference default off)
    "ConcurrentAdmission": False,      # variant fan-out + migration hooks
    # MultiKueue orchestrated preemption (KEP-8303)
    "MultiKueueOrchestratedPreemption": False,  # scheduler gate check
    # BestEffortFIFO NoFit equivalence-class dedup (kube_features.go)
    "SchedulingEquivalenceHashing": True,  # queue_manager no-fit hashes
    # fair-sharing variants (beta, on since 0.17)
    "FairSharingPreemptWithinNominal": True,   # preemption S1 bypass
    "FairSharingPrioritizeNonBorrowing": True,  # tournament step 1
    # LocalQueue status lists usable flavors (kube_features.go)
    "ExposeFlavorsInLocalQueue": True,  # core_controllers LQ status
    # namespace selector bounds queue-named jobs too (kube_features.go
    # :163-166, beta default true since 0.14)
    "ManagedJobsNamespaceSelectorAlwaysRespected": True,  # jobframework
    # default queue-name from the namespace's "default" LocalQueue (GA)
    "LocalQueueDefaulting": True,      # webhooks default_job
    # workload_creation_latency_seconds series (beta, on)
    "MetricForWorkloadCreationLatency": True,  # jobframework reconciler
    # SparkApplication integration opt-in (alpha, off)
    "SparkApplicationIntegration": False,  # jobframework registry
    # finish workloads whose owner job vanished (alpha, off)
    "FinishOrphanedWorkloads": False,  # jobframework reconcile_all GC
    # copy the owner job's labels onto its workload (GA)
    "PropagateBatchJobLabelsToWorkload": True,  # _create_workload
    # hashed 63-char workload names (alpha, off)
    "ShortWorkloadNames": False,       # workload_name_for
    # priority boost annotation adds to effective priority (alpha, off)
    "PriorityBoost": False,            # workload_info.effective_priority
    # same-priority preemption needs a 5-minute timestamp gap (alpha)
    "SchedulerTimestampPreemptionBuffer": False,  # preemption legality
    # Resources.quotaCheckStrategy=IgnoreUndeclared honored (GA)
    "QuotaCheckStrategy": True,        # flavor_assigner + solver export
    # inadmissible requeue sweeps batch at 10s instead of 1s (alpha)
    "SchedulerLongRequeueInterval": False,  # scheduler.serve requeue_due
    # per-CQ/LQ label values appended to metric series (alpha, off)
    "CustomMetricLabels": False,       # metrics custom label resolution
    # config-declared generic adapters for custom job GVKs (beta, on)
    "MultiKueueAdaptersForCustomJobs": True,  # externalframeworks adapter
    # kubeconfigs that skip TLS verification (deprecated, off)
    "MultiKueueAllowInsecureKubeconfigs": False,  # cluster.KubeConfigSource
    # ClusterProfile as a kubeconfig source (alpha, off)
    "MultiKueueClusterProfile": False,  # cluster.KubeConfigSource
    # dedupe env vars in podset templates at Workload creation (GA)
    "SanitizePodSets": True,           # webhooks sanitize_podsets
    # force-delete stuck-Terminating pods that opted in (alpha, off)
    "FailureRecoveryPolicy": False,    # pod._finalize_terminating
    # terminating pods release quota immediately (alpha, off)
    "FastQuotaReleaseInPodIntegration": False,  # pod.Pod.active
    # pods gated by a suspended parent skip the finalizer (GA)
    "SkipFinalizersForPodsSuspendedByParent": True,  # pod.upsert_pod
    # queue provenance labels stamped on created pods (beta, on)
    "AssignQueueLabelsForPods": True,  # reconciler._podset_infos
    # TLS options (minVersion/cipherSuites) applied to the HTTP servers
    # (beta, on; kube_features.go TLSOptions)
    "TLSOptions": True,              # util/tlsconfig build_ssl_context
    # workload status updates via merge patch instead of SSA-style
    # replacement (alpha, off; kube_features.go WorkloadRequestUseMergePatch)
    "WorkloadRequestUseMergePatch": False,  # client patch_status
    # finalizer removal via resourceVersion-checked patch (beta, on)
    "RemoveFinalizersWithStrictPatch": True,  # pod release_finalizer
    # admission-gated-by annotation propagation + validation (alpha, off)
    "AdmissionGatedBy": False,       # jobframework propagate + webhook
    # validate admissionChecksStrategy.onFlavors on CQ update (alpha, off)
    "RejectUpdatesToCQWithInvalidOnFlavors": False,  # webhooks
    # framework-specific (no reference analog): TAS phase-1 fill-in
    # counts on the accelerator, phase-2 tie-breaks host-side — the
    # balanced/multilayer hybrid (tas/snapshot.py _device_fill)
    "TASDeviceFillCounts": False,
}

_lock = threading.Lock()
_overrides: dict[str, bool] = {}


class UnknownFeatureGate(KeyError):
    pass


def enabled(name: str) -> bool:
    # Lock-free read: dict lookups are atomic under the GIL and
    # _overrides is replaced/updated only under _lock by writers. The
    # hot paths (per-workload effective_priority, export loops) call
    # this tens of thousands of times per cycle.
    if name not in _DEFAULTS:
        raise UnknownFeatureGate(name)
    v = _overrides.get(name)
    return _DEFAULTS[name] if v is None else v


def set_gates(gates: dict[str, bool]) -> None:
    """Apply overrides (Configuration.featureGates / --feature-gates)."""
    unknown = sorted(set(gates) - set(_DEFAULTS))
    if unknown:
        raise UnknownFeatureGate(", ".join(unknown))
    with _lock:
        _overrides.update(gates)


def reset() -> None:
    """Restore defaults (test isolation)."""
    with _lock:
        _overrides.clear()


def overrides_key() -> tuple:
    """The gates set away from their defaults, as a hashable key (what a
    memo of gate-dependent work is keyed by)."""
    return tuple(sorted(_overrides.items()))


def all_gates() -> dict[str, bool]:
    with _lock:
        return {n: _overrides.get(n, d) for n, d in _DEFAULTS.items()}
