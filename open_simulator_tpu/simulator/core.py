"""The Simulate() facade — the stable programmatic surface of the framework.

Mirrors /root/reference/pkg/simulator/core.go:67-119: expand the cluster's workloads
into pods, run the cluster sync (placing bound pods and scheduling pending ones), then
deploy each app in order, accumulating unschedulable pods.
"""

from __future__ import annotations

from typing import Callable, List, Optional

from ..core.types import AppResource, ResourceTypes, SimulateResult
from ..models.workloads import (
    expand_workloads_excluding_daemonsets,
    pods_from_daemonset,
)
from .engine import Simulator


def simulate(
    cluster: ResourceTypes,
    apps: List[AppResource],
    disable_progress: bool = True,
    patch_pod_funcs: Optional[List[Callable]] = None,
    sched_config=None,
    extra_plugins: Optional[List] = None,
) -> SimulateResult:
    """Run one full simulation; returns placements + unschedulable pods.

    `cluster.pods` is replaced by the expansion of all cluster workloads (raw pods,
    Deployments/RS/RC/STS/Jobs/CronJobs, then DaemonSets against the node list), exactly
    like Simulate (core.go:85-96).
    """
    from ..utils.trace import Span

    with Span("Simulate", log_if_longer=1.0):  # core.go:67-73 LogIfLong
        with Span("Simulate.expand_workloads"):
            cluster = cluster.copy()
            pods = expand_workloads_excluding_daemonsets(cluster)
            for ds in cluster.daemon_sets:
                pods.extend(pods_from_daemonset(ds, cluster.nodes))
            cluster.pods = pods

        sim = Simulator(cluster.nodes, disable_progress=disable_progress,
                        patch_pod_funcs=patch_pod_funcs, sched_config=sched_config,
                        extra_plugins=extra_plugins)
        with Span("Simulate.sync_cluster"):
            result = sim.run_cluster(cluster)
        failed = list(result.unscheduled_pods)
        for app in apps:
            with Span("Simulate.schedule_app") as span:
                span.annotate("app", app.name)
                result = sim.schedule_app(app)
            failed.extend(result.unscheduled_pods)
        result.unscheduled_pods = failed
    return result
