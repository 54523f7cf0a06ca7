"""KT005 — metric naming and registration (promtool-check analog).

Absorbed from the PR-1 standalone ``tools/lint_metrics.py`` (which now
shims onto this pass). Enforces, for every metric registration:

1. names are snake_case (``^[a-z][a-z0-9_]*$``);
2. names carry a unit/kind suffix — one of ``_seconds``, ``_bytes``,
   ``_total``, ``_ratio``, ``_info`` — so a scrape reader never has to
   guess units (``_count``/``_sum``/``_bucket`` are reserved for
   histogram/summary child series; a small reference-parity allowlist
   is grandfathered);
3. metrics are registered through ``metrics.DEFAULT`` (the registry the
   /metrics endpoints render); a bare ``metrics.Counter(...)`` outside
   utils/metrics.py would silently never be scraped;
4. names are string literals (a dynamic name defeats static lint and
   risks unbounded metric families).
"""

from __future__ import annotations

import ast
import re
from typing import List

from tools.ktlint.framework import FileContext, Finding, Rule, attr_chain

NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")
# NOTE: "_count" is deliberately NOT a valid suffix — promtool reserves
# _count/_sum/_bucket for histogram/summary child series.
UNIT_SUFFIXES = ("_seconds", "_bytes", "_total", "_ratio", "_info")
FACTORY_METHODS = {"counter", "gauge", "summary", "histogram"}
METRIC_CLASSES = {"Counter", "Gauge", "Summary", "Histogram"}

#: Reference-parity names grandfathered in (they match the reference
#: codebase's own metrics packages verbatim, and dashboards key on
#: them); everything new must carry a unit suffix.
ALLOWLIST = {
    "apiserver_request_count",  # pkg/apiserver/metrics.go
    "kubelet_running_pods",  # pkg/kubelet/metrics/metrics.go
}

#: Gang-scheduling metric family (scheduler/gang.py +
#: controllers/gangs.py). gang_solve_outcomes_total and
#: gang_controller_syncs_total satisfy the suffix rule on their own;
#: gang_pending_groups is a unitless snapshot gauge (a count of
#: objects, like kubelet_running_pods) and is allowlisted explicitly so
#: the linter documents — rather than silently tolerates — the family.
GANG_METRICS = {
    "gang_solve_outcomes_total",
    "gang_controller_syncs_total",
    "gang_pending_groups",
}
ALLOWLIST |= GANG_METRICS

#: Priority & preemption family (scheduler/daemon.py). The counters
#: carry _total on their own; preemption_active_nominations is a
#: unitless snapshot gauge (a count of held reservations, like
#: gang_pending_groups) and is allowlisted explicitly so the linter
#: documents the whole family rather than silently tolerating it.
PREEMPTION_METRICS = {
    "preemption_victims_total",
    "preemption_solve_outcomes_total",
    "preemption_active_nominations",
}
ALLOWLIST |= PREEMPTION_METRICS

#: Explainability & solver-convergence family (utils/flightrecorder.py,
#: observed by ops/sinkhorn.py, ops/wave.py, ops/pipeline.py,
#: ops/incremental.py). scheduler_decisions_total carries _total on its
#: own; the residual gauge (a log-domain mass excess) and the iteration
#: histogram (a count of price updates / waves) are unit-less by nature
#: and allowlisted explicitly so the linter documents the family rather
#: than silently tolerating it.
EXPLAIN_METRICS = {
    "scheduler_decisions_total",
    "scheduler_sinkhorn_residual",
    "scheduler_solve_iterations",
}
ALLOWLIST |= EXPLAIN_METRICS

#: SLI/SLO telemetry-plane family (utils/sli.py, store/watch.py,
#: scheduler/daemon.py — see docs/architecture.md "Telemetry plane &
#: SLOs"). Most names carry standard unit suffixes on their own; the
#: exceptions are unit-less by nature — watch_stream_queue_depth (a
#: count of queued events, like gang_pending_groups),
#: watch_fanout_lag_versions (a count of store versions), and
#: solver_xla_compile_cache_entries (a count of cached executables) —
#: and are allowlisted explicitly so the linter documents the whole
#: family rather than silently tolerating it.
SLI_METRICS = {
    "pod_startup_latency_seconds",
    "watch_streams_dropped_total",
    "watch_stream_queue_depth",
    "watch_fanout_lag_versions",
    "scheduler_informer_staleness_seconds",
    "solver_device_transfer_bytes_total",
    "solver_xla_compiles_total",
    "solver_xla_compile_cache_entries",
    "device_memory_bytes",
}
ALLOWLIST |= SLI_METRICS

#: Device-time profiling-plane family (ops/ledger.py — see
#: docs/performance.md "Profiling the solve path").
#: solver_compile_seconds_total carries a standard suffix on its own;
#: it is declared so the linter documents the family.
PROFILER_METRICS = {
    "solver_compile_seconds_total",
}
ALLOWLIST |= PROFILER_METRICS

#: Capacity & fragmentation plane family (utils/capacity.py, sampled
#: by scheduler/daemon.py — see docs/architecture.md "Capacity &
#: fragmentation plane"). node_utilization_ratio carries _ratio and
#: capacity_zero_headroom_ticks_total carries _total on their own;
#: the score/rate histograms are unit-less [0, 1] ratios on the
#: profiler's ratio ladder, cluster_headroom_pods is a unitless
#: snapshot gauge (a count of placeable probe pods, like
#: gang_pending_groups), and scheduler_backlog_pressure is a composite
#: (pods x seconds) watermark — all allowlisted explicitly so the
#: linter documents the whole family rather than silently tolerating
#: it.
CAPACITY_METRICS = {
    "cluster_fragmentation_score",
    "cluster_headroom_pods",
    "slice_alloc_success_rate",
    "scheduler_backlog_pressure",
}
ALLOWLIST |= CAPACITY_METRICS

#: Rebalancing-plane family (utils/rebalance.py, driven by
#: controllers/descheduler.py — see docs/architecture.md "Rebalancing
#: plane"). rebalance_moves_total and rebalance_stranded_pods_total
#: carry _total on their own; the improvement histogram is a unit-less
#: [0, 1] score delta on the profiler's ratio ladder and
#: rebalance_moves_per_improvement is a composite efficiency quotient
#: (evictions per score unit, the defrag-efficiency SLO series) — the
#: whole family is declared so the linter documents it rather than
#: silently tolerating the unsuffixed members.
REBALANCE_METRICS = {
    "rebalance_moves_total",
    "rebalance_score_improvement",
    "rebalance_moves_per_improvement",
    "rebalance_stranded_pods_total",
}
ALLOWLIST |= REBALANCE_METRICS

#: Elastic node-pool autoscaler family (controllers/autoscaler.py).
#: autoscaler_scale_events_total carries _total on its own;
#: autoscaler_pool_size is a unitless snapshot gauge (a node count per
#: pool, like cluster_headroom_pods) — declared as a family for the
#: same documentation reason.
AUTOSCALER_METRICS = {
    "autoscaler_pool_size",
    "autoscaler_scale_events_total",
}
ALLOWLIST |= AUTOSCALER_METRICS

#: HA control-plane family (store/replication.py, utils/lease.py,
#: scheduler/standby.py — see docs/architecture.md "HA control
#: plane"). leader_elections_total and the standby activation summary
#: carry standard suffixes on their own; replication_commit_index is a
#: store-version watermark and replication_follower_lag_versions a
#: count of store versions (like watch_fanout_lag_versions) — both
#: unit-less by nature and allowlisted explicitly so the linter
#: documents the whole family rather than silently tolerating it.
REPLICATION_METRICS = {
    "replication_commit_index",
    "replication_follower_lag_versions",
    "leader_elections_total",
    "scheduler_standby_activation_seconds",
}
ALLOWLIST |= REPLICATION_METRICS

#: Health-plane family (utils/timeseries.py, utils/alerts.py,
#: utils/lease.py — see docs/architecture.md "Alerting & health
#: plane"). timeseries_samples_total / timeseries_sample_seconds /
#: alert_transitions_total / lease_renew_latency_seconds carry
#: standard suffixes on their own; timeseries_retained_series is a
#: unitless snapshot gauge (a count of retained label sets, like
#: cluster_headroom_pods) and alerts_firing a 0/1 state gauge per
#: rule — both allowlisted explicitly so the linter documents the
#: whole family rather than silently tolerating it.
HEALTH_METRICS = {
    "timeseries_samples_total",
    "timeseries_retained_series",
    "timeseries_sample_seconds",
    "alerts_firing",
    "alert_transitions_total",
    "lease_renew_latency_seconds",
}
ALLOWLIST |= HEALTH_METRICS


class MetricNamingRule(Rule):
    id = "KT005"
    title = "metric names are snake_case, unit-suffixed, on metrics.DEFAULT"

    def applies(self, ctx: FileContext) -> bool:
        # The metric classes themselves live in utils/metrics.py.
        return not (
            ctx.path.name == "metrics.py" and ctx.path.parent.name == "utils"
        )

    def check(self, ctx: FileContext) -> List[Finding]:
        problems: List[Finding] = []
        # Names bound by `from ...metrics import Counter` (possibly
        # aliased) — a bare `Counter(...)` call through such an import
        # is the same registry bypass as `metrics.Counter(...)`.
        imported_classes = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom) and node.module and (
                node.module == "metrics" or node.module.endswith(".metrics")
            ):
                for alias in node.names:
                    if alias.name in METRIC_CLASSES:
                        imported_classes.add(alias.asname or alias.name)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = attr_chain(node.func)
            via_registry = (
                len(chain) >= 2
                and chain[-2] == "DEFAULT"
                and chain[-1] in FACTORY_METHODS
            )
            direct_class = (
                chain
                and chain[-1] in METRIC_CLASSES
                and "metrics" in chain[:-1]
            ) or (len(chain) == 1 and chain[0] in imported_classes)
            if not (via_registry or direct_class):
                continue
            if direct_class:
                problems.append(
                    ctx.finding(
                        self.id, node,
                        f"metrics.{chain[-1]}(...) bypasses metrics.DEFAULT "
                        "— unregistered metrics never reach /metrics",
                    )
                )
                continue
            if not node.args:
                problems.append(
                    ctx.finding(
                        self.id, node, "metric registration without a name"
                    )
                )
                continue
            arg = node.args[0]
            if not (isinstance(arg, ast.Constant) and isinstance(arg.value, str)):
                problems.append(
                    ctx.finding(
                        self.id, node, "metric name must be a string literal"
                    )
                )
                continue
            name = arg.value
            if not NAME_RE.match(name):
                problems.append(
                    ctx.finding(
                        self.id, node,
                        f"metric name {name!r} is not snake_case",
                    )
                )
            if name not in ALLOWLIST and not name.endswith(UNIT_SUFFIXES):
                problems.append(
                    ctx.finding(
                        self.id, node,
                        f"metric name {name!r} lacks a unit suffix "
                        f"({'/'.join(UNIT_SUFFIXES)})",
                    )
                )
        return problems
