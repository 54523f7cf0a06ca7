"""Device ops: the TPU scheduling solver.

The reference's per-pod Go loops (generic_scheduler.go:106-171) become
a jitted lax.scan whose carry is the cluster occupancy state and whose
per-step body evaluates every predicate and priority for one pod
against ALL nodes as vector ops. Node-axis arrays shard over a
jax.sharding.Mesh for multi-chip scale-out.
"""

# The persistent compilation cache is placed by the entry points, never
# at import (utils/compile_cache.py). Shape-bucketing
# (matrices._pod_axis_bucket) bounds how many executables a daemon
# compiles; the cache lets a restarted process load them instead.

from kubernetes_tpu.ops.matrices import DeviceSnapshot, device_snapshot
from kubernetes_tpu.ops.pipeline import solve_backlog_pipelined
from kubernetes_tpu.ops.preemption import (
    PreemptionDecision,
    build_preemption_problem,
    solve_preemption_device,
)
from kubernetes_tpu.ops.solver import solve, solve_assignments, solve_with_state
from kubernetes_tpu.ops.incremental import (
    RebuildRequired,
    SessionGang,
    SolverSession,
)
from kubernetes_tpu.ops.wave import solve_waves

__all__ = [
    "DeviceSnapshot",
    "PreemptionDecision",
    "RebuildRequired",
    "SessionGang",
    "SolverSession",
    "build_preemption_problem",
    "device_snapshot",
    "solve",
    "solve_assignments",
    "solve_backlog_pipelined",
    "solve_preemption_device",
    "solve_waves",
    "solve_with_state",
]
