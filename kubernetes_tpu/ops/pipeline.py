"""Pipelined backlog solve: overlap host lowering + upload with the
device scan.

The sequential-parity scan (ops.solver) is latency-bound on device, and
the host work around it (columnar lowering, host->device transfer,
readback) would otherwise serialize with it. This module chunks the
pending backlog and chains the solver's DONATED node carry across
chunks: while the device scans chunk k, the (single-core) host lowers
and stages chunk k+1 — JAX dispatch is async, so the Python thread is
free the moment a chunk's solve is enqueued.

Decisions are bit-identical to the monolithic solve: chunking changes
WHEN pod rows reach the device, never the order they are scanned or the
carry they see. (Parity with the scalar oracle is therefore inherited
from ops.solver; tests/test_solver_parity.py checks both.)

There is no reference analog to cite — the reference schedules one pod
per HTTP round-trip (plugin/pkg/scheduler/scheduler.go:113-158); this
pipeline is the TPU-native replacement for that loop's concurrency.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from kubernetes_tpu.models.columnar import SnapshotBuilder
from kubernetes_tpu.models.objects import Node, Pod, Service
from kubernetes_tpu.ops.matrices import (
    device_nodes,
    device_pods,
    node_axis_multiple,
    pow2_bucket,
    shardings_for,
)
from kubernetes_tpu.ops.solver import DEFAULT_WEIGHTS, solve_with_state
from kubernetes_tpu.utils import sanitizer, sli, tracing

# Measured on v5e-1 at 50k x 5k with the pallas scan kernel: 12544
# (4 chunks) walls 0.61-0.66s vs 0.88-0.96s at 8192 and 0.71-0.76s at
# 25088 — scan chunk boundaries are free (bit-identical carry
# chaining), so the trade is purely per-chunk dispatch overhead vs
# critical-path first-chunk lowering. Wave mode keeps its own sweet
# spot (25088, set by bench.py): its boundaries DO cost partial waves,
# which is also why a progressive small-first-chunk ramp was tried and
# LOST for wave.
DEFAULT_CHUNK = 12544


def gang_member_counts_device(
    placed, group_ids, num_groups: int
) -> np.ndarray:
    """Device path of the gang-acceptance reduction: stage the host
    placed-mask + group-id columns, run the masked segment reduction
    (ops.matrices.gang_member_counts), and return host counts. Both
    axes pad to power-of-two buckets (pods with placed=False/id=-1 —
    masked out by construction): num_groups is a static jit arg and the
    pod length is a traced shape, so per-batch drift in either must not
    trigger an XLA recompile."""
    import jax.numpy as jnp

    from kubernetes_tpu.ops.matrices import gang_member_counts

    G = int(num_groups)
    if G <= 0:
        return np.zeros(0, np.int32)
    placed = np.asarray(placed, bool)
    gids = np.asarray(group_ids, np.int32)
    P = placed.shape[0]
    PP = pow2_bucket(max(P, 1), minimum=8)
    if PP != P:
        placed = np.pad(placed, (0, PP - P))
        gids = np.pad(gids, (0, PP - P), constant_values=-1)
    GP = pow2_bucket(G, minimum=8)
    counts = gang_member_counts(
        jnp.asarray(placed), jnp.asarray(gids), num_groups=GP
    )
    return np.asarray(counts)[:G]


def solve_backlog_pipelined(
    pending: Sequence[Pod],
    nodes: Sequence[Node],
    assigned: Sequence[Pod] = (),
    services: Sequence[Service] = (),
    mesh=None,
    chunk: int = DEFAULT_CHUNK,
    weights=DEFAULT_WEIGHTS,
    mode: str = "scan",
) -> List[Optional[str]]:
    """Schedule the backlog; returns node names (None = unschedulable).

    mode="scan" (default) is bit-identical to schedule_backlog_tpu —
    the sequential-parity path. mode="wave"/"sinkhorn" runs the
    windowed batch solvers chunk-by-chunk over the SAME donated carry:
    chunk k+1's host lowering and upload overlap chunk k's device
    waves, so the end-to-end wall approaches the device-only wave
    time. Decisions are the approximate wave family's (quality gated
    by regret bounds in tests/test_quality_regression.py, published by
    bench.py), but every capacity/port/volume invariant still holds —
    the wave commit path enforces the same feasibility the scan does.
    Chunking never loosens quality vs a monolithic wave solve: chunks
    commit in backlog order, so a chunk's pods see strictly MORE
    committed state than the same pods in one big window ever would.
    """
    # jit dispatch + the final blocking readback must never run under a
    # sanitized lock (ktsan blocking-under-lock check; a multi-second
    # first-bucket compile under the apiserver or store lock would
    # freeze the control plane).
    sanitizer.check_blocking("jit-dispatch", "solve_backlog_pipelined")
    # Phase spans wrap whole host-side segments, never per-pod work —
    # their cost is a few monotonic reads per CHUNK. JAX dispatch is
    # async, so per-chunk "solve" measures dispatch; the device time
    # drains into the final blocking "readback".
    with tracing.phase("lower", pods=len(pending)):
        builder = SnapshotBuilder(pending, nodes, assigned, services)
        node_sharding, pod_sharding = shardings_for(mesh)
    with tracing.phase("upload"):
        # h2d transfer SLI is counted once, inside matrices._put_tree
        # (which device_nodes/device_pods funnel through) — counting
        # the host columns here too would double the metric.
        carry = device_nodes(
            builder.node_columns(), node_sharding,
            node_mult=node_axis_multiple(mesh),
        )
    # Convergence telemetry per chunk (device scalars — converted to
    # host ints only at the blocking readback, so the async overlap
    # never stalls on a telemetry copy).
    tele: List[Tuple] = []
    if mode == "scan":
        step = lambda dpods, carry: solve_with_state(dpods, carry, weights)
    elif mode == "wave":
        from kubernetes_tpu.ops.wave import solve_waves_with_state

        def step(dpods, carry):
            a, c, w = solve_waves_with_state(dpods, carry, weights)
            tele.append((w, None, None))
            return a, c
    elif mode == "sinkhorn":
        from kubernetes_tpu.ops.sinkhorn import solve_sinkhorn_with_state

        def step(dpods, carry):
            a, c, w, it, res = solve_sinkhorn_with_state(
                dpods, carry, weights
            )
            tele.append((w, it, res))
            return a, c
    else:
        raise ValueError(f"unknown pipeline mode {mode!r}")
    P = len(builder.pending)
    outs = []
    for ci, start in enumerate(range(0, max(P, 1), chunk)):
        with tracing.phase("lower", chunk=ci):
            cols = builder.pod_columns(start, min(start + chunk, P))
        # Full chunks share one executable; the (smaller) tail chunk
        # pads to its own 128 bucket rather than a full chunk, so small
        # backlogs and tails don't scan thousands of padding steps.
        with tracing.phase("upload", chunk=ci):
            dpods = device_pods(cols, pod_sharding)
        with tracing.phase("solve", chunk=ci):
            assignment, carry = step(dpods, carry)
            # Start this chunk's device->host copy NOW: it rides behind
            # the next chunk's device work instead of serializing at the
            # end (the final np.asarray finds the bytes already local).
            if hasattr(assignment, "copy_to_host_async"):
                assignment.copy_to_host_async()
        outs.append((assignment, cols.count))

    with tracing.phase("readback"):
        names = [n.metadata.name for n in builder.nodes]
        result: List[Optional[str]] = []
        n_nodes = len(builder.nodes)
        d2h = 0
        for assignment, count in outs:
            full = np.asarray(assignment)
            d2h += full.nbytes
            picks = full[:count]
            for j in picks.tolist():
                result.append(names[j] if 0 <= j < n_nodes else None)
        sli.note_transfer("d2h", d2h)
        if tele:
            from kubernetes_tpu.utils import flightrecorder

            waves = sum(int(w) for w, _, _ in tele)
            if mode == "sinkhorn":
                flightrecorder.observe_solve_telemetry(
                    "sinkhorn",
                    sum(int(it) for _, it, _ in tele),
                    residual=float(tele[-1][2]),
                    waves=waves,
                )
            else:
                flightrecorder.observe_solve_telemetry("wave", waves)
        return result


# -- explain readback ---------------------------------------------------


def explain_matrix(
    pending: Sequence[Pod],
    nodes: Sequence[Node],
    assigned: Sequence[Pod] = (),
    services: Sequence[Service] = (),
    mesh=None,
):
    """Raw explain readback for a backlog against one FIXED cluster
    state (`assigned` pods charge occupancy; `pending` pods commit
    nothing — every row sees the same state). Returns (node_names,
    bits u32[P, N], components dict of i32[P, N]): bit i of bits[p, n]
    set means matrices.EXPLAIN_PREDICATES[i] rejected node n for pod
    p; bits == 0 is feasibility under the default pipeline. One kernel
    dispatch + one readback — never on the solve path (the daemons run
    it inside its own "explain" phase)."""
    from kubernetes_tpu.models.columnar import build_snapshot
    from kubernetes_tpu.ops.matrices import device_snapshot
    from kubernetes_tpu.ops.solver import explain_rows

    snap = build_snapshot(
        pending, nodes, assigned_pods=assigned, services=services
    )
    dsnap = device_snapshot(snap, mesh=mesh)
    bits, lr, bra, spread = explain_rows(dsnap.pods, dsnap.nodes)
    P, N = dsnap.n_pods, dsnap.n_nodes
    return (
        snap.nodes.names,
        np.asarray(bits)[:P, :N],
        {
            "leastRequested": np.asarray(lr)[:P, :N],
            "balanced": np.asarray(bra)[:P, :N],
            "spreading": np.asarray(spread)[:P, :N],
        },
    )


def explain_backlog(
    pending: Sequence[Pod],
    nodes: Sequence[Node],
    assigned: Sequence[Pod] = (),
    services: Sequence[Service] = (),
    mesh=None,
    top_k: int = 3,
    max_failed: int = 16,
) -> List[dict]:
    """Bounded per-pod explain verdicts — the flight recorder's shape.
    For each pending pod (aligned with the input): the top_k feasible
    nodes ranked by total default-priority score (lowest index wins
    ties, the solver's tie-break) with the score decomposition, up to
    max_failed individually-listed infeasible nodes, and aggregate
    failed-predicate counts over ALL nodes — a 5k-node cluster folds
    into a handful of reason counts, not 5k rows."""
    pending = list(pending)
    if not pending:
        return []
    return explain_entries(
        pending,
        *explain_matrix(pending, nodes, assigned, services, mesh=mesh),
        top_k=top_k, max_failed=max_failed,
    )


def explain_entries(
    pending: Sequence[Pod],
    names: Sequence[str],
    bits: np.ndarray,
    comps: Dict[str, np.ndarray],
    top_k: int = 3,
    max_failed: int = 16,
) -> List[dict]:
    """explain_backlog's verdicts from an explain readback already
    taken (``explain_matrix``, or a SolverSession's ``explain``)."""
    from kubernetes_tpu.models.objects import pod_full_key
    from kubernetes_tpu.ops.matrices import (
        EXPLAIN_PREDICATES,
        decode_predicate_bits,
    )

    total = (
        comps["leastRequested"] + comps["balanced"] + comps["spreading"]
    )
    out: List[dict] = []
    n_nodes = len(names)
    for i, pod in enumerate(pending):
        row = bits[i]
        feasible = np.flatnonzero(row == 0)
        entry_nodes: List[dict] = []
        # Feasible candidates: score desc, node index asc on ties
        # (argsort is stable, so sorting by -score preserves index
        # order inside a score band — the scan's argmax tie-break).
        for j in feasible[np.argsort(-total[i][feasible], kind="stable")][
            :top_k
        ].tolist():
            entry_nodes.append(
                {
                    "node": names[j],
                    "ok": True,
                    "score": int(total[i, j]),
                    "components": {
                        k: int(v[i, j]) for k, v in comps.items()
                    },
                }
            )
        # Aggregate counts vectorized (one popcount per predicate bit,
        # not a Python loop over 5k nodes); only the max_failed nodes
        # listed individually pay per-node decoding.
        reason_counts: Dict[str, int] = {}
        for b, name in enumerate(EXPLAIN_PREDICATES):
            c = int(((row >> np.uint32(b)) & 1).sum())
            if c:
                reason_counts[name] = c
        for j in np.flatnonzero(row != 0)[:max_failed].tolist():
            entry_nodes.append(
                {
                    "node": names[j],
                    "ok": False,
                    "reasons": decode_predicate_bits(int(row[j])),
                }
            )
        out.append(
            {
                "pod": pod_full_key(pod),
                "feasibleNodes": int(len(feasible)),
                "totalNodes": n_nodes,
                "nodes": entry_nodes,
                "reasonCounts": reason_counts,
            }
        )
    return out
