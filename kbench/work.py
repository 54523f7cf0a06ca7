"""Work and bytes the scheduling problem needs, and the chip's peaks.

These count the *problem*, from the cell's pods and real nodes: never
padded slots, and never what a compiler's cost analysis sees of one
implementation. So another kernel, another tiling or another solver
reads the same work.
"""

from __future__ import annotations

#: Published peaks by ``device_kind``. Source: Google Cloud, "TPU v5e"
#: (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 819 GB/s HBM.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9},
}

#: Operations to judge one pod against one node under the default
#: provider:
#: - predicates: pod count (add, compare), cpu and memory fit (2 x add,
#:   compare), selector (and, compare per label word), host ports (and,
#:   test per port word), node ready (and), combining (5 ands) = 16
#:   with one word each;
#: - LeastRequested: per resource add, subtract, multiply, divide,
#:   compare, select (12), average (add, shift) = 14;
#: - BalancedResourceAllocation: two divides, subtract, abs, multiply,
#:   subtract, truncate, two compares, select = 10;
#: - ServiceSpreading: subtract, multiply, divide, select = 4 (the
#:   service's maximum is one reduction step per node);
#: - total, mask and arg-max: 2 adds, select, compare, select = 5.
OPS_PER_PAIR = 16 + 14 + 10 + 4 + 1 + 5

#: Bytes of one pod's columns in (cpu, memory, selector word, port
#: word, service id, pinned node: 4 bytes each) and its assignment out.
POD_BYTES = 6 * 4 + 4
#: Bytes of one node's state in and out per tick: capacity and use of
#: cpu, memory and pods (6), label and port words (2), readiness (1),
#: 4 bytes each, read and written back (x2 for the used half: 3).
NODE_BYTES = (6 + 2 + 1 + 3) * 4
#: Bytes of one node's count for one service (read, and written for
#: the services that gain a pod).
SVC_COUNT_BYTES = 2 * 4


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


def solve_work(pods: float, ticks: float, nodes: int, services: int) -> tuple:
    """(operations, bytes) of ``ticks`` solves placing ``pods`` pods
    in all onto ``nodes`` nodes with ``services`` services: every pod
    judged against every node; each tick reads and writes the node
    state once and the counts of the services its pods belong to
    (at most one per pod)."""
    ops = pods * nodes * OPS_PER_PAIR
    per_tick_svcs = min(services, pods / ticks) if ticks else 0
    nbytes = (
        pods * POD_BYTES
        + ticks * nodes * (NODE_BYTES + per_tick_svcs * SVC_COUNT_BYTES)
    )
    return ops, nbytes


def least_time(ops: float, nbytes: float, device_kind: str) -> tuple:
    """(seconds, bound) where bound is "compute" or "memory"."""
    pk = peaks(device_kind)
    t_ops, t_bytes = ops / pk["flops"], nbytes / pk["bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
