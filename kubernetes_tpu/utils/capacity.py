"""Capacity & fragmentation observability plane — the host half.

The dense pass lives in ops/capacity.py (jitted, KT006 twin, ktshape
contract); this module owns everything around it: the probe-shape set
(configured slice shapes + the backlog's observed shape quantiles),
the always-on metric series, the fragmentation trend ring, and the
``/debug/capacity`` snapshot. It must stay importable by a pure
control-plane process — jax is only imported inside :meth:`sample`
(the scheduler daemons are the only callers), exactly like
utils/profiler.py splits from ops/ledger.py.

Series (KT005 family ``CAPACITY_METRICS`` + standard suffixes):

- ``cluster_fragmentation_score`` — histogram of the kernel's
  capacity-weighted stranded fraction per sample ([0, 1] ratio bucket
  ladder, like the duty-cycle series) so the SLO engine can quantile
  it.
- ``node_utilization_ratio{resource}`` — histogram over LIVE nodes'
  charged/capacity ratios (cpu/mem/pods). Refreshed at most once per
  ``UTIL_REFRESH_S`` — it is O(nodes) python observes, and per-node
  distribution drift is a dashboard signal, not a per-tick one.
- ``cluster_headroom_pods{shape}`` — gauge: pods of each probe shape
  that still fit.
- ``slice_alloc_success_rate`` — histogram of the per-sample fraction
  of live probes whose gang bound clears minMember.
- ``scheduler_backlog_pressure`` — gauge: pending depth x oldest
  unbound pod age (seconds), from the FIFO depth and the SLI
  lifecycle collector's age watermark.
- ``capacity_zero_headroom_ticks_total`` — counter of samples where
  the backlog was non-empty while some live probe had ZERO headroom
  (capacity starvation: pods waiting that no reshuffling can place) —
  the SLO engine's zero-headroom burn objective reads it.

Sampling cadence: the scheduler daemons call :func:`sample_session` /
:func:`sample_cluster` once per resolved micro-tick inside their
``capacity`` phase span, plus an idle-tick refresh throttled to
``daemon.CAPACITY_IDLE_REFRESH_S`` (PR 9 staleness rule: telemetry
keeps moving on an idle cluster). See docs/architecture.md "Capacity
& fragmentation".
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from kubernetes_tpu.utils import metrics
from kubernetes_tpu.utils.profiler import RATIO_BUCKETS

FRAG_SCORE = metrics.DEFAULT.histogram(
    "cluster_fragmentation_score",
    "Capacity-weighted stranded fraction of aggregate free capacity "
    "across the probe-shape set (0 = perfectly packable, 1 = every "
    "free byte stranded)",
    buckets=RATIO_BUCKETS,
)
NODE_UTIL = metrics.DEFAULT.histogram(
    "node_utilization_ratio",
    "Per-live-node charged/capacity ratio, one observation per node "
    "per refresh",
    labels=("resource",),
    buckets=RATIO_BUCKETS,
)
HEADROOM = metrics.DEFAULT.gauge(
    "cluster_headroom_pods",
    "Pods of each probe shape that still fit cluster-wide (greedy "
    "per-node integral fit, mask-reduced over live nodes)",
    labels=("shape",),
)
SLICE_ALLOC = metrics.DEFAULT.histogram(
    "slice_alloc_success_rate",
    "Per-sample fraction of live probe shapes whose all-or-nothing "
    "gang bound (headroom >= minMember) is satisfiable right now",
    buckets=RATIO_BUCKETS,
)
BACKLOG_PRESSURE = metrics.DEFAULT.gauge(
    "scheduler_backlog_pressure",
    "Pending-backlog pressure watermark: FIFO depth x oldest unbound "
    "pod age in seconds (0 on an idle cluster)",
)
ZERO_HEADROOM = metrics.DEFAULT.counter(
    "capacity_zero_headroom_ticks_total",
    "Capacity samples taken while the backlog was non-empty and some "
    "live probe shape had zero cluster-wide headroom",
)

#: Default slice probes (cpu milli, mem MiB, minMember). Deliberately
#: spans a single small pod, a mid gang, and an 8-member accelerator
#: slice shape; operators tune via configure().
DEFAULT_SLICE_SHAPES: Tuple[Tuple[str, float, float, int], ...] = (
    ("slice-1x250m", 250.0, 256.0, 1),
    ("slice-4x500m", 500.0, 512.0, 4),
    ("slice-8x2000m", 2000.0, 2048.0, 8),
)

#: Seconds between O(nodes) utilization-histogram refreshes.
UTIL_REFRESH_S = 1.0

#: Fragmentation trend ring length (/debug/capacity's sparkline feed).
TREND_LEN = 120

#: Stranded-node table size in the snapshot.
TOP_K_STRANDED = 8

#: Backlog shapes remembered for the quantile probes.
SHAPE_WINDOW = 512


def _pow2(n: int, minimum: int) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


class CapacityMonitor:
    """Process-global capacity sampler: owns probe assembly, the dense
    kernel call, metric feeding, and the snapshot served by
    ``GET /debug/capacity``. Thread-safe; sampling never raises (the
    daemons call it on the hot tick path)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._slice_shapes = DEFAULT_SLICE_SHAPES
        self._recent_shapes: deque = deque(maxlen=SHAPE_WINDOW)
        self._trend: deque = deque(maxlen=TREND_LEN)
        self.samples = 0
        self._last_util_mono = 0.0
        self._last = None  # latest snapshot body (dict) or None

    # -- configuration ---------------------------------------------------

    def configure(
        self, slice_shapes: Sequence[Tuple[str, float, float, int]]
    ) -> None:
        """Replace the configured slice probes: (name, cpu milli,
        mem MiB, minMember) tuples."""
        with self._lock:
            self._slice_shapes = tuple(
                (str(n), float(c), float(m), int(k))
                for n, c, m, k in slice_shapes
            )

    def reset(self) -> None:
        with self._lock:
            self._slice_shapes = DEFAULT_SLICE_SHAPES
            self._recent_shapes.clear()
            self._trend.clear()
            self.samples = 0
            self._last_util_mono = 0.0
            self._last = None

    def warm(self, n_nodes: int = 0) -> None:
        """Pre-compile the kernel for the shape buckets a live daemon
        will hit: the node count's pow2 lattice row, crossed with the
        probe-count bucket both before and after the three
        backlog-quantile probes join. The cold XLA compile is ~1.5s;
        daemons kick this onto a background thread at start so it
        never lands in-band on a solve tick (and never GIL-starves
        the commit worker's decision-sink announce)."""
        try:
            from kubernetes_tpu.ops.capacity import capacity_report

            npad = _pow2(max(int(n_nodes), 1), 128)
            with self._lock:
                q = len(self._slice_shapes)
            for qp in sorted({_pow2(max(q, 1), 4), _pow2(q + 3, 4)}):
                out = capacity_report(
                    *(np.zeros(npad, np.float32) for _ in range(6)),
                    np.zeros(npad, bool),
                    np.zeros(npad, bool),
                    np.zeros(qp, np.float32),
                    np.zeros(qp, np.float32),
                    np.ones(qp, np.int32),
                    np.zeros(qp, bool),
                )
                np.asarray(out[-1])  # block until compiled
        except Exception:
            pass

    # -- probe assembly ---------------------------------------------------

    def note_backlog_shapes(
        self, shapes: Sequence[Tuple[float, float]]
    ) -> None:
        """Record observed pending-pod shapes (cpu milli, mem MiB) —
        the backlog-quantile probes are drawn from this window."""
        with self._lock:
            self._recent_shapes.extend(
                (float(c), float(m)) for c, m in shapes
            )

    def probe_set(self) -> List[Tuple[str, float, float, int]]:
        """Configured slice shapes + backlog shape quantiles (p50/p90/
        max over the recent-shape window, requests ceil'd so the
        columns stay integral)."""
        with self._lock:
            probes = list(self._slice_shapes)
            shapes = list(self._recent_shapes)
        if shapes:
            arr = np.asarray(shapes, dtype=np.float64)
            # One percentile call for every quantile of both columns.
            qs = np.ceil(np.percentile(arr, (50.0, 90.0, 100.0), axis=0))
            for tag, (cpu, mem) in zip(("p50", "p90", "max"), qs):
                probes.append((f"backlog-{tag}", float(cpu), float(mem), 1))
        return probes

    # -- sampling ----------------------------------------------------------

    def sample(
        self,
        cols: Dict[str, np.ndarray],
        node_names: Sequence[Optional[str]],
        backlog_depth: int = 0,
        oldest_age_s: float = 0.0,
    ) -> Optional[dict]:
        """One capacity sample over NODE_SCHEMA-style occupancy columns
        (cpu_cap/mem_cap/pods_cap/cpu_fit/mem_fit/pods_used f32[N],
        over/sched b8[N]; padding rows carry sched=False). Returns the
        snapshot body, or None if the kernel path failed — it NEVER
        raises (telemetry must not take down a tick)."""
        try:
            return self._sample(
                cols, node_names, int(backlog_depth), float(oldest_age_s)
            )
        except Exception:
            return None

    def _sample(self, cols, node_names, backlog_depth, oldest_age_s):
        from kubernetes_tpu.ops.capacity import capacity_report

        probes = self.probe_set()
        q = len(probes)
        qp = _pow2(max(q, 1), 4)
        probe_cpu = np.zeros(qp, np.float32)
        probe_mem = np.zeros(qp, np.float32)
        probe_min = np.ones(qp, np.int32)
        probe_live = np.zeros(qp, bool)
        for i, (_name, cpu, mem, minm) in enumerate(probes):
            probe_cpu[i] = cpu
            probe_mem[i] = mem
            probe_min[i] = max(int(minm), 1)
            probe_live[i] = True

        n = int(np.asarray(cols["cpu_cap"]).shape[0])
        npad = _pow2(max(n, 1), 128)

        def col(name, dtype):
            a = np.asarray(cols[name]).astype(dtype, copy=False)
            if a.shape[0] != npad:
                a = np.pad(a, (0, npad - a.shape[0]))
            return a

        args = (
            col("cpu_cap", np.float32),
            col("mem_cap", np.float32),
            col("pods_cap", np.float32),
            col("cpu_fit", np.float32),
            col("mem_fit", np.float32),
            col("pods_used", np.float32),
            col("over", bool),
            col("sched", bool),
            probe_cpu,
            probe_mem,
            probe_min,
            probe_live,
        )
        (
            util_cpu,
            util_mem,
            util_pods,
            fit_int,
            headroom,
            frag,
            slice_ok,
            stranded,
            frag_score,
            stranded_cpu,
            stranded_mem,
        ) = (np.asarray(x) for x in capacity_report(*args))

        now = time.monotonic()
        live = args[7][:npad] & ~args[6][:npad]
        live_idx = np.flatnonzero(live)
        score = float(frag_score)
        pressure = float(backlog_depth) * max(float(oldest_age_s), 0.0)

        # Probe table + headroom gauges.
        table = []
        n_ok = 0
        zero_headroom = False
        for i, (name, cpu, mem, minm) in enumerate(probes):
            h = int(headroom[i])
            ok = bool(slice_ok[i])
            n_ok += 1 if ok else 0
            zero_headroom = zero_headroom or h == 0
            HEADROOM.set(float(h), shape=name)
            table.append(
                {
                    "shape": name,
                    "cpu_milli": float(cpu),
                    "mem_mib": float(mem),
                    "min_member": int(minm),
                    "headroom_pods": h,
                    "fragmentation": round(float(frag[i]), 6),
                    "allocatable": ok,
                }
            )
        alloc_rate = (n_ok / q) if q else 0.0

        # Stranded top-k by leftover cpu.
        free_cpu = np.maximum(args[0] - args[3], 0.0) * live
        free_mem = np.maximum(args[1] - args[4], 0.0) * live
        stranded_idx = np.flatnonzero(stranded)
        order = stranded_idx[np.argsort(-free_cpu[stranded_idx])]
        top = []
        for j in order[:TOP_K_STRANDED]:
            name = (
                node_names[j]
                if j < len(node_names) and node_names[j] is not None
                else f"node[{j}]"
            )
            top.append(
                {
                    "node": str(name),
                    "free_cpu_milli": float(free_cpu[j]),
                    "free_mem_mib": float(free_mem[j]),
                }
            )

        # Series: always-on scalars every sample; the O(nodes)
        # utilization histogram at most once per UTIL_REFRESH_S.
        FRAG_SCORE.observe(score)
        SLICE_ALLOC.observe(alloc_rate)
        BACKLOG_PRESSURE.set(pressure)
        if backlog_depth > 0 and zero_headroom:
            ZERO_HEADROOM.inc()
        with self._lock:
            refresh_util = (
                now - self._last_util_mono >= UTIL_REFRESH_S
            )
            if refresh_util:
                self._last_util_mono = now
        if refresh_util:
            for resource, ratios in (
                ("cpu", util_cpu),
                ("mem", util_mem),
                ("pods", util_pods),
            ):
                for v in ratios[live_idx]:
                    NODE_UTIL.observe(float(v), resource=resource)

        def util_summary(ratios):
            vals = ratios[live_idx]
            if not len(vals):
                return {"mean": 0.0, "p50": 0.0, "p99": 0.0}
            p50, p99 = np.percentile(vals, (50, 99))
            return {
                "mean": round(float(vals.mean()), 6),
                "p50": round(float(p50), 6),
                "p99": round(float(p99), 6),
            }

        node_util = {}
        for j in live_idx:
            name = (
                node_names[j]
                if j < len(node_names) and node_names[j] is not None
                else None
            )
            if name is not None:
                node_util[str(name)] = [
                    round(float(util_cpu[j]), 4),
                    round(float(util_mem[j]), 4),
                    round(float(util_pods[j]), 4),
                ]

        body = {
            "kind": "CapacityReport",
            "sampled": True,
            "fragmentation_score": round(score, 6),
            "slice_alloc_success_rate": round(alloc_rate, 6),
            "stranded_cpu_fraction": round(float(stranded_cpu), 6),
            "stranded_mem_fraction": round(float(stranded_mem), 6),
            "stranded_nodes": top,
            "stranded_node_count": int(len(stranded_idx)),
            "live_nodes": int(len(live_idx)),
            "probes": table,
            "utilization": {
                "cpu": util_summary(util_cpu),
                "mem": util_summary(util_mem),
                "pods": util_summary(util_pods),
            },
            "node_utilization": node_util,
            "backlog": {
                "depth": int(backlog_depth),
                "oldest_age_s": round(max(float(oldest_age_s), 0.0), 3),
                "pressure": round(pressure, 3),
            },
        }
        with self._lock:
            self.samples += 1
            self._trend.append(round(score, 6))
            body["samples"] = self.samples
            body["trend"] = list(self._trend)
            self._last = body
        return body

    # -- surfaces ----------------------------------------------------------

    def snapshot(self) -> dict:
        """The ``/debug/capacity`` body. ``sampled: false`` on a cold
        cluster — the ktctl miss contract keys on it."""
        with self._lock:
            if self._last is None:
                return {
                    "kind": "CapacityReport",
                    "sampled": False,
                    "samples": 0,
                    "probes": [],
                    "stranded_nodes": [],
                    "trend": [],
                }
            return dict(self._last)


def session_columns(session) -> Tuple[Dict[str, np.ndarray], List]:
    """Occupancy columns straight off a SolverSession's host mirror —
    the already-staged matrices (``session.h`` is the device carry's
    numpy twin, kept in sync by the same scatter updates)."""
    h = session.h
    cols = {
        "cpu_cap": h["cpu_cap"],
        "mem_cap": h["mem_cap"],
        "pods_cap": h["pods_cap"],
        "cpu_fit": h["cpu_fit"],
        "mem_fit": h["mem_fit"],
        "pods_used": h["pods_used"],
        "over": h["over"],
        "sched": h["sched"],
    }
    return cols, list(session.node_names)


def cluster_columns(nodes, assigned) -> Tuple[Dict[str, np.ndarray], List]:
    """Occupancy columns from watch-cache object lists (the plain
    BatchScheduler path, which keeps no session). Terminal-phase
    (Succeeded/Failed) and Terminating pods are EXCLUDED — their
    capacity is free or about to be (filterNonRunningPods semantics,
    same rule the snapshot/session staging applies)."""
    from kubernetes_tpu import native
    from kubernetes_tpu.models.columnar import (
        MIB,
        RESOURCE_CPU,
        RESOURCE_MEMORY,
        RESOURCE_PODS,
        mem_to_mib_ceil,
        node_is_ready,
        pod_resource_limits,
    )
    from kubernetes_tpu.models.objects import pod_is_terminating

    names = [n.metadata.name for n in nodes]
    index = {name: j for j, name in enumerate(names)}
    n = len(nodes)
    cpu_cap = np.zeros(n, np.float32)
    mem_cap = np.zeros(n, np.float32)
    pods_cap = np.zeros(n, np.float32)
    sched = np.zeros(n, bool)
    for j, node in enumerate(nodes):
        cap = node.status.capacity or {}
        if RESOURCE_CPU in cap:
            cpu_cap[j] = cap[RESOURCE_CPU].milli_value()
        if RESOURCE_MEMORY in cap:
            mem_cap[j] = cap[RESOURCE_MEMORY].value() // MIB
        if RESOURCE_PODS in cap:
            pods_cap[j] = cap[RESOURCE_PODS].value()
        sched[j] = node_is_ready(node)

    occupants = [
        p
        for p in assigned
        if p.spec.node_name
        and p.status.phase not in ("Succeeded", "Failed")
        and not pod_is_terminating(p)
    ]
    a = len(occupants)
    a_idx = np.full(a, -1, np.int32)
    a_cpu = np.zeros(a, np.float32)
    a_mem = np.zeros(a, np.float32)
    for i, p in enumerate(occupants):
        j = index.get(p.spec.node_name)
        a_idx[i] = -1 if j is None else j
        cpu, mem = pod_resource_limits(p)
        a_cpu[i] = cpu
        a_mem[i] = mem_to_mib_ceil(mem)
    cpu_fit = np.zeros(n, np.float32)
    mem_fit = np.zeros(n, np.float32)
    over = np.zeros(n, bool)
    cpu_used = np.zeros(n, np.float32)
    mem_used = np.zeros(n, np.float32)
    pods_used = np.zeros(n, np.float32)
    native.greedy_fit(
        a_idx, a_cpu, a_mem, cpu_cap, mem_cap,
        cpu_fit, mem_fit, over, cpu_used, mem_used, pods_used,
    )
    cols = {
        "cpu_cap": cpu_cap,
        "mem_cap": mem_cap,
        "pods_cap": pods_cap,
        "cpu_fit": cpu_fit,
        "mem_fit": mem_fit,
        "pods_used": pods_used,
        "over": over,
        "sched": sched,
    }
    return cols, names


DEFAULT = CapacityMonitor()
