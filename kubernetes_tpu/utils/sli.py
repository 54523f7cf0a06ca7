"""Cluster SLI telemetry plane: pod-lifecycle watermarks + fan-out lag
+ device telemetry.

The reference gates cluster health on *measured service levels*: pod
startup latency observed through watch events (test/e2e/density.go
computes create -> Running watermarks from a watch, never by polling)
and the HighLatencyRequests apiserver gate (test/e2e/util.go:1286,
mirrored in server/httpserver.py). This module is the production-side
equivalent — always-on collectors that turn the event streams the
system already emits into scrapeable SLI series, so the SLO engine
(utils/slo.py), bench.py, and ``ktctl slo`` all read one truth.

Three collector families live here:

- **Lifecycle SLIs** (``LifecycleSLICollector``): one subscriber on the
  kvstore's event dispatcher (the same feed the PR-6 watch cache rides
  — ``KVStore.subscribe``; zero polling, zero extra copies) turns pod
  events into milestone watermarks exported as the
  ``pod_startup_latency_seconds{milestone}`` histogram:

    created   ADDED event for an unbound pod (the track's t0)
    decision  the scheduling flight recorder logged a Decision for the
              pod (PR-5 join: flightrecorder.record() notifies sinks)
    bound     first MODIFIED carrying spec.nodeName — "binding visible
              to a watch client", density.go's definition
    running   first MODIFIED carrying status.phase == Running (the
              kubelet's status write becoming watch-visible)

  Tracks are bounded (``MAX_TRACKED``, oldest evicted) and drain on
  the running milestone or DELETED, so a long-lived daemon never
  accumulates state for pods that will not progress.

- **Watch fan-out lag**: ``observe_watch_lag`` records how many store
  versions a watch connection's delivered burst trails the watch
  cache's applied watermark by (``watch_fanout_lag_versions``); the
  slow-consumer drop counter and per-resource queue-depth gauge live
  next to the drop site in store/watch.py. ``observe_informer_staleness``
  is the consumer-side mirror: seconds since each scheduler informer
  last processed a delta.

- **Device/solver telemetry**: host<->device transfer bytes
  (``note_transfer``, fed by ops/pipeline.py and ops/incremental.py
  from the staged buffer sizes), the XLA compile-cache sentinel the
  PR-7 recompilation test watches (``_solve_xla._cache_size()``)
  promoted to a gauge + compile counter, and live device-memory
  gauges — all sampled per solve tick by the batch daemons
  (``observe_device_telemetry``), next to the existing
  ``scheduler_phase_seconds`` histograms.

Everything here is host-side bookkeeping measured in microseconds per
event; tests/test_sli.py pins the collector + per-tick telemetry at
<5% of the bulk-churn drill's per-pod budget so it can stay always-on.

Scope note (same as the flight recorder's): the collector is
per-process and its tracks live where the STORE lives. In the
in-process cluster topology (tests, LocalCluster, local-up) the
scheduler daemons share that process, so the flight-recorder decision
sink finds the tracks and the ``decision`` milestone lands. A batch
daemon deployed in its OWN process against a remote apiserver records
decisions locally — bound/running milestones still land apiserver-side
via store events, but ``pod_decision_latency`` reads no_data there
(joining it across processes needs decision events on the API, a
follow-up).
"""

from __future__ import annotations

import logging
import time
from typing import Dict, List, Optional

from kubernetes_tpu.utils import flightrecorder, metrics, sanitizer

_LOG = logging.getLogger("kubernetes_tpu.sli")

#: Store key prefix of the pod resource (registry.ResourceInfo.prefix
#: shape) — the collector filters the firehose on it first thing.
POD_PREFIX = "/registry/pods/"
_PREFIX_LEN = len(POD_PREFIX)

#: Pod lifecycle milestone watermarks, measured from the watch-visible
#: ADDED event (density.go's pod-startup measurement, as an always-on
#: histogram instead of a bench-private loop).
STARTUP_LATENCY = metrics.DEFAULT.histogram(
    "pod_startup_latency_seconds",
    "Pod lifecycle milestone latency from watch-visible creation "
    "(milestone: decision | bound | running)",
    ("milestone",),
)

#: Store versions a watch connection's delivered burst trails the
#: watch cache's applied watermark by (0 = the consumer is current).
#: Buckets are powers of two — version counts, not seconds.
WATCH_LAG = metrics.DEFAULT.histogram(
    "watch_fanout_lag_versions",
    "Store versions a watch delivery trails the applied watermark by",
    ("resource",),
    buckets=(0, 1, 2, 4, 8, 16, 32, 64, 128, 256, 1024, 4096, 16384, 65536),
)

#: Seconds since a scheduler informer last processed a delta or relist
#: (set per solve tick). Large values under churn mean the daemon is
#: deciding on a stale cluster view.
INFORMER_STALENESS = metrics.DEFAULT.gauge(
    "scheduler_informer_staleness_seconds",
    "Seconds since the scheduler informer last processed a delta",
    ("resource",),
)

#: Host<->device transfer volume of the solve pipelines, from the
#: staged buffer sizes (direction: h2d | d2h).
TRANSFER_BYTES = metrics.DEFAULT.counter(
    "solver_device_transfer_bytes_total",
    "Host<->device bytes staged by the solve pipelines",
    ("direction",),
)

#: The PR-7 recompilation sentinel as a live metric: entries in the
#: solver's XLA executable cache, and a counter of compiles observed
#: (cache growth between ticks). Steady growth under steady load means
#: shape-bucket padding regressed and ticks are stalling on compiles.
XLA_CACHE_ENTRIES = metrics.DEFAULT.gauge(
    "solver_xla_compile_cache_entries",
    "Compiled executables in the solver's XLA jit cache",
)
XLA_COMPILES = metrics.DEFAULT.counter(
    "solver_xla_compiles_total",
    "XLA solver compiles observed (compile-cache growth between ticks)",
)

#: Live device memory (kind: in_use | peak | limit), when the backend
#: reports it (TPU does; CPU hosts usually return nothing).
DEVICE_MEMORY = metrics.DEFAULT.gauge(
    "device_memory_bytes",
    "Accelerator memory reported by the backend, by kind",
    ("kind",),
)


def nbytes_of(cols) -> int:
    """Total ndarray bytes in a dict or dataclass of columns (the
    pipeline's staged host buffers)."""
    if isinstance(cols, dict):
        vals = cols.values()
    else:
        vals = vars(cols).values() if hasattr(cols, "__dict__") else ()
    return sum(getattr(v, "nbytes", 0) for v in vals)


def note_transfer(direction: str, nbytes: int) -> None:
    if nbytes > 0:
        TRANSFER_BYTES.inc(float(nbytes), direction=direction)


def observe_watch_lag(resource: str, lag_versions: int) -> None:
    WATCH_LAG.observe(float(max(0, lag_versions)), resource=resource)


_XLA_SEEN = {"entries": 0}
#: Guards the _XLA_SEEN read-modify-write: two daemons sampling the
#: same process concurrently (leader pairs, batch+incremental) must
#: not double-count or swallow a compile-cache growth window.
_XLA_LOCK = sanitizer.lock("sli.xla")
_DEVICE_CACHE: List = []  # resolved once; per-tick stats read off it


def observe_device_telemetry() -> None:
    """Per-tick device telemetry sample: XLA compile-cache size (gauge
    + growth counter) and device memory. Never raises — a backend
    without memory stats (CPU) just skips those gauges."""
    try:
        from kubernetes_tpu.ops.solver import (
            _solve_with_state_xla,
            _solve_xla,
        )

        entries = int(_solve_xla._cache_size()) + int(
            _solve_with_state_xla._cache_size()
        )
    except Exception:
        entries = -1
    if entries >= 0:
        XLA_CACHE_ENTRIES.set(entries)
        with _XLA_LOCK:
            grown = entries - _XLA_SEEN["entries"]
            # Track shrinks too (cache cleared in tests) so the next
            # growth counts from the new floor instead of being
            # swallowed.
            _XLA_SEEN["entries"] = entries
        if grown > 0:
            XLA_COMPILES.inc(grown)
    try:
        if not _DEVICE_CACHE:
            import jax

            _DEVICE_CACHE.append(jax.local_devices()[0])
        stats = _DEVICE_CACHE[0].memory_stats() or {}
    except Exception:
        return
    for key, kind in (
        ("bytes_in_use", "in_use"),
        ("peak_bytes_in_use", "peak"),
        ("bytes_limit", "limit"),
    ):
        if key in stats:
            DEVICE_MEMORY.set(float(stats[key]), kind=kind)


class LifecycleSLICollector:
    """Watch-fed pod-lifecycle milestone collector (informer-style:
    state is kept current by events alone — it never lists or polls).

    Feed it by attaching to a store (``attach``: the kvstore dispatcher
    invokes ``_on_store_events`` once a pass with its events, in
    version order, on its own thread) and by the flight-recorder decision sink registered
    at module import (``note_decision``). Thread-safe; observations
    happen outside the track lock."""

    #: Bound on concurrently tracked (created-but-not-Running) pods;
    #: the oldest track is evicted at the cap, so a flood of pods that
    #: never progress cannot grow the collector without bound.
    MAX_TRACKED = 65536

    def __init__(self):
        self._lock = sanitizer.lock("sli.collector")
        # pod key ("ns/name") -> [created_mono, decided, bound, running]
        self._tracks: Dict[str, List] = {}
        self.enabled = True

    # -- wiring --------------------------------------------------------

    def attach(self, store) -> None:
        """Subscribe to a kvstore's event dispatcher (the same feed the
        apiserver watch cache rides). Idempotent per store: KVStore
        subscribers are append-only, so attach once per store."""
        store.subscribe(self._on_store_events)

    # -- event feed (dispatcher thread) --------------------------------

    def _on_store_events(self, items) -> None:
        """One dispatcher pass, in version order: one clock read, one
        lock hold for the pass's pod events, one histogram call per
        milestone. Every event of a pass is seen at the same instant."""
        if not self.enabled:
            return
        pods = [it for it in items if it[2].startswith(POD_PREFIX)]
        if not pods:
            return
        now = time.monotonic()
        bound_waits: List[float] = []
        running_waits: List[float] = []
        tracks = self._tracks
        with self._lock:
            for _version, etype, key, obj, _prev in pods:
                pod_key = key[_PREFIX_LEN:]
                if etype == "DELETED":
                    tracks.pop(pod_key, None)
                    continue
                if not isinstance(obj, dict):
                    continue
                spec = obj.get("spec")
                if etype == "ADDED":
                    if spec and spec.get("nodeName"):
                        continue  # born bound (static pod / replay): no startup story
                    if (
                        len(tracks) >= self.MAX_TRACKED
                        and pod_key not in tracks
                    ):
                        tracks.pop(next(iter(tracks)))
                    tracks[pod_key] = [now, False, False, False]
                    continue
                # MODIFIED: bound / running transitions (tracked pods only).
                t = tracks.get(pod_key)
                if t is None:
                    continue
                status = obj.get("status")
                if spec and spec.get("nodeName") and not t[2]:
                    t[2] = True
                    bound_waits.append(now - t[0])
                if status and status.get("phase") == "Running" and not t[3]:
                    t[3] = True
                    running_waits.append(now - t[0])
                if t[3]:
                    del tracks[pod_key]  # lifecycle complete: drain
        if bound_waits:
            STARTUP_LATENCY.observe_many(bound_waits, milestone="bound")
        if running_waits:
            STARTUP_LATENCY.observe_many(running_waits, milestone="running")

    # -- decision join (flight-recorder sink, scheduler thread) --------

    def note_decision(self, pod_key: str, outcome: str = "") -> None:
        """The flight recorder logged a Decision for this pod: stamp
        the decision milestone (first one wins — retries re-decide but
        the SLI is time-to-first-decision)."""
        now = time.monotonic()
        with self._lock:
            t = self._tracks.get(pod_key)
            if t is None or t[1]:
                return
            t[1] = True
            created = t[0]
        STARTUP_LATENCY.observe(now - created, milestone="decision")

    # -- introspection -------------------------------------------------

    def tracked_count(self) -> int:
        with self._lock:
            return len(self._tracks)

    #: Bound on the oldest-unbound scan below — tracks are insertion-
    #: ordered so the oldest unbound pod sits near the front; a cap
    #: keeps the per-tick capacity sample O(1) even at MAX_TRACKED.
    _AGE_SCAN_LIMIT = 1024

    def oldest_unbound_age_s(self) -> float:
        """Age (seconds) of the oldest tracked pod that has not reached
        the bound milestone — the backlog-pressure age watermark
        (utils/capacity.py multiplies it by the FIFO depth). 0.0 when
        nothing is waiting."""
        now = time.monotonic()
        with self._lock:
            for i, t in enumerate(self._tracks.values()):
                if i >= self._AGE_SCAN_LIMIT:
                    break
                if not t[2]:  # not yet bound
                    return max(now - t[0], 0.0)
        return 0.0

    def reset(self) -> None:
        with self._lock:
            self._tracks.clear()


DEFAULT = LifecycleSLICollector()

# The PR-5 join: every Decision the flight recorder logs stamps the
# pod's "decision" milestone (registered once at import; flightrecorder
# never imports sli, so there is no cycle).
flightrecorder.add_decision_sink(DEFAULT.note_decision)
