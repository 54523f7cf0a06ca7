"""On-demand device traces, and the ratio bucket ladder.

The compile/cost half of the profiling story lives in ops/ledger.py
(it needs jax; this module must stay importable by a control-plane
process that never touches the accelerator). Here lives the
``jax.profiler.trace`` wrapper behind ``GET /debug/device-profile``:
``capture_device_trace(seconds)`` sleeps on the calling (HTTP handler)
thread while the daemon threads keep dispatching — the produced
directory opens in XProf/TensorBoard or perfetto. One capture at a
time per process (the profiler backend cannot nest). The program's
phases (utils/tracing ``kt.*`` annotations) land in the same trace, on
the device's clock, so an idle stretch of the device reads against
what each host thread was doing.
"""

from __future__ import annotations

import os
import tempfile
import time
from typing import Optional

from kubernetes_tpu.utils import sanitizer

#: Bucket ladder for histograms of [0, 1] ratios (capacity scores,
#: rebalance improvements): the default latency buckets would dump
#: everything into one bucket.
RATIO_BUCKETS = (
    0.01, 0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 0.95,
    0.99, 1.0,
)


# -- on-demand device traces -------------------------------------------


class ProfilerUnavailable(RuntimeError):
    """jax (or its profiler backend) is not importable/startable in
    this process."""


class TraceInProgress(RuntimeError):
    """A device trace capture is already running (the profiler backend
    cannot nest sessions)."""


_CAPTURE_LOCK = sanitizer.lock("profiler.capture")
_CAPTURE_ACTIVE = [False]

#: Capture length clamp — a typo'd ?seconds= must not pin an HTTP
#: handler (and the trace buffer) for minutes.
MAX_TRACE_SECONDS = 60.0


def capture_device_trace(
    seconds: float = 2.0, out_dir: Optional[str] = None
) -> dict:
    """Record ``seconds`` of device activity via ``jax.profiler.trace``
    into a server-side directory (fresh tempdir unless ``out_dir``).
    The caller's thread sleeps inside the session; every OTHER thread's
    dispatches land in the trace — exactly what an operator wants from
    a live daemon. Returns {dir, seconds, files}."""
    if seconds != seconds:  # NaN slips through min/max clamps
        seconds = 2.0
    seconds = min(max(float(seconds), 0.1), MAX_TRACE_SECONDS)
    try:
        import jax
    except Exception as e:  # pragma: no cover - jax is baked into CI
        raise ProfilerUnavailable(f"jax unavailable: {e!r}")
    with _CAPTURE_LOCK:
        if _CAPTURE_ACTIVE[0]:
            raise TraceInProgress(
                "a device trace capture is already in progress"
            )
        _CAPTURE_ACTIVE[0] = True
    try:
        trace_dir = out_dir or tempfile.mkdtemp(prefix="kt-device-trace-")
        try:
            with jax.profiler.trace(trace_dir):
                time.sleep(seconds)
        except Exception as e:
            raise ProfilerUnavailable(
                f"device trace capture failed: {e!r}"
            )
        files = []
        for root, _dirs, names in os.walk(trace_dir):
            for name in names:
                files.append(
                    os.path.relpath(os.path.join(root, name), trace_dir)
                )
        return {
            "dir": trace_dir,
            "seconds": seconds,
            "files": sorted(files),
        }
    finally:
        with _CAPTURE_LOCK:
            _CAPTURE_ACTIVE[0] = False
