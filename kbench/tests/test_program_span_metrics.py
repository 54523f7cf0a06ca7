"""The readers of the program's own phase spans (api.request,
store.commit, watch.fanout_wait, sched.queue_wait) on a synthetic
window.

    JAX_PLATFORMS=cpu python -m pytest kbench/tests -q
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from kbench import run  # noqa: E402

#: (phase count, phase seconds) over one window, as kbench.run reads them.
PHASES = {
    "api.request": (40, 2.5),
    "store.commit": (30, 1.5),
    "watch.fanout_wait": (4000, 0.8),
    "sched.queue_wait": (2000, 3.0),
    "solve": (5, 0.1),
}
SCHEDULED = 2000.0

EXPECTED = {
    "api_self_ms_per_pod.burst": 1e3 * (2.5 - 1.5) / 2000,
    "store_commit_ms_per_pod.burst": 1e3 * 1.5 / 2000,
    "watch_fanout_wait_ms.burst": 1e3 * 0.8 / 4000,
    "sched_queue_wait_ms.burst": 1e3 * 3.0 / 2000,
}

#: The phase without which each reader has nothing to read.
NEEDS = {
    "api_self_ms_per_pod.burst": "api.request",
    "store_commit_ms_per_pod.burst": "store.commit",
    "watch_fanout_wait_ms.burst": "watch.fanout_wait",
    "sched_queue_wait_ms.burst": "sched.queue_wait",
}


def ctx(phases, scheduled=SCHEDULED):
    return {"phases": phases, "scheduled": scheduled, "notes": []}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_reads_its_phases(metric):
    assert run.reader(metric).read(ctx(PHASES)) == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", sorted(NEEDS))
def test_reader_reads_nothing_without_its_phase(metric):
    """A program without the span (the parent of the change that added
    it) reports no value, and the reader does not raise."""
    phases = {k: v for k, v in PHASES.items() if k != NEEDS[metric]}
    assert run.reader(metric).read(ctx(phases)) is None
    zeroed = dict(PHASES, **{NEEDS[metric]: (0, 0.0)})
    assert run.reader(metric).read(ctx(zeroed)) is None


@pytest.mark.parametrize("metric", ["api_self_ms_per_pod.burst", "store_commit_ms_per_pod.burst"])
def test_per_pod_readers_read_nothing_when_no_pod_bound(metric):
    assert run.reader(metric).read(ctx(PHASES, scheduled=0.0)) is None


def test_every_program_span_metric_is_declared_for_the_burst_cell():
    spec = run.load_cell("batch-5k.burst", rehearse=False)
    declared = {m["name"]: m for m in spec["per_layer"]}
    for name in EXPECTED:
        m = declared[name]
        assert m["source"] == "program_span"
        assert m["moves"] == "bound_pods_per_s.burst"
        assert m["workloads"] == ["batch-5k.burst"]
