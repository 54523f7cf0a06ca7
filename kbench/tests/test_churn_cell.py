"""The ``svc-5k.churn`` cell on the CPU at rehearsal size, and the
reader of its kernel share.

    JAX_PLATFORMS=cpu python -m pytest kbench/tests -q
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from kbench import gen, run  # noqa: E402

CELL = "svc-5k.churn"
SEED = 2**31 + 40_000_029  # wider than 32 signed bits, as a run's seed may be


@pytest.fixture
def env(tmp_path):
    return {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "JAX_ENABLE_COMPILATION_CACHE": "false",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"),
    }


def last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


def test_churn_runs_end_to_end_at_rehearsal_size(env):
    p = subprocess.run(
        [sys.executable, "-m", "kbench.run", "--workload", CELL, "--seed", str(SEED),
         "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    judged = [ln for ln in p.stderr.splitlines() if ln.startswith("check mismatch")]
    assert judged, p.stderr[-2000:]
    # Traced on the CPU: the XLA scan ran every solve, so the share of
    # the pallas kernel reads 0, and no device metric is read.
    assert res["metrics"]["solve_kernel_share.churn"]["value"] == 0.0
    assert "xla_scan (platform)" in p.stderr


def test_churn_control_comes_out_not_correct(env):
    """ServiceSpreading dropped: decisions land on nodes holding the
    pod's Service's pods while others tie without them."""
    p = subprocess.run(
        [sys.executable, "-m", "kbench.control", "--workload", CELL,
         "--seeds", str(SEED), "--seconds", "3", "--kind", "control", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["mismatch"]["value"] > res["checks"]["mismatch"]["limit"]


def test_churn_judges_every_decision_of_the_run():
    spec = run.load_cell(CELL, False)
    traffic, cfg = spec["traffic"], spec["config"]
    assert traffic["loop"] == "open" and traffic["rate_per_s"] > 0
    seconds = 51
    creates = traffic["rate_per_s"] * (traffic["warmup_s"] + seconds + traffic["tail_s"])
    assert traffic["check_sample"] >= creates
    # Deletes take the oldest prefill pods, one for each create.
    assert creates < cfg["prefill_pods"]
    assert traffic["control"]["weights"] == [1, 1, 0]
    assert [m["name"] for m in spec["end_to_end"]] == ["bound_pods_per_s.burst", "setup_s"]


def test_svc_5k_is_the_documented_limits():
    cfg = json.load(open(os.path.join(ROOT, "kbench/configs/svc-5k.json")))
    assert cfg["nodes"] == 5000 and cfg["prefill_pods"] == 150_000
    assert gen.services(cfg) == 10_000
    assert cfg["reduced"] == []
    nodes = gen.nodes(cfg, 3)
    per: dict = {}
    for p, node in gen.prefill(cfg, 3, nodes[:]):
        per[node] = per.get(node, 0) + 1
        assert 0 <= p.app < 10_000
    assert set(per.values()) == {30} and len(per) == 5000


class _Family:
    def __init__(self, values):
        self.values = values

    def snapshot(self):
        return dict(self.values)


class _Registry:
    def __init__(self, fam):
        self.fam = fam

    def get(self, name):
        return self.fam if name == "scheduler_device_solves_total" else None


def _read(monkeypatch, fam):
    from kubernetes_tpu.utils import metrics

    monkeypatch.setattr(metrics, "DEFAULT", _Registry(fam))
    ctx = {"notes": []}
    return run.reader("solve_kernel_share.churn").read(ctx), ctx["notes"]


def test_kernel_share_reads_pallas_over_all_solves(monkeypatch):
    value, notes = _read(monkeypatch, _Family({
        ("pallas", "eligible"): 300.0,
        ("xla_scan", "vmem"): 100.0,
    }))
    assert value == pytest.approx(75.0)
    assert "device solves: xla_scan (vmem) 100" in notes


def test_kernel_share_reads_nothing_without_the_counter(monkeypatch):
    """A program without the counter, or with none counted yet."""
    assert _read(monkeypatch, None)[0] is None
    assert _read(monkeypatch, _Family({}))[0] is None
