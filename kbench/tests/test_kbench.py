"""Tests of the benchmark under kbench/, on the CPU at rehearsal size.

    JAX_PLATFORMS=cpu python -m pytest kbench/tests -q

Every run here is a child process with ``--rehearse``, the one way a
run gets through without a TPU; the compile cache is off.
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from kbench import gen, loadgen, reference, run, trace, work  # noqa: E402

CELLS = ("batch-5k.burst",)

#: An open-loop mix (the churn generator) for the files-only test: no
#: cell of BENCHMARK.json uses one yet (PERF.md §7).
OPEN_MIX = {
    "loop": "open", "rate_per_s": 10, "senders": 4, "warmup_s": 2,
    "quiet_s": 1.0, "tail_s": 1, "drain_s": 20, "check_sample": 100000,
    "visibility_slack_s": 1.0, "mismatch_limit": 0.1,
    "control": {"weights": [1, 0, 1]},
}
SEED = 2**31 + 40_000_017  # wider than 32 signed bits, as the driver's are
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "session_v5e.xplane.pb.gz")


@pytest.fixture
def env(tmp_path):
    return {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "JAX_ENABLE_COMPILATION_CACHE": "false",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"),
    }


def bench(root, env, *args, timeout=240):
    return subprocess.run(
        [sys.executable, "-m", "kbench.run", *args], cwd=root, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


def last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_at_rehearsal_size(cell, env):
    p = bench(ROOT, env, "--workload", cell, "--seed", str(SEED),
              "--seconds", "3", "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    res = last_json(p.stdout)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    spec = run.load_cell(cell, True)
    assert set(res["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    for m in spec["end_to_end"]:
        got = res["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert set(res["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert res["device"]["platform"] == "cpu"
    tail = p.stderr.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("check ") for line in tail)


def test_a_run_without_a_tpu_fails(env):
    p = bench(ROOT, env, "--workload", CELLS[0], "--seed", "1",
              "--seconds", "3", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_the_benchmark_files_alone_fail(tmp_path, env):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "kbench"), tmp_path / "kbench")
    p = bench(tmp_path, env, "--workload", CELLS[0], "--seed", "1",
              "--seconds", "3", "--trace", "0", "--rehearse")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_new_cell_config_mix_and_metric_are_found_by_name(tmp_path, env):
    """Files and entries added, none edited: a config, a traffic mix, a
    per-layer metric's reader and a cell that uses them."""
    shutil.copytree(os.path.join(ROOT, "kbench"), tmp_path / "kbench")
    for name in ("kubernetes_tpu", "native"):
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cfg = json.load(open(os.path.join(ROOT, "kbench/configs/batch-5k.json")))
    cfg["rehearsal"] = {"nodes": 30, "prefill_pods": 900}
    (tmp_path / "kbench/configs/tiny.json").write_text(json.dumps(cfg))
    (tmp_path / "kbench/traffic/slow.json").write_text(json.dumps(OPEN_MIX))
    (tmp_path / "kbench/metrics/ticks_seen.py").write_text(
        "def read(ctx):\n    return float(ctx['phases'].get('solve', (0, 0))[0])\n"
    )
    b["configs"].append({**b["configs"][0], "name": "tiny", "file": "kbench/configs/tiny.json"})
    b["workloads"].append({"name": "tiny.slow", "config": "tiny", "traffic": "slow",
                           "chips": 1, "why": "a cell added as files"})
    b["per_layer"].append({"name": "ticks_seen", "unit": "ticks", "better": "higher",
                           "source": "program_span", "layer": "scheduler daemon",
                           "moves": "setup_s", "workloads": ["tiny.slow"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    spec = run.load_cell("tiny.slow", True, root=str(tmp_path))
    assert spec["config"]["nodes"] == 30 and spec["traffic"]["rate_per_s"] == 10
    assert [m["name"] for m in spec["per_layer"]] == ["ticks_seen"]
    p = bench(tmp_path, env, "--workload", "tiny.slow", "--seed", str(SEED),
              "--seconds", "3", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["correct"] is True
    assert res["metrics"]["ticks_seen"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize(
    "kind", ["control", "unchanged_state", "half_batch", "altered_answer"]
)
def test_control_and_faults_come_out_not_correct(cell, kind, env):
    """The control (the priority that decides the cell's placements
    dropped) and each fault the cell
    can have, planted under the timed path, make ``correct`` false."""
    p = subprocess.run(
        [sys.executable, "-m", "kbench.control", "--workload", cell,
         "--seeds", str(SEED), "--seconds", "3", "--kind", kind, "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["correct"] is False, res["checks"]


# -- the pieces, in process ---------------------------------------


def test_reducer_on_a_recorded_trace(tmp_path):
    """A trace of a few session ticks recorded on a v5e (PR 22); its
    numbers are the fixture's, not a device measurement of any cell."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(gzip.decompress(open(FIXTURE, "rb").read()))
    red = trace.reduce(trace.load(str(path)))
    assert red is not None
    # Three ticks of 50 pods on 64 nodes: the fixture's own numbers.
    assert red["busy_s"] == pytest.approx(359.343e-6, rel=1e-9)
    assert red["span_s"] == pytest.approx(66.503693e-3, rel=1e-9)
    solve = trace.module_seconds(red, ("_solve_packed",))
    assert solve == pytest.approx(360.484e-6, rel=1e-9)  # gaps inside count
    assert red["device_ops"][0][0] == "%_solve_packed.1"
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
    gaps = [g for _, g in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= red["span_s"] - red["busy_s"] + 1e-9


def test_reducer_unions_overlapping_ops():
    planes = [
        {"name": "/device:TPU:0", "lines": {
            "XLA Modules": [("jit__solve_packed(1)", 100, 400)],
            "XLA Ops": [("a", 100, 250), ("b", 200, 300), ("c", 600, 700)],
        }},
        {"name": "/host:CPU", "lines": {"t": [("bind", 0, 1000), ("lower", 300, 650)]}},
    ]
    red = trace.reduce(planes)
    assert red["busy_s"] == pytest.approx(300e-9)
    assert red["span_s"] == pytest.approx(1000e-9)
    assert red["modules"] == {"jit__solve_packed(1)": pytest.approx(300e-9)}
    assert red["idle_gaps"][0] == ["t: lower", pytest.approx(300e-9)]
    assert trace.reduce([planes[1]]) is None


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert run.pct(xs, 0.99) == 99 and run.pct(xs, 0.5) == 50
    assert run.pct([7.0], 0.99) == 7.0


def test_open_loop_offers_every_seed_the_same_count():
    a = loadgen.schedule(1, "s", 100.0, [2.0, 3.0])
    b = loadgen.schedule(2**31 + 5, "s", 100.0, [2.0, 3.0])
    assert len(a) == len(b) == 500
    assert sum(t < 2.0 for t in a) == sum(t < 2.0 for t in b) == 200
    assert a != b and a == sorted(a)


def test_prefill_fits_every_node():
    """Dealt evenly over the nodes, each within capacity, on a mixed
    shape (the generator's general case) as on the configurations'."""
    cfg = json.load(open(os.path.join(ROOT, "kbench/configs/batch-5k.json")))
    cfg.update(nodes=200, prefill_pods=6000)
    cfg["node_shape"] = {"cpu_cores": [8, 16, 32], "memory_gib": [16, 32, 64],
                         "pods": 110, "zones": ["z0", "z1", "z2", "z3"]}
    cfg["pod_mix"] = {"cpu_m": [100, 250, 500, 1000], "memory_mib": [64, 128, 256, 512],
                      "selector_share": 0.1, "host_port_share": 0.05,
                      "host_ports": 64, "pods_per_service": 100}
    nodes = gen.nodes(cfg, 7)
    spec = {n.name: n for n in nodes}
    by: dict = {}
    for p, node in gen.prefill(cfg, 7, nodes):
        by.setdefault(node, []).append(p)
        assert not p.zone or p.zone == spec[node].zone
    assert len(by) == 200 and all(len(v) == 30 for v in by.values())
    for node, pods in by.items():
        assert sum(p.cpu_m for p in pods) <= spec[node].cpu_m
        assert sum(p.mem_mib for p in pods) <= spec[node].mem_mib
        ports = [p.host_port for p in pods if p.host_port]
        assert len(ports) == len(set(ports))


@pytest.mark.parametrize("name", ["batch-5k"])
def test_configurations_place_their_prefill_within_capacity(name):
    cfg = json.load(open(os.path.join(ROOT, f"kbench/configs/{name}.json")))
    nodes = gen.nodes(cfg, 11)
    placed = gen.prefill(cfg, 11, nodes)
    assert len(placed) == cfg["prefill_pods"]
    per: dict = {}
    for p, node in placed:
        per[node] = per.get(node, 0) + 1
        assert p.app == -1 and not p.zone and not p.host_port
    assert max(per.values()) - min(per.values()) <= 1
    cap = nodes[0]
    assert max(per.values()) * 100 <= cap.cpu_m and max(per.values()) * 500 <= cap.mem_mib
    assert "labels" not in gen.pod_wire(placed[0][0])["metadata"]


def _cluster():
    nodes = [gen.NodeSpec("n0", "z0", 8000, 16384, 110),
             gen.NodeSpec("n1", "z1", 32000, 65536, 110)]
    return reference.Cluster(nodes, 2)


def test_reference_takes_a_best_node_and_refuses_others():
    cl = _cluster()
    cl.bind(gen.PodSpec("half", 1, 4000, 8192, 0, ""), 0)
    p = gen.PodSpec("x", 0, 500, 256, 0, "")
    assert reference.judge(cl, p, 1, {})[0]  # the emptier node
    assert not reference.judge(cl, p, 0, {})[0]
    assert not reference.judge(cl, gen.PodSpec("y", 0, 500, 256, 0, "z1"), 0, {})[0]
    cl.bind(gen.PodSpec("h", 1, 100, 64, 30001, ""), 1)
    assert not reference.judge(cl, gen.PodSpec("z", 0, 100, 64, 30001, ""), 1, {})[0]


def test_reference_judges_pods_without_a_service():
    """scheduler_perf's pods have no labels: spreading scores 10 on
    every node, and the node of 29 pods beats those of 30."""
    nodes = [gen.NodeSpec(f"n{j}", "", 4000, 32768, 110) for j in range(3)]
    cl = reference.Cluster(nodes, 0)
    for j, n in enumerate((30, 29, 30)):
        for i in range(n):
            cl.bind(gen.PodSpec(f"q{j}-{i}", -1, 100, 500, 0, ""), j)
    p = gen.PodSpec("x", -1, 100, 500, 0, "")
    assert reference.judge(cl, p, 1, {})[0]
    assert not reference.judge(cl, p, 0, {})[0]
    # Had the solve not yet seen the delete that left n1 at 29, n0 tied.
    assert reference.judge(cl, p, 0, {1: [gen.PodSpec("gone", -1, 100, 500, 0, "")]})[0]


def test_reference_allows_a_delete_the_solve_may_not_have_seen():
    cl = _cluster()
    cl.bind(gen.PodSpec("half", 1, 4000, 8192, 0, ""), 0)
    big = [gen.PodSpec(f"b{i}", 0, 3000, 8192, 0, "") for i in range(8)]
    for q in big:  # n1's memory is full
        cl.bind(q, 1)
    p = gen.PodSpec("x", 1, 500, 256, 0, "")
    assert reference.judge(cl, p, 0, {})[0]
    for q in big:  # deletes the watch showed: n1 is empty, and best
        cl.delete(q.name)
    assert not reference.judge(cl, p, 0, {})[0]
    # Had the solve not seen them yet, n0 was right; n1 is right anyway.
    assert reference.judge(cl, p, 0, {1: big})[0]
    assert reference.judge(cl, p, 1, {1: big})[0]


def test_roofline_names_its_bound_and_refuses_unknown_devices():
    ops, nbytes = work.solve_work(10_000, 4, 5_000, 100)
    t, bound = work.least_time(ops, nbytes, "TPU v5 lite")
    assert t > 0 and bound in ("compute", "memory")
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_spread_interval_is_ordered():
    c = np.array([0, 1, 2])
    assert (reference._spread(2, c) == [10, 5, 0]).all()
    assert (reference._spread(0, np.zeros(3, np.int64)) == 10).all()
