"""Prometheus text exposition format: golden output, label escaping,
histogram bucket invariants, summary quantile rendering — plus the
metric-name lint (ktlint pass KT005; tools/lint_metrics.py is now a
deprecation shim onto it) over the live package."""

import pathlib
import subprocess
import sys

from kubernetes_tpu.utils import metrics


class TestCounterGauge:
    def test_counter_golden(self):
        c = metrics.Counter("widgets_total", "Widgets made", ("kind",))
        c.inc(kind="round")
        c.inc(2, kind="square")
        c.inc(kind="square")
        assert c.render() == [
            "# HELP widgets_total Widgets made",
            "# TYPE widgets_total counter",
            'widgets_total{kind="round"} 1.0',
            'widgets_total{kind="square"} 3.0',
        ]

    def test_gauge_golden(self):
        g = metrics.Gauge("queue_depth_bytes", "Depth")
        g.set(7)
        assert g.render() == [
            "# HELP queue_depth_bytes Depth",
            "# TYPE queue_depth_bytes gauge",
            "queue_depth_bytes 7",
        ]

    def test_label_value_escaping(self):
        """Backslash, double-quote, and newline must be escaped per the
        text exposition format — a pod name carrying '"' used to
        corrupt the /metrics output."""
        c = metrics.Counter("pods_total", "by pod", ("pod",))
        c.inc(pod='we"ird\\name\nx')
        line = c.render()[-1]
        assert line == 'pods_total{pod="we\\"ird\\\\name\\nx"} 1.0'
        # The exposition line stays one physical line — the raw newline
        # never leaks into the output.
        assert "\n" not in line

    def test_help_escaping(self):
        c = metrics.Counter("x_total", "line1\nline2")
        assert c.render()[0] == "# HELP x_total line1\\nline2"


class TestHistogram:
    def test_default_bucket_ladder_golden(self):
        """The ladder the latency SLOs read, pinned (ISSUE 12): sub-
        100ms resolution (0.01/0.025/0.05/0.075/0.1) so the 100ms
        pod-to-bind objective has quantile resolution UNDER its
        target, and a 30/60/120 tail past client_golang's 10s cap so a
        saturated series reports a real (interpolated) p99 instead of
        a value clamped to exactly 10.0 — BENCH_r06's
        solve_phase_latency 'p99 10.0' was the clamp, not a
        measurement."""
        assert metrics.DEFAULT_BUCKETS == (
            0.005, 0.01, 0.025, 0.05, 0.075, 0.1, 0.25, 0.5, 1.0, 2.5,
            5.0, 10.0, 30.0, 60.0, 120.0,
        )
        # Sub-100ms band: four finite bounds strictly below 0.1.
        assert [b for b in metrics.DEFAULT_BUCKETS if b < 0.1] == [
            0.005, 0.01, 0.025, 0.05, 0.075,
        ]
        # A 12s-heavy series interpolates INSIDE (10, 30], not at the
        # old clamp.
        h = metrics.Histogram("ladder_seconds", "x")
        for _ in range(100):
            h.observe(12.0)
        q = h.quantile(0.99)
        assert 10.0 < q <= 30.0
        # Rendered exposition carries the new bounds.
        text = "\n".join(h.render())
        assert 'le="0.075"' in text and 'le="30"' in text
        assert 'le="120"' in text

    def test_type_line_and_buckets(self):
        h = metrics.Histogram(
            "req_seconds", "Request latency", buckets=(0.1, 1.0, 10.0)
        )
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        lines = h.render()
        assert lines[0] == "# HELP req_seconds Request latency"
        assert lines[1] == "# TYPE req_seconds histogram"
        assert lines[2:] == [
            'req_seconds_bucket{le="0.1"} 1',
            'req_seconds_bucket{le="1"} 3',
            'req_seconds_bucket{le="10"} 4',
            'req_seconds_bucket{le="+Inf"} 5',
            "req_seconds_sum 56.05",
            "req_seconds_count 5",
        ]

    def test_bucket_monotonicity_and_inf_equals_count(self):
        h = metrics.Histogram("lat_seconds", "x", ("phase",))
        import random

        rng = random.Random(7)
        for _ in range(500):
            h.observe(rng.expovariate(2.0), phase="solve")
        cums = []
        inf_val = count_val = None
        for line in h.render():
            if line.startswith("lat_seconds_bucket"):
                v = int(line.rsplit(" ", 1)[1])
                if 'le="+Inf"' in line:
                    inf_val = v
                else:
                    cums.append(v)
            elif line.startswith("lat_seconds_count"):
                count_val = int(line.rsplit(" ", 1)[1])
        assert cums == sorted(cums), "bucket counts must be cumulative"
        assert inf_val == count_val == 500
        assert cums[-1] <= inf_val

    def test_quantile_interpolation(self):
        h = metrics.Histogram("q_seconds", "x", buckets=(1.0, 2.0, 4.0))
        for v in (0.5,) * 50 + (1.5,) * 50:
            h.observe(v)
        # Median sits at the boundary of the first bucket.
        assert h.quantile(0.5) == 1.0
        # p99 interpolates inside the (1, 2] bucket.
        assert 1.9 < h.quantile(0.99) <= 2.0
        # Values beyond the top bound report the top finite bound.
        h2 = metrics.Histogram("q2_seconds", "x", buckets=(1.0,))
        h2.observe(100.0)
        assert h2.quantile(0.99) == 1.0
        # Empty series: NaN.
        import math

        assert math.isnan(metrics.Histogram("q3_seconds", "x").quantile(0.5))

    def test_observe_many_equals_repeated_observe(self):
        import random

        rng = random.Random(11)
        values = [rng.expovariate(0.5) for _ in range(400)] + [
            0.0, 0.005, 120.0, 121.0,
        ]
        one = metrics.Histogram("one_seconds", "x", ("phase",))
        many = metrics.Histogram("one_seconds", "x", ("phase",))
        for v in values:
            one.observe(v, phase="p")
        many.observe_many(values[:100], phase="p")
        many.observe_many(values[100:], phase="p")
        many.observe_many([], phase="empty")
        (k, (n, s, b)), = one.snapshot().items()
        assert many.snapshot() == {k: (n, s, b)}
        assert n == len(values)
        assert many.render() == one.render()

    def test_registry_histogram_in_default_render(self):
        h = metrics.DEFAULT.histogram(
            "exposition_test_seconds", "temp", ("k",)
        )
        h.observe(0.2, k="v")
        text = metrics.DEFAULT.render()
        assert "# TYPE exposition_test_seconds histogram" in text
        assert 'exposition_test_seconds_bucket{k="v",le="+Inf"} 1' in text


class TestSummary:
    def test_quantile_rendering(self):
        s = metrics.Summary("sum_seconds", "x", quantiles=(0.5, 0.99))
        for v in range(1, 101):
            s.observe(float(v))
        lines = s.render()
        assert lines[1] == "# TYPE sum_seconds summary"
        assert 'sum_seconds{quantile="0.5"} 50.0' in lines
        assert 'sum_seconds{quantile="0.99"} 99.0' in lines
        assert "sum_seconds_sum 5050.0" in lines
        assert "sum_seconds_count 100" in lines

    def test_reservoir_seedable(self):
        """Reservoir sampling draws from the module-level RNG, so tests
        can seed it for reproducible eviction patterns (and observe()
        no longer imports random on the hot path)."""

        def run():
            metrics._RNG.seed(42)
            s = metrics.Summary("seed_seconds", "x")
            for v in range(5000):
                s.observe(float(v))
            return sorted(s._stats[()]["res"])

        assert run() == run()


def _ktlint_kt005(root, target):
    """Run the KT005 pass the way CI does (baseline-free)."""
    return subprocess.run(
        [sys.executable, "-m", "tools.ktlint", "--select", "KT005",
         "--baseline=", str(target)],
        capture_output=True, text=True, timeout=120, cwd=str(root),
    )


def test_lint_metrics_clean():
    """ktlint KT005 over the live package: every registered metric is
    snake_case, unit-suffixed, and on metrics.DEFAULT."""
    root = pathlib.Path(__file__).resolve().parent.parent
    proc = _ktlint_kt005(root, root / "kubernetes_tpu")
    assert proc.returncode == 0, proc.stderr


def test_lint_metrics_catches_violations(tmp_path):
    root = pathlib.Path(__file__).resolve().parent.parent
    bad = tmp_path / "bad.py"
    bad.write_text(
        "from kubernetes_tpu.utils import metrics\n"
        "from kubernetes_tpu.utils.metrics import Counter\n"
        'A = metrics.DEFAULT.counter("CamelCase", "x")\n'
        'B = metrics.DEFAULT.gauge("no_unit_suffix", "x")\n'
        'C = metrics.Summary("rogue_seconds", "x")\n'
        'D = Counter("imported_bypass_seconds", "x")\n'
    )
    proc = _ktlint_kt005(root, tmp_path)
    assert proc.returncode == 1
    assert "not snake_case" in proc.stderr
    assert "lacks a unit suffix" in proc.stderr
    assert "bypasses metrics.DEFAULT" in proc.stderr
    # Both bypass shapes are caught: metrics.Summary(...) AND the
    # from-import form Counter(...).
    assert proc.stderr.count("bypasses metrics.DEFAULT") == 2


def test_lint_metrics_shim_still_works(tmp_path):
    """The deprecated tools/lint_metrics.py entry point execs the
    KT005 pass with the historical output format."""
    root = pathlib.Path(__file__).resolve().parent.parent
    bad = tmp_path / "bad.py"
    bad.write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.gauge("no_unit_suffix", "x")\n'
    )
    proc = subprocess.run(
        [sys.executable, str(root / "tools" / "lint_metrics.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert "lacks a unit suffix" in proc.stderr
    assert "1 metric lint problem(s)" in proc.stderr


def test_lint_metrics_knows_gang_names(tmp_path):
    """The gang_* metric family (scheduler/gang.py, controllers/
    gangs.py) is known to the linter: the suffixed counters pass the
    standard rule, the unitless gang_pending_groups gauge is
    explicitly allowlisted, and a novel suffix-less gang name still
    fails (the allowlist names metrics, not a prefix)."""
    from tools.ktlint.rules_metrics import GANG_METRICS

    assert GANG_METRICS == {
        "gang_solve_outcomes_total",
        "gang_controller_syncs_total",
        "gang_pending_groups",
    }
    root = pathlib.Path(__file__).resolve().parent.parent
    good = tmp_path / "good"
    good.mkdir()
    (good / "g.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.counter("gang_solve_outcomes_total", "x", ("outcome",))\n'
        'B = metrics.DEFAULT.counter("gang_controller_syncs_total", "x", ("result",))\n'
        'C = metrics.DEFAULT.gauge("gang_pending_groups", "x")\n'
    )
    proc = _ktlint_kt005(root, good)
    assert proc.returncode == 0, proc.stderr
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "b.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.gauge("gang_stuck", "x")\n'
    )
    proc = _ktlint_kt005(root, bad)
    assert proc.returncode == 1
    assert "lacks a unit suffix" in proc.stderr


def test_lint_metrics_knows_explain_names(tmp_path):
    """The explainability/convergence family (utils/flightrecorder.py)
    is known to the linter: scheduler_decisions_total passes the
    standard _total rule on its own, the unit-less residual gauge and
    iterations histogram are explicitly allowlisted, and a novel
    suffix-less scheduler_* name still fails (the allowlist names
    metrics, not a prefix)."""
    from tools.ktlint.rules_metrics import ALLOWLIST, EXPLAIN_METRICS

    assert EXPLAIN_METRICS == {
        "scheduler_decisions_total",
        "scheduler_sinkhorn_residual",
        "scheduler_solve_iterations",
    }
    assert EXPLAIN_METRICS <= ALLOWLIST
    root = pathlib.Path(__file__).resolve().parent.parent
    good = tmp_path / "good"
    good.mkdir()
    (good / "g.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.counter('
        '"scheduler_decisions_total", "x", ("outcome",))\n'
        'B = metrics.DEFAULT.gauge("scheduler_sinkhorn_residual", "x")\n'
        'C = metrics.DEFAULT.histogram('
        '"scheduler_solve_iterations", "x", ("mode",))\n'
    )
    proc = _ktlint_kt005(root, good)
    assert proc.returncode == 0, proc.stderr
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "b.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.gauge("scheduler_explain_lag", "x")\n'
    )
    proc = _ktlint_kt005(root, bad)
    assert proc.returncode == 1
    assert "lacks a unit suffix" in proc.stderr


def test_decision_and_convergence_metrics_exposed():
    """Exposition golden for the flight-recorder family: the decision
    counter, the residual gauge, and the iterations histogram all
    render on metrics.DEFAULT with their declared types (they are
    registered at flightrecorder import, so a scrape can never miss
    the family)."""
    from kubernetes_tpu.utils import flightrecorder as fr

    fr.DECISIONS_TOTAL.inc(outcome="exposition_test")
    fr.observe_solve_telemetry("exposition_test_mode", 7, residual=0.25)
    text = metrics.DEFAULT.render()
    assert "# TYPE scheduler_decisions_total counter" in text
    assert 'scheduler_decisions_total{outcome="exposition_test"} 1.0' in text
    assert "# TYPE scheduler_sinkhorn_residual gauge" in text
    assert "scheduler_sinkhorn_residual 0.25" in text
    assert "# TYPE scheduler_solve_iterations histogram" in text
    # 7 iterations lands in the le=8 bucket of the power-of-two ladder.
    assert (
        'scheduler_solve_iterations_bucket{mode="exposition_test_mode",'
        'le="8"} 1' in text
    )
    assert (
        'scheduler_solve_iterations_count{mode="exposition_test_mode"} 1'
        in text
    )


def test_lint_metrics_knows_sli_names(tmp_path):
    """The SLI/SLO telemetry-plane family (utils/sli.py,
    store/watch.py, scheduler/daemon.py) is known to the linter: the
    suffixed series pass the standard rule on their own, the unit-less
    ones (queue depth, version lag, compile-cache entries) are
    explicitly allowlisted, and a novel suffix-less name still fails
    (the allowlist names metrics, not a prefix)."""
    from tools.ktlint.rules_metrics import ALLOWLIST, SLI_METRICS

    assert SLI_METRICS == {
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
    assert SLI_METRICS <= ALLOWLIST
    root = pathlib.Path(__file__).resolve().parent.parent
    good = tmp_path / "good"
    good.mkdir()
    (good / "g.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.histogram('
        '"pod_startup_latency_seconds", "x", ("milestone",))\n'
        'B = metrics.DEFAULT.counter('
        '"watch_streams_dropped_total", "x", ("resource",))\n'
        'C = metrics.DEFAULT.gauge('
        '"watch_stream_queue_depth", "x", ("resource",))\n'
        'D = metrics.DEFAULT.histogram('
        '"watch_fanout_lag_versions", "x", ("resource",))\n'
        'E = metrics.DEFAULT.gauge("solver_xla_compile_cache_entries", "x")\n'
    )
    proc = _ktlint_kt005(root, good)
    assert proc.returncode == 0, proc.stderr
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "b.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.gauge("watch_backlog", "x")\n'
    )
    proc = _ktlint_kt005(root, bad)
    assert proc.returncode == 1
    assert "lacks a unit suffix" in proc.stderr


def test_sli_metrics_exposed():
    """Exposition golden for the telemetry-plane family: the milestone
    histogram renders cumulative +le buckets, the drop counter escapes
    hostile label values, and the lag/depth/device series render on
    metrics.DEFAULT with their declared types."""
    from kubernetes_tpu.store import watch as watchmod
    from kubernetes_tpu.utils import sli

    sli.STARTUP_LATENCY.observe(0.007, milestone="exposition_m")
    sli.STARTUP_LATENCY.observe(0.2, milestone="exposition_m")
    watchmod.STREAMS_DROPPED.inc(resource='we"ird\\res\nx')
    watchmod.QUEUE_DEPTH.set(3, resource="exposition_r")
    sli.observe_watch_lag("exposition_r", 5)
    sli.TRANSFER_BYTES.inc(1024, direction="exposition_d")
    text = metrics.DEFAULT.render()
    assert "# TYPE pod_startup_latency_seconds histogram" in text
    # Cumulative buckets: the 0.2 observation lands at le=0.25 and the
    # 0.007 one at le=0.01 — the +Inf bucket equals the count.
    assert (
        'pod_startup_latency_seconds_bucket{milestone="exposition_m",'
        'le="0.01"} 1' in text
    )
    assert (
        'pod_startup_latency_seconds_bucket{milestone="exposition_m",'
        'le="+Inf"} 2' in text
    )
    assert (
        'pod_startup_latency_seconds_count{milestone="exposition_m"} 2'
        in text
    )
    # Label escaping at the drop counter (a resource label can never
    # corrupt the exposition).
    assert (
        'watch_streams_dropped_total{resource="we\\"ird\\\\res\\nx"} 1.0'
        in text
    )
    assert "# TYPE watch_stream_queue_depth gauge" in text
    assert "# TYPE watch_fanout_lag_versions histogram" in text
    assert (
        'watch_fanout_lag_versions_bucket{resource="exposition_r",le="8"}'
        in text
    )
    assert "# TYPE solver_device_transfer_bytes_total counter" in text
    assert "# TYPE solver_xla_compile_cache_entries gauge" in text


def test_watch_dispatch_counters_exposed(tmp_path):
    """The watch dispatcher's pass and event counters (store/kvstore.py)
    render on metrics.DEFAULT as counters, and their names pass KT005
    on their _total suffix alone."""
    import time

    from kubernetes_tpu.store import kvstore

    store = kvstore.KVStore()
    try:
        p0 = kvstore.DISPATCH_PASSES.value()
        store.create("/registry/pods/default/x", {"metadata": {"name": "x"}})
        deadline = time.monotonic() + 5
        while kvstore.DISPATCH_PASSES.value() == p0:
            assert time.monotonic() < deadline
            time.sleep(0.01)
    finally:
        store.close()
    text = metrics.DEFAULT.render()
    for name in ("watch_dispatch_passes_total", "watch_dispatch_events_total"):
        assert f"# TYPE {name} counter" in text
        line = next(ln for ln in text.splitlines() if ln.startswith(name + " "))
        assert float(line.split()[1]) >= 1.0
    root = pathlib.Path(__file__).resolve().parent.parent
    good = tmp_path / "good"
    good.mkdir()
    (good / "g.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.counter("watch_dispatch_passes_total", "x")\n'
        'B = metrics.DEFAULT.counter("watch_dispatch_events_total", "x")\n'
    )
    proc = _ktlint_kt005(root, good)
    assert proc.returncode == 0, proc.stderr


def test_lint_metrics_knows_profiler_names(tmp_path):
    """The device-time profiling-plane family (ops/ledger.py) is known
    to the linter: the _total-suffixed compile counter passes the
    standard rule on its own, and a novel suffix-less profiler name
    still fails (the allowlist names metrics, not a prefix)."""
    from tools.ktlint.rules_metrics import ALLOWLIST, PROFILER_METRICS

    assert PROFILER_METRICS == {"solver_compile_seconds_total"}
    assert PROFILER_METRICS <= ALLOWLIST
    root = pathlib.Path(__file__).resolve().parent.parent
    good = tmp_path / "good"
    good.mkdir()
    (good / "g.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.counter('
        '"solver_compile_seconds_total", "x", ("kernel",))\n'
    )
    proc = _ktlint_kt005(root, good)
    assert proc.returncode == 0, proc.stderr
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "b.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.histogram("scheduler_device_idle", "x")\n'
    )
    proc = _ktlint_kt005(root, bad)
    assert proc.returncode == 1
    assert "lacks a unit suffix" in proc.stderr


def test_profiler_metrics_exposed():
    """Exposition golden for the profiling-plane family: the
    compile-seconds counter escapes hostile kernel label values."""
    from kubernetes_tpu.ops import ledger

    ledger.COMPILE_SECONDS.inc(1.5, kernel='we"ird\\kern\nx')
    text = metrics.DEFAULT.render()
    assert "# TYPE solver_compile_seconds_total counter" in text
    # Label escaping: a hostile kernel name can never corrupt the
    # exposition.
    assert (
        'solver_compile_seconds_total{kernel="we\\"ird\\\\kern\\nx"} 1.5'
        in text
    )


def test_lint_metrics_knows_capacity_names(tmp_path):
    """The capacity & fragmentation plane family (utils/capacity.py) is
    known to the linter: node_utilization_ratio and the zero-headroom
    _total counter pass the standard rule on their own, the unit-less
    score/rate/headroom/pressure series are explicitly allowlisted, and
    a novel suffix-less capacity name still fails (the allowlist names
    metrics, not a prefix)."""
    from tools.ktlint.rules_metrics import ALLOWLIST, CAPACITY_METRICS

    assert CAPACITY_METRICS == {
        "cluster_fragmentation_score",
        "cluster_headroom_pods",
        "slice_alloc_success_rate",
        "scheduler_backlog_pressure",
    }
    assert CAPACITY_METRICS <= ALLOWLIST
    root = pathlib.Path(__file__).resolve().parent.parent
    good = tmp_path / "good"
    good.mkdir()
    (good / "g.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.histogram("cluster_fragmentation_score", "x")\n'
        'B = metrics.DEFAULT.histogram('
        '"node_utilization_ratio", "x", ("resource",))\n'
        'C = metrics.DEFAULT.gauge("cluster_headroom_pods", "x", ("shape",))\n'
        'D = metrics.DEFAULT.histogram("slice_alloc_success_rate", "x")\n'
        'E = metrics.DEFAULT.gauge("scheduler_backlog_pressure", "x")\n'
        'F = metrics.DEFAULT.counter('
        '"capacity_zero_headroom_ticks_total", "x")\n'
    )
    proc = _ktlint_kt005(root, good)
    assert proc.returncode == 0, proc.stderr
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "b.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.gauge("cluster_stranded", "x")\n'
    )
    proc = _ktlint_kt005(root, bad)
    assert proc.returncode == 1
    assert "lacks a unit suffix" in proc.stderr


def test_capacity_metrics_exposed():
    """Exposition golden for the capacity-plane family: the score/rate
    histograms render cumulative +le buckets on the ratio ladder, the
    pressure gauge and zero-headroom counter carry their declared
    types, and the per-shape headroom gauge escapes hostile shape
    label values (an operator-configured probe name can never corrupt
    the exposition)."""
    from kubernetes_tpu.utils import capacity as capmod

    capmod.FRAG_SCORE.observe(0.35)
    capmod.SLICE_ALLOC.observe(0.75)
    capmod.HEADROOM.set(12.0, shape='we"ird\\shape\nx')
    capmod.BACKLOG_PRESSURE.set(2.5)
    capmod.NODE_UTIL.observe(0.55, resource="cpu")
    capmod.ZERO_HEADROOM.inc()
    text = metrics.DEFAULT.render()
    assert "# TYPE cluster_fragmentation_score histogram" in text
    assert 'cluster_fragmentation_score_bucket{le="0.4"}' in text
    assert 'cluster_fragmentation_score_bucket{le="+Inf"}' in text
    assert "# TYPE slice_alloc_success_rate histogram" in text
    assert 'slice_alloc_success_rate_bucket{le="0.8"}' in text
    assert "# TYPE node_utilization_ratio histogram" in text
    assert 'node_utilization_ratio_bucket{resource="cpu",le="0.6"}' in text
    assert "# TYPE cluster_headroom_pods gauge" in text
    # Label escaping on the shape label.
    assert (
        'cluster_headroom_pods{shape="we\\"ird\\\\shape\\nx"} 12.0' in text
    )
    assert "# TYPE scheduler_backlog_pressure gauge" in text
    assert "scheduler_backlog_pressure 2.5" in text
    assert "# TYPE capacity_zero_headroom_ticks_total counter" in text


def test_lint_metrics_knows_preemption_names(tmp_path):
    """The preemption_* family (scheduler/daemon.py) is known to the
    linter: the _total counters pass the standard rule, the unitless
    preemption_active_nominations gauge is explicitly allowlisted, and
    a novel suffix-less preemption name still fails (the allowlist
    names metrics, not a prefix)."""
    from tools.ktlint.rules_metrics import ALLOWLIST, PREEMPTION_METRICS

    assert PREEMPTION_METRICS == {
        "preemption_victims_total",
        "preemption_solve_outcomes_total",
        "preemption_active_nominations",
    }
    assert PREEMPTION_METRICS <= ALLOWLIST
    root = pathlib.Path(__file__).resolve().parent.parent
    good = tmp_path / "good"
    good.mkdir()
    (good / "g.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.counter("preemption_victims_total", "x")\n'
        'B = metrics.DEFAULT.counter('
        '"preemption_solve_outcomes_total", "x", ("outcome",))\n'
        'C = metrics.DEFAULT.gauge("preemption_active_nominations", "x")\n'
    )
    proc = _ktlint_kt005(root, good)
    assert proc.returncode == 0, proc.stderr
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "b.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.gauge("preemption_backlog", "x")\n'
    )
    proc = _ktlint_kt005(root, bad)
    assert proc.returncode == 1
    assert "lacks a unit suffix" in proc.stderr


def test_lint_metrics_knows_rebalance_names(tmp_path):
    """The rebalance plane family (utils/rebalance.py) is known to the
    linter: the _total counters pass the standard rule on their own,
    the unitless improvement/efficiency histograms are explicitly
    allowlisted, and a novel suffix-less rebalance name still fails
    (the allowlist names metrics, not a prefix)."""
    from tools.ktlint.rules_metrics import ALLOWLIST, REBALANCE_METRICS

    assert REBALANCE_METRICS == {
        "rebalance_moves_total",
        "rebalance_score_improvement",
        "rebalance_moves_per_improvement",
        "rebalance_stranded_pods_total",
    }
    assert REBALANCE_METRICS <= ALLOWLIST
    root = pathlib.Path(__file__).resolve().parent.parent
    good = tmp_path / "good"
    good.mkdir()
    (good / "g.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.counter('
        '"rebalance_moves_total", "x", ("outcome",))\n'
        'B = metrics.DEFAULT.histogram("rebalance_score_improvement", "x")\n'
        'C = metrics.DEFAULT.histogram('
        '"rebalance_moves_per_improvement", "x")\n'
        'D = metrics.DEFAULT.counter("rebalance_stranded_pods_total", "x")\n'
    )
    proc = _ktlint_kt005(root, good)
    assert proc.returncode == 0, proc.stderr
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "b.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.gauge("rebalance_churn", "x")\n'
    )
    proc = _ktlint_kt005(root, bad)
    assert proc.returncode == 1
    assert "lacks a unit suffix" in proc.stderr


def test_lint_metrics_knows_autoscaler_names(tmp_path):
    """The autoscaler family (controllers/autoscaler.py) is known to
    the linter: autoscaler_scale_events_total passes the standard rule
    on its own, the unitless per-pool size gauge is explicitly
    allowlisted, and a novel suffix-less autoscaler name still fails."""
    from tools.ktlint.rules_metrics import ALLOWLIST, AUTOSCALER_METRICS

    assert AUTOSCALER_METRICS == {
        "autoscaler_pool_size",
        "autoscaler_scale_events_total",
    }
    assert AUTOSCALER_METRICS <= ALLOWLIST
    root = pathlib.Path(__file__).resolve().parent.parent
    good = tmp_path / "good"
    good.mkdir()
    (good / "g.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.gauge("autoscaler_pool_size", "x", ("pool",))\n'
        'B = metrics.DEFAULT.counter('
        '"autoscaler_scale_events_total", "x", ("direction",))\n'
        'C = metrics.DEFAULT.counter('
        '"autoscaler_syncs_total", "x", ("result",))\n'
    )
    proc = _ktlint_kt005(root, good)
    assert proc.returncode == 0, proc.stderr
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "b.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.gauge("autoscaler_backlog", "x")\n'
    )
    proc = _ktlint_kt005(root, bad)
    assert proc.returncode == 1
    assert "lacks a unit suffix" in proc.stderr


def test_rebalance_metrics_exposed():
    """Exposition golden for the rebalance-plane family: the
    improvement histogram renders cumulative +le buckets on the ratio
    ladder, the moves-per-improvement efficiency histogram lands on
    the default ladder, and the move counter carries its outcome
    label with declared type."""
    from kubernetes_tpu.utils import rebalance as rebmod

    rebmod.MOVES.inc(outcome="evicted")
    rebmod.MOVES.inc(outcome="rebound")
    rebmod.IMPROVEMENT.observe(0.35)
    rebmod.MOVES_PER_IMPROVEMENT.observe(7.0)
    rebmod.STRANDED.inc()
    text = metrics.DEFAULT.render()
    assert "# TYPE rebalance_moves_total counter" in text
    assert 'rebalance_moves_total{outcome="evicted"} 1.0' in text
    assert 'rebalance_moves_total{outcome="rebound"} 1.0' in text
    assert "# TYPE rebalance_score_improvement histogram" in text
    assert 'rebalance_score_improvement_bucket{le="0.4"}' in text
    assert 'rebalance_score_improvement_bucket{le="+Inf"}' in text
    assert "# TYPE rebalance_moves_per_improvement histogram" in text
    assert 'rebalance_moves_per_improvement_bucket{le="10"}' in text
    assert "# TYPE rebalance_stranded_pods_total counter" in text


def test_autoscaler_metrics_exposed():
    """Exposition golden for the autoscaler family: the per-pool size
    gauge escapes hostile pool label values (an operator-named pool
    can never corrupt the exposition) and the scale-events counter
    carries its direction label with declared type."""
    from kubernetes_tpu.controllers.autoscaler import (
        POOL_SIZE,
        SCALE_EVENTS,
    )

    POOL_SIZE.set(3.0, pool='we"ird\\pool\nx')
    SCALE_EVENTS.inc(direction="up")
    SCALE_EVENTS.inc(direction="down")
    text = metrics.DEFAULT.render()
    assert "# TYPE autoscaler_pool_size gauge" in text
    # Label escaping on the pool label.
    assert 'autoscaler_pool_size{pool="we\\"ird\\\\pool\\nx"} 3.0' in text
    assert "# TYPE autoscaler_scale_events_total counter" in text
    assert 'autoscaler_scale_events_total{direction="up"} 1.0' in text
    assert 'autoscaler_scale_events_total{direction="down"} 1.0' in text


def test_lint_metrics_knows_replication_names(tmp_path):
    """The HA control-plane family (store/replication.py,
    utils/lease.py, scheduler/standby.py) is known to the linter:
    leader_elections_total and the activation summary pass the
    standard rule on their own, the unitless commit-index watermark
    and follower-lag version count are explicitly allowlisted, and a
    novel suffix-less replication name still fails (the allowlist
    names metrics, not a prefix)."""
    from tools.ktlint.rules_metrics import ALLOWLIST, REPLICATION_METRICS

    assert REPLICATION_METRICS == {
        "replication_commit_index",
        "replication_follower_lag_versions",
        "leader_elections_total",
        "scheduler_standby_activation_seconds",
    }
    assert REPLICATION_METRICS <= ALLOWLIST
    root = pathlib.Path(__file__).resolve().parent.parent
    good = tmp_path / "good"
    good.mkdir()
    (good / "g.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.gauge('
        '"replication_commit_index", "x", ("role",))\n'
        'B = metrics.DEFAULT.gauge('
        '"replication_follower_lag_versions", "x", ("follower",))\n'
        'C = metrics.DEFAULT.counter('
        '"leader_elections_total", "x", ("tier",))\n'
        'D = metrics.DEFAULT.summary('
        '"scheduler_standby_activation_seconds", "x")\n'
    )
    proc = _ktlint_kt005(root, good)
    assert proc.returncode == 0, proc.stderr
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "b.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.gauge("replication_backlog", "x")\n'
    )
    proc = _ktlint_kt005(root, bad)
    assert proc.returncode == 1
    assert "lacks a unit suffix" in proc.stderr


def test_replication_metrics_exposed():
    """Exposition golden for the HA control-plane family: commit
    index renders per role with declared gauge type, the follower-lag
    gauge escapes hostile follower names (a link name can never
    corrupt the exposition), and the per-tier election counter
    renders with declared counter type."""
    from kubernetes_tpu.store.replication import COMMIT_INDEX, FOLLOWER_LAG
    from kubernetes_tpu.utils.lease import ELECTIONS

    COMMIT_INDEX.set(42.0, role="leader")
    COMMIT_INDEX.set(40.0, role="follower:f1")
    FOLLOWER_LAG.set(2.0, follower='f"1\\x\ny')
    # The counter is process-global: earlier elector tests in the
    # suite may have counted real elections already — golden on the
    # delta, not an absolute.
    sched_base = ELECTIONS.value(tier="scheduler")
    kv_base = ELECTIONS.value(tier="kvstore")
    ELECTIONS.inc(tier="scheduler")
    ELECTIONS.inc(tier="kvstore")
    text = metrics.DEFAULT.render()
    assert "# TYPE replication_commit_index gauge" in text
    assert 'replication_commit_index{role="leader"} 42.0' in text
    assert 'replication_commit_index{role="follower:f1"} 40.0' in text
    assert "# TYPE replication_follower_lag_versions gauge" in text
    # Label escaping on the follower label.
    assert (
        'replication_follower_lag_versions{follower="f\\"1\\\\x\\ny"} 2.0'
        in text
    )
    assert "# TYPE leader_elections_total counter" in text
    assert (
        f'leader_elections_total{{tier="scheduler"}} {sched_base + 1.0}'
        in text
    )
    assert (
        f'leader_elections_total{{tier="kvstore"}} {kv_base + 1.0}' in text
    )


def test_lint_metrics_knows_health_names(tmp_path):
    """The health-plane family (utils/timeseries.py, utils/alerts.py,
    utils/lease.py) is known to the linter: the sample counter /
    sample-latency histogram / transition counter / renew-latency
    histogram pass the standard rule on their own, the unitless
    retained-series gauge and per-rule firing state gauge are
    explicitly allowlisted, and a novel suffix-less alert name still
    fails (the allowlist names metrics, not a prefix)."""
    from tools.ktlint.rules_metrics import ALLOWLIST, HEALTH_METRICS

    assert HEALTH_METRICS == {
        "timeseries_samples_total",
        "timeseries_retained_series",
        "timeseries_sample_seconds",
        "alerts_firing",
        "alert_transitions_total",
        "lease_renew_latency_seconds",
    }
    assert HEALTH_METRICS <= ALLOWLIST
    root = pathlib.Path(__file__).resolve().parent.parent
    good = tmp_path / "good"
    good.mkdir()
    (good / "g.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.counter("timeseries_samples_total", "x")\n'
        'B = metrics.DEFAULT.gauge("timeseries_retained_series", "x")\n'
        'C = metrics.DEFAULT.histogram("timeseries_sample_seconds", "x")\n'
        'D = metrics.DEFAULT.gauge("alerts_firing", "x", ("rule",))\n'
        'E = metrics.DEFAULT.counter('
        '"alert_transitions_total", "x", ("rule", "state"))\n'
        'F = metrics.DEFAULT.histogram('
        '"lease_renew_latency_seconds", "x", ("op",))\n'
    )
    proc = _ktlint_kt005(root, good)
    assert proc.returncode == 0, proc.stderr
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "b.py").write_text(
        "from kubernetes_tpu.utils import metrics\n"
        'A = metrics.DEFAULT.gauge("alerts_pending", "x")\n'
    )
    proc = _ktlint_kt005(root, bad)
    assert proc.returncode == 1
    assert "lacks a unit suffix" in proc.stderr


def test_health_metrics_exposed():
    """Exposition golden for the health-plane family: the sampler's
    counter/gauge/histogram render with declared types, the per-rule
    firing gauge escapes hostile rule names, and the transition
    counter renders its (rule, state) pair. Process-global counters
    may have been moved by earlier suites — golden on deltas."""
    from kubernetes_tpu.utils.alerts import FIRING, TRANSITIONS
    from kubernetes_tpu.utils.lease import RENEW_LATENCY
    from kubernetes_tpu.utils.timeseries import (
        RETAINED,
        SAMPLE_SECONDS,
        SAMPLES,
    )

    samples_base = SAMPLES.value()
    SAMPLES.inc()
    RETAINED.set(7.0)
    SAMPLE_SECONDS.observe(0.002)
    FIRING.set(1.0, rule='r"1\\x\ny')
    trans_base = TRANSITIONS.value(rule="bind_latency_burn", state="firing")
    TRANSITIONS.inc(rule="bind_latency_burn", state="firing")
    RENEW_LATENCY.observe(0.01, op="renew")
    text = metrics.DEFAULT.render()
    assert "# TYPE timeseries_samples_total counter" in text
    assert f"timeseries_samples_total {samples_base + 1.0}" in text
    assert "# TYPE timeseries_retained_series gauge" in text
    assert "timeseries_retained_series 7.0" in text
    assert "# TYPE timeseries_sample_seconds histogram" in text
    assert "timeseries_sample_seconds_bucket" in text
    assert "# TYPE alerts_firing gauge" in text
    # Label escaping on the rule label.
    assert 'alerts_firing{rule="r\\"1\\\\x\\ny"} 1.0' in text
    assert "# TYPE alert_transitions_total counter" in text
    assert (
        f'alert_transitions_total{{rule="bind_latency_burn",'
        f'state="firing"}} {trans_base + 1.0}' in text
    )
    assert "# TYPE lease_renew_latency_seconds histogram" in text
    assert 'lease_renew_latency_seconds_count{op="renew"}' in text
