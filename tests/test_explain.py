"""Scheduling flight recorder end to end: ring mechanics, the explain
readback's bounded verdict shape, both batch daemons feeding decisions
(joined with trace ids), the /debug/decisions + /debug/solves HTTP
surfaces, `ktctl explain`, and the solver convergence telemetry."""

import json
import time
import urllib.request

import numpy as np
import pytest

from kubernetes_tpu.client import Client, HTTPTransport, LocalTransport
from kubernetes_tpu.scheduler.daemon import (
    BatchScheduler,
    IncrementalBatchScheduler,
    SchedulerConfig,
)
from kubernetes_tpu.server import APIServer
from kubernetes_tpu.server.httpserver import APIHTTPServer
from kubernetes_tpu.utils import flightrecorder, tracing

pytestmark = pytest.mark.explain

SCHED_TIMEOUT = 60.0


@pytest.fixture(autouse=True)
def _clean_recorder():
    flightrecorder.configure(
        ring=4096, solve_ring=512, explain_top_k=3,
        explain_failed_nodes=16, explain_limit=64,
    )
    flightrecorder.DEFAULT.clear()
    tracing.configure(sample_rate=1.0, log_threshold_s=0.0)
    tracing.DEFAULT_BUFFER.clear()
    yield
    flightrecorder.configure(
        ring=4096, solve_ring=512, explain_top_k=3,
        explain_failed_nodes=16, explain_limit=64,
    )
    flightrecorder.DEFAULT.clear()
    tracing.DEFAULT_BUFFER.clear()


def pod_wire(name, selector=None, cpu="100m"):
    return {
        "kind": "Pod",
        "metadata": {"name": name, "namespace": "default"},
        "spec": {
            "nodeSelector": selector or {},
            "containers": [
                {"name": "c", "image": "nginx",
                 "resources": {"limits": {"cpu": cpu, "memory": "64Mi"}}}
            ],
        },
    }


def node_wire(name):
    return {
        "kind": "Node",
        "metadata": {"name": name},
        "status": {
            "capacity": {"cpu": "4", "memory": "8Gi", "pods": "110"},
            "conditions": [{"type": "Ready", "status": "True"}],
        },
    }


class TestFlightRecorderMechanics:
    def test_ring_is_bounded_newest_win(self):
        flightrecorder.configure(ring=8)
        flightrecorder.DEFAULT.record(
            flightrecorder.Decision(
                pod=f"default/p{i}", tick=1, trace_id="t", mode="scan",
                outcome="bound", node="n0",
            )
            for i in range(20)
        )
        size, cap = flightrecorder.DEFAULT.ring_stats()
        assert size == cap == 8
        got = flightrecorder.DEFAULT.decisions(limit=100)["decisions"]
        # Newest first, oldest 12 evicted.
        assert [d["pod"] for d in got] == [
            f"default/p{i}" for i in range(19, 11, -1)
        ]

    def test_limit_zero_returns_nothing(self):
        flightrecorder.DEFAULT.record(
            [
                flightrecorder.Decision(
                    pod="default/p0", tick=1, trace_id="", mode="scan",
                    outcome="bound", node="n0",
                )
            ]
        )
        flightrecorder.DEFAULT.record_solve(
            flightrecorder.SolveRecord(
                tick=1, trace_id="", mode="scan", pods=1, duration_s=0.1,
            )
        )
        assert flightrecorder.DEFAULT.decisions(limit=0)["decisions"] == []
        assert flightrecorder.DEFAULT.decisions(limit=-3)["decisions"] == []
        assert flightrecorder.DEFAULT.solves(limit=0)["solves"] == []

    def test_last_solve_telemetry_is_consume_once(self):
        flightrecorder.observe_solve_telemetry(
            "sinkhorn", 24, residual=0.5, waves=3
        )
        tele = flightrecorder.take_last_solve_telemetry()
        assert tele == {
            "mode": "sinkhorn", "iterations": 24, "waves": 3,
            "residual": 0.5,
        }
        assert flightrecorder.take_last_solve_telemetry() is None

    def test_pod_filter_matches_key_and_bare_name(self):
        flightrecorder.DEFAULT.record(
            [
                flightrecorder.Decision(
                    pod="ns1/web", tick=1, trace_id="", mode="scan",
                    outcome="bound", node="n0",
                ),
                flightrecorder.Decision(
                    pod="ns2/web", tick=1, trace_id="", mode="scan",
                    outcome="unschedulable",
                ),
            ]
        )
        by_key = flightrecorder.DEFAULT.decisions(pod="ns1/web")["decisions"]
        assert [d["pod"] for d in by_key] == ["ns1/web"]
        by_name = flightrecorder.DEFAULT.decisions(pod="web")["decisions"]
        assert {d["pod"] for d in by_name} == {"ns1/web", "ns2/web"}

    def test_preemption_amends_latest_decision(self):
        before = flightrecorder.DECISIONS_TOTAL.value(
            outcome="preempt_nominated"
        )
        flightrecorder.DEFAULT.record(
            [
                flightrecorder.Decision(
                    pod="default/hi", tick=3, trace_id="abc", mode="scan",
                    outcome="unschedulable",
                )
            ]
        )
        flightrecorder.DEFAULT.record_preemption(
            "default/hi", "preempt_nominated", node="n2",
            victims=("default/lo",),
        )
        got = flightrecorder.DEFAULT.decisions(pod="default/hi")["decisions"]
        assert len(got) == 1  # amended in place, not appended
        assert got[0]["outcome"] == "preempt_nominated"
        assert got[0]["nominatedNode"] == "n2"
        assert got[0]["victims"] == ["default/lo"]
        assert got[0]["traceId"] == "abc"  # join with /debug/traces survives
        assert (
            flightrecorder.DECISIONS_TOTAL.value(outcome="preempt_nominated")
            == before + 1
        )


class TestExplainBacklogShape:
    def test_infeasible_pod_reasons_and_counts(self):
        from kubernetes_tpu.ops.pipeline import explain_backlog
        from tests.test_solver_parity import mk_node, mk_pod

        nodes = [mk_node(f"n{j}") for j in range(5)]
        entries = explain_backlog(
            [mk_pod("stuck", selector={"disk": "ssd"})], nodes,
            max_failed=2,
        )
        (entry,) = entries
        assert entry["pod"] == "default/stuck"
        assert entry["feasibleNodes"] == 0
        assert entry["totalNodes"] == 5
        # Only max_failed nodes listed individually; counts cover ALL.
        assert len(entry["nodes"]) == 2
        assert all(
            v["reasons"] == ["MatchNodeSelector"] for v in entry["nodes"]
        )
        assert entry["reasonCounts"] == {"MatchNodeSelector": 5}

    def test_feasible_pod_topk_scores_decompose(self):
        from kubernetes_tpu.ops.pipeline import explain_backlog
        from tests.test_solver_parity import mk_node, mk_pod

        loaded = mk_pod("a0", cpu=3000, mem_mib=4096)
        loaded.spec.node_name = "n0"
        nodes = [mk_node(f"n{j}") for j in range(4)]
        entries = explain_backlog(
            [mk_pod("p0")], nodes, assigned=[loaded], top_k=2,
        )
        (entry,) = entries
        assert entry["feasibleNodes"] == 4
        winners = [v for v in entry["nodes"] if v["ok"]]
        assert len(winners) == 2
        # Ranked by score desc; the loaded node can't head the list.
        assert winners[0]["score"] >= winners[1]["score"]
        assert winners[0]["node"] != "n0"
        for v in winners:
            assert v["score"] == sum(v["components"].values())
            assert set(v["components"]) == {
                "leastRequested", "balanced", "spreading",
            }


def _explain_cluster(kind, seed):
    from tests.test_pallas_scan import banded_cluster
    from tests.test_solver_parity import random_cluster

    if kind == "random":
        return random_cluster(seed)
    return banded_cluster(seed, n_services=20, n_nodes=12, n_pods=30, multi=0.3)


class TestSessionExplain:
    """SolverSession.explain — the source of the deferred bound-pod
    tables — against the snapshot path (explain_matrix) on the same
    cluster, node by node."""

    @staticmethod
    def _columns(names, bits, comps):
        cols = {n: j for j, n in enumerate(names)}
        return cols, bits, comps

    def _assert_same(self, got, want):
        (gcols, gbits, gcomps), (wcols, wbits, wcomps) = got, want
        assert set(gcols) == set(wcols)
        g = [gcols[n] for n in wcols]
        w = [wcols[n] for n in wcols]
        assert np.array_equal(gbits[:, g], wbits[:, w])
        for k in wcomps:
            assert np.array_equal(gcomps[k][:, g], wcomps[k][:, w]), k

    @pytest.mark.parametrize("release", [False, True])
    @pytest.mark.parametrize(
        "kind,seed", [("random", 0), ("random", 5), ("services", 7)]
    )
    def test_session_explain_matches_snapshot_path(self, kind, seed, release):
        """Pending pods against the cluster as it stands; with
        ``release``, bound pods against the cluster less their own
        charges (the view before their bind)."""
        import copy

        from kubernetes_tpu.ops import SolverSession
        from kubernetes_tpu.ops.pipeline import explain_matrix

        pods, nodes, assigned, services = _explain_cluster(kind, seed)
        session = SolverSession(nodes, services=services, assigned=assigned)
        keys = []
        others = assigned
        if release:
            pods = []
            for a in assigned[::2]:
                p = copy.deepcopy(a)
                p.spec.node_name = ""
                pods.append(p)
                keys.append(f"default/{a.metadata.name}")
            others = assigned[1::2]
        got = session.explain(pods, release=keys)
        want = explain_matrix(pods, nodes, others, services)
        self._assert_same(self._columns(*got), self._columns(*want))
        if release:
            # The release is a view: the mirror keeps every charge.
            again = SolverSession(nodes, services=services, assigned=assigned)
            for k in session.h:
                assert np.array_equal(session.h[k], again.h[k]), k


class TestDecisionsEndToEnd:
    def _schedule(self, incremental=False):
        api = APIServer()
        client = Client(LocalTransport(api))
        for j in range(5):
            client.create("nodes", node_wire(f"n{j}"))
        for i in range(6):
            client.create("pods", pod_wire(f"xp{i}"))
        client.create("pods", pod_wire("stuck", selector={"disk": "ssd"}))
        cfg = SchedulerConfig(
            Client(LocalTransport(api)),
            raw_scheduled_cache=incremental,
        ).start()
        assert cfg.wait_for_sync(timeout=SCHED_TIMEOUT)
        sched = (
            IncrementalBatchScheduler(cfg)
            if incremental
            else BatchScheduler(cfg)
        )
        deadline = time.monotonic() + SCHED_TIMEOUT
        while time.monotonic() < deadline:
            sched.schedule_batch(timeout=0.5)
            pods, _ = client.list("pods")
            if sum(1 for p in pods if p.spec.node_name) >= 6:
                break
        cfg.stop()
        assert sum(1 for p in pods if p.spec.node_name) >= 6
        return api, client

    def _assert_recorded(self):
        bound = flightrecorder.DEFAULT.decisions(pod="default/xp3")
        assert bound["decisions"], "no decision recorded for xp3"
        d = bound["decisions"][0]
        assert d["outcome"] == "bound"
        assert d["node"].startswith("n")
        assert d["traceId"]
        assert d["feasibleNodes"] >= 1
        winner = next(v for v in d["nodes"] if v["ok"])
        assert winner["score"] == sum(winner["components"].values())
        stuck = flightrecorder.DEFAULT.decisions(pod="default/stuck")
        s = stuck["decisions"][0]
        assert s["outcome"] == "unschedulable"
        assert s["feasibleNodes"] == 0
        assert s["reasonCounts"].get("MatchNodeSelector") == 5
        # The solve record joins by trace id.
        solves = flightrecorder.DEFAULT.solves()["solves"]
        assert any(r["traceId"] == d["traceId"] for r in solves)
        return d

    def test_batch_daemon_records_decisions(self):
        self._schedule()
        d = self._assert_recorded()
        assert d["mode"] == "scan"

    def test_incremental_daemon_records_decisions(self):
        self._schedule(incremental=True)
        self._assert_recorded()
        solves = flightrecorder.DEFAULT.solves()["solves"]
        assert any(r.get("incremental") for r in solves)

    def test_debug_endpoints_and_ktctl(self, capsys):
        from kubernetes_tpu.cli import ktctl

        api, client = self._schedule()
        http = APIHTTPServer(api).start()
        try:
            with urllib.request.urlopen(
                http.address + "/debug/decisions?pod=xp2", timeout=10
            ) as resp:
                data = json.loads(resp.read())
            with urllib.request.urlopen(
                http.address + "/debug/solves", timeout=10
            ) as resp:
                solves = json.loads(resp.read())
            assert data["kind"] == "DecisionList"
            assert data["decisions"][0]["pod"] == "default/xp2"
            assert solves["kind"] == "SolveList"
            assert solves["solves"], "no solve records served"
            # ktctl explain over HTTP renders the verdict table.
            hclient = Client(HTTPTransport(http.address))
            rc = ktctl.main(["explain", "pod", "xp2"], client=hclient)
        finally:
            http.stop(release_store=False)
        assert rc == 0
        out = capsys.readouterr().out
        assert "DECISION default/xp2" in out
        assert "outcome bound" in out
        assert "feasible" in out and "score" in out

        # ktctl explain for the stuck pod: per-predicate reasons.
        rc = ktctl.main(["explain", "pod", "stuck"], client=client)
        assert rc == 0
        out = capsys.readouterr().out
        assert "MatchNodeSelector" in out

        # Unknown pod: clean nonzero exit, nothing on stdout.
        rc = ktctl.main(["explain", "pod", "no-such-pod"], client=client)
        captured = capsys.readouterr()
        assert rc == 1
        assert captured.out == ""
        assert 'no decision recorded for pod "no-such-pod"' in captured.err

    def test_decision_counter_moves(self):
        before = flightrecorder.DECISIONS_TOTAL.value(outcome="bound")
        self._schedule()
        assert flightrecorder.DECISIONS_TOTAL.value(outcome="bound") >= (
            before + 6
        )


class TestSolveTelemetry:
    def test_sinkhorn_stats_and_metrics(self):
        from kubernetes_tpu.models.columnar import build_snapshot
        from kubernetes_tpu.ops import device_snapshot
        from kubernetes_tpu.ops.sinkhorn import (
            sinkhorn_assignments,
            solve_sinkhorn_stats,
        )
        from tests.test_solver_parity import mk_node, mk_pod

        pods = [mk_pod(f"p{i}", cpu=200) for i in range(12)]
        nodes = [mk_node(f"n{j}") for j in range(3)]
        d = device_snapshot(build_snapshot(pods, nodes))
        a, waves, titers, residual = solve_sinkhorn_stats(
            d.pods, d.nodes, window=8
        )
        assert int(waves) >= 1
        assert int(titers) >= 1
        assert float(residual) >= 0.0
        before = flightrecorder.SOLVE_ITERATIONS.count(mode="sinkhorn")
        d2 = device_snapshot(build_snapshot(pods, nodes))
        assign, wave_count = sinkhorn_assignments(d2, window=8)
        assert wave_count >= 1
        assert (
            flightrecorder.SOLVE_ITERATIONS.count(mode="sinkhorn")
            == before + 1
        )

    def test_wave_iterations_observed(self):
        from kubernetes_tpu.models.columnar import build_snapshot
        from kubernetes_tpu.ops import device_snapshot
        from kubernetes_tpu.ops.wave import wave_assignments
        from tests.test_solver_parity import mk_node, mk_pod

        pods = [mk_pod(f"p{i}") for i in range(6)]
        nodes = [mk_node(f"n{j}") for j in range(2)]
        before = flightrecorder.SOLVE_ITERATIONS.count(mode="wave")
        wave_assignments(device_snapshot(build_snapshot(pods, nodes)))
        assert (
            flightrecorder.SOLVE_ITERATIONS.count(mode="wave") == before + 1
        )
