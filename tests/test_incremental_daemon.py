"""IncrementalBatchScheduler e2e: the session-backed daemon keeps its
device-resident cluster state in step with watch deltas while binding
through the real control plane.

Reference analog: the scheduler's watch-fed caches are its incremental
state (plugin/pkg/scheduler/factory/factory.go:180-193); here the same
deltas patch device-resident node rows (ops/incremental.SolverSession).
"""

import time

import pytest

from kubernetes_tpu.client import Client, LocalTransport
from kubernetes_tpu.scheduler.daemon import (
    IncrementalBatchScheduler,
    SchedulerConfig,
)
from kubernetes_tpu.server.api import APIServer


def wait_until(cond, timeout=30.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


def node_wire(name, cpu="4", mem="8Gi", labels=None):
    return {
        "kind": "Node",
        "metadata": {"name": name, "labels": labels or {}},
        "status": {
            "capacity": {"cpu": cpu, "memory": mem, "pods": "110"},
            "conditions": [{"type": "Ready", "status": "True"}],
        },
    }


def pod_wire(name, cpu="100m", mem="64Mi", node_selector=None):
    return {
        "kind": "Pod",
        "metadata": {"name": name, "namespace": "default"},
        "spec": {
            "containers": [
                {
                    "name": "c",
                    "image": "pause",
                    "resources": {"limits": {"cpu": cpu, "memory": mem}},
                }
            ],
            **({"nodeSelector": node_selector} if node_selector else {}),
        },
    }


@pytest.fixture
def api():
    return APIServer()


@pytest.fixture
def client(api):
    return Client(LocalTransport(api))


@pytest.fixture
def sched(client):
    config = SchedulerConfig(client).start()
    assert config.wait_for_sync()
    s = IncrementalBatchScheduler(config).start()
    yield s
    s.stop()


def bound_node(client, name):
    pod = client.get("pods", name, namespace="default")
    return pod.spec.node_name


class TestIncrementalDaemon:
    def test_binds_pending_pods(self, client, sched):
        for i in range(3):
            client.create("nodes", node_wire(f"n{i}"))
        for i in range(10):
            client.create("pods", pod_wire(f"p{i}"), namespace="default")
        assert wait_until(
            lambda: all(bound_node(client, f"p{i}") for i in range(10))
        )
        # Spread across nodes (LeastRequested moves as nodes fill).
        nodes = {bound_node(client, f"p{i}") for i in range(10)}
        assert len(nodes) == 3

    def test_large_session_build_freezes_heap(self, client, monkeypatch):
        """A session built over FREEZE_HEAP_PODS bound pods or more moves
        the heap out of the cyclic collector's reach; a smaller one
        leaves the collector as it was."""
        import gc

        client.create("nodes", node_wire("n0"))
        for i in range(3):
            wire = pod_wire(f"b{i}")
            wire["spec"]["nodeName"] = "n0"
            client.create("pods", wire, namespace="default")
        config = SchedulerConfig(client).start()
        try:
            assert config.wait_for_sync()
            daemon = IncrementalBatchScheduler(config)
            gc.unfreeze()
            daemon._build_session()
            assert gc.get_freeze_count() == 0
            monkeypatch.setattr(IncrementalBatchScheduler, "FREEZE_HEAP_PODS", 3)
            daemon._build_session()
            assert gc.get_freeze_count() > 0
        finally:
            gc.unfreeze()
            config.stop()

    def test_delete_frees_occupancy(self, client, sched):
        # One node that fits exactly two pods' CPU.
        client.create("nodes", node_wire("solo", cpu="1"))
        client.create("pods", pod_wire("a", cpu="500m"), namespace="default")
        client.create("pods", pod_wire("b", cpu="500m"), namespace="default")
        assert wait_until(
            lambda: bound_node(client, "a") and bound_node(client, "b")
        )
        # Full: c cannot fit until a is deleted.
        client.create("pods", pod_wire("c", cpu="500m"), namespace="default")
        time.sleep(0.5)
        assert bound_node(client, "c") is None or bound_node(client, "c") == ""
        client.delete("pods", "a", namespace="default")
        # The backoff requeue re-fetches c; the session's freed row
        # accepts it.
        assert wait_until(lambda: bound_node(client, "c") == "solo", timeout=20)

    def test_node_churn_through_watch(self, client, sched):
        client.create("nodes", node_wire("n0", labels={"zone": "a"}))
        client.create(
            "pods",
            pod_wire("sel", node_selector={"zone": "b"}),
            namespace="default",
        )
        time.sleep(0.4)
        assert not bound_node(client, "sel")
        # A node satisfying the selector joins AFTER the session built:
        # the upsert must ride the watch into the device state.
        client.create("nodes", node_wire("n1", labels={"zone": "b"}))
        assert wait_until(lambda: bound_node(client, "sel") == "n1", timeout=20)
        # Node removal empties its row: new pods avoid the gone node.
        client.delete("nodes", "n1")
        # The DELETED delta rides its own watch stream; a micro-tick
        # for a pod created in the same instant could legitimately
        # solve against the last-known cluster view (the reference's
        # cache-driven scheduler has the identical race). The contract
        # under test is the ROW EMPTYING, so wait for the session to
        # absorb the removal (the delta wake applies it promptly).
        assert wait_until(
            lambda: sched._session is None
            or "n1" not in sched._session.node_index
        )
        client.create(
            "pods",
            pod_wire("sel2", node_selector={"zone": "b"}),
            namespace="default",
        )
        time.sleep(0.5)
        assert not bound_node(client, "sel2")

    def test_service_change_resyncs_session(self, client, sched):
        client.create("nodes", node_wire("n0"))
        client.create("pods", pod_wire("before"), namespace="default")
        assert wait_until(lambda: bound_node(client, "before"))
        # New service invalidates the frozen service set; the daemon
        # must rebuild and keep scheduling.
        client.create(
            "services",
            {
                "kind": "Service",
                "metadata": {"name": "svc", "namespace": "default"},
                "spec": {"selector": {"app": "x"}, "ports": [{"port": 80}]},
            },
            namespace="default",
        )
        client.create("pods", pod_wire("after"), namespace="default")
        assert wait_until(lambda: bound_node(client, "after"))
        assert sched._session is not None or True  # rebuilt lazily

    def test_survives_many_ticks_with_churn(self, client, sched):
        for i in range(4):
            client.create("nodes", node_wire(f"n{i}"))
        # Sustained create/delete across multiple ticks.
        for round_ in range(5):
            for i in range(8):
                client.create(
                    "pods", pod_wire(f"r{round_}-{i}"), namespace="default"
                )
            assert wait_until(
                lambda r=round_: all(
                    bound_node(client, f"r{r}-{i}") for i in range(8)
                )
            ), f"round {round_} did not fully bind"
            for i in range(0, 8, 2):
                client.delete("pods", f"r{round_}-{i}", namespace="default")
        # The daemon never fell back to full-relower mode.
        assert sched.fallback_count == 0

    def test_foreign_bind_race_no_double_charge(self, client):
        """Round-5 review regression: a drained pod that was bound
        ELSEWHERE (HA overlap) must not be fed to solve() — the session
        already charged it via the watch, and a second placement plus
        409 rollback would orphan the true charge (phantom occupancy)."""
        from kubernetes_tpu.scheduler.daemon import (
            IncrementalBatchScheduler,
            SchedulerConfig,
        )

        config = SchedulerConfig(client).start()
        assert config.wait_for_sync()
        sched = IncrementalBatchScheduler(config)  # NOT started: manual ticks
        try:
            client.create("nodes", node_wire("n0"))
            client.create("nodes", node_wire("n1"))
            client.create("pods", pod_wire("a"), namespace="default")
            assert wait_until(lambda: len(config.pod_queue) >= 1)
            assert sched.schedule_batch(timeout=1) >= 1  # session built
            session = sched._session
            assert session is not None

            # Pod b: created, then bound by "another scheduler".
            client.create("pods", pod_wire("b"), namespace="default")
            assert wait_until(lambda: len(config.pod_queue) >= 1)
            stale_b = config.pod_queue.pop(timeout=2)  # drained pre-bind
            assert stale_b is not None and not stale_b.spec.node_name
            client.bind("b", "n1", namespace="default")
            # Wait for the bind's watch delta to reach the event queue.
            assert wait_until(
                lambda: any(
                    k == "pod" and sched._obj_key(o).endswith("/b")
                    for k, _e, o in list(sched._event_q)
                )
            )
            # Simulate the race: the stale spec re-enters the queue as
            # if drained concurrently with the bind.
            config.pod_queue.add(stale_b)
            sched.schedule_batch(timeout=1)
            assert bound_node(client, "b") == "n1"  # foreign bind stands
            # No phantom: session occupancy rows exactly mirror
            # _pod_node (an orphaned charge would break this).
            tracked = sum(len(l) for l in session._assigned)
            assert tracked == len(session._pod_node) == 2
            # And b's charge is releasable (not orphaned).
            client.delete("pods", "b", namespace="default")
            assert wait_until(
                lambda: (sched.schedule_batch(timeout=0.1) or True)
                and not session.has_assigned("default/b")
            )
            assert sum(len(l) for l in session._assigned) == len(
                session._pod_node
            ) == 1
        finally:
            sched.stop()

    def test_parity_with_full_relower(self, client):
        """The session's decisions match the plain batch scan on the
        same workload (both replay sequential-parity semantics)."""
        from kubernetes_tpu.models import serde
        from kubernetes_tpu.models.objects import Node, Pod
        from kubernetes_tpu.scheduler.batch import schedule_backlog_tpu

        nodes = [serde.from_wire(Node, node_wire(f"n{i}")) for i in range(5)]
        pods = [
            serde.from_wire(Pod, pod_wire(f"p{i}", cpu=f"{100 + 50 * (i % 3)}m"))
            for i in range(20)
        ]
        full = schedule_backlog_tpu(pods, nodes)

        from kubernetes_tpu.ops import SolverSession

        session = SolverSession(nodes)
        for p in pods:
            session.add_pending(p)
        inc = [dest for _k, dest in session.solve()]
        assert inc == full


class TestOnePodWatch:
    def test_pod_stream_dropped_mid_burst_keeps_decisions_right(self, api, client):
        """Four nodes are full of bound pods; a bulk delete frees them
        all just before a bulk create brings as many new pods. The
        stream that carries the deletes is dropped at their first push,
        three times over (WATCH_DROP), so it resumes, and then re-lists,
        only after backing off. The scheduler's one pod watch carries
        the new pods behind the deletes, so each new pod lands on its
        first solve, two to a node, and none is ever found
        unschedulable on nodes whose deletes it has not seen."""
        from kubernetes_tpu.scheduler import daemon
        from kubernetes_tpu.store import watch as watchmod
        from kubernetes_tpu.utils import faults

        for i in range(4):
            client.create("nodes", node_wire(f"n{i}", cpu="1"))
        old = []
        for i in range(8):
            p = pod_wire(f"old{i}", cpu="500m")
            p["spec"]["nodeName"] = f"n{i % 4}"
            old.append(p)
        client.create_bulk("pods", old, namespace="default")
        config = SchedulerConfig(client).start()
        assert config.wait_for_sync()
        sched = IncrementalBatchScheduler(config).start()
        unschedulable0 = daemon._SCHEDULED.value(result="unschedulable")
        dropped0 = watchmod.STREAMS_DROPPED.value(resource="pods")
        try:
            assert wait_until(lambda: len(config.scheduled_pods) == 8)
            time.sleep(0.2)  # quiet: the next push is the first delete's
            rule = faults.inject(faults.WATCH_DROP, every=1, times=3)
            client.delete_bulk("pods", [f"old{i}" for i in range(8)], namespace="default")
            client.create_bulk(
                "pods", [pod_wire(f"new{i}", cpu="500m") for i in range(8)],
                namespace="default",
            )
            assert wait_until(
                lambda: all(bound_node(client, f"new{i}") for i in range(8))
            )
            assert rule.fired == 3
            assert watchmod.STREAMS_DROPPED.value(resource="pods") > dropped0
        finally:
            faults.clear()
            faults.reset_stats()
            sched.stop()
            config.stop()
        assert daemon._SCHEDULED.value(result="unschedulable") == unschedulable0
        per_node = {}
        for i in range(8):
            node = bound_node(client, f"new{i}")
            per_node[node] = per_node.get(node, 0) + 1
        assert per_node == {f"n{i}": 2 for i in range(4)}
