"""Scheduler end-to-end: real apiserver + caches + daemon loop
(reference analog: plugin/pkg/scheduler/scheduler_test.go +
test/integration/scheduler_test.go)."""

import time

import pytest

from kubernetes_tpu.client import Client, LocalTransport
from kubernetes_tpu.scheduler.daemon import Scheduler, SchedulerConfig
from kubernetes_tpu.scheduler.generic import FitError, GenericScheduler
from kubernetes_tpu.scheduler.plugins import (
    PluginFactoryArgs,
    build_from_policy,
    default_predicates,
    default_priorities,
)
from kubernetes_tpu.scheduler.types import (
    StaticNodeLister,
    StaticPodLister,
    StaticServiceLister,
)
from kubernetes_tpu.server import APIServer


def pod_wire(name, cpu="100m", mem="100", ns="default"):
    return {
        "kind": "Pod",
        "metadata": {"name": name, "namespace": ns},
        "spec": {
            "containers": [
                {
                    "name": "c",
                    "image": "nginx",
                    "resources": {"limits": {"cpu": cpu, "memory": mem}},
                }
            ]
        },
    }


def node_wire(name, cpu="4", mem="8Gi", pods="40"):
    return {
        "kind": "Node",
        "metadata": {"name": name},
        "status": {
            "capacity": {"cpu": cpu, "memory": mem, "pods": pods},
            "conditions": [{"type": "Ready", "status": "True"}],
        },
    }


def wait_until(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return False


class TestGenericScheduler:
    """generic_scheduler_test.go expectations (condensed)."""

    def _args(self, nodes, pods=(), services=()):
        return PluginFactoryArgs(
            pod_lister=StaticPodLister(list(pods)),
            service_lister=StaticServiceLister(list(services)),
            node_lister=StaticNodeLister(nodes),
        )

    def test_picks_least_requested(self):
        from kubernetes_tpu.models.quantity import Quantity
        from tests.test_scheduler_priorities import cpu_mem_pod, make_minion

        nodes = [make_minion("big", 8000, 10**10), make_minion("small", 2000, 10**9)]
        for n in nodes:
            n.status.capacity["pods"] = Quantity.from_int(40)
        args = self._args(nodes)
        sched = GenericScheduler(
            default_predicates(args), default_priorities(args), args.pod_lister
        )
        # 3000m/5000B pod: only "big" passes PodFitsResources... small
        # has 2000m capacity < 3000m. Also scores favor big.
        dest = sched.schedule(cpu_mem_pod(""), args.node_lister)
        assert dest == "big"

    def test_fit_error_carries_predicates(self):
        from tests.test_scheduler_priorities import cpu_mem_pod, make_minion

        nodes = [make_minion("tiny", 100, 100)]
        args = self._args(nodes)
        sched = GenericScheduler(
            default_predicates(args), default_priorities(args), args.pod_lister
        )
        with pytest.raises(FitError) as e:
            sched.schedule(cpu_mem_pod(""), args.node_lister)
        assert "PodFitsResources" in str(e.value)

    def test_policy_file(self):
        from tests.test_scheduler_priorities import make_minion

        policy = {
            "kind": "Policy",
            "predicates": [{"name": "PodFitsResources"}, {"name": "HostName"}],
            "priorities": [
                {"name": "LeastRequestedPriority", "weight": 2},
                {
                    "name": "ZoneSpread",
                    "weight": 1,
                    "argument": {"serviceAntiAffinity": {"label": "zone"}},
                },
            ],
        }
        args = self._args([make_minion("m1", 1000, 1000)])
        predicates, priorities = build_from_policy(policy, args)
        assert set(predicates) == {"PodFitsResources", "HostName"}
        assert len(priorities) == 2
        assert priorities[0].weight == 2


class TestCustomAlgorithmSeam:
    """The algorithm seam is pluggable: any object with
    .schedule(pod, minion_lister) -> host slots into SchedulerConfig,
    exactly how contrib/mesos swaps its own ScheduleAlgorithm into
    scheduler.Config (reference: contrib/mesos/pkg/scheduler/
    scheduler.go:19-20 comment + plugin/pkg/scheduler/algorithm/
    scheduler_interface.go)."""

    def test_custom_algorithm_drives_placement(self):
        class StickyAlgorithm:
            """Places every pod on the lexicographically-last node —
            nothing like the default provider, which proves the daemon
            takes the seam's word for it."""

            def schedule(self, pod, minion_lister):
                nodes = sorted(n.metadata.name for n in minion_lister.list())
                if not nodes:
                    raise RuntimeError("no nodes")
                return nodes[-1]

        api = APIServer()
        client = Client(LocalTransport(api))
        cfg = SchedulerConfig(client).start()
        try:
            assert cfg.wait_for_sync()
            cfg.algorithm = StickyAlgorithm()
            sched = Scheduler(cfg)
            client.create("nodes", node_wire("a-node", cpu="8"))
            client.create("nodes", node_wire("z-node", cpu="1"))
            for i in range(3):
                client.create("pods", pod_wire(f"p{i}"))
            assert wait_until(lambda: len(cfg.pod_queue) >= 3)
            # The node informer is a separate watch thread from the pod
            # reflector: wait for both before scheduling.
            assert wait_until(lambda: len(cfg.node_lister.list()) == 2)
            for _ in range(3):
                assert sched.schedule_one(timeout=1)
            items, _ = client.list("pods", namespace="default")
            assert {p.spec.node_name for p in items} == {"z-node"}
        finally:
            cfg.stop()


class TestSchedulerDaemon:
    def _start(self, api=None, **cfg_kw):
        api = api or APIServer()
        client = Client(LocalTransport(api))
        cfg = SchedulerConfig(client, **cfg_kw).start()
        assert cfg.wait_for_sync()
        sched = Scheduler(cfg)
        return api, client, cfg, sched

    def test_schedules_pending_pod(self):
        api, client, cfg, sched = self._start()
        client.create("nodes", node_wire("n1"))
        client.create("pods", pod_wire("p1"))
        assert wait_until(lambda: len(cfg.pod_queue) > 0)
        assert sched.schedule_one(timeout=1)
        got = client.get("pods", "p1", namespace="default")
        assert got.spec.node_name == "n1"
        cfg.stop()

    def test_spreads_by_least_requested(self):
        api, client, cfg, sched = self._start()
        client.create("nodes", node_wire("n1", cpu="2"))
        client.create("nodes", node_wire("n2", cpu="4"))
        for i in range(4):
            client.create("pods", pod_wire(f"p{i}", cpu="500m"))
        assert wait_until(lambda: len(cfg.pod_queue) >= 4)
        for _ in range(4):
            assert sched.schedule_one(timeout=1)
        placements = {}
        items, _ = client.list("pods", namespace="default")
        for p in items:
            placements.setdefault(p.spec.node_name, 0)
            placements[p.spec.node_name] += 1
        # n2 has double capacity: it should absorb more pods.
        assert placements.get("n2", 0) >= placements.get("n1", 0)
        cfg.stop()

    def test_unschedulable_pod_requeued_with_backoff(self):
        api, client, cfg, sched = self._start()
        client.create("nodes", node_wire("n1", cpu="100m"))
        client.create("pods", pod_wire("huge", cpu="10"))
        assert wait_until(lambda: len(cfg.pod_queue) > 0)
        assert sched.schedule_one(timeout=1)
        got = client.get("pods", "huge", namespace="default")
        assert got.spec.node_name == ""
        # A FailedScheduling event was recorded.
        events, _ = client.list("events", namespace="default")
        assert any(e.reason == "FailedScheduling" for e in events)
        cfg.stop()

    def test_assumed_pod_blocks_capacity(self):
        """After bind, the assumed pod must count against the node
        before the watch confirms it (modeler semantics)."""
        api, client, cfg, sched = self._start()
        client.create("nodes", node_wire("n1", cpu="1", pods="40"))
        client.create("nodes", node_wire("n2", cpu="1", pods="40"))
        client.create("pods", pod_wire("a", cpu="600m"))
        client.create("pods", pod_wire("b", cpu="600m"))
        assert wait_until(lambda: len(cfg.pod_queue) >= 2)
        assert sched.schedule_one(timeout=1)
        assert sched.schedule_one(timeout=1)
        items, _ = client.list("pods", namespace="default")
        hosts = sorted(p.spec.node_name for p in items)
        # 600m + 600m > 1 CPU: they must land on different nodes even if
        # the scheduled-pods watch hasn't caught up.
        assert hosts == ["n1", "n2"]
        cfg.stop()

    def test_daemon_thread_drains_queue(self):
        api, client, cfg, sched = self._start()
        client.create("nodes", node_wire("n1"))
        sched.start()
        for i in range(5):
            client.create("pods", pod_wire(f"d{i}"))
        assert wait_until(
            lambda: all(
                p.spec.node_name == "n1"
                for p in client.list("pods", namespace="default")[0]
            )
            and len(client.list("pods", namespace="default")[0]) == 5,
            timeout=8,
        )
        sched.stop()


class TestDaemonRegressions:
    """Regression tests for review findings."""

    def test_externally_bound_pod_leaves_fifo(self):
        """A pod bound by another actor must produce a synthesized
        DELETED on the filtered watch and leave the scheduler's FIFO."""
        api = APIServer()
        client = Client(LocalTransport(api))
        cfg = SchedulerConfig(client).start()
        assert cfg.wait_for_sync()
        client.create("nodes", node_wire("n1"))
        client.create("pods", pod_wire("stolen"))
        assert wait_until(lambda: len(cfg.pod_queue) == 1)
        # Another actor binds it out from under the scheduler.
        client.bind("stolen", "n1", namespace="default")
        assert wait_until(lambda: len(cfg.pod_queue) == 0)
        cfg.stop()

    def test_deleted_pod_not_requeued_forever(self):
        api = APIServer()
        client = Client(LocalTransport(api))
        cfg = SchedulerConfig(client).start()
        cfg.backoff.initial = 0.05
        assert cfg.wait_for_sync()
        client.create("nodes", node_wire("n1", cpu="100m"))
        client.create("pods", pod_wire("doomed", cpu="10"))
        sched = Scheduler(cfg)
        assert wait_until(lambda: len(cfg.pod_queue) == 1)
        assert sched.schedule_one(timeout=1)  # fails, schedules a requeue
        client.delete("pods", "doomed", namespace="default")
        time.sleep(0.3)  # backoff elapses; re-fetch sees 404 and drops
        assert len(cfg.pod_queue) == 0
        cfg.stop()

    def test_node_deleted_mid_schedule_does_not_crash(self):
        """KeyError from a vanished node is treated as retryable."""
        api = APIServer()
        client = Client(LocalTransport(api))
        cfg = SchedulerConfig(client).start()
        assert cfg.wait_for_sync()
        client.create("nodes", node_wire("n1"))
        client.create("pods", pod_wire("p1"))
        sched = Scheduler(cfg)
        assert wait_until(lambda: len(cfg.pod_queue) == 1)
        # Sabotage: make the node lister's get always fail.
        cfg.node_lister.get = lambda name: (_ for _ in ()).throw(KeyError(name))
        assert sched.schedule_one(timeout=1) is True  # no crash
        cfg.stop()

    def test_one_pod_watch_splits_by_binding(self):
        """The scheduler's one pod watch feeds the FIFO with unbound
        pods and the scheduled cache with bound ones; a bind moves a
        pod across (out of the FIFO, MODIFIED into the cache), a delete
        leaves the side it was on, and a relist replaces both sides,
        with DELETED for every pod that vanished: scheduled, or pending
        and possibly placed by a tick whose bind the watch has not
        shown yet."""
        from kubernetes_tpu.client.cache import FIFO, ThreadSafeStore
        from kubernetes_tpu.scheduler.daemon import _PodSplit

        emitted = []
        queue, scheduled = FIFO(), ThreadSafeStore()
        split = _PodSplit(
            queue, scheduled, decode_scheduled=None,
            emit=lambda etype, obj: emitted.append(
                (etype, obj["metadata"]["name"])
            ),
        )

        def bound(name, node):
            p = pod_wire(name)
            p["spec"]["nodeName"] = node
            return p

        split.add(pod_wire("a"))
        split.add(pod_wire("b"))
        split.add(bound("c", "n1"))
        split.add(pod_wire("f"))
        assert len(queue) == 3 and scheduled.keys() == ["default/c"]
        split.update(bound("a", "n1"))  # the bind
        assert len(queue) == 2 and sorted(scheduled.keys()) == ["default/a", "default/c"]
        split.delete(pod_wire("b"))
        split.delete(bound("c", "n1"))
        assert len(queue) == 1 and scheduled.keys() == ["default/a"]
        assert emitted == [("ADDED", "c"), ("MODIFIED", "a"), ("DELETED", "c")]
        emitted.clear()
        assert queue.pop(timeout=0).metadata.name == "f"  # a tick took f
        split.replace([pod_wire("d"), bound("e", "n2")])
        assert queue.pop(timeout=0).metadata.name == "d"
        assert scheduled.keys() == ["default/e"]
        assert emitted == [("DELETED", "f"), ("DELETED", "a"), ("ADDED", "e")]
