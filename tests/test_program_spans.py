"""The program's own phases where the work happens: ``kt.*`` profiler
annotations on the device trace's clock, and the API front door
(api.request), store (store.commit, watch.fanout_wait) and scheduler
pod queue (sched.queue_wait) observations in scheduler_phase_seconds."""

import glob
import os
import subprocess
import sys
import time

import pytest

from kubernetes_tpu.client import FIFO, Client, HTTPTransport, LocalTransport
from kubernetes_tpu.scheduler.daemon import SchedulerConfig
from kubernetes_tpu.server import APIServer
from kubernetes_tpu.server.httpserver import APIHTTPServer
from kubernetes_tpu.store.kvstore import KVStore
from kubernetes_tpu.utils import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def count(phase):
    return tracing.PHASE_SECONDS.count(phase=phase)


def seconds(phase):
    return tracing.PHASE_SECONDS.snapshot().get((phase,), (0, 0.0))[1]


def wait_for(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not pred() and time.monotonic() < deadline:
        time.sleep(0.01)
    return pred()


def pod_wire(name):
    return {
        "kind": "Pod",
        "metadata": {"name": name, "namespace": "default"},
        "spec": {"containers": [{"name": "c", "image": "pause"}]},
    }


def test_phase_leaves_a_kt_host_event_in_the_device_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        with tracing.phase("lower", pods=3):
            time.sleep(0.001)
        with tracing.trace("schedule_batch"):
            pass
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events = {
        e.name: dict(e.stats)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
        if e.name.startswith("kt.")
    }
    assert events["kt.lower"] == {"pods": 3}
    assert "kt.schedule_batch" in events


def test_tracing_never_imports_jax_nor_annotates_without_it():
    code = (
        "import sys\n"
        "from kubernetes_tpu.utils import tracing\n"
        "with tracing.phase('lower', pods=1):\n"
        "    with tracing.trace('schedule_batch'):\n"
        "        pass\n"
        "assert tracing.PHASE_SECONDS.count(phase='lower') == 1\n"
        "print('jax' in sys.modules, tracing._annotation_cls)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["False", "None"]


def test_one_bulk_post_observes_one_request_and_one_commit():
    api = APIServer()
    client = Client(LocalTransport(api))
    client.create("pods", pod_wire("warm"))  # namespace defaults settle
    srv = APIHTTPServer(api).start()
    try:
        req0, commit0 = count("api.request"), count("store.commit")
        res = Client(HTTPTransport(srv.address)).create_bulk(
            "pods", [pod_wire(f"bulk-{i}") for i in range(20)],
            namespace="default",
        )
        assert all(r.get("status") == "Success" for r in res)
        # The handler observes after its reply is on the wire.
        assert wait_for(lambda: count("api.request") > req0)
    finally:
        srv.stop()
    assert count("api.request") == req0 + 1
    assert count("store.commit") == commit0 + 1


def test_a_watch_request_is_no_api_request():
    """A watch holds its handler for its whole life: it is left out of
    api.request, which times the work of one request."""
    api = APIServer()
    srv = APIHTTPServer(api).start()
    try:
        stream = Client(HTTPTransport(srv.address)).watch(
            "pods", namespace="default"
        )
        before = count("api.request")
        Client(LocalTransport(api)).create("pods", pod_wire("watched"))
        ev = stream.next(timeout=10.0)
        assert ev is not None and ev.object["metadata"]["name"] == "watched"
        stream.close()
    finally:
        srv.stop()
    # The handler ends with the server; give it the time to observe.
    assert not wait_for(lambda: count("api.request") != before, timeout=1.0)


def test_each_store_write_observes_one_fanout_wait():
    store = KVStore()
    try:
        before = count("watch.fanout_wait")
        n = 25
        for i in range(n):
            store.create(f"/registry/pods/default/w{i}", {"metadata": {"name": f"w{i}"}})
        assert wait_for(lambda: count("watch.fanout_wait") >= before + n)
        time.sleep(0.05)
        assert count("watch.fanout_wait") == before + n
        # A bulk create is one write call but 500 events: 500 waits,
        # however few dispatcher passes fan them out.
        before = count("watch.fanout_wait")
        bulk = 500
        store.create_many([
            (f"/registry/pods/default/b{i}", {"metadata": {"name": f"b{i}"}}, None)
            for i in range(bulk)
        ])
        assert wait_for(lambda: count("watch.fanout_wait") >= before + bulk)
        time.sleep(0.05)
        assert count("watch.fanout_wait") == before + bulk
    finally:
        store.close()


def test_scheduler_queue_observes_each_pop_and_other_fifos_do_not():
    from kubernetes_tpu.models.objects import Pod, ObjectMeta

    def pod(name):
        return Pod(metadata=ObjectMeta(name=name, namespace="default"))

    q = SchedulerConfig(Client(LocalTransport(APIServer()))).pod_queue
    n0, s0 = count("sched.queue_wait"), seconds("sched.queue_wait")
    n = 12
    q.add(pod("q0"))
    time.sleep(0.05)
    q.add(pod("q0"))  # an update keeps the key's first stamp
    for i in range(1, n):
        q.add(pod(f"q{i}"))
    popped = [q.pop(timeout=0) for _ in range(n)]
    assert [p.metadata.name for p in popped] == [f"q{i}" for i in range(n)]
    assert count("sched.queue_wait") == n0 + n
    assert seconds("sched.queue_wait") - s0 >= 0.05
    # A requeue stamps anew.
    s1 = seconds("sched.queue_wait")
    q.add(popped[0])
    assert q.pop(timeout=0) is popped[0]
    assert count("sched.queue_wait") == n0 + n + 1
    assert seconds("sched.queue_wait") - s1 < 0.05

    other = FIFO()
    for i in range(n):
        other.add(pod(f"o{i}"))
    for _ in range(n):
        other.pop(timeout=0)
    assert count("sched.queue_wait") == n0 + n + 1
