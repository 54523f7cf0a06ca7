"""The store's batched watch fan-out: a held subscriber lets a burst of
events queue, then one dispatcher pass fans the backlog out. Each
watcher must see what the per-event fan-out gave it: its events in
version order, the synthesized DELETED of a selector, its shard's
events only, nothing twice and nothing above its floor missed, the
slow-consumer drop, and caches at least as fresh as any delivery."""

import threading
import time

import pytest

from kubernetes_tpu.server import APIServer
from kubernetes_tpu.store import watch as watchmod
from kubernetes_tpu.store.kvstore import DISPATCH_EVENTS, DISPATCH_PASSES, KVStore
from kubernetes_tpu.store.watch import ADDED, DELETED, MODIFIED, Event, WatchStream
from kubernetes_tpu.utils import faults

BURST = 1200
POD = "/registry/pods/default/"


def pod(name, node=""):
    spec = {"containers": [{"name": "c", "image": "pause"}]}
    if node:
        spec["nodeName"] = node
    return {"kind": "Pod", "metadata": {"name": name, "namespace": "default"},
            "spec": spec}


class Gate:
    """A subscriber that holds the dispatcher inside the pass of the
    first event it sees, until released: what is written meanwhile
    queues, and the next pass takes all of it."""

    def __init__(self, store):
        self.store = store
        self._entered = threading.Event()
        self._open = threading.Event()
        store.subscribe(self._hold)

    def _hold(self, items):
        if not self._open.is_set():
            self._entered.set()
            self._open.wait(30)

    def close(self, key="/registry/gate/g"):
        """Write one event and wait until the dispatcher holds on it."""
        self.store.create(key, {"metadata": {"name": "g"}})
        assert self._entered.wait(10)

    def release(self):
        self._open.set()


def drain(stream, n, timeout=20.0):
    out = []
    deadline = time.monotonic() + timeout
    while len(out) < n and time.monotonic() < deadline:
        ev = stream.next(timeout=0.1)
        if ev is not None:
            out.append(ev)
    return out


def settle(stream, timeout=0.3):
    """Whatever else arrives within `timeout`."""
    return drain(stream, 10**9, timeout=timeout)


@pytest.fixture
def api():
    a = APIServer()
    yield a
    a.store.close()


def test_burst_reaches_every_stream_in_version_order(api):
    store = api.store
    gate = Gate(store)
    everything = store.watch("/registry/", maxsize=65536)
    pods = api.watch("pods", "", maxsize=65536)
    assigned = api.watch("pods", "", field_selector="spec.nodeName!=", maxsize=65536)
    gate.close()
    for i in range(BURST):
        store.create(POD + f"p{i}", pod(f"p{i}"))
    for i in range(BURST):
        store.set(POD + f"p{i}", pod(f"p{i}", f"n{i % 7}"))
    gate.release()
    all_evs = drain(everything, 1 + 2 * BURST)
    pod_evs = drain(pods, 2 * BURST)
    bound = drain(assigned, BURST)
    for evs in (all_evs, pod_evs, bound):
        versions = [e.version for e in evs]
        assert versions == sorted(set(versions))
    assert len(all_evs) == 1 + 2 * BURST
    assert [e.type for e in pod_evs] == [ADDED] * BURST + [MODIFIED] * BURST
    assert [e.type for e in bound] == [MODIFIED] * BURST
    assert [e.object["spec"]["nodeName"] for e in bound] == [
        f"n{i % 7}" for i in range(BURST)
    ]
    assert not settle(everything) and not settle(pods) and not settle(assigned)


def test_bind_out_of_filter_synthesizes_deleted(api):
    store = api.store
    gate = Gate(store)
    unassigned = api.watch("pods", "", field_selector="spec.nodeName=", maxsize=65536)
    gate.close()
    for i in range(BURST):
        store.create(POD + f"u{i}", pod(f"u{i}"))
    for i in range(BURST):
        store.set(POD + f"u{i}", pod(f"u{i}", "n1"))
    store.create(POD + "born-bound", pod("born-bound", "n2"))
    store.delete(POD + "born-bound")
    gate.release()
    evs = drain(unassigned, 2 * BURST)
    assert [e.type for e in evs] == [ADDED] * BURST + [DELETED] * BURST
    # The DELETED carries the object as it left the view: bound.
    assert {e.object["spec"].get("nodeName") for e in evs[BURST:]} == {"n1"}
    assert [e.object["metadata"]["name"] for e in evs[BURST:]] == [
        f"u{i}" for i in range(BURST)
    ]
    assert not settle(unassigned)  # nothing of the pod born bound


def test_sharded_watchers_get_only_their_node(api):
    store = api.store
    gate = Gate(store)
    n1 = api.watch("pods", "", field_selector="spec.nodeName=n1", maxsize=65536)
    n2 = api.watch("pods", "", field_selector="spec.nodeName=n2", maxsize=65536)
    gate.close()
    for i in range(BURST):
        store.create(POD + f"s{i}", pod(f"s{i}"))
    for i in range(BURST):
        store.set(POD + f"s{i}", pod(f"s{i}", "n1" if i % 3 else "n2"))
    for i in range(0, BURST, 2):
        store.delete(POD + f"s{i}")
    gate.release()
    on1 = [i for i in range(BURST) if i % 3]
    on2 = [i for i in range(BURST) if not i % 3]
    for stream, mine in ((n1, on1), (n2, on2)):
        want = [(MODIFIED, f"s{i}") for i in mine] + [
            (DELETED, f"s{i}") for i in mine if not i % 2
        ]
        evs = drain(stream, len(want))
        assert [(e.type, e.object["metadata"]["name"]) for e in evs] == want
        assert not settle(stream)


@pytest.mark.parametrize("resume", [False, True])
def test_watcher_registered_during_backlog_sees_each_event_once(api, resume):
    """A watcher registers while a backlog is queued: from now (floor =
    the store's version) or resumed from a version inside the backlog
    (replay from history up to the floor). Either way each event above
    its starting point arrives exactly once, in order."""
    store = api.store
    gate = Gate(store)
    gate.close()
    for i in range(BURST):
        store.create(POD + f"a{i}", pod(f"a{i}"))
    mid = store.version - BURST // 2
    w = store.watch(POD, since=mid if resume else 0, maxsize=65536)
    for i in range(BURST):
        store.create(POD + f"b{i}", pod(f"b{i}"))
    gate.release()
    want = ([f"a{i}" for i in range(BURST // 2, BURST)] if resume else []) + [
        f"b{i}" for i in range(BURST)
    ]
    evs = drain(w, len(want))
    assert [e.object["metadata"]["name"] for e in evs] == want
    versions = [e.version for e in evs]
    assert versions == sorted(set(versions))
    assert not settle(w)


def test_batch_overflowing_a_stream_drops_it_as_push_does():
    store = KVStore()
    try:
        gate = Gate(store)
        small = store.watch(POD, maxsize=10)
        roomy = store.watch(POD, maxsize=65536)
        dropped0 = watchmod.STREAMS_DROPPED.value(resource="pods")
        gate.close()
        for i in range(100):
            store.create(POD + f"o{i}", pod(f"o{i}"))
        gate.release()
        assert len(drain(roomy, 100)) == 100
        got = drain(small, 11, timeout=2.0)  # what fit, then the close
        assert [e.object["metadata"]["name"] for e in got] == [
            f"o{i}" for i in range(10)
        ]
        assert small.closed
        assert watchmod.STREAMS_DROPPED.value(resource="pods") == dropped0 + 1
        # Pruned from the watcher index, once.
        deadline = time.monotonic() + 5
        while any(w[2] is small for w in store._watchers):
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert any(w[2] is roomy for w in store._watchers)
    finally:
        store.close()


def test_push_many_keeps_push_semantics():
    s = WatchStream(maxsize=4, resource="push-many")
    s.floor = 2
    dropped0 = watchmod.STREAMS_DROPPED.value(resource="push-many")
    evs = [Event(ADDED, {"n": v}, v) for v in range(1, 8)]
    # Versions 1 and 2 are at the floor; 3..6 fill the queue; 7 overflows.
    assert s.push_many(evs) is False
    assert s.closed
    assert watchmod.STREAMS_DROPPED.value(resource="push-many") == dropped0 + 1
    assert watchmod.QUEUE_DEPTH.value(resource="push-many") == 4
    assert [e.version for e in s] == [3, 4, 5, 6]
    closed = WatchStream(maxsize=4, resource="pods")
    closed.close()
    assert closed.push_many(evs) is False


def test_push_many_fires_the_chaos_seams_per_event(monkeypatch):
    """With faults enabled, each event meets WATCH_DROP and WATCH_DELAY
    as push() would: the fourth drop seam drops the stream after three
    events."""
    calls = []

    def fire(site, detail=""):
        if detail != "seam-test":
            return False  # other threads of the process: no fault
        calls.append(site)
        return site is faults.WATCH_DROP and calls.count(site) == 4

    monkeypatch.setattr(faults, "enabled", lambda: True)
    monkeypatch.setattr(faults, "fire", fire)
    s = WatchStream(maxsize=100, resource="seam-test")
    assert s.push_many([Event(ADDED, {}, v) for v in range(1, 11)]) is False
    assert [e.version for e in s] == [1, 2, 3]
    assert calls == [faults.WATCH_DROP, faults.WATCH_DELAY] * 3 + [faults.WATCH_DROP]


def test_watch_cache_is_as_fresh_as_any_delivery(api):
    store = api.store
    api.caches.cache_for("/registry/pods/")
    gate = Gate(store)
    w = api.watch("pods", "", maxsize=65536)
    stale = []
    gate.close()
    for i in range(BURST):
        store.create(POD + f"c{i}", pod(f"c{i}"))
    gate.release()
    for ev in drain(w, BURST):
        if api.caches.applied < ev.version:
            stale.append((ev.version, api.caches.applied))
    assert not stale
    assert api.caches.peek("/registry/pods/").get(POD + f"c{BURST - 1}")


def test_a_gated_burst_is_few_passes_and_a_lone_write_one(api):
    store = api.store
    gate = Gate(store)
    w = store.watch(POD, maxsize=65536)
    gate.close()
    p0, e0 = DISPATCH_PASSES.value(), DISPATCH_EVENTS.value()
    for i in range(BURST):
        store.create(POD + f"e{i}", pod(f"e{i}"))
    gate.release()
    assert len(drain(w, BURST)) == BURST
    passes = DISPATCH_PASSES.value() - p0
    events = DISPATCH_EVENTS.value() - e0
    assert events >= BURST and passes >= 1
    assert events >= 10 * passes

    p1, e1 = DISPATCH_PASSES.value(), DISPATCH_EVENTS.value()
    store.create(POD + "lone", pod("lone"))
    assert len(drain(w, 1)) == 1
    assert DISPATCH_PASSES.value() - p1 == 1
    assert DISPATCH_EVENTS.value() - e1 == 1


def test_subscribe_takes_one_form():
    """A subscriber takes a pass's events as one list: together, every
    event once and in version order; a gated burst is one call."""
    store = KVStore()
    try:
        batches = []
        store.subscribe(lambda items: batches.append([i[0] for i in items]))
        gate = Gate(store)
        gate.close()
        for i in range(50):
            store.create(f"/k/{i}", {"metadata": {"name": str(i)}})
        gate.release()
        deadline = time.monotonic() + 10
        while sum(map(len, batches)) < 51 and time.monotonic() < deadline:
            time.sleep(0.01)
        flat = [v for b in batches for v in b]
        assert flat == sorted(flat) and len(flat) == 51
        assert batches[1] == flat[1:]  # the burst came as one batch
    finally:
        store.close()


def test_an_api_watch_has_room_for_a_backlog_pass(api):
    """A watch that asks for a deep queue (?maxsize=) takes a bulk
    commit's burst of more than the default depth in one pass without
    being dropped; the depth is the store's default unless asked, and
    clamped to [1024, 65536]."""
    store = api.store
    gate = Gate(store)
    w = api.watch("pods", "", maxsize=8192)
    gate.close()
    n = 6000
    store.create_many([(POD + f"r{i}", pod(f"r{i}"), None) for i in range(n)])
    gate.release()
    evs = drain(w, n)
    assert len(evs) == n and not w.closed
    assert api.watch("pods", "")._q.maxsize == watchmod.DEFAULT_MAXSIZE
    assert api.watch("pods", "", maxsize=10**9)._q.maxsize == 65536
    assert api.watch("pods", "", maxsize=1)._q.maxsize == 1024


@pytest.mark.parametrize("resume", [False, True])
def test_a_deleted_event_carries_the_delete_version(resume):
    """A DELETED event's object is the last stored state stamped with
    the delete's version, live and replayed, over HTTP too (the frame
    carries no other version): a client that resumes from the last
    version it saw must not go back to the deleted object's last write.
    The stored history is not touched."""
    from kubernetes_tpu.client import Client, HTTPTransport
    from kubernetes_tpu.server.httpserver import APIHTTPServer

    api = APIServer()
    srv = APIHTTPServer(api).start()
    try:
        client = Client(HTTPTransport(srv.address))
        live = client.watch("pods", namespace="default")
        base = api.store.version
        api.store.create(POD + "x", pod("x"))  # base + 1
        api.store.set(POD + "x", pod("x", "n1"))  # base + 2
        api.store.create(POD + "y", pod("y"))  # base + 3
        api.store.delete(POD + "x")  # base + 4
        since = base + 1 if resume else base
        stream = client.watch(
            "pods", namespace="default", since=since
        ) if resume else live
        evs = drain(stream, base + 4 - since, timeout=10)
        assert [e.version for e in evs] == list(range(since + 1, base + 5))
        assert evs[-1].type == DELETED
        assert evs[-1].object["metadata"]["resourceVersion"] == str(base + 4)
        assert evs[-1].object["spec"]["nodeName"] == "n1"
        hist = [o for v, t, k, o in api.store._history if t == DELETED]
        assert hist[-1]["metadata"]["resourceVersion"] == str(base + 2)
        live.close()
        stream.close()
    finally:
        srv.stop()
