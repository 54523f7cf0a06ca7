"""KV store semantics tests (reference: pkg/tools/etcd_helper*.go)."""

import threading
import time

import pytest

from kubernetes_tpu.store import (
    ADDED,
    AlreadyExistsError,
    CompactedError,
    ConflictError,
    DELETED,
    KVStore,
    MODIFIED,
    NotFoundError,
)


def obj(name, ns="default", **extra):
    return {"kind": "Pod", "metadata": {"name": name, "namespace": ns}, **extra}


def test_create_get_stamps_version():
    s = KVStore()
    created = s.create("/pods/default/a", obj("a"))
    assert created["metadata"]["resourceVersion"] == "1"
    got = s.get("/pods/default/a")
    assert got["metadata"]["name"] == "a"
    with pytest.raises(AlreadyExistsError):
        s.create("/pods/default/a", obj("a"))
    with pytest.raises(NotFoundError):
        s.get("/pods/default/missing")


def test_copies_not_aliased():
    s = KVStore()
    o = obj("a")
    s.create("/k", o)
    o["metadata"]["name"] = "mutated"
    assert s.get("/k")["metadata"]["name"] == "a"
    got = s.get("/k")
    got["metadata"]["name"] = "mutated2"
    assert s.get("/k")["metadata"]["name"] == "a"


def test_cas_set_and_delete():
    s = KVStore()
    created = s.create("/k", obj("a"))
    v = int(created["metadata"]["resourceVersion"])
    s.set("/k", obj("a", spec={"x": 1}), expected_version=v)
    with pytest.raises(ConflictError):
        s.set("/k", obj("a"), expected_version=v)  # stale
    with pytest.raises(ConflictError):
        s.delete("/k", expected_version=v)
    s.delete("/k", expected_version=v + 1)
    with pytest.raises(NotFoundError):
        s.get("/k")


def test_guaranteed_update_retries_on_conflict():
    s = KVStore()
    s.create("/k", obj("a", count=0))
    calls = {"n": 0}

    def bump(cur):
        calls["n"] += 1
        if calls["n"] == 1:
            # Interleave a conflicting write mid-update (another writer).
            s.set("/k", obj("a", count=100))
        cur["count"] = cur.get("count", 0) + 1
        return cur

    out = s.guaranteed_update("/k", bump)
    assert out["count"] == 101  # second attempt saw the interleaved write
    assert calls["n"] == 2


def test_list_prefix_and_version():
    s = KVStore()
    s.create("/pods/default/a", obj("a"))
    s.create("/pods/default/b", obj("b"))
    s.create("/nodes/n1", {"kind": "Node", "metadata": {"name": "n1"}})
    pods, v = s.list("/pods/")
    assert [p["metadata"]["name"] for p in pods] == ["a", "b"]
    assert v == 3


def test_watch_live_events_in_order():
    s = KVStore()
    w = s.watch("/pods/")
    s.create("/pods/default/a", obj("a"))
    s.set("/pods/default/a", obj("a", spec={"nodeName": "n1"}))
    s.delete("/pods/default/a")
    s.create("/nodes/n1", {"kind": "Node", "metadata": {"name": "n1"}})  # filtered
    evs = [w.next(timeout=1) for _ in range(3)]
    assert [e.type for e in evs] == [ADDED, MODIFIED, DELETED]
    assert [e.version for e in evs] == [1, 2, 3]
    assert evs[1].object["spec"]["nodeName"] == "n1"
    assert w.next(timeout=0.05) is None  # node event was filtered by prefix


def test_watch_replay_from_version():
    s = KVStore()
    s.create("/pods/a", obj("a"))
    s.create("/pods/b", obj("b"))
    _, v = s.list("/pods/")
    s.create("/pods/c", obj("c"))
    s.set("/pods/a", obj("a", spec={"x": 1}))
    w = s.watch("/pods/", since=v)
    evs = [w.next(timeout=1) for _ in range(2)]
    assert [(e.type, e.object["metadata"]["name"]) for e in evs] == [
        (ADDED, "c"),
        (MODIFIED, "a"),
    ]
    # live continues after replay
    s.delete("/pods/b")
    ev = w.next(timeout=1)
    assert (ev.type, ev.object["metadata"]["name"]) == (DELETED, "b")


def test_watch_compacted():
    s = KVStore(history_limit=4)
    for i in range(10):
        s.create(f"/pods/p{i}", obj(f"p{i}"))
    with pytest.raises(CompactedError):
        s.watch("/pods/", since=1)


def test_ttl_expiry():
    s = KVStore()
    s.create("/events/e1", {"kind": "Event", "metadata": {"name": "e1"}}, ttl=0.05)
    assert s.get("/events/e1")["metadata"]["name"] == "e1"
    time.sleep(0.08)
    with pytest.raises(NotFoundError):
        s.get("/events/e1")
    # Expiry produced a DELETED event visible to watch replay.
    w = s.watch("/events/", since=1)
    ev = w.next(timeout=1)
    assert ev.type == DELETED


def test_concurrent_guaranteed_updates():
    s = KVStore()
    s.create("/k", obj("a", count=0))

    def worker():
        for _ in range(50):
            s.guaranteed_update(
                "/k", lambda cur: {**cur, "count": cur["count"] + 1}
            )

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert s.get("/k")["count"] == 200


def test_slow_consumer_stream_closed():
    s = KVStore()
    w = s.watch("/pods/", maxsize=2)
    for i in range(5):
        s.create(f"/pods/p{i}", obj(f"p{i}"))
    # Queue overflowed -> stream closed; consumer drains then sees close.
    seen = list(w)
    assert len(seen) <= 3
    assert w.closed


class TestFilteredWatch:
    """Store-level selector filtering with etcd's old/new-aware
    translation (kvstore._filter_type; reference:
    pkg/tools/etcd_helper_watch.go sendModify/sendDelete). The filter
    runs INSIDE the fan-out, so a watcher is never even offered events
    for objects that don't concern it."""

    @staticmethod
    def drain(w, n, timeout=2.0):
        out = []
        deadline = time.time() + timeout
        while len(out) < n and time.time() < deadline:
            ev = w.next(timeout=0.1)
            if ev is not None:
                out.append(ev)
        return out

    @staticmethod
    def unassigned_pred(o):
        return not o.get("spec", {}).get("nodeName")

    def test_modified_out_of_filter_becomes_deleted(self):
        # The scheduler's spec.nodeName=="" watch: binding a pod must
        # surface as DELETED (it left the view), not MODIFIED.
        s = KVStore()
        w = s.watch("/pods/", pred=self.unassigned_pred)
        s.create("/pods/a", obj("a", spec={}))
        s.set("/pods/a", obj("a", spec={"nodeName": "n1"}))
        evs = self.drain(w, 2)
        assert [e.type for e in evs] == [ADDED, DELETED]

    def test_never_matching_object_is_silent(self):
        # A pod born bound: the unassigned watcher sees NOTHING for its
        # whole lifecycle — old/new awareness suppresses the spurious
        # DELETED per status write that the pre-store filter emitted.
        s = KVStore()
        w = s.watch("/pods/", pred=self.unassigned_pred)
        s.create("/pods/b", obj("b", spec={"nodeName": "n1"}))
        s.set("/pods/b", obj("b", spec={"nodeName": "n1"}, status={"p": 1}))
        s.set("/pods/b", obj("b", spec={"nodeName": "n1"}, status={"p": 2}))
        s.delete("/pods/b")
        s.create("/pods/c", obj("c", spec={}))  # sentinel that DOES match
        evs = self.drain(w, 1)
        assert len(evs) == 1 and evs[0].key == "default/c"

    def test_delete_of_matching_object_delivered(self):
        s = KVStore()
        w = s.watch("/pods/", pred=self.unassigned_pred)
        s.create("/pods/d", obj("d", spec={}))
        s.delete("/pods/d")
        evs = self.drain(w, 2)
        assert [e.type for e in evs] == [ADDED, DELETED]

    def test_modified_within_filter_stays_modified(self):
        s = KVStore()
        w = s.watch("/pods/", pred=self.unassigned_pred)
        s.create("/pods/e", obj("e", spec={}))
        s.set("/pods/e", obj("e", spec={}, status={"phase": "Pending"}))
        evs = self.drain(w, 2)
        assert [e.type for e in evs] == [ADDED, MODIFIED]

    def test_replay_degrades_to_spurious_deleted(self):
        # History has no prev state: a replayed non-matching MODIFIED
        # becomes a (harmless) DELETED instead of being dropped.
        s = KVStore()
        s.create("/pods/f", obj("f", spec={"nodeName": "n1"}))
        v = s.version
        s.set("/pods/f", obj("f", spec={"nodeName": "n1"}, status={"x": 1}))
        w = s.watch("/pods/", since=v, pred=self.unassigned_pred)
        evs = self.drain(w, 1)
        assert [e.type for e in evs] == [DELETED]

    def test_replay_then_live_no_duplicates_no_gaps(self):
        # The version floor: replay covers <= registration version;
        # the dispatcher's backlog must not re-deliver, later writes
        # must all arrive.
        s = KVStore()
        s.create("/pods/base", obj("base", spec={"nodeName": "n0"}))
        v0 = s.version
        s.create("/pods/g", obj("g", spec={}))
        w = s.watch("/pods/", since=v0, pred=self.unassigned_pred)
        s.create("/pods/h", obj("h", spec={}))
        evs = self.drain(w, 2)
        assert sorted(e.key for e in evs) == ["default/g", "default/h"]
        assert len({e.version for e in evs}) == 2
