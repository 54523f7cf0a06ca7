"""HA control plane: WAL-shipping replication (store/replication.py),
follower promotion, stateless apiserver fan-out with write forwarding,
client endpoint rotation, and Reflector watch resume.

The raft-lite contract under test: a write acked to a client is
durable on a quorum, and a follower promoted at ANY instant exposes
exactly the committed prefix — byte-identical WAL, never a torn or
unacked record."""

import json
import time

import pytest

from kubernetes_tpu.client import Client, LocalTransport, Reflector
from kubernetes_tpu.client.cache import ThreadSafeStore
from kubernetes_tpu.client.rest import HTTPTransport
from kubernetes_tpu.server.api import APIError, APIServer
from kubernetes_tpu.server.httpserver import APIHTTPServer
from kubernetes_tpu.store.kvstore import KVStore
from kubernetes_tpu.store.replication import (
    FollowerReplica,
    HTTPLink,
    LocalLink,
    ReplicationError,
    ReplicationHub,
)


def wait_until(cond, timeout=10.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


def pod_wire(name, ns="default"):
    return {
        "kind": "Pod",
        "apiVersion": "v1",
        "metadata": {"name": name, "namespace": ns},
        "spec": {"containers": [{"name": "c", "image": "nginx"}]},
    }


class PartitionableLink(LocalLink):
    """LocalLink with a partition switch (the shipper sees a dead
    link; the follower simply stops receiving)."""

    def __init__(self, replica, name="follower"):
        super().__init__(replica, name)
        self.partitioned = False

    def append(self, lines, commit):
        if self.partitioned:
            raise ConnectionError(f"{self.name}: partitioned")
        return super().append(lines, commit)


def _wal_bytes(store):
    with open(store._wal_path, "rb") as f:
        return f.read()


class TestWALShipping:
    def test_quorum_ack_and_follower_convergence(self):
        leader = KVStore()
        hub = ReplicationHub(leader).attach()
        api = APIServer(store=leader)
        api.replication = hub
        f1, f2 = FollowerReplica(name="f1"), FollowerReplica(name="f2")
        hub.add_follower(LocalLink(f1, "f1"))
        hub.add_follower(LocalLink(f2, "f2"))
        c = Client(LocalTransport(api))
        for i in range(20):
            c.create("pods", pod_wire(f"p{i}"))  # acks only at quorum
        # Acked writes are quorum-committed by definition of the gate.
        assert hub.commit_index == leader.version
        # Both followers converge to the full log and apply the
        # committed prefix into their live mirrors.
        assert wait_until(
            lambda: f1.store.journaled_version == leader.version
            and f2.store.journaled_version == leader.version
        )
        assert wait_until(
            lambda: f1.store.version == leader.version
            and f2.store.version == leader.version
        )
        st = hub.status()
        assert st["role"] == "leader"
        assert {f["name"] for f in st["followers"]} == {"f1", "f2"}
        assert all(f["alive"] for f in st["followers"])
        hub.stop()

    def test_single_node_cluster_acks_alone(self):
        """No followers: local fsync IS quorum (majority of 1)."""
        leader = KVStore()
        ReplicationHub(leader).attach()
        api = APIServer(store=leader)
        c = Client(LocalTransport(api))
        c.create("pods", pod_wire("solo"))
        assert c.get("pods", "solo", namespace="default") is not None

    def test_one_dead_follower_does_not_block_acks(self):
        """3-replica cluster (leader + 2): majority is 2, so one
        partitioned follower lags alone while writes keep acking."""
        leader = KVStore()
        hub = ReplicationHub(leader, ack_timeout_s=5.0).attach()
        api = APIServer(store=leader)
        f1, f2 = FollowerReplica(name="f1"), FollowerReplica(name="f2")
        l1 = PartitionableLink(f1, "f1")
        hub.add_follower(l1)
        hub.add_follower(LocalLink(f2, "f2"))
        l1.partitioned = True
        c = Client(LocalTransport(api))
        for i in range(5):
            c.create("pods", pod_wire(f"p{i}"))
        assert hub.commit_index == leader.version
        # Heal the partition: the lagging follower catches up.
        l1.partitioned = False
        assert wait_until(
            lambda: f1.store.version == leader.version
        )
        hub.stop()

    def test_lost_quorum_refuses_to_ack(self):
        """2-replica cluster (leader + 1): majority is 2. With the
        only follower partitioned the write journals locally but the
        ack times out — exactly a raft leader losing its quorum."""
        leader = KVStore()
        hub = ReplicationHub(leader, ack_timeout_s=0.4).attach()
        api = APIServer(store=leader)
        f1 = FollowerReplica(name="f1")
        link = PartitionableLink(f1, "f1")
        hub.add_follower(link)
        link.partitioned = True
        c = Client(LocalTransport(api))
        with pytest.raises(ReplicationError):
            c.create("pods", pod_wire("unacked"))
        hub.stop()


class TestPromotion:
    def test_promoted_follower_byte_identical_committed_prefix(
        self, tmp_path
    ):
        """The acceptance oracle: after leader crash, the promoted
        follower's WAL is byte-identical to the committed prefix of
        the leader's WAL, and the promoted store serves every acked
        write (snapshot rotation disabled so the WAL holds the full
        history on both sides)."""
        leader = KVStore(
            data_dir=str(tmp_path / "leader"), snapshot_every=10**9
        )
        hub = ReplicationHub(leader).attach()
        f1 = FollowerReplica(
            store=KVStore(
                data_dir=str(tmp_path / "f1"), snapshot_every=10**9
            ),
            name="f1",
        )
        # Follower joins BEFORE the first write so every record ships
        # as a WAL line (a late joiner bootstraps from dump_state and
        # only the post-join suffix is byte-comparable).
        hub.add_follower(LocalLink(f1, "f1"))
        api = APIServer(store=leader)
        c = Client(LocalTransport(api))
        for i in range(30):
            c.create("pods", pod_wire(f"p{i}"))
        acked_version = leader.version
        assert wait_until(
            lambda: f1.store.journaled_version == acked_version
        )
        leader_wal = _wal_bytes(leader)
        leader.crash()
        promoted = f1.promote()
        # Byte-identical committed prefix: every acked record, no
        # torn tail.
        follower_wal = _wal_bytes(promoted)
        assert follower_wal == leader_wal[: len(follower_wal)]
        assert promoted.version == acked_version
        # The promoted store serves every acked write...
        new_api = APIServer(store=promoted)
        nc = Client(LocalTransport(new_api))
        pods, _ = nc.list("pods", namespace="default")
        assert {p.metadata.name for p in pods} >= {
            f"p{i}" for i in range(30)
        }
        # ...and is writable (a new leader).
        nc.create("pods", pod_wire("after-failover"))
        assert nc.get("pods", "after-failover", namespace="default")

    def test_unacked_write_never_exposed_after_promote(self, tmp_path):
        """A write that journals on the leader but never reaches
        quorum is NOT acked — and a follower promoted afterwards must
        not expose it (the torn-record half of the oracle)."""
        leader = KVStore(
            data_dir=str(tmp_path / "leader"), snapshot_every=10**9
        )
        hub = ReplicationHub(leader, ack_timeout_s=0.4).attach()
        f1 = FollowerReplica(
            store=KVStore(
                data_dir=str(tmp_path / "f1"), snapshot_every=10**9
            ),
            name="f1",
        )
        link = PartitionableLink(f1, "f1")
        hub.add_follower(link)
        api = APIServer(store=leader)
        c = Client(LocalTransport(api))
        for i in range(10):
            c.create("pods", pod_wire(f"acked{i}"))
        acked_version = leader.version
        assert wait_until(
            lambda: f1.store.journaled_version == acked_version
        )
        link.partitioned = True
        with pytest.raises(ReplicationError):
            c.create("pods", pod_wire("torn"))
        assert leader.version > acked_version  # journaled locally...
        promoted = f1.promote()
        assert promoted.version == acked_version  # ...but never here
        leader_wal = _wal_bytes(leader)
        follower_wal = _wal_bytes(promoted)
        assert follower_wal == leader_wal[: len(follower_wal)]
        assert len(follower_wal) < len(leader_wal)
        nc = Client(LocalTransport(APIServer(store=promoted)))
        with pytest.raises(APIError):
            nc.get("pods", "torn", namespace="default")

    def test_promoted_follower_rejects_stale_leader(self):
        """A stale leader shipping into a promoted follower gets a
        hard refusal, not a silent divergence."""
        f1 = FollowerReplica(name="f1")
        f1.promote()
        with pytest.raises(ReplicationError):
            f1.append([], 5)
        assert f1.status()["role"] == "leader"


class TestHTTPPlane:
    """N stateless apiservers over the replication plane: reads fan
    out on every replica's watch cache, writes forward to the leader,
    /replication rides the same HTTP plane, /healthz reports the
    replication subcheck."""

    def _cluster(self):
        leader_store = KVStore()
        leader_api = APIServer(store=leader_store)
        leader_http = APIHTTPServer(leader_api).start()
        hub = ReplicationHub(leader_store).attach()
        leader_api.replication = hub
        followers = []
        for name in ("f1", "f2"):
            rep = FollowerReplica(name=name)
            api = APIServer(store=rep.store)
            api.replication = rep
            api.leader_url = leader_http.address
            http = APIHTTPServer(api).start()
            hub.add_follower(HTTPLink(http.address, name=name))
            followers.append((rep, api, http))
        return leader_store, leader_api, leader_http, hub, followers

    def test_forwarded_write_and_fanout_read(self):
        _store, _api, leader_http, hub, followers = self._cluster()
        f1_http = followers[0][2]
        try:
            # Write through a FOLLOWER endpoint: forwarded to the
            # leader, acked at quorum, then readable from the same
            # follower's own watch cache.
            c = Client(HTTPTransport(f1_http.address))
            c.create("pods", pod_wire("fwd"))
            assert wait_until(
                lambda: any(
                    p.metadata.name == "fwd"
                    for p in c.list("pods", namespace="default")[0]
                )
            )
            # Writes through the leader replicate out to followers.
            lc = Client(HTTPTransport(leader_http.address))
            lc.create("pods", pod_wire("direct"))
            assert wait_until(
                lambda: any(
                    p.metadata.name == "direct"
                    for p in c.list("pods", namespace="default")[0]
                )
            )
        finally:
            hub.stop()
            leader_http.stop()
            for _, _, http in followers:
                http.stop()

    def test_forwarded_write_shares_one_trace_id(self):
        """A write through a follower is ONE operation: the follower's
        request-log entry and the leader's carry the SAME trace id —
        minted on the follower when the client sent no X-Trace-Id, and
        reused verbatim when it did (before the fix, an unstamped
        forwarded mutation appeared as two unrelated requests at
        /debug/requests)."""
        import urllib.request

        from kubernetes_tpu.utils import debug

        _store, _api, leader_http, hub, followers = self._cluster()
        f1_http = followers[0][2]

        def logged(match, n):
            # Each hop logs in its handler's finally, AFTER its response
            # is sent: the follower's entry can land after the client
            # has already read the reply.
            deadline = time.monotonic() + 5.0
            while True:
                got = [
                    e for e in list(debug.DEFAULT_REQUEST_LOG._ring)
                    if match(e)
                ]
                if len(got) >= n or time.monotonic() > deadline:
                    return got
                time.sleep(0.01)

        def is_post(e):
            return e[1] == "POST" and e[2].endswith("/pods")

        try:
            c = Client(HTTPTransport(f1_http.address))
            earlier = len(logged(is_post, 0))
            c.create("pods", pod_wire("traced"))
            posts = logged(is_post, earlier + 2)
            # The leader's hop logs first (it responds before the
            # follower's own finally runs), then the follower's.
            assert len(posts) >= 2
            tids = {e[5] for e in posts[-2:]}
            assert len(tids) == 1, posts[-2:]
            assert tids.pop(), "trace id was never minted on the hop"
            # A client-stamped id is reused verbatim across both hops.
            req = urllib.request.Request(
                f1_http.address + "/api/v1/namespaces/default/pods",
                data=json.dumps(pod_wire("traced2")).encode(),
                headers={
                    "Content-Type": "application/json",
                    "X-Trace-Id": "trace-fwd-regress",
                },
                method="POST",
            )
            urllib.request.urlopen(req, timeout=10).read()
            stamped = logged(lambda e: e[5] == "trace-fwd-regress", 2)
            assert len(stamped) == 2  # follower hop + leader hop
        finally:
            hub.stop()
            leader_http.stop()
            for _, _, http in followers:
                http.stop()

    def test_healthz_replication_subcheck(self):
        import urllib.request

        _store, _api, leader_http, hub, followers = self._cluster()
        try:
            h = json.loads(
                urllib.request.urlopen(
                    leader_http.address + "/healthz"
                ).read()
            )
            rep = h["checks"]["replication"]
            assert rep["status"] == "ok"
            assert rep["role"] == "leader"
            assert set(rep["followerLag"]) == {"f1", "f2"}
            fh = json.loads(
                urllib.request.urlopen(
                    followers[0][2].address + "/healthz"
                ).read()
            )
            assert fh["checks"]["replication"]["role"] == "follower"
            st = json.loads(
                urllib.request.urlopen(
                    followers[0][2].address + "/replication/status"
                ).read()
            )
            assert st["role"] == "follower"
            assert "journaled" in st
        finally:
            hub.stop()
            leader_http.stop()
            for _, _, http in followers:
                http.stop()


class TestEndpointRotation:
    def test_client_rotates_on_dead_endpoint(self):
        """Two stateless apiservers over ONE store; killing the one
        the client is pinned to rotates reads to the survivor inside
        the retry loop — no caller-visible failure."""
        store = KVStore()
        api = APIServer(store=store)
        s1 = APIHTTPServer(api).start()
        s2 = APIHTTPServer(api).start()
        try:
            from urllib.parse import urlparse

            t = HTTPTransport([s1.address, s2.address])
            c = Client(t)
            c.create("pods", pod_wire("p0"))
            u1, u2 = urlparse(s1.address), urlparse(s2.address)
            assert (t.host, t.port) == (u1.hostname, u1.port)
            s1.stop(release_store=False)
            got = c.get("pods", "p0", namespace="default")
            assert got.metadata.name == "p0"
            assert (t.host, t.port) == (u2.hostname, u2.port)
        finally:
            for s in (s1, s2):
                try:
                    s.stop()
                except Exception:
                    pass

    def test_transport_accepts_single_url_string(self):
        t = HTTPTransport("http://127.0.0.1:1")
        assert t.endpoints == [("127.0.0.1", 1)]
        with pytest.raises(ValueError):
            HTTPTransport([])


class TestWatchResume:
    def test_resume_skips_full_relist_after_rotation(self):
        """The satellite regression: a Reflector whose endpoint dies
        mid-watch rotates and RESUMES the watch from its last
        resourceVersion — list_count stays 1 and later events still
        arrive."""
        store = KVStore()
        api = APIServer(store=store)
        s1 = APIHTTPServer(api).start()
        s2 = APIHTTPServer(api).start()
        refl = None
        try:
            c = Client(HTTPTransport([s1.address, s2.address]))
            c.create("pods", pod_wire("pre"))
            cache = ThreadSafeStore()
            refl = Reflector(c, "pods", cache, namespace="default").start()
            assert refl.wait_for_sync(10)
            assert refl.list_count == 1
            s1.stop(release_store=False)  # kill the watched endpoint
            wc = Client(HTTPTransport(s2.address))
            wc.create("pods", pod_wire("post-rotation"))
            assert wait_until(
                lambda: cache.get("default/post-rotation") is not None
            ), "event after rotation never arrived"
            assert refl.list_count == 1, (
                "rotation must resume the watch, not re-LIST"
            )
        finally:
            if refl is not None:
                refl.stop()
            for s in (s1, s2):
                try:
                    s.stop()
                except Exception:
                    pass

    def test_compacted_resume_falls_back_to_relist(self):
        """When the resume version has been compacted out of watch
        history the server answers 410 Gone and the Reflector falls
        back to a full re-LIST, converging anyway. Driven at the
        cycle seam (one _list_and_watch call per cycle) so the
        outage window is deterministic."""
        api = APIServer(store=KVStore(history_limit=4))
        c = Client(LocalTransport(api))
        c.create("pods", pod_wire("pre"))
        cache = ThreadSafeStore()
        refl = Reflector(c, "pods", cache, namespace="default")
        refl._list()
        assert refl.list_count == 1
        # A prior cycle reached its watch phase, then the transport
        # failed (endpoint rotation): the next cycle tries to resume.
        refl._resume_watch = True
        # Meanwhile the cluster churns far past the history window.
        for i in range(40):
            c.create("pods", pod_wire(f"burst{i}"))
        # The resume attempt 410s and demands a fresh cycle with a
        # full LIST (no list happened in THIS cycle).
        assert refl._list_and_watch() is True
        assert refl.list_count == 1
        assert refl._resume_watch is False
        # The fresh cycle re-LISTs and converges (stop is set so the
        # cycle ends after its list half instead of blocking in the
        # watch loop).
        refl._stop.set()
        refl._list_and_watch()
        assert refl.list_count == 2
        assert cache.get("default/burst39") is not None
