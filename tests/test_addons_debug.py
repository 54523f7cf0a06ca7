"""DNS addon + debug endpoints.

Reference: cluster/addons/dns (skydns + kube2sky), pkg/httplog,
net/http/pprof."""

import socket
import struct
import time
import urllib.request

import pytest

from kubernetes_tpu.addons.dns import ClusterDNS, build_response, parse_query
from kubernetes_tpu.client import Client, LocalTransport
from kubernetes_tpu.server.api import APIServer
from kubernetes_tpu.server.httpserver import APIHTTPServer


def dns_query(port, name, timeout=2.0, host="127.0.0.1"):
    """Send one A query with the stdlib only; return resolved IP or
    None (NXDOMAIN)."""
    qname = b"".join(
        bytes([len(p)]) + p.encode() for p in name.strip(".").split(".")
    ) + b"\x00"
    q = struct.pack(">HHHHHH", 0x1234, 0x0100, 1, 0, 0, 0)
    q += qname + struct.pack(">HH", 1, 1)
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.settimeout(timeout)
    try:
        s.sendto(q, (host, port))
        data, _ = s.recvfrom(512)
    finally:
        s.close()
    txid, flags, qd, an, _, _ = struct.unpack(">HHHHHH", data[:12])
    assert txid == 0x1234
    assert flags & 0x8000  # response bit
    if an == 0:
        assert flags & 0x000F == 3  # NXDOMAIN
        return None
    return socket.inet_ntoa(data[-4:])


def service_wire(name, ip, ns="default"):
    return {
        "kind": "Service",
        "metadata": {"name": name, "namespace": ns},
        "spec": {
            "selector": {"app": name},
            "ports": [{"name": "http", "port": 80}],
            "clusterIP": ip,
        },
    }


class TestClusterDNS:
    @pytest.fixture
    def dns(self):
        api = APIServer()
        client = Client(LocalTransport(api))
        client.create("services", service_wire("web", "10.0.0.10"))
        server = ClusterDNS(Client(LocalTransport(api))).start()
        yield server, client
        server.stop()

    def test_resolves_service_fqdn(self, dns):
        server, client = dns
        assert (
            dns_query(server.port, "web.default.svc.cluster.local")
            == "10.0.0.10"
        )

    def test_resolves_short_form(self, dns):
        server, client = dns
        assert dns_query(server.port, "web.default") == "10.0.0.10"

    def test_nxdomain_for_unknown(self, dns):
        server, client = dns
        assert dns_query(server.port, "nope.default.svc.cluster.local") is None

    def test_tracks_service_churn(self, dns):
        server, client = dns
        client.create("services", service_wire("api", "10.0.0.20"))
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if dns_query(server.port, "api.default") == "10.0.0.20":
                break
            time.sleep(0.05)
        assert dns_query(server.port, "api.default") == "10.0.0.20"
        client.delete("services", "api", namespace="default")
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if dns_query(server.port, "api.default") is None:
                break
            time.sleep(0.05)
        assert dns_query(server.port, "api.default") is None

    def test_wire_roundtrip_units(self):
        q = struct.pack(">HHHHHH", 7, 0x0100, 1, 0, 0, 0)
        q += b"\x03web\x07default\x00" + struct.pack(">HH", 1, 1)
        parsed = parse_query(q)
        assert parsed is not None
        txid, flags, qname, qtype, question = parsed
        assert (txid, qname, qtype) == (7, "web.default", 1)
        resp = build_response(txid, flags, question, "1.2.3.4")
        assert socket.inet_ntoa(resp[-4:]) == "1.2.3.4"


class TestDebugEndpoints:
    @pytest.fixture
    def server(self):
        srv = APIHTTPServer(APIServer()).start()
        yield srv
        srv.stop()

    def test_request_log_records(self, server):
        urllib.request.urlopen(server.address + "/api/v1/nodes").read()
        # The handler records a request after its reply is sent: poll
        # until the record lands.
        deadline = time.monotonic() + 5.0
        while True:
            body = urllib.request.urlopen(
                server.address + "/debug/requests"
            ).read().decode()
            if "/api/v1/nodes" in body or time.monotonic() > deadline:
                break
            time.sleep(0.05)
        assert "/api/v1/nodes" in body
        assert "GET" in body

    def test_stack_dump(self, server):
        body = urllib.request.urlopen(
            server.address + "/debug/stacks"
        ).read().decode()
        assert "--- thread" in body
        assert "serve_forever" in body  # the serving thread is visible

    def test_sampling_profile(self, server):
        body = urllib.request.urlopen(
            server.address + "/debug/profile?seconds=0.3"
        ).read().decode()
        assert "sampling profile:" in body
        assert "samples over" in body

    def test_unknown_debug_404(self, server):
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(server.address + "/debug/nope")
        assert e.value.code == 404


class TestKubeDNSService:
    """The DNS addon published as the well-known kube-dns service
    (cluster/addons/dns skydns-svc.yaml pins 10.0.0.10): with a
    real-portal kube-proxy, VIP:53/UDP actually answers queries."""

    def test_dns_reachable_at_the_well_known_vip(self):
        from kubernetes_tpu.addons import ClusterDNS
        from kubernetes_tpu.proxy.config import ProxyServer
        from kubernetes_tpu.proxy.portal import LoopbackPortals

        if not LoopbackPortals.supported():
            pytest.skip("needs CAP_NET_ADMIN for real portals")
        api = APIServer()
        client = Client(LocalTransport(api))
        dns = ClusterDNS(client).start()
        proxy = None
        try:
            dns.publish(client)
            svc = api.get("services", "default", "kube-dns")
            assert svc["spec"]["clusterIP"] == "10.0.0.10"
            client.create(
                "services", service_wire("web", "10.0.0.77"),
                namespace="default",
            )
            proxy = ProxyServer(client, real_portals=True).start()

            def resolves():
                try:
                    return (
                        dns_query(
                            53, "web.default.svc.cluster.local",
                            host="10.0.0.10",
                        )
                        == "10.0.0.77"
                    )
                except (OSError, AssertionError):
                    return False

            deadline = time.monotonic() + 10
            ok = False
            while time.monotonic() < deadline and not ok:
                ok = resolves()
                time.sleep(0.2)
            assert ok, "kube-dns VIP never answered"
        finally:
            if proxy is not None:
                proxy.stop()
            dns.stop()

    def test_publish_idempotent(self):
        from kubernetes_tpu.addons import ClusterDNS

        api = APIServer()
        client = Client(LocalTransport(api))
        dns = ClusterDNS(client).start()
        try:
            dns.publish(client)
            dns.publish(client)  # restart: must not conflict
            eps = api.get("endpoints", "default", "kube-dns")
            assert eps["subsets"][0]["ports"][0]["port"] == dns.port
        finally:
            dns.stop()
