"""Scheduler benchmark — prints ONE JSON line for the driver.

Headline (BASELINE.md north star): schedule a 50k-pending-pod backlog
onto 5k nodes in < 2s wall-clock, vs the reference's sequential
~15 bindings/s ceiling (scheduler bind rate limit, factory.go:43-46).

Measures the full pipeline: columnar lowering (host) -> upload ->
jitted sequential-parity solve (device) -> assignment readback.
Compile time is excluded via a warmup solve on identical shapes.

Env overrides: BENCH_PODS, BENCH_NODES, BENCH_REPEATS,
BENCH_MODE=backlog|churn (churn = BASELINE config 5: sustained
create/delete stream against a device-resident SolverSession).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_PODS_PER_SEC = 15.0  # reference bind rate limit ceiling


def _mp_context():
    """Process context for the load-generator children. NEVER fork:
    the parent runs JAX plus a dozen reflector/daemon threads, and
    os.fork() from a multithreaded process is exactly what the
    'os.fork() is incompatible with multithreaded code' RuntimeWarning
    (and the latent post-fork deadlock it warns about) is for. The
    children only do sockets/json, so a fresh interpreter via
    forkserver (spawn where unavailable) is cheap and clean."""
    import multiprocessing as mp

    try:
        return mp.get_context("forkserver")
    except ValueError:  # pragma: no cover - non-POSIX fallback
        return mp.get_context("spawn")


def _churn_figure(n_nodes: int, rate: int, ticks: int, mode: str) -> dict:
    """BASELINE config 5 measured: sustained create/delete churn with
    incremental device updates (no re-lowering the cluster). Returns
    {"churn_scheduled_per_sec": ..., ...} for embedding in any record."""
    import random

    from __graft_entry__ import _synthetic_problem  # noqa: F401 (warms imports)
    from kubernetes_tpu.ops import SolverSession
    from kubernetes_tpu.models.objects import (
        Container, Node, NodeCondition, NodeStatus, ObjectMeta, Pod, PodSpec,
        ResourceRequirements,
    )
    from kubernetes_tpu.models.quantity import Quantity, parse_quantity

    rng = random.Random(0)
    nodes = [
        Node(
            metadata=ObjectMeta(name=f"n{j}"),
            status=NodeStatus(
                capacity={
                    "cpu": Quantity.from_milli(rng.choice([8000, 16000, 32000])),
                    "memory": parse_quantity(f"{rng.choice([16, 32, 64])}Gi"),
                    "pods": Quantity.from_int(110),
                },
                conditions=[NodeCondition(type="Ready", status="True")],
            ),
        )
        for j in range(n_nodes)
    ]

    def mkpod(i):
        return Pod(
            metadata=ObjectMeta(name=f"p{i}", namespace="default"),
            spec=PodSpec(
                containers=[
                    Container(
                        name="c", image="app",
                        resources=ResourceRequirements(
                            limits={
                                "cpu": Quantity.from_milli(
                                    rng.choice([100, 250, 500])
                                ),
                                "memory": parse_quantity(
                                    f"{rng.choice([64, 128, 256])}Mi"
                                ),
                            }
                        ),
                    )
                ]
            ),
        )

    session = SolverSession(nodes, mode=mode)
    # Warm-up must compile EVERY executable the timed ticks hit: the
    # solve itself AND the delete-path row scatter at the same dirty-
    # row bucket width the ticks produce (a cold scatter compile was
    # costing ~2.4s on the first timed tick).
    counter = 0
    live = []  # O(1) deletes via swap-with-last (don't time bookkeeping)
    for warm_tick in range(2):
        for _ in range(rate):
            counter += 1
            session.add_pending(mkpod(counter))
        for _ in range(min(rate, len(live))):
            i = rng.randrange(len(live))
            live[i], live[-1] = live[-1], live[i]
            session.delete_assigned(live.pop())
        for key, dest in session.solve():
            if dest is not None:
                live.append(key)

    t0 = time.perf_counter()
    scheduled = 0
    for _ in range(ticks):
        for _ in range(rate):
            counter += 1
            session.add_pending(mkpod(counter))
        for _ in range(min(rate, len(live))):
            i = rng.randrange(len(live))
            live[i], live[-1] = live[-1], live[i]
            session.delete_assigned(live.pop())
        for key, dest in session.solve():
            if dest is not None:
                live.append(key)
                scheduled += 1
    elapsed = time.perf_counter() - t0
    pods_per_sec = scheduled / elapsed
    print(
        f"# churn: {ticks} ticks x {rate} create+delete/s, {scheduled} "
        f"scheduled in {elapsed:.2f}s ({len(live)} live)",
        file=sys.stderr,
    )
    return {
        "churn_scheduled_per_sec": round(pods_per_sec, 1),
        "churn_tick_mode": mode,
        "churn_nodes": n_nodes,
    }


class _LeanHTTP:
    """Minimal keep-alive HTTP/1.1 load driver (the wrk/hey role:
    stdlib http.client costs ~120us/op in pure-Python parsing, which
    on a 1-core host becomes the load generator starving the system
    under test). Server-side handling is unchanged — this only strips
    CLIENT-side stdlib overhead. Not a general client: no chunked
    responses, no redirects; exactly what the apiserver sends on the
    CRUD paths used here."""

    def __init__(self, address: str):
        host, port = address.split("//")[1].split(":")
        self.addr = (host, int(port))
        self.sock = None
        self.buf = b""

    def _connect(self):
        import socket

        self.sock = socket.create_connection(self.addr)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = b""

    def request(self, verb: str, path: str, body: bytes = b"") -> int:
        head = (
            f"{verb} {path} HTTP/1.1\r\nHost: b\r\n"
            f"Content-Length: {len(body)}\r\n"
            + ("Content-Type: application/json\r\n" if body else "")
            + "\r\n"
        ).encode()
        for attempt in (0, 1):
            if self.sock is None:
                self._connect()
            try:
                self.sock.sendall(head + body)
                status, _rbody = self._read_response()
                return status
            except OSError:
                self.sock = None  # stale keep-alive: one retry
                if attempt:
                    raise
        raise OSError("unreachable")

    def _read_response(self):
        while b"\r\n\r\n" not in self.buf:
            chunk = self.sock.recv(65536)
            if not chunk:
                raise OSError("connection closed")
            self.buf += chunk
        head, self.buf = self.buf.split(b"\r\n\r\n", 1)
        lines = head.split(b"\r\n")
        status = int(lines[0].split(b" ", 2)[1])
        clen = 0
        for ln in lines[1:]:
            if ln[:15].lower() == b"content-length:":
                clen = int(ln[15:])
                break
        while len(self.buf) < clen:
            chunk = self.sock.recv(65536)
            if not chunk:
                raise OSError("connection closed")
            self.buf += chunk
        body, self.buf = self.buf[:clen], self.buf[clen:]
        return status, body

    def close(self):
        if self.sock is not None:
            try:
                self.sock.close()
            except OSError:
                pass


def _churn_node_wire(j: int) -> dict:
    """Deterministic per-index node (same values in every process)."""
    return {
        "kind": "Node",
        "metadata": {"name": f"n{j}"},
        "status": {
            "capacity": {
                "cpu": str((8, 16, 32)[j % 3]),
                "memory": f"{(16, 32, 64)[j % 3]}Gi",
                "pods": "110",
            },
            "conditions": [{"type": "Ready", "status": "True"}],
        },
    }


def _churn_pod_wire(name: str) -> dict:
    import zlib

    h = zlib.crc32(name.encode())  # deterministic across processes/runs
    return {
        "kind": "Pod",
        "metadata": {"name": name, "namespace": "default"},
        "spec": {
            "containers": [
                {
                    "name": "c",
                    "image": "app",
                    "resources": {
                        "limits": {
                            "cpu": f"{(100, 250, 500)[h % 3]}m",
                            "memory": f"{(64, 128, 256)[h // 3 % 3]}Mi",
                        }
                    },
                }
            ]
        },
    }


def _churn_load(
    address: str,
    rate: int,
    creators: int,
    warmup_s: float,
    duration_s: float,
    conn,
) -> None:
    """Load-generator process body: paced creators + deleter over lean
    HTTP, a watch stream timestamping binding visibility. Sends a
    result dict (sorted latencies for the measurement window, created
    count, window seconds) through `conn`."""
    import threading

    from kubernetes_tpu.client import Client, HTTPTransport

    stats_lock = threading.Lock()
    t_create: dict = {}
    t_bound: dict = {}
    bound_q: list = []  # names available for deletion, FIFO
    stop = threading.Event()
    errors: list = []
    path = "/api/v1/namespaces/default/pods"

    def watcher():
        client = Client(HTTPTransport(address))
        _, version = client.list("pods", namespace="default")
        stream = client.watch(
            "pods",
            namespace="default",
            since=version,
            field_selector="spec.nodeName!=",
        )
        try:
            while not stop.is_set():
                ev = stream.next(timeout=0.2)
                if ev is None:
                    if stream.closed:
                        return
                    continue
                obj = ev.object
                if not isinstance(obj, dict):
                    continue
                name = obj.get("metadata", {}).get("name")
                if not name or not obj.get("spec", {}).get("nodeName"):
                    continue
                now = time.perf_counter()
                with stats_lock:
                    if name not in t_bound:
                        t_bound[name] = now
                        bound_q.append(name)
        finally:
            stream.close()

    seq_lock = threading.Lock()
    seq = [0]

    def creator(wid):
        c = _LeanHTTP(address)
        interval = creators / rate
        next_t = time.perf_counter()
        while not stop.is_set():
            with seq_lock:
                seq[0] += 1
                name = f"c{seq[0]}"
            body = json.dumps(_churn_pod_wire(name)).encode()
            t0 = time.perf_counter()
            with stats_lock:
                t_create[name] = t0
            try:
                status = c.request("POST", path, body)
                # 409 = our own stale-keep-alive resend raced a create
                # the server already applied (names are unique per run):
                # the pod exists, which is what we wanted.
                if status >= 400 and status != 409:
                    raise RuntimeError(f"create {name}: HTTP {status}")
            except Exception as e:  # pragma: no cover
                errors.append(repr(e))
                with stats_lock:
                    t_create.pop(name, None)
                if len(errors) > 50:
                    return
            next_t += interval
            delay = next_t - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            elif delay < -2.0:
                next_t = time.perf_counter()  # fell behind: re-anchor
        c.close()

    def deleter():
        c = _LeanHTTP(address)
        interval = 1.0 / rate
        next_t = time.perf_counter()
        while not stop.is_set():
            name = None
            with stats_lock:
                # Keep a cushion of live pods so deletes never outpace
                # binds (steady-state live size ~= cushion).
                if len(bound_q) > 200:
                    name = bound_q.pop(0)
            if name is not None:
                try:
                    c.request("DELETE", f"{path}/{name}")
                except Exception:
                    pass
            next_t += interval
            delay = next_t - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            elif delay < -2.0:
                next_t = time.perf_counter()
        c.close()

    threads = [threading.Thread(target=watcher, daemon=True)]
    threads += [
        threading.Thread(target=creator, args=(w,), daemon=True)
        for w in range(creators)
    ]
    threads += [threading.Thread(target=deleter, daemon=True)]
    try:
        for t in threads:
            t.start()
        time.sleep(warmup_s)
        t_start = time.perf_counter()
        time.sleep(duration_s)
        t_end = time.perf_counter()
        # Drain: give in-flight pods a grace window to bind.
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with stats_lock:
                missing = any(
                    t_start <= t0 < t_end and n not in t_bound
                    for n, t0 in t_create.items()
                )
            if not missing:
                break
            time.sleep(0.1)
        with stats_lock:
            lats = sorted(
                t_bound[n] - t0
                for n, t0 in t_create.items()
                if t_start <= t0 < t_end and n in t_bound
            )
            created = sum(
                1 for t0 in t_create.values() if t_start <= t0 < t_end
            )
        if errors:
            conn.send({"error": errors[0]})
        else:
            conn.send(
                {"lats": lats, "created": created, "window": t_end - t_start}
            )
    except Exception as e:  # pragma: no cover
        try:
            conn.send({"error": repr(e)})
        except Exception:
            pass
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=3)


def _api_churn_figure(
    n_nodes: int,
    rate: int,
    duration_s: float,
    mode: str = "scan",
    warmup_s: float = 6.0,
    creators: int = 2,
    gate_s: float = 0.0,
    microticks: bool = True,
) -> dict:
    """The OTHER half of the headline metric (VERDICT r4 #1): p99
    pod-to-bind latency + churn throughput THROUGH the real control
    plane. Pods are created/deleted over the HTTP API against a live
    apiserver; the incremental batch scheduler (its own HTTP client)
    watches, solves on-device, and commits via bulk bindings; a watch
    stream on a third HTTP connection timestamps when each binding
    becomes VISIBLE to a client. Latency = create-call-start ->
    binding-visible-via-watch, the reference's e2e definition
    (test/e2e/util.go:1286-1301); SLO: 99% < 1s (docs/roadmap.md:66).
    """
    from kubernetes_tpu.client import Client, LocalTransport, HTTPTransport
    from kubernetes_tpu.scheduler.daemon import (
        IncrementalBatchScheduler,
        SchedulerConfig,
    )
    from kubernetes_tpu.server.api import APIServer
    from kubernetes_tpu.server.httpserver import APIHTTPServer

    node_wire, pod_wire = _churn_node_wire, _churn_pod_wire

    api = APIServer()
    setup = Client(LocalTransport(api))  # fixture only, not measured
    for j in range(n_nodes):
        setup.create("nodes", node_wire(j))

    import gc

    srv = APIHTTPServer(api, max_in_flight=800).start()

    sched_client = Client(HTTPTransport(srv.address))
    config = SchedulerConfig(sched_client, raw_scheduled_cache=True).start()
    config.wait_for_sync(30.0)
    # prewarm_buckets=1024 + prewarm(): the daemon builds its session
    # and compiles every pod-bucket solve and dirty-row scatter width
    # the timed window can hit BEFORE traffic starts — a fresh bucket
    # must never stall an SLO-gated tick (SolverSession.prewarm).
    # microticks=False is the fixed-tick baseline leg: the PR-11-era
    # cadence (blocking drain window, inline commits) measured on the
    # same box for the before/after comparison BENCH artifacts record.
    sched = IncrementalBatchScheduler(
        config, mode=mode, max_batch=1024, prewarm_buckets=1024,
        microticks=microticks,
    )
    sched.prewarm()
    sched.start()

    # The load generator runs in its OWN process (the reference's e2e
    # shape: the driver is outside the system under test). On a 1-core
    # host this also keeps the driver's Python work off the control
    # plane's GIL.
    ctx = _mp_context()  # child only does sockets/json, no jax
    parent_conn, child_conn = ctx.Pipe(duplex=False)
    child = ctx.Process(
        target=_churn_load,
        args=(srv.address, rate, creators, warmup_s, duration_s, child_conn),
        daemon=True,
    )
    try:
        # The backlog phases (and the control plane just built — 5k
        # nodes of reflector caches + the daemon's session) are a
        # multi-GB heap; a gen2 GC pass over it mid-window lands
        # straight in the bind-latency p99. Freeze it all out of
        # collection consideration for the measured phase. Inside the
        # try: every exit path below unfreezes.
        gc.collect()
        gc.freeze()
        child.start()
        child_conn.close()
        if not parent_conn.poll(warmup_s + duration_s + 60):
            raise RuntimeError("load generator produced no result")
        result = parent_conn.recv()
    finally:
        child.join(timeout=10)
        if child.is_alive():
            child.terminate()
        sched.stop()
        srv.stop()
        gc.unfreeze()
    if "error" in result:
        raise RuntimeError(f"load generator failed: {result['error']}")

    lats = result["lats"]
    unbound = result["created"] - len(lats)
    window = result["window"]
    if not lats:
        raise RuntimeError("no pods bound during the measurement window")

    def pct(p):
        return lats[min(len(lats) - 1, int(p * len(lats)))]

    p50, p99 = pct(0.50), pct(0.99)
    fig = {
        # Full-loop figure (create -> solve -> bind -> watch-visible);
        # the API-plane ingestion figure is churn_api_pods_per_sec from
        # the bulk churn drill (_bulk_churn_figure).
        "churn_bound_pods_per_sec": round(len(lats) / window, 1),
        "bind_latency_p50_s": round(p50, 4),
        "bind_latency_p99_s": round(p99, 4),
        "bind_latency_max_s": round(lats[-1], 4),
        "bind_latency_pods": len(lats),
        "bind_latency_unbound": unbound,
        "bind_latency_nodes": n_nodes,
        "bind_rate_requested": rate,
        "bind_tick_mode": mode,
        # Engine verdict (utils/slo.py BENCH_OBJECTIVES — the 100ms
        # always-resident-loop gate; gate_s>0 overrides the target):
        # the p99 gate, worsened to "burn" outright when any created
        # pod never bound — a cluster that sheds pods cannot pass its
        # latency SLO on the survivors.
        "bind_latency_slo": _slo.worst(
            _slo.verdict_for_value(
                _slo.with_target(
                    _slo.BENCH_OBJECTIVES["bind_latency_slo"], gate_s
                )
                if gate_s
                else _slo.BENCH_OBJECTIVES["bind_latency_slo"],
                p99,
            ),
            "burn" if unbound else "pass",
        ),
        "bind_latency_slo_target": (
            gate_s or _slo.BENCH_OBJECTIVES["bind_latency_slo"].target
        ),
        "bind_microticks": microticks,
    }
    # The production SLO engine's own report over this drill: the
    # apiserver ran in THIS process, so the always-on SLI collector
    # (utils/sli.py) watched every create/bind through the same
    # dispatcher feed production uses. Embedding it proves bench and
    # /debug/slo read one truth.
    report = _slo.evaluate()
    fig["slo_verdict"] = report["verdict"]
    fig["slo_report"] = {
        o["name"]: {
            k: o[k] for k in ("p50", "p99", "value", "samples", "verdict")
            if k in o
        }
        for o in report["objectives"]
    }
    print(
        f"# api-churn: {len(lats)} pods bound through HTTP control plane "
        f"in {window:.1f}s at {n_nodes} nodes — p50 {p50 * 1000:.0f}ms, "
        f"p99 {p99 * 1000:.0f}ms, max {lats[-1] * 1000:.0f}ms, "
        f"{unbound} unbound",
        file=sys.stderr,
    )
    return fig


def _bulk_churn_figure(duration_s: float = 8.0, batch: int = 1024) -> dict:
    """API-plane ingestion under sustained churn (ISSUE 6 headline):
    bulk-create and bulk-delete pods over real HTTP as fast as the
    plane accepts them, each batch one WAL group commit, with a live
    watch connection confirming every create becomes a visible ADDED
    event (counted at the byte level so the load generator, not the
    server, stays out of the measurement's way) and a final LIST
    consistency check. This measures the API/storage plane itself —
    create -> store -> watch fan-out -> delete; the solve-and-bind
    loop has its own drill (_api_churn_figure: bind latency +
    churn_bound_pods_per_sec)."""
    import multiprocessing as mp

    from kubernetes_tpu.client import Client, HTTPTransport
    from kubernetes_tpu.server.api import APIServer
    from kubernetes_tpu.server.httpserver import APIHTTPServer

    api = APIServer()
    api.list("pods", "default")  # build the pods watch cache up front
    srv = APIHTTPServer(api, max_in_flight=800).start()
    ctx = _mp_context()
    parent_conn, child_conn = ctx.Pipe(duplex=False)
    child = ctx.Process(
        target=_bulk_churn_load,
        args=(srv.address, duration_s, batch, child_conn),
        daemon=True,
    )
    try:
        child.start()
        child_conn.close()
        if not parent_conn.poll(duration_s + 60):
            raise RuntimeError("bulk churn load generator produced no result")
        result = parent_conn.recv()
    finally:
        child.join(timeout=10)
        if child.is_alive():
            child.terminate()
    if "error" in result:
        srv.stop()
        raise RuntimeError(f"bulk churn load failed: {result['error']}")
    # Consistency: the survivors the driver didn't delete must all be
    # LISTable (read-your-writes through the watch cache).
    live = len(
        Client(HTTPTransport(srv.address)).list("pods", namespace="default")[0]
    )
    srv.stop()
    created, deleted = result["created"], result["deleted"]
    if live != created - deleted:
        raise RuntimeError(
            f"churn consistency: {created} created - {deleted} deleted "
            f"!= {live} listed"
        )
    rate = created / result["window"]
    fig = {
        "churn_api_pods_per_sec": round(rate, 1),
        "churn_api_created": created,
        "churn_api_deleted": deleted,
        "churn_api_batch": batch,
        "churn_api_watch_added_seen": result["watch_added_seen"],
        # False = the watch was dropped mid-drill (slow consumer): the
        # rate then excludes fan-out cost and must not be trusted.
        "churn_api_watch_complete": result["watch_added_seen"] >= created,
        "churn_api_slo_target": CHURN_API_SLO_PODS_PER_SEC,
        # Engine verdict (utils/slo.py). An incomplete watch means the
        # rate excludes fan-out cost — the figure can't be trusted, so
        # the verdict is at best "warn" regardless of the rate.
        "churn_api_slo": _slo.worst(
            _slo.verdict_for_value(
                _slo.BENCH_OBJECTIVES["churn_api_slo"], rate
            ),
            "pass" if result["watch_added_seen"] >= created else "warn",
        ),
    }
    print(
        f"# bulk-churn: {created} pods created + {deleted} deleted over "
        f"HTTP in {result['window']:.1f}s ({rate:.0f} pods/s each way), "
        f"{result['watch_added_seen']} ADDED frames watched, "
        f"{live} live at drain",
        file=sys.stderr,
    )
    return fig


#: Wire-form pod as a %-template: the churn load generator emits
#: request bodies by string formatting instead of dict building +
#: json.dumps — at bulk rates the driver's own serialization was
#: starving the server under test (sampled stacks showed the apiserver
#: idle in accept/readinto).
_POD_JSON_TMPL = (
    '{"kind": "Pod", "metadata": {"name": "%s", "namespace": "default"}, '
    '"spec": {"containers": [{"name": "c", "image": "app", '
    '"resources": {"limits": {"cpu": "250m", "memory": "128Mi"}}}]}}'
)


def _bulk_churn_load(address: str, duration_s: float, batch: int, conn) -> None:
    """Load-generator process body for _bulk_churn_figure: two bulk
    creator connections pipelined against one bulk deleter, plus a
    raw-socket watch counting ADDED frames on the wire (no per-event
    JSON parse — at bulk rates the stdlib client would be the
    bottleneck, not the server under test)."""
    import socket
    import threading

    host, port = address.split("//")[1].split(":")
    addr = (host, int(port))
    added = [0]
    stop = threading.Event()
    ready = threading.Event()

    def watcher():
        s = socket.create_connection(addr)
        # Deep server-side buffer (?maxsize=): one 1024-pod group
        # commit bursts 2048 events into the queue faster than any
        # consumer can be scheduled; the default 4096 bound would drop
        # this watch mid-drill.
        s.sendall(
            b"GET /api/v1/watch/namespaces/default/pods?maxsize=65536 "
            b"HTTP/1.1\r\nHost: bench\r\n\r\n"
        )
        s.settimeout(0.3)
        pattern = b'{"type": "ADDED"'
        keep = len(pattern) - 1  # tail >= pattern length double-counts
        tail = b""
        n = 0
        # Ready only once the server ANSWERED (headers parsed): the
        # watch is registered before the 200 is sent, so creators
        # released now cannot out-race registration and lose frames.
        hdr = b""
        while b"\r\n\r\n" not in hdr:
            try:
                chunk = s.recv(65536)
            except socket.timeout:
                continue
            if not chunk:
                break
            hdr += chunk
        tail = hdr.split(b"\r\n\r\n", 1)[-1][-keep:] if hdr else b""
        n += hdr.split(b"\r\n\r\n", 1)[-1].count(pattern) if hdr else 0
        added[0] = n
        ready.set()
        try:
            while not stop.is_set():
                try:
                    chunk = s.recv(1 << 20)
                except socket.timeout:
                    continue
                if not chunk:
                    break
                data = tail + chunk
                n += data.count(pattern)
                tail = data[-keep:]
                added[0] = n
        finally:
            s.close()

    wt = threading.Thread(target=watcher, daemon=True)
    wt.start()
    ready.wait(timeout=5)
    path = "/api/v1/namespaces/default/pods"
    seq_lock = threading.Lock()
    seq = [0]
    created = [0]
    deleted = [0]
    delq: list = []
    errors: list = []
    t_end = [0.0]

    def creator():
        c = _LeanHTTP(address)
        try:
            while not stop.is_set() and time.perf_counter() < t_end[0]:
                with seq_lock:
                    s0 = seq[0]
                    seq[0] += batch
                names = [f"bc{s0 + i}" for i in range(batch)]
                body = (
                    '{"items": ['
                    + ",".join(_POD_JSON_TMPL % x for x in names)
                    + "]}"
                ).encode()
                status = c.request("POST", path + ":bulk", body)
                if status != 200:
                    raise RuntimeError(f"bulk create: HTTP {status}")
                created[0] += batch
                with seq_lock:
                    delq.append(names)
        except Exception as e:  # pragma: no cover
            errors.append(repr(e))
        finally:
            c.close()

    def deleter():
        c = _LeanHTTP(address)
        try:
            while not stop.is_set():
                names = None
                with seq_lock:
                    if len(delq) > 2:  # keep a live cushion
                        names = delq.pop(0)
                if names is None:
                    if time.perf_counter() >= t_end[0]:
                        return
                    time.sleep(0.002)
                    continue
                body = (
                    '{"names": ['
                    + ",".join(f'"{x}"' for x in names)
                    + "]}"
                ).encode()
                status = c.request("POST", path + ":bulkdelete", body)
                if status != 200:
                    raise RuntimeError(f"bulk delete: HTTP {status}")
                deleted[0] += len(names)
        except Exception as e:  # pragma: no cover
            errors.append(repr(e))
        finally:
            c.close()

    t0 = time.perf_counter()
    t_end[0] = t0 + duration_s
    threads = [threading.Thread(target=creator, daemon=True) for _ in range(2)]
    threads.append(threading.Thread(target=deleter, daemon=True))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    window = time.perf_counter() - t0
    # Watch drain: every created pod must surface as an ADDED frame.
    deadline = time.monotonic() + 10.0
    while added[0] < created[0] and time.monotonic() < deadline:
        time.sleep(0.05)
    stop.set()
    wt.join(timeout=3)
    if errors:
        conn.send({"error": errors[0]})
    else:
        conn.send(
            {
                "created": created[0],
                "deleted": deleted[0],
                "window": window,
                "watch_added_seen": added[0],
            }
        )


def apichurn_main() -> None:
    n_nodes = int(os.environ.get("BENCH_NODES", "5000"))
    rate = int(os.environ.get("BENCH_CHURN_RATE", "1000"))
    duration = float(os.environ.get("BENCH_CHURN_SECONDS", "10"))
    mode = os.environ.get("BENCH_CHURN_MODE", "scan")
    fig = _api_churn_figure(n_nodes, rate, duration, mode=mode)
    if os.environ.get("BENCH_BASELINE", "0") == "1":
        # Before/after leg: the SAME drill with micro-ticks off (fixed
        # drain window, inline commits, no pipeline) — the fixed-tick
        # cadence this PR replaced, measured on the same box so the
        # artifact records the comparison the acceptance gate asks for.
        base = _api_churn_figure(
            n_nodes, rate, duration, mode=mode, microticks=False
        )
        fig["fixed_tick_baseline"] = {
            k: base[k]
            for k in (
                "bind_latency_p50_s", "bind_latency_p99_s",
                "bind_latency_max_s", "churn_bound_pods_per_sec",
                "bind_latency_pods", "bind_latency_unbound",
            )
        }
    fig.update(_bulk_churn_figure())
    print(
        json.dumps(
            {
                "metric": f"churn_api_pods_per_sec_{n_nodes}nodes",
                "value": fig["churn_api_pods_per_sec"],
                "unit": "pods/s",
                "vs_baseline": round(
                    fig["churn_api_pods_per_sec"] / BASELINE_PODS_PER_SEC, 1
                ),
                **fig,
            }
        )
    )


def _soak_figure(n_nodes: int = 64, seed: int = 7) -> dict:
    """ISSUE 15: a miniature chaos soak (tools/soak.py) inside the
    bench run — hollow-node fleet, one apiserver kill -9 with WAL
    replay, one abrupt daemon kill mid-gang, then a clean measurement
    wave. The artifact carries the chaos plane's acceptance triple:
    faults injected, invariant violations (must chart at ZERO), and
    the post-fault bind p99."""
    import sys as _sys

    _sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tools.soak import run_soak

    artifact = run_soak(
        n_nodes=n_nodes, seed=seed,
        epochs=[
            "baseline", "apiserver_restart",
            "daemon_restart_mid_gang", "final",
        ],
        verbose=False,
    )
    fired = sum(
        s["fired"] for s in artifact["faults_injected"].values()
    )
    return {
        "soak": {
            "nodes": n_nodes,
            "seed": seed,
            "epochs": [e["epoch"] for e in artifact["epochs"]],
            "faults_injected": fired,
            "restarts": artifact["restarts"],
            "pods_bound": artifact["pods_bound"],
            "invariant_violations": len(artifact["invariant_violations"]),
            "violation_detail": artifact["invariant_violations"][:5],
            "post_fault_bind_p50_s": artifact["post_fault_bind_p50_s"],
            "post_fault_bind_p99_s": artifact["post_fault_bind_p99_s"],
            "wall_s": artifact["wall_s"],
        }
    }


def _alerts_overhead_figure() -> dict:
    """ISSUE 20: the bulk-churn drill re-run with the health plane
    LIVE — retention sampler snapshotting every registry series plus
    the burn-rate alert engine evaluating every rule as a sampler
    hook, at a cadence 10x production (0.5s vs 5s). The figure is the
    plane's own measured cost over the drill's wall, gated at <5%:
    ``timeseries_sample_seconds`` times the whole sweep INCLUDING
    hooks, so the fraction covers retention + evaluation together."""
    import time as _time

    from kubernetes_tpu.utils import alerts as _alerts
    from kubernetes_tpu.utils import timeseries as _ts

    def sample_wall() -> float:
        return sum(
            s for (_c, s, _b) in _ts.SAMPLE_SECONDS.snapshot().values()
        )

    interval_s = 0.5
    _alerts.ensure_started(interval_s=interval_s)
    wall0 = sample_wall()
    trans0 = len(_alerts.DEFAULT.transitions())
    t0 = _time.monotonic()
    try:
        fig = _bulk_churn_figure()
    finally:
        _ts.SAMPLER.stop()
    drill_wall = max(_time.monotonic() - t0, 1e-9)
    overhead = (sample_wall() - wall0) / drill_wall
    snap = _alerts.DEFAULT.snapshot()
    fig["alerts"] = {
        "rules_evaluated": len(_alerts.DEFAULT.rules),
        "evaluations": snap["evaluations"],
        "firing": snap["firing"],
        "transitions": len(_alerts.DEFAULT.transitions()) - trans0,
        "sampler_interval_s": interval_s,
        "retained_series": int(_ts.RETAINED.value()),
        "sampler_overhead_fraction": round(overhead, 5),
        "overhead_gate_fraction": 0.05,
        # The acceptance gate: the health plane must cost <5% of the
        # drill it observes (at 10x the production cadence, so the
        # production fraction is ~an order of magnitude lower still).
        "overhead_ok": overhead < 0.05,
    }
    return fig


def _failover_figure(n_nodes: int = 8, rounds: int = 5) -> dict:
    """ISSUE 19: the failover drill behind failover_to_first_bind_s —
    with a pod already trickled in, kill the active scheduler abruptly,
    activate the PREWARMED standby (informers hot, SolverSession
    built), and clock kill -> that pod's bind becoming visible.
    p50/p99 over `rounds` drills; the 1 s p99 gate is the warm-standby
    budget. Lease-expiry wait is deliberately excluded here (it is a
    configured duration, not a performance property — check.sh's
    failover smoke and tier-1 cover the e2e lease path)."""
    from kubernetes_tpu.client import Client, LocalTransport
    from kubernetes_tpu.scheduler.standby import WarmStandbyScheduler
    from kubernetes_tpu.server.api import APIServer
    from kubernetes_tpu.utils import slo as _slo

    api = APIServer()

    def client():
        return Client(LocalTransport(api))

    c = client()
    for j in range(n_nodes):
        c.create("nodes", _churn_node_wire(j))
    samples = []
    active = WarmStandbyScheduler(client(), sync_timeout=120.0)
    active.activate()
    try:
        # Warm the solve path first (bucket compile) — the drill
        # measures failover on a fleet that has served traffic, which
        # is the only fleet a failover can happen on.
        c.create("pods", _churn_pod_wire("failover-warmup"))
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            if c.get("pods", "failover-warmup", namespace="default"
                     ).spec.node_name:
                break
            time.sleep(0.005)
        else:
            raise RuntimeError("failover warmup pod never bound")
        for r in range(rounds):
            # Prewarm the successor BEFORE the crash — the HA deploy
            # shape (HAScheduler keeps exactly one warm non-leader).
            standby = WarmStandbyScheduler(client(), sync_timeout=120.0)
            standby.prewarm()
            active.kill()
            t0 = time.monotonic()
            name = f"failover-r{r}"
            c.create("pods", _churn_pod_wire(name))
            standby.activate()
            deadline = t0 + 60.0
            while time.monotonic() < deadline:
                pod = c.get("pods", name, namespace="default")
                if pod.spec.node_name:
                    break
                time.sleep(0.002)
            else:
                raise RuntimeError(f"failover round {r}: pod never bound")
            samples.append(time.monotonic() - t0)
            active = standby
    finally:
        active.stop()
    samples.sort()
    p50 = samples[len(samples) // 2]
    p99 = samples[min(len(samples) - 1, int(len(samples) * 0.99))]
    obj = _slo.BENCH_OBJECTIVES["failover_to_first_bind_s"]
    print(
        f"# failover: {rounds} scheduler-leader kills at {n_nodes} nodes "
        f"— kill-to-first-bind p50 {p50 * 1000:.0f}ms, "
        f"p99 {p99 * 1000:.0f}ms (gate {obj.target:.1f}s)",
        file=sys.stderr,
    )
    return {
        "failover_rounds": rounds,
        "failover_nodes": n_nodes,
        "failover_to_first_bind_p50_s": round(p50, 4),
        "failover_to_first_bind_p99_s": round(p99, 4),
        "failover_slo_target_s": obj.target,
        "failover_slo": _slo.verdict_for_value(obj, p99),
    }


def _capacity_figure(n_pods: int = 32) -> dict:
    """ISSUE 16: capacity & fragmentation figures from a LIVE
    micro-tick daemon — an in-process cluster loaded to a meaningful
    fill so the fragmentation score and probe-shape headroom read
    back non-trivially (the acceptance gate pins both keys in this
    artifact)."""
    from kubernetes_tpu.client import Client, LocalTransport
    from kubernetes_tpu.scheduler.daemon import (
        IncrementalBatchScheduler,
        SchedulerConfig,
    )
    from kubernetes_tpu.server.api import APIServer
    from kubernetes_tpu.utils import capacity as capmod

    def node_wire(j):
        return {
            "kind": "Node", "metadata": {"name": f"cap-n{j}"},
            "status": {
                "capacity": {"cpu": "2", "memory": "4Gi", "pods": "110"},
                "conditions": [{"type": "Ready", "status": "True"}],
            },
        }

    def pod_wire(name):
        return {
            "kind": "Pod",
            "metadata": {"name": name, "namespace": "default"},
            "spec": {"containers": [{
                "name": "c", "image": "pause",
                "resources": {"limits": {"cpu": "200m", "memory": "128Mi"}},
            }]},
        }

    # Fresh measurement window: earlier segments drove daemons in this
    # process and fed the same process-global monitor.
    capmod.DEFAULT.reset()
    api = APIServer()
    client = Client(LocalTransport(api))
    for j in range(4):
        client.create("nodes", node_wire(j))
    cfg = SchedulerConfig(Client(LocalTransport(api))).start()
    cfg.wait_for_sync(60)
    sched = IncrementalBatchScheduler(cfg)
    bound = 0
    try:
        sched.start()
        # 32 x 200m on 4 x 2000m: an ~80% cpu-tight fill, so the big
        # slice probes lose headroom while small ones keep it.
        for i in range(n_pods):
            client.create("pods", pod_wire(f"cap-p{i}"))
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            pods, _ = client.list("pods", namespace="default")
            bound = sum(1 for p in pods if p.spec.node_name)
            if bound >= n_pods and capmod.DEFAULT.snapshot()["sampled"]:
                break
            time.sleep(0.1)
    finally:
        sched.stop()
        cfg.stop()
    snap = capmod.DEFAULT.snapshot()
    fig = {"capacity_pods_bound": bound}
    if snap.get("sampled"):
        fig.update(
            {
                "fragmentation_score": snap["fragmentation_score"],
                "slice_alloc_success_rate": snap[
                    "slice_alloc_success_rate"
                ],
                "capacity_samples": snap["samples"],
                "capacity_stranded_nodes": snap["stranded_node_count"],
                "cluster_headroom_pods": {
                    p["shape"]: p["headroom_pods"] for p in snap["probes"]
                },
            }
        )
    return fig


def _rebalance_figure(n_nodes: int = 4) -> dict:
    """ISSUE 17: one live defrag cycle on a deliberately fragmented
    cluster — every node carries three 1000m fillers (born bound, the
    static-pod create shape), leaving a 1000m shard per node, so the
    slice-8x2000m probe shape has ZERO headroom until the descheduler
    consolidates two shards onto one node. The acceptance gate pins
    fragmentation_score_before > fragmentation_score_after in this
    artifact; the post-defrag 2000m probe binding is the payoff."""
    from kubernetes_tpu.client import Client, LocalTransport
    from kubernetes_tpu.controllers.descheduler import Descheduler
    from kubernetes_tpu.scheduler.daemon import (
        IncrementalBatchScheduler,
        SchedulerConfig,
    )
    from kubernetes_tpu.server.api import APIServer
    from kubernetes_tpu.utils import capacity as capmod
    from kubernetes_tpu.models.objects import REBALANCE_DEST_ANNOTATION
    from kubernetes_tpu.utils import rebalance as rebmod

    def node_wire(j):
        return {
            "kind": "Node", "metadata": {"name": f"reb-n{j}"},
            "status": {
                "capacity": {"cpu": "4", "memory": "8Gi", "pods": "110"},
                "conditions": [{"type": "Ready", "status": "True"}],
            },
        }

    def pod_wire(name, cpu, node=""):
        spec = {"containers": [{
            "name": "c", "image": "pause",
            "resources": {"limits": {"cpu": cpu, "memory": "256Mi"}},
        }]}
        if node:
            spec["nodeName"] = node
        return {
            "kind": "Pod",
            "metadata": {"name": name, "namespace": "default"},
            "spec": spec,
        }

    capmod.DEFAULT.reset()
    rebmod.DEFAULT.reset()
    api = APIServer()
    client = Client(LocalTransport(api))
    for j in range(n_nodes):
        client.create("nodes", node_wire(j))
    # Born-bound fillers: the only race-free way to stage an exact
    # fragmented placement (a live scheduler would pack them).
    for j in range(n_nodes):
        for k in range(3):
            client.create(
                "pods", pod_wire(f"reb-f{j}-{k}", "1", node=f"reb-n{j}")
            )
    cfg = SchedulerConfig(Client(LocalTransport(api))).start()
    cfg.wait_for_sync(60)
    sched = IncrementalBatchScheduler(cfg)
    fig: dict = {}
    try:
        sched.start()
        desched = Descheduler(
            client,
            frag_threshold=0.01,
            move_budget=8,
            disruption_cap=8,
            wait_timeout_s=10.0,
        )
        summary = desched.sync_once(force=True)
        # Let every evicted mover re-bind on its nominated node before
        # reading the payoff (the dest annotation marks movers).
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            pods, _ = client.list("pods", namespace="default")
            movers = [
                p for p in pods
                if (p.metadata.annotations or {}).get(
                    REBALANCE_DEST_ANNOTATION
                )
            ]
            if all(p.spec.node_name for p in movers):
                break
            time.sleep(0.1)
        # The payoff: a 2000m slice-shaped pod that had zero headroom
        # pre-defrag binds on the consolidated node.
        client.create("pods", pod_wire("reb-probe", "2"))
        probe_bound = False
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            probe = client.get("pods", "reb-probe", namespace="default")
            if probe.spec.node_name:
                probe_bound = True
                break
            time.sleep(0.1)
        fig = {
            "fragmentation_score_before": summary["score_before"],
            "fragmentation_score_after": summary["score_after"],
            "rebalance_improvement": summary["improvement"],
            "rebalance_moves_executed": summary["moves_executed"],
            "rebalance_probe_bound": probe_bound,
        }
        if summary["improvement"] > 0:
            fig["rebalance_moves_per_improvement"] = round(
                summary["moves_executed"] / summary["improvement"], 2
            )
    finally:
        sched.stop()
        cfg.stop()
    return fig


def churn_main() -> None:
    n_nodes = int(os.environ.get("BENCH_NODES", "5000"))
    rate = int(os.environ.get("BENCH_CHURN_RATE", "1000"))  # pods/s each way
    ticks = int(os.environ.get("BENCH_CHURN_TICKS", "10"))
    mode = os.environ.get("BENCH_CHURN_MODE", "scan")
    fig = _churn_figure(n_nodes, rate, ticks, mode)
    pods_per_sec = fig["churn_scheduled_per_sec"]
    print(
        json.dumps(
            {
                "metric": f"churn_scheduled_per_sec_{n_nodes}nodes",
                "value": pods_per_sec,
                "unit": "pods/s",
                "vs_baseline": round(pods_per_sec / BASELINE_PODS_PER_SEC, 1),
                "tick_mode": mode,
            }
        )
    )


def _hotspot_figure() -> dict:
    """Sinkhorn's winning regime (VERDICT r4 #9): a capacity-tight
    heterogeneous fleet — 50 big nodes every pod prefers + 950 small,
    sized so the fleet is ~85% CPU-tight. Plain waves stampede the big
    nodes and drain in dribbles (the packer admits only per-node
    capacity per wave); congestion prices meter demand so whole waves
    survive: measured ~1.9x fewer device steps, ~1.6x faster solve,
    and slightly better mean regret at equal balance."""
    import numpy as np

    from kubernetes_tpu.models import serde
    from kubernetes_tpu.models.columnar import build_snapshot
    from kubernetes_tpu.models.objects import Node, Pod
    from kubernetes_tpu.ops import device_snapshot
    from kubernetes_tpu.ops.oracle import assignment_quality
    from kubernetes_tpu.ops.sinkhorn import sinkhorn_assignments
    from kubernetes_tpu.ops.wave import wave_assignments

    def node_wire(j):
        return {
            "kind": "Node",
            "metadata": {"name": f"h{j}"},
            "status": {
                "capacity": {
                    "cpu": "32" if j < 50 else "4",  # 50 hot + 950 small
                    "memory": "32Gi",
                    "pods": "110",
                },
                "conditions": [{"type": "Ready", "status": "True"}],
            },
        }

    def pod_wire(name):  # identical demand: maximal contention
        return {
            "kind": "Pod",
            "metadata": {"name": name, "namespace": "default"},
            "spec": {
                "containers": [
                    {
                        "name": "c",
                        "image": "app",
                        "resources": {
                            "limits": {"cpu": "250m", "memory": "128Mi"}
                        },
                    }
                ]
            },
        }

    nodes = [serde.from_wire(Node, node_wire(j)) for j in range(1000)]
    total_milli = 50 * 32000 + 950 * 4000
    n_pods = int(total_milli * 0.85 / 250)
    pods = [
        serde.from_wire(Pod, pod_wire(f"h{i}")) for i in range(n_pods)
    ]
    snap = build_snapshot(pods, nodes)
    d = device_snapshot(snap)
    out = {"hotspot_pods": n_pods}
    for label, fn in (("wave", wave_assignments), ("sinkhorn", sinkhorn_assignments)):
        fn(d)  # warm
        t0 = time.perf_counter()
        a, w = fn(d)
        a = np.asarray(a)[: d.n_pods]
        elapsed = time.perf_counter() - t0
        q = assignment_quality(snap, a)
        out[f"hotspot_{label}_waves"] = int(w)
        out[f"hotspot_{label}_solve_s"] = round(elapsed, 3)
        out[f"hotspot_{label}_placed"] = int((a >= 0).sum())
        out[f"hotspot_{label}_mean_regret"] = round(q["mean_regret"], 2)
    print(
        f"# hotspot ({n_pods} pods, 85% tight): sinkhorn "
        f"{out['hotspot_sinkhorn_waves']} waves/"
        f"{out['hotspot_sinkhorn_solve_s']}s vs wave "
        f"{out['hotspot_wave_waves']} waves/"
        f"{out['hotspot_wave_solve_s']}s",
        file=sys.stderr,
    )
    return out


def _parity_figures() -> dict:
    """Parity evidence published with every bench run (VERDICT r1 #3).

    - BASELINE config 2 (1k x 100): device vs the scalar object-graph
      oracle — the reference semantics themselves.
    - BASELINE config 3 (10k x 1k): device vs the sequential NumPy
      oracle (exact host arithmetic replay; its equivalence to the
      scalar oracle is tested in tests/test_solver_parity.py).
    - The NORTH-STAR shape (BENCH_PODS x BENCH_NODES, 50k x 5k by
      default): device vs the NumPy oracle at full scale — the >=0.99
      number BASELINE.md demands, measured rather than extrapolated
      (VERDICT r2 item 3). BENCH_FULL_PARITY=0 skips it.
    """
    import numpy as np

    from __graft_entry__ import _synthetic_objects
    from kubernetes_tpu.models.columnar import build_snapshot
    from kubernetes_tpu.ops import device_snapshot
    from kubernetes_tpu.ops.oracle import solve_sequential_numpy
    from kubernetes_tpu.ops.solver import solve_assignments
    from kubernetes_tpu.scheduler.batch import (
        parity_report,
        schedule_backlog_scalar,
    )

    out = {}
    pods, nodes, services = _synthetic_objects(1000, 100, seed=11)
    snap = build_snapshot(pods, nodes, services=services)
    scalar = schedule_backlog_scalar(pods, nodes, services=services)
    dev = solve_assignments(device_snapshot(snap))
    names = snap.nodes.names
    dev_names = [names[i] if i >= 0 else None for i in dev]
    out["parity_scalar_1kx100"], _ = parity_report(scalar, dev_names)

    pods, nodes, services = _synthetic_objects(10000, 1000, seed=12)
    snap = build_snapshot(pods, nodes, services=services)
    seq = solve_sequential_numpy(snap)
    d = device_snapshot(snap)
    dev = np.asarray(solve_assignments(d))
    out["parity_seq_oracle_10kx1k"] = float((seq == dev).mean())

    if os.environ.get("BENCH_FULL_PARITY", "1") != "0":
        n_pods = int(os.environ.get("BENCH_PODS", "50000"))
        n_nodes = int(os.environ.get("BENCH_NODES", "5000"))
        pods, nodes, services = _synthetic_objects(n_pods, n_nodes, seed=13)
        snap = build_snapshot(pods, nodes, services=services)
        seq = solve_sequential_numpy(snap)
        dev = np.asarray(solve_assignments(device_snapshot(snap)))
        def _k(n: int) -> str:
            return f"{n // 1000}k" if n >= 1000 else str(n)

        key = f"parity_seq_oracle_{_k(n_pods)}x{_k(n_nodes)}"
        out[key] = float((seq == dev).mean())
    # NOTE: decision-identity parity is only meaningful for the scan
    # (which replicates the oracle's lowest-index tie-break). The
    # approximate modes (wave/sinkhorn) hash their ties, so on fleets
    # full of equal-score nodes their decisions rarely coincide with
    # the oracle's pick even at equal quality — their published
    # quality figures are placed counts and load stddev instead.
    return {k: round(v, 4) for k, v in out.items()}


#: Warn-only SLO thresholds for the API-plane drills (ISSUE 6): the
#: achieved figures and these targets are BOTH recorded in the bench
#: JSON; missing a target flags "warn", never fails the run. Since
#: PR 9 the definitions live in the production SLO engine
#: (utils/slo.BENCH_OBJECTIVES) so bench and `ktctl slo` can never
#: disagree; these module constants just surface the targets.
from kubernetes_tpu.utils import slo as _slo  # noqa: E402

CHURN_API_SLO_PODS_PER_SEC = _slo.BENCH_OBJECTIVES["churn_api_slo"].target
POD_CRUD_SLO_OPS_PER_SEC = _slo.BENCH_OBJECTIVES["pod_crud_slo"].target


def _crud_pod_wire(name: str) -> dict:
    return {
        "kind": "Pod",
        "metadata": {"name": name, "namespace": "default"},
        "spec": {"containers": [{"name": "c", "image": "x"}]},
    }


def _crud_worker(address, wid, tasks, batch, errors) -> None:
    """One CRUD driver connection: bulk create -> LIST -> bulk update
    -> bulk delete per cycle. Module-level (not a closure) so the
    forkserver/spawn driver process can pickle its way here."""
    c = _LeanHTTP(address)
    path = "/api/v1/namespaces/default/pods"
    try:
        for i in range(tasks):
            names = [f"crud-{wid}-{i}-{j}" for j in range(batch)]
            items = [_crud_pod_wire(n) for n in names]
            st = c.request(
                "POST", path + ":bulk",
                json.dumps({"items": items}).encode(),
            )
            if st != 200:
                raise RuntimeError(f"bulk create: HTTP {st}")
            # Read: one LIST over this worker's label-less namespace
            # view (served from the watch cache's per-object
            # encodings).
            st = c.request("GET", path)
            if st != 200:
                raise RuntimeError(f"list: HTTP {st}")
            for it in items:
                it["metadata"]["labels"] = {"touched": "true"}
                it["metadata"].pop("resourceVersion", None)
            st = c.request(
                "POST", path + ":bulkupdate",
                json.dumps({"items": items}).encode(),
            )
            if st != 200:
                raise RuntimeError(f"bulk update: HTTP {st}")
            st = c.request(
                "POST", path + ":bulkdelete",
                json.dumps({"names": names}).encode(),
            )
            if st != 200:
                raise RuntimeError(f"bulk delete: HTTP {st}")
    except Exception as e:  # pragma: no cover
        errors.append(e)
    finally:
        c.close()


def _crud_drive(address, n_workers, n_tasks, batch, conn) -> None:
    """Driver process body for _crud_figure: the timed worker threads
    in their own interpreter, result over the pipe."""
    import threading

    errors: list = []
    t0 = time.perf_counter()
    threads = [
        threading.Thread(
            target=_crud_worker, args=(address, w, n_tasks, batch, errors)
        )
        for w in range(n_workers)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    conn.send({"elapsed": elapsed, "errors": [repr(e) for e in errors]})


def _crud_figure(n_workers: int, n_tasks: int, batch: int = 256) -> dict:
    """Master pod-CRUD throughput over real HTTP (reference:
    test/integration/master_benchmark_test.go:38-93 — -bench-pods /
    -bench-workers against a local master), driven through the BULK
    verbs: each cycle bulk-creates `batch` pods, reads them back in one
    watch-cache LIST, bulk-updates them (label touch), and bulk-deletes
    them — 4 object operations per pod, one WAL group commit per batch
    verb. `n_tasks` counts cycles per worker. Returns
    {"pod_crud_ops_per_sec": ..., ...} (ops = objects touched)."""
    from kubernetes_tpu.server.api import APIServer
    from kubernetes_tpu.server.httpserver import APIHTTPServer

    api = APIServer()
    api.list("pods", "default")  # build the pods watch cache up front
    srv = APIHTTPServer(api).start()
    try:
        ops = 4  # create + read + update(label) + delete, per pod

        # Short warmup (primes connections/threads); a failure here
        # means the server is broken — don't run the timed section.
        errors: list = []
        _crud_worker(srv.address, "warm", 2, batch, errors)
        if errors:
            raise errors[0]

        # The timed workers run in their OWN process: the load
        # generator's JSON encode/decode must not share the control
        # plane's GIL, or the driver becomes the thing measured.
        ctx = _mp_context()
        parent_conn, child_conn = ctx.Pipe(duplex=False)
        child = ctx.Process(
            target=_crud_drive,
            args=(srv.address, n_workers, n_tasks, batch, child_conn),
            daemon=True,
        )
        child.start()
        child_conn.close()
        if not parent_conn.poll(600):
            raise RuntimeError("crud drivers produced no result")
        result = parent_conn.recv()
        child.join(timeout=10)
        if result["errors"]:
            raise RuntimeError(result["errors"][0])
        elapsed = result["elapsed"]
        total_ops = n_workers * n_tasks * batch * ops
        rate = total_ops / elapsed
        print(
            f"# crud: {n_workers} workers x {n_tasks} cycles x {batch} pods "
            f"x {ops} bulk ops in {elapsed:.2f}s over HTTP "
            f"({rate:.0f} ops/s)",
            file=sys.stderr,
        )
        return {
            "pod_crud_ops_per_sec": round(rate, 1),
            "crud_workers": n_workers,
            "crud_batch": batch,
            "pod_crud_slo_target": POD_CRUD_SLO_OPS_PER_SEC,
            # Engine verdict (utils/slo.py): the warn-severity floor —
            # identical definition production serves at /debug/slo.
            "pod_crud_slo": _slo.verdict_for_value(
                _slo.BENCH_OBJECTIVES["pod_crud_slo"], rate
            ),
        }
    finally:
        srv.stop()


def crud_main() -> None:
    n_workers = int(os.environ.get("BENCH_CRUD_WORKERS", "4"))
    n_tasks = int(os.environ.get("BENCH_CRUD_TASKS", "200"))  # per worker
    fig = _crud_figure(n_workers, n_tasks)
    print(
        json.dumps(
            {
                "metric": f"pod_crud_ops_per_sec_{n_workers}w",
                "value": fig["pod_crud_ops_per_sec"],
                "unit": "ops/s",
                "vs_baseline": 0,  # reference publishes no number
            }
        )
    )


def main() -> None:
    n_pods = int(os.environ.get("BENCH_PODS", "50000"))
    n_nodes = int(os.environ.get("BENCH_NODES", "5000"))
    repeats = int(os.environ.get("BENCH_REPEATS", "3"))

    import numpy as np

    from __graft_entry__ import _synthetic_objects
    from kubernetes_tpu.models.columnar import build_snapshot
    from kubernetes_tpu.ops import device_snapshot
    from kubernetes_tpu.ops.solver import solve

    import gc

    from kubernetes_tpu.ops.pipeline import solve_backlog_pipelined

    # Fast-path configuration (VERDICT r3 next #1): the wave-mode
    # chunked pipeline. chunk=25088 (2 chunks at 50k) swept best on
    # hardware — fewer chunk-boundary waves than small chunks, while
    # still overlapping chunk 2's host lowering with chunk 1's device
    # waves (single-chunk control: ~1.2s; 8192 chunks: ~1.5s;
    # 25088: ~0.89s).
    fast_mode = os.environ.get("BENCH_FAST_MODE", "wave")
    fast_chunk = int(os.environ.get("BENCH_FAST_CHUNK", "25088"))

    # Warmup: one FULL pass of each path (compile + first-execution
    # program-load costs excluded from every timed repeat).
    pods, nodes, services = _synthetic_objects(n_pods, n_nodes, seed=1)
    solve_backlog_pipelined(pods, nodes, services=services)
    solve_backlog_pipelined(
        pods, nodes, services=services, mode=fast_mode, chunk=fast_chunk
    )
    snap = build_snapshot(pods, nodes, services=services)
    d = device_snapshot(snap)
    np.asarray(solve(d.pods, d.nodes))
    del snap, d

    # Fresh in-situ phase window: the warmup's observations include the
    # XLA compiles, which would swamp the p99 of the steady-state phase
    # histogram the headline repeats populate below.
    from kubernetes_tpu.utils import tracing as _tracing

    _tracing.PHASE_SECONDS.reset()

    # Each fixture is built OUTSIDE its timed region: creating the
    # synthetic workload objects is test scaffolding, not framework
    # work. The timed region is the framework's full pipeline from API
    # objects to bound assignments: columnar lowering -> upload ->
    # jitted solve -> readback. GC is paused inside the timed region
    # (single-core machine: a collection pass over 50k live API objects
    # lands directly on the critical path).
    #
    # Headline path: solve_backlog_pipelined (chunked; host lowering
    # and upload overlap the device scan; decisions bit-identical).
    times = []
    placed = 0
    for r in range(repeats):
        pods, nodes, services = _synthetic_objects(n_pods, n_nodes, seed=2 + r)
        gc.collect()
        gc.disable()
        t0 = time.perf_counter()
        out = solve_backlog_pipelined(pods, nodes, services=services)
        t1 = time.perf_counter()
        gc.enable()
        times.append(t1 - t0)
        placed = sum(1 for x in out if x is not None)

    # Fast path: same end-to-end contract (API objects in, bound node
    # names out), wave-family solver, quality-gated below — regret
    # bounds decide whether it may carry the headline.
    fast_times = []
    fast_placed = 0
    for r in range(repeats):
        pods, nodes, services = _synthetic_objects(n_pods, n_nodes, seed=2 + r)
        gc.collect()
        gc.disable()
        t0 = time.perf_counter()
        out = solve_backlog_pipelined(
            pods, nodes, services=services, mode=fast_mode, chunk=fast_chunk
        )
        t1 = time.perf_counter()
        gc.enable()
        fast_times.append(t1 - t0)
        fast_placed = sum(1 for x in out if x is not None)

    # In-situ phase histograms (utils/tracing.PHASE_SECONDS): the
    # always-on per-phase instrumentation inside the pipeline itself,
    # captured over the headline repeats above — device timings as the
    # running system sees them, not an external stopwatch. Under async
    # dispatch "solve" is dispatch-side; device time drains into the
    # blocking "readback".
    phase_p50 = {}
    phase_p99 = {}
    _phase_keys = [k for (k,) in _tracing.PHASE_SECONDS.label_values()]
    for ph in sorted(_phase_keys):
        p50 = _tracing.PHASE_SECONDS.quantile(0.5, phase=ph)
        p99 = _tracing.PHASE_SECONDS.quantile(0.99, phase=ph)
        if p50 == p50:  # NaN-safe: keep the BENCH json strictly valid
            phase_p50[ph] = round(p50, 4)
        if p99 == p99:
            phase_p99[ph] = round(p99, 4)

    # One monolithic (unpipelined) pass for the per-phase breakdown —
    # the pipeline overlaps these phases, so they are only separable
    # when run serially.
    pods, nodes, services = _synthetic_objects(n_pods, n_nodes, seed=2)
    gc.collect()
    gc.disable()
    t0 = time.perf_counter()
    snap = build_snapshot(pods, nodes, services=services)
    t1 = time.perf_counter()
    d = device_snapshot(snap)
    import jax

    jax.block_until_ready((d.pods, d.nodes))
    t2 = time.perf_counter()
    out = solve(d.pods, d.nodes)
    out.block_until_ready()
    t3 = time.perf_counter()
    np.asarray(out)
    t4 = time.perf_counter()
    gc.enable()
    phases = {
        "lower": round(t1 - t0, 3),
        "upload": round(t2 - t1, 3),
        "solve": round(t3 - t2, 3),
        "readback": round(t4 - t3, 3),
        "serial_total": round(t4 - t0, 3),
    }

    # Wave-vs-scan comparison (VERDICT r1 #6): the batched wave solver
    # against the sequential-parity scan on the same device problem.
    from kubernetes_tpu.ops.wave import wave_assignments

    pods, nodes, services = _synthetic_objects(n_pods, n_nodes, seed=2)
    snap = build_snapshot(pods, nodes, services=services)
    d = device_snapshot(snap)
    wave_assignments(d)  # warm
    gc.collect()
    t0 = time.perf_counter()
    wave_assign, waves = wave_assignments(d)
    t_wave = time.perf_counter() - t0
    t0 = time.perf_counter()
    np.asarray(solve(d.pods, d.nodes))
    t_scan = time.perf_counter() - t0
    wave_placed = int((wave_assign >= 0).sum())
    wave_stats = {
        "wave_solve_s": round(t_wave, 3),
        "scan_solve_s": round(t_scan, 3),
        "wave_speedup": round(t_scan / max(t_wave, 1e-9), 2),
        "wave_count": int(waves),
        "pods_per_wave": round(wave_placed / max(int(waves), 1), 1),
        "wave_placed": wave_placed,
    }

    # Sinkhorn-matched mode (the north star's "Hungarian/Sinkhorn"
    # framing): congestion-priced waves; published next to the plain
    # wave so the step-count and balance win is measurable.
    from kubernetes_tpu.ops.sinkhorn import sinkhorn_assignments

    sinkhorn_assignments(d)  # warm
    gc.collect()
    t0 = time.perf_counter()
    sk_assign, sk_waves = sinkhorn_assignments(d)
    t_sk = time.perf_counter() - t0
    sk_placed = int((sk_assign >= 0).sum())
    per_node = np.bincount(
        sk_assign[sk_assign >= 0], minlength=d.n_nodes
    )[: d.n_nodes]
    wave_per_node = np.bincount(
        wave_assign[wave_assign >= 0].astype(int), minlength=d.n_nodes
    )[: d.n_nodes]
    wave_stats.update(
        {
            "sinkhorn_solve_s": round(t_sk, 3),
            "sinkhorn_waves": int(sk_waves),
            "sinkhorn_placed": sk_placed,
            "sinkhorn_load_stddev": round(float(per_node.std()), 2),
            "wave_load_stddev": round(float(wave_per_node.std()), 2),
        }
    )

    # Decision quality of the approximate modes (VERDICT r2 item 4):
    # pod-order replay against the greedy oracle — mean/p99 score
    # regret and exact-greedy match rate at 10k x 1k (scores are a
    # 0-30 scale: three 0-10 priorities). Match-rate vs the scan is
    # near zero by construction (tie hashing), so regret is the
    # published quality number; tests/test_quality_regression.py
    # bounds it in CI.
    from kubernetes_tpu.ops.oracle import assignment_quality

    pods_q, nodes_q, svcs_q = _synthetic_objects(10000, 1000, seed=12)
    snap_q = build_snapshot(pods_q, nodes_q, services=svcs_q)
    d_q = device_snapshot(snap_q)
    for label, fn in (
        ("wave", wave_assignments),
        ("sinkhorn", sinkhorn_assignments),
    ):
        a, _w = fn(d_q)
        a = np.asarray(a)[: d_q.n_pods]
        q = assignment_quality(snap_q, a)
        wave_stats[f"{label}_mean_regret_10kx1k"] = round(q["mean_regret"], 3)
        wave_stats[f"{label}_p99_regret_10kx1k"] = round(q["p99_regret"], 1)
        wave_stats[f"{label}_greedy_match_10kx1k"] = round(q["greedy_match"], 3)

    # BASELINE configs 1-3 (100x10, 1k x 100, 10k x 1k): the small and
    # mid configurations through the same full pipeline — published so
    # every BASELINE row has a measured number, not just the headline.
    small_walls = {}
    for cp, cn in ((100, 10), (1000, 100), (10000, 1000)):
        pods_s, nodes_s, svcs_s = _synthetic_objects(cp, cn, seed=7)
        solve_backlog_pipelined(pods_s, nodes_s, services=svcs_s)  # warm
        pods_s, nodes_s, svcs_s = _synthetic_objects(cp, cn, seed=8)
        gc.collect()
        t0 = time.perf_counter()
        solve_backlog_pipelined(pods_s, nodes_s, services=svcs_s)
        small_walls[f"{cp}x{cn}"] = round(time.perf_counter() - t0, 4)

    # Quality gate for the fast path: regret of the CHUNKED pipeline's
    # own decisions at 10k x 1k (the bounds tests/test_quality_regression.py
    # enforces in CI: mean <= 1.5, p99 <= 5). Passing lets the fast wall
    # carry the headline; failing falls back to the parity scan's wall —
    # speed never silently buys worse placements.
    name_idx = {n.metadata.name: i for i, n in enumerate(nodes_q)}
    fast_out = solve_backlog_pipelined(
        pods_q, nodes_q, services=svcs_q, mode=fast_mode, chunk=fast_chunk
    )
    fast_a = np.array(
        [name_idx.get(x, -1) if x is not None else -1 for x in fast_out],
        dtype=np.int32,
    )
    fast_q = assignment_quality(snap_q, fast_a)
    gate_ok = fast_q["mean_regret"] <= 1.5 and fast_q["p99_regret"] <= 5.0

    parity = _parity_figures()
    best = min(times)
    best_fast = min(fast_times)
    # The parity scan is ALWAYS quality-eligible (it IS the oracle
    # semantics); the approximate fast path must both pass its regret
    # gate and actually be faster to carry the headline. With the
    # pallas scan kernel the exact path usually wins outright.
    headline = n_pods / (best_fast if (gate_ok and best_fast < best) else best)
    record = {
        "metric": f"pods_scheduled_per_sec_{n_pods//1000}kx{n_nodes}",
        "value": round(headline, 1),
        "unit": "pods/s",
        "vs_baseline": round(headline / BASELINE_PODS_PER_SEC, 1),
        "wall_fast_s": [round(t, 3) for t in fast_times],
        "fast_mode": fast_mode,
        "fast_chunk": fast_chunk,
        "fast_placed": fast_placed,
        "fast_mean_regret_10kx1k": round(fast_q["mean_regret"], 3),
        "fast_p99_regret_10kx1k": round(fast_q["p99_regret"], 1),
        "fast_quality_gate": "pass" if gate_ok else "FAIL (headline=scan)",
        "headline_path": "fast" if (gate_ok and best_fast < best) else "scan",
        "wall_s": [round(t, 3) for t in times],
        "phases_serial_s": phases,
        "phase_p50_s": phase_p50,
        "phase_p99_s": phase_p99,
        "placed": placed,
    }
    record["config_walls_s"] = small_walls
    record.update(wave_stats)
    # Sinkhorn convergence telemetry next to the phase percentiles:
    # iteration-count p50/p99 + final residual, read from the same
    # always-on flight-recorder series the running daemons observe
    # (scheduler_solve_iterations / scheduler_sinkhorn_residual were
    # fed by the sinkhorn runs above). NaN-guarded like phase_p50_s so
    # the BENCH json stays strictly valid.
    from kubernetes_tpu.utils import flightrecorder as _fr

    sk_it_p50 = _fr.SOLVE_ITERATIONS.quantile(0.5, mode="sinkhorn")
    sk_it_p99 = _fr.SOLVE_ITERATIONS.quantile(0.99, mode="sinkhorn")
    if sk_it_p50 == sk_it_p50:
        record["sinkhorn_iters_p50"] = round(sk_it_p50, 1)
    if sk_it_p99 == sk_it_p99:
        record["sinkhorn_iters_p99"] = round(sk_it_p99, 1)
    record["sinkhorn_final_residual"] = round(
        float(_fr.SINKHORN_RESIDUAL.value()), 4
    )
    record.update(parity)
    # Short witnessed churn + CRUD segments (VERDICT r3 next #3: these
    # lived only behind BENCH_MODE env vars nothing set). Kept brief;
    # the dedicated BENCH_MODE=churn|crud runs remain for full-length
    # figures.
    if os.environ.get("BENCH_SEGMENTS", "1") != "0":
        record.update(
            _churn_figure(n_nodes=n_nodes, rate=1000, ticks=3, mode="scan")
        )
        record.update(_crud_figure(n_workers=2, n_tasks=20))
        # API-plane ingestion through the bulk fast path (ISSUE 6
        # headline: one WAL group commit per batch, watch-cache reads,
        # byte-counted watch visibility) — run with the health plane
        # live so record["alerts"] carries the sampler+engine overhead
        # fraction against its <5% gate (ISSUE 20).
        record.update(_alerts_overhead_figure())
        # The headline metric's second half (VERDICT r4 #1): churn +
        # p99 pod-to-bind latency through the REAL HTTP control plane.
        record.update(
            _api_churn_figure(n_nodes=n_nodes, rate=1000, duration_s=8.0)
        )
        # Sinkhorn's winning regime (VERDICT r4 #9).
        record.update(_hotspot_figure())
        # Capacity & fragmentation plane (ISSUE 16 acceptance:
        # fragmentation_score / slice_alloc_success_rate appear in the
        # artifact).
        try:
            record.update(_capacity_figure())
        except Exception as e:
            record["capacity_error"] = str(e)  # never sink a bench run
        # Rebalance plane (ISSUE 17 acceptance: one live defrag cycle
        # with fragmentation_score_before > _after in the artifact).
        try:
            record.update(_rebalance_figure())
        except Exception as e:
            record["rebalance_error"] = str(e)  # never sink a bench run
        # Chaos soak (ISSUE 15): faults injected / violations=0 /
        # post-fault bind p99 must appear in the artifact.
        try:
            record.update(_soak_figure())
        except Exception as e:
            record["soak_error"] = str(e)  # must never sink a bench run
        # HA failover drill (ISSUE 19 acceptance: scheduler-leader
        # kill -> warm standby's first bind under the 1 s p99 gate).
        try:
            record.update(_failover_figure())
        except Exception as e:
            record["failover_error"] = str(e)  # never sink a bench run
    # Preemption counters ride the record alongside the per-phase
    # latency fields (phase_p50_s/phase_p99_s already carry the
    # "preempt" phase when it ran): solve outcomes by kind + victims
    # evicted, read from the scheduler's own process-global series.
    from kubernetes_tpu.scheduler import daemon as _sched_daemon

    record["preemption"] = {
        "victims_total": _sched_daemon._PREEMPT_VICTIMS.value(),
        "solve_outcomes": {
            outcome: _sched_daemon._PREEMPT_OUTCOMES.value(outcome=outcome)
            for (outcome,) in _sched_daemon._PREEMPT_OUTCOMES.label_values()
        },
    }
    # Static-analysis counters: per-rule ktlint findings ride the bench
    # record so dashboards can chart lint debt over time alongside the
    # perf series (same JSON pipeline).
    try:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from tools import ktlint as _ktlint

        _rep = _ktlint.lint()
        record["ktlint_findings_per_rule"] = _rep.counts()
        record["ktlint_suppressed"] = len(_rep.suppressed)
        record["ktlint_baselined"] = len(_rep.baselined)
    except Exception as e:
        record["ktlint_error"] = str(e)  # lint must never sink a bench run
    # ktsan: the interprocedural lock analysis rides next to the
    # per-rule counts — cycles/contract violations must chart at ZERO;
    # the lock/edge totals show the sanitizer's coverage growing.
    try:
        from tools.ktlint import lockgraph as _lockgraph

        _lg = _lockgraph.analyze()
        record["ktsan_findings"] = {
            "cycles": len(_lg.cycles),
            "locked_contract": len(_lg.violations),
            "suppressed": _lg.suppressed,
            "locks": len(_lg.locks),
            "edges": len(_lg.edges),
        }
    except Exception as e:
        record["ktsan_error"] = str(e)
    # ktshape: the kernel contract checker's verdict rides beside the
    # ktlint/ktsan counts — findings must chart at ZERO; the shardable
    # list is the live go/no-go set for the pod-axis Mesh work
    # (ROADMAP #2), so a kernel silently falling OFF it is visible.
    try:
        from tools.ktlint import ktshape as _ktshape

        _ks = _ktshape.analyze()
        record["ktshape_contracts"] = {
            "kernels_checked": len(_ks.kernels),
            "shardable": _ks.shardable,
            "findings": len(_ks.findings),
            "errors": len(_ks.errors),
        }
    except Exception as e:
        record["ktshape_error"] = str(e)
    # ktmesh: the static SPMD budget verdict — budget findings must
    # chart at ZERO, and the collective totals show the communication
    # the declared shardings cost (drift in either is a sharding
    # regression or a stale CommBudget pin).
    try:
        from tools.ktlint import ktmesh as _ktmesh

        _km = _ktmesh.analyze()
        record["ktmesh_budgets"] = {
            "kernels_checked": len(_km.kernels),
            "collectives_total": _km.collectives_total,
            "collective_bytes_total": _km.collective_bytes_total,
            "skipped": sum(
                1 for k in _km.kernels if k["status"] == "skipped"
            ),
            "budget_findings": len(_km.findings),
            "errors": len(_km.errors),
        }
    except Exception as e:
        record["ktmesh_error"] = str(e)
    # Compile/cost ledger summary (ISSUE 13): total compile wall +
    # top-3 kernels by FLOPs/bytes from the always-on traced-jit
    # ledger the run's solves populated, next to the ktlint/ktsan
    # counts. wait_pending lets the background Compiled.cost_analysis
    # harvest land before the read.
    try:
        from kubernetes_tpu.ops import ledger as _ledger

        _ledger.DEFAULT.wait_pending(60)
        record["profiler"] = _ledger.DEFAULT.summary()
    except Exception as e:
        record["profiler_error"] = str(e)  # must never sink a bench run
    print(json.dumps(record))
    print(
        f"# fast wall best {best_fast:.3f}s ({fast_mode}, gate "
        f"{'pass' if gate_ok else 'FAIL'}), scan wall best {best:.3f}s for "
        f"{n_pods} pods x {n_nodes} nodes ({placed} placed); "
        f"fast={['%.3f' % t for t in fast_times]}; "
        f"scan={['%.3f' % t for t in times]}; "
        f"serial phases={phases}; parity={parity}",
        file=sys.stderr,
    )


if __name__ == "__main__":
    from kubernetes_tpu import native as _native
    from kubernetes_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    if not _native.ensure_built(quiet=False):
        sys.exit("bench: native/ failed to build (make -C native lib)")
    mode = os.environ.get("BENCH_MODE", "backlog")
    if mode == "churn":
        churn_main()
    elif mode == "crud":
        crud_main()
    elif mode == "apichurn":
        apichurn_main()
    else:
        main()
