"""The load generator: a process of its own that never imports JAX or
the program (standard library and ``kbench.gen`` only).

Run as ``python -m kbench.loadgen``. It reads one JSON line on stdin
(the cell), prints JSON lines on stdout as it goes:

- ``{"event": "ready"}`` once the cluster's nodes, services and prefill
  are written; it then waits for a ``go`` line on stdin;
- ``{"event": "open"}`` and ``{"event": "close"}`` at the measured
  window's edges, so that the process holding the chip can read its
  counters and its trace over the same window;
- ``{"event": "done", ...}`` last, with every request's due, sent and
  done times, every watch event in the order the store committed it,
  and the pods' final bindings as the store lists them.

Open-loop traffic (``rate_per_s`` > 0) times each pod from when it was
due, not from when it was sent: a sender that falls behind makes the
pods it owes late, and its lateness is reported, never re-anchored
away. Closed-loop traffic (``burst``) submits a job array in bulk and
the next one once every pod of the previous one is bound.
"""

from __future__ import annotations

import json
import socket
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from kbench import gen

PODS = "/api/v1/namespaces/default/pods"


class LeanHTTP:
    """Minimal keep-alive HTTP/1.1 client (copied from bench.py's
    ``_LeanHTTP``): the standard library's client costs ~120 us a call
    in parsing, which would make the generator the bottleneck."""

    def __init__(self, address: str):
        host, port = address.split("//")[1].split(":")
        self.addr = (host, int(port))
        self.sock: Optional[socket.socket] = None
        self.buf = b""

    def _connect(self) -> None:
        self.sock = socket.create_connection(self.addr)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = b""

    def request(self, verb: str, path: str, body: Optional[dict] = None) -> Tuple[int, bytes]:
        data = json.dumps(body).encode() if body is not None else b""
        head = (
            f"{verb} {path} HTTP/1.1\r\nHost: b\r\n"
            f"Content-Length: {len(data)}\r\n"
            + ("Content-Type: application/json\r\n" if data else "")
            + "\r\n"
        ).encode()
        if self.sock is None:
            self._connect()
        self.sock.sendall(head + data)
        return self._read_response()

    def _read_response(self) -> Tuple[int, bytes]:
        while b"\r\n\r\n" not in self.buf:
            self._fill()
        head, self.buf = self.buf.split(b"\r\n\r\n", 1)
        lines = head.split(b"\r\n")
        status = int(lines[0].split(b" ", 2)[1])
        clen = 0
        for ln in lines[1:]:
            if ln[:15].lower() == b"content-length:":
                clen = int(ln[15:])
        while len(self.buf) < clen:
            self._fill()
        body, self.buf = self.buf[:clen], self.buf[clen:]
        return status, body

    def _fill(self) -> None:
        chunk = self.sock.recv(1 << 20)
        if not chunk:
            raise OSError("connection closed")
        self.buf += chunk

    def close(self) -> None:
        if self.sock is not None:
            self.sock.close()
            self.sock = None


class Watch:
    """Pods bound in the default namespace, as a client sees them: a
    chunked watch with ``spec.nodeName!=``. Records every event in
    stream order (the store's commit order) with its receive time."""

    def __init__(self, address: str, since: int):
        host, port = address.split("//")[1].split(":")
        self.sock = socket.create_connection((host, int(port)))
        self.sock.sendall(
            (
                f"GET /api/v1/watch/namespaces/default/pods?resourceVersion={since}&maxsize=65536"
                "&fieldSelector=spec.nodeName%21%3D HTTP/1.1\r\nHost: b\r\n\r\n"
            ).encode()
        )
        self.lock = threading.Lock()
        self.cond = threading.Condition(self.lock)
        #: (seq, "B"|"D", name, node, t_recv)
        self.events: List[Tuple[int, str, str, str, float]] = []
        self.bound: Dict[str, float] = {}
        self.deleted: Dict[str, float] = {}
        self.error: Optional[str] = None
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _run(self) -> None:
        f = self.sock.makefile("rb")
        try:
            status = f.readline()
            if b" 200 " not in status:
                raise OSError(f"watch refused: {status[:80]!r}")
            while f.readline() not in (b"\r\n", b""):
                pass  # headers
            while True:
                size = int(f.readline() or b"0", 16)
                if size == 0:
                    raise OSError("watch closed")
                chunk = f.read(size + 2)
                now = time.monotonic()
                evs = [json.loads(line) for line in chunk.splitlines() if line.strip()]
                with self.cond:
                    for ev in evs:
                        self._event(ev, now)
                    self.cond.notify_all()
        except (OSError, ValueError) as e:
            if self.error is None and self.sock is not None:
                self.error = repr(e)
        finally:
            f.close()
            with self.cond:
                self.cond.notify_all()

    def _event(self, ev: dict, now: float) -> None:
        obj = ev.get("object") or {}
        name = obj.get("metadata", {}).get("name", "")
        node = obj.get("spec", {}).get("nodeName", "")
        if ev.get("type") == "DELETED":
            self.deleted[name] = now
            self.events.append((len(self.events), "D", name, node, now))
        elif name not in self.bound:  # later updates of a bound pod pass
            self.bound[name] = now
            self.events.append((len(self.events), "B", name, node, now))

    def wait(self, names: List[str], table: Dict[str, float], deadline: float) -> bool:
        """Until every name is in ``table`` (``bound`` or ``deleted``)."""
        left = list(names)
        with self.cond:
            while True:
                left = [n for n in left if n not in table]
                if not left:
                    return True
                wait = deadline - time.monotonic()
                if wait <= 0 or self.error is not None:
                    return False
                self.cond.wait(min(wait, 0.5))

    def close(self) -> None:
        sock, self.sock = self.sock, None
        try:
            sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        sock.close()
        self.thread.join(timeout=10)


def emit(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def bulk(c: LeanHTTP, path: str, items: List[dict], chunk: int = 1000) -> int:
    """Bulk-create ``items``; returns the last resourceVersion."""
    rv = 0
    for i in range(0, len(items), chunk):
        status, body = c.request("POST", path, {"items": items[i:i + chunk]})
        if status != 200:
            raise RuntimeError(f"bulk create {path}: HTTP {status} {body[:200]!r}")
        for res in json.loads(body)["results"]:
            if res.get("status") != "Success":
                raise RuntimeError(f"bulk create {path}: {res}")
            rv = max(rv, int(res["details"]["resourceVersion"]))
    return rv


def setup_cluster(address: str, cfg: dict, seed: int) -> int:
    """Nodes, services and the prefill, written through the API's bulk
    create. Returns the resourceVersion the watch starts after."""
    c = LeanHTTP(address)
    try:
        node_list = gen.nodes(cfg, seed)
        bulk(c, "/api/v1/nodes:bulk", [gen.node_wire(n) for n in node_list])
        bulk(
            c, "/api/v1/namespaces/default/services:bulk",
            [gen.service_wire(s) for s in range(gen.services(cfg))],
        )
        items = [gen.pod_wire(p, node) for p, node in gen.prefill(cfg, seed, node_list)]
        return bulk(c, PODS + ":bulk", items)
    finally:
        c.close()


def schedule(seed: int, stream: str, rate: float, segments: List[float]) -> List[float]:
    """Open-loop arrival times: a Poisson process conditioned on its
    count, ``rate * length`` arrivals in each segment, uniform within
    it. Every seed thus offers the same number of pods to every window,
    in a different order and at different instants."""
    r = gen.rng(seed, stream)
    out: List[float] = []
    t0 = 0.0
    for length in segments:
        n = int(round(rate * length))
        out.extend(sorted(t0 + r.random() * length for _ in range(n)))
        t0 += length
    return out


class Requests:
    """Per-request records: (name, due, sent, done, status)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.rows: List[Tuple[str, float, float, float, int]] = []
        self.errors: List[str] = []

    def add(self, row) -> None:
        with self.lock:
            self.rows.append(row)


def paced(address: str, t0: float, times: List[float], names: List[str],
          make, rec: Requests, threads: int) -> List[threading.Thread]:
    """Send request i at ``t0 + times[i]`` from a pool of keep-alive
    connections. ``make(name) -> (verb, path, body)``."""
    lock = threading.Lock()
    nxt = [0]

    def worker():
        c = LeanHTTP(address)
        try:
            while True:
                with lock:
                    i = nxt[0]
                    nxt[0] += 1
                if i >= len(times):
                    return
                due = t0 + times[i]
                delay = due - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                verb, path, body = make(names[i])
                sent = time.monotonic()
                try:
                    status, _ = c.request(verb, path, body)
                except OSError as e:
                    c.close()
                    status = 0
                    rec.errors.append(f"{verb} {names[i]}: {e!r}")
                rec.add((names[i], due, sent, time.monotonic(), status))
        finally:
            c.close()

    ts = [threading.Thread(target=worker, daemon=True) for _ in range(threads)]
    for t in ts:
        t.start()
    return ts


def run_open(address: str, cfg: dict, traffic: dict, seed: int, seconds: float,
             watch: Watch) -> dict:
    rate = traffic["rate_per_s"]
    warm, tail = traffic["warmup_s"], traffic["tail_s"]
    # A quiet gap closes the warm-up: the scheduler's deferred
    # explain runs only once its loop has idled, and so compiles its
    # shape here rather than inside the window.
    quiet = traffic.get("quiet_s", 0.0)
    segs = [warm, seconds, tail]

    def times(stream: str) -> List[float]:
        return [t + quiet * (t >= warm) for t in schedule(seed, stream, rate, segs)]

    c_times, d_times = times("create-times"), times("delete-times")
    warm += quiet
    new = gen.pods(cfg, seed, "churn", "c", len(c_times))
    spec = {p.name: p for p in new}
    victims = [f"p{i}" for i in range(len(d_times))]
    if len(victims) > cfg["prefill_pods"]:
        raise RuntimeError("deletes would outrun the prefill")
    creates, deletes = Requests(), Requests()
    t0 = time.monotonic() + 0.2
    threads = paced(
        address, t0, c_times, [p.name for p in new],
        lambda n: ("POST", PODS, gen.pod_wire(spec[n])),
        creates, traffic["senders"],
    ) + paced(
        address, t0, d_times, victims,
        lambda n: ("DELETE", f"{PODS}/{n}", None),
        deletes, traffic["senders"],
    )
    open_t, close_t = t0 + warm, t0 + warm + seconds
    time.sleep(max(0.0, open_t - time.monotonic()))
    emit({"event": "open", "t": time.monotonic()})
    time.sleep(max(0.0, close_t - time.monotonic()))
    emit({"event": "close", "t": time.monotonic()})
    for t in threads:
        t.join()
    window = [p.name for p, t in zip(new, c_times) if warm <= t < warm + seconds]
    created = [r[0] for r in creates.rows if 200 <= r[4] < 300]
    watch.wait(created, watch.bound, time.monotonic() + traffic["drain_s"])
    return {
        "open": open_t, "close": close_t, "rate_end": close_t,
        "window_pods": window, "created": created,
        "creates": creates.rows, "deletes": deletes.rows,
        "errors": creates.errors + deletes.errors,
        "due": {p.name: t0 + t for p, t in zip(new, c_times)},
    }


def run_bursts(address: str, cfg: dict, traffic: dict, seed: int, seconds: float,
               watch: Watch) -> dict:
    """Closed loop of job arrays. Each cycle first bulk-deletes the
    previous array, waits until the watch shows it gone and then
    ``settle_s`` more, then submits the next one in bulk creates, then
    waits for every pod of it to be bound."""
    B, chunk = cfg["pending_pods"], traffic["create_batch"]
    c = LeanHTTP(address)
    creates, deletes = Requests(), Requests()
    due: Dict[str, float] = {}
    window: List[str] = []
    prev: List[str] = []
    open_t = close_t = rate_end = None
    k = 0
    try:
        while True:
            if prev:
                for i in range(0, len(prev), chunk):
                    names = prev[i:i + chunk]
                    sent = time.monotonic()
                    status, _ = c.request("POST", PODS + ":bulkdelete", {"names": names})
                    done = time.monotonic()
                    deletes.add((f"bulk{len(names)}", sent, sent, done, status))
                if not watch.wait(prev, watch.deleted,
                                  time.monotonic() + traffic["drain_s"]):
                    raise RuntimeError("previous job array never left the watch")
                # The scheduler's own watch must have applied the deletes
                # before the next array is solved (see reference.py).
                time.sleep(traffic["settle_s"])
            now = time.monotonic()
            if k == traffic["warmup_bursts"]:
                open_t, close_t = now, now + seconds
                emit({"event": "open", "t": now})
            in_window = open_t is not None
            if in_window and now >= close_t:
                break
            pods = gen.pods(cfg, seed, f"burst{k}", f"b{k}-", B)
            t_burst = time.monotonic()
            for i in range(0, B, chunk):
                items = [gen.pod_wire(p) for p in pods[i:i + chunk]]
                sent = time.monotonic()
                status, _ = c.request("POST", PODS + ":bulk", {"items": items})
                creates.add((f"bulk{len(items)}", sent, sent, time.monotonic(), status))
                if status != 200:
                    raise RuntimeError(f"bulk create: HTTP {status}")
            names = [p.name for p in pods]
            for n in names:
                due[n] = t_burst
            if in_window:
                window.extend(names)
            all_bound = watch.wait(names, watch.bound,
                                   time.monotonic() + traffic["drain_s"])
            if in_window:
                rate_end = max(watch.bound.get(n, time.monotonic()) for n in names)
            prev = names
            k += 1
            if not all_bound:
                break  # an array that never binds ends the loop: no more load
    finally:
        c.close()
    if open_t is None:  # ended before the window opened
        open_t = close_t = rate_end = time.monotonic()
        emit({"event": "open", "t": open_t})
    emit({"event": "close", "t": rate_end})
    return {
        "open": open_t, "close": close_t, "rate_end": rate_end,
        "window_pods": window, "created": list(due), "creates": creates.rows,
        "deletes": deletes.rows, "errors": creates.errors + deletes.errors,
        "due": due,
    }


def final_bindings(address: str) -> List[Tuple[str, str]]:
    c = LeanHTTP(address)
    try:
        status, body = c.request("GET", PODS)
    finally:
        c.close()
    if status != 200:
        raise RuntimeError(f"list pods: HTTP {status}")
    return [
        (p["metadata"]["name"], p.get("spec", {}).get("nodeName", ""))
        for p in json.loads(body)["items"]
    ]


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    address, cfg, traffic = spec["address"], spec["config"], spec["traffic"]
    seed, seconds = spec["seed"], spec["seconds"]
    since = setup_cluster(address, cfg, seed)
    watch = Watch(address, since)
    emit({"event": "ready"})
    if sys.stdin.readline().strip() != "go":
        watch.close()
        return 1
    try:
        drive = run_bursts if traffic["loop"] == "closed" else run_open
        out = drive(address, cfg, traffic, seed, seconds, watch)
    finally:
        watch.close()
    out["watch"] = watch.events
    out["watch_error"] = watch.error
    out["bindings"] = final_bindings(address)
    emit({"event": "done", **out})
    return 0


if __name__ == "__main__":
    sys.exit(main())
