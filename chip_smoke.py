#!/usr/bin/env python3
"""Smoke test of the scheduler's main path on one TPU chip.

One process, through the entry points a user calls:

1. device   — JAX's devices; anything but a TPU fails (``--rehearse``
               alone lets a CPU run through, at a tiny size).
2. native   — ``make -B -C native lib`` from the committed sources.
3. backlog  — 50,000 pending pods onto 5,000 nodes (the top of
               BASELINE's ladder) through ``ops.solve_backlog_pipelined``,
               compared decision for decision with the sequential NumPy
               oracle on the same snapshot: the scan is exact, so parity
               must be 1.0.
4. served   — HTTP apiserver, the scheduler exactly as ``hyperkube
               scheduler --batch`` builds it, and a watch client; 5,000
               Ready nodes and 5,000 pods created over HTTP in waves. Every
               pod must bind, no node may exceed its capacity under the
               scalar predicates, the daemon must never fall back, and
               the compile ledger must show the session ran the pallas
               scan.

Times printed on the way are readings of this run, not claims. The last
line of standard output is the one JSON object the driver reads; it is
printed only when every check passed. Any failure exits non-zero.
"""

import argparse
import json
import os
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 2026

#: (backlog pods, backlog nodes, served nodes, served pods, waves)
FULL = (50_000, 5_000, 5_000, 5_000, 5)
REHEARSAL = (2_000, 200, 200, 400, 4)

PALLAS_KERNEL = "pallas_scan._solve_packed"
XLA_SCAN_KERNEL = "solver._solve_with_state_xla"


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


def phase_device(rehearse: bool):
    import jax

    devices = jax.devices()
    d0 = devices[0]
    log(f"devices: {devices}")
    log(
        f"platform={d0.platform} kind={d0.device_kind!r} "
        f"count={len(devices)}"
    )
    if d0.platform != "tpu" and not rehearse:
        raise SmokeFailure(
            f"JAX found platform {d0.platform!r}, not a TPU: this smoke "
            "runs on the chip only (--rehearse allows a CPU rehearsal)"
        )
    return d0, len(devices)


def phase_native() -> None:
    t0 = time.perf_counter()
    subprocess.run(
        ["make", "-B", "-C", os.path.join(REPO, "native"), "lib"],
        check=True, stdout=subprocess.DEVNULL,
    )
    from kubernetes_tpu import native

    ok = native.available()
    log(
        f"native: built in {time.perf_counter() - t0:.3f}s, "
        f"available={ok}"
    )
    check(ok, "native library built but does not load")


def phase_backlog(n_pods: int, n_nodes: int) -> None:
    import numpy as np

    from __graft_entry__ import _synthetic_objects
    from kubernetes_tpu.models.columnar import build_snapshot
    from kubernetes_tpu.ops import solve_backlog_pipelined
    from kubernetes_tpu.ops.oracle import solve_sequential_numpy

    pods, nodes, services = _synthetic_objects(n_pods, n_nodes, seed=SEED)
    walls = []
    for _ in range(2):  # cold (compiles included), then warm
        t0 = time.perf_counter()
        out = solve_backlog_pipelined(pods, nodes, services=services)
        walls.append(time.perf_counter() - t0)
    snap = build_snapshot(pods, nodes, services=services)
    t0 = time.perf_counter()
    seq = solve_sequential_numpy(snap)
    oracle_s = time.perf_counter() - t0
    index = {name: j for j, name in enumerate(snap.nodes.names)}
    dev = np.array([index[n] if n is not None else -1 for n in out])
    parity = float((dev == seq).mean())
    placed = int((dev >= 0).sum())
    log(
        f"backlog {n_pods}x{n_nodes}: wall cold {walls[0]:.3f}s, warm "
        f"{walls[1]:.3f}s; placed {placed}; parity vs sequential NumPy "
        f"oracle {parity} (oracle {oracle_s:.1f}s on the host)"
    )
    check(parity == 1.0, f"backlog parity {parity} != 1.0")
    check(placed > 0, "backlog placed nothing")


def _ledger_calls() -> dict:
    from kubernetes_tpu.ops import ledger

    return {r["kernel"]: r["calls"] for r in ledger.DEFAULT.rows()}


def _phase_totals() -> dict:
    """phase -> (observations, seconds) of the scheduler's in-situ
    phase spans (utils/tracing.PHASE_SECONDS)."""
    from kubernetes_tpu.utils.tracing import PHASE_SECONDS

    return {k[0]: v[:2] for k, v in PHASE_SECONDS.snapshot().items()}


def _check_capacity(client) -> int:
    """Every binding against the scalar predicates: per node, the bound
    pods fit its cpu/memory (greedy fit), its pod count, its selector
    and its host ports. Returns the number of bound pods checked."""
    from kubernetes_tpu.scheduler import predicates

    nodes = {n.metadata.name: n for n in client.list("nodes")[0]}
    pods, _ = client.list("pods", namespace="default")
    by_node: dict = {}
    for p in pods:
        check(bool(p.spec.node_name), f"pod {p.metadata.name} unbound")
        check(p.spec.node_name in nodes, f"pod bound to unknown node")
        by_node.setdefault(p.spec.node_name, []).append(p)
    for name, bound in by_node.items():
        node = nodes[name]
        cpu, mem, cap_pods = predicates._capacity(node)
        _, over = predicates.check_pods_exceeding_capacity(bound, (cpu, mem))
        check(not over, f"node {name} over cpu/memory capacity")
        check(len(bound) <= cap_pods, f"node {name} over pod capacity")
        for i, p in enumerate(bound):
            check(
                predicates.pod_matches_node_labels(p, node),
                f"pod {p.metadata.name} violates its node selector",
            )
            check(
                predicates.pod_fits_ports(p, bound[:i], name),
                f"pod {p.metadata.name} host-port conflict on {name}",
            )
    return len(pods)


def phase_served(n_nodes: int, n_pods: int, waves: int, tpu: bool) -> None:
    from __graft_entry__ import _synthetic_objects
    from kubernetes_tpu.client import Client, HTTPTransport
    from kubernetes_tpu.cmd.daemons import scheduler_parser, start_scheduler
    from kubernetes_tpu.models import serde
    from kubernetes_tpu.server.api import APIServer
    from kubernetes_tpu.server.httpserver import APIHTTPServer

    pods, nodes, _services = _synthetic_objects(n_pods, n_nodes, seed=SEED + 1)
    srv = APIHTTPServer(APIServer()).start()
    client = Client(HTTPTransport(srv.address))
    sched = stream = None
    stop = threading.Event()
    t_bound: dict = {}
    try:
        t0 = time.perf_counter()
        node_wire = [serde.to_wire(n) for n in nodes]
        for i in range(0, len(node_wire), 1000):
            res = client.create_bulk("nodes", node_wire[i : i + 1000])
            bad = [r for r in res if r.get("status") != "Success"]
            check(not bad, f"node registration failed: {bad[:1]}")
        log(f"served: {n_nodes} nodes registered over HTTP in "
            f"{time.perf_counter() - t0:.2f}s")

        # The daemon exactly as `hyperkube scheduler --batch` builds it:
        # the incremental session, default policy, --batch-mode scan,
        # default pre-warm.
        t0 = time.perf_counter()
        sched = start_scheduler(
            scheduler_parser().parse_args(["--server", srv.address, "--batch"])
        )
        # Its idle loop builds the session and pre-warms it; traffic
        # waits for that, as a deployment's would for a ready scheduler.
        deadline = time.monotonic() + 600
        while sched._session is None:
            check(time.monotonic() < deadline, "session never built")
            time.sleep(0.05)
        log(f"served: scheduler up, session built and pre-warmed in "
            f"{time.perf_counter() - t0:.2f}s "
            f"(node slots {sched._session.N_cap})")

        _, version = client.list("pods", namespace="default")
        stream = client.watch(
            "pods", namespace="default", since=version,
            field_selector="spec.nodeName!=",
        )

        def watcher():
            while not stop.is_set():
                ev = stream.next(timeout=0.2)
                if ev is None:
                    if stream.closed:
                        return
                    continue
                obj = ev.object
                if isinstance(obj, dict) and obj.get("spec", {}).get("nodeName"):
                    t_bound.setdefault(obj["metadata"]["name"], time.perf_counter())

        watch_thread = threading.Thread(target=watcher, daemon=True)
        watch_thread.start()
        calls_before = _ledger_calls()
        phases_before = _phase_totals()

        t_create: dict = {}
        per_wave = -(-n_pods // waves)
        for w in range(waves):
            batch = pods[w * per_wave : (w + 1) * per_wave]
            t0 = time.perf_counter()
            res = client.create_bulk(
                "pods", [serde.to_wire(p) for p in batch], namespace="default"
            )
            bad = [r for r in res if r.get("status") != "Success"]
            check(not bad, f"pod create failed: {bad[:1]}")
            for p in batch:
                t_create[p.metadata.name] = t0
            time.sleep(0.5)  # separate the waves: several micro-ticks

        deadline = time.monotonic() + 600
        while True:
            listed, _ = client.list("pods", namespace="default")
            bound = sum(1 for p in listed if p.spec.node_name)
            if bound == n_pods:
                break
            check(
                time.monotonic() < deadline,
                f"only {bound}/{n_pods} pods bound in time",
            )
            time.sleep(0.5)
        deadline = time.monotonic() + 30
        while len(t_bound) < n_pods and time.monotonic() < deadline:
            time.sleep(0.1)
        check(len(t_bound) == n_pods,
              f"watch saw {len(t_bound)}/{n_pods} bindings")

        checked = _check_capacity(client)
        calls_after = _ledger_calls()
        phases = {
            ph: (n - phases_before.get(ph, (0, 0.0))[0],
                 sec - phases_before.get(ph, (0, 0.0))[1])
            for ph, (n, sec) in _phase_totals().items()
        }
        log("served: scheduler phases during the drill (spans, seconds): "
            + ", ".join(
                f"{ph} {n} {sec:.3f}s"
                for ph, (n, sec) in sorted(phases.items()) if n
            ))
        delta = {
            k: calls_after.get(k, 0) - calls_before.get(k, 0)
            for k in (PALLAS_KERNEL, XLA_SCAN_KERNEL)
        }
        lats = sorted(t_bound[n] - t_create[n] for n in t_create)
        span = max(t_bound.values()) - min(t_create.values())
        log(
            f"served: {n_pods} pods bound over HTTP at {n_nodes} nodes, "
            f"{checked} checked against the scalar predicates; "
            f"{n_pods / span:.1f} pods/s; create-to-bind p50 "
            f"{lats[len(lats) // 2]:.4f}s p99 "
            f"{lats[min(len(lats) - 1, int(0.99 * len(lats)))]:.4f}s; "
            f"fallback_count={sched.fallback_count}; "
            f"solve calls during the drill {delta}"
        )
        check(sched.fallback_count == 0,
              f"scheduler fell back {sched.fallback_count} time(s)")
        # On the chip the session must run the pallas kernel, never the
        # XLA scan; a CPU rehearsal runs the XLA scan.
        ran, idle = (
            (PALLAS_KERNEL, XLA_SCAN_KERNEL) if tpu
            else (XLA_SCAN_KERNEL, PALLAS_KERNEL)
        )
        check(delta[ran] >= 2, f"{ran} ran {delta[ran]} session ticks, want >= 2")
        check(delta[idle] == 0, f"{idle} ran during the drill")
    finally:
        stop.set()
        if stream is not None:
            stream.close()
        if sched is not None:
            sched.stop()
        srv.stop()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument(
        "--rehearse", action="store_true",
        help="tiny sizes, and a non-TPU platform is allowed (CPU rehearsal)",
    )
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "kubernetes_tpu")):
        print("chip_smoke: kubernetes_tpu/ is not next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)
    from kubernetes_tpu.utils.compile_cache import place_compile_cache

    cache = place_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"compile cache: {cache} ({entries} entries at start)")
    t_start = time.perf_counter()
    try:
        device, count = phase_device(args.rehearse)
        phase_native()
        bp, bn, sn, sp, waves = REHEARSAL if args.rehearse else FULL
        phase_backlog(bp, bn)
        phase_served(sn, sp, waves, tpu=device.platform == "tpu")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    from kubernetes_tpu.ops import ledger

    summary = ledger.DEFAULT.summary()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(
        f"done in {time.perf_counter() - t_start:.1f}s; compile ledger: "
        f"{summary['compiles']} compiles, "
        f"{summary['compile_seconds_total']:.2f}s; compile cache "
        f"{entries} entries at end"
    )
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": count,
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
