"""Seeded cluster and pod generator: the yardstick's copy of
``__graft_entry__._synthetic_objects``.

The same draw as the original, with every width read from the
configuration: node cores, memory, pods and zone labels; pod cpu and
memory limits, the share with a zone selector, the share with a host
port, and one service per ``pods_per_service`` pods (each pod labelled
for exactly one service; 0 means no services and unlabelled pods).
The configurations in ``kbench/configs/`` hold one shape each, the
published templates of upstream's ``scheduler_perf``.

Everything here is plain data (wire dicts and tuples) built with the
standard library alone, so the load generator, which never imports JAX
or the program, and the reference, which imports nothing of the
program, both regenerate the same objects from the same seed.
"""

from __future__ import annotations

import random
from typing import Dict, List, NamedTuple, Optional, Tuple

class NodeSpec(NamedTuple):
    name: str
    zone: str
    cpu_m: int
    mem_mib: int
    pods: int


class PodSpec(NamedTuple):
    name: str
    app: int  # service index: label app=app<app>; -1 = none
    cpu_m: int
    mem_mib: int
    host_port: int  # 0 = none
    zone: str  # nodeSelector zone, "" = none


def rng(seed: int, stream: str) -> random.Random:
    """One independent, reproducible stream per (seed, purpose)."""
    return random.Random(f"kbench:{seed}:{stream}")


def nodes(cfg: dict, seed: int) -> List[NodeSpec]:
    r = rng(seed, "nodes")
    shape = cfg["node_shape"]
    zones = shape["zones"]
    return [
        NodeSpec(
            f"n{j}",
            r.choice(zones) if zones else "",
            r.choice(shape["cpu_cores"]) * 1000,
            r.choice(shape["memory_gib"]) * 1024,
            shape["pods"],
        )
        for j in range(cfg["nodes"])
    ]


def services(cfg: dict) -> int:
    """Number of services: one per ``pods_per_service`` pods of the
    cluster's working set (the prefill, or one job array); 0 when the
    mix has none."""
    per = cfg["pod_mix"]["pods_per_service"]
    if not per:
        return 0
    return max(1, (cfg["prefill_pods"] + cfg.get("pending_pods", 0)) // per)


def draw_pod(r: random.Random, cfg: dict, name: str, n_services: int) -> PodSpec:
    mix = cfg["pod_mix"]
    app = r.randrange(n_services) if n_services else -1
    port = (
        30000 + r.randrange(mix["host_ports"])
        if r.random() < mix["host_port_share"] else 0
    )
    return PodSpec(
        name,
        app,
        r.choice(mix["cpu_m"]),
        r.choice(mix["memory_mib"]),
        port,
        r.choice(cfg["node_shape"]["zones"])
        if r.random() < mix["selector_share"] else "",
    )


def pods(cfg: dict, seed: int, stream: str, prefix: str, n: int) -> List[PodSpec]:
    """``n`` pending pods named ``<prefix><i>`` from one seeded stream."""
    r = rng(seed, stream)
    S = services(cfg)
    return [draw_pod(r, cfg, f"{prefix}{i}", S) for i in range(n)]


def prefill(cfg: dict, seed: int, node_list: List[NodeSpec]) -> List[Tuple[PodSpec, str]]:
    """The already-bound pods a restarted scheduler finds:
    ``prefill_pods`` pods drawn from the mix, dealt in turn over the
    nodes in a seeded order (so pod ``p<i>`` sits on the ``i mod N``-th
    node of it, and the nodes' counts differ by one at most), each
    within its node's own capacity (a draw that would leave no room for
    the node's remaining pods at the smallest shape, or that reuses a
    host port, is drawn again); a selector pod selects its node's
    zone."""
    total = cfg["prefill_pods"]
    if not total:
        return []
    mix = cfg["pod_mix"]
    r = rng(seed, "prefill")
    S = services(cfg)
    N = len(node_list)
    order = list(range(N))
    r.shuffle(order)
    quota = [total // N + (rank < total % N) for rank in range(N)]
    cmin, mmin = min(mix["cpu_m"]), min(mix["memory_mib"])
    left = {j: [node_list[j].cpu_m, node_list[j].mem_mib, set(), quota[rank]]
            for rank, j in enumerate(order)}
    out: List[Tuple[PodSpec, str]] = []
    for i in range(total):
        node = node_list[order[i % N]]
        st = left[order[i % N]]
        st[3] -= 1
        for _ in range(32):
            p = draw_pod(r, cfg, f"p{i}", S)
            if (
                st[0] - p.cpu_m >= cmin * st[3]
                and st[1] - p.mem_mib >= mmin * st[3]
                and (not p.host_port or p.host_port not in st[2])
            ):
                break
        else:
            p = PodSpec(f"p{i}", p.app, cmin, mmin, 0, "")
        if p.zone:
            p = p._replace(zone=node.zone)
        st[0] -= p.cpu_m
        st[1] -= p.mem_mib
        if p.host_port:
            st[2].add(p.host_port)
        out.append((p, node.name))
    return out


# -- wire forms ------------------------------------------------------


def node_wire(n: NodeSpec) -> dict:
    return {
        "kind": "Node",
        "metadata": {"name": n.name, "labels": {"zone": n.zone} if n.zone else {}},
        "status": {
            "capacity": {
                "cpu": f"{n.cpu_m}m",
                "memory": f"{n.mem_mib}Mi",
                "pods": str(n.pods),
            },
            "conditions": [{"type": "Ready", "status": "True"}],
        },
    }


def service_wire(s: int) -> dict:
    return {
        "kind": "Service",
        "metadata": {"name": f"svc{s}", "namespace": "default"},
        "spec": {
            # Headless: the apiserver's service range (10.0.0.0/24)
            # holds 254 cluster IPs, and spreading needs only the
            # selector.
            "clusterIP": "None",
            "selector": {"app": f"app{s}"},
            "ports": [{"port": 80, "protocol": "TCP"}],
        },
    }


def pod_wire(p: PodSpec, node: Optional[str] = None) -> dict:
    container: Dict = {
        "name": "c",
        "image": "app",
        "resources": {
            "limits": {"cpu": f"{p.cpu_m}m", "memory": f"{p.mem_mib}Mi"}
        },
    }
    if p.host_port:
        container["ports"] = [{"containerPort": 80, "hostPort": p.host_port}]
    spec: Dict = {"containers": [container]}
    if p.zone:
        spec["nodeSelector"] = {"zone": p.zone}
    if node:
        spec["nodeName"] = node
    meta: Dict = {"name": p.name, "namespace": "default"}
    if p.app >= 0:
        meta["labels"] = {"app": f"app{p.app}"}
    return {"kind": "Pod", "metadata": meta, "spec": spec}
