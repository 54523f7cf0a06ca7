#!/usr/bin/env python3
"""The pallas scan against the XLA scan at a service cluster's real
shape, on one TPU chip.

    python tools/pallas_parity.py [--seed N] [--rehearse]

The shape is kbench's ``svc-5k``: 5,000 nodes in 8,192 slots, 150,000
bound pods, 30 a node, in 10,000 Services (``kbench/gen.py`` draws them
from the seed). One tick of 2,048 pending pods of the mix runs through
the session's kernel entry (``solve_banded``, the (S_pad, N) int16
carry) and through the XLA scan (``_solve_with_state_xla``, the (N, S)
f32 carry). Assignments and every final carry must be bit-identical;
then a one-pod tick and the 2,048-pod tick are timed on each path
(host clock around calls blocked until the device is done, best of 5).

The last line of standard output is one JSON object; a mismatch exits
1. ``--rehearse`` runs a tiny shape on the CPU, the kernel interpreted.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: (nodes, node slots, prefill pods, pending pods)
FULL = (5_000, 8_192, 150_000, 2_048)
REHEARSAL = (40, 128, 600, 256)


def cluster(seed: int, rehearse: bool):
    """Host columns of the session's schema, and one tick's pods."""
    import numpy as np

    from kbench import gen
    from kubernetes_tpu.ops.matrices import SVC_K

    with open(os.path.join(REPO, "kbench", "configs", "svc-5k.json")) as f:
        cfg = json.load(f)
    n_nodes, N, prefill, P = REHEARSAL if rehearse else FULL
    cfg.update(nodes=n_nodes, prefill_pods=prefill)
    S = gen.services(cfg)
    node_list = gen.nodes(cfg, seed)
    index = {n.name: j for j, n in enumerate(node_list)}
    W = 4  # label, port and volume words, as the daemon's session sizes them
    nodes = {
        "cpu_cap": np.zeros(N, np.float32),
        "mem_cap": np.zeros(N, np.float32),
        "pods_cap": np.zeros(N, np.float32),
        "cpu_fit": np.zeros(N, np.float32),
        "mem_fit": np.zeros(N, np.float32),
        "over": np.zeros(N, bool),
        "cpu_used": np.zeros(N, np.float32),
        "mem_used": np.zeros(N, np.float32),
        "pods_used": np.zeros(N, np.float32),
        "labels": np.zeros((N, W), np.uint32),
        "uport": np.zeros((N, W), np.uint32),
        "uvol_any": np.zeros((N, W), np.uint32),
        "uvol_rw": np.zeros((N, W), np.uint32),
        "svc_counts": np.zeros((N, S), np.float32),
        "sched": np.zeros(N, bool),
    }
    for j, n in enumerate(node_list):
        nodes["cpu_cap"][j] = n.cpu_m
        nodes["mem_cap"][j] = n.mem_mib
        nodes["pods_cap"][j] = n.pods
        nodes["sched"][j] = True
    for p, name in gen.prefill(cfg, seed, node_list):
        j = index[name]
        for key, v in (("cpu", p.cpu_m), ("mem", p.mem_mib)):
            nodes[f"{key}_fit"][j] += v
            nodes[f"{key}_used"][j] += v
        nodes["pods_used"][j] += 1
        nodes["svc_counts"][j, p.app] += 1
    mix = gen.pods(cfg, seed, "churn", "c", P)
    pods = {
        "cpu": np.array([p.cpu_m for p in mix], np.float32),
        "mem": np.array([p.mem_mib for p in mix], np.float32),
        "zero_req": np.zeros(P, bool),
        "sel": np.zeros((P, W), np.uint32),
        "port": np.zeros((P, W), np.uint32),
        "vol_any": np.zeros((P, W), np.uint32),
        "vol_rw": np.zeros((P, W), np.uint32),
        "pinned": np.full(P, -1, np.int32),
        "svc": np.array([p.app for p in mix], np.int32),
        "svc_ids": np.full((P, SVC_K), -1, np.int32),
    }
    pods["svc_ids"][:, 0] = pods["svc"]
    return nodes, pods, S


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=2026)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubernetes_tpu.models.algspec import DEFAULT_LOWERED
    from kubernetes_tpu.ops import pallas_scan
    from kubernetes_tpu.ops.solver import DEFAULT_WEIGHTS, _solve_with_state_xla

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        print(f"found {device.platform!r}, not a TPU", file=sys.stderr)
        return 1
    nodes, pods, S = cluster(args.seed, args.rehearse)
    interpret = args.rehearse

    banded = {k: jnp.asarray(v) for k, v in nodes.items()}
    banded["svc_counts"] = jnp.asarray(pallas_scan.to_bands(nodes["svc_counts"]))
    plain = {k: jnp.asarray(v) for k, v in nodes.items()}

    def kernel(dpods, carry):
        return pallas_scan.solve_banded(dpods, carry, DEFAULT_WEIGHTS, interpret)

    def xla(dpods, carry):
        return _solve_with_state_xla(dpods, carry, DEFAULT_WEIGHTS, DEFAULT_LOWERED)

    def fresh(carry):
        # Both paths consume their count carry: each call gets a copy,
        # made on the device before any clock starts.
        out = {k: jnp.copy(v) for k, v in carry.items()}
        jax.block_until_ready(out)
        return out

    def timed(fn, base, dpods):
        jax.block_until_ready(fn(dpods, fresh(base)))  # compile, warm
        best = float("inf")
        for _ in range(5):
            carry = fresh(base)
            t0 = time.perf_counter()
            jax.block_until_ready(fn(dpods, carry))
            best = min(best, time.perf_counter() - t0)
        return best

    dpods = {k: jnp.asarray(v) for k, v in pods.items()}
    got, got_state = kernel(dpods, fresh(banded))
    ref, ref_state = xla(dpods, fresh(plain))
    got, ref = np.asarray(got), np.asarray(ref)
    mismatched = {
        k: int(np.sum(np.asarray(ref_state[k]) != np.asarray(
            pallas_scan.from_bands(got_state[k], S) if k == "svc_counts" else got_state[k]
        )))
        for k in ref_state
    }
    # One pod, in the smallest bucket, as a churn tick brings it.
    one = {k: v.copy() for k, v in pods.items()}
    for k, v in one.items():
        v[1:] = -2 if k == "pinned" else (-1 if k in ("svc", "svc_ids") else 0)
    one = {k: jnp.asarray(v[:128]) for k, v in one.items()}
    res = {
        "device": device.device_kind,
        "platform": device.platform,
        "nodes_slots": int(nodes["cpu_cap"].shape[0]),
        "services": S,
        "pods": int(got.shape[0]),
        "placed": int((got >= 0).sum()),
        "decisions_differ": int((got != ref).sum()),
        "carry_entries_differ": mismatched,
        "tick_one_pod_s": {
            "pallas": timed(kernel, banded, one), "xla_scan": timed(xla, plain, one),
        },
        "tick_2048_s": {
            "pallas": timed(kernel, banded, dpods), "xla_scan": timed(xla, plain, dpods),
        },
    }
    ok = res["decisions_differ"] == 0 and not any(mismatched.values())
    res["identical"] = ok
    print(json.dumps(res), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
