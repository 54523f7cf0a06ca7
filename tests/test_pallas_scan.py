"""Pallas scan kernel: bit-parity with the XLA scan + dispatch rules.

The kernel (ops/pallas_scan.py) must make EXACTLY the decisions the XLA
lax.scan makes — it carries the sequential-parity referee's wall on
TPU. On this CPU test platform the kernel runs in pallas interpret
mode; the real-TPU lowering is exercised by bench.py and was verified
bit-identical at the full 50k x 5k shape."""

import numpy as np
import pytest

from kubernetes_tpu.models.columnar import build_snapshot
from kubernetes_tpu.ops import device_snapshot
from kubernetes_tpu.ops.pallas_scan import (
    pallas_eligible,
    solve_with_state_pallas,
)
from kubernetes_tpu.ops.solver import DEFAULT_WEIGHTS, _solve_with_state_xla
from kubernetes_tpu.models.algspec import DEFAULT_LOWERED

from tests.test_solver_parity import random_cluster


def _both(pending, nodes, assigned=(), services=()):
    snap = build_snapshot(pending, nodes, assigned_pods=assigned, services=services)
    d = device_snapshot(snap)
    # XLA path donates nodes: give it its own copies.
    import jax

    nodes_copy = {k: jax.numpy.array(v) for k, v in d.nodes.items()}
    ref, ref_state = _solve_with_state_xla(
        d.pods, nodes_copy, DEFAULT_WEIGHTS, DEFAULT_LOWERED
    )
    got, got_state = solve_with_state_pallas(
        d.pods, d.nodes, DEFAULT_WEIGHTS, interpret=True
    )
    return np.asarray(ref), ref_state, np.asarray(got), got_state


class TestBitParity:
    @pytest.mark.parametrize("seed", range(8))
    def test_random_cluster_decisions_identical(self, seed):
        pods, nodes, assigned, services = random_cluster(seed)
        ref, _, got, _ = _both(pods, nodes, assigned, services)
        assert (ref == got).all(), (
            f"seed {seed}: {int((ref != got).sum())}/{len(ref)} decisions differ"
        )

    def test_final_state_matches_for_chunk_chaining(self):
        """The pipeline chains the carry across chunks: the kernel's
        post-commit state must equal the XLA scan's, field by field."""
        pods, nodes, assigned, services = random_cluster(3)
        _, ref_state, _, got_state = _both(pods, nodes, assigned, services)
        for key in (
            "cpu_fit", "mem_fit", "cpu_used", "mem_used", "pods_used",
            "uport", "uvol_any", "uvol_rw", "svc_counts",
        ):
            assert np.array_equal(
                np.asarray(ref_state[key]), np.asarray(got_state[key])
            ), key

    def test_chunked_equals_monolithic(self):
        """Two pallas calls chained through the carry == one call (the
        exact contract solve_backlog_pipelined relies on)."""
        pods, nodes, assigned, services = random_cluster(5)
        snap = build_snapshot(
            pods, nodes, assigned_pods=assigned, services=services
        )
        d = device_snapshot(snap)
        whole, _ = solve_with_state_pallas(d.pods, d.nodes, interpret=True)
        P = snap.pods.count
        if P < 2:
            pytest.skip("need >=2 pods to chunk")
        cut = P // 2
        import jax.numpy as jnp

        def slice_pods(lo, hi):
            out = {}
            for k, v in d.pods.items():
                sl = v[lo:hi]
                # re-bucket to the 128 floor the kernel expects
                pad = 128 - sl.shape[0] % 128 if sl.shape[0] % 128 else 0
                if pad:
                    fill = -2 if k == "pinned" else (-1 if k in ("svc", "svc_ids") else 0)
                    width = [(0, pad)] + [(0, 0)] * (sl.ndim - 1)
                    sl = jnp.pad(sl, width, constant_values=fill)
                out[k] = sl
            return out

        a1, state = solve_with_state_pallas(
            slice_pods(0, cut), d.nodes, interpret=True
        )
        a2, _ = solve_with_state_pallas(
            slice_pods(cut, P), state, interpret=True
        )
        chained = np.concatenate([np.asarray(a1)[:cut], np.asarray(a2)[: P - cut]])
        assert (np.asarray(whole)[:P] == chained).all()


class TestDispatch:
    def test_not_eligible_on_cpu_platform(self):
        pods, nodes, assigned, services = random_cluster(0)
        snap = build_snapshot(
            pods, nodes, assigned_pods=assigned, services=services
        )
        d = device_snapshot(snap)
        # conftest forces the CPU platform: the real kernel must not
        # engage; solver.solve falls back to the XLA scan.
        assert not pallas_eligible(d.pods, d.nodes, DEFAULT_LOWERED)

    def test_host_arrays_not_eligible(self):
        # NumPy columns carry no device placement: ineligible by the
        # explicit type check, not by a swallowed exception.
        pods, nodes, assigned, services = random_cluster(0)
        snap = build_snapshot(
            pods, nodes, assigned_pods=assigned, services=services
        )
        d = device_snapshot(snap)
        host = {k: np.asarray(v) for k, v in d.nodes.items()}
        assert not pallas_eligible(d.pods, host, DEFAULT_LOWERED)

    def test_env_kill_switch(self, monkeypatch):
        monkeypatch.setenv("KTPU_PALLAS", "off")
        pods, nodes, assigned, services = random_cluster(0)
        snap = build_snapshot(
            pods, nodes, assigned_pods=assigned, services=services
        )
        d = device_snapshot(snap)
        assert not pallas_eligible(d.pods, d.nodes, DEFAULT_LOWERED)


class TestServiceAxisPadding:
    """Regression (round-4 review): SolverSession carries UNPADDED
    service axes — S=1 with no services (the churn bench shape), or any
    S not a multiple of 8 — and the kernel's 8-row banded access must
    pad rather than crash (S<8) or clamp into a neighbor service's
    counts (S%8 != 0)."""

    @pytest.mark.parametrize("n_services", [0, 1, 3, 12])
    def test_odd_service_axis_matches_xla(self, n_services):
        from kubernetes_tpu.models.objects import (
            ObjectMeta,
            Service,
            ServiceSpec,
        )
        from tests.test_solver_parity import mk_node, mk_pod

        services = [
            Service(
                metadata=ObjectMeta(name=f"s{i}", namespace="default"),
                spec=ServiceSpec(selector={"app": f"a{i}"}),
            )
            for i in range(n_services)
        ]
        nodes = [mk_node(f"n{j}") for j in range(5)]
        pods = [
            mk_pod(
                f"p{i}", cpu=100, mem_mib=64,
                labels={"app": f"a{i % max(1, n_services)}"},
            )
            for i in range(20)
        ]
        ref, _, got, got_state = _both(pods, nodes, services=services)
        assert (ref == got).all()
        # The returned carry keeps the caller's (N, S) schema exactly.
        snap = build_snapshot(pods, nodes, services=services)
        d = device_snapshot(snap)
        assert (
            np.asarray(got_state["svc_counts"]).shape
            == np.asarray(d.nodes["svc_counts"]).shape
        )

    def test_vmem_guard_rejects_oversized_shapes(self):
        from kubernetes_tpu.ops.pallas_scan import (
            VMEM_BUDGET_BYTES,
            _vmem_bytes,
        )

        # The review's counter-example: ~3072 nodes x ~1536 services
        # needs >16MB for the counts carry alone — must be rejected.
        assert _vmem_bytes(3072, 1536, 2, 2, 2) > VMEM_BUDGET_BYTES
        # The bench's 50k x 5k shape (N=5120, S=512) must be admitted.
        assert _vmem_bytes(5120, 512, 2, 2, 2) <= VMEM_BUDGET_BYTES


def test_multiword_bitsets_match_xla():
    """Port vocabularies past 64 entries need 3+ u32 words — the
    kernel's static per-word loops must agree with the XLA scan across
    the word boundary (each pod claims a distinct hostPort; a second
    same-port pod must avoid the first's node)."""
    from tests.test_solver_parity import mk_node, mk_pod

    nodes = [mk_node(f"n{j}", pods=200) for j in range(4)]
    pods = []
    for i in range(70):  # 70 distinct ports -> 3 words, bucketed to 4
        pods.append(mk_pod(f"p{i}", cpu=10, mem_mib=8, host_port=7000 + i))
    for i in range(8):  # conflicts: same ports again
        pods.append(mk_pod(f"q{i}", cpu=10, mem_mib=8, host_port=7000 + i))
    ref, _, got, _ = _both(pods, nodes)
    assert (ref == got).all()
