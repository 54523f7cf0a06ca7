"""Pallas scan kernel: bit-parity with the XLA scan + dispatch rules.

The kernel (ops/pallas_scan.py) must make EXACTLY the decisions the XLA
lax.scan makes — it carries the sequential-parity referee's wall on
TPU. On this CPU test platform the kernel runs in pallas interpret
mode; the real-TPU lowering compiles in tests/test_tpu_compile.py and
is checked bit-identical on the chip by tools/pallas_parity.py."""

import functools
import random

import numpy as np
import pytest

from kubernetes_tpu.models.columnar import build_snapshot
from kubernetes_tpu.models.objects import ObjectMeta, Service, ServiceSpec
from kubernetes_tpu.ops import SolverSession, device_snapshot, pallas_scan
from kubernetes_tpu.ops.oracle import solve_sequential_numpy
from kubernetes_tpu.ops.pallas_scan import pallas_reason, solve_with_state_pallas
from kubernetes_tpu.ops.solver import DEFAULT_WEIGHTS, _solve_with_state_xla
from kubernetes_tpu.models.algspec import DEFAULT_LOWERED

from tests.test_solver_parity import mk_node, mk_pod, random_cluster

CARRY_KEYS = (
    "cpu_fit", "mem_fit", "cpu_used", "mem_used", "pods_used",
    "uport", "uvol_any", "uvol_rw", "svc_counts",
)


def banded_cluster(
    seed, n_services, n_nodes, n_pods, run=1, no_service=0.0, multi=0.0
):
    """(pending, nodes, assigned, services) over many Services, so the
    count carry spans many 8-row bands: ``app`` Services a<i> (one per
    pod, drawn at random in runs of ``run`` consecutive pods), a
    ``no_service`` share of pods labelled for none, and a ``multi``
    share also labelled for one of 8 ``tier`` Services, so they match
    two (K > 1). Two pods a node are already bound, labelled alike."""
    rng = random.Random(seed)
    services = [
        Service(
            metadata=ObjectMeta(name=f"s{i}", namespace="default"),
            spec=ServiceSpec(selector={"app": f"a{i}"}),
        )
        for i in range(n_services)
    ] + [
        Service(
            metadata=ObjectMeta(name=f"t{i}", namespace="default"),
            spec=ServiceSpec(selector={"tier": f"t{i}"}),
        )
        for i in range(8 if multi else 0)
    ]
    nodes = [
        mk_node(f"n{j}", cpu=rng.choice([2000, 4000]), mem_mib=8192, pods=20)
        for j in range(n_nodes)
    ]

    def labels():
        if rng.random() < no_service:
            return {}
        out = {"app": f"a{rng.randrange(n_services)}"}
        if rng.random() < multi:
            out["tier"] = f"t{rng.randrange(8)}"
        return out

    assigned = []
    for j, node in enumerate(nodes):
        for k in range(2):
            a = mk_pod(f"a{j}-{k}", cpu=100, mem_mib=64, labels=labels())
            a.spec.node_name = node.metadata.name
            assigned.append(a)
    pods = []
    while len(pods) < n_pods:
        lab = labels()
        for _ in range(run):
            pods.append(
                mk_pod(
                    f"p{len(pods)}", cpu=rng.choice([50, 100, 250]),
                    mem_mib=rng.choice([64, 128]), labels=dict(lab),
                )
            )
    return pods[:n_pods], nodes, assigned, services


#: Clusters whose count carry spans many bands, each named for the
#: band path it drives.
BANDED_CASES = {
    # 75 bands over 256 nodes: most pods fetch a band no neighbour
    # touched (the prefetch path).
    "600-services": dict(n_services=600, n_nodes=256, n_pods=300, no_service=0.1),
    # Consecutive pods of one Service: the band is reused and must carry
    # each commit to the next pod (the band hazard).
    "runs-of-one-service": dict(n_services=40, n_nodes=64, n_pods=200, run=6),
    # No pending pod has a Service: nothing is fetched.
    "no-service": dict(n_services=24, n_nodes=32, n_pods=120, no_service=1.0),
    # Half the pods match two Services (K > 1): the 8 tier Services
    # share bands with the 12 app ones, so a pod commits to its own
    # band, to the next pod's (which must then not be read stale) or
    # to another.
    "two-services": dict(
        n_services=12, n_nodes=64, n_pods=200, run=2, multi=0.5
    ),
}


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

    @pytest.mark.parametrize("case", sorted(BANDED_CASES))
    def test_service_bands_match_xla_and_oracle(self, case):
        """Many Services: decisions equal the XLA scan's and the scalar
        oracle's, and the final carries equal the XLA scan's."""
        pods, nodes, assigned, services = banded_cluster(7, **BANDED_CASES[case])
        snap = build_snapshot(
            pods, nodes, assigned_pods=assigned, services=services
        )
        ref, ref_state, got, got_state = _both(pods, nodes, assigned, services)
        P = snap.pods.count
        oracle = solve_sequential_numpy(snap)
        assert (got == ref).all(), int((got != ref).sum())
        assert (got[:P] == oracle).all(), int((got[:P] != oracle).sum())
        for key in CARRY_KEYS:
            assert np.array_equal(
                np.asarray(ref_state[key]), np.asarray(got_state[key])
            ), key
        assert (got[:P] >= 0).any()

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
        _check_chained(*random_cluster(5))

    def test_chained_calls_over_bands(self):
        """The same across 75 count bands, the final carries included."""
        _check_chained(*banded_cluster(11, **BANDED_CASES["600-services"]))


def _check_chained(pods, nodes, assigned, services):
    snap = build_snapshot(
        pods, nodes, assigned_pods=assigned, services=services
    )
    d = device_snapshot(snap)
    whole, whole_state = solve_with_state_pallas(
        d.pods, d.nodes, interpret=True
    )
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
    a2, state = solve_with_state_pallas(
        slice_pods(cut, P), state, interpret=True
    )
    chained = np.concatenate([np.asarray(a1)[:cut], np.asarray(a2)[: P - cut]])
    assert (np.asarray(whole)[:P] == chained).all()
    for key in CARRY_KEYS:
        assert np.array_equal(
            np.asarray(whole_state[key]), np.asarray(state[key])
        ), key


class TestDispatch:
    def test_not_eligible_on_cpu_platform(self):
        pods, nodes, assigned, services = random_cluster(0)
        snap = build_snapshot(
            pods, nodes, assigned_pods=assigned, services=services
        )
        d = device_snapshot(snap)
        # conftest forces the CPU platform: the real kernel must not
        # engage; solver.solve falls back to the XLA scan.
        assert pallas_reason(d.nodes, DEFAULT_LOWERED) == "platform"

    def test_host_arrays_not_eligible(self):
        # NumPy columns carry no device placement: ineligible by the
        # explicit type check, not by a swallowed exception.
        pods, nodes, assigned, services = random_cluster(0)
        snap = build_snapshot(
            pods, nodes, assigned_pods=assigned, services=services
        )
        d = device_snapshot(snap)
        host = {k: np.asarray(v) for k, v in d.nodes.items()}
        assert pallas_reason(host, DEFAULT_LOWERED) == "host_arrays"

    def test_env_kill_switch(self, monkeypatch):
        monkeypatch.setenv("KTPU_PALLAS", "off")
        pods, nodes, assigned, services = random_cluster(0)
        snap = build_snapshot(
            pods, nodes, assigned_pods=assigned, services=services
        )
        d = device_snapshot(snap)
        assert pallas_reason(d.nodes, DEFAULT_LOWERED) == "disabled"

    def test_solves_counted_by_kernel_and_reason(self):
        """Every solve counts under the kernel that ran it: the CPU
        backend's go to the XLA scan with the reason, a banded session's
        tick to the kernel."""
        from kubernetes_tpu.ops.solver import solve

        fam = pallas_scan.SOLVES
        xla = lambda: fam.value(kernel="xla_scan", reason="platform")
        pallas = lambda: fam.value(kernel="pallas", reason="eligible")
        before_x, before_p = xla(), pallas()
        pods, nodes, assigned, services = random_cluster(2)
        d = device_snapshot(
            build_snapshot(pods, nodes, assigned_pods=assigned, services=services)
        )
        solve(d.pods, d.nodes)
        assert xla() == before_x + 1
        sess = SolverSession(nodes)
        assert sess.kernel_reason == "platform" and not sess.banded
        sess.add_pending(pods[0])
        sess.solve()
        assert xla() == before_x + 2
        assert pallas() == before_p
        assert pallas_scan.count_solve("") is True
        assert pallas() == before_p + 1
        assert pallas_scan.count_solve("vmem") is False
        assert fam.value(kernel="xla_scan", reason="vmem") >= 1


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
            MAX_NODES,
            VMEM_BUDGET_BYTES,
            _vmem_bytes,
            shape_reason,
        )

        # The per-node word carries stay in VMEM: 64 hostPort and 64
        # volume words over 8,192 slots need more than the budget.
        assert _vmem_bytes(MAX_NODES, 1, 4, 64, 64) > VMEM_BUDGET_BYTES
        assert shape_reason(MAX_NODES, 4, 64, 64) == "vmem"
        assert shape_reason(MAX_NODES + 128, 4, 4, 4) == "nodes"
        # The Service counts live in HBM: the documented limits (5,000
        # nodes bucketed to 8,192 slots, 10,000 Services) are admitted,
        # as is the bench's 50k x 5k shape (N=5120, S=512).
        assert _vmem_bytes(8192, 10000, 4, 4, 4) <= VMEM_BUDGET_BYTES
        assert _vmem_bytes(8192, 10000, 4, 4, 4) == _vmem_bytes(8192, 1, 4, 4, 4)
        assert shape_reason(8192, 4, 4, 4) == ""
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


@pytest.fixture
def banded_sessions(monkeypatch):
    """Sessions that take the kernel's layout here on the CPU: the
    kernel's choice forced, the kernel itself interpreted."""
    monkeypatch.setattr(pallas_scan, "session_reason", lambda *a: "")
    monkeypatch.setattr(
        pallas_scan, "solve_banded",
        functools.partial(pallas_scan.solve_banded, interpret=True),
    )


def test_banded_session_ticks_with_deletes_match_xla(banded_sessions, monkeypatch):
    """A session in the kernel's layout against one in the XLA scan's,
    over the same ticks: bound pods deleted between ticks (the column
    scatter into the banded carry), then more pods solved. Decisions,
    the host mirror and the device carry agree after every tick."""
    from kubernetes_tpu.ops.incremental import CARRY_BYTES

    pods, nodes, assigned, services = banded_cluster(
        3, n_services=48, n_nodes=40, n_pods=240, run=3, multi=0.3
    )
    banded = SolverSession(nodes, services=services, assigned=assigned)
    assert banded.banded
    S_pad = pallas_scan._svc_pad(len(services))
    assert banded.dev["svc_counts"].shape == (S_pad, banded.N_cap)
    assert CARRY_BYTES.value() == S_pad * banded.N_cap * 2
    monkeypatch.setattr(pallas_scan, "session_reason", lambda *a: "platform")
    plain = SolverSession(nodes, services=services, assigned=assigned)
    assert not plain.banded
    rng = random.Random(5)
    bound = [a for a in assigned]
    for tick in range(3):
        for p in pods[tick * 80 : (tick + 1) * 80]:
            banded.add_pending(p)
            plain.add_pending(p)
        got, ref = banded.solve(), plain.solve()
        assert got == ref
        by_name = {p.metadata.name: p for p in pods}
        for key, node in got:
            if node is not None:
                bound.append(by_name[key.split("/", 1)[1]])
        # Deletes between ticks: a third of the bound pods, at random.
        for victim in rng.sample(bound, len(bound) // 3):
            bound.remove(victim)
            key = f"default/{victim.metadata.name}"
            assert banded.delete_assigned(key) == plain.delete_assigned(key)
        banded._flush_dirty()
        plain._flush_dirty()
        for k in plain.h:
            assert np.array_equal(banded.h[k], plain.h[k]), k
        counts = pallas_scan.from_bands(banded.dev["svc_counts"], banded.S)
        assert np.array_equal(
            np.asarray(counts), np.asarray(plain.dev["svc_counts"])
        )
        for k in plain.dev:
            if k != "svc_counts":
                assert np.array_equal(
                    np.asarray(banded.dev[k]), np.asarray(plain.dev[k])
                ), k


def test_banded_session_node_churn_matches_xla(banded_sessions, monkeypatch):
    """Nodes removed and pods bound from outside (``add_assigned``) on a
    banded session against an XLA one: the delete patch writes every
    count entry they changed, and the next tick decides alike."""
    import copy

    pods, nodes, assigned, services = banded_cluster(
        4, n_services=48, n_nodes=40, n_pods=160, run=2, multi=0.3
    )
    banded = SolverSession(nodes, services=services, assigned=assigned)
    assert banded.banded
    monkeypatch.setattr(pallas_scan, "session_reason", lambda *a: "platform")
    plain = SolverSession(nodes, services=services, assigned=assigned)
    foreign = []
    for i, p in enumerate(pods[:40]):
        f = copy.deepcopy(p)
        f.spec.node_name = nodes[(7 * i) % len(nodes)].metadata.name
        foreign.append(f)
    for session in (banded, plain):
        session.remove_node("n3")
        session.remove_node("n17")
        for f in foreign:
            assert session.add_assigned(f) == (f.spec.node_name not in ("n3", "n17"))
        session._flush_dirty()
    for k in plain.h:
        assert np.array_equal(banded.h[k], plain.h[k]), k
    counts = pallas_scan.from_bands(banded.dev["svc_counts"], banded.S)
    assert np.array_equal(np.asarray(counts), np.asarray(plain.dev["svc_counts"]))
    for p in pods[40:]:
        banded.add_pending(p)
        plain.add_pending(p)
    assert banded.solve() == plain.solve()
    counts = pallas_scan.from_bands(banded.dev["svc_counts"], banded.S)
    assert np.array_equal(np.asarray(counts), np.asarray(plain.dev["svc_counts"]))
