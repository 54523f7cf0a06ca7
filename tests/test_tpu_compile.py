"""The main path's kernels compile for a TPU v5e at real widths.

Nothing runs: each test compiles one kernel from shapes only, for a
chip that is described, not attached (``topologies.get_topology_desc``),
so what the chip's compiler would refuse — a block not aligned to the
tiling, more VMEM than a kernel may use, a program that does not fit —
fails here at no chip time. The shapes come from the same lowering the
chip runs (``device_snapshot`` for a backlog, the pipeline's chunk, a
``SolverSession`` sized as the daemon sizes it), read off host arrays.

The topology is described inside a module fixture, never at import:
only the xdist worker given this file loads the TPU compiler library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from __graft_entry__ import _synthetic_objects
from kubernetes_tpu.models.algspec import DEFAULT_LOWERED
from kubernetes_tpu.models.columnar import SnapshotBuilder, build_snapshot
from kubernetes_tpu.ops import SolverSession, device_snapshot
from kubernetes_tpu.ops.incremental import _scatter_rows
from kubernetes_tpu.ops.matrices import device_pods
from kubernetes_tpu.ops.pallas_scan import (
    MAX_NODES,
    VMEM_BUDGET_BYTES,
    _solve_packed,
    _svc_pad,
    _vmem_bytes,
    count_points,
    scatter_rows_banded,
)
from kubernetes_tpu.ops.pipeline import DEFAULT_CHUNK
from kubernetes_tpu.ops.solver import DEFAULT_WEIGHTS, _solve_with_state_xla

#: The served drill's cluster (chip_smoke.py) and the daemon's pre-warm
#: scatter widths top out here (SolverSession.prewarm).
SESSION_NODES = 5000
MAX_SCATTER_WIDTH = 512
#: Services of the largest configured cluster (kbench svc-5k: 150,000
#: pods, 15 a Service).
MAX_SERVICES = 10_000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def on_chip(one_chip):
    """Host arrays -> ShapeDtypeStructs placed on the described chip."""

    def convert(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree,
        )

    return convert


@pytest.fixture(scope="module")
def backlog():
    """(n_pods, n_nodes) -> the whole-backlog device snapshot (what
    solve_assignments and the full-relower daemon run) and its API
    objects, built once per shape on the host."""
    built: dict = {}

    def get(n_pods: int, n_nodes: int):
        if (n_pods, n_nodes) not in built:
            objs = _synthetic_objects(n_pods, n_nodes, seed=7)
            pods, nodes, services = objs
            snap = build_snapshot(pods, nodes, services=services)
            built[n_pods, n_nodes] = device_snapshot(snap), objs
        return built[n_pods, n_nodes]

    return get


@pytest.fixture(scope="module")
def session():
    """A session over the served drill's cluster, sized exactly as the
    incremental daemon sizes it (IncrementalBatchScheduler._build_session)."""
    _pods, nodes, services = _synthetic_objects(0, SESSION_NODES, seed=7)
    return SolverSession(
        nodes,
        services=services[:0],
        node_capacity=max(64, int(len(nodes) * 1.25)),
    )


def _banded(nodes, S=None):
    """Node shapes with the service counts in the kernel's layout,
    (S_pad, N) int16, for ``S`` services (default: as given)."""
    N, S0 = nodes["svc_counts"].shape
    sharding = getattr(nodes["svc_counts"], "sharding", None)
    counts = jax.ShapeDtypeStruct(
        (_svc_pad(S0 if S is None else S), N), jnp.int16, sharding=sharding
    )
    return dict(nodes, svc_counts=counts)


def _compile_pallas(pods, nodes, S=None):
    """Compile the kernel for (N, S)-schema node shapes, banded."""
    banded = _banded(nodes, S)
    rest = {k: v for k, v in banded.items() if k != "svc_counts"}
    compiled = _solve_packed.lower(
        pods, rest, banded["svc_counts"], DEFAULT_WEIGHTS
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _fits_vmem(nodes) -> bool:
    return _vmem_bytes(
        nodes["cpu_cap"].shape[0],
        nodes["svc_counts"].shape[1],
        nodes["labels"].shape[1],
        nodes["uport"].shape[1],
        nodes["uvol_any"].shape[1],
    ) <= VMEM_BUDGET_BYTES


@pytest.mark.parametrize(
    "n_pods,n_nodes", [(10_000, 1_000), (50_000, 5_000)], ids=["10kx1k", "50kx5k"]
)
def test_pallas_scan_whole_backlog(on_chip, backlog, n_pods, n_nodes):
    d, _objs = backlog(n_pods, n_nodes)
    # pallas_reason's shape rule admits this shape on one chip.
    assert d.nodes["cpu_cap"].shape[0] <= MAX_NODES and _fits_vmem(d.nodes)
    _compile_pallas(on_chip(d.pods), on_chip(d.nodes))


def test_pallas_scan_pipeline_chunk_50kx5k(on_chip, backlog):
    """The chunk solve_backlog_pipelined dispatches at 50k x 5k."""
    d, (pods, nodes, services) = backlog(50_000, 5_000)
    cols = SnapshotBuilder(pods, nodes, (), services).pod_columns(
        0, DEFAULT_CHUNK
    )
    assert cols.count == DEFAULT_CHUNK
    chunk = device_pods(cols, jax.devices()[0])
    _compile_pallas(on_chip(chunk), on_chip(d.nodes))


def test_xla_scan_50kx5k(on_chip, backlog):
    d, _objs = backlog(50_000, 5_000)
    _solve_with_state_xla.lower(
        on_chip(d.pods), on_chip(d.nodes), DEFAULT_WEIGHTS, DEFAULT_LOWERED
    ).compile()


@pytest.mark.parametrize("bucket", [128, 1024])
def test_session_solve(on_chip, session, bucket):
    """The session tick at the default pre-warm bucket (128) and at the
    bucket a 1,000-pod wave lands in."""
    assert session.N_cap <= MAX_NODES and _fits_vmem(session.h)
    pods = session._stage_arrays([], bucket, reuse=False)
    _compile_pallas(on_chip(pods), on_chip(session.dev))


def test_session_solve_at_documented_limits(on_chip, session):
    """8,192 node slots (5,000 nodes bucketed) and 10,000 Services: the
    kernel keeps no Service term in VMEM and takes the shape."""
    N = session.N_cap
    assert N == MAX_NODES
    assert _vmem_bytes(N, MAX_SERVICES, session.LW, session.PW, session.VW) <= (
        VMEM_BUDGET_BYTES
    )
    pods = session._stage_arrays([], 1024, reuse=False)
    compiled = _compile_pallas(on_chip(pods), on_chip(session.dev), MAX_SERVICES)
    # The count carry is updated in place: no copy of its 164 MB.
    mem = compiled.memory_analysis()
    carry = _svc_pad(MAX_SERVICES) * N * 2
    assert mem.alias_size_in_bytes >= carry
    assert mem.temp_size_in_bytes < carry


def test_session_scatter_rows(on_chip, session):
    idx = np.zeros(MAX_SCATTER_WIDTH, np.int32)
    rows = {k: v[idx] for k, v in session.h.items()}
    _scatter_rows.lower(
        on_chip(session.dev), on_chip(idx), on_chip(rows)
    ).compile()


def test_session_scatter_banded_columns(on_chip, session):
    """The delete patch of a banded session at 10,000 Services: the
    rows of the per-node leaves, then point writes of count entries
    into the (S_pad, N) int16 carry, in place — no copy of its 164 MB."""
    idx = np.zeros(MAX_SCATTER_WIDTH, np.int32)
    rows = {k: v[idx] for k, v in session.h.items() if k != "svc_counts"}
    nodes = on_chip({k: v for k, v in session.dev.items() if k != "svc_counts"})
    _scatter_rows.lower(nodes, on_chip(idx), on_chip(rows)).compile()
    counts = _banded(on_chip(session.dev), MAX_SERVICES)["svc_counts"]
    svc, slot, value = count_points(
        np.zeros((1, 1), np.float32), [(0, 0)], MAX_SCATTER_WIDTH
    )
    compiled = scatter_rows_banded.lower(
        counts, on_chip(svc), on_chip(slot), on_chip(value)
    ).compile()
    mem = compiled.memory_analysis()
    carry = _svc_pad(MAX_SERVICES) * session.N_cap * 2
    assert mem.alias_size_in_bytes >= carry
    assert mem.temp_size_in_bytes < carry
