"""Host snapshot -> device arrays.

Uploads the columnar Snapshot (kubernetes_tpu.models.columnar) to the
accelerator, optionally sharding every node-axis array over a
jax.sharding.Mesh axis ("nodes"). Pod-axis arrays are replicated: the
solver scans over pods, so each step broadcasts one pod against the
sharded node state (the TPU analog of the reference's
pod-at-a-time loop against the full cluster).

Shapes are padded to multiples of `pad_to` so repeated solves with
slightly different cluster sizes reuse the compiled executable
(XLA static-shape requirement; SURVEY.md hard part (d)).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.models.algspec import DEFAULT_LOWERED, LoweredSpec
from kubernetes_tpu.models.columnar import SVC_K, Snapshot  # noqa: F401
from kubernetes_tpu.ops.ledger import traced_jit
# (SVC_K re-exported: device consumers import it from here.)


def _pad(arr: np.ndarray, n: int, fill=0) -> np.ndarray:
    """Pad axis 0 to length n."""
    if arr.shape[0] == n:
        return arr
    pad_width = [(0, n - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, pad_width, constant_values=fill)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m if x > 0 else m


def pow2_bucket(n: int, minimum: int = 128) -> int:
    """Next power-of-two bucket >= n (>= minimum). Canonical copy —
    incremental.py's session sizing uses this same helper."""
    b = minimum
    while b < n:
        b *= 2
    return b


def _pod_axis_bucket(n: int, minimum: int) -> int:
    """Pod-axis padding target: power-of-two buckets up to 8192, then
    multiples of 1024. A scheduler daemon's drain sizes vary with
    arrival timing, and every distinct padded shape is a fresh XLA
    compile (seconds each) — pow2 bucketing caps the daemon at ~7
    executables total, while huge offline solves (50k backlog) stay
    within ~2% padding waste on the scan's sequential steps."""
    if n <= 8192:
        return pow2_bucket(n, minimum)
    return _round_up(n, 1024)


def _pad_cols(arr: np.ndarray, m: int) -> np.ndarray:
    """Pad axis 1 up to a multiple of m (shape-bucketing for the minor
    dims: bitset word counts and the service axis drift with snapshot
    vocabularies, and every distinct shape is a fresh XLA executable)."""
    cols = arr.shape[1]
    target = _round_up(cols, m)
    if cols == target:
        return arr
    return np.pad(arr, [(0, 0), (0, target - cols)])


def _put_tree(arrs: Dict[str, np.ndarray], sharding) -> Dict[str, jnp.ndarray]:
    """ONE device_put for a whole dict of arrays, not one per array:
    each call pays a dispatch, and 10-16 small transfers per upload
    put that many on the pipelined solve's critical path. All-zero
    leaves (a fresh backlog's occupancy matrices — svc_counts alone is
    N x S f32 ~10 MB at 5k x 500) materialize directly on device
    instead of shipping zeros host-to-device."""
    zeros = {k: v for k, v in arrs.items() if v.size > 4096 and not v.any()}
    rest = {k: v for k, v in arrs.items() if k not in zeros}
    if rest:
        # Transfer SLI (utils/sli.py): what actually ships host->device
        # — the all-zero leaves materialize on device and move nothing.
        from kubernetes_tpu.utils import sli

        sli.note_transfer("h2d", sli.nbytes_of(rest))
    out = dict(jax.device_put(rest, sharding)) if rest else {}
    for k, v in zeros.items():
        out[k] = jnp.zeros(v.shape, dtype=v.dtype, device=sharding)
    return out


@dataclass
class DeviceSnapshot:
    """Device-resident scheduling problem. `pods`/`nodes` are dicts of
    jnp arrays; padded entries are masked off (pods: pinned == -2 never
    fits anywhere; nodes: schedulable == False)."""

    pods: Dict[str, jnp.ndarray]
    nodes: Dict[str, jnp.ndarray]
    n_pods: int  # real (unpadded) counts
    n_nodes: int
    # Policy lowering riding along (defaults = the stock pipeline).
    lowered: LoweredSpec = DEFAULT_LOWERED
    weights: Tuple[int, int, int] = (1, 1, 1)

    @property
    def pod_count_padded(self) -> int:
        return int(self.pods["cpu"].shape[0])

    @property
    def node_count_padded(self) -> int:
        return int(self.nodes["cpu_cap"].shape[0])


# Bucket minor dims: bitset widths to pairs of u32 words, the service
# axis to 128 — so vocab drift between snapshots reuses the compiled
# executable instead of triggering a fresh XLA compile.
WORD_BUCKET, SVC_BUCKET = 2, 128


def device_pods(
    p,
    sharding,
    pad_to: int = 128,
) -> Dict[str, jnp.ndarray]:
    """PodColumns -> device dict (padded axis 0 to a pad_to multiple)."""
    P = p.count
    PP = _pod_axis_bucket(P, pad_to)
    sel_rows = (
        p.sel_bits[p.selector_id]
        if P
        else np.zeros((0, p.sel_bits.shape[1]), np.uint32)
    )
    pods = {
        "cpu": _pad(p.cpu_milli, PP),
        "mem": _pad(p.mem_mib, PP),
        "zero_req": _pad(p.zero_req, PP, fill=False),
        "sel": _pad(_pad_cols(sel_rows, WORD_BUCKET), PP),
        "port": _pad(_pad_cols(p.port_bits, WORD_BUCKET), PP),
        "vol_any": _pad(_pad_cols(p.vol_any_bits, WORD_BUCKET), PP),
        "vol_rw": _pad(_pad_cols(p.vol_rw_bits, WORD_BUCKET), PP),
        # Padding pods are pinned to -2 (an impossible node) so they
        # always come back unassigned.
        "pinned": _pad(p.pinned_node, PP, fill=-2),
        "svc": _pad(p.service_id, PP, fill=-1),
        "svc_ids": _pad(p.svc_topk, PP, fill=-1),
    }
    if p.aff_pin is not None:
        # Padded pods are already pinned to -2 (never placed); -1 here
        # just means "no pinned affinity value".
        pods["aff_pin"] = _pad(p.aff_pin, PP, fill=-1)
    return _put_tree(pods, sharding)


def device_nodes(
    n,
    sharding,
    pad_to: int = 128,
    node_mult: Optional[int] = None,
) -> Dict[str, jnp.ndarray]:
    """NodeColumns -> device dict (padded so the node axis divides
    evenly across mesh shards)."""
    N = n.count
    NP = _round_up(N, node_mult or pad_to)
    nodes = {
        "cpu_cap": _pad(n.cpu_cap, NP),
        "mem_cap": _pad(n.mem_cap, NP),
        "pods_cap": _pad(n.pods_cap, NP),
        "cpu_fit": _pad(n.cpu_fit_used, NP),
        "mem_fit": _pad(n.mem_fit_used, NP),
        "over": _pad(n.overcommitted, NP, fill=False),
        "cpu_used": _pad(n.cpu_used, NP),
        "mem_used": _pad(n.mem_used, NP),
        "pods_used": _pad(n.pods_used, NP),
        "labels": _pad(_pad_cols(n.label_bits, WORD_BUCKET), NP),
        "uport": _pad(_pad_cols(n.used_port_bits, WORD_BUCKET), NP),
        "uvol_any": _pad(_pad_cols(n.used_vol_any_bits, WORD_BUCKET), NP),
        "uvol_rw": _pad(_pad_cols(n.used_vol_rw_bits, WORD_BUCKET), NP),
        "svc_counts": _pad(_pad_cols(n.service_counts, SVC_BUCKET), NP),
        # Padding nodes are unschedulable -> never chosen.
        "sched": _pad(n.schedulable, NP, fill=False),
    }
    # Policy-spec columns (padding nodes are unschedulable, so fills
    # only need to be type-safe, not semantically meaningful).
    if n.policy_ok is not None:
        nodes["policy_ok"] = _pad(n.policy_ok, NP, fill=False)
    if n.static_prio is not None:
        nodes["static_prio"] = _pad(n.static_prio, NP)
    if n.aff_vid is not None:
        nodes["aff_vid"] = _pad(n.aff_vid, NP, fill=-1)
    if n.aa_zone is not None:
        nodes["aa_zone"] = _pad(n.aa_zone, NP, fill=-1)
    return _put_tree(nodes, sharding)


#: Predicate bit positions in the explain readback's packed per-node
#: failure mask (ops.solver.explain_rows; bit set = the predicate
#: REJECTED the node). Order is the solver's evaluation order; names
#: match the reference FitPredicate names operators already know from
#: FailedScheduling events (plugin/pkg/scheduler/factory/plugins.go) —
#: plus NodeSchedulable, the reference's ready/unschedulable node
#: filter that runs before predicates (factory.go:166,209).
EXPLAIN_PREDICATES = (
    "NodeSchedulable",
    "PodFitsResources",
    "MatchNodeSelector",
    "PodFitsPorts",
    "NoDiskConflict",
    "HostName",
)


def decode_predicate_bits(bits: int) -> list:
    """Failed-predicate names for one node's packed verdict mask."""
    return [
        name
        for i, name in enumerate(EXPLAIN_PREDICATES)
        if bits & (1 << i)
    ]


@traced_jit(static_argnames=("num_groups",))
def gang_member_counts(
    placed: jnp.ndarray, group_ids: jnp.ndarray, num_groups: int
) -> jnp.ndarray:
    """Per-group placed-member counts as a MASKED segment reduction —
    the gang-acceptance primitive. `placed` is bool[P] (pod i received
    a feasible assignment), `group_ids` int32[P] with -1 for ungrouped
    and padding rows. Ungrouped/padded rows are masked out of the sum
    rather than filtered (static shapes: the solver's pod axis is
    padded, and XLA recompiles on any shape change). Callers bucket
    num_groups (it is a static arg) so group-count drift between
    batches reuses the compiled executable."""
    mask = placed & (group_ids >= 0)
    idx = jnp.clip(group_ids, 0, num_groups - 1)
    return jax.ops.segment_sum(
        mask.astype(jnp.int32), idx, num_segments=num_groups
    )


def node_axis_multiple(
    mesh: Optional[jax.sharding.Mesh], pad_to: int = 128
) -> int:
    """Node-axis padding multiple: must divide evenly across mesh shards."""
    if mesh is None:
        return pad_to
    return max(pad_to, int(np.prod([mesh.shape[a] for a in mesh.axis_names])))


def host_mesh(
    n: int, axis: str = "nodes"
) -> Optional[jax.sharding.Mesh]:
    """The sanctioned mesh constructor for the kernel layer: a 1-D mesh
    over the first `n` visible devices, or None when a mesh is not
    viable (n < 2, or fewer than n devices — e.g. a host platform that
    was not forced to multiple CPU devices). Sessions, the
    KT_MESH_DEVICES escape hatch, and test fixtures all route through
    here so ops/ shares one topology (KT009 flags ad-hoc Mesh
    construction elsewhere in the package)."""
    if n < 2:
        return None
    devices = jax.devices()
    if len(devices) < n:
        return None
    return jax.sharding.Mesh(np.asarray(devices[:n]), axis_names=(axis,))


def shardings_for(mesh: Optional[jax.sharding.Mesh], node_axis: str = "nodes"):
    """(node_sharding, pod_sharding) for a mesh (or the default device)."""
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as PS

        return NamedSharding(mesh, PS(node_axis)), NamedSharding(mesh, PS())
    # The ONE sanctioned default-device read in ops/ (no-mesh staging).
    # ktlint: disable=KT009
    device = jax.devices()[0]
    return device, device


def device_snapshot(
    snap: Snapshot,
    mesh: Optional[jax.sharding.Mesh] = None,
    node_axis: str = "nodes",
    pad_to: int = 128,
) -> DeviceSnapshot:
    node_mult = node_axis_multiple(mesh, pad_to)
    node_sharding, pod_sharding = shardings_for(mesh, node_axis)
    nodes = device_nodes(
        snap.nodes, node_sharding, pad_to=pad_to, node_mult=node_mult
    )
    if snap.anchor_init is not None:
        # ServiceAffinity/AntiAffinity carry seeds: service-axis state
        # sized to the padded svc_counts column count PLUS one scratch
        # slot (the last index), which absorbs -1-padded svc_ids
        # scatters in the solver commit. Replicated, not node-sharded.
        SP = _round_up(max(snap.anchor_init.shape[0], 1), SVC_BUCKET)
        anchor = np.full(SP + 1, -1, dtype=np.int32)
        anchor[: snap.anchor_init.shape[0]] = snap.anchor_init
        total = np.zeros(SP + 1, dtype=np.float32)
        total[: snap.svc_total_init.shape[0]] = snap.svc_total_init
        nodes["anchor"] = jax.device_put(anchor, pod_sharding)
        nodes["svc_total"] = jax.device_put(total, pod_sharding)
    return DeviceSnapshot(
        pods=device_pods(snap.pods, pod_sharding, pad_to=pad_to),
        nodes=nodes,
        n_pods=snap.pods.count,
        n_nodes=snap.nodes.count,
        lowered=snap.lowered or DEFAULT_LOWERED,
        weights=snap.weights or (1, 1, 1),
    )
