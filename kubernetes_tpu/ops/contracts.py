"""Kernel shape/dtype/sharding contracts for every registered kernel.

The kernel layer's correctness rests on three invariants that used to
be enforced only dynamically and partially:

- **bucketed shapes** — every staged axis comes off a known lattice
  (pow2 buckets, word/service multiples), so a drifting cluster never
  triggers an XLA recompile storm (PR 7's recompilation sentinel
  watches this at runtime; the contract states it);
- **stable dtypes** — kernel results carry the exact dtypes the NumPy
  oracle twins (ops/parity.py ORACLE_TWINS) produce, with no weak-type
  or accidental f64 promotion (bit-parity with the oracles depends on
  it);
- **pod-axis coupling** — whether a kernel is independent along the
  pod axis (``shardable``: the precondition for sharding the pod axis
  over a Mesh, ROADMAP item #2), intentionally couples pods
  (``reduces``: scans/segment reductions), or never touches the pod
  axis at all (``replicated``);
- **mesh sharding + communication budget** — HOW the kernel partitions
  over a 1-D Mesh (which symbolic dim is sharded over which axis — a
  symbolic PartitionSpec per array leaf, see :func:`partition_specs`)
  and the exact collective inventory XLA's SPMD partitioner inserts
  for it (:class:`CommBudget`, pinned at the distinct-dims probe
  point). ``tools/ktlint/ktmesh.py`` VERIFIES the budget by
  partitioned-lowering under a forced multi-device CPU mesh (compile,
  never execute); the ledger joins runtime compiles against it via
  :func:`comm_verdict`.

This module DECLARES those invariants, one :class:`Contract` per
ORACLE_TWINS key; ``tools/ktlint/ktshape.py`` VERIFIES them without
executing anything (``jax.eval_shape`` + a jaxpr walk over
``ShapeDtypeStruct`` probes). The checker enforces completeness both
ways: a kernel without a contract, or a contract without a kernel, is
a finding.

It is also the single home of the **staged-shape signature**: the
compact ``f32[128],i32[128,8],...`` string the PR-13 compile ledger
keys its per-shape rows by. :func:`shape_signature` is THE
implementation (ops/ledger.py delegates here), and
:func:`contract_verdict` joins observed ledger signatures back against
the declared contracts — a drifted staged shape shows up as a CONTRACT
mismatch in ``GET /debug/kernels`` / ``ktctl profile kernels``.

No module-level jax import (ops/ledger.py rides this module at import
time and keeps the "a CPU-only control plane never loads jax" rule).
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from kubernetes_tpu.models.columnar import SVC_K
from kubernetes_tpu.ops.parity import ORACLE_TWINS

__all__ = [
    "ArraySpec",
    "CommBudget",
    "Contract",
    "CONTRACTS",
    "DIM_LATTICES",
    "MeshSharding",
    "Static",
    "DimRef",
    "POD_AXIS_KINDS",
    "abstract_args",
    "collective_inventory",
    "comm_verdict",
    "contract_verdict",
    "declared_array_leaves",
    "leaf_signature",
    "match_signature",
    "partition_specs",
    "resolve_kernel",
    "shape_signature",
    "sharded_abstract_args",
]


# -- staged-shape signatures (canonical; the ledger delegates here) ----


def leaf_signature(leaf) -> str:
    """One pytree leaf's signature token: ``f32[128,8]`` for arrays
    (numpy dtype kind + bit width + shape), a truncated repr for
    non-array leaves (static scalars, spec namedtuple fields)."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        r = repr(leaf)
        return r if len(r) <= 32 else r[:29] + "..."
    import numpy as np

    d = np.dtype(dtype)
    return f"{d.kind}{d.itemsize * 8}[{','.join(str(s) for s in shape)}]"


def shape_signature(args, kwargs=None) -> str:
    """Compact staged-shape signature of one kernel call — the ledger's
    per-bucket row key AND the string :func:`contract_verdict` checks
    against the declared contract. One implementation; the two surfaces
    can never drift."""
    import jax

    leaves = jax.tree_util.tree_leaves((args, kwargs or {}))
    return ",".join(leaf_signature(leaf) for leaf in leaves)


#: Array tokens inside a signature: dtype kind letter + bits + [dims].
#: Non-array tokens (static reprs) never match — shapes are the only
#: bracketed digit lists a signature contains.
_ARRAY_TOKEN_RE = re.compile(r"\b([a-zA-Z])(\d+)\[([\d,]*)\]")


def parse_signature(signature: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """[(dtype token like 'f32', shape tuple)] for every ARRAY leaf in
    a signature, in call order; static/non-array leaves are skipped."""
    out = []
    for m in _ARRAY_TOKEN_RE.finditer(signature):
        kind, bits, dims = m.group(1), m.group(2), m.group(3)
        shape = tuple(int(d) for d in dims.split(",")) if dims else ()
        out.append((f"{kind}{bits}", shape))
    return out


# -- the dim lattice ----------------------------------------------------


def _is_pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


#: Symbolic dims and their bucket lattices. A concrete staged size off
#: its symbol's lattice means the staging layer's bucketing leaked — a
#: fresh XLA executable per cluster-size drift (the recompile storm the
#: pow2 helpers exist to prevent).
DIM_LATTICES: Dict[str, Tuple[str, object]] = {
    # Solver-family pod axis (matrices._pod_axis_bucket): pow2 >= 128
    # up to 8192, then 1024-multiples.
    "P": (
        "pod axis: pow2 >= 128, then 1024-multiples past 8192",
        lambda n: (_is_pow2(n) and n >= 128) or (n > 8192 and n % 1024 == 0),
    ),
    # Gang acceptance pod axis (pipeline.gang_member_counts_device).
    "PG": ("gang pod axis: pow2 >= 8", lambda n: _is_pow2(n) and n >= 8),
    "G": ("gang group axis: pow2 >= 8", lambda n: _is_pow2(n) and n >= 8),
    # Node axis: multiples of 128 (device_nodes pads to pad_to/mesh
    # multiples; sessions use pow2 >= 128, a subset).
    "N": ("node axis: multiple of 128", lambda n: n >= 128 and n % 128 == 0),
    # Bitset word axes (matrices.WORD_BUCKET): label/selector words,
    # hostPort words, volume words bucket independently.
    "LW": ("label/selector words: multiple of 2", lambda n: n >= 2 and n % 2 == 0),
    "PW": ("hostPort words: multiple of 2", lambda n: n >= 2 and n % 2 == 0),
    "VW": ("volume words: multiple of 2", lambda n: n >= 2 and n % 2 == 0),
    # Service axis: SVC_BUCKET multiples on the snapshot path; the
    # incremental session freezes the raw service count at build time
    # (fixed per session, so no recompile churn) — any size >= 1.
    "S": ("service axis: session-frozen, >= 1", lambda n: n >= 1),
    "K": (f"service top-K: exactly {SVC_K}", lambda n: n == SVC_K),
    # Preemption staging (preemption.candidate_prefixes_device).
    "V": ("victim axis: pow2 >= 8", lambda n: _is_pow2(n) and n >= 8),
    "M": ("preemption node axis: pow2 >= 8", lambda n: _is_pow2(n) and n >= 8),
    # Dirty-row scatter width (SolverSession._flush_dirty).
    "R": ("scatter width: pow2 >= 8", lambda n: _is_pow2(n) and n >= 8),
    # Capacity probe-shape axis (utils/capacity.py pads the probe set —
    # backlog quantiles + configured slice shapes — to pow2 buckets).
    "Q": ("probe-shape axis: pow2 >= 4", lambda n: _is_pow2(n) and n >= 4),
    # Rebalance movable-pod axis (utils/rebalance.py pads the sorted
    # movable worklist to pow2 buckets).
    "D": ("rebalance pod axis: pow2 >= 8", lambda n: _is_pow2(n) and n >= 8),
    # Policy-lowering minor axes: sized by the configured policy
    # (affinity label count, anti-affinity zone vocab) — static per
    # lowered spec, not bucketed.
    "A": ("policy affinity axis: >= 1", lambda n: n >= 1),
    "Z": ("policy zone axis: >= 1", lambda n: n >= 1),
    "S1": ("service axis + scratch slot: >= 2", lambda n: n >= 2),
    # The pallas kernel's service-count carry: whole 8-row bands
    # (pallas_scan._svc_pad).
    "SB": ("service bands: multiple of 8", lambda n: n >= 8 and n % 8 == 0),
}


def dim_ok(symbol: str, size: int) -> bool:
    entry = DIM_LATTICES.get(symbol)
    return bool(entry and entry[1](size))


# -- contract schema ----------------------------------------------------


@dataclass(frozen=True)
class ArraySpec:
    """One array leaf: symbolic dims + canonical dtype token
    (``f32``/``i32``/``u32``/``b8`` — numpy kind + bits, matching
    :func:`leaf_signature`). ``optional`` marks policy-lowering leaves
    that only exist when a policy spec adds them."""

    dims: Tuple[str, ...]
    dtype: str
    optional: bool = False


@dataclass(frozen=True)
class Static:
    """A static (non-array) argument: ``value`` is the sample the
    checker passes at trace time; a callable is resolved lazily (specs
    that would pull jax-adjacent imports at module load)."""

    value: object = None


@dataclass(frozen=True)
class DimRef:
    """A static argument whose sample value is a bound dim (e.g.
    ``num_groups=DimRef('G')``)."""

    symbol: str


@dataclass(frozen=True)
class CommBudget:
    """The exact collective set one kernel's partitioned lowering may
    emit under its declared :class:`MeshSharding`, pinned at the
    distinct-dims probe point (jax 0.4.x GSPMD on the forced 8-device
    host platform). ktmesh compares the compiled module's inventory
    against this EXACTLY — a phantom collective (sharding regression)
    and a vanished one (stale budget) are both findings."""

    all_gather: int = 0
    all_reduce: int = 0
    reduce_scatter: int = 0
    collective_permute: int = 0
    all_to_all: int = 0

    def as_dict(self) -> Dict[str, int]:
        """Sparse {HLO op name: count} — keys match the hyphenated
        names :func:`collective_inventory` counts, zero entries
        dropped so declared == observed is a plain dict compare."""
        pairs = (
            ("all-gather", self.all_gather),
            ("all-reduce", self.all_reduce),
            ("reduce-scatter", self.reduce_scatter),
            ("collective-permute", self.collective_permute),
            ("all-to-all", self.all_to_all),
        )
        return {k: v for k, v in pairs if v}

    def total(self) -> int:
        return sum(self.as_dict().values())


@dataclass(frozen=True)
class MeshSharding:
    """How one kernel partitions over a 1-D Mesh: ``dim`` is the
    symbolic dim sharded across mesh axis ``axis`` (None: every leaf
    replicated — the kernel runs identically on every device).
    The per-leaf PartitionSpec is DERIVED (:func:`partition_specs`):
    an array leaf shards exactly its ``dim`` dims, everything else
    replicates — the same layout ``matrices.shardings_for`` produces
    at runtime, so the static budget and the production staging agree
    by construction. ``lower_overrides`` pins contract kwargs for the
    mesh lowering only (e.g. the pallas kernel needs interpret=True to
    compile on the host platform)."""

    dim: Optional[str]
    axis: str  # "pods" | "nodes"
    budget: CommBudget = CommBudget()
    lower_overrides: Tuple[Tuple[str, object], ...] = ()
    notes: str = ""


@dataclass(frozen=True)
class Contract:
    """One kernel's declared interface. ``args`` are (name, spec-tree)
    in call order — spec-tree is an ArraySpec, a dict of ArraySpecs
    (sorted-key flattening, like jax), a Static, or a DimRef.
    ``results`` is the declared result pytree (tuples/dicts of
    ArraySpecs). ``pod_dim`` names which symbol is the pod axis (None:
    the kernel never sees pods); ``pod_axis`` declares its coupling
    class. ``samples`` are the bucket-lattice points the checker
    abstract-evaluates at. ``sharding`` declares the mesh partitioning
    + communication budget (ktmesh's subject; every registered kernel
    must carry one)."""

    kernel: str
    args: Tuple[Tuple[str, object], ...]
    results: object
    pod_dim: Optional[str]
    pod_axis: str  # "shardable" | "reduces" | "replicated"
    samples: Tuple[Dict[str, int], ...]
    kwargs: Tuple[Tuple[str, object], ...] = ()
    notes: str = ""
    sharding: Optional[MeshSharding] = None


POD_AXIS_KINDS = ("shardable", "reduces", "replicated")


def _f32(*dims, optional=False):
    return ArraySpec(tuple(dims), "f32", optional)


def _i32(*dims, optional=False):
    return ArraySpec(tuple(dims), "i32", optional)


def _u32(*dims, optional=False):
    return ArraySpec(tuple(dims), "u32", optional)


def _b8(*dims, optional=False):
    return ArraySpec(tuple(dims), "b8", optional)


def _i16(*dims, optional=False):
    return ArraySpec(tuple(dims), "i16", optional)


#: The pod-column schema every solver-family kernel consumes
#: (matrices.device_pods). aff_pin rides only when ServiceAffinity is
#: lowered.
POD_SCHEMA: Dict[str, ArraySpec] = {
    "cpu": _f32("P"),
    "mem": _f32("P"),
    "zero_req": _b8("P"),
    "sel": _u32("P", "LW"),
    "port": _u32("P", "PW"),
    "vol_any": _u32("P", "VW"),
    "vol_rw": _u32("P", "VW"),
    "pinned": _i32("P"),
    "svc": _i32("P"),
    "svc_ids": _i32("P", "K"),
    "aff_pin": _i32("P", "A", optional=True),
}

#: The node-column schema (matrices.device_nodes / SolverSession
#: _empty_node_columns). Policy columns + service-affinity carries are
#: optional.
NODE_SCHEMA: Dict[str, ArraySpec] = {
    "cpu_cap": _f32("N"),
    "mem_cap": _f32("N"),
    "pods_cap": _f32("N"),
    "cpu_fit": _f32("N"),
    "mem_fit": _f32("N"),
    "over": _b8("N"),
    "cpu_used": _f32("N"),
    "mem_used": _f32("N"),
    "pods_used": _f32("N"),
    "labels": _u32("N", "LW"),
    "uport": _u32("N", "PW"),
    "uvol_any": _u32("N", "VW"),
    "uvol_rw": _u32("N", "VW"),
    "svc_counts": _f32("N", "S"),
    "sched": _b8("N"),
    "policy_ok": _b8("N", optional=True),
    "static_prio": _i32("N", optional=True),
    "aff_vid": _i32("N", "A", optional=True),
    "aa_zone": _i32("N", "Z", optional=True),
    "anchor": _i32("S1", optional=True),
    "svc_total": _f32("S1", optional=True),
}

#: The dirty-row scatter's row schema: NODE_SCHEMA's non-optional
#: leaves with the node axis narrowed to the scatter width.
ROW_SCHEMA: Dict[str, ArraySpec] = {
    k: ArraySpec(("R",) + v.dims[1:], v.dtype)
    for k, v in NODE_SCHEMA.items()
    if not v.optional
}


def _default_lowered():
    from kubernetes_tpu.models.algspec import DEFAULT_LOWERED

    return DEFAULT_LOWERED


_SOLVE_SAMPLES = (
    {"P": 128, "N": 128, "LW": 2, "PW": 2, "VW": 2, "K": SVC_K, "S": 128},
    {"P": 512, "N": 256, "LW": 4, "PW": 2, "VW": 2, "K": SVC_K, "S": 128},
)

#: The pallas kernel's per-node columns: NODE_SCHEMA's non-optional
#: leaves but the service counts, which travel on their own as the
#: banded (SB, N) int16 carry.
_KERNEL_NODES = {
    k: v for k, v in NODE_SCHEMA.items()
    if not v.optional and k != "svc_counts"
}
_PALLAS_SAMPLES = tuple(dict(s, SB=s["S"]) for s in _SOLVE_SAMPLES)

_WAVE_TELEMETRY = (_i32(), _i32(), _f32())  # waves, iters, residual


#: The contract registry. Keys are ORACLE_TWINS keys — the checker
#: enforces completeness both ways, so a kernel lands with its oracle
#: twin AND its contract or it does not land.
CONTRACTS: Dict[str, Contract] = {
    "solver._solve_xla": Contract(
        kernel="solver._solve_xla",
        args=(
            ("pods", POD_SCHEMA),
            ("nodes", NODE_SCHEMA),
            ("weights", Static((1, 1, 1))),
            ("lspec", Static(_default_lowered)),
        ),
        results=_i32("P"),
        pod_dim="P",
        pod_axis="reduces",
        samples=_SOLVE_SAMPLES,
        notes="sequential scan over the pod axis — the parity path",
        sharding=MeshSharding(
            dim="N", axis="nodes",
            budget=CommBudget(all_gather=24, all_reduce=8),
            notes=(
                "the MULTICHIP layout: node columns sharded, pod "
                "columns replicated (the scan couples pods "
                "sequentially, so the pod axis cannot shard); the "
                "per-step argmax runs as a cross-shard reduce + "
                "node-axis gathers"
            ),
        ),
    ),
    "solver._solve_with_state_xla": Contract(
        kernel="solver._solve_with_state_xla",
        args=(
            ("pods", POD_SCHEMA),
            ("nodes", NODE_SCHEMA),
            ("weights", Static((1, 1, 1))),
            ("lspec", Static(_default_lowered)),
        ),
        results=(_i32("P"), NODE_SCHEMA),
        pod_dim="P",
        pod_axis="reduces",
        samples=_SOLVE_SAMPLES,
        notes="scan + donated occupancy carry",
        sharding=MeshSharding(
            dim="N", axis="nodes",
            budget=CommBudget(all_gather=24, all_reduce=8),
        ),
    ),
    "solver.explain_rows": Contract(
        kernel="solver.explain_rows",
        args=(("pods", POD_SCHEMA), ("nodes", NODE_SCHEMA)),
        results=(
            ArraySpec(("P", "N"), "u32"),
            ArraySpec(("P", "N"), "i32"),
            ArraySpec(("P", "N"), "i32"),
            ArraySpec(("P", "N"), "i32"),
        ),
        pod_dim="P",
        pod_axis="shardable",
        samples=_SOLVE_SAMPLES,
        notes=(
            "vmapped per-pod verdicts against FIXED occupancy — every "
            "pod independent; the proven go-case for the pod-axis Mesh"
        ),
        sharding=MeshSharding(
            dim="P", axis="pods",
            budget=CommBudget(),
            notes=(
                "THE go-case: pod columns sharded over the pod axis, "
                "node columns replicated — must lower with ZERO "
                "collectives (any collective here means the "
                "embarrassingly-parallel claim broke)"
            ),
        ),
    ),
    "wave.solve_waves": Contract(
        kernel="wave.solve_waves",
        args=(("pods", POD_SCHEMA), ("nodes", NODE_SCHEMA)),
        results=(_i32("P"), _i32()),
        pod_dim="P",
        pod_axis="reduces",
        samples=_SOLVE_SAMPLES,
        notes="windowed commit loop: waves gather/scatter the pod axis",
        sharding=MeshSharding(
            dim="N", axis="nodes",
            budget=CommBudget(all_gather=2, all_reduce=11),
            notes=(
                "per-wave feasibility scored on node shards, wave "
                "commits psum'd — a dozen windowed rounds instead of "
                "the scan's P per-pod rounds (why auto resolves to "
                "wave on a mesh)"
            ),
        ),
    ),
    "wave.solve_waves_with_state": Contract(
        kernel="wave.solve_waves_with_state",
        args=(("pods", POD_SCHEMA), ("nodes", NODE_SCHEMA)),
        results=(_i32("P"), NODE_SCHEMA, _i32()),
        pod_dim="P",
        pod_axis="reduces",
        samples=_SOLVE_SAMPLES,
        sharding=MeshSharding(
            dim="N", axis="nodes",
            budget=CommBudget(all_gather=2, all_reduce=11),
        ),
    ),
    "sinkhorn.solve_sinkhorn_stats": Contract(
        kernel="sinkhorn.solve_sinkhorn_stats",
        args=(("pods", POD_SCHEMA), ("nodes", NODE_SCHEMA)),
        results=(_i32("P"),) + _WAVE_TELEMETRY,
        pod_dim="P",
        pod_axis="reduces",
        samples=_SOLVE_SAMPLES,
        notes="Sinkhorn-priced windowed loop + convergence telemetry",
        sharding=MeshSharding(
            dim="N", axis="nodes",
            budget=CommBudget(all_gather=2, all_reduce=15),
            notes=(
                "wave's inventory + the Sinkhorn price iteration's "
                "extra node-shard psums (row/col marginals)"
            ),
        ),
    ),
    "sinkhorn.solve_sinkhorn_with_state": Contract(
        kernel="sinkhorn.solve_sinkhorn_with_state",
        args=(("pods", POD_SCHEMA), ("nodes", NODE_SCHEMA)),
        results=(_i32("P"), NODE_SCHEMA) + _WAVE_TELEMETRY,
        pod_dim="P",
        pod_axis="reduces",
        samples=_SOLVE_SAMPLES,
        sharding=MeshSharding(
            dim="N", axis="nodes",
            budget=CommBudget(all_gather=2, all_reduce=15),
        ),
    ),
    "pallas_scan._solve_packed": Contract(
        kernel="pallas_scan._solve_packed",
        args=(
            ("pods", POD_SCHEMA),
            ("nodes", _KERNEL_NODES),
            ("counts", _i16("SB", "N")),
            ("weights", Static((1, 1, 1))),
        ),
        results=(_i32("P"), _KERNEL_NODES, _i16("SB", "N")),
        pod_dim="P",
        pod_axis="reduces",
        samples=_PALLAS_SAMPLES,
        kwargs=(("interpret", Static(False)),),
        notes=(
            "whole sequential solve as one pallas_call (VMEM node "
            "carry, HBM service-count bands)"
        ),
        sharding=MeshSharding(
            dim=None, axis="nodes",
            budget=CommBudget(),
            lower_overrides=(("interpret", True),),
            notes=(
                "single-device only by design (the VMEM carry cannot "
                "shard): fully replicated, zero collectives; Mosaic "
                "cannot lower on the host platform, so the mesh probe "
                "compiles the interpreter path"
            ),
        ),
    ),
    "matrices.gang_member_counts": Contract(
        kernel="matrices.gang_member_counts",
        args=(("placed", _b8("PG")), ("group_ids", _i32("PG"))),
        results=_i32("G"),
        pod_dim="PG",
        pod_axis="reduces",
        samples=(
            {"PG": 8, "G": 8},
            {"PG": 256, "G": 16},
        ),
        kwargs=(("num_groups", DimRef("G")),),
        notes="masked segment_sum over the pod axis — gang acceptance",
        sharding=MeshSharding(
            dim="PG", axis="pods",
            budget=CommBudget(all_reduce=1),
            notes=(
                "the canonical reduces-kernel budget: pod rows "
                "sharded, per-shard segment_sum, ONE psum over the "
                "pod axis — and nothing more"
            ),
        ),
    ),
    "incremental._scatter_rows": Contract(
        kernel="incremental._scatter_rows",
        args=(
            ("nodes", {k: v for k, v in NODE_SCHEMA.items() if not v.optional}),
            ("idx", _i32("R")),
            ("rows", ROW_SCHEMA),
        ),
        results={k: v for k, v in NODE_SCHEMA.items() if not v.optional},
        pod_dim=None,
        pod_axis="replicated",
        samples=(
            {"N": 128, "LW": 2, "PW": 2, "VW": 2, "S": 1, "R": 8},
            {"N": 256, "LW": 2, "PW": 2, "VW": 4, "S": 16, "R": 64},
        ),
        notes="node-row patch; never sees the pod axis",
        sharding=MeshSharding(
            dim=None, axis="nodes",
            budget=CommBudget(),
            notes=(
                "dirty-row scatter stays replicated: sharding the "
                "node axis would turn every row patch into a "
                "collective-permute round on the micro-tick path"
            ),
        ),
    ),
    "pallas_scan.scatter_rows_banded": Contract(
        kernel="pallas_scan.scatter_rows_banded",
        args=(
            ("counts", _i16("SB", "N")),
            ("svc", _i32("R")),
            ("slot", _i32("R")),
            ("value", _i16("R")),
        ),
        results=_i16("SB", "N"),
        pod_dim=None,
        pod_axis="replicated",
        samples=(
            {"SB": 8, "N": 128, "R": 8},
            {"SB": 648, "N": 256, "R": 64},
        ),
        notes=(
            "point patch of the banded service-count carry (the "
            "dirty-row scatter's count half); never sees the pod axis"
        ),
        sharding=MeshSharding(
            dim=None, axis="nodes",
            budget=CommBudget(),
            notes=(
                "stays replicated, as the row scatter it completes: "
                "the banded carry lives on one chip"
            ),
        ),
    ),
    "preemption._victim_prefix_kernel.kernel": Contract(
        kernel="preemption._victim_prefix_kernel.kernel",
        args=(
            ("v_cpu", _f32("V")),
            ("v_mem", _f32("V")),
            ("v_prio", _i32("V")),
            ("v_node", _i32("V")),
            ("v_alive", _b8("V")),
            ("free_cpu", _f32("M")),
            ("free_mem", _f32("M")),
            ("free_pods", _f32("M")),
            ("node_ok", _b8("M")),
            ("p_cpu", _f32()),
            ("p_mem", _f32()),
            ("p_prio", _i32()),
        ),
        results=(_i32("M"), _i32("M"), _i32("V"), _i32("V")),
        pod_dim="V",
        pod_axis="reduces",
        samples=(
            {"V": 8, "M": 8},
            {"V": 64, "M": 32},
        ),
        kwargs=(("num_nodes", DimRef("M")),),
        notes=(
            "victim rows ARE pods: the lexsort + per-node prefix "
            "cumsums couple them by construction"
        ),
        sharding=MeshSharding(
            dim=None, axis="nodes",
            budget=CommBudget(),
            notes=(
                "replicated: victim sets are small (pow2 >= 8, not "
                "the 500k pod axis) and the lexsort would serialize "
                "across shards anyway"
            ),
        ),
    ),
    "capacity.capacity_report": Contract(
        kernel="capacity.capacity_report",
        args=(
            ("cpu_cap", _f32("N")),
            ("mem_cap", _f32("N")),
            ("pods_cap", _f32("N")),
            ("cpu_fit", _f32("N")),
            ("mem_fit", _f32("N")),
            ("pods_used", _f32("N")),
            ("over", _b8("N")),
            ("sched", _b8("N")),
            ("probe_cpu", _f32("Q")),
            ("probe_mem", _f32("Q")),
            ("probe_min", _i32("Q")),
            ("probe_live", _b8("Q")),
        ),
        results=(
            _f32("N"),  # util_cpu
            _f32("N"),  # util_mem
            _f32("N"),  # util_pods
            ArraySpec(("Q", "N"), "i32"),  # fit_int
            _i32("Q"),  # headroom
            _f32("Q"),  # frag
            _b8("Q"),  # slice_ok
            _b8("N"),  # stranded
            _f32(),  # frag_score
            _f32(),  # stranded_cpu
            _f32(),  # stranded_mem
        ),
        pod_dim="Q",
        pod_axis="reduces",
        samples=(
            {"Q": 4, "N": 128},
            {"Q": 8, "N": 256},
        ),
        notes=(
            "probes ARE canonical pod shapes: headroom/fragmentation "
            "totals reduce over the probe axis (and stranded-node "
            "detection any()s across it)"
        ),
        sharding=MeshSharding(
            dim="N", axis="nodes",
            budget=CommBudget(all_reduce=6),
            notes=(
                "node columns sharded (the probe axis is tiny), "
                "per-probe headroom counts and the frag/stranded "
                "totals psum across node shards"
            ),
        ),
    ),
    "rebalance.plan_moves": Contract(
        kernel="rebalance.plan_moves",
        args=(
            ("cpu_cap", _f32("N")),
            ("mem_cap", _f32("N")),
            ("pods_cap", _f32("N")),
            ("cpu_fit", _f32("N")),
            ("mem_fit", _f32("N")),
            ("pods_used", _f32("N")),
            ("over", _b8("N")),
            ("sched", _b8("N")),
            ("pod_cpu", _f32("D")),
            ("pod_mem", _f32("D")),
            ("pod_node", _i32("D")),
            ("pod_live", _b8("D")),
            ("pod_force", _b8("D")),
            ("probe_cpu", _f32("Q")),
            ("probe_mem", _f32("Q")),
            ("probe_min", _i32("Q")),
            ("probe_live", _b8("Q")),
            ("move_budget", _i32()),
        ),
        results=(
            _i32("D"),  # dest
            _b8("D"),  # moved
            _i32("D"),  # gain
            _i32(),  # n_moves
            _f32(),  # score_before
            _f32(),  # score_after
        ),
        pod_dim="D",
        pod_axis="reduces",
        samples=(
            {"D": 8, "N": 128, "Q": 4},
            {"D": 64, "N": 256, "Q": 8},
        ),
        notes=(
            "best-fit-decreasing scan over the movable-pod axis with "
            "an evolving occupancy carry — later moves see earlier "
            "ones by construction"
        ),
        sharding=MeshSharding(
            dim="N", axis="nodes",
            budget=CommBudget(all_gather=12, all_reduce=5),
            notes=(
                "node occupancy sharded; each best-fit step gathers "
                "the per-shard scores and psums the move verdicts "
                "(the movable-pod scan itself is sequential)"
            ),
        ),
    ),
}


# -- contract -> abstract inputs ----------------------------------------


def _distinct_bindings(contract: Contract) -> Dict[str, int]:
    """A binding where every bound dim size is unique — the jaxpr
    walk's pod-axis tracking identifies the pod axis by its size, so
    probe sizes must not collide. Sizes still satisfy every kernel's
    trace-time requirements (e.g. the pallas pod axis is a multiple of
    128), though not necessarily the bucket lattice — tracing does not
    care, and lattice conformance is checked separately."""
    symbols = _contract_symbols(contract)
    pool = {
        "P": 384, "PG": 24, "G": 48, "N": 256, "LW": 2, "PW": 4, "VW": 6,
        "S": 640, "K": SVC_K, "V": 40, "M": 16, "R": 12,
        "A": 3, "Z": 5, "S1": 641, "Q": 32, "D": 64, "SB": 648,
    }
    return {s: pool[s] for s in symbols if s in pool}


def _contract_symbols(contract: Contract) -> List[str]:
    syms: List[str] = []

    def scan(spec):
        if isinstance(spec, ArraySpec):
            if not spec.optional:
                for d in spec.dims:
                    if d not in syms:
                        syms.append(d)
        elif isinstance(spec, dict):
            for k in sorted(spec):
                scan(spec[k])
        elif isinstance(spec, DimRef):
            if spec.symbol not in syms:
                syms.append(spec.symbol)

    for _, spec in contract.args + contract.kwargs:
        scan(spec)
    scan(contract.results) if isinstance(contract.results, (ArraySpec, dict)) \
        else [scan(s) for s in contract.results]
    return syms


_DTYPE_OF = {
    "f32": "float32", "f64": "float64",
    "i32": "int32", "i64": "int64", "i16": "int16",
    "u32": "uint32", "b8": "bool_",
}


def _np_dtype(token: str):
    import numpy as np

    name = _DTYPE_OF.get(token)
    if name is None:
        raise ValueError(f"unknown dtype token {token!r}")
    return getattr(np, name)


def _materialize(spec, bindings: Dict[str, int], leaf_sharding=None):
    """spec-tree -> ShapeDtypeStruct pytree (statics resolve to their
    sample values). ``leaf_sharding(ArraySpec) -> jax sharding`` tags
    each array aval for partitioned lowering (the ktmesh probe)."""
    import jax

    if isinstance(spec, ArraySpec):
        if spec.optional:
            return None  # optional leaves are omitted from probes
        shape = tuple(bindings[d] for d in spec.dims)
        if leaf_sharding is not None:
            return jax.ShapeDtypeStruct(
                shape, _np_dtype(spec.dtype), sharding=leaf_sharding(spec)
            )
        return jax.ShapeDtypeStruct(shape, _np_dtype(spec.dtype))
    if isinstance(spec, dict):
        out = {}
        for k in sorted(spec):
            v = _materialize(spec[k], bindings, leaf_sharding)
            if v is not None:
                out[k] = v
        return out
    if isinstance(spec, DimRef):
        return bindings[spec.symbol]
    if isinstance(spec, Static):
        return spec.value() if callable(spec.value) else spec.value
    raise ValueError(f"unknown spec node {spec!r}")


def abstract_args(
    contract: Contract, bindings: Dict[str, int]
) -> Tuple[tuple, dict]:
    """(args, kwargs) of ShapeDtypeStructs + statics for one lattice
    point — what the checker feeds eval_shape / trace."""
    args = tuple(
        _materialize(spec, bindings) for _, spec in contract.args
    )
    kwargs = {
        name: _materialize(spec, bindings)
        for name, spec in contract.kwargs
    }
    return args, kwargs


def expected_results(contract: Contract, bindings: Dict[str, int]):
    """The declared result pytree materialized at one lattice point."""

    def mat(spec):
        if isinstance(spec, ArraySpec):
            return _materialize(spec, bindings)
        if isinstance(spec, dict):
            out = {}
            for k in sorted(spec):
                v = mat(spec[k])
                if v is not None:
                    out[k] = v
            return out
        return tuple(mat(s) for s in spec)

    return mat(contract.results)


# -- mesh shardings + collective inventories (ktmesh's substrate) ------


def partition_spec(
    leaf: ArraySpec, sharding: MeshSharding
) -> Tuple[Optional[str], ...]:
    """One array leaf's symbolic PartitionSpec under the contract's
    sharding: the sharded dim carries the mesh axis name, everything
    else replicates. ``('nodes', None)`` for an (N, S) leaf sharded
    over dim 'N' on axis 'nodes'."""
    return tuple(
        sharding.axis if d == sharding.dim else None for d in leaf.dims
    )


def partition_specs(contract: Contract) -> Dict[str, object]:
    """The whole contract's symbolic PartitionSpecs, arguments and
    results — the declarative sharding surface tests and docs quote.
    Array leaves map to axis tuples, statics/DimRefs to None."""
    sh = contract.sharding
    if sh is None:
        raise ValueError(f"{contract.kernel}: no sharding leaf declared")

    def mat(spec):
        if isinstance(spec, ArraySpec):
            return partition_spec(spec, sh)
        if isinstance(spec, dict):
            return {k: mat(spec[k]) for k in sorted(spec)}
        if isinstance(spec, (Static, DimRef)):
            return None
        return tuple(mat(s) for s in spec)

    return {
        "args": {name: mat(spec) for name, spec in contract.args},
        "results": mat(contract.results),
    }


def sharded_abstract_args(
    contract: Contract, bindings: Dict[str, int], mesh
) -> Tuple[tuple, dict]:
    """:func:`abstract_args` with every array aval tagged with the
    NamedSharding its symbolic PartitionSpec implies on `mesh`, and
    the sharding leaf's lower_overrides applied to the kwargs — the
    exact input ktmesh partitioned-lowers (and the runtime cross-check
    in tests/test_multichip.py re-lowers)."""
    import jax  # noqa: F401  (NamedSharding needs an initialized jax)
    from jax.sharding import NamedSharding, PartitionSpec

    sh = contract.sharding
    if sh is None:
        raise ValueError(f"{contract.kernel}: no sharding leaf declared")

    def leaf_sharding(spec: ArraySpec):
        return NamedSharding(mesh, PartitionSpec(*partition_spec(spec, sh)))

    args = tuple(
        _materialize(spec, bindings, leaf_sharding)
        for _, spec in contract.args
    )
    kwargs = {
        name: _materialize(spec, bindings, leaf_sharding)
        for name, spec in contract.kwargs
    }
    for name, value in sh.lower_overrides:
        kwargs[name] = value
    return args, kwargs


#: One partitioned-HLO collective op: its result type (one array, or
#: a tuple of arrays when XLA's combiner fused several collectives of
#: one kind into a single op) and its kind. Matched per line so the
#: all-gather `dimensions={d}` attribute (the gathered dim — what the
#: pod-axis full-gather check needs) can be read off the same line.
_COLLECTIVE_LINE_RE = re.compile(
    r"= (?P<result>\([^()]*\)|[a-z]+\d*\[[\d,]*\]\S*) "
    r"(?P<kind>all-gather|all-reduce|reduce-scatter|collective-permute"
    r"|all-to-all)\("
)
_ARRAY_TYPE_RE = re.compile(r"(?P<dtype>[a-z]+\d*)\[(?P<dims>[\d,]*)\]")
_GATHER_DIM_RE = re.compile(r"dimensions=\{(\d+)\}")

_HLO_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8": 1, "s8": 1, "u8": 1, "pred": 1,
}


def collective_inventory(hlo_text: str) -> Dict[str, object]:
    """Walk one compiled/partitioned HLO module's text for collective
    ops. Returns {"counts": {kind: n}, "bytes": {kind: result bytes},
    "total": n, "ops": [per-op dicts]} — each op carries kind, dtype,
    shape, bytes, and (all-gather/all-to-all) the gathered dim index.
    Pure regex over ``Compiled.as_text()``: no jax import, so the
    ledger's harvest thread and ktmesh share THIS implementation
    without the control plane loading anything."""
    counts: Dict[str, int] = {}
    byte_volume: Dict[str, int] = {}
    ops: List[Dict[str, object]] = []
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_LINE_RE.search(line)
        if m is None:
            continue
        kind = m.group("kind")
        gm = _GATHER_DIM_RE.search(line)
        # A combined (tuple-result) collective counts once per element:
        # the inventory names the logical collectives the partitioner
        # inserted, whether or not the combiner fused them into one op.
        for t in _ARRAY_TYPE_RE.finditer(m.group("result")):
            dims = tuple(int(d) for d in t.group("dims").split(",") if d)
            width = _HLO_DTYPE_BYTES.get(t.group("dtype"), 4)
            n_elem = 1
            for d in dims:
                n_elem *= d
            counts[kind] = counts.get(kind, 0) + 1
            byte_volume[kind] = byte_volume.get(kind, 0) + n_elem * width
            op: Dict[str, object] = {
                "kind": kind,
                "dtype": t.group("dtype"),
                "shape": list(dims),
                "bytes": n_elem * width,
            }
            if kind in ("all-gather", "all-to-all") and gm is not None:
                op["gather_dim"] = int(gm.group(1))
            ops.append(op)
    return {
        "counts": counts,
        "bytes": byte_volume,
        "total": sum(counts.values()),
        "ops": ops,
    }


def comm_verdict(kernel: str, counts: Dict[str, int]) -> str:
    """The COMM column for one ledger shape row: the collective KINDS
    a runtime compile emitted, joined against the declared budget.
    Lenient on counts — runtime buckets differ from the pinned probe
    point, and ktmesh owns the exact-count gate there — but strict on
    kinds: a collective kind outside the declared budget is sharding
    drift no matter the shape. Single-device compiles have empty
    inventories and are trivially 'ok'."""
    contract = CONTRACTS.get(kernel)
    if contract is None or contract.sharding is None:
        return "uncontracted"
    if not counts:
        return "ok"
    declared = set(contract.sharding.budget.as_dict())
    extra = sorted(set(counts) - declared)
    if extra:
        return f"drift: undeclared {','.join(extra)}"
    return "ok"


def resolve_kernel(key: str):
    """The live TracedJit object for one registry key (imports the ops
    module; the preemption kernel builds lazily through its factory)."""
    import importlib

    mod_name, _, path = key.partition(".")
    mod = importlib.import_module(f"kubernetes_tpu.ops.{mod_name}")
    if key == "preemption._victim_prefix_kernel.kernel":
        return mod._victim_prefix_kernel()
    obj = mod
    for part in path.split("."):
        obj = getattr(obj, part)
    return obj


# -- observed-signature matching ---------------------------------------


def declared_array_leaves(
    contract: Contract,
) -> List[Tuple[str, ArraySpec]]:
    """The contract's array leaves in jax flattening order — args in
    call order, dict schemas by sorted key, kwargs after args (the
    order :func:`shape_signature` emits). Optional leaves keep their
    slot and may be skipped by the matcher."""
    out: List[Tuple[str, ArraySpec]] = []

    def scan(name, spec):
        if isinstance(spec, ArraySpec):
            out.append((name, spec))
        elif isinstance(spec, dict):
            for k in sorted(spec):
                scan(f"{name}.{k}", spec[k])

    for name, spec in contract.args:
        scan(name, spec)
    for name in sorted(dict(contract.kwargs)):
        scan(name, dict(contract.kwargs)[name])
    return out


def _match_leaves(
    observed: Sequence[Tuple[str, Tuple[int, ...]]],
    declared: Sequence[Tuple[str, ArraySpec]],
    bindings: Dict[str, int],
) -> Optional[str]:
    """Unify observed array tokens against declared leaves (optional
    leaves may be absent). Returns an error string or None on success;
    `bindings` accumulates dim assignments."""
    if not declared:
        if observed:
            tok = observed[0]
            return f"unexpected extra array leaf {tok[0]}{list(tok[1])}"
        return None
    name, spec = declared[0]
    # Try consuming one observed token with this leaf.
    if observed:
        dtype, shape = observed[0]
        if dtype == spec.dtype and len(shape) == len(spec.dims):
            trial = dict(bindings)
            ok = True
            for sym, size in zip(spec.dims, shape):
                if trial.setdefault(sym, size) != size:
                    ok = False
                    break
            if ok:
                err = _match_leaves(observed[1:], declared[1:], trial)
                if err is None:
                    bindings.clear()
                    bindings.update(trial)
                    return None
        if not spec.optional:
            want = f"{spec.dtype}[{','.join(spec.dims)}]"
            return (
                f"leaf {name}: observed {dtype}{list(shape)}, "
                f"declared {want}"
            )
    elif not spec.optional:
        return f"leaf {name}: missing (declared {spec.dtype})"
    # Skip an optional leaf.
    return _match_leaves(observed, declared[1:], bindings)


def match_signature(kernel: str, signature: str) -> Tuple[bool, str]:
    """(ok, detail): does one observed staged-shape signature satisfy
    the kernel's contract — dtypes and dim symbols unify, and every
    bound dim sits on its declared bucket lattice?"""
    contract = CONTRACTS.get(kernel)
    if contract is None:
        return False, "no contract declared"
    observed = parse_signature(signature)
    declared = declared_array_leaves(contract)
    bindings: Dict[str, int] = {}
    err = _match_leaves(observed, declared, bindings)
    if err is not None:
        return False, err
    for sym, size in sorted(bindings.items()):
        if not dim_ok(sym, size):
            desc = DIM_LATTICES.get(sym, ("?", None))[0]
            return False, (
                f"dim {sym}={size} is off its bucket lattice ({desc})"
            )
    return True, ",".join(f"{s}={v}" for s, v in sorted(bindings.items()))


def contract_verdict(kernel: str, signature: str) -> str:
    """The CONTRACT column for one ledger shape row: 'ok' when the
    observed staged shapes unify with the declared contract on-lattice,
    else 'mismatch: ...' (or 'uncontracted' for a kernel outside the
    registry)."""
    if kernel not in CONTRACTS:
        return "uncontracted"
    ok, detail = match_signature(kernel, signature)
    return "ok" if ok else f"mismatch: {detail}"


def registry_keys() -> List[str]:
    """Sorted ORACLE_TWINS keys (the completeness yardstick)."""
    return sorted(ORACLE_TWINS)
