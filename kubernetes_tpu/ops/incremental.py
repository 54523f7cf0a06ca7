"""Incremental solver session: device-resident cluster state + churn.

BASELINE.md config 5 (50k-pod churn replay at 1k pods/s) cannot afford
re-lowering and re-uploading the full pod x node problem every tick.
This session keeps the NODE state (occupancy, bitsets, service counts
— the big, long-lived half of the problem) resident on the
accelerator:

- solve() feeds the pending backlog through solve_with_state, whose
  DONATED node carry becomes the next tick's device state — bindings
  commit on device with zero host round-trip of node columns;
- pod deletions touch one node row each: the host mirror recomputes
  that row (greedy-fit order, reference MapPodsToMachines semantics)
  and a jitted scatter patches just those rows on device;
- where the pallas kernel takes the ticks (one TPU, shapes it fits),
  the service counts live on the device in the kernel's own layout,
  (S_pad, N) int16 (ops/pallas_scan.py ``to_bands``), so a tick moves
  only the bands it touches and a delete writes only the (Service,
  node) entries it changed; the host mirror keeps the XLA scan's
  (N, S) f32 schema, converted at the one full upload;
- the flight recorder's verdict tables (``explain``) read the host
  mirror: the node rows and the Service columns the explained pods
  read, never a re-lowering of the cluster's bound pods;
- pending pods are transient per tick and upload as small bucketed
  arrays (bucket sizes limit XLA recompiles; SURVEY.md hard part (d)).

Vocabularies (labels / hostPorts / volumes) and the service set are
frozen at session start with headroom; overflow raises RebuildRequired
and the owner builds a fresh session (cheap resync — the host store
stays the source of truth, SURVEY.md §5 checkpoint model).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.models.columnar import (
    MIB,
    ServiceMatcher,
    Vocab,
    bitset,
    mem_to_mib_ceil,
    node_is_ready,
    pod_host_ports,
    pod_key,
    pod_resource_limits,
    pod_volumes,
)
from kubernetes_tpu.models.objects import (
    REBALANCE_DEST_ANNOTATION,
    RESOURCE_CPU,
    RESOURCE_MEMORY,
    RESOURCE_PODS,
    Node,
    Pod,
    Service,
)
from kubernetes_tpu.ops import pallas_scan
from kubernetes_tpu.ops.ledger import traced_jit
from kubernetes_tpu.ops.matrices import SVC_K
from kubernetes_tpu.ops.solver import DEFAULT_WEIGHTS, solve_with_state
from kubernetes_tpu.utils import metrics

#: Device bytes of the newest session's service-count carry.
CARRY_BYTES = metrics.DEFAULT.gauge(
    "scheduler_spreading_carry_bytes",
    "Device bytes of the incremental session's service-count carry.",
)


class RebuildRequired(Exception):
    """Capacity (vocab words / node slots / services) exhausted — build
    a fresh session from the authoritative host store."""


from kubernetes_tpu.ops.matrices import pow2_bucket as _bucket  # noqa: E402


@traced_jit(donate_argnames=("nodes",))
def _scatter_rows(nodes: Dict[str, jnp.ndarray], idx: jnp.ndarray, rows: Dict):
    return {k: nodes[k].at[idx].set(rows[k]) for k in nodes}


@dataclass
class SessionGang:
    """One PodGroup's stake in a session tick (ops-layer mirror of
    scheduler.gang.GangGroup, keyed by pod keys instead of backlog
    indices — the session addresses pods by key)."""

    key: str  # "namespace/name"
    min_member: int
    bound: int  # members already bound before this tick
    pod_keys: frozenset  # this tick's pending members


class PendingSolve:
    """One in-flight session tick: the jitted solve has been DISPATCHED
    (async — no host sync) and the assignment's device->host copy is
    already streaming (`copy_to_host_async`). ``result()`` blocks on
    the readback, applies the host-mirror commits, and returns the
    same ``[(pod_key, node_name | None)]`` list ``solve()`` does.

    The overlap contract: between dispatch and ``result()`` the owner
    may freely ``add_pending`` (next tick's staging), apply node/pod
    deltas (``upsert_node``/``delete_assigned``/``add_assigned`` — row
    recomputes miss the in-flight placements, but ``result()`` re-adds
    them incrementally, so recompute-then-apply converges to the same
    rows), and do arbitrary host work (bind commits, HTTP). Only the
    next dirty-row flush / solve dispatch requires the tick to finish
    first — ``solve_async`` resolves any outstanding handle itself."""

    __slots__ = (
        "_session", "pending", "assignment", "tele",
        "dispatch_s", "block_s", "_done", "_result",
    )

    def __init__(self, session, pending, assignment, tele, dispatch_s):
        self._session = session
        self.pending = pending
        self.assignment = assignment
        self.tele = tele  # (waves, sinkhorn_iters, sinkhorn_residual)
        self.dispatch_s = dispatch_s
        self.block_s = 0.0  # host time spent blocked in result()
        self._done = assignment is None
        self._result: List[Tuple[str, Optional[str]]] = []

    @property
    def keys(self) -> List[str]:
        """Pod keys of the in-flight tick (placement unknown until
        result()): owners use these to avoid re-staging a pod whose
        first solve has not landed yet."""
        return [lp.key for lp in self.pending]

    def done(self) -> bool:
        return self._done

    def result(self) -> List[Tuple[str, Optional[str]]]:
        if not self._done:
            self._session._finish_solve(self)
        return self._result


@dataclass
class _LoweredPod:
    """Host-side lowered pod row (everything solve() needs)."""

    key: str
    cpu: float
    mem_mib: float
    zero_req: bool
    sel_ids: List[int]
    port_ids: List[int]
    vol_any_ids: List[int]
    vol_rw_ids: List[int]
    # Pinned NODE NAME ("" = unpinned): resolved to a slot index at
    # solve() time — slot indices are recycled across node churn, so an
    # index resolved at add time could point at a different node.
    pinned_name: str
    svc: int
    # Top-SVC_K matching service ids — the exact set the device commit
    # scatters (solver._commit). Host mirrors MUST use this truncated
    # set, not the dense membership row: a pod matching > SVC_K
    # services would otherwise diverge host vs device (advisor r1).
    svc_topk: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    # Soft pin (rebalance nomination, not spec.nodeName): an unknown
    # destination resolves to UNPINNED (-1) instead of infeasible (-2)
    # — a dest node that vanished mid-move must not strand the pod.
    pin_soft: bool = False


class SolverSession:
    """Long-lived incremental scheduling session over one cluster."""

    def __init__(
        self,
        nodes: Sequence[Node],
        services: Sequence[Service] = (),
        assigned: Sequence[Pod] = (),
        label_words: int = 4,
        port_words: int = 4,
        vol_words: int = 4,
        node_capacity: int = 0,
        weights: Tuple[int, int, int] = DEFAULT_WEIGHTS,
        mesh=None,
        mode: str = "scan",
        pod_bucket: int = 0,
    ):
        nodes = list(nodes)
        self.services = list(services)
        self.weights = tuple(weights)
        self.mesh = mesh
        # Tick solver: "scan" replays the sequential-parity policy;
        # "wave"/"sinkhorn" batch each tick's backlog (same windowed
        # commit machinery as the batch modes — ops.wave/ops.sinkhorn).
        if mode not in ("scan", "wave", "sinkhorn"):
            raise ValueError(f"unknown session mode {mode!r}")
        self.mode = mode
        # pod_bucket > 0 pads every tick's pending upload to AT LEAST
        # this bucket: ONE compiled executable instead of one per
        # power-of-2 batch size. Long-lived daemons under churn want
        # this — a fresh pow2 bucket mid-workload stalls the tick for
        # a full XLA compile (minutes on CPU hosts).
        self.pod_bucket = pod_bucket
        self.LW, self.PW, self.VW = label_words, port_words, vol_words
        self.S = max(1, len(self.services))
        self._matcher = ServiceMatcher(self.services)
        self.N_cap = _bucket(max(node_capacity, len(nodes), 1))
        self.label_vocab, self.port_vocab, self.vol_vocab = Vocab(), Vocab(), Vocab()

        self.node_names: List[Optional[str]] = [None] * self.N_cap
        self.node_index: Dict[str, int] = {}
        # Assigned pods per node slot, in arrival order (greedy-fit
        # recompute on delete follows this order, as the reference's
        # MapPodsToMachines list order does).
        self._assigned: List[List[_LoweredPod]] = [[] for _ in range(self.N_cap)]
        self._pod_node: Dict[str, int] = {}
        self._node_specs: List[Optional[Node]] = [None] * self.N_cap

        self.h = self._empty_node_columns()
        for node in nodes:
            self._admit_node(node)
        for pod in assigned:
            lp = self._lower_pod(pod)
            j = self.node_index.get(pod.spec.node_name)
            if j is None:
                continue
            self._assigned[j].append(lp)
            self._pod_node[lp.key] = j
        for j in range(self.N_cap):
            if self.node_names[j] is not None:
                self._recompute_node_row(j)

        self._pending: List[_LoweredPod] = []
        # Which kernel runs the scan ticks decides the carry's layout:
        # banded (the pallas kernel's) when it takes them.
        self.kernel_reason = ""
        if mode == "scan":
            self.kernel_reason = pallas_scan.session_reason(
                mesh, self.N_cap, self.LW, self.PW, self.VW
            )
        self.banded = mode == "scan" and not self.kernel_reason
        self.dev = self._upload_all()
        CARRY_BYTES.set(self.dev["svc_counts"].nbytes)
        self._dirty: set = set()
        # (Service, node slot) count entries the dirty rows changed:
        # what a banded carry's delete patch writes.
        self._dirty_counts: set = set()
        # Convergence telemetry of the most recent solve() tick — the
        # incremental daemon folds this into its SolveRecord.
        self.last_stats: Dict[str, float] = {}
        # Pipelined dispatch state: the (at most one) in-flight tick,
        # plus double-buffered host staging arrays — tick k+1's pod
        # staging must never overwrite buffers whose device transfer
        # for tick k may still be draining (device_put is async).
        self._inflight: Optional[PendingSolve] = None
        self._stage_bufs: Tuple[Dict, Dict] = ({}, {})
        self._stage_flip = 0

    # -- lowering -----------------------------------------------------

    def _vocab_id(self, vocab: Vocab, words: int, token: str) -> int:
        i = vocab.id(token)
        if i >= words * 32:
            raise RebuildRequired(f"vocab overflow: {token!r}")
        return i

    def _lower_pod(self, pod: Pod) -> _LoweredPod:
        cpu, mem = pod_resource_limits(pod)
        sel_ids = [
            self._vocab_id(self.label_vocab, self.LW, f"{k}={v}")
            for k, v in sorted((pod.spec.node_selector or {}).items())
        ]
        port_ids = [
            self._vocab_id(self.port_vocab, self.PW, str(p))
            for p in pod_host_ports(pod)
        ]
        vols = pod_volumes(pod)
        vol_any = [self._vocab_id(self.vol_vocab, self.VW, v) for v, _ in vols]
        vol_rw = [self._vocab_id(self.vol_vocab, self.VW, v) for v, rw in vols if rw]
        ids, first = self._matcher.membership_ids(pod)
        # Rebalance nomination: mirror models/columnar.py — a pod the
        # descheduler recreated after a defrag eviction carries its
        # planned destination as an annotation; honor it as a soft
        # HostName pin so the incremental daemon rebinds it there
        # (without this, the solver happily re-packs the mover onto
        # the very node the defrag cycle just drained).
        pinned_name = pod.spec.node_name or ""
        pin_soft = False
        if not pinned_name:
            pinned_name = (pod.metadata.annotations or {}).get(
                REBALANCE_DEST_ANNOTATION, ""
            )
            pin_soft = bool(pinned_name)
        return _LoweredPod(
            svc_topk=ids[:SVC_K],
            key=pod_key(pod),
            cpu=float(cpu),
            mem_mib=float(mem_to_mib_ceil(mem)),
            zero_req=(cpu == 0 and mem == 0),
            sel_ids=sel_ids,
            port_ids=port_ids,
            vol_any_ids=vol_any,
            vol_rw_ids=vol_rw,
            pinned_name=pinned_name,
            pin_soft=pin_soft,
            svc=first,
        )

    # -- node columns (host mirror) -----------------------------------

    def _empty_node_columns(self) -> Dict[str, np.ndarray]:
        N = self.N_cap
        return {
            "cpu_cap": np.zeros(N, np.float32),
            "mem_cap": np.zeros(N, np.float32),
            "pods_cap": np.zeros(N, np.float32),
            "cpu_fit": np.zeros(N, np.float32),
            "mem_fit": np.zeros(N, np.float32),
            "over": np.zeros(N, bool),
            "cpu_used": np.zeros(N, np.float32),
            "mem_used": np.zeros(N, np.float32),
            "pods_used": np.zeros(N, np.float32),
            "labels": np.zeros((N, self.LW), np.uint32),
            "uport": np.zeros((N, self.PW), np.uint32),
            "uvol_any": np.zeros((N, self.VW), np.uint32),
            "uvol_rw": np.zeros((N, self.VW), np.uint32),
            "svc_counts": np.zeros((N, self.S), np.float32),
            "sched": np.zeros(N, bool),
        }

    def _admit_node(self, node: Node) -> int:
        name = node.metadata.name
        j = self.node_index.get(name)
        if j is None:
            try:
                j = self.node_names.index(None)
            except ValueError:
                raise RebuildRequired("node slots exhausted")
            self.node_names[j] = name
            self.node_index[name] = j
        self._node_specs[j] = node
        return j

    def _recompute_node_row(
        self, j: int, h: Optional[Dict] = None, skip=frozenset()
    ) -> None:
        """Rebuild slot j's full row from spec + assigned pods (the
        only non-monotonic operation: deletes can't be expressed as
        bitset increments). ``h`` is the host mirror unless given (the
        explain view passes copies of some columns); pods in ``skip``
        are left out."""
        node = self._node_specs[j]
        h = self.h if h is None else h
        for k in h:
            h[k][j] = 0
        if node is None:
            return
        cap = node.status.capacity or {}
        if RESOURCE_CPU in cap:
            h["cpu_cap"][j] = cap[RESOURCE_CPU].milli_value()
        if RESOURCE_MEMORY in cap:
            h["mem_cap"][j] = cap[RESOURCE_MEMORY].value() // MIB
        if RESOURCE_PODS in cap:
            h["pods_cap"][j] = cap[RESOURCE_PODS].value()
        h["labels"][j] = bitset(
            [
                self._vocab_id(self.label_vocab, self.LW, f"{k}={v}")
                for k, v in (node.metadata.labels or {}).items()
            ],
            self.LW,
        )
        h["sched"][j] = node_is_ready(node)
        for lp in self._assigned[j]:
            if lp.key in skip:
                continue
            # Greedy-fit order = arrival order (reference semantics).
            fits_cpu = h["cpu_cap"][j] == 0 or (
                h["cpu_fit"][j] + lp.cpu <= h["cpu_cap"][j]
            )
            fits_mem = h["mem_cap"][j] == 0 or (
                h["mem_fit"][j] + lp.mem_mib <= h["mem_cap"][j]
            )
            if fits_cpu and fits_mem:
                h["cpu_fit"][j] += lp.cpu
                h["mem_fit"][j] += lp.mem_mib
            else:
                h["over"][j] = True
            h["cpu_used"][j] += lp.cpu
            h["mem_used"][j] += lp.mem_mib
            h["pods_used"][j] += 1
            h["uport"][j] |= bitset(lp.port_ids, self.PW)
            h["uvol_any"][j] |= bitset(lp.vol_any_ids, self.VW)
            h["uvol_rw"][j] |= bitset(lp.vol_rw_ids, self.VW)
            if len(lp.svc_topk) and "svc_counts" in h:
                h["svc_counts"][j, lp.svc_topk] += 1.0

    def _charge_changed(self, j: int, lp: _LoweredPod) -> None:
        """Pod ``lp`` joined or left slot j: its row and its Service
        count entries there are dirty."""
        self._dirty.add(j)
        self._dirty_counts.update((int(s), j) for s in lp.svc_topk)

    def _apply_commit_host(self, j: int, lp: _LoweredPod) -> None:
        """Mirror of solver._commit — keeps host state bit-identical to
        the device carry for nodes untouched by deletes."""
        h = self.h
        h["cpu_fit"][j] += lp.cpu
        h["mem_fit"][j] += lp.mem_mib
        h["cpu_used"][j] += lp.cpu
        h["mem_used"][j] += lp.mem_mib
        h["pods_used"][j] += 1
        h["uport"][j] |= bitset(lp.port_ids, self.PW)
        h["uvol_any"][j] |= bitset(lp.vol_any_ids, self.VW)
        h["uvol_rw"][j] |= bitset(lp.vol_rw_ids, self.VW)
        if len(lp.svc_topk):
            h["svc_counts"][j, lp.svc_topk] += 1.0

    # -- device transfer ----------------------------------------------

    def _row_keys(self) -> List[str]:
        """The leaves the dirty-row scatter patches by row: all of them,
        but a banded carry's service counts (patched by entry)."""
        return [k for k in self.h if not (self.banded and k == "svc_counts")]

    def _upload_all(self) -> Dict[str, jnp.ndarray]:
        from kubernetes_tpu.utils import sli

        cols = self.h
        if self.banded:
            cols = dict(cols, svc_counts=pallas_scan.to_bands(cols["svc_counts"]))
        sli.note_transfer("h2d", sli.nbytes_of(cols))
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as PS

            sharding = NamedSharding(self.mesh, PS("nodes"))
            return {k: jax.device_put(v, sharding) for k, v in cols.items()}
        return {k: jnp.asarray(v) for k, v in cols.items()}

    def _flush_dirty(self) -> None:
        if not self._dirty:
            return
        from kubernetes_tpu.utils import sli, tracing

        idx = sorted(self._dirty)
        points = sorted(self._dirty_counts)
        self._dirty.clear()
        self._dirty_counts.clear()
        with tracing.phase("scatter", rows=len(idx)):
            # Bucket the scatter width: pad by repeating the last index
            # (identical row, harmless duplicate) so recompiles are rare.
            width = _bucket(len(idx), minimum=8)
            padded = idx + [idx[-1]] * (width - len(idx))
            keys = self._row_keys()
            rows = {k: self.h[k][padded] for k in keys}
            sli.note_transfer("h2d", sli.nbytes_of(rows))
            dev = _scatter_rows(
                {k: self.dev[k] for k in keys},
                jnp.asarray(padded, dtype=jnp.int32), rows,
            )
            if self.banded:
                counts = self.dev["svc_counts"]
                if points:
                    staged = pallas_scan.count_points(
                        self.h["svc_counts"], points,
                        _bucket(len(points), minimum=8),
                    )
                    sli.note_transfer("h2d", sli.nbytes_of(staged))
                    counts = pallas_scan.scatter_rows_banded(counts, *staged)
                dev["svc_counts"] = counts
            self.dev = dev

    # -- public API ---------------------------------------------------

    def add_pending(self, pod: Pod) -> None:
        self._pending.append(self._lower_pod(pod))

    def pending_count(self) -> int:
        return len(self._pending)

    def upsert_node(self, node: Node) -> None:
        j = self._admit_node(node)
        self._recompute_node_row(j)
        self._dirty.add(j)

    def remove_node(self, name: str) -> None:
        j = self.node_index.pop(name, None)
        if j is None:
            return
        self.node_names[j] = None
        self._node_specs[j] = None
        for lp in self._assigned[j]:
            self._pod_node.pop(lp.key, None)
            self._charge_changed(j, lp)
        self._assigned[j] = []
        self._recompute_node_row(j)  # zeroes the row; sched stays False
        self._dirty.add(j)

    def add_assigned(self, pod: Pod) -> bool:
        """An already-bound pod appeared from outside this session
        (bound by another scheduler, a static pod, resync replay):
        charge its occupancy to its node's row. Greedy-fit replay via
        the full row recompute — foreign pods may overcommit, which
        _apply_commit_host (the mirror of a solver commit, which only
        places fitting pods) cannot express. Idempotent per pod key."""
        if not pod.spec.node_name:
            return False
        lp = self._lower_pod(pod)
        if lp.key in self._pod_node:
            return False
        j = self.node_index.get(pod.spec.node_name)
        if j is None:
            return False
        self._assigned[j].append(lp)
        self._pod_node[lp.key] = j
        self._recompute_node_row(j)
        self._charge_changed(j, lp)
        return True

    def has_assigned(self, key: str) -> bool:
        return key in self._pod_node

    def delete_assigned(self, key: str) -> bool:
        """A running pod vanished: free its occupancy (one node row)."""
        j = self._pod_node.pop(key, None)
        if j is None:
            return False
        for lp in self._assigned[j]:
            if lp.key == key:
                self._charge_changed(j, lp)
        self._assigned[j] = [lp for lp in self._assigned[j] if lp.key != key]
        self._recompute_node_row(j)
        return True

    def _dispatch(self, pods, carry):
        """Enqueue one tick's jitted solve for the session mode. Pure
        dispatch — JAX returns immediately; nothing here syncs the
        host. Returns (assignment, new_carry, (waves, iters, res)) with
        the telemetry entries still device scalars (or None)."""
        waves = s_iters = s_res = None
        if self.mode == "wave":
            from kubernetes_tpu.ops.wave import solve_waves_with_state

            assignment, carry, waves = solve_waves_with_state(
                pods, carry, self.weights
            )
        elif self.mode == "sinkhorn":
            from kubernetes_tpu.ops.sinkhorn import solve_sinkhorn_with_state

            assignment, carry, waves, s_iters, s_res = (
                solve_sinkhorn_with_state(pods, carry, self.weights)
            )
        elif self.banded:
            pallas_scan.count_solve("")
            assignment, carry = pallas_scan.solve_banded(
                pods, carry, self.weights
            )
        else:
            assignment, carry = solve_with_state(pods, carry, self.weights)
        return assignment, carry, (waves, s_iters, s_res)

    def solve_async(self) -> PendingSolve:
        """Pipelined tick: stage the pending backlog, dispatch the
        jitted solve, start the assignment's device->host copy, and
        return WITHOUT a blocking host sync. The returned handle's
        ``result()`` performs the readback and host-mirror commits;
        until then the caller overlaps the device time with the next
        tick's staging (``add_pending``), watch-delta application, and
        its own commit I/O. At most one tick is in flight: a second
        ``solve_async`` resolves the first before dispatching (the
        donated carry and the dirty-row flush both require it)."""
        from kubernetes_tpu.utils import tracing

        self._finish_inflight()
        pending, self._pending = self._pending, []
        if not pending:
            self._flush_dirty()
            return PendingSolve(self, [], None, (None, None, None), 0.0)
        t0 = time.monotonic()
        # Phase spans cover the session tick's segments: "upload" is
        # the dirty-row scatter plus staging this tick's pod arrays
        # onto the device, "solve" the async dispatch, "readback" the
        # blocking copy-out (which therefore absorbs the device time).
        # The "lower" phase is the per-pod _lower_pod work, observed at
        # the daemon's add_pending loop — NOT here, so each tick
        # contributes exactly one observation per phase.
        with tracing.phase(
            "upload", dirty=len(self._dirty), pods=len(pending)
        ):
            self._flush_dirty()
            pods = self._pod_arrays(pending)
        with tracing.phase("solve", mode=self.mode, incremental=True):
            assignment, self.dev, tele = self._dispatch(pods, self.dev)
            # Start the device->host copy NOW: it streams behind the
            # solve, so result() finds the bytes (mostly) local.
            if hasattr(assignment, "copy_to_host_async"):
                assignment.copy_to_host_async()
        handle = PendingSolve(
            self, pending, assignment, tele, time.monotonic() - t0
        )
        self._inflight = handle
        return handle

    def _finish_inflight(self) -> None:
        if self._inflight is not None:
            self._inflight.result()

    def _finish_solve(self, handle: PendingSolve) -> None:
        """Blocking half of a pipelined tick: copy the assignment out,
        record telemetry, and mirror the device commits into the host
        rows. Called (once) from PendingSolve.result()."""
        from kubernetes_tpu.utils import tracing

        if self._inflight is handle:
            self._inflight = None
        t0 = time.monotonic()
        waves, s_iters, s_res = handle.tele
        pending = handle.pending
        with tracing.phase("readback"):
            from kubernetes_tpu.utils import sli

            full = np.asarray(handle.assignment)
            sli.note_transfer("d2h", full.nbytes)
            picks = full[: len(pending)]
            # Telemetry scalars convert AFTER the assignment copy
            # blocked — no extra device sync on the tick path.
            self.last_stats = {}
            if waves is not None:
                self.last_stats["waves"] = int(waves)
            if s_iters is not None:
                from kubernetes_tpu.utils import flightrecorder

                self.last_stats["sinkhorn_iters"] = int(s_iters)
                self.last_stats["sinkhorn_residual"] = float(s_res)
                flightrecorder.observe_solve_telemetry(
                    "sinkhorn", int(s_iters), residual=float(s_res),
                    waves=int(waves),
                )
            elif waves is not None:
                from kubernetes_tpu.utils import flightrecorder

                flightrecorder.observe_solve_telemetry("wave", int(waves))
        out: List[Tuple[str, Optional[str]]] = []
        for lp, j in zip(pending, picks.tolist()):
            if j < 0 or j >= self.N_cap or self.node_names[j] is None:
                out.append((lp.key, None))
                continue
            self._assigned[j].append(lp)
            self._pod_node[lp.key] = j
            self._apply_commit_host(j, lp)
            out.append((lp.key, self.node_names[j]))
        handle.block_s = time.monotonic() - t0
        handle._result = out
        handle._done = True

    def solve(self) -> List[Tuple[str, Optional[str]]]:
        """Schedule the pending backlog against the device-resident
        cluster state; commits ride the donated carry. Returns
        [(pod_key, node_name | None)] and clears the backlog. The
        synchronous shape of solve_async() — dispatch + immediate
        readback."""
        return self.solve_async().result()

    def prewarm(
        self, max_pod_bucket: int = 0, max_scatter_width: int = 512,
        explain_pods: int = 0,
    ) -> int:
        """Compile every executable a live tick can hit — the solve at
        each pow2 pod bucket up to max_pod_bucket, the dirty-row
        scatter at each pow2 width (by row, and on a banded carry by
        count entry) and the ``explain`` readback at each pow2 bucket
        up to explain_pods — against THROWAWAY copies of the node
        state, so the process-global XLA cache is hot before the first
        real pod arrives (a fresh bucket mid-workload otherwise stalls
        that tick for a full compile: seconds on TPU, minutes on CPU
        hosts). Returns the number of warm dispatches issued."""
        compiled = 0
        bucket = max(_bucket(1), self.pod_bucket)
        top = max(bucket, _bucket(max_pod_bucket)) if max_pod_bucket else 0
        while bucket <= top:
            pods = self._stage_arrays([], bucket, reuse=False)
            # Throwaway carries are device copies of the live self.dev:
            # identical layout and sharding (mesh sessions included) —
            # a differently-placed warm carry would compile a cache
            # entry the real ticks never hit.
            carry = self._copy_dev()
            assignment, carry, _tele = self._dispatch(pods, carry)
            jax.block_until_ready(assignment)
            del carry
            compiled += 1
            bucket *= 2
        width = 8
        idx_max = max(
            (j for j, n in enumerate(self.node_names) if n is not None),
            default=0,
        )
        keys = self._row_keys()
        while width <= min(max_scatter_width, self.N_cap):
            idx = np.full(width, idx_max, np.int32)
            rows = {k: self.h[k][idx] for k in keys}
            carry = self._copy_dev(keys)
            out = _scatter_rows(carry, jnp.asarray(idx), rows)
            jax.block_until_ready(jax.tree_util.tree_leaves(out))
            compiled += 1
            if self.banded:
                staged = pallas_scan.count_points(
                    self.h["svc_counts"], [(0, int(idx_max))], width
                )
                counts = self._copy_dev(["svc_counts"])["svc_counts"]
                jax.block_until_ready(
                    pallas_scan.scatter_rows_banded(counts, *staged)
                )
                compiled += 1
            del carry, out
            width *= 2
        width = 8
        while width <= _bucket(explain_pods, minimum=8) and explain_pods:
            self.explain([], bucket=width)
            compiled += 1
            width *= 2
        return compiled

    def explain(
        self, pods: Sequence[Pod], release: Sequence[str] = (),
        bucket: int = 0,
    ):
        """Per-node verdicts for ``pods`` against the cluster as the
        host mirror holds it now, with the charges of the pods keyed
        ``release`` taken off their nodes (a bound pod's view before
        its own bind): ``ops.pipeline.explain_matrix``'s (node names,
        bits u32[P, N], components) over the live node slots, in slot
        order (the solve's tie-break order). One dispatch over the node
        rows and only the Service columns the pods read — never a
        re-lowering of the cluster's bound pods. Lowers through the
        session's vocabularies: the solve loop's thread only."""
        from dataclasses import replace

        from kubernetes_tpu.ops.solver import explain_rows

        lowered = [self._lower_pod(pod) for pod in pods]
        PP = max(_bucket(len(lowered), minimum=8), bucket)
        ids = sorted({lp.svc for lp in lowered if lp.svc >= 0})
        col = {s: c for c, s in enumerate(ids)}
        svc = np.zeros((self.N_cap, PP), np.float32)
        svc[:, : len(ids)] = self.h["svc_counts"][:, ids]
        view = {k: v for k, v in self.h.items() if k != "svc_counts"}
        gone = [
            (self._pod_node[k], k) for k in release if k in self._pod_node
        ]
        if gone:
            view = {k: v.copy() for k, v in view.items()}
            skip = {k for _, k in gone}
            for j in {j for j, _ in gone}:
                self._recompute_node_row(j, view, skip)
            for j, key in gone:
                for lp in self._assigned[j]:
                    if lp.key == key:
                        for s in lp.svc_topk.tolist():
                            if s in col:
                                svc[j, col[s]] -= 1.0
        view["svc_counts"] = svc
        staged = [
            replace(lp, svc=col.get(lp.svc, -1), svc_topk=lp.svc_topk[:0])
            for lp in lowered
        ]
        bits, lr, bra, spread = explain_rows(
            self._stage_arrays(staged, PP, reuse=False),
            {k: jnp.asarray(v) for k, v in view.items()},
        )
        live = [j for j, n in enumerate(self.node_names) if n is not None]
        P = len(lowered)
        return (
            [self.node_names[j] for j in live],
            np.asarray(bits)[:P][:, live],
            {
                "leastRequested": np.asarray(lr)[:P][:, live],
                "balanced": np.asarray(bra)[:P][:, live],
                "spreading": np.asarray(spread)[:P][:, live],
            },
        )

    def _copy_dev(self, keys=None) -> Dict[str, jnp.ndarray]:
        return {k: jnp.copy(self.dev[k]) for k in (keys or self.dev)}

    def solve_gang(
        self, gangs: Sequence[SessionGang]
    ) -> Tuple[List[Tuple[str, Optional[str]]], List[str]]:
        """solve() with group-level all-or-nothing acceptance, session-
        aware: a rejected group's tentative placements were already
        committed into the DONATED device carry by the tick's solve, so
        releasing them goes through delete_assigned — the host mirror
        recomputes the touched node rows and the next solve's dirty
        flush scatters them back onto the device. Each rejection round
        releases EVERY placement made this tick and re-solves the
        surviving backlog, so the freed capacity is usable immediately
        and the accepted-group set matches the batch paths' (same
        fixed-point loop as scheduler.gang.gang_solve). Acceptance
        counts run through the same masked segment reduction as the
        batch device path."""
        from kubernetes_tpu.ops.pipeline import gang_member_counts_device

        tick = list(self._pending)
        if not gangs:
            return self.solve(), []
        gangs = list(gangs)
        gi_of_key: Dict[str, int] = {}
        for gi, g in enumerate(gangs):
            for k in g.pod_keys:
                gi_of_key[k] = gi
        results: Dict[str, Optional[str]] = {}
        rejected: set = set()
        while True:
            for key, dest in self.solve():
                results[key] = dest
            placed = np.fromiter(
                (results.get(lp.key) is not None for lp in tick),
                bool, count=len(tick),
            )
            gids = np.fromiter(
                (gi_of_key.get(lp.key, -1) for lp in tick),
                np.int32, count=len(tick),
            )
            counts = gang_member_counts_device(placed, gids, len(gangs))
            newly = [
                gi
                for gi, g in enumerate(gangs)
                if gi not in rejected
                and int(counts[gi]) + g.bound < g.min_member
            ]
            if not newly:
                break
            rejected.update(newly)
            # Release the whole tick's tentative placements (device rows
            # restore via the dirty scatter) and re-solve the survivors
            # in original arrival order.
            for lp in tick:
                if results.get(lp.key) is not None:
                    self.delete_assigned(lp.key)
                results[lp.key] = None
            self._pending = [
                lp for lp in tick
                if gi_of_key.get(lp.key, -1) not in rejected
            ]
        return (
            [(lp.key, results.get(lp.key)) for lp in tick],
            [gangs[gi].key for gi in sorted(rejected)],
        )

    def _pod_arrays(self, pending: List[_LoweredPod]) -> Dict[str, jnp.ndarray]:
        PP = max(_bucket(len(pending)), self.pod_bucket)
        return self._stage_arrays(pending, PP)

    #: (key, pad value) layout of the staged pod columns; padding slots
    #: are pinned to -2 (never placeable).
    _STAGE_FILL = (
        ("cpu", 0), ("mem", 0), ("zero_req", 0), ("sel", 0), ("port", 0),
        ("vol_any", 0), ("vol_rw", 0), ("pinned", -2), ("svc", -1),
        ("svc_ids", -1),
    )

    def _stage_arrays(
        self, pending: List[_LoweredPod], PP: int, reuse: bool = True
    ) -> Dict[str, jnp.ndarray]:
        """Host staging buffers for one tick's pod upload. Buffers are
        DOUBLE-buffered per bucket size: device_put may still be
        draining tick k's transfer when tick k+1 stages, so k+1 always
        writes the other slot (at most one solve is in flight — two
        slots suffice). reuse=False (prewarm) allocates throwaway
        arrays instead."""
        arr = None
        if reuse:
            slot = self._stage_bufs[self._stage_flip]
            self._stage_flip ^= 1
            arr = slot.get(PP)
            if arr is not None:
                for key, fill in self._STAGE_FILL:
                    arr[key].fill(fill)
        if arr is None:
            arr = {
                "cpu": np.zeros(PP, np.float32),
                "mem": np.zeros(PP, np.float32),
                "zero_req": np.zeros(PP, bool),
                "sel": np.zeros((PP, self.LW), np.uint32),
                "port": np.zeros((PP, self.PW), np.uint32),
                "vol_any": np.zeros((PP, self.VW), np.uint32),
                "vol_rw": np.zeros((PP, self.VW), np.uint32),
                # Padding slots pinned to -2: never placeable.
                "pinned": np.full(PP, -2, np.int32),
                "svc": np.full(PP, -1, np.int32),
                "svc_ids": np.full((PP, SVC_K), -1, np.int32),
            }
            if reuse:
                slot[PP] = arr
        for i, lp in enumerate(pending):
            arr["cpu"][i] = lp.cpu
            arr["mem"][i] = lp.mem_mib
            arr["zero_req"][i] = lp.zero_req
            arr["sel"][i] = bitset(lp.sel_ids, self.LW)
            arr["port"][i] = bitset(lp.port_ids, self.PW)
            arr["vol_any"][i] = bitset(lp.vol_any_ids, self.VW)
            arr["vol_rw"][i] = bitset(lp.vol_rw_ids, self.VW)
            if lp.pinned_name:
                arr["pinned"][i] = self.node_index.get(
                    lp.pinned_name, -1 if lp.pin_soft else -2
                )
            else:
                arr["pinned"][i] = -1
            arr["svc"][i] = lp.svc
            arr["svc_ids"][i, : len(lp.svc_topk)] = lp.svc_topk
        from kubernetes_tpu.utils import sli

        sli.note_transfer("h2d", sli.nbytes_of(arr))
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as PS

            repl = NamedSharding(self.mesh, PS())
            return {k: jax.device_put(v, repl) for k, v in arr.items()}
        return {k: jnp.asarray(v) for k, v in arr.items()}
