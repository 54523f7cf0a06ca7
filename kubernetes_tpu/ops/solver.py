"""The TPU assignment solver.

Replicates the reference's sequential greedy semantics — pod k's
placement affects pod k+1's feasibility and scores — as a jitted
lax.scan whose carry is the cluster occupancy state. Each scan step
evaluates the full default predicate/priority pipeline for ONE pod
against ALL nodes as vector ops:

  predicates (masks):           reference
    resources + pod count       PodFitsResources  predicates.go:139-156
    nodeSelector subset         MatchNodeSelector predicates.go:184-190
    hostPort conflicts          PodFitsPorts      predicates.go:337-349
    exclusive volumes           NoDiskConflict    predicates.go:85-95
    pinned host                 HostName          predicates.go:192-197
  priorities (scores, exact integer math):
    LeastRequested              priorities.go:31-95 (int32 division)
    BalancedResourceAllocation  priorities.go:146-205 (f32 fractions)
    ServiceSpreading            spreading.go:38-87 (f32, like Go's float32)

Score-tie selection is "lowest node index", matching the scalar
oracle's deterministic tie-break (generic.py select_host).

All node-axis tensors may be sharded over a Mesh axis; XLA SPMD then
turns the per-step argmax into a sharded reduce + tiny all-reduce over
ICI, and the occupancy updates stay local to the owning shard.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.models.algspec import DEFAULT_LOWERED, LoweredSpec
from kubernetes_tpu.ops.ledger import traced_jit
from kubernetes_tpu.ops.matrices import DeviceSnapshot

# Weighted-sum weights for the default provider (defaults.go:51-60):
# LeastRequested=1, BalancedResourceAllocation=1, ServiceSpreading=1.
DEFAULT_WEIGHTS = (1, 1, 1)


def _pred_resources(pod: Dict, nodes: Dict) -> jnp.ndarray:
    """PodFitsResources (predicates.go:139-156) as bool[N]."""
    cpu_cap, mem_cap = nodes["cpu_cap"], nodes["mem_cap"]
    fits_cpu = (cpu_cap == 0) | (nodes["cpu_fit"] + pod["cpu"] <= cpu_cap)
    fits_mem = (mem_cap == 0) | (nodes["mem_fit"] + pod["mem"] <= mem_cap)
    fits_count = nodes["pods_used"] + 1 <= nodes["pods_cap"]
    nonzero_ok = (~nodes["over"]) & fits_cpu & fits_mem & fits_count
    # Zero-request pods only check pod-count headroom (predicates.go:146).
    zero_ok = nodes["pods_used"] < nodes["pods_cap"]
    return jnp.where(pod["zero_req"], zero_ok, nonzero_ok)


def _pred_selector(pod: Dict, nodes: Dict) -> jnp.ndarray:
    """MatchNodeSelector: selector bits must be a subset of labels."""
    sel = pod["sel"][None, :]
    return jnp.all((sel & nodes["labels"]) == sel, axis=1)


def _pred_ports(pod: Dict, nodes: Dict) -> jnp.ndarray:
    """PodFitsPorts."""
    return ~jnp.any(pod["port"][None, :] & nodes["uport"], axis=1)


def _pred_disk(pod: Dict, nodes: Dict) -> jnp.ndarray:
    """NoDiskConflict: conflict when either side holds it read-write."""
    return ~jnp.any(
        (pod["vol_rw"][None, :] & nodes["uvol_any"])
        | (pod["vol_any"][None, :] & nodes["uvol_rw"]),
        axis=1,
    )


def _pred_hostname(pod: Dict, N: int) -> jnp.ndarray:
    """HostName."""
    idx = jnp.arange(N, dtype=jnp.int32)
    return (pod["pinned"] == -1) | (idx == pod["pinned"])


def _feasible(
    pod: Dict, nodes: Dict, N: int, ls: LoweredSpec = DEFAULT_LOWERED
) -> jnp.ndarray:
    """The configured predicates as one bool[N] mask (defaults when no
    policy is lowered — each term is gated by the static LoweredSpec,
    so a policy that omits a predicate omits its ops entirely). The
    per-predicate helpers above are the single implementation shared
    with the explain readback (explain_rows): the decision and its
    explanation can never drift."""
    ok = nodes["sched"]
    if ls.resources:
        ok = ok & _pred_resources(pod, nodes)
    if ls.selector:
        ok = ok & _pred_selector(pod, nodes)
    if ls.ports:
        ok = ok & _pred_ports(pod, nodes)
    if ls.disk:
        ok = ok & _pred_disk(pod, nodes)
    if ls.hostname:
        ok = ok & _pred_hostname(pod, N)
    if ls.node_label:
        # -- CheckNodeLabelPresence: static node mask (predicates.go:226) --
        ok = ok & nodes["policy_ok"]
    if ls.service_affinity:
        # -- CheckServiceAffinity (predicates.go:268-335) --
        # Per affinity label k the pod needs "l_k = v" where v is its
        # own pinned nodeSelector value, else the value on the node
        # hosting the first service peer (the anchor); no requirement
        # when neither exists. A peer on an unknown node is the
        # scalar's GetNodeInfo error: the pod fits nowhere.
        pin = pod["aff_pin"]  # i32[K]
        s = pod["svc"]
        scratch = nodes["anchor"].shape[0] - 1
        slot = jnp.where(s >= 0, s, scratch)
        anchor = nodes["anchor"][slot]
        peers = nodes["svc_total"][slot] > 0
        consults = jnp.any(pin < 0) & (s >= 0) & peers
        anchor_err = consults & (anchor == -2)
        anchor_ok = consults & (anchor >= 0)
        a_vid = jnp.where(
            anchor_ok, nodes["aff_vid"][jnp.maximum(anchor, 0)], -1
        )  # i32[K]
        need = jnp.where(pin >= 0, pin, a_vid)
        ok = ok & jnp.all(
            (need[None, :] < 0) | (nodes["aff_vid"] == need[None, :]), axis=1
        )
        ok = ok & ~anchor_err
    return ok


def _component_scores(
    pod: Dict, nodes: Dict
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The three default priority columns — (LeastRequested,
    BalancedResourceAllocation, ServiceSpreading) as separate int32[N]
    vectors. _scores sums them weighted; the explain readback
    (explain_rows) reports them decomposed. One implementation, so the
    published breakdown can never drift from the decision.

    Integer score math in int32: columns are integer-valued f32 with
    magnitudes < 2^24, so the cast is exact and the Go int64 division
    semantics (truncation of nonnegative quotients) are reproduced
    without float rounding hazards."""
    cpu_cap = nodes["cpu_cap"].astype(jnp.int32)
    mem_cap = nodes["mem_cap"].astype(jnp.int32)
    cpu_req = (nodes["cpu_used"] + pod["cpu"]).astype(jnp.int32)
    mem_req = (nodes["mem_used"] + pod["mem"]).astype(jnp.int32)

    def calc_score(req, cap):
        # priorities.go:31-40: 0 if cap == 0 or req > cap.
        raw = jnp.where(cap > 0, ((cap - req) * 10) // jnp.maximum(cap, 1), 0)
        return jnp.where((cap == 0) | (req > cap), 0, raw)

    lr = (calc_score(cpu_req, cpu_cap) + calc_score(mem_req, mem_cap)) // 2

    # BalancedResourceAllocation (priorities.go:146-205). TPU float
    # division is reciprocal-based and NOT correctly rounded (~1 ulp
    # low), which truncates scores one short at exact boundaries like
    # |0.75-0.25|*10 == 5. The epsilon absorbs that device error; it is
    # far below the smallest legitimate gap between distinct exact
    # score values for realistic capacities.
    cfrac = jnp.where(cpu_cap == 0, 1.0, cpu_req / jnp.maximum(cpu_cap, 1))
    mfrac = jnp.where(mem_cap == 0, 1.0, mem_req / jnp.maximum(mem_cap, 1))
    bra = jnp.where(
        (cfrac >= 1) | (mfrac >= 1),
        0,
        (10 - jnp.abs(cfrac - mfrac) * 10 + 1e-5).astype(jnp.int32),
    )

    # ServiceSpreading (spreading.go:38-87) in exact integer math
    # (counts are small integers): 10*(maxc-count) // maxc. Go truncates
    # the float32 quotient; integer division agrees except where Go's
    # f32 rounding lands exactly on an integer from below — rare and
    # covered by the >=99% parity budget.
    svc = pod["svc"]
    counts = jax.lax.dynamic_index_in_dim(
        nodes["svc_counts"], jnp.maximum(svc, 0), axis=1, keepdims=False
    ).astype(jnp.int32)
    maxc = jnp.max(counts)
    spread_raw = (10 * (maxc - counts)) // jnp.maximum(maxc, 1)
    spread = jnp.where((svc < 0) | (maxc == 0), 10, spread_raw)
    return lr, bra, spread


def _scores(
    pod: Dict,
    nodes: Dict,
    weights,
    ls: LoweredSpec = DEFAULT_LOWERED,
    feas: jnp.ndarray = None,
) -> jnp.ndarray:
    """Weighted configured priorities as one int32[N] score vector.

    `feas` is the pod's feasibility mask: the reference prioritizes
    over the FILTERED node list (generic_scheduler.go:80-86), which
    only matters for ServiceAntiAffinity — its per-zone peer counts
    skip peers hosted on filtered-out nodes (spreading.go:133-147).
    Every other priority's per-node score is filter-independent."""
    w_lr, w_bra, w_spread = weights
    total = jnp.zeros(nodes["cpu_cap"].shape[0], dtype=jnp.int32)

    if w_lr or w_bra or w_spread:
        # Unused components are dead code XLA eliminates; the shared
        # helper keeps the explain readback's score decomposition
        # (explain_rows) THE solver arithmetic, not a twin.
        lr, bra, spread = _component_scores(pod, nodes)
    if w_lr:
        total = total + lr * w_lr
    if w_bra:
        total = total + bra * w_bra
    if w_spread:
        total = total + spread * w_spread

    svc = pod["svc"]
    if ls.aa_weights:
        counts = jax.lax.dynamic_index_in_dim(
            nodes["svc_counts"], jnp.maximum(svc, 0), axis=1, keepdims=False
        ).astype(jnp.int32)

    if ls.static_prio:
        # CalculateNodeLabelPriority: pod-independent, weights folded
        # into the column host-side (priorities.go:113-138).
        total = total + nodes["static_prio"]

    if ls.aa_weights:
        # ServiceAntiAffinity (spreading.go:105-169): spread the pod's
        # first service across the values ("zones") of one node label.
        # numServicePods counts peers regardless of node presence
        # (svc_total); per-zone counts sum the per-node peer counts.
        scratch = nodes["svc_total"].shape[0] - 1
        slot = jnp.where(svc >= 0, svc, scratch)
        num = jnp.where(svc >= 0, nodes["svc_total"][slot], 0.0).astype(jnp.int32)
        for i, (w, nz) in enumerate(zip(ls.aa_weights, ls.aa_zones)):
            zone = nodes["aa_zone"][:, i]
            in_zone = zone >= 0
            if feas is not None:
                in_zone = in_zone & feas
            zc = jnp.zeros(nz, dtype=jnp.int32).at[jnp.maximum(zone, 0)].add(
                jnp.where(in_zone, counts, 0)
            )
            count_z = zc[jnp.maximum(zone, 0)]
            score = jnp.where(
                num > 0, (10 * (num - count_z)) // jnp.maximum(num, 1), 10
            )
            score = jnp.where(zone < 0, 0, score)
            total = total + score * w

    return total


def _commit(nodes: Dict, pod: Dict, choice: jnp.ndarray, N: int) -> Dict:
    """Apply one placement to the occupancy carry (the batch analog of
    Modeler.AssumePod, modeler.go:113)."""
    assigned = choice >= 0
    onehot = (jnp.arange(N, dtype=jnp.int32) == choice) & assigned
    fonehot = onehot.astype(jnp.float32)
    new = dict(nodes)
    new["cpu_fit"] = nodes["cpu_fit"] + fonehot * pod["cpu"]
    new["mem_fit"] = nodes["mem_fit"] + fonehot * pod["mem"]
    new["cpu_used"] = nodes["cpu_used"] + fonehot * pod["cpu"]
    new["mem_used"] = nodes["mem_used"] + fonehot * pod["mem"]
    new["pods_used"] = nodes["pods_used"] + fonehot
    mask = onehot[:, None]
    new["uport"] = jnp.where(mask, nodes["uport"] | pod["port"][None, :], nodes["uport"])
    new["uvol_any"] = jnp.where(
        mask, nodes["uvol_any"] | pod["vol_any"][None, :], nodes["uvol_any"]
    )
    new["uvol_rw"] = jnp.where(
        mask, nodes["uvol_rw"] | pod["vol_rw"][None, :], nodes["uvol_rw"]
    )
    # As an existing pod, the placement counts toward EVERY service
    # whose selector matches it. Membership travels as a top-K id list
    # (i32[K], -1 padded) instead of a dense f32[S] row: at 50k pods x
    # 500 services the dense rows were 100 MB of upload per solve.
    # The commit is a K-element scatter-add into row `choice` — NOT a
    # broadcasted full-matrix add: rewriting the N x S counts matrix
    # every scan step costs ~N*S*8 bytes of HBM traffic per pod
    # (~500 GB over a 50k backlog), which alone blew the <2s budget.
    ids = pod["svc_ids"]
    row = jnp.maximum(choice, 0)
    valid = ((ids >= 0) & assigned).astype(jnp.float32)
    new["svc_counts"] = nodes["svc_counts"].at[row, jnp.maximum(ids, 0)].add(
        valid, mode="drop"
    )
    if "anchor" in nodes:
        # ServiceAffinity/AntiAffinity carry: the placed pod becomes a
        # peer of every service it matches; it becomes a service's
        # anchor only when that service had no listed peer yet (the
        # scalar's nsServicePods[0] is first-in-list-order, and the
        # backlog commits in order). Invalid/padded ids route to the
        # scratch slot (last index), which no real pod ever reads.
        scratch = nodes["anchor"].shape[0] - 1
        slot = jnp.where((ids >= 0) & assigned, ids, scratch)
        new["svc_total"] = nodes["svc_total"].at[slot].add(1.0)
        cur = nodes["anchor"][slot]
        new["anchor"] = nodes["anchor"].at[slot].set(
            jnp.where(cur == -1, choice, cur)
        )
    return new


def _scan_solve(pods, nodes, weights, lspec=DEFAULT_LOWERED):
    N = nodes["cpu_cap"].shape[0]

    def step(carry, pod):
        feas = _feasible(pod, carry, N, lspec)
        score = _scores(pod, carry, weights, lspec, feas)
        masked = jnp.where(feas, score, -1)
        best = jnp.argmax(masked).astype(jnp.int32)  # first max = lowest index
        # Feasibility folds into the same reduction: infeasible nodes
        # carry -1, so "any feasible" == "max masked value >= 0". One
        # N-wide reduction instead of two.
        choice = jnp.where(masked[best] >= 0, best, -1)
        return _commit(carry, pod, choice, N), choice

    # The scan is latency-bound on TPU (per-iteration sequencing
    # overhead ~30us dominates the ~500KB the body actually touches),
    # so unrolling amortizes it. Swept at 50k x 5k on v5e: unroll
    # 2/8/16/32 solve in 1.27/1.16/1.15/1.12s with compile+first-run
    # at 6.2/5.0/-/8.7s — 8 takes most of the runtime win at the
    # LOWEST compile cost. Decisions are bit-identical for any unroll.
    return jax.lax.scan(step, nodes, pods, unroll=8)


@traced_jit(static_argnames=("weights", "lspec"))
def _solve_xla(pods, nodes, weights, lspec):
    _, assignment = _scan_solve(pods, nodes, weights, lspec)
    return assignment


@traced_jit(static_argnames=("weights", "lspec"), donate_argnames=("nodes",))
def _solve_with_state_xla(pods, nodes, weights, lspec):
    final, assignment = _scan_solve(pods, nodes, weights, lspec)
    return assignment, final


def _use_pallas(pods, nodes, lspec) -> bool:
    """Pick the kernel for one solve, and count it."""
    from kubernetes_tpu.ops.pallas_scan import count_solve, pallas_reason

    return count_solve(pallas_reason(nodes, lspec))


def solve(
    pods: Dict[str, jnp.ndarray],
    nodes: Dict[str, jnp.ndarray],
    weights: Tuple[int, int, int] = DEFAULT_WEIGHTS,
    lspec: LoweredSpec = DEFAULT_LOWERED,
) -> jnp.ndarray:
    """Sequential-parity assignment: i32[P] of node indices (-1 =
    unschedulable). The scan IS the reference's scheduleOne loop.
    `lspec` selects the configured predicate/priority pipeline (static:
    one compiled executable per distinct policy).

    Dispatch: the default spec on a single unsharded TPU device runs
    the pallas kernel (ops/pallas_scan.py — same decisions, ~3x faster:
    the per-node occupancy carry lives in VMEM instead of round-tripping
    HBM every scan step). Policy specs, meshes, and CPU run the XLA
    scan. Bit-identical by test (tests/test_pallas_scan.py) and by the
    bench's measured sequential-oracle parity chain."""
    if _use_pallas(pods, nodes, lspec):
        from kubernetes_tpu.ops.pallas_scan import solve_pallas

        return solve_pallas(pods, nodes, weights)
    return _solve_xla(pods, nodes, weights, lspec)


def solve_with_state(
    pods: Dict[str, jnp.ndarray],
    nodes: Dict[str, jnp.ndarray],
    weights: Tuple[int, int, int] = DEFAULT_WEIGHTS,
    lspec: LoweredSpec = DEFAULT_LOWERED,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Like solve, but also returns the post-commit occupancy carry.
    On the XLA path `nodes` is DONATED: the caller's buffers are
    consumed and the returned state aliases them — the substrate for
    incremental churn (SolverSession keeps this state device-resident
    across ticks). The pallas path (same dispatch rule as solve())
    returns fresh state arrays instead; either way the caller must not
    reuse its argument."""
    if _use_pallas(pods, nodes, lspec):
        from kubernetes_tpu.ops.pallas_scan import solve_with_state_pallas

        return solve_with_state_pallas(pods, nodes, weights)
    return _solve_with_state_xla(pods, nodes, weights, lspec)


# -- explain readback --------------------------------------------------


def _explain_row(pod: Dict, nodes: Dict, N: int):
    """One pod's per-node verdict against a FIXED occupancy state:
    packed predicate-failure bits (bit i = matrices.EXPLAIN_PREDICATES
    [i] REJECTED the node) plus the default priority components. Built
    from the same _pred_* / _component_scores the solver decides with."""
    preds = (
        nodes["sched"],
        _pred_resources(pod, nodes),
        _pred_selector(pod, nodes),
        _pred_ports(pod, nodes),
        _pred_disk(pod, nodes),
        _pred_hostname(pod, N),
    )
    bits = jnp.zeros(N, jnp.uint32)
    for i, ok in enumerate(preds):
        bits = bits | ((~ok).astype(jnp.uint32) << i)
    lr, bra, spread = _component_scores(pod, nodes)
    return bits, lr, bra, spread


@traced_jit
def explain_rows(pods: Dict[str, jnp.ndarray], nodes: Dict[str, jnp.ndarray]):
    """The explain readback: default-pipeline verdicts for a batch of
    pods, vmapped — (bits u32[P, N], lr i32[P, N], bra, spread). The
    occupancy state `nodes` is FIXED (no commits): callers choose
    which state — pre-solve for "why did this pod win", post-solve for
    "why is this pod still stuck" — and strip padding themselves
    (ops.pipeline.explain_matrix does both). Off the solve hot path by
    construction: one dispatch per tick, over arrays the tick already
    staged."""
    N = nodes["cpu_cap"].shape[0]
    return jax.vmap(lambda p: _explain_row(p, nodes, N))(pods)


def solve_assignments(
    dsnap: DeviceSnapshot, weights: Optional[Tuple[int, int, int]] = None
) -> np.ndarray:
    """Run the solver and strip padding: returns i32[n_pods] with real
    node indices (-1 unschedulable). Policy lowering (lspec + weights)
    rides on the DeviceSnapshot; an explicit `weights` overrides."""
    if weights is None:
        weights = dsnap.weights
    out = np.asarray(solve(dsnap.pods, dsnap.nodes, weights, dsnap.lowered))
    from kubernetes_tpu.utils import sli

    sli.note_transfer("d2h", out.nbytes)
    out = out[: dsnap.n_pods]
    # Padding nodes can never be chosen (schedulable=False), but clamp
    # defensively so a bug can't leak a phantom index.
    out = np.where(out >= dsnap.n_nodes, -1, out)
    return out
