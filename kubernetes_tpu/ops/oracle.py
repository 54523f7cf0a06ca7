"""Sequential NumPy oracle: the reference's scheduleOne semantics
replayed pod-at-a-time in exact host arithmetic (int64 / float64).

Role in the parity chain (BASELINE.md >=99% target):
- The scalar object-graph oracle (scheduler.batch.schedule_backlog_scalar)
  IS the reference semantics (plugin/pkg/scheduler/generic_scheduler.go:
  60-171), but it is O(P^2 * N) Python — unusable beyond ~1k pods.
- This oracle replays the same decisions over the columnar Snapshot with
  one batch of NumPy N-vector ops per pod, so parity can be MEASURED at
  the full 50k x 5k scale instead of asserted from toy runs.
- Equivalence scalar-oracle == numpy-oracle is itself tested at fuzz
  scale and at BASELINE config 2 (tests/test_solver_parity.py), so
  device-vs-numpy parity at 50k is evidence about the device scan, and
  scalar-vs-device parity at 1k is evidence about the lowering.

Arithmetic notes: LeastRequested uses int64 // (Go int64 truncation,
priorities.go:31-40); BalancedResourceAllocation and ServiceSpreading
use float64 then int-truncate exactly like the scalar path
(priorities.go:146-205, spreading.go:38-87). This intentionally does
NOT reproduce the device's f32-reciprocal epsilon hack — divergence
there is precisely what the parity number is meant to expose.
"""

from __future__ import annotations

import numpy as np

from kubernetes_tpu.models.columnar import Snapshot
from kubernetes_tpu.models.columnar import SVC_K  # noqa: F401


def solve_sequential_numpy(snap: Snapshot) -> np.ndarray:
    """i32[P] node indices (-1 = unschedulable), in pod order."""
    out, _ = _replay(snap, forced=None)
    return out


def explain_bits_numpy(snap: Snapshot):
    """The explain readback's scalar twin: per-(pod, node) packed
    predicate-failure bits plus the default priority components, in
    host arithmetic over the FIXED snapshot state (no sequential
    commit — every pod sees the same occupancy, exactly like the
    device readback in ops.solver.explain_rows evaluates it).

    Bit layout is ops.matrices.EXPLAIN_PREDICATES. Returns
    (bits u32[P, N], lr i64[P, N], bra i64[P, N], spread i64[P, N]).

    Unlike the solve oracle above, BalancedResourceAllocation here
    reproduces the device's float32 + epsilon recipe on purpose: this
    twin certifies the READBACK bit-for-bit (tests/test_solver_parity
    TestExplainParity demands 100%), while Go-semantics divergence
    remains the solve-parity suite's business."""
    p, n = snap.pods, snap.nodes
    P, N = p.count, n.count
    bits = np.zeros((P, N), np.uint32)
    lr = np.zeros((P, N), np.int64)
    bra = np.zeros((P, N), np.int64)
    spread = np.zeros((P, N), np.int64)
    if P == 0 or N == 0:
        return bits, lr, bra, spread

    cpu_cap = n.cpu_cap.astype(np.int64)
    mem_cap = n.mem_cap.astype(np.int64)
    pods_cap = n.pods_cap.astype(np.int64)
    cpu_fit = n.cpu_fit_used.astype(np.int64)
    mem_fit = n.mem_fit_used.astype(np.int64)
    over = n.overcommitted
    cpu_used = n.cpu_used.astype(np.int64)
    mem_used = n.mem_used.astype(np.int64)
    pods_used = n.pods_used.astype(np.int64)
    labels = n.label_bits
    uport = n.used_port_bits
    uvol_any = n.used_vol_any_bits
    uvol_rw = n.used_vol_rw_bits
    svc_counts = n.service_counts.astype(np.int64)
    sched = n.schedulable
    idx = np.arange(N, dtype=np.int64)
    pod_cpu = p.cpu_milli.astype(np.int64)
    pod_mem = p.mem_mib.astype(np.int64)
    sel_rows = p.sel_bits[p.selector_id]

    for i in range(P):
        # -- predicates, one bit each (solver._pred_* formulas) --
        fits_cpu = (cpu_cap == 0) | (cpu_fit + pod_cpu[i] <= cpu_cap)
        fits_mem = (mem_cap == 0) | (mem_fit + pod_mem[i] <= mem_cap)
        fits_count = pods_used + 1 <= pods_cap
        if p.zero_req[i]:
            res_ok = pods_used < pods_cap
        else:
            res_ok = (~over) & fits_cpu & fits_mem & fits_count
        sel = sel_rows[i]
        sel_ok = ((sel[None, :] & labels) == sel[None, :]).all(axis=1)
        port_ok = ~(p.port_bits[i][None, :] & uport).any(axis=1)
        vol_ok = ~(
            (p.vol_rw_bits[i][None, :] & uvol_any)
            | (p.vol_any_bits[i][None, :] & uvol_rw)
        ).any(axis=1)
        pin = int(p.pinned_node[i])
        host_ok = np.ones(N, bool) if pin == -1 else (idx == pin)
        for b, ok in enumerate(
            (sched, res_ok, sel_ok, port_ok, vol_ok, host_ok)
        ):
            bits[i] |= (~ok).astype(np.uint32) << b

        # -- components --
        creq = cpu_used + pod_cpu[i]
        mreq = mem_used + pod_mem[i]
        lr_c = np.where(
            (cpu_cap == 0) | (creq > cpu_cap),
            0,
            ((cpu_cap - creq) * 10) // np.maximum(cpu_cap, 1),
        )
        lr_m = np.where(
            (mem_cap == 0) | (mreq > mem_cap),
            0,
            ((mem_cap - mreq) * 10) // np.maximum(mem_cap, 1),
        )
        lr[i] = (lr_c + lr_m) // 2
        # float32 on the host is IEEE round-to-nearest — identical to
        # the CPU jax backend the parity suite runs on.
        cfrac = np.where(
            cpu_cap == 0,
            np.float32(1.0),
            creq.astype(np.float32) / np.maximum(cpu_cap, 1).astype(np.float32),
        ).astype(np.float32)
        mfrac = np.where(
            mem_cap == 0,
            np.float32(1.0),
            mreq.astype(np.float32) / np.maximum(mem_cap, 1).astype(np.float32),
        ).astype(np.float32)
        bra[i] = np.where(
            (cfrac >= 1) | (mfrac >= 1),
            0,
            (
                np.float32(10)
                - np.abs(cfrac - mfrac) * np.float32(10)
                + np.float32(1e-5)
            ).astype(np.int64),
        )
        svc = int(p.service_id[i])
        if svc < 0:
            spread[i] = 10
        else:
            counts = svc_counts[:, svc]
            maxc = int(counts.max())
            if maxc == 0:
                spread[i] = 10
            else:
                spread[i] = (10 * (maxc - counts)) // maxc
    return bits, lr, bra, spread


def assignment_quality(snap: Snapshot, assignment: np.ndarray) -> dict:
    """Score an APPROXIMATE solver's assignment against the greedy
    oracle (VERDICT r2 Weak #2: wave/sinkhorn quality must be a
    number, not a claim). Replays the backlog in pod order committing
    each pod to its ASSIGNED node, and at each step measures the score
    gap to the oracle's best feasible node at that state:

      regret_i = max feasible score - score(assigned node)

    Returns mean/p99 regret (0 = every placement was greedy-optimal in
    order), the fraction of placements that were exactly greedy-best,
    and the fraction feasible under pod-order replay (wave commits in
    a different order, so a valid wave placement can transiently look
    infeasible here; regret is measured over the feasible ones)."""
    _, stats = _replay(snap, forced=np.asarray(assignment, dtype=np.int32))
    return stats


def _replay(snap: Snapshot, forced):
    p, n = snap.pods, snap.nodes
    P, N = p.count, n.count
    out = np.full(P, -1, dtype=np.int32)
    regrets = []
    greedy_hits = 0
    placed = 0
    infeasible_in_order = 0
    if P == 0 or N == 0:
        return out, {
            "mean_regret": 0.0,
            "p99_regret": 0.0,
            "greedy_match": 1.0,
            "feasible_in_order": 1.0,
            "placed": 0,
        }

    cpu_cap = n.cpu_cap.astype(np.int64)
    mem_cap = n.mem_cap.astype(np.int64)
    pods_cap = n.pods_cap.astype(np.int64)
    cpu_fit = n.cpu_fit_used.astype(np.int64).copy()
    mem_fit = n.mem_fit_used.astype(np.int64).copy()
    over = n.overcommitted.copy()
    cpu_used = n.cpu_used.astype(np.int64).copy()
    mem_used = n.mem_used.astype(np.int64).copy()
    pods_used = n.pods_used.astype(np.int64).copy()
    labels = n.label_bits
    uport = n.used_port_bits.copy()
    uvol_any = n.used_vol_any_bits.copy()
    uvol_rw = n.used_vol_rw_bits.copy()
    svc_counts = n.service_counts.astype(np.int64).copy()
    sched = n.schedulable
    idx = np.arange(N, dtype=np.int64)

    pod_cpu = p.cpu_milli.astype(np.int64)
    pod_mem = p.mem_mib.astype(np.int64)
    sel_rows = p.sel_bits[p.selector_id]
    # Same top-K membership truncation the device path commits with.
    svc_ids = p.svc_topk

    for i in range(P):
        # -- predicates (solver.py _feasible formulas) --
        fits_cpu = (cpu_cap == 0) | (cpu_fit + pod_cpu[i] <= cpu_cap)
        fits_mem = (mem_cap == 0) | (mem_fit + pod_mem[i] <= mem_cap)
        fits_count = pods_used + 1 <= pods_cap
        if p.zero_req[i]:
            res_ok = pods_used < pods_cap
        else:
            res_ok = (~over) & fits_cpu & fits_mem & fits_count
        sel = sel_rows[i]
        sel_ok = ((sel[None, :] & labels) == sel[None, :]).all(axis=1)
        port_ok = ~(p.port_bits[i][None, :] & uport).any(axis=1)
        vol_bad = (
            (p.vol_rw_bits[i][None, :] & uvol_any)
            | (p.vol_any_bits[i][None, :] & uvol_rw)
        ).any(axis=1)
        pin = int(p.pinned_node[i])
        host_ok = True if pin == -1 else (idx == pin)
        feas = res_ok & sel_ok & port_ok & ~vol_bad & host_ok & sched

        # -- priorities (exact host arithmetic) --
        creq = cpu_used + pod_cpu[i]
        mreq = mem_used + pod_mem[i]
        lr_c = np.where(
            (cpu_cap == 0) | (creq > cpu_cap),
            0,
            ((cpu_cap - creq) * 10) // np.maximum(cpu_cap, 1),
        )
        lr_m = np.where(
            (mem_cap == 0) | (mreq > mem_cap),
            0,
            ((mem_cap - mreq) * 10) // np.maximum(mem_cap, 1),
        )
        lr = (lr_c + lr_m) // 2
        cfrac = np.where(cpu_cap == 0, 1.0, creq / np.maximum(cpu_cap, 1))
        mfrac = np.where(mem_cap == 0, 1.0, mreq / np.maximum(mem_cap, 1))
        bra = np.where(
            (cfrac >= 1) | (mfrac >= 1),
            0,
            (10.0 - np.abs(cfrac - mfrac) * 10.0).astype(np.int64),
        )
        svc = int(p.service_id[i])
        if svc < 0:
            spread = np.full(N, 10, dtype=np.int64)
        else:
            counts = svc_counts[:, svc]
            maxc = int(counts.max())
            if maxc == 0:
                spread = np.full(N, 10, dtype=np.int64)
            else:
                spread = (10.0 * ((maxc - counts) / maxc)).astype(np.int64)
        score = lr + bra + spread

        masked = np.where(feas, score, -1)
        best = int(np.argmax(masked))  # first max = lowest node index
        if forced is None:
            if masked[best] < 0:
                continue
            out[i] = best
        else:
            chosen = int(forced[i])
            if chosen < 0:
                continue  # the approximate solver left it unplaced
            placed += 1
            if masked[best] >= 0 and feas[chosen]:
                regrets.append(int(masked[best]) - int(score[chosen]))
                if int(score[chosen]) == int(masked[best]):
                    greedy_hits += 1
            else:
                infeasible_in_order += 1
            out[i] = best = chosen

        # -- commit (AssumePod analog) --
        cpu_fit[best] += pod_cpu[i]
        mem_fit[best] += pod_mem[i]
        cpu_used[best] += pod_cpu[i]
        mem_used[best] += pod_mem[i]
        pods_used[best] += 1
        uport[best] |= p.port_bits[i]
        uvol_any[best] |= p.vol_any_bits[i]
        uvol_rw[best] |= p.vol_rw_bits[i]
        ids = svc_ids[i]
        ids = ids[ids >= 0]
        if len(ids):
            svc_counts[best, ids] += 1

    stats = None
    if forced is not None:
        r = np.asarray(regrets, dtype=np.float64)
        stats = {
            "mean_regret": float(r.mean()) if len(r) else 0.0,
            "p99_regret": float(np.percentile(r, 99)) if len(r) else 0.0,
            "greedy_match": greedy_hits / max(placed, 1),
            "feasible_in_order": 1.0 - infeasible_in_order / max(placed, 1),
            "placed": placed,
        }
    return out, stats


def scatter_rows_numpy(
    nodes: dict, idx: np.ndarray, rows: dict
) -> dict:
    """NumPy twin of ops.incremental._scatter_rows (the session's
    dirty-row commit): out-of-place fancy-index row replacement over a
    dict-of-arrays. Registered in ops/parity.py; parity pinned by
    tests/test_ktsan.py."""
    out = {}
    for k, arr in nodes.items():
        a = np.array(arr, copy=True)
        a[np.asarray(idx)] = np.asarray(rows[k])
        out[k] = a
    return out


def scatter_rows_banded_numpy(
    counts: np.ndarray, svc: np.ndarray, slot: np.ndarray, value: np.ndarray
) -> np.ndarray:
    """NumPy twin of ops.pallas_scan.scatter_rows_banded (the banded
    carry's point patch): out-of-place (Service, node slot) writes.
    Registered in ops/parity.py; parity pinned by tests/test_ktsan.py."""
    out = np.array(counts, copy=True)
    out[np.asarray(svc), np.asarray(slot)] = np.asarray(value)
    return out


def validate_assignment_numpy(snap: Snapshot, assignment) -> None:
    """Replay every placement against the snapshot's own predicate
    semantics in NumPy; raises AssertionError on any capacity /
    selector / port / volume / pin violation.

    This is the NumPy oracle twin for the approximate wave-family
    kernels (ops.wave.solve_waves, ops.sinkhorn.solve_sinkhorn_stats):
    they trade decision-ORDER parity for batching, so their invariant
    is placement VALIDITY, not destination equality — see
    tests/test_wave.py / tests/test_sinkhorn.py, which drive every
    fuzz case through this checker."""
    n = snap.nodes
    cpu_fit = n.cpu_fit_used.copy()
    mem_fit = n.mem_fit_used.copy()
    pods_used = n.pods_used.copy()
    uport = n.used_port_bits.copy()
    uvol_any = n.used_vol_any_bits.copy()
    uvol_rw = n.used_vol_rw_bits.copy()
    p = snap.pods
    sel_rows = p.sel_bits[p.selector_id]
    for i, j in enumerate(assignment):
        if j < 0:
            continue
        assert n.schedulable[j], f"pod {i} on unschedulable node {j}"
        assert not n.overcommitted[j], f"pod {i} on overcommitted node {j}"
        if p.zero_req[i]:
            assert pods_used[j] < n.pods_cap[j], f"pod {i}: count overflow"
        else:
            if n.cpu_cap[j] > 0:
                assert cpu_fit[j] + p.cpu_milli[i] <= n.cpu_cap[j], (
                    f"pod {i}: cpu overflow on node {j}"
                )
            if n.mem_cap[j] > 0:
                assert mem_fit[j] + p.mem_mib[i] <= n.mem_cap[j], (
                    f"pod {i}: mem overflow on node {j}"
                )
            assert pods_used[j] + 1 <= n.pods_cap[j], f"pod {i}: count"
        sel = sel_rows[i]
        assert ((sel & n.label_bits[j]) == sel).all(), f"pod {i}: selector"
        assert not (p.port_bits[i] & uport[j]).any(), f"pod {i}: port clash"
        assert not (
            (p.vol_rw_bits[i] & uvol_any[j]) | (p.vol_any_bits[i] & uvol_rw[j])
        ).any(), f"pod {i}: volume clash"
        pin = p.pinned_node[i]
        assert pin in (-1, j), f"pod {i}: pinned to {pin}, placed on {j}"
        cpu_fit[j] += p.cpu_milli[i]
        mem_fit[j] += p.mem_mib[i]
        pods_used[j] += 1
        uport[j] |= p.port_bits[i]
        uvol_any[j] |= p.vol_any_bits[i]
        uvol_rw[j] |= p.vol_rw_bits[i]

def capacity_report_numpy(
    cpu_cap,
    mem_cap,
    pods_cap,
    cpu_fit,
    mem_fit,
    pods_used,
    over,
    sched,
    probe_cpu,
    probe_mem,
    probe_min,
    probe_live,
):
    """Exact host twin of ops.capacity.capacity_report (KT006).

    Same float32 elementwise arithmetic, same int32-quantized
    reductions — cross-node/cross-probe sums are integer, so this twin
    matches the device kernel BIT-FOR-BIT (no tolerance), unlike the
    Go-semantics solve oracle above whose divergence is the signal.
    See tests/test_solver_parity.py TestCapacityParity."""
    from kubernetes_tpu.ops.capacity import BIG_FIT, FIT_CAP, FRAC_Q

    f32 = np.float32
    cpu_cap = np.asarray(cpu_cap, f32)
    mem_cap = np.asarray(mem_cap, f32)
    pods_cap = np.asarray(pods_cap, f32)
    cpu_fit = np.asarray(cpu_fit, f32)
    mem_fit = np.asarray(mem_fit, f32)
    pods_used = np.asarray(pods_used, f32)
    over = np.asarray(over, bool)
    sched = np.asarray(sched, bool)
    probe_cpu = np.asarray(probe_cpu, f32)
    probe_mem = np.asarray(probe_mem, f32)
    probe_min = np.asarray(probe_min, np.int32)
    probe_live = np.asarray(probe_live, bool)

    f0, f1, big = f32(0.0), f32(1.0), f32(BIG_FIT)
    live = sched & ~over
    livef = live.astype(f32)

    free_cpu = np.maximum(cpu_cap - cpu_fit, f0) * livef
    free_mem = np.maximum(mem_cap - mem_fit, f0) * livef
    free_pods = np.maximum(pods_cap - pods_used, f0) * livef

    def util(used_part, cap):
        return np.where(
            (cap > f0) & live,
            np.clip(used_part / np.maximum(cap, f1), f0, f1),
            f0,
        ).astype(f32)

    util_cpu = util(cpu_fit, cpu_cap)
    util_mem = util(mem_fit, mem_cap)
    util_pods = util(pods_used, pods_cap)

    pc = probe_cpu[:, None]
    pm = probe_mem[:, None]
    per_cpu = np.where(pc > f0, free_cpu[None, :] / np.maximum(pc, f1), big)
    per_mem = np.where(pm > f0, free_mem[None, :] / np.maximum(pm, f1), big)
    fit_frac = np.minimum(np.minimum(per_cpu, per_mem), free_pods[None, :])
    fit_frac = np.clip(fit_frac, f0, f32(FIT_CAP)).astype(f32)
    fit_int = np.floor(fit_frac).astype(np.int32)
    frac_milli = np.floor(fit_frac * f32(FRAC_Q)).astype(np.int32)

    plive = probe_live.astype(np.int32)
    usable = (fit_int.sum(axis=1, dtype=np.int32) * plive).astype(np.int32)
    potential = (
        frac_milli.sum(axis=1, dtype=np.int32) * plive
    ).astype(np.int32)
    headroom = usable
    frag = np.where(
        potential > 0,
        f1
        - (usable.astype(f32) * f32(FRAC_Q))
        / np.maximum(potential, 1).astype(f32),
        f0,
    ).astype(f32)
    frag = (np.clip(frag, f0, f1) * probe_live.astype(f32)).astype(f32)
    slice_ok = probe_live & (
        headroom >= np.maximum(probe_min, np.int32(1))
    )

    total_usable = np.int32(usable.sum(dtype=np.int32))
    total_potential = np.int32(potential.sum(dtype=np.int32))
    if total_potential > 0:
        frag_score = f32(
            f1 - (f32(total_usable) * f32(FRAC_Q)) / f32(total_potential)
        )
    else:
        frag_score = f0
    frag_score = f32(np.clip(frag_score, f0, f1))

    hosts_any = ((fit_int > 0) & probe_live[:, None]).any(axis=0)
    any_live_probe = bool(probe_live.any())
    stranded = (
        live
        & ((free_cpu > f0) | (free_mem > f0))
        & ~hosts_any
        & any_live_probe
    )

    def stranded_frac(free):
        free_i = free.astype(np.int32)
        tot = np.int32(free_i.sum(dtype=np.int32))
        strand = np.int32(
            (free_i * stranded.astype(np.int32)).sum(dtype=np.int32)
        )
        return f32(f32(strand) / f32(tot)) if tot > 0 else f0

    stranded_cpu = stranded_frac(free_cpu)
    stranded_mem = stranded_frac(free_mem)

    return (
        util_cpu,
        util_mem,
        util_pods,
        fit_int,
        headroom,
        frag,
        slice_ok,
        stranded,
        np.float32(frag_score),
        np.float32(stranded_cpu),
        np.float32(stranded_mem),
    )


def plan_moves_numpy(
    cpu_cap,
    mem_cap,
    pods_cap,
    cpu_fit,
    mem_fit,
    pods_used,
    over,
    sched,
    pod_cpu,
    pod_mem,
    pod_node,
    pod_live,
    pod_force,
    probe_cpu,
    probe_mem,
    probe_min,
    probe_live,
    move_budget,
):
    """Exact host twin of ops.rebalance.plan_moves (KT006).

    The device kernel's lax.scan written as the Python loop it is:
    same f32 elementwise arithmetic, same int32-quantized fits, same
    first-minimum argmin tie-break — bit-for-bit, no tolerance. See
    tests/test_solver_parity.py TestRebalanceParity."""
    from kubernetes_tpu.ops.capacity import BIG_FIT, FIT_CAP, FRAC_Q
    from kubernetes_tpu.ops.rebalance import NO_FIT_KEY

    f32 = np.float32
    cpu_cap = np.asarray(cpu_cap, f32)
    mem_cap = np.asarray(mem_cap, f32)
    pods_cap = np.asarray(pods_cap, f32)
    cf = np.asarray(cpu_fit, f32).copy()
    mf = np.asarray(mem_fit, f32).copy()
    pu = np.asarray(pods_used, f32).copy()
    over = np.asarray(over, bool)
    sched = np.asarray(sched, bool)
    pod_cpu = np.asarray(pod_cpu, f32)
    pod_mem = np.asarray(pod_mem, f32)
    pod_node = np.asarray(pod_node, np.int32)
    pod_live = np.asarray(pod_live, bool)
    pod_force = np.asarray(pod_force, bool)
    probe_cpu = np.asarray(probe_cpu, f32)
    probe_mem = np.asarray(probe_mem, f32)
    probe_live = np.asarray(probe_live, bool)
    budget = np.int32(np.asarray(move_budget))

    f0, f1, big = f32(0.0), f32(1.0), f32(BIG_FIT)
    live = sched & ~over
    livef = live.astype(f32)
    n = cpu_cap.shape[0]
    d = pod_cpu.shape[0]
    plive_i = probe_live.astype(np.int32)

    def free_vectors(cf, mf, pu):
        return (
            np.maximum(cpu_cap - cf, f0) * livef,
            np.maximum(mem_cap - mf, f0) * livef,
            np.maximum(pods_cap - pu, f0) * livef,
        )

    def frag_score(cf, mf, pu):
        free_cpu, free_mem, free_pods = free_vectors(cf, mf, pu)
        pc = probe_cpu[:, None]
        pm = probe_mem[:, None]
        per_cpu = np.where(pc > f0, free_cpu[None, :] / np.maximum(pc, f1), big)
        per_mem = np.where(pm > f0, free_mem[None, :] / np.maximum(pm, f1), big)
        fit_frac = np.minimum(np.minimum(per_cpu, per_mem), free_pods[None, :])
        fit_frac = np.clip(fit_frac, f0, f32(FIT_CAP)).astype(f32)
        fit_int = np.floor(fit_frac).astype(np.int32)
        frac_q = np.floor(fit_frac * f32(FRAC_Q)).astype(np.int32)
        usable = np.int32(
            (fit_int.sum(axis=1, dtype=np.int32) * plive_i).sum(dtype=np.int32)
        )
        potential = np.int32(
            (frac_q.sum(axis=1, dtype=np.int32) * plive_i).sum(dtype=np.int32)
        )
        if potential > 0:
            score = f32(f1 - (f32(usable) * f32(FRAC_Q)) / f32(potential))
        else:
            score = f0
        return f32(np.clip(score, f0, f1))

    def node_usable(fc, fm, fp):
        pcu = np.where(probe_cpu > f0, f32(fc) / np.maximum(probe_cpu, f1), big)
        pme = np.where(probe_mem > f0, f32(fm) / np.maximum(probe_mem, f1), big)
        ff = np.clip(np.minimum(np.minimum(pcu, pme), f32(fp)), f0, f32(FIT_CAP))
        return np.int32(
            (np.floor(ff).astype(np.int32) * plive_i).sum(dtype=np.int32)
        )

    score_before = frag_score(cf, mf, pu)

    dest = np.full(d, -1, np.int32)
    moved = np.zeros(d, bool)
    gain_out = np.zeros(d, np.int32)
    moves = np.int32(0)
    arange_n = np.arange(n, dtype=np.int32)
    for i in range(d):
        cpu, mem = pod_cpu[i], pod_mem[i]
        src = pod_node[i]
        free_cpu, free_mem, free_pods = free_vectors(cf, mf, pu)

        src_c = int(np.clip(src, 0, n - 1))
        src_valid = bool(0 <= src < n)
        is_src = (arange_n == np.int32(src_c)) & src_valid

        feasible = (
            live
            & (free_cpu >= cpu)
            & (free_mem >= mem)
            & (free_pods >= f1)
            & ~is_src
        )

        kc = np.where(cpu > f0, (free_cpu - cpu) / np.maximum(cpu, f1), big)
        km = np.where(mem > f0, (free_mem - mem) / np.maximum(mem, f1), big)
        key_frac = np.clip(np.minimum(kc, km), f0, f32(FIT_CAP)).astype(f32)
        key = np.floor(key_frac * f32(FRAC_Q)).astype(np.int32)
        key = np.where(feasible, key, np.int32(NO_FIT_KEY))
        dst = int(np.argmin(key))
        any_feasible = bool(feasible.any())

        src_live = src_valid and bool(live[src_c])
        if src_live:
            u_src_before = node_usable(
                free_cpu[src_c], free_mem[src_c], free_pods[src_c]
            )
            u_src_after = node_usable(
                max(f32(cpu_cap[src_c] - (cf[src_c] - cpu)), f0),
                max(f32(mem_cap[src_c] - (mf[src_c] - mem)), f0),
                max(f32(pods_cap[src_c] - (pu[src_c] - f1)), f0),
            )
        else:
            u_src_before = np.int32(0)
            u_src_after = np.int32(0)
        u_dst_before = node_usable(free_cpu[dst], free_mem[dst], free_pods[dst])
        u_dst_after = node_usable(
            max(f32(cpu_cap[dst] - (cf[dst] + cpu)), f0),
            max(f32(mem_cap[dst] - (mf[dst] + mem)), f0),
            max(f32(pods_cap[dst] - (pu[dst] + f1)), f0),
        )
        gain = np.int32(
            (u_src_after + u_dst_after) - (u_src_before + u_dst_before)
        )

        commit = bool(
            pod_live[i]
            and any_feasible
            and moves < budget
            and (gain > 0 or bool(pod_force[i]))
        )
        if commit:
            cf[dst] = f32(cf[dst] + cpu)
            mf[dst] = f32(mf[dst] + mem)
            pu[dst] = f32(pu[dst] + f1)
            if src_valid:
                cf[src_c] = f32(cf[src_c] - cpu)
                mf[src_c] = f32(mf[src_c] - mem)
                pu[src_c] = f32(pu[src_c] - f1)
            moves = np.int32(moves + 1)
            dest[i] = dst
            moved[i] = True
            gain_out[i] = gain

    score_after = frag_score(cf, mf, pu)
    return (
        dest,
        moved,
        gain_out,
        np.int32(moves),
        np.float32(score_before),
        np.float32(score_after),
    )
