"""Pallas TPU kernel for the sequential-parity scan solver.

The XLA lax.scan in ops/solver.py is latency-bound: each of the P steps
touches ~500KB of occupancy state in HBM and pays the scan's
per-iteration sequencing (~23us/step at 50k x 5k). That state fits in
VMEM with room to spare, which is exactly the case SURVEY.md §7 step 7
reserves for a hand kernel ("pallas kernels only where XLA fusion falls
short"). This kernel runs the ENTIRE sequential solve as one
pallas_call:

- grid = (P,): TPU grid steps execute sequentially on a core, so the
  occupancy carry lives in the OUTPUT refs (constant index_map keeps
  them VMEM-resident across all steps; they flush to HBM once at the
  end — the standard accumulator pattern).
- pod columns are packed host-side into ONE i32 row per pod
  (scalars + selector/port/volume bitset words + service top-K), so
  each grid step fetches a single tiny block instead of ~10.
- service spreading counts are (S, N) int16 in VMEM (counts are bounded
  by pods_cap <= 110, so int16 is exact; Mosaic vector arithmetic supports i16/i32, not i8); the XLA carry keeps its
  (N, S) f32 schema — the wrapper transposes/casts at the boundary.

Decision parity: the kernel reproduces ops/solver.py's default-spec
math op for op (integer LeastRequested, f32 BalancedResourceAllocation
with the same +1e-5 boundary epsilon, integer ServiceSpreading,
first-max-by-lowest-index tie-break). tests/test_pallas_scan.py checks
bit-identical assignments against the XLA scan (interpret mode on CPU,
the real kernel on TPU); policy specs and sharded meshes fall back to
the XLA path (ops/solver.py chooses).

Reference for the semantics being accelerated: the scheduleOne loop,
plugin/pkg/scheduler/scheduler.go:113-158 + generic_scheduler.go.
"""

from __future__ import annotations

import functools
import logging
import os
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from kubernetes_tpu.ops.ledger import traced_jit

_LOG = logging.getLogger("kubernetes_tpu.pallas")

# Lane layout of the packed per-pod row (i32). Bitset word counts are
# static per compiled kernel (shape-derived).
#   [0]=cpu [1]=mem [2]=zero [3]=pinned [4]=svc
#   [5 : 5+SW]=sel  [..+PW]=port  [..+VW]=vol_any  [..+VW]=vol_rw
#   [..+K]=svc_ids
_FIXED = 5

# VMEM budget for the kernel's resident blocks (v5e: ~16MB/core; leave
# headroom for double-buffered pod blocks and compiler scratch).
VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def _svc_pad(S: int) -> int:
    """Service axis inside the kernel: banded dynamic-sublane access
    needs >= 8 rows and 8-row alignment."""
    return max(8, ((S + 7) // 8) * 8)


def _vmem_bytes(N: int, S: int, LW: int, PW: int, VW: int) -> int:
    """Resident bytes: the int16 counts carry appears as BOTH a full
    input block and a full output block (so 2x), as do the word
    carries; f32 rows are cheap but counted."""
    counts = 2 * _svc_pad(S) * N * 2
    words = 2 * (PW + 2 * VW) * N * 4 + LW * N * 4
    rows = (5 + 2 * 5 + 1) * N * 4  # consts + init+carry f32 rows
    return counts + words + rows


#: Largest node axis the kernel takes: the packed (score, 8191-idx)
#: select needs N <= 8192.
MAX_NODES = 8192


@functools.lru_cache(maxsize=None)
def _log_ineligible(reason: str) -> None:
    """Once per distinct reason: a TPU solve the kernel cannot take runs
    the slower XLA scan, and that must show in the log."""
    _LOG.warning("pallas scan not eligible (%s): running the XLA scan", reason)


def pallas_eligible(pods: Dict, nodes: Dict, lspec) -> bool:
    """Default spec, single unsharded TPU device, VMEM-sized shapes —
    decided from explicit checks on the node carry's placement and
    shape. A single-TPU default-spec solve turned away for its size is
    logged (`_log_ineligible`)."""
    if os.environ.get("KTPU_PALLAS", "") == "off":
        return False
    from kubernetes_tpu.models.algspec import DEFAULT_LOWERED

    if lspec != DEFAULT_LOWERED:
        return False  # policy columns: XLA scan carries them
    arr = nodes["cpu_cap"]
    if not isinstance(arr, jax.Array):
        return False  # host arrays: jit places them on the default device
    if isinstance(arr, jax.core.Tracer):
        return False  # traced into an enclosing jit: placement unknown
    devices = arr.sharding.device_set
    if len(devices) != 1 or next(iter(devices)).platform != "tpu":
        return False  # a mesh, or the CPU backend
    N = arr.shape[0]
    S = nodes["svc_counts"].shape[1]
    if N > MAX_NODES:
        _log_ineligible(f"N={N} > {MAX_NODES} nodes")
        return False
    vmem = _vmem_bytes(
        N,
        S,
        nodes["labels"].shape[1],
        nodes["uport"].shape[1],
        nodes["uvol_any"].shape[1],
    )
    if vmem > VMEM_BUDGET_BYTES:
        _log_ineligible(
            f"N={N} S={S} needs {vmem} B of VMEM > {VMEM_BUDGET_BYTES}"
        )
        return False
    return True


def _pack_pods(pods: Dict) -> jnp.ndarray:
    """One i32 row per pod; cpu/mem are integer-valued f32 (milli-CPU,
    MiB) so the cast is exact."""
    cols = [
        pods["cpu"].astype(jnp.int32)[:, None],
        pods["mem"].astype(jnp.int32)[:, None],
        pods["zero_req"].astype(jnp.int32)[:, None],
        pods["pinned"][:, None],
        pods["svc"][:, None],
        pods["sel"].astype(jnp.int32),
        pods["port"].astype(jnp.int32),
        pods["vol_any"].astype(jnp.int32),
        pods["vol_rw"].astype(jnp.int32),
        pods["svc_ids"],
    ]
    return jnp.concatenate(cols, axis=1)


def _kernel(
    SW: int, PW: int, VW: int, K: int, N: int, S: int, C: int, weights,
    packed_ref,
    cpu_cap_ref, mem_cap_ref, pods_cap_ref, over_ref, sched_ref,
    labels_ref,
    cpu_fit0_ref, mem_fit0_ref, cpu_used0_ref, mem_used0_ref,
    pods_used0_ref, uport0_ref, uvola0_ref, uvolr0_ref, counts0_ref,
    choice_ref,
    cpu_fit_ref, mem_fit_ref, cpu_used_ref, mem_used_ref, pods_used_ref,
    uport_ref, uvola_ref, uvolr_ref, counts_ref,
):
    """One grid step = C pods, looped sequentially inside (TPU block
    shapes need >=8 sublanes, so per-pod grid steps are out); the
    occupancy carry lives in the OUTPUT refs, resident across the whole
    sequential grid."""
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        cpu_fit_ref[...] = cpu_fit0_ref[...]
        mem_fit_ref[...] = mem_fit0_ref[...]
        cpu_used_ref[...] = cpu_used0_ref[...]
        mem_used_ref[...] = mem_used0_ref[...]
        pods_used_ref[...] = pods_used0_ref[...]
        uport_ref[...] = uport0_ref[...]
        uvola_ref[...] = uvola0_ref[...]
        uvolr_ref[...] = uvolr0_ref[...]
        counts_ref[...] = counts0_ref[...]

    iota = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)
    # Per-chunk choice accumulator: (C//128, 128) i32, flat index j.
    ch_rows = C // 128
    ch_iota = (
        jax.lax.broadcasted_iota(jnp.int32, (ch_rows, 128), 0) * 128
        + jax.lax.broadcasted_iota(jnp.int32, (ch_rows, 128), 1)
    )
    cap_c = cpu_cap_ref[...]  # (1, N) f32
    cap_m = mem_cap_ref[...]
    cap_p = pods_cap_ref[...]
    cap_ci = cap_c.astype(jnp.int32)
    cap_mi = cap_m.astype(jnp.int32)
    w_lr, w_bra, w_spread = weights

    rows8_sel = jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0)

    def body(j, choices):
        # 8-aligned band + sublane select (dynamic sublane indices must
        # be provably 8-aligned on TPU).
        jbase = pl.multiple_of((j // 8) * 8, 8)
        band = packed_ref[pl.ds(jbase, 8), :]  # (8, L) i32
        rmask = (rows8_sel == j % 8).astype(jnp.int32)  # (8, 1)
        row = jnp.sum(band * rmask, axis=0)  # (L,) i32
        cpu_f = row[0].astype(jnp.float32)
        mem_f = row[1].astype(jnp.float32)
        zero = row[2]
        pin = row[3]
        svc = row[4]

        used_p = pods_used_ref[...]
        # -- predicates (ops/solver.py _feasible, default spec) -------
        fits_cpu = (cap_c == 0) | (cpu_fit_ref[...] + cpu_f <= cap_c)
        fits_mem = (cap_m == 0) | (mem_fit_ref[...] + mem_f <= cap_m)
        fits_count = used_p + 1 <= cap_p
        nonzero_ok = (over_ref[...] == 0) & fits_cpu & fits_mem & fits_count
        zero_ok = used_p < cap_p
        # Boolean algebra, not where(): Mosaic can't legalize
        # arith.select on i1 vectors.
        zb = zero != 0
        ok = (sched_ref[...] != 0) & ((zb & zero_ok) | (~zb & nonzero_ok))
        for w in range(SW):
            sw = row[_FIXED + w]
            ok = ok & ((sw & labels_ref[w : w + 1, :]) == sw)
        for w in range(PW):
            pw = row[_FIXED + SW + w]
            ok = ok & ((pw & uport_ref[w : w + 1, :]) == 0)
        for w in range(VW):
            va = row[_FIXED + SW + PW + w]
            vr = row[_FIXED + SW + PW + VW + w]
            ok = ok & (
                ((vr & uvola_ref[w : w + 1, :]) | (va & uvolr_ref[w : w + 1, :]))
                == 0
            )
        ok = ok & ((pin == -1) | (iota == pin))

        # -- priorities (ops/solver.py _scores, default spec) ---------
        req_c = (cpu_used_ref[...] + cpu_f).astype(jnp.int32)
        req_m = (mem_used_ref[...] + mem_f).astype(jnp.int32)
        total = jnp.zeros((1, N), jnp.int32)
        if w_lr:
            def calc(req, cap):
                raw = jnp.where(
                    cap > 0, ((cap - req) * 10) // jnp.maximum(cap, 1), 0
                )
                return jnp.where((cap == 0) | (req > cap), 0, raw)

            total = total + (
                (calc(req_c, cap_ci) + calc(req_m, cap_mi)) // 2
            ) * w_lr
        if w_bra:
            cfrac = jnp.where(cap_ci == 0, 1.0, req_c / jnp.maximum(cap_ci, 1))
            mfrac = jnp.where(cap_mi == 0, 1.0, req_m / jnp.maximum(cap_mi, 1))
            bra = jnp.where(
                (cfrac >= 1) | (mfrac >= 1),
                0,
                (10 - jnp.abs(cfrac - mfrac) * 10 + 1e-5).astype(jnp.int32),
            )
            total = total + bra * w_bra
        if w_spread:
            # Dynamic sublane indexing must be 8-aligned on TPU: load
            # the aligned 8-row band around the service's row, then
            # select the row with a sublane one-hot reduction.
            slot = jnp.maximum(svc, 0)
            base = pl.multiple_of((slot // 8) * 8, 8)
            band = counts_ref[pl.ds(base, 8), :].astype(jnp.int32)  # (8, N)
            rows = jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0)
            counts = jnp.sum(
                band * (rows == slot % 8).astype(jnp.int32),
                axis=0,
                keepdims=True,
            )
            maxc = jnp.max(counts)
            spread_raw = (10 * (maxc - counts)) // jnp.maximum(maxc, 1)
            spread = jnp.where((svc < 0) | (maxc == 0), 10, spread_raw)
            total = total + spread * w_spread

        # -- select: first max by lowest index (generic.select_host) --
        # One reduction instead of three (max, tie-break min-index,
        # feasibility test): pack (score, inverted index) into one i32.
        # Among equal scores the larger 8191-idx — i.e. the LOWEST
        # index — wins, exactly the scalar oracle's tie-break. Scores
        # are bounded (<= 30 on the default spec) and N <= 8192 is an
        # eligibility requirement, so the pack cannot overflow or
        # collide. Infeasible nodes encode as -1, strictly below every
        # feasible encoding (score >= 0 => enc >= 8191 - idx >= 0).
        enc = jnp.where(ok, total * 8192 + (8191 - iota), -1)
        m = jnp.max(enc)
        choice = jnp.where(m >= 0, 8191 - (m & 8191), jnp.int32(-1))

        # -- commit (ops/solver.py _commit) ----------------------------
        assigned = choice >= 0
        onehot_b = (iota == choice) & assigned
        onehot_f = onehot_b.astype(jnp.float32)
        cpu_fit_ref[...] = cpu_fit_ref[...] + onehot_f * cpu_f
        mem_fit_ref[...] = mem_fit_ref[...] + onehot_f * mem_f
        cpu_used_ref[...] = cpu_used_ref[...] + onehot_f * cpu_f
        mem_used_ref[...] = mem_used_ref[...] + onehot_f * mem_f
        pods_used_ref[...] = pods_used_ref[...] + onehot_f
        for w in range(PW):
            pw = row[_FIXED + SW + w]
            uport_ref[w : w + 1, :] = jnp.where(
                onehot_b, uport_ref[w : w + 1, :] | pw, uport_ref[w : w + 1, :]
            )
        for w in range(VW):
            va = row[_FIXED + SW + PW + w]
            vr = row[_FIXED + SW + PW + VW + w]
            uvola_ref[w : w + 1, :] = jnp.where(
                onehot_b, uvola_ref[w : w + 1, :] | va, uvola_ref[w : w + 1, :]
            )
            uvolr_ref[w : w + 1, :] = jnp.where(
                onehot_b, uvolr_ref[w : w + 1, :] | vr, uvolr_ref[w : w + 1, :]
            )
        onehot_i32 = onehot_b.astype(jnp.int32)
        rows8 = jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0)
        for k in range(K):
            sid = row[_FIXED + SW + PW + 2 * VW + k]
            valid = (sid >= 0) & assigned
            slot = jnp.maximum(sid, 0)
            base = pl.multiple_of((slot // 8) * 8, 8)
            band = counts_ref[pl.ds(base, 8), :]  # (8, N) i16
            # Mask product in i32 (this TPU's VPU has no i16 multiply),
            # cast to i16 for the add (i16 add IS supported).
            rmask = (rows8 == slot % 8).astype(jnp.int32)  # (8, 1)
            vmask = jnp.where(valid, onehot_i32, 0)  # (1, N) i32
            counts_ref[pl.ds(base, 8), :] = band + (rmask * vmask).astype(
                jnp.int16
            )
        return jnp.where(ch_iota == j, choice, choices)

    choices = jax.lax.fori_loop(
        0, C, body, jnp.full((ch_rows, 128), -1, jnp.int32)
    )
    choice_ref[...] = choices


@traced_jit(static_argnames=("weights", "interpret"))
def _solve_packed(pods, nodes, weights, interpret=False):
    """Prep (pack/transpose/cast) + pallas_call + carry rebuild, fused
    under one jit."""
    P = pods["cpu"].shape[0]
    N = nodes["cpu_cap"].shape[0]
    S = nodes["svc_counts"].shape[1]
    SW = pods["sel"].shape[1]
    PW = pods["port"].shape[1]
    VW = pods["vol_any"].shape[1]
    K = pods["svc_ids"].shape[1]

    packed = _pack_pods(pods)  # (P, L) i32
    L = packed.shape[1]
    # Chunk size per grid step: the largest divisor of P that is a
    # multiple of 128 (choice blocks need 128 lanes) and <= 1024. The
    # pod axis is always a multiple of 128 (matrices._pod_axis_bucket),
    # so C=128 is guaranteed to exist.
    C = 128
    for cand in (1024, 896, 768, 640, 512, 384, 256, 128):
        if cand <= P and P % cand == 0:
            C = cand
            break
    assert P % C == 0 and C % 128 == 0, (P, C)
    G = P // C

    row1 = lambda a, dt=None: (a if dt is None else a.astype(dt)).reshape(1, N)
    consts = [
        row1(nodes["cpu_cap"]),
        row1(nodes["mem_cap"]),
        row1(nodes["pods_cap"]),
        row1(nodes["over"], jnp.int32),
        row1(nodes["sched"], jnp.int32),
        nodes["labels"].astype(jnp.int32).T,  # (LW, N)
    ]
    # Service axis padded to the kernel's 8-row band granularity (and a
    # floor of 8): SolverSession carries unpadded S (even S=1 with no
    # services), and a dynamic 8-row band must never clamp into a
    # NEIGHBOR service's counts.
    SP = _svc_pad(S)
    counts0 = nodes["svc_counts"].astype(jnp.int16).T  # (S, N)
    if SP != S:
        counts0 = jnp.pad(counts0, [(0, SP - S), (0, 0)])
    init = [
        row1(nodes["cpu_fit"]),
        row1(nodes["mem_fit"]),
        row1(nodes["cpu_used"]),
        row1(nodes["mem_used"]),
        row1(nodes["pods_used"]),
        nodes["uport"].astype(jnp.int32).T,  # (PW, N)
        nodes["uvol_any"].astype(jnp.int32).T,
        nodes["uvol_rw"].astype(jnp.int32).T,
        counts0,  # (SP, N)
    ]
    LW = consts[5].shape[0]

    full = lambda shape: pl.BlockSpec(shape, lambda g: (0, 0))
    out_shapes = [
        jax.ShapeDtypeStruct((P // 128, 128), jnp.int32),  # choice, flat j
        jax.ShapeDtypeStruct((1, N), jnp.float32),
        jax.ShapeDtypeStruct((1, N), jnp.float32),
        jax.ShapeDtypeStruct((1, N), jnp.float32),
        jax.ShapeDtypeStruct((1, N), jnp.float32),
        jax.ShapeDtypeStruct((1, N), jnp.float32),
        jax.ShapeDtypeStruct((PW, N), jnp.int32),
        jax.ShapeDtypeStruct((VW, N), jnp.int32),
        jax.ShapeDtypeStruct((VW, N), jnp.int32),
        jax.ShapeDtypeStruct((SP, N), jnp.int16),
    ]
    out_specs = [
        pl.BlockSpec((C // 128, 128), lambda g: (g, 0)),
        full((1, N)), full((1, N)), full((1, N)), full((1, N)), full((1, N)),
        full((PW, N)), full((VW, N)), full((VW, N)), full((SP, N)),
    ]
    in_specs = (
        [pl.BlockSpec((C, L), lambda g: (g, 0))]
        + [full((1, N))] * 5
        + [full((LW, N))]
        + [full((1, N))] * 5
        + [full((PW, N)), full((VW, N)), full((VW, N)), full((SP, N))]
    )
    kernel = functools.partial(
        _kernel, SW, PW, VW, K, N, SP, C, tuple(weights)
    )
    outs = pl.pallas_call(
        kernel,
        grid=(G,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(packed, *consts, *init)

    choice = outs[0].reshape(P)
    new_nodes = dict(nodes)
    new_nodes["cpu_fit"] = outs[1].reshape(N)
    new_nodes["mem_fit"] = outs[2].reshape(N)
    new_nodes["cpu_used"] = outs[3].reshape(N)
    new_nodes["mem_used"] = outs[4].reshape(N)
    new_nodes["pods_used"] = outs[5].reshape(N)
    new_nodes["uport"] = outs[6].T.astype(nodes["uport"].dtype)
    new_nodes["uvol_any"] = outs[7].T.astype(nodes["uvol_any"].dtype)
    new_nodes["uvol_rw"] = outs[8].T.astype(nodes["uvol_rw"].dtype)
    new_nodes["svc_counts"] = outs[9][:S].T.astype(nodes["svc_counts"].dtype)
    return choice, new_nodes


def solve_with_state_pallas(
    pods: Dict, nodes: Dict, weights=(1, 1, 1), interpret: bool = False
) -> Tuple[jnp.ndarray, Dict]:
    """Drop-in for solver.solve_with_state on the default spec."""
    return _solve_packed(pods, nodes, tuple(weights), interpret=interpret)


def solve_pallas(pods: Dict, nodes: Dict, weights=(1, 1, 1), interpret: bool = False):
    choice, _ = _solve_packed(pods, nodes, tuple(weights), interpret=interpret)
    return choice
