"""Pallas TPU kernel for the sequential-parity scan solver.

The XLA lax.scan in ops/solver.py is latency-bound: each of the P steps
touches ~500KB of occupancy state in HBM and pays the scan's
per-iteration sequencing (~23us/step at 50k x 5k). That state fits in
VMEM with room to spare, which is exactly the case SURVEY.md §7 step 7
reserves for a hand kernel ("pallas kernels only where XLA fusion falls
short"). This kernel runs the ENTIRE sequential solve as one
pallas_call:

- grid = (P / C,): TPU grid steps execute sequentially on a core, so
  the per-node occupancy carry lives in the OUTPUT refs (constant
  index_map keeps them VMEM-resident across all steps; they flush to
  HBM once at the end — the standard accumulator pattern).
- pod columns are packed host-side into ONE i32 row per pod
  (scalars + selector/port/volume bitset words + service top-K), so
  each grid step fetches a single tiny block instead of ~10.
- service spreading counts are (S_pad, N) int16 (counts are bounded by
  pods_cap <= 110, so int16 is exact; Mosaic vector arithmetic supports
  i16/i32, not i8) and stay in HBM, aliased input to output: their
  size grows with the number of Services (164 MB at 10,000 Services
  over 8,192 node slots), far past VMEM. Each pod with a Service reads
  the 8-row band holding its Service's counts by DMA — the next pod's
  band streams in while the current pod is scored — and writes back
  the bands its placement changes. Pods with no Service move no band.
  This is the layout SolverSession keeps on the device
  (``to_bands``); the (N, S) f32 schema of the XLA scan converts at
  the boundary of the drop-ins below.

Decision parity: the kernel reproduces ops/solver.py's default-spec
math op for op (integer LeastRequested, f32 BalancedResourceAllocation
with the same +1e-5 boundary epsilon, integer ServiceSpreading,
first-max-by-lowest-index tie-break). tests/test_pallas_scan.py checks
bit-identical assignments and carries against the XLA scan (interpret
mode on CPU, the real kernel on TPU); policy specs and sharded meshes
run the XLA path (ops/solver.py chooses).

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
from jax.experimental.pallas import tpu as pltpu

from kubernetes_tpu.ops.ledger import traced_jit
from kubernetes_tpu.utils import metrics

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

#: Rows of the service-count carry one DMA moves: dynamic sublane
#: offsets must be 8-aligned on TPU.
BAND = 8

#: Device solves of the sequential-parity policy, by the kernel that
#: ran them: ``pallas`` (this module) or ``xla_scan`` (ops/solver.py),
#: and why (``eligible``, or what turned the kernel away).
SOLVES = metrics.DEFAULT.counter(
    "scheduler_device_solves_total",
    "Sequential-parity solves dispatched, by kernel and reason.",
    labels=("kernel", "reason"),
)


def _svc_pad(S: int) -> int:
    """Service axis inside the kernel: whole 8-row bands, at least one."""
    return max(BAND, ((S + BAND - 1) // BAND) * BAND)


def _vmem_bytes(N: int, S: int, LW: int, PW: int, VW: int) -> int:
    """Resident bytes: the word carries appear as BOTH a full input
    block and a full output block (so 2x); f32 rows are cheap but
    counted; three (8, N) int16 band buffers, laid out in 16-row tiles.
    The S Services add nothing: their counts stay in HBM."""
    bands = 3 * 2 * BAND * N * 2
    words = 2 * (PW + 2 * VW) * N * 4 + LW * N * 4
    rows = (5 + 2 * 5 + 1) * N * 4  # consts + init+carry f32 rows
    return bands + words + rows


#: Largest node axis the kernel takes: the packed (score, 8191-idx)
#: select needs N <= 8192.
MAX_NODES = 8192


@functools.lru_cache(maxsize=None)
def _log_ineligible(reason: str) -> None:
    """Once per distinct reason: a TPU solve the kernel cannot take runs
    the slower XLA scan, and that must show in the log."""
    _LOG.warning("pallas scan not eligible (%s): running the XLA scan", reason)


def shape_reason(N: int, LW: int, PW: int, VW: int) -> str:
    """'' when the kernel takes an N-slot cluster with these bitset
    widths on one TPU, else the reason label (``nodes``, ``vmem``),
    logged once with its numbers."""
    if N > MAX_NODES:
        _log_ineligible(f"N={N} > {MAX_NODES} nodes")
        return "nodes"
    vmem = _vmem_bytes(N, 0, LW, PW, VW)
    if vmem > VMEM_BUDGET_BYTES:
        _log_ineligible(
            f"N={N} LW={LW} PW={PW} VW={VW} needs {vmem} B of VMEM "
            f"> {VMEM_BUDGET_BYTES}"
        )
        return "vmem"
    return ""


def _device_reason(n_devices: int, platform: str) -> str:
    """'' when the solve runs on one TPU device and the kernel is not
    switched off (``KTPU_PALLAS=off``), else the reason label."""
    if os.environ.get("KTPU_PALLAS", "") == "off":
        return "disabled"
    if n_devices != 1:
        return "mesh"
    if platform != "tpu":
        return "platform"
    return ""


def pallas_reason(nodes: Dict, lspec) -> str:
    """'' when the kernel takes this solve — default spec, one unsharded
    TPU device, shapes it fits — else the reason label, decided from
    explicit checks on the node carry's placement and shape."""
    from kubernetes_tpu.models.algspec import DEFAULT_LOWERED

    if lspec != DEFAULT_LOWERED:
        return "policy"  # policy columns: XLA scan carries them
    arr = nodes["cpu_cap"]
    if not isinstance(arr, jax.Array):
        return "host_arrays"  # jit places them on the default device
    if isinstance(arr, jax.core.Tracer):
        return "traced"  # traced into an enclosing jit: placement unknown
    devices = arr.sharding.device_set
    platform = next(iter(devices)).platform
    return _device_reason(len(devices), platform) or shape_reason(
        arr.shape[0],
        nodes["labels"].shape[1],
        nodes["uport"].shape[1],
        nodes["uvol_any"].shape[1],
    )


def session_reason(mesh, N: int, LW: int, PW: int, VW: int) -> str:
    """``pallas_reason`` for a SolverSession's default-spec ticks, from
    the mesh (None: the default device) and the shapes it will place its
    carry on — decided before the upload, which takes the kernel's
    layout when this is ''."""
    if mesh is None:
        n_devices, platform = 1, jax.default_backend()
    else:
        n_devices, platform = mesh.devices.size, mesh.devices.flat[0].platform
    return _device_reason(n_devices, platform) or shape_reason(N, LW, PW, VW)


def count_solve(reason: str) -> bool:
    """Count one device solve under the kernel ``reason`` picks ('' =
    this kernel); True when it is this kernel."""
    SOLVES.inc(
        kernel="xla_scan" if reason else "pallas", reason=reason or "eligible"
    )
    return not reason


def to_bands(svc_counts):
    """(N, S) f32 counts -> the kernel's (S_pad, N) int16 carry. Works on
    numpy (host staging) and jax arrays alike."""
    xp = np if isinstance(svc_counts, np.ndarray) else jnp
    S = svc_counts.shape[1]
    bands = svc_counts.astype(xp.int16).T
    return xp.pad(bands, [(0, _svc_pad(S) - S), (0, 0)])


def from_bands(bands, S: int):
    """The kernel's (S_pad, N) int16 carry -> the XLA scan's (N, S) f32."""
    return bands[:S].T.astype(jnp.float32)


def count_points(svc_counts: np.ndarray, entries, width: int):
    """Host staging for ``scatter_rows_banded``: the (Service, node
    slot) ``entries`` of the (N, S) host counts, as (svc i32[width],
    slot i32[width], value i16[width]), padded by repeating the last
    entry (the same write twice)."""
    entries = list(entries)
    entries += [entries[-1]] * (width - len(entries))
    svc = np.fromiter((s for s, _ in entries), np.int32, count=width)
    slot = np.fromiter((j for _, j in entries), np.int32, count=width)
    return svc, slot, svc_counts[slot, svc].astype(np.int16)


@traced_jit(donate_argnames=("counts",))
def scatter_rows_banded(counts, svc, slot, value):
    """The service-count half of the session's dirty-row scatter on the
    kernel's (S_pad, N) int16 carry: point writes of the (Service, node
    slot) entries a delete changed, in place (the carry is donated), so
    a delete moves its own entries and no band or column.
    incremental._scatter_rows patches the per-node leaves."""
    return counts.at[svc, slot].set(value)


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
    SW: int, PW: int, VW: int, K: int, N: int, C: int, weights,
    packed_ref,
    cpu_cap_ref, mem_cap_ref, pods_cap_ref, over_ref, sched_ref,
    labels_ref,
    cpu_fit0_ref, mem_fit0_ref, cpu_used0_ref, mem_used0_ref,
    pods_used0_ref, uport0_ref, uvola0_ref, uvolr0_ref, _counts_in_ref,
    choice_ref,
    cpu_fit_ref, mem_fit_ref, cpu_used_ref, mem_used_ref, pods_used_ref,
    uport_ref, uvola_ref, uvolr_ref, counts_ref,
    band_ref, next_ref, other_ref, sem,
):
    """One grid step = C pods, looped sequentially inside (TPU block
    shapes need >=8 sublanes, so per-pod grid steps are out); the
    per-node carry lives in the OUTPUT refs, resident across the whole
    sequential grid. ``counts_ref`` is the HBM count carry (aliased to
    the input, so every read and write goes through it).

    Band buffers: ``band_ref`` holds the current pod's band, as
    committed so far; ``next_ref`` receives the next pod's band while
    the current pod is scored; ``other_ref`` stages a band the pod
    commits to that is neither. A band written back is always written
    from the VMEM copy that is current, and only after every read of
    HBM has landed, so no read sees a stale band: the next pod's band
    is never fetched when it is the current band (it is reused), and a
    fetched one is patched by the current pod's commit before it is
    used."""
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
    rows8 = jax.lax.broadcasted_iota(jnp.int32, (BAND, 1), 0)
    SVC_IDS = _FIXED + SW + PW + 2 * VW

    def pod_row(j):
        # 8-aligned band + sublane select (dynamic sublane indices must
        # be provably 8-aligned on TPU).
        jbase = pl.multiple_of((j // 8) * 8, 8)
        band = packed_ref[pl.ds(jbase, 8), :]  # (8, L) i32
        return jnp.sum(band * (rows8 == j % 8).astype(jnp.int32), axis=0)

    def base_of(s):
        return pl.multiple_of((jnp.maximum(s, 0) // BAND) * BAND, BAND)

    def hbm(base):
        return counts_ref.at[pl.ds(base, BAND), :]

    def fetch(base, dst, slot):
        return pltpu.make_async_copy(hbm(base), dst, sem.at[slot])

    def store(src, base):
        cp = pltpu.make_async_copy(src, hbm(base), sem.at[2])
        cp.start()
        cp.wait()

    # The chunk's first pod: its band, synchronously (every earlier
    # write has landed).
    row0 = pod_row(0)

    @pl.when(row0[4] >= 0)
    def _first_band():
        cp = fetch(base_of(row0[4]), band_ref, 0)
        cp.start()
        cp.wait()

    def body(j, carry):
        choices, row = carry
        cpu_f = row[0].astype(jnp.float32)
        mem_f = row[1].astype(jnp.float32)
        zero = row[2]
        pin = row[3]
        svc = row[4]
        has = svc >= 0
        base = base_of(svc)
        # The next pod's band streams in while this pod is scored,
        # unless it is this pod's band (then band_ref carries over).
        nrow = pod_row(jnp.minimum(j + 1, C - 1))
        nsvc = nrow[4]
        nbase = base_of(nsvc)
        prefetch = (j + 1 < C) & (nsvc >= 0) & ~(has & (nbase == base))

        @pl.when(prefetch)
        def _start_next():
            fetch(nbase, next_ref, 1).start()

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
            # The service's row of its band, by a sublane one-hot
            # reduction (band_ref holds garbage when svc < 0, which the
            # final select discards).
            counts = jnp.sum(
                band_ref[...].astype(jnp.int32)
                * (rows8 == jnp.maximum(svc, 0) % BAND).astype(jnp.int32),
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

        # The prefetch lands before any band is written back.
        @pl.when(prefetch)
        def _wait_next():
            fetch(nbase, next_ref, 1).wait()

        onehot_i32 = onehot_b.astype(jnp.int32)
        for k in range(K):
            sid = row[SVC_IDS + k]
            valid = (sid >= 0) & assigned
            sbase = base_of(sid)
            in_band = has & (sbase == base)
            in_next = prefetch & (sbase == nbase)

            def add(ref, sid=sid):
                # Mask product in i32 (this TPU's VPU has no i16
                # multiply), cast to i16 for the add (i16 add IS
                # supported).
                rmask = (rows8 == sid % BAND).astype(jnp.int32)  # (8, 1)
                ref[...] = ref[...] + (rmask * onehot_i32).astype(jnp.int16)

            @pl.when(valid & in_band)
            def _commit_band(sbase=sbase):
                add(band_ref)
                store(band_ref, sbase)

            @pl.when(valid & in_next)
            def _commit_next(sbase=sbase):
                add(next_ref)
                store(next_ref, sbase)

            @pl.when(valid & ~in_band & ~in_next)
            def _commit_other(sbase=sbase):
                cp = fetch(sbase, other_ref, 0)
                cp.start()
                cp.wait()
                add(other_ref)
                store(other_ref, sbase)

        @pl.when(prefetch)
        def _advance():
            band_ref[...] = next_ref[...]

        return jnp.where(ch_iota == j, choice, choices), nrow

    choices, _ = jax.lax.fori_loop(
        0, C, body, (jnp.full((ch_rows, 128), -1, jnp.int32), row0)
    )
    choice_ref[...] = choices


@traced_jit(static_argnames=("weights", "interpret"), donate_argnames=("counts",))
def _solve_packed(pods, nodes, counts, weights, interpret=False):
    """Prep (pack/transpose) + pallas_call + carry rebuild, fused under
    one jit. ``counts`` is the (S_pad, N) int16 service-count carry,
    donated: the kernel updates it in place and returns it."""
    P = pods["cpu"].shape[0]
    N = nodes["cpu_cap"].shape[0]
    SP = counts.shape[0]
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
    assert SP % BAND == 0, SP
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
    init = [
        row1(nodes["cpu_fit"]),
        row1(nodes["mem_fit"]),
        row1(nodes["cpu_used"]),
        row1(nodes["mem_used"]),
        row1(nodes["pods_used"]),
        nodes["uport"].astype(jnp.int32).T,  # (PW, N)
        nodes["uvol_any"].astype(jnp.int32).T,
        nodes["uvol_rw"].astype(jnp.int32).T,
    ]
    LW = consts[5].shape[0]

    full = lambda shape: pl.BlockSpec(shape, lambda g: (0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
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
        full((PW, N)), full((VW, N)), full((VW, N)), in_hbm,
    ]
    in_specs = (
        [pl.BlockSpec((C, L), lambda g: (g, 0))]
        + [full((1, N))] * 5
        + [full((LW, N))]
        + [full((1, N))] * 5
        + [full((PW, N)), full((VW, N)), full((VW, N)), in_hbm]
    )
    kernel = functools.partial(_kernel, SW, PW, VW, K, N, C, tuple(weights))
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(G,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((BAND, N), jnp.int16),
                pltpu.VMEM((BAND, N), jnp.int16),
                pltpu.VMEM((BAND, N), jnp.int16),
                pltpu.SemaphoreType.DMA((3,)),
            ],
        ),
        out_shape=out_shapes,
        input_output_aliases={len(in_specs) - 1: len(out_specs) - 1},
        interpret=interpret,
    )(packed, *consts, *init, counts)

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
    return choice, new_nodes, outs[9]


def solve_banded(
    pods: Dict, nodes: Dict, weights=(1, 1, 1), interpret: bool = False
) -> Tuple[jnp.ndarray, Dict]:
    """The session's tick: ``nodes["svc_counts"]`` is already the
    kernel's (S_pad, N) int16 carry (``to_bands``) and comes back in it,
    updated in place (donated). No other leaf is consumed."""
    rest = {k: v for k, v in nodes.items() if k != "svc_counts"}
    choice, new_nodes, counts = _solve_packed(
        pods, rest, nodes["svc_counts"], tuple(weights), interpret=interpret
    )
    new_nodes["svc_counts"] = counts
    return choice, new_nodes


def solve_with_state_pallas(
    pods: Dict, nodes: Dict, weights=(1, 1, 1), interpret: bool = False
) -> Tuple[jnp.ndarray, Dict]:
    """Drop-in for solver.solve_with_state on the default spec: the
    (N, S) f32 count schema in and out, converted at the boundary."""
    S = nodes["svc_counts"].shape[1]
    banded = dict(nodes, svc_counts=to_bands(nodes["svc_counts"]))
    choice, out = solve_banded(pods, banded, weights, interpret)
    out["svc_counts"] = from_bands(out["svc_counts"], S)
    return choice, out


def solve_pallas(pods: Dict, nodes: Dict, weights=(1, 1, 1), interpret: bool = False):
    choice, _ = solve_with_state_pallas(pods, nodes, weights, interpret)
    return choice
