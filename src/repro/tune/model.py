"""Analytical cost model that seeds and prunes the empirical sweep.

Per "Co-Design of the Dense Linear Algebra Software Stack" (PAPERS.md) the
tuning search should be *model-seeded*: a cheap analytical ranking picks the
few candidates worth measuring, and only those hit the wall clock.  The
model reuses the roofline flop/byte accounting constants from
:mod:`repro.launch.roofline` (peak FLOP/s, HBM bandwidth) and adds the two
empirical facts the paper's §5/§6.1 analysis turns on:

* the trailing update runs near BLAS-3 peak (``GEMM_EFF``), while the
  unblocked panel factorization is latency-bound and runs orders of
  magnitude below it (``PANEL_EFF``) — this is what makes small ``b`` lose;
* per-iteration combination depends on the scheduling variant: ``mtb``
  serializes panel and update, ``la``/``la_mb`` overlap them
  (``max(PF, TU)``, paper §4), and ``rtm`` pays a per-task overhead for its
  fragmented trailing update (paper §3.3).

Absolute predictions are not the point — only the *ranking* feeds the
search, and the search always measures the fixed-``b`` baseline too.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax.numpy as jnp

from repro.core.blocking import BlockSpec, expand_schedule, panel_steps
from repro.launch.roofline import HBM_BW, PEAK_FLOPS

__all__ = ["Machine", "MACHINE", "gemm_attainment", "gemm_blocks", "predict",
           "rank", "step_costs", "TILE_TASK_COSTS"]


# ---------------------------------------------------------------------------
# The machine description — ONE source of truth for the §9-style roofline
# constants AND the VMEM geometry the Pallas kernels block for.  The paper's
# §2 sizes (n_c, k_c, m_c) from cache capacities and §6.1 quotes the machine
# table once; everything downstream (``repro.kernels.blis_gemm.pick_blocks``,
# the VMEM panel budget in ``repro.kernels.ops``, the GEMM attainment term
# of :func:`predict`) derives from this record instead of re-quoting
# numbers.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Machine:
    """Roofline + memory-hierarchy constants of the target chip (v5e)."""

    peak_flops: float = PEAK_FLOPS        # bf16 FLOP/s per chip
    hbm_bw: float = HBM_BW                # bytes/s per chip
    vmem_bytes: int = 16 * 1024 * 1024    # VMEM per core
    #: working-set ceiling for the BLIS GEMM tiles (double-buffered A_c/B_c
    #: + f32 accumulator) — vmem_bytes minus headroom for spills/pipeline.
    vmem_budget_bytes: int = 12 * 1024 * 1024
    #: ceiling for a whole-panel single-cell kernel (panel + outputs); the
    #: ``ops.py`` wrappers fall back to the traced panels above this.
    vmem_panel_budget_bytes: int = 10 * 1024 * 1024
    #: scoped VMEM a whole-panel kernel asks Mosaic for.  ``vmem_bytes`` is
    #: Mosaic's default scope; a panel at the budget above, double-buffered
    #: in and out, needs about three times the budget (a 4096 × 256 f32 LU
    #: panel takes 18 MiB).  Half of the v5e core's 128 MiB of VMEM.
    vmem_kernel_limit_bytes: int = 64 * 1024 * 1024
    lane: int = 128                       # MXU/VPU lane width (last dim)
    sublane_f32: int = 8                  # second-minor tile, f32
    sublane_bf16: int = 16                # second-minor tile, bf16
    mxu: int = 128                        # systolic array edge

    def sublane(self, dtype) -> int:
        dt = jnp.dtype(dtype)
        if dt == jnp.dtype(jnp.bfloat16):
            return self.sublane_bf16
        return self.sublane_f32

    @property
    def ridge_flops_per_byte(self) -> float:
        """Arithmetic intensity at which compute and HBM traffic balance."""
        return self.peak_flops / self.hbm_bw


MACHINE = Machine()


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def gemm_blocks(m: int, n: int, k: int, dtype,
                target=(512, 512, 512),
                machine: Machine = MACHINE) -> Tuple[int, int, int]:
    """(bm, bn, bk) for the BLIS five-loop kernel, derived from ``machine``.

    The §2/§9 derivation: align to the (sublane, lane) tile grid, then
    shrink until the double-buffered A_c/B_c tiles plus the f32 accumulator
    fit the VMEM budget — shrinking ``bk`` first (stream more K steps; K is
    the sequential grid dim so this costs latency, not traffic), then ``bn``
    then ``bm``.  ``repro.kernels.blis_gemm.pick_blocks`` delegates here —
    the kernel layer holds no machine numbers of its own.
    """
    itemsize = jnp.dtype(dtype).itemsize
    sub = machine.sublane(dtype)
    lane = machine.lane
    bm = min(_round_up(m, sub), target[0])
    bn = min(_round_up(n, lane), target[1])
    bk = min(_round_up(k, lane), target[2])

    def footprint(bm, bn, bk):
        return 2 * (bm * bk + bk * bn) * itemsize + bm * bn * 4

    while footprint(bm, bn, bk) > machine.vmem_budget_bytes and bk > lane:
        bk //= 2
    while footprint(bm, bn, bk) > machine.vmem_budget_bytes and bn > lane:
        bn //= 2
    while footprint(bm, bn, bk) > machine.vmem_budget_bytes and bm > sub:
        bm //= 2
    return bm, bn, bk


def gemm_attainment(m: int, n: int, k: int, dtype,
                    blocks: Optional[Tuple[int, int, int]] = None,
                    machine: Machine = MACHINE) -> float:
    """Roofline attainment (fraction of peak) of a blocked m×k·k×n GEMM.

    Traffic model of the five-loop structure: every A tile is re-read once
    per ``bn`` column block and every B tile once per ``bm`` row block
    (C is written exactly once — the accumulator stays in VMEM across K):

        bytes = itemsize · (m·k·⌈n/bn⌉ + k·n·⌈m/bm⌉) + m·n·itemsize

    Attainment = min(1, intensity / ridge) with intensity = 2mnk / bytes —
    the §9 ingredient :func:`predict` uses to scale GEMM efficiency per
    kernel-blocking candidate.
    """
    if blocks is None:
        blocks = gemm_blocks(m, n, k, dtype, machine=machine)
    bm, bn, _ = blocks
    itemsize = jnp.dtype(dtype).itemsize
    n_reads = -(-n // max(bn, 1))
    m_reads = -(-m // max(bm, 1))
    traffic = itemsize * (m * k * n_reads + k * n * m_reads) + m * n * itemsize
    intensity = 2.0 * m * n * k / max(traffic, 1.0)
    return min(1.0, intensity / machine.ridge_flops_per_byte)

# Effective fraction of bf16 peak for BLAS-3 trailing updates, per backend.
# The Pallas kernels run interpreted on CPU (DESIGN.md §2) — heavily derated
# so the model never sends the sweep there unless asked to.
GEMM_EFF = {"jnp": 0.80, "pallas": 0.05}
# The unblocked panel is a sequential fori_loop of rank-1 updates.
PANEL_EFF = 0.01
# Fixed per-iteration dispatch cost and the RTM per-tile task overhead.
STEP_OVERHEAD_S = 2e-6
RTM_TASK_OVERHEAD_S = 1e-6
# Per-task dispatch cost of the tile-DAG executor (DESIGN.md §16) — same
# order as the RTM fragmentation it generalizes.
TILE_TASK_OVERHEAD_S = 1e-6


def _peak_flops(dtype) -> float:
    """Scale the bf16 roofline peak by element width (MXU-style)."""
    itemsize = jnp.dtype(dtype).itemsize
    return PEAK_FLOPS * 2.0 / max(itemsize, 2)


# ---------------------------------------------------------------------------
# Per-step (panel_flops, update_flops, update_bytes) decompositions.
# `k, bk` come from the PanelStep; `n` is the traversal width.
# ---------------------------------------------------------------------------
def _lu(n: int, k: int, bk: int, itemsize: int):
    r = n - k - bk
    pf = 2.0 * bk * bk * (n - k)                     # GETF2 rank-1 sweep
    tu = bk * bk * r + 2.0 * bk * r * r              # TRSM + GEMM
    byts = 3.0 * r * (r + bk) * itemsize             # read/update/write trailing
    return pf, tu, byts


def _cholesky(n: int, k: int, bk: int, itemsize: int):
    r = n - k - bk
    pf = bk * bk * (n - k)
    tu = bk * bk * r + bk * r * r                    # TRSM + half-GEMM (syrk)
    byts = 1.5 * r * (r + bk) * itemsize
    return pf, tu, byts


def _qr(n: int, k: int, bk: int, itemsize: int):
    r = n - k - bk
    m = n - k                                        # panel rows
    pf = 4.0 * bk * bk * m                           # GEQR2 + T build
    tu = 4.0 * bk * m * r                            # two GEMMs of the WY apply
    byts = 3.0 * m * r * itemsize
    return pf, tu, byts


def _gauss_jordan(n: int, k: int, bk: int, itemsize: int):
    pf = 2.0 * bk * bk * n                           # D⁻¹ + M build
    tu = 2.0 * bk * n * (n - bk)                     # update of ALL other cols
    byts = 3.0 * n * n * itemsize
    return pf, tu, byts


def _band_reduction(n: int, k: int, bk: int, itemsize: int):
    r = n - k - bk
    m = n - k
    pf = 8.0 * bk * bk * m                           # left QR + right LQ panels
    tu = 8.0 * bk * m * r                            # both two-sided updates
    byts = 4.0 * m * r * itemsize
    return pf, tu, byts


def _qrcp(n: int, k: int, bk: int, itemsize: int):
    # GEQP3: the panel is *expensive* — every reflector's F column is a
    # GEMV over the whole trailing block (half the factorization's flops
    # live in PF, which is why the paper flags QRCP for look-ahead)
    r = n - k - bk
    m = n - k
    pf = 4.0 * bk * m * (n - k)                      # F GEMVs + pivot rows
    tu = 2.0 * bk * m * r                            # deferred V·Fᵀ GEMM
    byts = 3.0 * m * r * itemsize
    return pf, tu, byts


def _qrcp_local(n: int, k: int, bk: int, itemsize: int):
    # Windowed pivoting (DESIGN.md §12): the pivot search never leaves the
    # panel, so the panel cost collapses from GEQP3's trailing-wide F GEMVs
    # to GEQR2-plus-pivot-bookkeeping — the same O(m·b²) shape as QR, which
    # is exactly what makes its (legal) look-ahead worth scheduling.
    r = n - k - bk
    m = n - k
    pf = 5.0 * bk * bk * m                           # GEQR2 + F + norm track
    tu = 4.0 * bk * m * r                            # two GEMMs of the WY apply
    byts = 3.0 * m * r * itemsize
    return pf, tu, byts


def _hessenberg(n: int, k: int, bk: int, itemsize: int):
    # GEHRD: panel dominated by the per-column A₀·v GEMVs over the full
    # matrix; the trailing update is two-sided (right over all n rows)
    r = n - k - bk
    pf = 2.0 * bk * n * (n - k)                      # W = A₀·V build
    tu = 6.0 * bk * n * r                            # right + left WY GEMMs
    byts = 4.0 * n * r * itemsize
    return pf, tu, byts


STEP_COSTS: Dict[str, Callable] = {
    "lu": _lu,
    "cholesky": _cholesky,
    "qr": _qr,
    "ldlt": _cholesky,                               # same BLAS-3 shape
    "gauss_jordan": _gauss_jordan,
    "band_reduction": _band_reduction,
    "qrcp": _qrcp,
    "qrcp_local": _qrcp_local,
    "hessenberg": _hessenberg,
}


# ---------------------------------------------------------------------------
# §9 cost entries for the tile task kinds (DESIGN.md §16).  Each entry maps
# the tile widths (w_k, w_i, w_j) of a task keyed (k, i, j) to
# (flops, bytes, class): "panel" tasks are the sequential fori-loop kernels
# (GEQR2/LARFT, unblocked Cholesky) running at PANEL_EFF; "gemm" tasks are
# BLAS-3 tile ops at the backend's GEMM efficiency with an HBM traffic term.
# ---------------------------------------------------------------------------
TILE_TASK_COSTS: Dict[str, Callable] = {
    # GEQR2 + T build on the w_k × w_k diagonal tile
    "GEQRT": lambda wk, wi, wj, it: (4.0 * wk * wk * wk, 0.0, "panel"),
    # GEQR2 + T on the stacked (w_k + w_i) × w_k pair (non-structured TSQRT)
    "TSQRT": lambda wk, wi, wj, it: (4.0 * (wk + wi) * wk * wk, 0.0, "panel"),
    # WY apply (two GEMMs) of w_k reflectors to a w_k × w_j tile
    "UNMQR": lambda wk, wi, wj, it: (4.0 * wk * wk * wj,
                                     3.0 * wk * wj * it, "gemm"),
    # WY apply to the stacked (w_k + w_i) × w_j tile pair
    "TSMQR": lambda wk, wi, wj, it: (4.0 * (wk + wi) * wk * wj,
                                     3.0 * (wk + wi) * wj * it, "gemm"),
    # unblocked Cholesky of the w_k × w_k diagonal tile
    "POTRF": lambda wk, wi, wj, it: (wk * wk * wk / 3.0, 0.0, "panel"),
    # triangular solve against the w_i × w_k tile
    "TRSM": lambda wk, wi, wj, it: (wi * wk * wk,
                                    3.0 * wi * wk * it, "gemm"),
    # symmetric rank-w_k update of the w_j × w_j diagonal tile
    "SYRK": lambda wk, wi, wj, it: (2.0 * wj * wj * wk,
                                    3.0 * wj * wj * it, "gemm"),
    # rank-w_k update of the w_i × w_j tile
    "GEMM": lambda wk, wi, wj, it: (2.0 * wi * wj * wk,
                                    3.0 * wi * wj * it, "gemm"),
}


def _predict_tiled(dmf: str, n: int, dtype, schedule: BlockSpec,
                   peak: float, gemm_eff: float) -> float:
    """Modeled seconds for the tile-DAG executor (serial-sum over tasks).

    Enumerates the same task program the executor runs
    (:data:`repro.core.tiles.TILE_PROGRAMS`) over the square-n tile grid
    and prices each task by its kind's §9 entry plus the per-task dispatch
    overhead.  The executor runs wavefronts serially on this backend, so
    the sum — not the critical path — is the wall-clock model (the DAG
    critical path is what :func:`repro.obs.report.tile_dag` measures).
    """
    from repro.core.tiles import TILE_PROGRAMS

    if dmf not in TILE_PROGRAMS:
        raise KeyError(f"no tiled task program (or cost model) for {dmf!r}")
    widths = expand_schedule(n, schedule)
    nt = len(widths)
    builder = TILE_PROGRAMS[dmf][0]
    tasks = builder(nt, nt) if dmf == "qr" else builder(nt)
    itemsize = jnp.dtype(dtype).itemsize
    total = 0.0
    for t in tasks:
        k, i, j = t.key
        fl, byts, cls = TILE_TASK_COSTS[t.kind](widths[k], widths[i],
                                                widths[j], itemsize)
        eff = PANEL_EFF if cls == "panel" else gemm_eff
        task_t = fl / (peak * eff)
        if byts:
            task_t = max(task_t, byts / HBM_BW)
        total += task_t + TILE_TASK_OVERHEAD_S
    return total


def step_costs(dmf: str, n: int, k: int, bk: int,
               dtype=jnp.float32) -> Tuple[float, float, float]:
    """(panel_flops, update_flops, update_bytes) for iteration ``k``."""
    if dmf not in STEP_COSTS:
        raise KeyError(f"no cost model for DMF {dmf!r}")
    return STEP_COSTS[dmf](n, k, bk, jnp.dtype(dtype).itemsize)


def predict(dmf: str, n: int, dtype, variant: str, schedule: BlockSpec,
            backend: str = "jnp",
            kernel_blocks: Optional[Tuple[int, int, int]] = None) -> float:
    """Modeled seconds for one factorization under ``schedule``.

    ``kernel_blocks`` is the tuner's kernel-blocking axis: for a Pallas
    backend it scales the GEMM efficiency by the roofline attainment of the
    dominant trailing-update shape under that (bm, bn, bk) — so candidates
    differing only in kernel blocking get distinct §9 predictions.

    Raises ValueError for schedules the DMF would reject (band reduction's
    uniform-bandwidth rule, checked by the same core helper the drivers
    use), so :func:`rank` can sort them last.
    """
    from repro.core.lookahead import parse_variant

    if dmf == "band_reduction":
        from repro.core.band_reduction import check_uniform_tiling

        check_uniform_tiling(n, schedule)
    base, depth = parse_variant(variant)
    peak = _peak_flops(dtype)
    gemm_eff = GEMM_EFF.get(backend, 0.5)
    if backend.startswith("pallas"):
        # dominant TU shape: the first iteration's bulk (r × b) · (b × r)
        steps0 = list(panel_steps(n, schedule))
        b0 = steps0[0].bk if steps0 else int(n)
        r0 = max(n - b0, 1)
        gemm_eff *= gemm_attainment(r0, r0, b0, dtype, blocks=kernel_blocks)
    if base == "tiled":
        return _predict_tiled(dmf, n, dtype, schedule, peak, gemm_eff)
    total = 0.0
    for st in panel_steps(n, schedule):
        pf_fl, tu_fl, tu_by = step_costs(dmf, n, st.k, st.bk, dtype)
        pf_t = pf_fl / (peak * PANEL_EFF)
        tu_t = max(tu_fl / (peak * gemm_eff), tu_by / HBM_BW)
        if base in ("la", "la_mb", "tuned"):
            # look-ahead: the panel of k+1 hides under TU_right(k); a
            # depth-d window hides up to d panels under one bulk update, so
            # the panel term amortizes with depth (diminishing: the narrow
            # per-panel updates it buys are not free)
            step_t = max(pf_t / (0.5 * (1 + depth)), tu_t)
            if base == "la_mb":
                step_t = max(0.8 * pf_t / (0.5 * (1 + depth)), tu_t)
                #                                    ^ fused PU, VMEM-resident
        elif variant == "rtm":
            r = n - st.k_next
            ntasks = max(1, -(-r // st.bk)) ** 2
            step_t = pf_t + tu_t + ntasks * RTM_TASK_OVERHEAD_S
        else:                                        # mtb: barrier-separated
            step_t = pf_t + tu_t
        total += step_t + STEP_OVERHEAD_S
    return total


def rank(dmf: str, n: int, dtype,
         candidates: Sequence) -> list:
    """Candidates sorted by modeled time (ascending).

    Each candidate needs ``.variant``, ``.schedule``, ``.backend``
    attributes (see :class:`repro.tune.sweep.Candidate`); an optional
    ``.kernel_blocks`` feeds the Pallas attainment term.  Candidates whose
    schedule :func:`predict` rejects as invalid for the DMF (band
    reduction's uniform-bandwidth rule) sort last rather than raising.
    """
    def score(c):
        try:
            return predict(dmf, n, dtype, c.variant, c.schedule, c.backend,
                           kernel_blocks=getattr(c, "kernel_blocks", None))
        except (KeyError, ValueError):
            return float("inf")

    return sorted(candidates, key=score)
