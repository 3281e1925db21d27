"""BLIS five-loop GEMM → Pallas TPU kernel (paper §2, Listing 1).

Mapping of the BLIS/GotoBLAS structure onto the TPU memory hierarchy
(DESIGN.md §2 — this is the "cache-aware BLAS" the paper's trailing update
relies on, re-derived for HBM→VMEM→MXU instead of RAM→L2/L1→registers):

| BLIS (Listing 1)                         | this kernel                         |
|------------------------------------------|-------------------------------------|
| Loop 1/2/3 over (j_c, p_c, i_c)          | grid = (M/bm, N/bn, K/bk)           |
| ``Pack_buffer_B`` → B_c in L3            | BlockSpec (bk, bn) HBM→VMEM copy    |
| ``Pack_buffer_A`` → A_c in L2            | BlockSpec (bm, bk) HBM→VMEM copy    |
| micro-panel of B_c in L1                 | MXU operand staging (hardware)      |
| Loop 4/5 + micro-kernel (m_r × n_r)      | 128×128 systolic contraction        |
| C streamed from memory                   | f32 VMEM accumulator, one writeback |

The "packing" the paper performs explicitly is done by the Pallas pipeline
emitter: each grid step DMAs the next (bm, bk)/(bk, bn) tiles into VMEM
double buffers while the MXU contracts the current ones.  The K grid
dimension is innermost (sequential on a TensorCore) so the f32 accumulator
lives in VMEM across the K loop and C is written back exactly once — the
analogue of BLIS keeping C micro-tiles in registers.

Block-shape selection (the ``n_c, k_c, m_c`` analogue) lives in
:func:`repro.tune.model.gemm_blocks` — derived from the §9 machine record
(:data:`repro.tune.model.MACHINE`: VMEM budget, lane/sublane tiling) so the
kernel layer quotes no machine numbers of its own; :func:`pick_blocks` is
the thin delegate the kernels and the tuner's kernel-blocking axis share.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.tune.model import MACHINE, gemm_blocks

#: Re-exported from the machine record (single source of truth) — kept under
#: the historical name for callers/tests that size against the GEMM budget.
VMEM_BUDGET_BYTES = MACHINE.vmem_budget_bytes


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pick_blocks(m: int, n: int, k: int, dtype,
                target=(512, 512, 512)) -> tuple[int, int, int]:
    """Choose (bm, bn, bk): hardware-aligned, VMEM-resident (BLIS §2 analogue).

    Delegates to :func:`repro.tune.model.gemm_blocks` — the §9 roofline
    machine record is the one place the VMEM budget and tile grid live.
    """
    return gemm_blocks(m, n, k, dtype, target=target)


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, ksteps: int):
    """Grid step: one (bm, bk)·(bk, bn) MXU contraction into the accumulator."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            precision=lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == ksteps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def blis_gemm(a: jnp.ndarray, b: jnp.ndarray, *,
              blocks: tuple[int, int, int] | None = None,
              interpret: bool = False) -> jnp.ndarray:
    """C = A·B through the five-loop Pallas kernel.

    Pads every dim up to its block multiple (zero padding is exact for
    matmul), runs the kernel, slices the result back.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    dtype = a.dtype
    bm, bn, bk = blocks or pick_blocks(m, n, k, dtype)

    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    if (mp, kp) != (m, k):
        a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        b = jnp.pad(b, ((0, kp - k), (0, np_ - n)))

    ksteps = kp // bk
    out = pl.pallas_call(
        functools.partial(_gemm_kernel, ksteps=ksteps),
        grid=(mp // bm, np_ // bn, ksteps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),   # A_c → VMEM
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),   # B_c → VMEM
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((mp, np_), dtype),
        interpret=interpret,
    )(a, b)
    return out[:m, :n]


def _gemm_accum_kernel(c_ref, a_ref, b_ref, o_ref, acc_ref, *,
                       ksteps: int, alpha: float):
    """Trailing-update shape: O = C + alpha·A·B, fused (no extra C pass)."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = c_ref[...].astype(jnp.float32)

    acc_ref[...] += alpha * jnp.dot(a_ref[...], b_ref[...],
                                    precision=lax.Precision.HIGHEST,
                                    preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == ksteps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def blis_gemm_accum(c: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray, *,
                    alpha: float = -1.0,
                    blocks: tuple[int, int, int] | None = None,
                    interpret: bool = False) -> jnp.ndarray:
    """O = C + alpha·A·B — the DMF trailing update as one fused kernel.

    Fusing the addition avoids a second HBM pass over C (the fork–join MTB
    structure would materialize A·B and then subtract — see DESIGN.md §2 on
    malleability-as-fusion).
    """
    m, k = a.shape
    _, n = b.shape
    assert c.shape == (m, n), (c.shape, a.shape, b.shape)
    dtype = c.dtype
    bm, bn, bk = blocks or pick_blocks(m, n, k, dtype)

    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    if (mp, np_) != (m, n):
        c = jnp.pad(c, ((0, mp - m), (0, np_ - n)))
    if (mp, kp) != (m, k):
        a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        b = jnp.pad(b, ((0, kp - k), (0, np_ - n)))

    ksteps = kp // bk
    out = pl.pallas_call(
        functools.partial(_gemm_accum_kernel, ksteps=ksteps, alpha=alpha),
        grid=(mp // bm, np_ // bn, ksteps),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((mp, np_), dtype),
        interpret=interpret,
    )(c, a, b)
    return out[:m, :n]
