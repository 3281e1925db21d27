"""Fused panel-update kernels — the malleable-BLAS (LA_MB) analogue.

Paper §4.2: when the panel thread finishes, it *joins* the trailing update so
no core idles.  A TPU core cannot change its worker count mid-kernel, but the
bubble the paper eliminates has an exact analogue here: in the unfused LA
variant, ``PU(k+1)`` is three kernels (TRSM → GEMM → GETF2/GEQR2) with two
HBM round-trips of the panel between them.  These kernels fuse the whole
``PU`` into ONE ``pallas_call`` in which the panel never leaves VMEM — the
compute units stay busy on a single seamless pipeline, which is precisely the
resource-utilization property malleability buys on the CPU.

VMEM budget: the wrapper in ``ops.py`` checks the footprint and falls back to
the composed path for panels that don't fit (the paper sizes b to the cache
for the same reason).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels import single_cell_params
from repro.kernels.panel_lu import getf2, pick
from repro.kernels.trsm import substitute


# ---------------------------------------------------------------------------
# LU: PU(k+1) = TRSM + GEMM + GETF2, one kernel.
# ---------------------------------------------------------------------------
def _lu_pu_body(l11, l21, a1l, a2l):
    """The fused LU PU(k+1) op sequence on plain values (f32 compute).

    Shared by the Pallas kernel (tracing it over VMEM refs) and the eager
    ``fused_lu_panel_update_ref`` twin, so the ``ops.py`` VMEM-budget
    fallback is bitwise transparent on the interpret backend.
    """
    l11 = l11.astype(jnp.float32)
    l21 = l21.astype(jnp.float32)
    # 1. U12 = L11⁻¹ · A1L            (unit-lower substitution)
    u12 = substitute(l11, a1l.astype(jnp.float32), unit=True)
    # 2. panel = A2L − L21 · U12      (MXU contraction, TU_k^L)
    panel = a2l.astype(jnp.float32) - jnp.dot(
        l21, u12, precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    # 3. PF_{k+1}                     (GETF2 with partial pivoting)
    packed, piv = getf2(panel)
    return u12, packed, piv


def _fused_lu_pu_kernel(l11_ref, l21_ref, a1l_ref, a2l_ref,
                        u12_ref, out_ref, piv_ref):
    u12, packed, piv = _lu_pu_body(
        l11_ref[...], l21_ref[...], a1l_ref[...], a2l_ref[...])
    u12_ref[...] = u12.astype(u12_ref.dtype)
    out_ref[...] = packed.astype(out_ref.dtype)
    piv_ref[...] = piv


def fused_lu_panel_update_ref(l11, l21, a1l, a2l):
    """Eager twin of :func:`fused_lu_panel_update` — same op sequence,
    no ``pallas_call``.  Bitwise-matches the kernel on the interpret
    backend; used as the over-budget fallback in ``ops.py``."""
    u12, packed, piv = _lu_pu_body(l11, l21, a1l, a2l)
    return u12.astype(a1l.dtype), packed.astype(a2l.dtype), piv[:, 0]


def fused_lu_panel_update(l11, l21, a1l, a2l, *, interpret: bool = False):
    """``PU(k+1)`` for LU in one VMEM-resident kernel.

    Args: l11 (b,b) unit-lower, l21 (m,b), a1l (b,bn), a2l (m,bn).
    Returns: (u12 (b,bn), packed panel (m,bn), piv (bn,)).
    """
    b = l11.shape[0]
    m, bn = a2l.shape
    steps = min(m, bn)
    u12, out, piv = pl.pallas_call(
        _fused_lu_pu_kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((b, b), lambda i: (0, 0)),
            pl.BlockSpec((m, b), lambda i: (0, 0)),
            pl.BlockSpec((b, bn), lambda i: (0, 0)),
            pl.BlockSpec((m, bn), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((b, bn), lambda i: (0, 0)),
            pl.BlockSpec((m, bn), lambda i: (0, 0)),
            pl.BlockSpec((steps, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, bn), a1l.dtype),
            jax.ShapeDtypeStruct((m, bn), a2l.dtype),
            jax.ShapeDtypeStruct((steps, 1), jnp.int32),
        ],
        compiler_params=single_cell_params(),
        interpret=interpret,
    )(l11, l21, a1l, a2l)
    return u12, out, piv[:, 0]


# ---------------------------------------------------------------------------
# Cholesky: PU(k+1) = GEMM + (POTF2 + TRSM), one kernel.
# ---------------------------------------------------------------------------
def _chol_factor_top(a: jnp.ndarray, nb: int) -> jnp.ndarray:
    """Masked unblocked Cholesky of the top (nb × nb) of a VMEM value."""
    rows = lax.broadcasted_iota(jnp.int32, (nb, 1), 0)
    cols = lax.broadcasted_iota(jnp.int32, (1, nb), 1)

    def body(j, a):
        colj = pick(a, cols == j, 1)
        d = jnp.sqrt(pick(colj, rows == j, 0))
        col = jnp.where(rows > j, colj / d, 0.0)
        a = a - col * pick(col, rows == cols, 0)        # col · colᵀ
        newcol = jnp.where(rows > j, col, pick(a, cols == j, 1))
        newcol = jnp.where(rows == j, d, newcol)
        return jnp.where(cols == j, newcol, a)

    return lax.fori_loop(0, nb, body, a)


def _chol_pu_body(lrow, l21, panel, bn):
    """The fused Cholesky PU(k+1) op sequence on plain values (f32 compute).

    Shared by the Pallas kernel and the eager
    ``fused_cholesky_panel_update_ref`` twin (bitwise-transparent fallback).
    """
    lrow = lrow.astype(jnp.float32)                 # (bn, b)
    l21 = l21.astype(jnp.float32)                   # (m, b)
    panel = panel.astype(jnp.float32)               # (m, bn)
    # 1. TU_k^L : panel −= L21 · lrowᵀ
    panel = panel - jnp.dot(l21, lrow.T, precision=lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    # 2. PF_{k+1}: factor diag block (tril: match the oracle's zeroed
    #    upper triangle), substitute the rest
    top = jnp.tril(_chol_factor_top(panel[:bn], bn))
    if panel.shape[0] > bn:                         # static shape check
        rest = substitute(top, panel[bn:].T, unit=False).T  # X·L11ᵀ = A21
        return jnp.concatenate([top, rest])
    return top


def _fused_chol_pu_kernel(lrow_ref, l21_ref, panel_ref, out_ref, *, bn: int):
    out = _chol_pu_body(lrow_ref[...], l21_ref[...], panel_ref[...], bn)
    out_ref[...] = out.astype(out_ref.dtype)


def fused_cholesky_panel_update_ref(lrow, l21, panel):
    """Eager twin of :func:`fused_cholesky_panel_update` — same op sequence,
    no ``pallas_call``; the over-budget fallback in ``ops.py``."""
    return _chol_pu_body(lrow, l21, panel, lrow.shape[0]).astype(panel.dtype)


def fused_cholesky_panel_update(lrow, l21, panel, *, interpret: bool = False):
    """``PU(k+1)`` for Cholesky in one VMEM-resident kernel.

    Args: lrow (bn,b) = L rows of next block col, l21 (m,b), panel (m,bn).
    Returns the factored next panel (m, bn).
    """
    bn = lrow.shape[0]
    m = panel.shape[0]
    b = lrow.shape[1]
    return pl.pallas_call(
        functools.partial(_fused_chol_pu_kernel, bn=bn),
        grid=(1,),
        in_specs=[
            pl.BlockSpec((bn, b), lambda i: (0, 0)),
            pl.BlockSpec((m, b), lambda i: (0, 0)),
            pl.BlockSpec((m, bn), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, bn), panel.dtype),
        compiler_params=single_cell_params(),
        interpret=interpret,
    )(lrow, l21, panel)
