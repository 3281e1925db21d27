"""LU panel factorization (GETF2) Pallas kernel — partial pivoting in VMEM.

The panel factorization is the paper's sequential bottleneck (§3.5).  On TPU
its cost is latency (dependent small ops), not FLOPs — so the one thing that
matters is *never touching HBM* during the column loop.  This kernel holds
the whole (m × nb) panel in VMEM, runs the pivot-search / swap / rank-1 loop
there, and writes the packed result plus the pivot vector once.

The kernel body is :func:`getf2`, the GETF2 sweep of
:func:`repro.core.lu.lu_unblocked` (the routine the jnp drivers use as their
default panel) with every dynamic row/column access written as a one-hot
mask: ``max(where(mask, a, -inf))`` returns the one selected element exactly
(``-0.0`` and NaN included) and ``where(mask, new, a)`` writes it.  Mosaic
has no dynamic slice or scatter on values, so the kernel needs this form;
the jnp panel keeps its indexed sweep, which XLA runs faster.  Every
arithmetic operation is the same, so the Pallas panel is **bitwise
identical** to the jnp panel (the transparency guarantee behind the
VMEM-budget fallback in ``ops.py``).  The kernel runs in the input dtype
(f64 only in interpret mode: on a TPU the ``ops.py`` wrapper refuses it).

The wrapper enforces the VMEM budget (panels larger than VMEM fall back to
the jnp path in ``ops.py`` — in the DMF the panel is chosen to fit, exactly
as the paper sizes b to the cache).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels import single_cell_params


def pick(a: jnp.ndarray, mask: jnp.ndarray, axis: int) -> jnp.ndarray:
    """The masked entries of ``a`` along ``axis`` (one per lane), exactly.

    ``mask`` selects one index along ``axis``; the result keeps that axis
    with length 1.
    """
    return jnp.max(jnp.where(mask, a, -jnp.inf), axis=axis,
                   keepdims=True).astype(a.dtype)


def first_argmax(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``argmax`` down axis 0 of an (m × 1) column as a (1 × 1) int32.

    Same choice as ``jnp.argmax``: the first maximum, or the first NaN.
    """
    mx = jnp.max(x, axis=0, keepdims=True)
    hit = (x == mx) | jnp.isnan(x)
    return jnp.min(jnp.where(hit, idx, x.shape[0]), axis=0, keepdims=True)


def getf2(a: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """GETF2 sweep over an (m × nb) value: (packed LU, piv as (steps × 1)).

    :func:`repro.core.lu.lu_unblocked` in one-hot form; the body of the
    panel kernel and of the fused LU PU kernel.
    """
    m, nb = a.shape
    steps = min(m, nb)
    rows = lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    cols = lax.broadcasted_iota(jnp.int32, (1, nb), 1)
    sidx = lax.broadcasted_iota(jnp.int32, (steps, 1), 0)

    def body(j, carry):
        a, piv = carry
        # --- pivot search over rows >= j of column j --------------------
        col = jnp.where(rows < j, -jnp.inf, jnp.abs(pick(a, cols == j, 1)))
        p = first_argmax(col, rows)
        piv = jnp.where(sidx == j, p, piv)
        # --- row interchange j <-> p ------------------------------------
        rj, rp = pick(a, rows == j, 0), pick(a, rows == p, 0)
        a = jnp.where(rows == j, rp, jnp.where(rows == p, rj, a))
        # --- scale L column and rank-1 update ---------------------------
        pivval = pick(rp, cols == j, 1)
        l = jnp.where(rows > j, pick(a, cols == j, 1) / pivval,
                      0.0).astype(a.dtype)
        urow = jnp.where(cols > j, rp, 0.0).astype(a.dtype)
        a = a - l * urow
        a = jnp.where((cols == j) & (rows > j), l, a)
        return a, piv

    piv0 = jnp.zeros((steps, 1), jnp.int32)
    return lax.fori_loop(0, steps, body, (a, piv0))


def _lu_panel_kernel(a_ref, out_ref, piv_ref):
    packed, piv = getf2(a_ref[...])
    out_ref[...] = packed
    piv_ref[...] = piv


def lu_panel(panel: jnp.ndarray, *, interpret: bool = False):
    """Factor an (m × nb) panel in one VMEM-resident kernel.

    Returns (packed, piv) with the same semantics as
    :func:`repro.core.lu.lu_unblocked` (panel-relative 0-based pivots).
    """
    m, nb = panel.shape
    steps = min(m, nb)
    out, piv = pl.pallas_call(
        _lu_panel_kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((m, nb), lambda i: (0, 0))],
        out_specs=[
            pl.BlockSpec((m, nb), lambda i: (0, 0)),
            pl.BlockSpec((steps, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, nb), panel.dtype),
            jax.ShapeDtypeStruct((steps, 1), jnp.int32),
        ],
        compiler_params=single_cell_params(),
        interpret=interpret,
    )(panel)
    return out, piv[:, 0]
