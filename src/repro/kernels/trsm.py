"""Triangular solve (TRSM) Pallas kernel.

``L·X = B`` with L (nb × nb) lower triangular resident in VMEM and B split
into (nb, bn) column blocks — one grid step per block, mirroring how the
paper's TRSM parallelizes over the trailing columns.  The substitution loop
is the latency-bound "small sequential op" of the DMF; keeping L and the
block of B in VMEM for its entire lifetime is the point of the kernel.

Right-side solves (``X·Lᵀ = B``, the Cholesky/LDLᵀ ``L21`` shape) reduce to
the left kernel by transposition in the wrapper (XLA fuses the transposes
into the surrounding copies).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels import single_cell_params
from repro.kernels.panel_lu import pick


def substitute(t: jnp.ndarray, x: jnp.ndarray, *, lower: bool = True,
               unit: bool = True) -> jnp.ndarray:
    """Solve ``T·X = B`` by row substitution on VMEM-resident values.

    ``t`` is (n × n) triangular, ``x`` holds B (n × bn); both f32.  Row i
    is read and written through one-hot masks (``repro.kernels.panel_lu.pick``), the
    form a Pallas TPU kernel can lower, and the rows already solved enter
    one (1 × n)·(n × bn) MXU product.  Shared by the TRSM, fused LU solve
    and fused panel-update kernels and by their traced twins.
    """
    n = t.shape[0]
    rows = lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    cols = lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def body(s, x):
        i = s if lower else n - 1 - s
        ti = pick(t, rows == i, 0)                          # (1, n)
        solved = jnp.where(rows < i if lower else rows > i, x, 0.0)
        contrib = jnp.dot(ti, solved, precision=lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)  # (1, bn)
        xi = pick(x, rows == i, 0) - contrib
        if not unit:
            xi = xi / pick(ti, cols == i, 1)
        return jnp.where(rows == i, xi, x)

    return lax.fori_loop(0, n, body, x)


def _trsm_kernel(l_ref, b_ref, x_ref, *, unit: bool):
    x = substitute(l_ref[...].astype(jnp.float32),
                   b_ref[...].astype(jnp.float32), unit=unit)
    x_ref[...] = x.astype(x_ref.dtype)


def trsm_left_lower(l: jnp.ndarray, b: jnp.ndarray, *,
                    unit_diagonal: bool = True,
                    block_n: int = 512,
                    interpret: bool = False) -> jnp.ndarray:
    """Solve L·X = B via the Pallas substitution kernel."""
    nb = l.shape[0]
    assert l.shape == (nb, nb) and b.shape[0] == nb, (l.shape, b.shape)
    n = b.shape[1]
    bn = min(block_n, max(128, n))
    npad = (n + bn - 1) // bn * bn
    if npad != n:
        b = jnp.pad(b, ((0, 0), (0, npad - n)))

    out = pl.pallas_call(
        functools.partial(_trsm_kernel, unit=unit_diagonal),
        grid=(npad // bn,),
        in_specs=[
            pl.BlockSpec((nb, nb), lambda j: (0, 0)),   # L resident per step
            pl.BlockSpec((nb, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((nb, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((nb, npad), b.dtype),
        compiler_params=single_cell_params(),
        interpret=interpret,
    )(l, b)
    return out[:, :n]


def trsm_right_lower_t(l: jnp.ndarray, b: jnp.ndarray, *,
                       unit_diagonal: bool = False,
                       interpret: bool = False) -> jnp.ndarray:
    """Solve X·Lᵀ = B  ⇔  L·Xᵀ = Bᵀ (the L21 panel shape)."""
    xt = trsm_left_lower(l, b.T, unit_diagonal=unit_diagonal,
                         interpret=interpret)
    return xt.T


# ---------------------------------------------------------------------------
# Fused small-RHS LU solve — the solve layer's LA_MB analogue.
# ---------------------------------------------------------------------------
def _lu_solve_kernel(lu_ref, b_ref, x_ref):
    """Forward (unit-lower) + backward (upper) substitution in one kernel.

    The packed LU stays VMEM-resident for both sweeps — for the small
    factor-once/solve-many systems of the serving scenario the two
    substitutions are latency-bound, so fusing them removes one full
    HBM round-trip of the factor (DESIGN.md §8).  The strictly-upper half
    is masked out of the forward sweep and the strictly-lower half out of
    the backward one by the ``solved`` row masks.
    """
    a = lu_ref[...].astype(jnp.float32)
    y = substitute(a, b_ref[...].astype(jnp.float32), lower=True, unit=True)
    x = substitute(a, y, lower=False, unit=False)
    x_ref[...] = x.astype(x_ref.dtype)


def lu_solve_small(lu: jnp.ndarray, b: jnp.ndarray, *,
                   block_n: int = 512,
                   interpret: bool = False) -> jnp.ndarray:
    """Solve L·U·X = B from packed LU via the fused substitution kernel."""
    n = lu.shape[0]
    assert lu.shape == (n, n) and b.shape[0] == n, (lu.shape, b.shape)
    nrhs = b.shape[1]
    bn = min(block_n, max(128, nrhs))
    npad = (nrhs + bn - 1) // bn * bn
    if npad != nrhs:
        b = jnp.pad(b, ((0, 0), (0, npad - nrhs)))

    out = pl.pallas_call(
        _lu_solve_kernel,
        grid=(npad // bn,),
        in_specs=[
            pl.BlockSpec((n, n), lambda j: (0, 0)),     # LU resident per step
            pl.BlockSpec((n, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((n, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((n, npad), b.dtype),
        compiler_params=single_cell_params(),
        interpret=interpret,
    )(lu, b)
    return out[:, :nrhs]
