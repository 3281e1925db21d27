"""Plain references for the benchmark's correctness checks.

Two kinds live here, both independent of the program under test (nothing
from ``repro`` is imported):

* host checks in float64 NumPy, which judge what a timed step returned
  (:func:`solve_error`, :func:`logdet_spd64`);
* straightforward blocked LU and Cholesky factorizations in ``jax.numpy``
  whose matrix products run at a stated precision (:func:`matmul`).  Run at
  ``"high"`` in the program's place they are the controls that the checks
  have to fail (PERF.md §2).

The blocked factorizations keep every shape fixed: each panel step works
on full-height column panels and full-size masked trailing updates, inside
``lax.fori_loop``, so one small program serves every n and compiles in
seconds.  Only their matrix products carry the precision; the unblocked
panel sweeps and the diagonal substitutions are elementwise float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: Unit of every backward error below: float32 machine epsilon.
EPS32 = float(np.finfo(np.float32).eps)

#: Precisions a reference product can run at.  ``"high"`` is three bf16
#: passes: ``Precision.HIGH`` on a TPU; written out on other backends,
#: which ignore ``lax.Precision`` for float32 — each operand is split into
#: a bf16 head and a bf16 tail, and the tail × tail term is dropped, as
#: the TPU does.  (Written out on a TPU, the split comes back as zero
#: tails: XLA removes the f32 → bf16 → f32 round trip there.)
PRECISIONS = ("highest", "high", "bf16")


def matmul(a: jnp.ndarray, b: jnp.ndarray, precision: str) -> jnp.ndarray:
    """``a @ b`` in float32 at ``precision`` (one of :data:`PRECISIONS`)."""
    f32, bf16 = jnp.float32, jnp.bfloat16

    def dot(x, y):
        return jnp.matmul(x, y, preferred_element_type=f32,
                          precision=lax.Precision.HIGHEST)

    if precision == "highest":
        return dot(a.astype(f32), b.astype(f32))
    if precision == "bf16":
        return dot(a.astype(bf16), b.astype(bf16))
    if precision == "high":
        def three_pass(a, b):
            a_hi, b_hi = a.astype(bf16), b.astype(bf16)
            a_lo = (a - a_hi.astype(f32)).astype(bf16)
            b_lo = (b - b_hi.astype(f32)).astype(bf16)
            return dot(a_lo, b_hi) + dot(a_hi, b_lo) + dot(a_hi, b_hi)

        def native(a, b):
            return jnp.matmul(a, b, preferred_element_type=f32,
                              precision=lax.Precision.HIGH)

        return lax.platform_dependent(a.astype(f32), b.astype(f32),
                                      tpu=native, default=three_pass)
    raise ValueError(f"precision must be one of {PRECISIONS}, got "
                     f"{precision!r}")


# ---------------------------------------------------------------------------
# Host checks in float64.
# ---------------------------------------------------------------------------
def solve_error(a, x, b) -> float:
    """``max_j ‖A·x_j − b_j‖∞ / (‖A‖∞·‖x_j‖∞·ε)`` in float64 on the host.

    HPL's scaled residual times n, in units of float32 ε; the worst
    right-hand side counts.  A non-finite ``x`` reads ``inf``.
    """
    a, x, b = (np.asarray(v, np.float64) for v in (a, x, b))
    if x.ndim == 1:
        x, b = x[:, None], b[:, None]
    if not np.all(np.isfinite(x)):
        return float("inf")
    r = np.abs(a @ x - b).max(axis=0)
    scale = np.abs(a).sum(axis=1).max() * np.abs(x).max(axis=0) * EPS32
    return float((r / np.maximum(scale, np.finfo(np.float64).tiny)).max())


def logdet_spd64(k: np.ndarray) -> float:
    """``log det K`` of an SPD matrix, by a float64 Cholesky."""
    l = np.linalg.cholesky(np.asarray(k, np.float64))
    return float(2.0 * np.log(np.diagonal(l)).sum())


# ---------------------------------------------------------------------------
# Blocked factorizations in jax.numpy at a stated product precision.
# ---------------------------------------------------------------------------
def _substitute(t, rhs, c0, nb, *, lower, unit):
    """Solve the nb × nb diagonal block of ``t`` at ``c0`` against ``rhs``.

    ``rhs`` holds the block's nb rows (nb × m); elementwise float32.
    """
    tkk = lax.dynamic_slice(t, (c0, c0), (nb, nb))
    rows = jnp.arange(nb)[:, None]

    def body(s, x):
        j = s if lower else nb - 1 - s
        xj = x[j] if unit else x[j] / tkk[j, j]
        x = x.at[j].set(xj)
        below = (rows > j) if lower else (rows < j)
        return jnp.where(below, x - tkk[:, j][:, None] * xj[None, :], x)

    return lax.fori_loop(0, nb, body, rhs)


def triangular_solve(t, rhs, nb: int, precision: str, *, lower: bool,
                     unit: bool = False, trans: bool = False):
    """``op(T)⁻¹·rhs`` by blocked substitution; off-diagonal products at
    ``precision``.  ``trans`` solves with ``Tᵀ`` (so lower becomes upper).
    """
    if trans:
        t, lower = t.T, not lower
    n = t.shape[0]
    nblocks = n // nb
    idx = jnp.arange(n)

    def body(s, x):
        kb = s if lower else nblocks - 1 - s
        c0 = kb * nb
        xk = _substitute(t, lax.dynamic_slice_in_dim(x, c0, nb, 0), c0, nb,
                         lower=lower, unit=unit)
        x = lax.dynamic_update_slice_in_dim(x, xk, c0, 0)
        col = lax.dynamic_slice_in_dim(t, c0, nb, 1)          # n × nb
        rest = (idx >= c0 + nb) if lower else (idx < c0)
        col = jnp.where(rest[:, None], col, 0.0)
        return x - matmul(col, xk, precision)

    return lax.fori_loop(0, nblocks, body, rhs)


def lu_factor(a, nb: int, precision: str):
    """Right-looking blocked LU with partial pivoting: ``(LU, perm)`` with
    ``A[perm] = L·U``.  Trailing updates at ``precision``."""
    n = a.shape[0]
    if n % nb:
        raise ValueError(f"n={n} is not a multiple of nb={nb}")
    idx = jnp.arange(n)
    cols = jnp.arange(nb)

    def panel(kb, carry):
        a, perm = carry
        c0 = kb * nb
        p = lax.dynamic_slice_in_dim(a, c0, nb, 1)             # n × nb
        local = idx

        def column(j, st):
            p, local = st
            r = c0 + j
            mag = jnp.where(idx >= r, jnp.abs(p[:, j]), -1.0)
            q = jnp.argmax(mag)
            rows_rq = jnp.stack([r, q])
            p = p.at[rows_rq].set(p[jnp.stack([q, r])])
            local = local.at[rows_rq].set(local[jnp.stack([q, r])])
            below = idx > r
            l = jnp.where(below, p[:, j] / p[r, j], p[:, j])
            p = p.at[:, j].set(l)
            right = cols > j
            upd = jnp.where(below[:, None] & right[None, :],
                            l[:, None] * p[r][None, :], 0.0)
            return p - upd, local

        p, local = lax.fori_loop(0, nb, column, (p, local))
        a = a[local]
        perm = perm[local]
        a = lax.dynamic_update_slice_in_dim(a, p, c0, 1)
        # U12 = L11⁻¹·A12, then A22 −= L21·U12 (masked to the trailing part).
        a12 = lax.dynamic_slice_in_dim(a, c0, nb, 0)           # nb × n
        u12 = _substitute(a, a12, c0, nb, lower=True, unit=True)
        right = idx >= c0 + nb
        u12 = jnp.where(right[None, :], u12, a12)
        a = lax.dynamic_update_slice_in_dim(a, u12, c0, 0)
        l21 = jnp.where((idx >= c0 + nb)[:, None], p, 0.0)
        u12r = jnp.where(right[None, :], u12, 0.0)
        return a - matmul(l21, u12r, precision), perm

    return lax.fori_loop(0, n // nb, panel, (a, idx))


def lu_solve(lu, perm, b, nb: int, precision: str):
    """``A⁻¹·b`` from :func:`lu_factor`'s output."""
    y = triangular_solve(lu, b[perm], nb, precision, lower=True, unit=True)
    return triangular_solve(lu, y, nb, precision, lower=False)


def cholesky(a, nb: int, precision: str):
    """Right-looking blocked Cholesky ``A = L·Lᵀ``; returns lower ``L``.
    Trailing updates at ``precision``."""
    n = a.shape[0]
    if n % nb:
        raise ValueError(f"n={n} is not a multiple of nb={nb}")
    idx = jnp.arange(n)
    cols = jnp.arange(nb)

    def panel(kb, a):
        c0 = kb * nb
        p = lax.dynamic_slice_in_dim(a, c0, nb, 1)             # n × nb

        def column(j, p):
            r = c0 + j
            d = jnp.sqrt(p[r, j])
            below = idx > r
            l = jnp.where(below, p[:, j] / d, jnp.where(idx == r, d, 0.0))
            p = p.at[:, j].set(l)
            # rows below r, panel columns right of j: P[i, j'] −= l_i·l_{c0+j'}
            lrow = lax.dynamic_slice_in_dim(l, c0, nb, 0)
            upd = jnp.where(below[:, None] & (cols > j)[None, :],
                            l[:, None] * lrow[None, :], 0.0)
            return p - upd

        p = lax.fori_loop(0, nb, column, p)
        a = lax.dynamic_update_slice_in_dim(a, p, c0, 1)
        l21 = jnp.where((idx >= c0 + nb)[:, None], p, 0.0)
        return a - matmul(l21, l21.T, precision)

    return jnp.tril(lax.fori_loop(0, n // nb, panel, a))
