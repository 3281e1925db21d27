"""Compute backend used by the factorization drivers.

The paper builds its DMFs on a cache-aware BLAS (BLIS).  Here the same role is
played by a small backend vtable: the default implementation lowers to XLA's
native ops (the "vendor BLAS" analogue), while :mod:`repro.kernels.ops`
provides a drop-in backend built from our Pallas kernels (the "modified BLIS"
analogue — paper §6.1 uses a modified BLIS 0.1.8).

Keeping the factorization *algorithms* independent of the backend mirrors the
paper's separation between the DMF framework (§3) and the BLAS layer (§2).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Mapping, Optional

import jax
import jax.numpy as jnp
from jax import lax


def _acc_dtype(dtype) -> jnp.dtype:
    """f32 accumulation for low-precision inputs (MXU semantics)."""
    if dtype in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    return dtype


#: K-dimension quantum for :func:`gemm_jnp` — every contraction is zero-padded
#: to a multiple of this and accumulated chunk-by-chunk in a fixed order.
_GEMM_KQ = 128
#: M/N-dimension quanta.  XLA picks its CPU dot kernel by shape (an M=1
#: product lowers to a matvec whose batched variant reassociates; small-M
#: and large-M tilings differ), so M and N are padded to multiples of 32.
#: With 32-aligned serve buckets this makes every GEMM in a padded run have
#: exactly the same operand shapes as in the raw-shape run — kernel choice,
#: and therefore accumulation order, cannot diverge between the two.
_GEMM_MQ = 32
_GEMM_NQ = 32

#: Every f32 product runs at full f32 precision.  On a TPU a default-
#: precision f32 dot takes bf16 passes, which would leave the factors with
#: bf16-level error; on the CPU the setting changes no value.
_PRECISION = lax.Precision.HIGHEST


def _gemm_impl(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """C = A·B with f32 accumulation for bf16 inputs.

    jit-wrapped so an *eager* driver call costs one cached executable per
    shape instead of ~8 dispatched ops (pad, two scatters, dot, slice, …);
    inside an outer ``jit``/``vmap`` trace the wrapper inlines.  Fusion does
    not move the dots, so the bitwise contract below survives the wrapper —
    ``tests/test_serve_solver.py`` pins jit == eager across the full
    dmf × dtype matrix.

    Canonicalized for bitwise reproducibility (DESIGN.md §13): XLA's dot
    accumulation order over K depends on the *total* K (and an M=1 product
    lowers to a matvec with a different batched kernel), so a zero-padded or
    ``vmap``-batched GEMM is not bit-identical to the unpadded/unbatched one
    in general.  Here M is padded to a multiple of ``_GEMM_MQ`` and K to a
    multiple of ``_GEMM_KQ``, and chunks of ``_GEMM_KQ`` are accumulated
    sequentially — so the result depends only on the real values, never on
    how much zero padding or batching surrounds them.  This is what lets the
    serve layer promise padded+batched == unbatched bitwise.

    Lowered for the CPU, N is also computed in ``_GEMM_NQ``-wide tiles.
    XLA:CPU picks its dot algorithm (threading, K-splitting) by the *total*
    N, so ``gemm(A, B)[:, j0:j1]`` and ``gemm(A, B[:, j0:j1])`` round
    differently unless every dot has the same N.  Fixed-width column tiles
    make the product column-decomposable, the property the mesh engine's
    per-block trailing updates rely on (``core/distributed.py``).  Any other
    platform takes the full-width dot, the one the MXU wants; the choice
    follows the platform the GEMM is lowered for.
    """
    acc = _acc_dtype(a.dtype)
    dot = functools.partial(jnp.matmul, preferred_element_type=acc,
                            precision=_PRECISION)
    if a.ndim != 2 or b.ndim != 2:
        return dot(a, b).astype(a.dtype)
    m, k = a.shape
    n = b.shape[1]
    kp = max(_GEMM_KQ, -(-k // _GEMM_KQ) * _GEMM_KQ)
    mp = -(-m // _GEMM_MQ) * _GEMM_MQ
    np_ = -(-n // _GEMM_NQ) * _GEMM_NQ
    ap = a if (m == mp and k == kp) else (
        jnp.zeros((mp, kp), a.dtype).at[:m, :k].set(a))
    bp = b if (k == kp and n == np_) else (
        jnp.zeros((kp, np_), b.dtype).at[:k, :n].set(b))

    def product(bt):
        if kp == _GEMM_KQ:
            return dot(ap, bt)

        def body(i, c):
            ac = lax.dynamic_slice_in_dim(ap, i * _GEMM_KQ, _GEMM_KQ, 1)
            bc = lax.dynamic_slice_in_dim(bt, i * _GEMM_KQ, _GEMM_KQ, 0)
            return c + dot(ac, bc)
        return lax.fori_loop(0, kp // _GEMM_KQ, body,
                             jnp.zeros((mp, bt.shape[1]), acc))

    def column_tiles(bt):
        tiles = bt.reshape(kp, np_ // _GEMM_NQ, _GEMM_NQ).transpose(1, 0, 2)
        return lax.map(product, tiles).transpose(1, 0, 2).reshape(mp, np_)

    if np_ == _GEMM_NQ:
        out = product(bp)
    else:
        out = lax.platform_dependent(bp, cpu=column_tiles, default=product)
    return out[:m, :n].astype(a.dtype)


#: jit entry point (same rationale as :data:`trsm_jnp` below).  The unjitted
#: body ``_gemm_impl`` stays reachable for callers that must embed the exact
#: same op sequence inside another staged context — the Pallas panel kernels
#: trace the shared sweep bodies into a kernel, and an inner ``pjit`` there
#: would re-stage rather than inline.  jit == eager is bitwise for this body
#: (pinned by tests/test_serve_solver.py), so both spellings agree.
gemm_jnp = functools.wraps(_gemm_impl)(jax.jit(_gemm_impl))


#: Width of the substitution diagonal blocks inside :func:`trsm_jnp`.
_TRSM_DIAG = 32


def _trsm_impl(
    t: jnp.ndarray,
    b: jnp.ndarray,
    *,
    side: str = "left",
    lower: bool = True,
    trans: bool = False,
    unit_diagonal: bool = False,
) -> jnp.ndarray:
    """Solve ``op(T)·X = B`` (side=left) or ``X·op(T) = B`` (side=right).

    Implemented as blocked substitution (elementwise column sweeps on
    ``_TRSM_DIAG``-wide diagonal blocks, GEMM off-diagonal updates) rather
    than ``lax.linalg.triangular_solve``: the lax primitive lowers to a
    *different algorithm* when a batch dimension is present, so a
    ``vmap``-batched solve is not bit-identical to the unbatched one.  The
    serving layer's reproducibility contract (DESIGN.md §13) requires
    batched == unbatched bitwise, and elementwise ops + GEMM are the
    primitives that lower identically with and without batch dimensions.
    """
    if side == "right":
        # X·op(T) = B  ⇔  op(T)ᵀ·Xᵀ = Bᵀ; transposing T flips lower/upper
        # unless op already transposes.
        if trans:
            return _trsm_impl(t, b.T, side="left", lower=lower, trans=False,
                            unit_diagonal=unit_diagonal).T
        return _trsm_impl(t.T, b.T, side="left", lower=not lower, trans=False,
                        unit_diagonal=unit_diagonal).T
    if side != "left":
        raise ValueError(f"side must be left/right, got {side}")
    if trans:
        return _trsm_impl(t.T, b, side="left", lower=not lower, trans=False,
                        unit_diagonal=unit_diagonal)

    m = t.shape[0]
    blocks = [(k, min(_TRSM_DIAG, m - k)) for k in range(0, m, _TRSM_DIAG)]
    if not lower:
        blocks = list(reversed(blocks))
    x = b
    for k, bk in blocks:
        tkk = t[k : k + bk, k : k + bk]
        rows = jnp.arange(bk)[:, None]

        def body(i, xk, tkk=tkk, bk=bk, rows=rows, lower=lower):
            j = i if lower else bk - 1 - i
            xj = xk[j] if unit_diagonal else xk[j] / tkk[j, j]
            xk = xk.at[j].set(xj)
            mask = (rows > j) if lower else (rows < j)
            return jnp.where(mask, xk - tkk[:, j][:, None] * xj[None, :],
                             xk).astype(xk.dtype)

        xk = lax.fori_loop(0, bk, body, x[k : k + bk])
        x = x.at[k : k + bk].set(xk)
        rem = slice(k + bk, m) if lower else slice(0, k)
        if rem.start < rem.stop:
            x = x.at[rem].set(
                (x[rem] - gemm_jnp(t[rem, k : k + bk], xk)).astype(x.dtype))
    return x


#: jit entry point for the same reason as :func:`gemm_jnp` — an eager
#: substitution solve is a storm of scatter/fori dispatches otherwise
#: (the lax primitive it replaced was one op; this claws that back).
trsm_jnp = functools.wraps(_trsm_impl)(jax.jit(
    _trsm_impl,
    static_argnames=("side", "lower", "trans", "unit_diagonal")))


@dataclasses.dataclass(frozen=True)
class Backend:
    """BLAS-like vtable the DMF drivers are written against.

    ``panel_fns`` / ``fused_pu`` are optional per-DMF kernel registries
    (keyed by ``StepOps.name``): when set, :func:`repro.core.pipeline.
    factorize` resolves a default ``panel_fn=`` / ``fused_pu=`` from them
    for callers that passed none — this is how ``backend="pallas"`` routes
    every driver through the VMEM-resident panel kernels and the fused
    PU(k+1) pipeline without per-call plumbing.  ``None`` (the jnp default)
    leaves the DMFs' own unblocked panels in place, preserving the
    bit-pinned legacy op sequence.
    """

    name: str
    gemm: Callable[..., jnp.ndarray]
    trsm: Callable[..., jnp.ndarray]
    panel_fns: Optional[Mapping[str, Callable]] = None
    fused_pu: Optional[Mapping[str, Callable]] = None

    def update(self, c: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        """Rank-k update ``C - A·B`` — the trailing-update workhorse."""
        return (c - self.gemm(a, b)).astype(c.dtype)


JNP_BACKEND = Backend(name="jnp", gemm=gemm_jnp, trsm=trsm_jnp)


def get_backend(name: str = "jnp") -> Backend:
    if name == "jnp":
        return JNP_BACKEND
    if name == "pallas":
        from repro.kernels import ops as kops  # local import; optional dep

        return kops.PALLAS_BACKEND
    raise ValueError(f"unknown backend {name!r} (expected 'jnp' or 'pallas')")
