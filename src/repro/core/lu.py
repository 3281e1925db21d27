"""LU factorization with partial pivoting (LUpp) — all scheduling variants.

The algorithm is declared once as :data:`LU_OPS` (a
:class:`~repro.core.pipeline.StepOps` record); every scheduling variant is
emitted by the generic engine in :mod:`repro.core.pipeline`:

* :func:`lu_unblocked`            — GETF2 analogue; also the PF building block.
* :func:`lu_blocked`              — right-looking blocked GETRF; the **MTB**
  analogue (panel, barrier, trailing update as separate ops).
* :func:`lu_tiled`                — **RTM** analogue: the trailing update is
  fragmented into per-panel (and per-tile) tasks, mirroring Listing 4.
* :func:`lu_lookahead`            — **LA**: static look-ahead (Listing 5).
  ``TU_k^L + PF_{k+1}`` (= ``PU(k+1)``) is made *data-independent* of
  ``TU_k^R`` within each iteration so the scheduler can overlap them — the
  TPU analogue of the paper's two ``parallel sections``.  ``depth=d`` keeps
  d panels in flight (the paper's §5 generalization; DESIGN.md §10).
* ``lu_lookahead(fused_pu=...)``  — **LA_MB**: look-ahead plus a fused
  VMEM-resident panel-update kernel (the malleable-BLAS analogue; see
  ``repro/kernels/fused_panel_update.py``).

Pivoting follows GETRF semantics: ``ipiv[j]`` (0-based, global) is the row
swapped with row ``j`` at step ``j``; row interchanges apply to the full row,
so ``P·A = L·U`` exactly — the numerics are unchanged by look-ahead (at any
depth), which is the property the paper highlights against RTM incremental
pivoting (§3.3).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax.numpy as jnp
from jax import lax

from repro.core import pipeline
from repro.core.backend import Backend, JNP_BACKEND
from repro.core.blocking import BlockSpec
from repro.core.pipeline import StepOps

__all__ = [
    "lu_unblocked",
    "lu_blocked",
    "lu_tiled",
    "lu_lookahead",
    "laswp",
    "permutation_from_pivots",
    "unpack_lu",
    "LU_OPS",
]


# ---------------------------------------------------------------------------
# Unblocked panel factorization (PF) — GETF2 with masked full-width updates.
# ---------------------------------------------------------------------------
def lu_unblocked(panel: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Factor an (m × nb) panel in place: returns (packed LU, piv).

    ``piv`` is panel-relative: at step j, rows ``j`` and ``piv[j]`` (>= j)
    were interchanged.  Uses masked rank-1 updates so all shapes are static
    inside the ``fori_loop`` (the j-th iteration touches only rows/cols > j).
    """
    m, nb = panel.shape
    steps = min(m, nb)
    rows = jnp.arange(m)
    cols = jnp.arange(nb)

    def body(j, carry):
        a, piv = carry
        # --- pivot search over rows >= j of column j --------------------
        col = jnp.abs(a[:, j])
        col = jnp.where(rows < j, -jnp.inf, col)
        p = jnp.argmax(col).astype(jnp.int32)
        piv = piv.at[j].set(p)
        # --- row interchange j <-> p ------------------------------------
        rj, rp = a[j], a[p]
        a = a.at[j].set(rp).at[p].set(rj)
        # --- scale L column and rank-1 update ---------------------------
        pivval = a[j, j]
        l = jnp.where(rows > j, a[:, j] / pivval, 0.0).astype(a.dtype)
        urow = jnp.where(cols > j, a[j], 0.0).astype(a.dtype)
        a = a - jnp.outer(l, urow)
        a = a.at[:, j].set(jnp.where(rows > j, l, a[:, j]))
        return a, piv

    piv0 = jnp.zeros((steps,), jnp.int32)
    out, piv = lax.fori_loop(0, steps, body, (panel, piv0))
    return out, piv


# ---------------------------------------------------------------------------
# Row interchanges (LASWP analogue).
# ---------------------------------------------------------------------------
def laswp(a: jnp.ndarray, piv: jnp.ndarray, offset: int = 0) -> jnp.ndarray:
    """Apply the swap sequence ``row offset+j <-> row offset+piv[j]``.

    The sequence is composed into one permutation first, on integers only;
    the rows it moves are then copied with one gather and one scatter.
    """
    rows, src = _moved_rows(piv, a.shape[0] - offset)
    return a.at[rows + offset].set(a[src + offset])


def _moved_rows(piv: jnp.ndarray, m: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The rows a swap sequence on ``m`` rows can move, and where each one's
    new content comes from: ``P·A`` and ``A`` differ only in rows
    ``[0, bk) ∪ piv``, and row ``rows[i]`` of ``P·A`` is row ``src[i]`` of
    ``A``.  Repeated entries of ``rows`` carry the same ``src``, so a scatter
    through them writes identical values whatever its order."""
    piv = piv.astype(jnp.int32)
    rows = jnp.concatenate([jnp.arange(piv.shape[0], dtype=jnp.int32), piv])
    return rows, permutation_from_pivots(piv, m)[rows]


def permutation_from_pivots(piv: jnp.ndarray, n: int) -> jnp.ndarray:
    """Row-permutation vector ``perm`` such that ``A[perm] == P·A``."""

    def body(j, perm):
        p = piv[j]
        pj, pp = perm[j], perm[p]
        return perm.at[j].set(pp).at[p].set(pj)

    return lax.fori_loop(0, piv.shape[0], body,
                         jnp.arange(n, dtype=piv.dtype))


def unpack_lu(lu: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Split packed LU into (unit-lower L, upper U)."""
    l = jnp.tril(lu, -1) + jnp.eye(lu.shape[0], dtype=lu.dtype)
    u = jnp.triu(lu)
    return l, u


# ---------------------------------------------------------------------------
# The StepOps declaration — everything above is algorithm, everything the
# engine needs to schedule it is below (DESIGN.md §10).
# ---------------------------------------------------------------------------
class _LUCtx(NamedTuple):
    piv: jnp.ndarray          # panel-relative pivots of the factored panel


def _init(a):
    return a, jnp.zeros((min(a.shape),), jnp.int32)


def _factor(state, st, backend, panel_fn):
    # PF(k): ``panel_fn`` (Pallas GETF2 kernel) has the `lu_unblocked`
    # signature: (m × nb panel) -> (packed LU, panel-relative piv).
    a, ipiv = state
    k, bk = st.k, st.bk
    panel, piv = (panel_fn or lu_unblocked)(a[k:, k : k + bk])
    a = a.at[k:, k : k + bk].set(panel)
    ipiv = ipiv.at[k : k + bk].set(piv + k)
    return (a, ipiv), _LUCtx(piv)


def _swap(state, ctx, st, backend):
    # Interchanges of panel k applied to every column outside the panel —
    # eager under mtb/rtm, deferred one iteration under la (Listing 5).  One
    # gather and one scatter of the moved full rows; the panel's columns
    # keep what PF wrote, as its rows were swapped inside PF.
    a, ipiv = state
    k, k_next = st.k, st.k_next
    rows, src = _moved_rows(ctx.piv, a.shape[0] - k)
    rows, src = rows + k, src + k
    cols = jnp.arange(a.shape[1])
    in_panel = (cols >= k) & (cols < k_next)
    a = a.at[rows].set(jnp.where(in_panel, a[rows], a[src]))
    return (a, ipiv)


def _update(state, ctx, st, c0, c1, backend):
    # TU_k over columns [c0, c1): TRSM on the block row, GEMM below it.
    a, ipiv = state
    k, bk, k_next = st.k, st.bk, st.k_next
    l11 = a[k : k + bk, k : k + bk]
    u12 = backend.trsm(l11, a[k : k + bk, c0:c1],
                       side="left", lower=True, unit_diagonal=True)
    a = a.at[k : k + bk, c0:c1].set(u12)
    l21 = a[k_next:, k : k + bk]
    a = a.at[k_next:, c0:c1].set(
        backend.update(a[k_next:, c0:c1], l21, u12))
    return (a, ipiv)


def _tiles(state, ctx, st, backend):
    # RTM: one TRSM task per trailing column panel, one GEMM task per tile.
    a, ipiv = state
    n = a.shape[1]
    k, bk = st.k, st.bk
    l11 = a[k : k + bk, k : k + bk]
    for j in range(st.k_next, n, bk):
        bj = min(bk, n - j)
        u12 = backend.trsm(l11, a[k : k + bk, j : j + bj],
                           side="left", lower=True, unit_diagonal=True)
        a = a.at[k : k + bk, j : j + bj].set(u12)
        for i in range(st.k_next, n, bk):
            bi = min(bk, n - i)
            l21 = a[i : i + bi, k : k + bk]
            a = a.at[i : i + bi, j : j + bj].set(
                backend.update(a[i : i + bi, j : j + bj], l21, u12))
    return (a, ipiv)


def _pu(state, ctx, st, st_next, backend, fused):
    # LA_MB: TRSM + GEMM + GETF2 in one VMEM-resident kernel call —
    # ``fused(l11, l21, a1l, a2l) -> (u12_panel, packed_panel, piv)``.
    a, ipiv = state
    k, bk, k_next = st.k, st.bk, st.k_next
    lcols = slice(st_next.k, st_next.k_next)
    l11 = a[k : k + bk, k : k + bk]
    l21 = a[k_next:, k : k + bk]
    u12l, panel_next, piv_next = fused(
        l11, l21, a[k : k + bk, lcols], a[k_next:, lcols])
    a = a.at[k : k + bk, lcols].set(u12l)
    a = a.at[k_next:, lcols].set(panel_next)
    ipiv = ipiv.at[st_next.k : st_next.k + st_next.bk].set(
        piv_next + st_next.k)
    return (a, ipiv), _LUCtx(piv_next)


LU_OPS = StepOps(
    name="lu",
    init=_init,
    factor=_factor,
    update=_update,
    finalize=lambda state: state,
    swap=_swap,
    tiles=_tiles,
    pu=_pu,
)


# ---------------------------------------------------------------------------
# Public drivers — thin engine wrappers, signatures unchanged since PR 0.
# ---------------------------------------------------------------------------
def lu_blocked(
    a: jnp.ndarray,
    b: BlockSpec = 128,
    *,
    backend: Backend = JNP_BACKEND,
    panel_fn: Optional[Callable] = None,
    mesh=None,
    layout=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Right-looking blocked LUpp (MTB).  Returns (packed LU, global ipiv).

    ``mesh=`` runs the same schedule over block-cyclic shards, bitwise
    (pivots included) — DESIGN.md §17.
    """
    return pipeline.factorize(LU_OPS, a, b, variant="mtb", backend=backend,
                              panel_fn=panel_fn, mesh=mesh, layout=layout)


def lu_tiled(
    a: jnp.ndarray,
    b: BlockSpec = 128,
    *,
    backend: Backend = JNP_BACKEND,
    panel_fn: Optional[Callable] = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Blocked LUpp with the trailing update fragmented into per-tile tasks
    (RTM, paper Listing 4) — the fragmentation that causes the paper's
    observed RTM overhead on a fast BLAS."""
    return pipeline.factorize(LU_OPS, a, b, variant="rtm", backend=backend,
                              panel_fn=panel_fn)


@pipeline.mark_depth_capable
def lu_lookahead(
    a: jnp.ndarray,
    b: BlockSpec = 128,
    *,
    backend: Backend = JNP_BACKEND,
    panel_fn: Optional[Callable] = None,
    fused_pu: Optional[Callable] = None,
    depth: int = 1,
    mesh=None,
    layout=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """LUpp with static look-ahead; ``depth`` panels in flight.

    ``mesh=`` runs the same depth-d schedule over block-cyclic shards with
    the panel broadcast issued before the bulk update (DESIGN.md §17);
    results stay bitwise, pivots included.

    The pivots of ``PF(k+1)`` are applied lazily at the start of iteration
    k+1 (row interchanges commute with the row-parallel GEMM update),
    keeping GETRF numerics bit-for-bit at every depth.

    ``fused_pu``: optional fused panel-update kernel ``(l11, l21, a1l, a2l)
    -> (u12_panel, packed_panel, piv)`` implementing TRSM+GEMM+PF in one
    VMEM-resident call — the malleable-BLAS (LA_MB) analogue.
    """
    return pipeline.factorize(LU_OPS, a, b, variant="la", depth=depth,
                              backend=backend, panel_fn=panel_fn,
                              fused_pu=fused_pu, mesh=mesh, layout=layout)
