"""Pre-refactor DMF loop drivers, kept verbatim as the bitwise golden reference.

ISSUE 3 replaced the hand-written MTB/RTM/LA loop bodies in
``repro/core/{lu,cholesky,qr,ldlt,gauss_jordan}.py`` with ``StepOps``
declarations consumed by the generic engine in ``repro/core/pipeline.py``.
The acceptance bar is *bitwise* equality: the engine must emit the exact op
sequence the removed loops emitted.  Hard-coded checksums would pin one
machine's float behaviour, so instead this module preserves the removed loop
bodies **unchanged** (same slicing, same op order), importing the panel /
update building blocks from the live modules — the building blocks were not
touched by the refactor, so any test divergence isolates to the loop
restructuring under test.  The one exception is ``laswp``: the live one
applies a panel's interchanges as a single gather and scatter, so the
sequential per-pivot loop it replaced is frozen here too (copied from commit
857133f), and the engine's row interchanges are checked against it.

Copied from commit c8308c9 (PR 2 head).  Do not "improve" this file: it is a
historical artifact by design.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.core.backend import JNP_BACKEND
from repro.core.blocking import panel_steps, split_trailing
from repro.core.cholesky import cholesky_panel
from repro.core.gauss_jordan import gj_inverse_unblocked
from repro.core.ldlt import ldlt_panel
from repro.core.lu import lu_unblocked
from repro.core.qr import (_factor_panel, _Panel, apply_qt_blocked,
                           build_t_matrix, unpack_v)


# ---------------------------------------------------------------------------
# LU — verbatim pre-refactor laswp / lu_blocked / lu_tiled / lu_lookahead.
# ---------------------------------------------------------------------------
def laswp(a, piv, offset=0):
    """Apply the swap sequence ``row offset+j <-> row offset+piv[j]``."""

    def body(j, a):
        p = piv[j] + offset
        q = j + offset
        rq, rp = a[q], a[p]
        return a.at[q].set(rp).at[p].set(rq)

    return lax.fori_loop(0, piv.shape[0], body, a)


def lu_blocked(a, b=128, *, backend=JNP_BACKEND, panel_fn=None):
    n = a.shape[0]
    panel_fn = panel_fn or lu_unblocked
    ipiv = jnp.zeros((min(a.shape),), jnp.int32)

    for st in panel_steps(n, b):
        k, bk = st.k, st.bk
        panel, piv = panel_fn(a[k:, k : k + bk])
        a = a.at[k:, k : k + bk].set(panel)
        ipiv = ipiv.at[k : k + bk].set(piv + k)
        if k > 0:
            a = a.at[:, :k].set(laswp(a[:, :k], piv, offset=k))
        if st.k_next < n:
            a = a.at[:, st.k_next :].set(laswp(a[:, st.k_next :], piv, offset=k))
            l11 = a[k : k + bk, k : k + bk]
            u12 = backend.trsm(l11, a[k : k + bk, st.k_next :],
                               side="left", lower=True, unit_diagonal=True)
            a = a.at[k : k + bk, st.k_next :].set(u12)
            l21 = a[st.k_next :, k : k + bk]
            a = a.at[st.k_next :, st.k_next :].set(
                backend.update(a[st.k_next :, st.k_next :], l21, u12))
    return a, ipiv


def lu_tiled(a, b=128, *, backend=JNP_BACKEND):
    n = a.shape[0]
    ipiv = jnp.zeros((min(a.shape),), jnp.int32)

    for st in panel_steps(n, b):
        k, bk = st.k, st.bk
        panel, piv = lu_unblocked(a[k:, k : k + bk])
        a = a.at[k:, k : k + bk].set(panel)
        ipiv = ipiv.at[k : k + bk].set(piv + k)
        if k > 0:
            a = a.at[:, :k].set(laswp(a[:, :k], piv, offset=k))
        if st.k_next >= n:
            break
        a = a.at[:, st.k_next :].set(laswp(a[:, st.k_next :], piv, offset=k))
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
    return a, ipiv


def lu_lookahead(a, b=128, *, backend=JNP_BACKEND, fused_pu=None):
    n = a.shape[0]
    ipiv = jnp.zeros((min(a.shape),), jnp.int32)
    steps = list(panel_steps(n, b))

    st0 = steps[0]
    panel, piv = lu_unblocked(a[:, : st0.bk])
    a = a.at[:, : st0.bk].set(panel)
    ipiv = ipiv.at[: st0.bk].set(piv)
    pending_piv = piv

    for st in steps:
        k, bk, k_next = st.k, st.bk, st.k_next
        lcols, rcols = split_trailing(k_next, st.b_next, n)
        if k > 0:
            a = a.at[:, :k].set(laswp(a[:, :k], pending_piv, offset=k))
        if k_next < n:
            a = a.at[:, k_next:].set(laswp(a[:, k_next:], pending_piv, offset=k))
        if k_next >= n:
            break

        l11 = a[k : k + bk, k : k + bk]
        l21 = a[k_next:, k : k + bk]

        if fused_pu is not None and st.b_next > 0:
            u12l, panel_next, piv_next = fused_pu(
                l11, l21, a[k : k + bk, lcols], a[k_next:, lcols])
            a = a.at[k : k + bk, lcols].set(u12l)
            a = a.at[k_next:, lcols].set(panel_next)
        elif st.b_next > 0:
            u12l = backend.trsm(l11, a[k : k + bk, lcols],
                                side="left", lower=True, unit_diagonal=True)
            a = a.at[k : k + bk, lcols].set(u12l)
            nxt = backend.update(a[k_next:, lcols], l21, u12l)
            panel_next, piv_next = lu_unblocked(nxt)
            a = a.at[k_next:, lcols].set(panel_next)
        if st.b_next > 0:
            ipiv = ipiv.at[k_next : k_next + st.b_next].set(piv_next + k_next)

        if rcols.start < n:
            u12r = backend.trsm(l11, a[k : k + bk, rcols],
                                side="left", lower=True, unit_diagonal=True)
            a = a.at[k : k + bk, rcols].set(u12r)
            a = a.at[k_next:, rcols].set(
                backend.update(a[k_next:, rcols], l21, u12r))

        pending_piv = piv_next if st.b_next > 0 else None
    return a, ipiv


# ---------------------------------------------------------------------------
# Cholesky — verbatim pre-refactor blocked / tiled / lookahead.
# ---------------------------------------------------------------------------
def cholesky_blocked(a, b=128, *, backend=JNP_BACKEND):
    n = a.shape[0]
    for st in panel_steps(n, b):
        k, bk, k_next = st.k, st.bk, st.k_next
        a = a.at[k:, k : k + bk].set(
            cholesky_panel(a[k:, k : k + bk], bk, backend))
        if k_next < n:
            l21 = a[k_next:, k : k + bk]
            a = a.at[k_next:, k_next:].set(
                backend.update(a[k_next:, k_next:], l21, l21.T))
    return jnp.tril(a)


def cholesky_tiled(a, b=128, *, backend=JNP_BACKEND):
    n = a.shape[0]
    for st in panel_steps(n, b):
        k, bk, k_next = st.k, st.bk, st.k_next
        a = a.at[k:, k : k + bk].set(
            cholesky_panel(a[k:, k : k + bk], bk, backend))
        for j in range(k_next, n, bk):
            bj = min(bk, n - j)
            lj = a[j : j + bj, k : k + bk]
            for i in range(j, n, bk):
                bi = min(bk, n - i)
                li = a[i : i + bi, k : k + bk]
                a = a.at[i : i + bi, j : j + bj].set(
                    backend.update(a[i : i + bi, j : j + bj], li, lj.T))
    return jnp.tril(a)


def cholesky_lookahead(a, b=128, *, backend=JNP_BACKEND, fused_pu=None):
    n = a.shape[0]
    steps = list(panel_steps(n, b))

    st0 = steps[0]
    a = a.at[:, : st0.bk].set(cholesky_panel(a[:, : st0.bk], st0.bk, backend))

    for st in steps:
        k, bk, k_next = st.k, st.bk, st.k_next
        if k_next >= n:
            break
        lcols, rcols = split_trailing(k_next, st.b_next, n)
        l21 = a[k_next:, k : k + bk]

        if st.b_next > 0:
            lrow_next = a[lcols, k : k + bk]
            if fused_pu is not None:
                panel_next = fused_pu(lrow_next, l21, a[k_next:, lcols])
            else:
                upd = backend.update(a[k_next:, lcols], l21, lrow_next.T)
                panel_next = cholesky_panel(upd, st.b_next, backend)
            a = a.at[k_next:, lcols].set(panel_next)

        if rcols.start < n:
            lrow_r = a[rcols, k : k + bk]
            a = a.at[rcols.start :, rcols].set(
                backend.update(a[rcols.start :, rcols],
                               a[rcols.start :, k : k + bk], lrow_r.T))
    return jnp.tril(a)


# ---------------------------------------------------------------------------
# QR — verbatim pre-refactor blocked / tiled / lookahead.
# ---------------------------------------------------------------------------
def qr_blocked(a, b=128, *, backend=JNP_BACKEND):
    m, n = a.shape
    taus = jnp.zeros((min(m, n),), a.dtype)
    for st in panel_steps(n, b):
        k, bk, k_next = st.k, st.bk, st.k_next
        if k >= m:
            break
        packed, tau, p = _factor_panel(a[k:, k : k + bk])
        a = a.at[k:, k : k + bk].set(packed)
        taus = taus.at[k : k + bk].set(tau[: min(bk, m - k)])
        if k_next < n:
            a = a.at[k:, k_next:].set(
                apply_qt_blocked(p, a[k:, k_next:], backend))
    return a, taus


def qr_tiled(a, b=128, *, backend=JNP_BACKEND):
    m, n = a.shape
    taus = jnp.zeros((min(m, n),), a.dtype)
    for st in panel_steps(n, b):
        k, bk, k_next = st.k, st.bk, st.k_next
        if k >= m:
            break
        packed, tau, p = _factor_panel(a[k:, k : k + bk])
        a = a.at[k:, k : k + bk].set(packed)
        taus = taus.at[k : k + bk].set(tau[: min(bk, m - k)])
        for j in range(k_next, n, bk):
            bj = min(bk, n - j)
            a = a.at[k:, j : j + bj].set(
                apply_qt_blocked(p, a[k:, j : j + bj], backend))
    return a, taus


def qr_lookahead(a, b=128, *, backend=JNP_BACKEND, fused_pu=None):
    m, n = a.shape
    taus = jnp.zeros((min(m, n),), a.dtype)
    steps = list(panel_steps(n, b))

    st0 = steps[0]
    packed, tau, pnl = _factor_panel(a[:, : st0.bk])
    a = a.at[:, : st0.bk].set(packed)
    taus = taus.at[: st0.bk].set(tau[: min(st0.bk, m)])

    for st in steps:
        k, bk, k_next = st.k, st.bk, st.k_next
        if k_next >= n or k >= m:
            break
        lcols, rcols = split_trailing(k_next, st.b_next, n)

        if st.b_next > 0 and k_next < m:
            if fused_pu is not None:
                packed_n, tau_n = fused_pu(pnl.v, pnl.t, a[k:, lcols])
                upd = packed_n
                a = a.at[k:, lcols].set(upd)
                pkd = a[k_next:, lcols]
                v_n = unpack_v(pkd, st.b_next)
                pnl_next = _Panel(v_n, build_t_matrix(v_n, tau_n))
            else:
                upd = apply_qt_blocked(pnl, a[k:, lcols], backend)
                packed_n, tau_n, pnl_next = _factor_panel(upd[bk:])
                a = a.at[k:, lcols].set(upd.at[bk:].set(packed_n))
            taus = taus.at[k_next : k_next + st.b_next].set(
                tau_n[: min(st.b_next, m - k_next)])

        if rcols.start < n:
            a = a.at[k:, rcols].set(
                apply_qt_blocked(pnl, a[k:, rcols], backend))

        if st.b_next > 0 and k_next < m:
            pnl = pnl_next
    return a, taus


# ---------------------------------------------------------------------------
# LDLᵀ — verbatim pre-refactor blocked / lookahead.
# ---------------------------------------------------------------------------
def ldlt_blocked(a, b=128, *, backend=JNP_BACKEND):
    n = a.shape[0]
    for st in panel_steps(n, b):
        k, bk, k_next = st.k, st.bk, st.k_next
        a = a.at[k:, k : k + bk].set(ldlt_panel(a[k:, k : k + bk], bk, backend))
        if k_next < n:
            l21 = a[k_next:, k : k + bk]
            d = jnp.diagonal(a[k : k + bk, k : k + bk])
            w = (l21 * d[None, :]).astype(a.dtype)
            a = a.at[k_next:, k_next:].set(
                backend.update(a[k_next:, k_next:], l21, w.T))
    return jnp.tril(a)


def ldlt_lookahead(a, b=128, *, backend=JNP_BACKEND, fused_pu=None):
    n = a.shape[0]
    steps = list(panel_steps(n, b))
    st0 = steps[0]
    a = a.at[:, : st0.bk].set(ldlt_panel(a[:, : st0.bk], st0.bk, backend))

    for st in steps:
        k, bk, k_next = st.k, st.bk, st.k_next
        if k_next >= n:
            break
        lcols, rcols = split_trailing(k_next, st.b_next, n)
        l21 = a[k_next:, k : k + bk]
        d = jnp.diagonal(a[k : k + bk, k : k + bk])

        if st.b_next > 0:
            lrow = a[lcols, k : k + bk]
            w = (lrow * d[None, :]).astype(a.dtype)
            upd = backend.update(a[k_next:, lcols], l21, w.T)
            if fused_pu is not None:
                panel_next = fused_pu(upd, st.b_next)
            else:
                panel_next = ldlt_panel(upd, st.b_next, backend)
            a = a.at[k_next:, lcols].set(panel_next)

        if rcols.start < n:
            lrow_r = a[rcols, k : k + bk]
            w = (lrow_r * d[None, :]).astype(a.dtype)
            a = a.at[rcols.start :, rcols].set(
                backend.update(a[rcols.start :, rcols],
                               a[rcols.start :, k : k + bk], w.T))
    return jnp.tril(a)


# ---------------------------------------------------------------------------
# Gauss–Jordan inversion — verbatim pre-refactor blocked / lookahead.
# ---------------------------------------------------------------------------
def _gj_panel(a, k, bk, backend):
    n = a.shape[0]
    dinv = gj_inverse_unblocked(a[k : k + bk, k : k + bk])
    p = a[:, k : k + bk]
    eye_cols = jnp.zeros((n, bk), a.dtype).at[k : k + bk].set(
        jnp.eye(bk, dtype=a.dtype))
    return backend.gemm(p - eye_cols, dinv)


def gj_inverse_blocked(a, b=128, *, backend=JNP_BACKEND):
    n = a.shape[0]
    for st in panel_steps(n, b):
        k, bk = st.k, st.bk
        m = _gj_panel(a, k, bk, backend)
        arow = a[k : k + bk, :]
        upd = a - backend.gemm(m, arow)
        eye_cols = jnp.zeros((n, bk), a.dtype).at[k : k + bk].set(
            jnp.eye(bk, dtype=a.dtype))
        a = upd.at[:, k : k + bk].set(eye_cols - m)
    return a


def gj_inverse_lookahead(a, b=128, *, backend=JNP_BACKEND):
    n = a.shape[0]
    steps = list(panel_steps(n, b))
    st0 = steps[0]
    m_cur = _gj_panel(a, st0.k, st0.bk, backend)

    for st in steps:
        k, bk, k_next = st.k, st.bk, st.k_next
        arow = a[k : k + bk, :]
        eye_cols = jnp.zeros((n, bk), a.dtype).at[k : k + bk].set(
            jnp.eye(bk, dtype=a.dtype))

        if st.b_next > 0:
            lcols = slice(k_next, k_next + st.b_next)
            pnl = a[:, lcols] - backend.gemm(m_cur, arow[:, lcols])
            a = a.at[:, lcols].set(pnl)
            dinv_next = gj_inverse_unblocked(pnl[k_next : k_next + st.b_next])
            eye_next = jnp.zeros((n, st.b_next), a.dtype).at[lcols].set(
                jnp.eye(st.b_next, dtype=a.dtype))
            m_next = backend.gemm(pnl - eye_next, dinv_next)

        left = a[:, :k] - backend.gemm(m_cur, arow[:, :k]) if k > 0 else a[:, :0]
        rstart = k_next + st.b_next
        right = (a[:, rstart:] - backend.gemm(m_cur, arow[:, rstart:])
                 if rstart < n else a[:, n:])
        a = a.at[:, :k].set(left)
        if rstart < n:
            a = a.at[:, rstart:].set(right)
        a = a.at[:, k : k + bk].set(eye_cols - m_cur)

        if st.b_next > 0:
            m_cur = m_next
    return a
