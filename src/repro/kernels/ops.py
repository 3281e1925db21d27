"""Public jit'd wrappers for the Pallas kernels + the Pallas BLAS backend.

Interpret mode is decided at each call from the default backend: on a
TPU every kernel compiles natively through Mosaic and never runs
interpreted; on any other backend (the CPU test suite) the kernel body runs
with ``interpret=True``, validating the same dataflow.  A TPU has no f64
vector unit, so a wrapper handed f64 there raises :class:`KernelDtypeError`
instead of running something else.

``PALLAS_BACKEND`` plugs into :mod:`repro.core.backend` so every DMF driver
can run on top of the paper-analogous BLIS kernels; its ``panel_fns`` /
``fused_pu`` registries are how ``backend="pallas"`` routes the drivers
through the VMEM-resident panel kernels (``FUSED_PU`` is what the ``la_mb``
variant resolves through).

VMEM-budget fallback (DESIGN.md §15): each wrapper checks the kernel's VMEM
footprint at the input dtype against :data:`VMEM_PANEL_BUDGET` (from the §9
machine record, :data:`repro.tune.model.MACHINE`) and falls back to the
traced panel / composed update for shapes that don't fit — the paper sizes
``b`` to the cache for the same reason.  The fallback is *bitwise
transparent* on the interpret backend (each Pallas kernel traces the same
op sequence as its fallback) and *observable*: with a tracer installed
(:mod:`repro.obs`) the wrapper emits a zero-duration span tagged
``meta={"fallback": "vmem"}`` instead of silently rerouting.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.backend import Backend, trsm_jnp
from repro.kernels import blis_gemm as _bg
from repro.kernels import fused_panel_update as _fpu
from repro.kernels import panel_hessenberg as _phs
from repro.kernels import panel_lu as _plu
from repro.kernels import panel_qr as _pqr
from repro.kernels import panel_qrcp as _pqrcp
from repro.kernels import panels as _panels
from repro.kernels import trsm as _tr
from repro.obs import tracer as _obs
from repro.tune.model import MACHINE

# largest working set (bytes, at the input dtype) a single-cell kernel may
# claim in VMEM before falling back — single-sourced from the machine record.
VMEM_PANEL_BUDGET = MACHINE.vmem_panel_budget_bytes


class KernelDtypeError(TypeError):
    """A Pallas kernel was handed a dtype the TPU cannot run (f64)."""


class KernelNotLoweredError(NotImplementedError):
    """The kernel has no Mosaic lowering yet, so it cannot run on a TPU."""


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret(*arrays) -> bool:
    """Interpret mode for this call: off on a TPU, on everywhere else.

    On a TPU, f64 operands raise :class:`KernelDtypeError`.
    """
    if not _on_tpu():
        return True
    for x in arrays:
        if x.dtype == jnp.float64:
            raise KernelDtypeError(
                f"Pallas kernels take f32/bf16 on a TPU, got {x.dtype}")
    return False


def _require_lowering(name: str) -> None:
    """Raise on a TPU for a kernel Mosaic cannot compile yet.

    The QR-family sweeps (``qr_unblocked``, ``_qrcp_sweep``,
    ``_hessenberg_sweep``) still index values dynamically and call the
    canonical GEMM, whose K loop slices values; Mosaic refuses both.
    """
    if _on_tpu():
        raise KernelNotLoweredError(
            f"{name}: no TPU lowering yet; use backend='jnp' on a TPU")


def _nbytes(itemsize: int, *shapes) -> int:
    """Footprint of ``shapes`` at ``itemsize`` bytes per element."""
    total = 0
    for s in shapes:
        n = 1
        for d in s:
            n *= d
        total += itemsize * n
    return total


def _note_fallback(name: str, *shapes) -> None:
    """Tag a VMEM-budget fallback on the installed tracer (if any).

    Zero-duration span, ``meta={"fallback": "vmem"}`` — the observable
    record that a Pallas wrapper rerouted to its traced/composed twin.
    """
    tr = _obs.active()
    if tr is None:
        return
    t = tr.clock()
    dims = ",".join("x".join(str(d) for d in s) for s in shapes)
    tr.add(_obs.Span("panel", f"{name}[{dims}]->fallback", t, t,
                     meta={"fallback": "vmem"}))


# ---------------------------------------------------------------------------
# GEMM / TRSM (the BLAS-3 layer)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("blocks",))
def gemm(a, b, blocks=None):
    """C = A·B via the BLIS five-loop Pallas kernel."""
    return _bg.blis_gemm(a, b, blocks=blocks, interpret=_interpret(a, b))


@functools.partial(jax.jit, static_argnames=("alpha", "blocks"))
def gemm_accum(c, a, b, alpha=-1.0, blocks=None):
    """O = C + alpha·A·B (fused trailing update)."""
    return _bg.blis_gemm_accum(c, a, b, alpha=alpha, blocks=blocks,
                               interpret=_interpret(c, a, b))


def trsm(t, b, *, side="left", lower=True, trans=False, unit_diagonal=False):
    """Backend-compatible TRSM; Pallas path for the two DMF shapes."""
    if side == "left" and lower and not trans:
        return _tr.trsm_left_lower(t, b, unit_diagonal=unit_diagonal,
                                   interpret=_interpret(t, b))
    if side == "right" and lower and trans:
        return _tr.trsm_right_lower_t(t, b, unit_diagonal=unit_diagonal,
                                      interpret=_interpret(t, b))
    # other combinations are not on the DMF hot path — defer to XLA
    return trsm_jnp(t, b, side=side, lower=lower, trans=trans,
                    unit_diagonal=unit_diagonal)


def lu_solve_small(lu, b):
    """Fused small-RHS LU solve (forward+back substitution in one kernel).

    The solve-layer analogue of the fused panel-update: for small
    factor-once/solve-many systems both substitution sweeps run in a single
    VMEM residency of the packed factor.  Falls back to the two XLA
    triangular solves when the factor exceeds the VMEM budget.
    """
    if _nbytes(b.dtype.itemsize, lu.shape, b.shape, b.shape) \
            > VMEM_PANEL_BUDGET:
        _note_fallback("lu_solve_small", lu.shape, b.shape)
        y = trsm_jnp(lu, b, side="left", lower=True, unit_diagonal=True)
        return trsm_jnp(lu, y, side="left", lower=False)
    return _tr.lu_solve_small(lu, b, interpret=_interpret(lu, b))


# ---------------------------------------------------------------------------
# Panel factorizations (the sequential bottleneck, VMEM-resident).
#
# Each wrapper's fallback is the *traced* panel from ``repro.kernels.panels``
# — the Pallas kernel body traces the same op sequence over its VMEM refs,
# so crossing the budget boundary is bitwise invisible on the interpret
# backend (pinned by tests/test_kernels_pallas.py).
# ---------------------------------------------------------------------------
def lu_panel(panel):
    """GETF2 panel kernel; traced-panel fallback beyond the VMEM budget."""
    if _nbytes(panel.dtype.itemsize, panel.shape, panel.shape) \
            > VMEM_PANEL_BUDGET:
        _note_fallback("lu_panel", panel.shape)
        return _panels.lu_panel(panel)
    return _plu.lu_panel(panel, interpret=_interpret(panel))


def qr_panel(panel):
    """GEQR2+LARFT panel kernel; traced-panel fallback."""
    _require_lowering("qr_panel")
    m, nb = panel.shape
    if _nbytes(panel.dtype.itemsize, (m, nb), (m, nb), (nb, nb)) \
            > VMEM_PANEL_BUDGET:
        _note_fallback("qr_panel", panel.shape)
        return _panels.qr_panel(panel)
    return _pqr.qr_panel(panel, interpret=_interpret(panel))


def qrcp_panel(block, steps):
    """xLAQPS panel kernel (in-core norm downdate + pivot argmax).

    Serves both the ``qrcp`` contract (full trailing block) and the
    ``qrcp_local`` windowed-pivoting contract (bare window) — same as the
    traced ``panels.qrcp_panel`` it falls back to.
    """
    _require_lowering("qrcp_panel")
    r, c = block.shape
    if _nbytes(block.dtype.itemsize,
               (r, c), (r, c), (r, steps), (c, steps)) > VMEM_PANEL_BUDGET:
        _note_fallback("qrcp_panel", block.shape)
        return _panels.qrcp_panel(block, steps)
    return _pqrcp.qrcp_panel(block, steps, interpret=_interpret(block))


def hessenberg_panel(a, k, bk):
    """xLAHR2 panel kernel (whole matrix + V/T/W aux VMEM-resident)."""
    _require_lowering("hessenberg_panel")
    n = a.shape[0]
    if _nbytes(a.dtype.itemsize,
               (n, n), (n, n), (n, bk), (n, bk), (bk, bk)) \
            > VMEM_PANEL_BUDGET:
        _note_fallback("hessenberg_panel", a.shape)
        return _panels.hessenberg_panel(a, k, bk)
    return _phs.hessenberg_panel(a, k, bk, interpret=_interpret(a))


# ---------------------------------------------------------------------------
# Fused panel updates — LA_MB (malleable) building blocks.  Fallbacks are
# the eager ``_ref`` twins tracing the identical op sequence (bitwise on
# the interpret backend) — NOT the composed ``ref.py`` oracles.
# ---------------------------------------------------------------------------
def fused_lu_panel_update(l11, l21, a1l, a2l):
    if _nbytes(a2l.dtype.itemsize,
               l11.shape, l21.shape, a1l.shape, a2l.shape, a2l.shape) \
            > VMEM_PANEL_BUDGET:
        _note_fallback("fused_lu_pu", l21.shape, a2l.shape)
        return _fpu.fused_lu_panel_update_ref(l11, l21, a1l, a2l)
    return _fpu.fused_lu_panel_update(
        l11, l21, a1l, a2l, interpret=_interpret(l11, l21, a1l, a2l))


def fused_cholesky_panel_update(lrow, l21, panel):
    if _nbytes(panel.dtype.itemsize,
               lrow.shape, l21.shape, panel.shape, panel.shape) \
            > VMEM_PANEL_BUDGET:
        _note_fallback("fused_chol_pu", l21.shape, panel.shape)
        return _fpu.fused_cholesky_panel_update_ref(lrow, l21, panel)
    return _fpu.fused_cholesky_panel_update(
        lrow, l21, panel, interpret=_interpret(lrow, l21, panel))


# resolved by repro.core.lookahead.get_variant("<dmf>", "la_mb") — composes
# with any look-ahead depth ("la_mb2", ...): the engine fuses PU(k+1) and
# issues the deeper narrow updates through the regular backend ops.
FUSED_PU = {
    "lu": fused_lu_panel_update,
    "cholesky": fused_cholesky_panel_update,
}

# Panel kernels in the per-DMF ``panel_fn=`` contract documented on each
# ``STEP_OPS`` declaration (DESIGN.md §10/§12).  Every scheduling variant
# of every pipeline-backed driver threads ``panel_fn=`` through
# ``StepOps.factor``, so these plug into mtb/rtm/la(depth=d) uniformly:
#
#     lu_tiled(a, 128, panel_fn=kops.PANEL_KERNELS["lu"])
#
# All five panel contracts now resolve to VMEM-resident Pallas kernels
# (lu / qr / qrcp / qrcp_local / hessenberg — each with the traced-panel
# fallback above).  On a TPU only lu compiles; the QR-family wrappers raise
# ``KernelNotLoweredError`` there (``_require_lowering``).  ldlt stays traced from ``panels.TRACED_PANELS`` (its
# panel is a backend-TRSM diagonal sweep, nothing to pin in VMEM).  The
# traced forms stay reachable as ``panels.TRACED_PANELS[...]`` for explicit
# selection (the tuner's traced-vs-pallas panel axis).  cholesky and
# gauss_jordan have no entry: their panels are backend TRSM / a
# latency-trivial diagonal inverse.
PANEL_KERNELS = {
    **{k: v for k, v in _panels.TRACED_PANELS.items()
       if k not in ("lu", "qr", "qrcp", "qrcp_local", "hessenberg")},
    "lu": lu_panel,
    "qr": qr_panel,
    "qrcp": qrcp_panel,
    "qrcp_local": qrcp_panel,
    "hessenberg": hessenberg_panel,
}


# ---------------------------------------------------------------------------
# The Pallas BLAS backend (drop-in for repro.core.backend.JNP_BACKEND)
# ---------------------------------------------------------------------------
def _backend_gemm(a, b):
    return gemm(a, b)


def _backend_trsm(t, b, *, side="left", lower=True, trans=False,
                  unit_diagonal=False):
    return trsm(t, b, side=side, lower=lower, trans=trans,
                unit_diagonal=unit_diagonal)


def make_pallas_backend(blocks=None) -> Backend:
    """A Pallas backend with an explicit BLIS GEMM blocking.

    ``blocks=None`` → per-shape :func:`repro.tune.model.gemm_blocks` (the
    §9-derived default).  The tuner's kernel-blocking axis instantiates one
    backend per ``(bm, bn, bk)`` candidate; every backend carries the panel
    and fused-PU registries so ``factorize`` / ``la_mb`` resolve the
    VMEM-resident kernels without per-call plumbing.
    """
    if blocks is None:
        g = _backend_gemm
    else:
        def g(a, b, blocks=tuple(blocks)):
            return gemm(a, b, blocks=blocks)
    return Backend(name="pallas", gemm=g, trsm=_backend_trsm,
                   panel_fns=PANEL_KERNELS, fused_pu=FUSED_PU)


PALLAS_BACKEND = make_pallas_backend()
