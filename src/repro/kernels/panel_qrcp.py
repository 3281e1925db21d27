"""QRCP panel (xLAQPS) Pallas kernel — norm downdate + pivot argmax in VMEM.

The GEQP3 panel is the most HBM-hostile PF in the repo: every step reads the
*whole* trailing block (pivot argmax over the partial column norms), updates
one column, and downdates the norms — a latency chain of small ops that
round-trips the block through HBM once per reflector when composed from XLA
ops.  This kernel pins the block, the reflector store ``V``, the incremental
``F = B₀ᵀ·V·T``, and the norm vector in VMEM for the entire sweep and writes
the five outputs once.

The kernel body traces :func:`repro.kernels.panels._qrcp_sweep` — the exact
function behind the traced (PR 5) panel — over the VMEM-resident value, so
the Pallas panel is **bitwise identical** to the traced panel on the
interpret backend, which is what makes the VMEM-budget fallback in
``kernels/ops.py`` transparent.  Runs in the input dtype in interpret mode
(f64 included).  The sweep still indexes values dynamically, which Mosaic
cannot lower, so on a TPU the ``ops.qrcp_panel`` wrapper raises
``KernelNotLoweredError``.

Same routine serves both registry keys: ``qrcp`` hands it the full trailing
block (global greedy pivoting) and ``qrcp_local`` hands it the bare
``steps``-column window (windowed pivoting, DESIGN.md §12).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import single_cell_params


def _qrcp_panel_kernel(block_ref, b_ref, v_ref, f_ref, tau_ref, piv_ref, *,
                       steps: int):
    from repro.kernels.panels import _qrcp_sweep

    b, v, f, tau, piv = _qrcp_sweep(block_ref[...], steps)
    b_ref[...] = b
    v_ref[...] = v
    f_ref[...] = f
    tau_ref[...] = tau[:, None]
    piv_ref[...] = piv[:, None]


def qrcp_panel(block: jnp.ndarray, steps: int, *, interpret: bool = False):
    """xLAQPS over an (r × c) trailing block, all ``steps`` reflectors in one
    VMEM residency.  Returns ``(block, v, f, tau, piv)`` — the
    :func:`repro.kernels.panels.qrcp_panel` contract."""
    r, c = block.shape
    b, v, f, tau, piv = pl.pallas_call(
        functools.partial(_qrcp_panel_kernel, steps=steps),
        grid=(1,),
        in_specs=[pl.BlockSpec((r, c), lambda i: (0, 0))],
        out_specs=[
            pl.BlockSpec((r, c), lambda i: (0, 0)),
            pl.BlockSpec((r, steps), lambda i: (0, 0)),
            pl.BlockSpec((c, steps), lambda i: (0, 0)),
            pl.BlockSpec((steps, 1), lambda i: (0, 0)),
            pl.BlockSpec((steps, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, c), block.dtype),
            jax.ShapeDtypeStruct((r, steps), block.dtype),
            jax.ShapeDtypeStruct((c, steps), block.dtype),
            jax.ShapeDtypeStruct((steps, 1), block.dtype),
            jax.ShapeDtypeStruct((steps, 1), jnp.int32),
        ],
        compiler_params=single_cell_params(),
        interpret=interpret,
    )(block)
    return b, v, f, tau[:, 0], piv[:, 0]
