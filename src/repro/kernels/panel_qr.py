"""Householder QR panel (GEQR2 + LARFT) Pallas kernel.

Computes the packed panel, tau, *and* the compact-WY ``T`` matrix in a single
VMEM-resident kernel — in the blocked QR the panel+T is the sequential
bottleneck the paper's look-ahead hides, and building T in the same kernel
saves a second pass over V.

The kernel body traces :func:`repro.core.qr.qr_unblocked` +
:func:`~repro.core.qr.build_t_matrix` — the same routines behind the traced
``panels.qr_panel`` — so the Pallas panel is **bitwise identical** to the
jnp panel on the interpret backend and runs in the input dtype (f64
included); the ``ops.py`` VMEM-budget fallback is therefore transparent.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import single_cell_params


def _qr_panel_kernel(a_ref, out_ref, tau_ref, t_ref):
    from repro.core.qr import build_t_matrix, qr_unblocked, unpack_v

    packed, tau = qr_unblocked(a_ref[...])
    v = unpack_v(packed, a_ref.shape[1])
    out_ref[...] = packed
    tau_ref[...] = tau[:, None]
    t_ref[...] = build_t_matrix(v, tau)


def qr_panel(panel: jnp.ndarray, *, interpret: bool = False):
    """Factor an (m × nb) panel: returns (packed, tau, T) — GEQR2+LARFT."""
    m, nb = panel.shape
    out, tau, t = pl.pallas_call(
        _qr_panel_kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((m, nb), lambda i: (0, 0))],
        out_specs=[
            pl.BlockSpec((m, nb), lambda i: (0, 0)),
            pl.BlockSpec((nb, 1), lambda i: (0, 0)),
            pl.BlockSpec((nb, nb), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, nb), panel.dtype),
            jax.ShapeDtypeStruct((nb, 1), panel.dtype),
            jax.ShapeDtypeStruct((nb, nb), panel.dtype),
        ],
        compiler_params=single_cell_params(),
        interpret=interpret,
    )(panel)
    return out, tau[:, 0], t
