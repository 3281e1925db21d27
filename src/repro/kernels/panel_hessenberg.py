"""Hessenberg panel (xLAHR2) Pallas kernel — the W=A₀·V build in VMEM.

The GEHRD panel reads the *entire* matrix every step (``W``'s new column is
``A₀·v_j`` over all trailing columns), so composing it from XLA ops streams
the matrix from HBM once per reflector.  This kernel holds the matrix plus
the ``V``/``T``/``W`` aux blocks in VMEM for the whole ``bk``-column sweep.

The kernel body traces :func:`repro.kernels.panels._hessenberg_sweep` — the
function behind the traced (PR 5) panel — so the Pallas panel bitwise-matches
the traced one on the interpret backend (the ``ops.py`` fallback rule's
transparency guarantee).  Runs in the input dtype.

``bk`` is a static kernel parameter (it sizes the aux blocks: one Pallas
trace per (shape, dtype, bk)); the panel offset ``k`` is a *data* operand —
a (1, 1) i32 block — so every panel of a factorization reuses one kernel,
mirroring how the traced panel jit-keys on ``bk`` alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import single_cell_params


def _hessenberg_panel_kernel(a_ref, k_ref, a_out_ref, v_ref, t_ref, w_ref,
                             tau_ref, *, bk: int):
    from repro.kernels.panels import _hessenberg_sweep

    a, v, t, w, tau = _hessenberg_sweep(a_ref[...], k_ref[0, 0], bk)
    a_out_ref[...] = a
    v_ref[...] = v
    t_ref[...] = t
    w_ref[...] = w
    tau_ref[...] = tau[:, None]


def hessenberg_panel(a: jnp.ndarray, k, bk: int, *, interpret: bool = False):
    """xLAHR2: reduce columns ``k .. k+bk`` of the (n × n) matrix with the
    whole working set VMEM-resident.  Returns ``(a, v, t, w, tau)`` — the
    :func:`repro.kernels.panels.hessenberg_panel` contract."""
    n = a.shape[0]
    karr = jnp.asarray(k, jnp.int32).reshape(1, 1)
    a_out, v, t, w, tau = pl.pallas_call(
        functools.partial(_hessenberg_panel_kernel, bk=bk),
        grid=(1,),
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),
            pl.BlockSpec((n, bk), lambda i: (0, 0)),
            pl.BlockSpec((bk, bk), lambda i: (0, 0)),
            pl.BlockSpec((n, bk), lambda i: (0, 0)),
            pl.BlockSpec((bk, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, n), a.dtype),
            jax.ShapeDtypeStruct((n, bk), a.dtype),
            jax.ShapeDtypeStruct((bk, bk), a.dtype),
            jax.ShapeDtypeStruct((n, bk), a.dtype),
            jax.ShapeDtypeStruct((bk, 1), a.dtype),
        ],
        compiler_params=single_cell_params(),
        interpret=interpret,
    )(a, karr)
    return a_out, v, t, w, tau[:, 0]
