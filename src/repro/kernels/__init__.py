"""Pallas TPU kernels for the DMF hot spots (validated via interpret=True).

Kernel inventory (each with a pure-jnp oracle in :mod:`repro.kernels.ref`):

* ``blis_gemm``           — BLIS five-loop GEMM → BlockSpec VMEM tiling (§2)
* ``trsm``                — VMEM-resident triangular solve
* ``panel_lu``            — GETF2 with partial pivoting, panel in VMEM
* ``panel_qr``            — GEQR2 + LARFT (packed, tau, T) in one kernel
* ``fused_panel_update``  — PU(k+1) fused: the malleable-BLAS analogue (§4.2)
* ``attention``           — flash-style blockwise attention for the LM zoo
* ``wkv6``                — fused WKV6 chunk sweep (state + score tiles in VMEM)

Public entry points live in :mod:`repro.kernels.ops`.
"""


def single_cell_params():
    """Mosaic compiler params of the whole-panel (``grid=(1,)``) kernels.

    Their working set is resident for the whole sweep, so they ask for
    :attr:`~repro.tune.model.Machine.vmem_kernel_limit_bytes` of scoped
    VMEM instead of Mosaic's default.  Imports are local: this package's
    init must not pull in ``repro.tune`` (and through it ``repro.core``).
    """
    from jax.experimental.pallas import tpu as pltpu

    from repro.tune.model import MACHINE

    return pltpu.CompilerParams(
        vmem_limit_bytes=MACHINE.vmem_kernel_limit_bytes)
