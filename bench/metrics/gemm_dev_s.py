"""gemm_dev_s: device seconds per step inside the backend's GEMM,
``bench.*/repro.gemm`` (innermost kernel scope, ``core/backend.py``),
in factor and solve alike.

Read from the phase and kernel split of :mod:`bench.phases`.
"""


def read(summary):
    return summary.split.per_step()["gemm_dev_s"]
