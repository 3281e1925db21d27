"""trsm_dev_s: device seconds per step inside the backend's TRSM,
``bench.*/repro.trsm`` (innermost kernel scope, ``core/backend.py``), in
the panels and the solves alike.

Read from the phase and kernel split of :mod:`bench.phases`.
"""


def read(summary):
    return summary.split.per_step()["trsm_dev_s"]
