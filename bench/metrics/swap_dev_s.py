"""swap_dev_s: device seconds per step of the engine's row-swap phase,
``bench.factor/repro.SWAP``: LU's ``laswp`` (``core/lu.py``); Cholesky
has none.

Read from the phase and kernel split of :mod:`bench.phases`.
"""


def read(summary):
    return summary.split.per_step()["swap_dev_s"]
