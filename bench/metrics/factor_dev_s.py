"""factor_dev_s: device seconds per step under ``bench.factor``.

The factorization inside the timed step: ``lu_factor`` or
``cholesky_factor`` down through ``core/pipeline.py``, the DMF's panels
(and ``laswp`` for LU) and the backend's GEMMs.
"""


def read(summary):
    t = summary.scope_s.get("bench.factor")
    return t / summary.steps if t and summary.steps else None
