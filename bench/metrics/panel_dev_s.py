"""panel_dev_s: device seconds per step of the engine's panel phase,
``bench.factor/repro.PF``: LU's GETF2 or Cholesky's diagonal block and
the panel's TRSM below it (``core/pipeline.py`` hooks).

Read from the phase and kernel split of :mod:`bench.phases`.
"""


def read(summary):
    return summary.split.per_step()["panel_dev_s"]
