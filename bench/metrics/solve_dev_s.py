"""solve_dev_s: device seconds per step under ``bench.solve``.

The triangular solves from the factors: ``.solve`` through
``solve/triangular.py`` and the backend's ``trsm_jnp``.
"""


def read(summary):
    t = summary.scope_s.get("bench.solve")
    return t / summary.steps if t and summary.steps else None
