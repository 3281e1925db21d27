"""hpl_lu: HPL's dense system, solved by ``lu_factor`` and ``.solve``.

Each step draws a fresh A (n × n) and b (n × nrhs), uniform in
[−0.5, 0.5] as HPL_dmatgen makes them, from (seed, step) on the device,
and solves A·x = b through the program's public entry, exactly what
``repro.solve.gesv`` does, in one jitted program.  The check is HPL's
scaled residual in float64 on the host (:func:`bench.plain.solve_error`);
the control is the plain blocked LU of :mod:`bench.plain` at a lower
product precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import plain


class Cell:
    def __init__(self, cfg: dict, traffic: dict):
        del traffic  # the solve mix has no parameters of its own
        self.n, self.nb, self.nrhs = cfg["n"], cfg["nb"], cfg["nrhs"]
        self.limits = dict(cfg["limits"])

    def prepare(self, key):
        del key
        return ()

    def inputs(self, key, i, data):
        del data
        ka, kb = jax.random.split(jax.random.fold_in(key, i))
        with jax.named_scope("bench.inputs"):
            a = jax.random.uniform(ka, (self.n, self.n), jnp.float32,
                                   -0.5, 0.5)
            b = jax.random.uniform(kb, (self.n, self.nrhs), jnp.float32,
                                   -0.5, 0.5)
        return a, b

    def step(self, data, ops):
        from repro.solve import lu_factor

        a, b = ops
        with jax.named_scope("bench.factor"):
            f = lu_factor(a, self.nb)
        with jax.named_scope("bench.solve"):
            return f.solve(b)

    def control(self, data, ops, precision: str):
        a, b = ops
        with jax.named_scope("bench.factor"):
            lu, perm = plain.lu_factor(a, self.nb, precision)
        with jax.named_scope("bench.solve"):
            return plain.lu_solve(lu, perm, b, self.nb, precision)

    def check(self, data, ops, out) -> dict[str, float]:
        a, b = jax.device_get(ops)
        return {"backward_error_eps": plain.solve_error(a, out, b)}

    def plant(self, kind: str, data, ops, out):
        """``out`` with a fault planted, for the CPU tests (never timed):
        ``state_unchanged`` hands back b as x, ``answer_altered`` scales
        x's first row by 1.01."""
        if kind == "state_unchanged":
            return ops[1]
        if kind == "answer_altered":
            return out.at[0].multiply(1.01)
        raise ValueError(f"no planted fault {kind!r}")
