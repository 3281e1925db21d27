"""gp_rbf: one exact-GP log marginal likelihood (GPML Algorithm 2.1).

Set-up draws X (n × d) and y from the seed on the device and takes the
base lengthscale from the median pairwise distance of the first 1024
points, worked out on the host.  Each step perturbs the lengthscale and the noise variance by up
to ``perturb`` (relative, log scale) from (seed, step), as a
hyperparameter optimizer moves them, builds K + σ²I on the device and
runs ``cholesky_factor`` → ``.solve(y)`` → ``.logdet()`` and the log
likelihood.  K is the step's operand: the inputs program builds it, so
the check can build the same bits again.

The check takes that K to float64 on the host and compares α by its
backward error and log det K with a float64 Cholesky's
(:mod:`bench.plain`).  The log likelihood itself is not compared: its
float32 error follows the conditioning of yᵀα (PERF.md §2), and it is a
function of the two numbers that are.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import plain

#: Points the median-distance heuristic looks at.
_MEDIAN_POINTS = 1024
#: Stream of the per-run data; steps use streams 0, 1, 2, ...
_DATA_STREAM = np.uint32(0xFFFFFFFF)


def rbf_kernel(x, lengthscale, noise):
    """``exp(−‖xᵢ−xⱼ‖²/2ℓ²) + σ²·I`` in float32, HIGHEST-precision Gram."""
    sq = jnp.sum(x * x, axis=1)
    gram = jnp.matmul(x, x.T, precision=lax.Precision.HIGHEST)
    d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * gram, 0.0)
    k = jnp.exp(d2 * (-0.5 / lengthscale ** 2))
    return k + noise * jnp.eye(x.shape[0], dtype=x.dtype)


class Cell:
    def __init__(self, cfg: dict, traffic: dict):
        self.n, self.d, self.nb = cfg["n"], cfg["d"], cfg["nb"]
        self.noise = cfg["noise_variance"]
        self.perturb = traffic["perturb"]
        self.limits = dict(cfg["limits"])

    def prepare(self, key):
        x, y = jax.jit(self._draw)(key)
        # The median on the host: a sort costs the TPU's compiler ~50 s.
        m = np.asarray(jax.device_get(x[:_MEDIAN_POINTS]), np.float64)
        sq = np.einsum("ij,ij->i", m, m)
        d2 = sq[:, None] + sq[None, :] - 2.0 * (m @ m.T)
        length = np.sqrt(np.median(d2[np.triu_indices(len(m), 1)]))
        return x, y, jnp.float32(length)

    def _draw(self, key):
        kx, kw, ke = jax.random.split(jax.random.fold_in(key, _DATA_STREAM), 3)
        x = jax.random.normal(kx, (self.n, self.d), jnp.float32)
        w = jax.random.normal(kw, (self.d,), jnp.float32)
        y = (jnp.sin(x @ w / math.sqrt(self.d))
             + 0.1 * jax.random.normal(ke, (self.n,), jnp.float32))
        return x, (y - y.mean()) / y.std()

    def inputs(self, key, i, data):
        x, _, length0 = data
        u = jax.random.uniform(jax.random.fold_in(key, i), (2,), jnp.float32,
                               -1.0, 1.0)
        with jax.named_scope("bench.inputs"):
            return rbf_kernel(x, length0 * jnp.exp(self.perturb * u[0]),
                              jnp.float32(self.noise)
                              * jnp.exp(self.perturb * u[1]))

    def _loglik(self, y, alpha, logdet):
        with jax.named_scope("bench.loglik"):
            return (logdet, -0.5 * jnp.dot(y, alpha) - 0.5 * logdet
                    - 0.5 * self.n * math.log(2.0 * math.pi))

    def step(self, data, k):
        from repro.solve import cholesky_factor

        y = data[1]
        with jax.named_scope("bench.factor"):
            f = cholesky_factor(k, self.nb)
        with jax.named_scope("bench.solve"):
            alpha = f.solve(y)
        with jax.named_scope("bench.loglik"):
            _, logdet = f.logdet()
        return (alpha, *self._loglik(y, alpha, logdet))

    def control(self, data, k, precision: str):
        y = data[1]
        with jax.named_scope("bench.factor"):
            l = plain.cholesky(k, self.nb, precision)
        with jax.named_scope("bench.solve"):
            z = plain.triangular_solve(l, y[:, None], self.nb, precision,
                                       lower=True)
            alpha = plain.triangular_solve(l, z, self.nb, precision,
                                           lower=True, trans=True)[:, 0]
        with jax.named_scope("bench.loglik"):
            logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(l)))
        return (alpha, *self._loglik(y, alpha, logdet))

    def check(self, data, k, out) -> dict[str, float]:
        y = np.asarray(jax.device_get(data[1]), np.float64)
        k = np.asarray(jax.device_get(k), np.float64)
        alpha, logdet, _ = out
        ref = plain.logdet_spd64(k)
        rel = abs(float(logdet) - ref) / abs(ref) / plain.EPS32
        return {"alpha_backward_error_eps": plain.solve_error(k, alpha, y),
                "logdet_rel_error_eps": rel if np.isfinite(rel)
                else float("inf")}

    def plant(self, kind: str, data, ops, out):
        """``out`` with a fault planted, for the CPU tests (never timed):
        ``state_unchanged`` hands back y as α, ``answer_altered`` scales
        α's first entry by 1.01 and log det by 1 + 1e-4."""
        alpha, logdet, *rest = out
        if kind == "state_unchanged":
            return (data[1], logdet, *rest)
        if kind == "answer_altered":
            return (alpha.at[0].multiply(1.01), logdet * (1 + 1e-4), *rest)
        raise ValueError(f"no planted fault {kind!r}")
