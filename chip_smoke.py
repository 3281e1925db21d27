#!/usr/bin/env python3
"""Smoke run of the solve path on a TPU, through the public entry points.

    python chip_smoke.py [--seed 0]     # one chip
    python chip_smoke.py --chips 4      # the mesh path only

One chip, three phases, each checked against a NumPy f64 reference:

  a. ``repro.solve`` drivers: ``gesv`` (LU, look-ahead) and ``posv``
     (Cholesky) on 8192 × 8192 f32 systems with 8 right-hand sides,
     ``gels`` on a tall-skinny 16384 × 1024 f32 least-squares problem.
  b. ``repro.serve.SolveServer`` with the default ``ServerConfig``: mixed
     gesv/posv/gels requests, then one cached LU factor reused across
     several right-hand sides.
  c. jitted ``lu_factor(..., backend="pallas")`` and its solve, with a
     ``repro.obs`` tracer installed; the phase fails if a kernel fell back
     past its VMEM budget.

``--chips 4`` runs only the mesh phase: ``gesv``/``posv`` with ``mesh=``
over four chips at variants mtb and la, each compared with the one-device
answer (max difference, bitwise equality) and with the f64 reference.

Every phase prints its first-call seconds (compile plus run), its steady
seconds and its backward error.  The last line is one JSON object,
``{"ok": true, "device": {...}}``.  The script exits non-zero, with no such
line, when JAX finds no TPU or any check fails.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import warnings

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

EPS = float(np.finfo(np.float32).eps)
#: Bound on every backward error below, in units of f32 epsilon.  HPL's own
#: bound (16, in units of n·ε) does not separate f32 from bf16 work at these
#: sizes: gesv with bf16 inputs and dots scores 15.6 at n = 1024 on the CPU.
#: In units of ε the same run scores about 16000, and f32 about 3.
BOUND = 64.0
#: HPL's acceptance bound on its scaled residual (units of n·ε).
HPL_BOUND = 16.0


def solve_error(a, x, b) -> float:
    """``max_j ‖A·x_j − b_j‖∞ / (‖A‖∞·‖x_j‖∞·ε)`` in f64 on the host.

    HPL's scaled residual times n; the worst right-hand side counts.
    """
    a, x, b = (np.asarray(v, np.float64) for v in (a, x, b))
    r = np.abs(a @ x - b).max(axis=0)
    return float((r / (np.abs(a).sum(axis=1).max()
                       * np.abs(x).max(axis=0) * EPS)).max())


def lstsq_error(a, x, b) -> float:
    """``max_j ‖Aᵀ(A·x_j − b_j)‖∞ / (‖Aᵀ‖∞·(‖A‖∞·‖x_j‖∞ + ‖b_j‖∞)·ε)``.

    The least-squares optimality condition ``Aᵀr = 0``, scaled like
    :func:`solve_error`; f64 on the host.
    """
    a, x, b = (np.asarray(v, np.float64) for v in (a, x, b))
    g = np.abs(a.T @ (a @ x - b)).max(axis=0)
    scale = np.abs(a).sum(axis=0).max() * (
        np.abs(a).sum(axis=1).max() * np.abs(x).max(axis=0)
        + np.abs(b).max(axis=0))
    return float((g / (scale * EPS)).max())


def _report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _check(phase: str, err: float, bound: float = BOUND) -> None:
    if not np.isfinite(err) or err > bound:
        raise SystemExit(f"{phase}: backward error {err!r} exceeds {bound}")


def _timed(fn, *args):
    """(result, first-call seconds, steady seconds) of ``fn(*args)``."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, t1 - t0, time.perf_counter() - t1


def _matrices(seed: int, n: int, nrhs: int):
    """A general and an SPD n × n f32 matrix and a right-hand side block."""
    import jax
    import jax.numpy as jnp

    kg, ks, kb = jax.random.split(jax.random.key(seed), 3)
    a = jax.random.normal(kg, (n, n), jnp.float32)
    g = jax.random.normal(ks, (n, n), jnp.float32)
    spd = jnp.matmul(g, g.T, precision="highest") / n + 0.1 * jnp.eye(n)
    b = jax.random.normal(kb, (n, nrhs), jnp.float32)
    return a, spd, b


def phase_drivers(seed: int, n: int, block: int, nrhs: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.solve import gels, gesv, posv

    a, spd, b = _matrices(seed, n, nrhs)
    for name, fn, mat in (("gesv", gesv, a), ("posv", posv, spd)):
        x, first, steady = _timed(jax.jit(functools.partial(fn, block=block)),
                                  mat, b)
        err = solve_error(mat, x, b)
        _report(f"a.{name}", n=n, block=block, nrhs=nrhs, first_s=first,
                steady_s=steady, backward_error=err, bound=BOUND,
                hpl_scaled_residual=err / n)
        _check(name, err)
        _check(f"{name} (HPL)", err / n, HPL_BOUND)

    m, k = 2 * n, n // 8
    kt, kr = jax.random.split(jax.random.key(seed + 1))
    t = jax.random.normal(kt, (m, k), jnp.float32)
    rhs = jax.random.normal(kr, (m, nrhs), jnp.float32)
    x, first, steady = _timed(jax.jit(functools.partial(gels, block=block)),
                              t, rhs)
    err = lstsq_error(t, x, rhs)
    _report("a.gels", m=m, n=k, block=block, nrhs=nrhs, first_s=first,
            steady_s=steady, backward_error=err, bound=BOUND)
    _check("gels", err)


def phase_server(seed: int) -> None:
    from repro.serve import ServerConfig, SolveServer

    rng = np.random.default_rng(seed)
    mix = [("gesv", 256, 256, 4), ("gesv", 256, 256, 4),
           ("posv", 512, 512, 2), ("posv", 512, 512, 2),
           ("gels", 1024, 256, 2), ("gels", 1024, 256, 2)]
    reqs = []
    for dmf, m, n, nrhs in mix:
        a = rng.standard_normal((m, n)).astype(np.float32)
        if dmf == "posv":
            a = (a @ a.T / n + 0.1 * np.eye(n)).astype(np.float32)
        reqs.append((dmf, a, rng.standard_normal((m, nrhs))
                     .astype(np.float32)))
    srv = SolveServer(ServerConfig())

    def serve_round():
        ids = [srv.submit(dmf, a, b) for dmf, a, b in reqs]
        srv.drain()
        return [srv.take(i).x for i in ids]

    xs, first, steady = _timed(serve_round)
    worst = 0.0
    for (dmf, a, b), x in zip(reqs, xs):
        err = (lstsq_error if dmf == "gels" else solve_error)(a, x, b)
        _check(f"server {dmf} {a.shape}", err)
        worst = max(worst, err)
    _report("b.server_mixed", requests=len(reqs), first_s=first,
            steady_s=steady, backward_error=worst, bound=BOUND)

    a = rng.standard_normal((512, 512)).astype(np.float32)
    rhs = [rng.standard_normal((512, 2)).astype(np.float32) for _ in range(4)]
    worst, times = 0.0, []
    for b in rhs:
        t0 = time.perf_counter()
        rid = srv.submit("gesv", a, b, cache=True)
        srv.drain()
        x = srv.take(rid).x.block_until_ready()
        times.append(time.perf_counter() - t0)
        err = solve_error(a, x, b)
        _check("server cached gesv", err)
        worst = max(worst, err)
    cache = srv.factor_cache
    if (cache.hits, cache.misses) != (len(rhs) - 1, 1):
        raise SystemExit(f"factor cache: {cache.hits} hits, {cache.misses} "
                         f"misses for one matrix and {len(rhs)} solves")
    _report("b.server_cached", solves=len(rhs), first_s=times[0],
            steady_s=float(np.median(times[1:])), cache_hits=cache.hits,
            backward_error=worst, bound=BOUND)


def phase_pallas(seed: int, n: int, block: int, nrhs: int) -> None:
    import jax

    from repro import obs
    from repro.solve import lu_factor

    a, _, b = _matrices(seed + 2, n, nrhs)

    @jax.jit
    def run(a, b):
        return lu_factor(a, block, backend="pallas").solve(b)

    # The wrappers decide a VMEM fallback while tracing, so the tracer sees
    # it under jit too; the span times themselves are not used here.
    with obs.trace() as tr, warnings.catch_warnings():
        warnings.filterwarnings("ignore", "repro.obs: span",
                                RuntimeWarning)
        x, first, steady = _timed(run, a, b)
    falls = [s.name for s in tr.spans if s.meta.get("fallback") == "vmem"]
    if falls:
        raise SystemExit(f"pallas phase fell back past the VMEM budget: "
                         f"{falls}")
    err = solve_error(a, x, b)
    _report("c.pallas_lu", n=n, block=block, nrhs=nrhs, first_s=first,
            steady_s=steady, backward_error=err, bound=BOUND,
            fallbacks=len(falls))
    _check("pallas lu_factor", err)


def phase_mesh(seed: int, n: int, block: int, nrhs: int, chips: int) -> None:
    import jax

    from repro.solve import gesv, posv

    if len(jax.devices()) < chips:
        raise SystemExit(f"--chips {chips}: JAX sees {len(jax.devices())} "
                         f"devices")
    mesh = jax.make_mesh((chips,), ("model",), devices=jax.devices()[:chips])
    a, spd, b = _matrices(seed, n, nrhs)
    for name, fn, mat in (("gesv", gesv, a), ("posv", posv, spd)):
        for variant in ("mtb", "la"):
            one = jax.block_until_ready(fn(mat, b, block, variant=variant))
            x, first, steady = _timed(
                functools.partial(fn, block=block, variant=variant,
                                  mesh=mesh), mat, b)
            err = solve_error(mat, x, b)
            _report(f"mesh.{name}.{variant}", n=n, block=block, chips=chips,
                    first_s=first, steady_s=steady, backward_error=err,
                    bound=BOUND,
                    max_diff_vs_one_device=float(np.abs(
                        np.asarray(x) - np.asarray(one)).max()),
                    bitwise_equal=bool(np.array_equal(np.asarray(x),
                                                      np.asarray(one))),
                    one_device_backward_error=solve_error(mat, one, b))
            _check(f"mesh {name} {variant}", err)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh phase over four chips")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    warm = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    _report("device", platform=devices[0].platform,
            kind=devices[0].device_kind, count=len(devices),
            compile_cache=cache_dir, cache_warm=warm)
    if args.chips == 4:
        phase_mesh(args.seed, 2048, 256, 8, chips=4)
    else:
        phase_drivers(args.seed, 8192, 256, 8)
        phase_server(args.seed)
        phase_pallas(args.seed, 2048, 128, 8)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
