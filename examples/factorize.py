"""DMF scheduling-variant demo — the paper's experiment in miniature.

    PYTHONPATH=src python examples/factorize.py [--n 1024] [--b 192]

Times MTB (fork–join) vs RTM (fragmented) vs LA (static look-ahead) for
LU / QR / Cholesky on JAX's default backend (a TPU where one is attached,
else the CPU) and validates that all variants produce identical results
(the paper's key numerics claim).

Then drives the solve layer (DESIGN.md §8): gesv/posv round trips, QR least
squares, the factor-once/solve-many amortization that motivates the
``repro.solve`` factor objects, a rank-revealing QRCP (geqp3) demo, and a
Hessenberg→eigenvalue pipeline (gehrd) — the two StepOps DMFs added in
ISSUE 4 (DESIGN.md §11).
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.lookahead import get_variant
from repro.solve import gehrd, gels, geqp3, gesv, lu_factor, posv

FLOPS = {"lu": lambda n: 2 * n**3 / 3, "qr": lambda n: 4 * n**3 / 3,
         "cholesky": lambda n: n**3 / 3}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--b", type=int, default=192)
    args = ap.parse_args()
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((args.n, args.n)).astype(np.float32))
    spd = a @ a.T + args.n * jnp.eye(args.n)

    for dmf, x in (("lu", a), ("qr", a), ("cholesky", spd)):
        print(f"--- {dmf} (n={args.n}, b={args.b}) ---")
        outs = {}
        for variant in ("mtb", "rtm", "la"):
            fn = jax.jit(lambda m, v=variant: get_variant(dmf, v)(m, args.b))
            jax.block_until_ready(fn(x))           # compile + warm
            t0 = time.perf_counter()
            out = fn(x)
            jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            outs[variant] = jax.tree.leaves(out)[0]
            gf = FLOPS[dmf](args.n) / dt / 1e9
            print(f"  {variant:3s}: {dt*1e3:8.1f} ms   {gf:7.2f} GFLOPS")
        for v in ("rtm", "la"):
            d = float(jnp.abs(outs[v] - outs["mtb"]).max())
            print(f"  max|{v} − mtb| = {d:.2e}")

    # ---- solve layer: the factorizations put to work ----------------------
    nrhs = 16
    rhs = jnp.asarray(rng.standard_normal((args.n, nrhs)).astype(np.float32))
    print(f"--- solve layer (n={args.n}, nrhs={nrhs}, b={args.b}) ---")

    for name, fn, mat in (("gesv", gesv, a), ("posv", posv, spd)):
        drv = jax.jit(lambda m, r, f=fn: f(m, r, args.b, variant="la"))
        jax.block_until_ready(drv(mat, rhs))
        t0 = time.perf_counter()
        x = drv(mat, rhs)
        jax.block_until_ready(x)
        dt = time.perf_counter() - t0
        res = float(jnp.linalg.norm(mat @ x - rhs) / jnp.linalg.norm(rhs))
        print(f"  {name}: {dt*1e3:8.1f} ms   rel-residual {res:.2e}")

    tall = jnp.asarray(rng.standard_normal((args.n, args.n // 2))
                       .astype(np.float32))
    xl = jax.jit(lambda m, r: gels(m, r, args.b))(tall, rhs)
    nr = float(jnp.linalg.norm(tall.T @ (tall @ xl - rhs)))
    print(f"  gels ({args.n}×{args.n // 2}): normal-eq residual {nr:.2e}")

    # factor once, solve many — the point of the factors objects
    facs = jax.jit(lambda m: lu_factor(m, args.b))(a)
    solve = jax.jit(lambda f, r: f.solve(r))
    jax.block_until_ready(solve(facs, rhs))
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(solve(facs, rhs))
    per_solve = (time.perf_counter() - t0) / 5
    print(f"  factor-once/solve-many: {per_solve*1e3:8.1f} ms per re-solve "
          f"(factorization amortized away)")

    # ---- rank-revealing QRCP: geqp3 + pivoted gels ------------------------
    # the panel runs as a traced fori_loop microkernel (DESIGN.md §12), so
    # the demo size is no longer compile-bound; 128 keeps the printout quick
    nq = min(args.n, 128)
    true_rank = max(4, nq // 8)
    g1 = rng.standard_normal((nq, true_rank)).astype(np.float32)
    g2 = rng.standard_normal((true_rank, nq)).astype(np.float32)
    lowrank = jnp.asarray(g1 @ g2)
    print(f"--- geqp3 rank-revealing (n={nq}, true rank {true_rank}) ---")
    facs = geqp3(lowrank, min(args.b, 64))
    d = np.abs(np.asarray(jnp.diagonal(facs.packed)))
    print(f"  |diag R|: r_00 {d[0]:.2e}   r at rank {d[true_rank - 1]:.2e}   "
          f"past rank {d[true_rank]:.2e}")
    print(f"  estimated rank (rcond=1e-5): {int(facs.rank(rcond=1e-5))}")
    rhs_q = rhs[:nq]
    xq = gels(lowrank, rhs_q, min(args.b, 64), pivot=True, rcond=1e-5)
    res = float(jnp.linalg.norm(lowrank @ xq - rhs_q)
                / jnp.linalg.norm(rhs_q))
    print(f"  pivoted gels on the rank-deficient system: rel-residual "
          f"{res:.3f} with ‖x‖ = {float(jnp.linalg.norm(xq)):.2e} "
          f"(unpivoted QR would blow the solution up)")
    # windowed pivoting (qrcp_local): pivots stay inside the panel window,
    # which legalizes the look-ahead schedule — same rank on this
    # well-conditioned low-rank input (DESIGN.md §12)
    facs_l = geqp3(lowrank, min(args.b, 64), local=True)
    print(f"  windowed pivoting (local=True, variant='la'): rank "
          f"{int(facs_l.rank(rcond=1e-5))} — look-ahead legal")

    # ---- Hessenberg → eigenvalue pipeline: gehrd --------------------------
    nh = min(args.n, 128)                  # traced panel too; capped for the
    #                                        O(n³)·10/3 flops, not compile time
    ah = jnp.asarray(rng.standard_normal((nh, nh)).astype(np.float32))
    print(f"--- gehrd → eigenvalues (n={nh}) ---")
    t0 = time.perf_counter()
    hf = gehrd(ah, min(args.b, 64))
    jax.block_until_ready(hf.packed)
    t_red = time.perf_counter() - t0
    h = hf.h
    sub = float(jnp.abs(jnp.tril(h, -2)).max())
    ev_h = np.sort_complex(np.linalg.eigvals(np.asarray(h)))
    ev_a = np.sort_complex(np.linalg.eigvals(np.asarray(ah)))
    print(f"  reduction: {t_red*1e3:8.1f} ms   below-subdiagonal max {sub:.1e}")
    print(f"  spectrum drift |eig(H) − eig(A)|_max = "
          f"{float(np.abs(ev_h - ev_a).max()):.2e} (similarity preserved)")
    q = hf.q()
    rec = float(jnp.linalg.norm(ah - q @ h @ q.T) / jnp.linalg.norm(ah))
    print(f"  ‖A − Q·H·Qᵀ‖/‖A‖ = {rec:.2e}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
