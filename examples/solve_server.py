"""Factorization-as-a-service demo: bucketed batching + factor cache.

    PYTHONPATH=src python examples/solve_server.py [--requests 48]

Submits a mixed stream of gesv/posv/gels/geqp3 requests, lets the server
bucket and batch them, then reuses one cached LU factor across several
right-hand sides.  Prints the shared serve-layer summary (same schema as
``examples/serve_lm.py``) plus the server's metrics snapshot.
"""
import argparse

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.serve import ServerConfig, SolveServer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    rng = np.random.default_rng(args.seed)
    srv = SolveServer(ServerConfig(max_batch=8, max_wait_s=0.005))

    # mixed heterogeneous load — the server buckets by (dmf, dtype, shape)
    mix = [("gesv", 48, 48, 2), ("gesv", 33, 33, 1), ("posv", 40, 40, 2),
           ("gels", 56, 30, 2), ("geqp3", 64, 17, 1)]
    ids = []
    for i in range(args.requests):
        dmf, m, n, nrhs = mix[i % len(mix)]
        a = rng.standard_normal((m, n)).astype(np.float32)
        if dmf == "posv":
            a = a @ a.T + n * np.eye(n, dtype=np.float32)
        b = rng.standard_normal((m, nrhs)).astype(np.float32)
        ids.append(srv.submit(dmf, a, b))
        srv.pump()
    srv.drain()
    x0 = srv.take(ids[0]).x
    print(f"served {len(ids)} mixed requests; first solution shape "
          f"{tuple(x0.shape)}")

    # factor-once / solve-many: one matrix, several right-hand sides —
    # the second round hits the LRU factor cache instead of refactoring
    a = rng.standard_normal((48, 48)).astype(np.float32)
    for _ in range(3):
        b = rng.standard_normal((48, 2)).astype(np.float32)
        srv.submit("gesv", a, b, cache=True)
        srv.drain()
    print(f"factor cache: hits={srv.factor_cache.hits} "
          f"misses={srv.factor_cache.misses} "
          f"hit_rate={srv.factor_cache.hit_rate:.2f}")

    summ = srv.summary()
    print(f"wall {summ['wall']:.2f} s | {summ['items_per_s']:.1f} req/s | "
          f"p50 {summ['p50_ms']:.1f} ms | p99 {summ['p99_ms']:.1f} ms | "
          f"{summ['gflops_per_s']:.2f} GFLOP/s")
    snap = srv.snapshot()
    for key in sorted(snap):
        if any(s in key for s in ("bucket_fill", "padding_waste", "compiles",
                                  "cache")):
            print(f"  {key} = {snap[key]:.3f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
