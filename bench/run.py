#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload hpl_lu.solve --seed 7 --seconds 30 --trace 0

From the root of a checkout.  The run turns on JAX's persistent
compilation cache in ``.jax_cache/`` of the checkout, makes the cell's
operands on the device from ``--seed``, warms up the cell's one program
with one step (all of that is ``setup_s``), runs steps back to back for
``--seconds``, checks the answers of the window against the plain
reference, and prints one JSON object as the last line of stdout (each
number compared, beside its limit, last on stderr).  ``--trace 1`` runs
the window under the profiler and reports the per-layer metrics instead
of the end-to-end ones.  Without an accelerator, or with fewer chips than
the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: The compilation cache lives at one fixed place inside the checkout.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness

    try:
        line = harness.run(args.workload, args.seed, args.seconds,
                           bool(args.trace), t0=T0)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.print_line(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
