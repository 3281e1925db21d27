#!/usr/bin/env python3
"""Readings that the check limits are set from, many seeds in one process.

    python bench/readings.py --workload hpl_lu.solve --seeds 101-112 \
        --steps 2 --control high --control-seeds 201-203

For each seed it sets the cell up as a run does, runs ``--steps`` steps of
the timed program and prints the numbers its check compares, one JSON
object per step; then the same for the plain reference in the program's
place at each ``--control`` precision (``high``: three bf16 passes, the
precision just below the configuration's ``highest``).  The limits in
``bench/configs/<config>.json`` lie between the largest program reading
and the smallest control reading (PERF.md §2).  A benchmark run never runs
this.  ``--set key=value`` overrides a size of the configuration file, for
rehearsals at a small size.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def _seeds(text: str) -> list[int]:
    out = []
    for part in filter(None, text.split(",")):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def readings(workload: str, seeds, steps: int, controls, control_seeds,
             overrides=None, require_chip: bool = True, emit=print):
    """Yield one dict per (who, seed, step) with the check's numbers."""
    import jax
    import numpy as np

    from bench import harness

    _, _, cell = harness.build(workload, overrides, require_chip)
    inputs = jax.jit(cell.inputs)
    runs = [("program", jax.jit(cell.step), seeds)]
    runs += [(f"control:{p}",
              jax.jit(functools.partial(cell.control, precision=p)),
              control_seeds) for p in controls]
    for who, fn, who_seeds in runs:
        for seed in who_seeds:
            key = harness.seed_key(seed)
            data = cell.prepare(key)
            for i in range(1, steps + 1):
                ops = inputs(key, np.int32(i), data)
                t = time.perf_counter()
                out = jax.block_until_ready(fn(data, ops))
                dt = time.perf_counter() - t
                got = cell.check(data, ops, jax.device_get(out))
                emit({"who": who, "seed": seed, "step": i, "step_s": dt,
                      **got})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--steps", type=int, default=1)
    ap.add_argument("--control", action="append", default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--set", action="append", default=[],
                    help="key=value: override a configuration size")
    args = ap.parse_args(argv)
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    overrides = {k: int(v) for k, v in (s.split("=") for s in args.set)}
    from bench import harness

    try:
        readings(args.workload, args.seeds, args.steps, args.control,
                 args.control_seeds, overrides,
                 emit=lambda r: print(json.dumps(r), flush=True))
    except harness.NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
