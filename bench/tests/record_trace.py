#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench_trace.py`` reads.

    python bench/tests/record_trace.py --workload hpl_lu.solve \
        --set n=128 --set nb=32 --out fixture

Runs the cell's inputs and step programs at the given sizes for two steps
under the profiler, as a ``--trace 1`` run does, and writes
``<out>/trace.xplane.pb.gz`` and ``<out>/<module>.hlo.txt.gz`` for each
program.  Run it on a TPU and copy the files into ``bench/tests/data``
(the files there were recorded so, on a TPU v5e).
"""
from __future__ import annotations

import argparse
import glob
import gzip
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import numpy as np

    from bench import harness, trace

    _, _, _, cell = harness.build(
        args.workload, {k: int(v) for k, v in (s.split("=") for s in args.set)})
    key = harness.seed_key(1)
    data = cell.prepare(key)
    inputs = jax.jit(cell.inputs).lower(key, np.int32(0), data).compile()
    ops = inputs(key, np.int32(0), data)
    step = jax.jit(cell.step).lower(data, ops).compile()
    jax.block_until_ready(step(data, ops))

    tmp = os.path.join(ROOT, ".bench_trace")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in (1, 2):
            with jax.profiler.TraceAnnotation("bench.inputs"):
                ops = inputs(key, np.int32(i), data)
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                jax.block_until_ready(step(data, ops))
    jax.profiler.stop_trace()
    os.makedirs(args.out, exist_ok=True)
    (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    with open(path, "rb") as f, gzip.open(
            os.path.join(args.out, "trace.xplane.pb.gz"), "wb") as g:
        g.write(f.read())
    for prog in (inputs, step):
        text = prog.as_text()
        with gzip.open(os.path.join(
                args.out, trace.module_name(text) + ".hlo.txt.gz"),
                "wt") as g:
            g.write(text)
    shutil.rmtree(tmp, ignore_errors=True)
    print(sorted(os.listdir(args.out)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
