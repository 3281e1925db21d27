"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--large] [--only NAME] [--csv PATH]

Emits ``name,us_per_call,derived`` CSV rows (also aggregated at the end).
Mapping to the paper: bench_gemm → Fig 2 (top); bench_lu → Figs 2/4/6;
bench_qr → Fig 7; bench_svd → Fig 8; bench_cholesky → §3.1 generality;
bench_blocksizes → §6.1 block-size choice + tuned-vs-fixed (repro.tune);
bench_distributed → §4 at pod scale (schedule evidence from the optimized
HLO); bench_solve → §8 ("a considerable fraction of LAPACK"): driver +
batched solve throughput; bench_tiles (``--tiles``) → DESIGN.md §16
tile-DAG scheduling vs the pipeline variants.

``--only`` substring-filters the benchmark groups (so the tuner and CI can
run targeted sweeps); ``--csv`` writes the aggregated rows to a file.
``--trace`` additionally runs the observability pass (bench_obs): traced
mtb/la/la2 LU + Cholesky runs, the model-vs-measured attainment table,
and BENCH_obs.json rows.  The trace pass is deliberately *not* subject to ``--only`` — its
artifacts join LU and Cholesky against the cost model regardless of which
benchmark groups were selected.
"""
from __future__ import annotations

import argparse
import sys

CSV_HEADER = "name,us_per_call,derived"


def _groups(args):
    """(name, thunk) per benchmark group — thunks close over problem sizes."""
    from benchmarks import (bench_blocksizes, bench_cholesky,
                            bench_distributed, bench_gemm, bench_lu, bench_qr,
                            bench_solve, bench_svd)

    sizes = (512, 1024, 2048) if args.large else (512, 1024)
    svd_sizes = (384, 768, 1152) if args.large else (384, 768)
    groups = [
        ("gemm", lambda: bench_gemm.run(sizes=sizes)),
        ("lu", lambda: bench_lu.run(sizes=sizes)),
        ("qr", lambda: bench_qr.run(sizes=sizes)),
        ("cholesky", lambda: bench_cholesky.run(sizes=sizes)),
        ("svd", lambda: bench_svd.run(sizes=svd_sizes)),
        ("solve", lambda: bench_solve.run(sizes=sizes)),
        ("blocksizes", lambda: bench_blocksizes.run(n=sizes[-1],
                                                    tuned=not args.skip_tune)),
    ]
    if not args.skip_distributed:
        groups.append(("distributed", bench_distributed.run))
    if args.kernels:
        # ISSUE 8: the Pallas kernel layer (BLIS-GEMM blocking sweep,
        # traced-vs-pallas panels, fused-vs-composed PU) — opt-in because
        # interpret mode makes these slow and their CPU wall-clock is not a
        # speed comparison (bench_gemm.run_kernels docstring).
        groups.append(("kernels", bench_gemm.run_kernels))
    if args.tiles:
        # ISSUE 9: tile-DAG schedule vs the pipeline variants + the tuner
        # arbitration row (bench_tiles module doc) — opt-in because the
        # paired measurements run eagerly and CI gives them their own job.
        from benchmarks import bench_tiles
        groups.append(("tiles", bench_tiles.run))
    if args.distributed:
        # ISSUE 10: the mesh-engine depth sweep (mtb vs la/la2/la3 per
        # device count, broadcast-hidden fraction per row) — opt-in
        # because each traced eager run forces 8 host devices in a child;
        # writes BENCH_dist.json itself (rows carry overlap extras the
        # shared --json schema doesn't).
        groups.append(("distributed-sweep",
                       lambda: bench_distributed.run_extended(
                           json_path=args.distributed_json)))
    return groups


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--large", action="store_true",
                    help="larger problem sizes (slower)")
    ap.add_argument("--skip-distributed", action="store_true")
    ap.add_argument("--skip-tune", action="store_true",
                    help="omit the tuned-vs-fixed row (no tuner search, no "
                         "write to the persistent tune cache)")
    ap.add_argument("--kernels", action="store_true",
                    help="include the Pallas kernel-layer group (BLIS-GEMM "
                         "blocking sweep, traced-vs-pallas panels, "
                         "fused-vs-composed PU -> BENCH_kernels.json rows)")
    ap.add_argument("--tiles", action="store_true",
                    help="include the tile-DAG scheduling group (tiled vs la "
                         "paired rows + the tuned-arbitration row -> "
                         "BENCH_tiles.json rows)")
    ap.add_argument("--distributed", action="store_true",
                    help="include the mesh-engine depth-sweep group (mtb vs "
                         "la/la2/la3 per device count, broadcast-hidden "
                         "fraction per row -> BENCH_dist.json)")
    ap.add_argument("--distributed-json", default="BENCH_dist.json",
                    metavar="PATH",
                    help="BENCH_dist.json path for --distributed rows")
    ap.add_argument("--only", default=None, metavar="NAME",
                    help="run only benchmark groups whose name contains NAME")
    ap.add_argument("--csv", default=None, metavar="PATH",
                    help="also write the aggregated rows to PATH")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write BENCH_*.json trajectory rows (schema: "
                         "bench, n, b, variant, gflops, wall, commit, ts)")
    ap.add_argument("--trace", action="store_true",
                    help="also run the traced observability pass (spans, "
                         "overlap/attainment, BENCH_obs.json)")
    ap.add_argument("--trace-json", default="BENCH_obs.json", metavar="PATH",
                    help="BENCH_obs.json path for --trace rows")
    args = ap.parse_args(argv)

    groups = _groups(args)
    if args.only is not None:
        groups = [(n, fn) for n, fn in groups if args.only in n]
        if not groups:
            ap.error(f"--only {args.only!r} matches no benchmark group "
                     f"(have: {', '.join(n for n, _ in _groups(args))})")

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    rows = []
    print(CSV_HEADER)
    for name, fn in groups:
        rows += fn()
    print(f"\n# {len(rows)} rows")

    if args.csv:
        with open(args.csv, "w") as f:
            f.write(CSV_HEADER + "\n")
            f.writelines(row + "\n" for row in rows)
        print(f"# wrote {args.csv}", file=sys.stderr)

    if args.json:
        from benchmarks.common import write_json_rows
        write_json_rows(args.json, rows)
        print(f"# wrote {args.json}", file=sys.stderr)

    if args.trace:
        from benchmarks import bench_obs
        obs_rows = bench_obs.run_trace(json_path=args.trace_json)
        print(f"# trace pass: {len(obs_rows)} BENCH_obs rows",
              file=sys.stderr)


if __name__ == "__main__":
    main()
