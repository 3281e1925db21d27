"""Traced-engine observability benchmark (DESIGN.md §14).

    PYTHONPATH=src python -m benchmarks.bench_obs \
        [--n N] [--b B] [--dmfs lu,cholesky] [--variants mtb,la,la2] \
        [--json PATH] [--no-hlo] [--small]

For each (dmf, variant) the factorization runs **eagerly** under an
installed :class:`repro.obs.Tracer` (tracing a jitted run would time trace
construction, not device work), then two artifacts are produced:

* one BENCH_obs.json trajectory row per run: the shared schema
  (``benchmarks.common.validate_rows``) plus ``overlap_efficiency``,
  ``critical_path_s``, ``ideal_speedup`` and the model-vs-measured join
  (``model_s``, ``attainment``, ``hlo_flops``, ``hlo_warnings``);
* the attainment table on stdout.

For a timeline, run the same calls under ``jax.profiler.trace(dir,
create_perfetto_trace=True)``: every span is also a profiler annotation
(DESIGN.md §14).

Overlap efficiency is *structural* (see ``repro.obs.report``): on the
serializing CPU backend it reports how much panel time the la(d) schedule
made hideable — 0 for mtb/rtm by construction — not a wall-clock speedup.

The HLO join jit-compiles each (dmf, variant, n) once and feeds the
optimized module text through ``repro.launch.hlo_accounting`` so the row
carries the compiler-side flop count next to the §9 model's; ``--no-hlo``
skips that compile (the CI smoke lane).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from benchmarks.common import (git_commit, random_matrix, random_spd,
                               validate_rows)

#: Input builders per DMF — Cholesky needs SPD.
_INPUTS = {
    "lu": random_matrix,
    "cholesky": random_spd,
    "qr": random_matrix,
    "ldlt": random_spd,
}


def _trace_one(dmf: str, variant: str, n: int, b: int, *, hlo: bool):
    """One eager traced run → (overlap dict, attainment row)."""
    import jax

    from repro.core.lookahead import get_variant
    from repro.obs import Tracer, trace
    from repro.obs import report as obs_report

    a = _INPUTS[dmf](n)
    fn = get_variant(dmf, variant)
    jax.block_until_ready(fn(a, b))          # warm compile caches untraced

    tr = Tracer()
    with trace(tr):
        jax.block_until_ready(fn(a, b))

    hlo_text = None
    if hlo:
        hlo_text = jax.jit(lambda x: fn(x, b)).lower(a).compile().as_text()

    ov = obs_report.overlap(tr.spans)
    row = obs_report.attainment_row(dmf, n, variant, b, tr.spans,
                                    hlo_text=hlo_text)
    return ov, row


def run_trace(dmfs=("lu", "cholesky"), variants=("mtb", "la", "la2"),
              n: int = 512, b: int = 128, json_path: str = "BENCH_obs.json",
              hlo: bool = True, quiet: bool = False):
    """Trace every (dmf, variant); append the rows; return them."""
    from repro.obs import report as obs_report

    commit = git_commit()
    rows, att_rows = [], []
    for dmf in dmfs:
        for variant in variants:
            ov, att = _trace_one(dmf, variant, n, b, hlo=hlo)
            row = dict(att)
            row.update(ov)
            row.update(bench="obs", wall=ov["wall_s"], commit=commit,
                       ts=time.time())
            rows.append(row)
            att_rows.append(att)
            if not quiet:
                print(f"# obs_{dmf}_{variant}_n{n}: overlap_efficiency="
                      f"{ov['overlap_efficiency']:.3f} "
                      f"ideal_speedup={ov['ideal_speedup']:.2f}")

    validate_rows(rows)
    with open(json_path, "a") as f:
        for row in rows:
            f.write(json.dumps(row, sort_keys=True) + "\n")
    if not quiet:
        print(obs_report.format_attainment(att_rows))
        print(f"# wrote {len(rows)} rows to {json_path}", file=sys.stderr)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--b", type=int, default=128)
    ap.add_argument("--dmfs", default="lu,cholesky",
                    help="comma-separated DMF names "
                         f"(have: {', '.join(_INPUTS)})")
    ap.add_argument("--variants", default="mtb,la,la2")
    ap.add_argument("--json", default="BENCH_obs.json")
    ap.add_argument("--no-hlo", action="store_true",
                    help="skip the jit compile that feeds the HLO flop join")
    ap.add_argument("--small", action="store_true",
                    help="CI smoke preset: lu la2 only, n=192 b=64, no HLO")
    args = ap.parse_args(argv)

    if args.small:
        rows = run_trace(dmfs=("lu",), variants=("la2",), n=192, b=64,
                         json_path=args.json, hlo=False)
    else:
        rows = run_trace(dmfs=tuple(args.dmfs.split(",")),
                         variants=tuple(args.variants.split(",")),
                         n=args.n, b=args.b, json_path=args.json, hlo=not args.no_hlo)
    missing = [r for r in rows if "overlap_efficiency" not in r]
    if missing:
        sys.exit(f"{len(missing)} rows missing overlap_efficiency")


if __name__ == "__main__":
    main()
