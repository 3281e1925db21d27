"""Pod-scale look-ahead evidence: distributed schedule comparison.

Runs in a subprocess with 8 virtual CPU devices (the only place outside
``launch/dryrun.py`` that forces a device count).  The child pins itself
to the CPU (``JAX_PLATFORMS=cpu``): on a TPU host the parent may already
hold the chip, and a second process must not reach for it.  Two lanes:

* :func:`run` — the quick default-group lane: wall-clock of the
  block-cyclic LU wrapper with ``lookahead=True`` vs ``False`` (virtual
  CPU devices — directional only, recorded as such) plus the **HLO
  schedule evidence**: collective instruction count and operand bytes for
  both variants.
* :func:`run_extended` (``run.py --distributed`` → ``BENCH_dist.json``) —
  the ISSUE-10 depth sweep: traced eager mesh-engine runs
  (``pipeline.factorize(mesh=...)``) over ``mtb`` and ``la``/``la2``/
  ``la3``, per (variant, depth, nd).  Every row carries the
  broadcast-hidden fraction from ``repro.obs.report.overlap`` — the
  structural share of collective time the schedule moved ahead of the
  bulk trailing update (CPU serializes; a real mesh overlaps — same
  caveat as overlap-efficiency, DESIGN.md §14/§17).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from benchmarks.common import emit, git_commit, validate_rows, parse_row

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import numpy as np, jax, jax.numpy as jnp
from repro.core import distributed as dist
from repro.launch.roofline import collective_bytes

n, b, nd = 512, 64, 4
mesh = jax.make_mesh((nd,), ("model",))
rng = np.random.default_rng(0)
a = jnp.asarray(rng.standard_normal((n, n)).astype(np.float32))
out = {}
for la in (False, True):
    fn = jax.jit(lambda x, la=la: dist.lu_block_cyclic(x, b, mesh, lookahead=la)[0])
    lowered = fn.lower(a)
    compiled = lowered.compile()
    coll = collective_bytes(compiled.as_text())
    jax.block_until_ready(fn(a))
    ts = []
    for _ in range(3):
        t0 = time.perf_counter(); jax.block_until_ready(fn(a))
        ts.append(time.perf_counter() - t0)
    out["la" if la else "mtb"] = {
        "seconds": float(np.median(ts)),
        "collectives": coll,
    }
print("RESULT:" + json.dumps(out))
"""


# Depth-sweep lane: one traced *eager* run per (dmf, variant, nd) — the
# tracer is meaningless under jit (repro.obs.tracer module doc), and the
# mesh engine's per-hook steps are jit-cached internally so the eager loop
# stays one-executable-per-hook fast.
_SWEEP_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import numpy as np, jax, jax.numpy as jnp
from repro import obs
from repro.core.backend import get_backend
from repro.core.lookahead import get_variant, parse_variant
from repro.obs import report

n, b = 256, 32
be = get_backend("jnp")
rng = np.random.default_rng(0)
a = jnp.asarray(rng.standard_normal((n, n)).astype(np.float32))
rows = []
for nd, variants in ((4, ("mtb", "la", "la2", "la3")), (8, ("la2",))):
    mesh = jax.make_mesh((nd,), ("model",))
    for variant in variants:
        fn = get_variant("lu", variant)
        fn(a, b, backend=be, mesh=mesh)            # warm the step caches
        t0 = time.perf_counter()
        with obs.trace() as tr:
            fn(a, b, backend=be, mesh=mesh)
        wall = time.perf_counter() - t0
        rep = report.overlap(tr.spans)
        rows.append({
            "name": f"dist_lu_{variant}_n{n}_b{b}",
            "seconds": wall,
            "nd": nd,
            "depth": parse_variant(variant)[1],
            "overlap_efficiency": rep["overlap_efficiency"],
            "bcast_s": rep["bcast_s"],
            "bcast_bytes": rep["bcast_bytes"],
            "bcast_hidden_frac": rep["bcast_hidden_frac"],
        })
print("RESULT:" + json.dumps(rows))
"""


def _child(script: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT:"):
            return json.loads(line[len("RESULT:"):])
    print(proc.stdout[-2000:])
    print(proc.stderr[-2000:])
    raise RuntimeError("distributed bench failed")


def run():
    res = _child(_CHILD)
    rows = []
    for var, d in res.items():
        coll = d["collectives"]
        rows.append(emit(
            f"dist_lu_{var}_n512_b64_nd4", d["seconds"],
            f"coll_count={coll['count']};coll_bytes="
            f"{sum(v for k, v in coll.items() if k != 'count')}"))
    return rows


def run_extended(json_path: str = "BENCH_dist.json"):
    """Depth-sweep lane (module doc).  Emits one CSV row per
    (variant, depth, nd) and writes the same rows — with the overlap /
    broadcast-hidden extras the CSV derived field only summarizes — as
    schema-validated BENCH_dist.json trajectory records."""
    res = _child(_SWEEP_CHILD)
    commit = git_commit()
    ts = time.time()
    csv_rows, records = [], []
    for d in res:
        derived = (f"nd={d['nd']};depth={d['depth']};"
                   f"bcast_hidden_frac={d['bcast_hidden_frac']:.3f}")
        row = emit(d["name"], d["seconds"], derived)
        csv_rows.append(row)
        rec = parse_row(row, commit, ts)
        rec.update(nd=d["nd"], depth=d["depth"],
                   overlap_efficiency=d["overlap_efficiency"],
                   bcast_s=d["bcast_s"], bcast_bytes=d["bcast_bytes"],
                   bcast_hidden_frac=d["bcast_hidden_frac"])
        records.append(rec)
    validate_rows(records)
    with open(json_path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec, sort_keys=True) + "\n")
    print(f"# wrote {json_path}", file=sys.stderr)
    return csv_rows


if __name__ == "__main__":
    run()
    run_extended()
