"""Distributed DMF + elastic checkpoint tests (subprocess: 8 host devices).

Runs in a child process so the 8-device XLA flag never leaks into the rest
of the suite (smoke tests must see 1 device).

Two device-backed children plus fast single-device tests:

* ``child_result`` — the original wrapper sweep (``lu_block_cyclic`` & co.)
  and the elastic-checkpoint reshard.
* ``matrix_result`` — the ISSUE-10 bitwise matrix: engine mesh variants
  (``pipeline.factorize(mesh=...)`` via ``get_variant``) against the
  single-device engine over lu/cholesky/qr × mtb/la/la2 × f32/f64 ×
  exact/ragged n, **exact equality, pivots included**; plus the solve
  drivers' ``mesh=`` thread-through and one traced ``la2`` run checking
  BCAST spans, shard tags, and ``report.overlap``'s broadcast accounting.
* Single-device: block-cyclic round-trip property tests (1-D and 2-D,
  ragged shapes) and the bitwise N-decomposability pin the distributed
  trailing update relies on (module docstring of
  :mod:`repro.core.distributed`).
"""
import json
import os
import subprocess
import sys

import pytest

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np, jax, jax.numpy as jnp
jax.config.update("jax_enable_x64", True)
from repro.core import distributed as dist, lu as L, qr as Q
from repro.core.cholesky import cholesky_blocked

out = {}
mesh = jax.make_mesh((4,), ("model",))
rng = np.random.default_rng(7)
n, b = 128, 16
A = jnp.asarray(rng.standard_normal((n, n)))

ref_fac, ref_piv = L.lu_blocked(A, b)
for la in (False, True):
    fac, piv = dist.lu_block_cyclic(A, b, mesh, lookahead=la)
    out[f"lu_la{la}_fac"] = float(jnp.abs(fac - ref_fac).max())
    out[f"lu_la{la}_piv"] = bool((piv == ref_piv).all())

S = A @ A.T + n * jnp.eye(n)
ref_l = cholesky_blocked(S, b)
for la in (False, True):
    lf = dist.cholesky_block_cyclic(S, b, mesh, lookahead=la)
    out[f"chol_la{la}"] = float(jnp.abs(lf - ref_l).max())

ref_pk, ref_tau = Q.qr_blocked(A, b)
for la in (False, True):
    pk, tau = dist.qr_block_cyclic(A, b, mesh, lookahead=la)
    out[f"qr_la{la}"] = float(jnp.abs(pk - ref_pk).max())

# elastic checkpoint: save params sharded on 4-dev mesh, restore on 2-dev mesh
import tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train import checkpoint as ck
x = jnp.arange(64.0).reshape(8, 8)
m4 = jax.make_mesh((4,), ("model",))
m2 = jax.make_mesh((2,), ("model",))
xs = jax.device_put(x, NamedSharding(m4, P("model")))
with tempfile.TemporaryDirectory() as d:
    ck.save_checkpoint(d, 1, {"x": xs})
    restored, _ = ck.restore_checkpoint(
        ck.latest_checkpoint(d), {"x": x},
        shardings={"x": NamedSharding(m2, P("model"))})
    out["elastic_ok"] = bool(jnp.abs(restored["x"] - x).max() == 0)
    out["elastic_nshards"] = len(restored["x"].sharding.device_set)

print("RESULT:" + json.dumps(out))
"""


# The ISSUE-10 acceptance matrix.  Exact equality everywhere: the mesh
# engine re-lowers the same StepOps schedule, so any ULP drift is a bug,
# not a tolerance question (repro.core.distributed module docstring).
_MATRIX_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np, jax, jax.numpy as jnp
jax.config.update("jax_enable_x64", True)
from repro.core.backend import get_backend
from repro.core.lookahead import get_variant
from repro import obs
from repro.obs import report
from repro.solve import drivers

out = {}
mesh = jax.make_mesh((4,), ("model",))
be = get_backend("jnp")
rng = np.random.default_rng(11)
b = 16

def exact(x, y):
    lx, ly = jax.tree.leaves(x), jax.tree.leaves(y)
    return len(lx) == len(ly) and all(
        bool((jnp.asarray(p) == jnp.asarray(q)).all())
        for p, q in zip(lx, ly))

# n=64: divisible by nd*b; n=70: ragged both ways (n % b != 0 too)
for dmf in ("lu", "cholesky", "qr"):
    for dt in ("float32", "float64"):
        for n in (64, 70):
            a = rng.standard_normal((n, n)).astype(dt)
            if dmf == "cholesky":
                a = a @ a.T + n * np.eye(n, dtype=dt)
            a = jnp.asarray(a)
            for variant in ("mtb", "la", "la2"):
                fn = get_variant(dmf, variant)
                ref = fn(a, b, backend=be)
                got = fn(a, b, backend=be, mesh=mesh)
                out[f"{dmf}_{variant}_{dt}_n{n}"] = exact(ref, got)

# the mesh's axis types: jax.make_mesh gives Explicit axes by default; the
# engine and the layout helpers must work on Explicit and Auto alike
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.core.distributed import from_block_cyclic, to_block_cyclic
a = jnp.asarray(np.random.default_rng(12).standard_normal((64, 64))
                .astype(np.float32))
for kind in ("Explicit", "Auto"):
    m4 = jax.make_mesh((4,), ("model",),
                       axis_types=(getattr(AxisType, kind),))
    fn = get_variant("lu", "la")
    out[f"axis_{kind}_lu"] = exact(fn(a, b, backend=be),
                                   fn(a, b, backend=be, mesh=m4))
    cyc = jax.device_put(to_block_cyclic(a, 4, b),
                         NamedSharding(m4, P("model", None, None)))
    out[f"axis_{kind}_roundtrip"] = exact(a, from_block_cyclic(cyc, b))

# solve drivers: mesh= accepted, bitwise vs the single-device path
a = jnp.asarray(rng.standard_normal((64, 64)))
rhs = jnp.asarray(rng.standard_normal((64, 3)))
out["gesv"] = exact(drivers.gesv(a, rhs, 16),
                    drivers.gesv(a, rhs, 16, mesh=mesh))
s = a @ a.T + 64 * jnp.eye(64)
out["posv"] = exact(drivers.posv(s, rhs, 16),
                    drivers.posv(s, rhs, 16, mesh=mesh))
ta = jnp.asarray(rng.standard_normal((80, 48)))
trhs = jnp.asarray(rng.standard_normal((80, 2)))
out["gels"] = exact(drivers.gels(ta, trhs, 16),
                    drivers.gels(ta, trhs, 16, mesh=mesh))
try:
    drivers.gels(ta, trhs, 16, mesh=mesh, pivot=True)
    out["gels_pivot_rejected"] = False
except ValueError:
    out["gels_pivot_rejected"] = True

# traced la2 run: BCAST spans carry shard owner + payload bytes, the
# overlap report folds them into a broadcast-hidden fraction, and the
# spans name more than one owning device
with obs.trace() as tr:
    get_variant("lu", "la2")(a, 16, backend=be, mesh=mesh)
bc = [sp for sp in tr.spans if sp.cat == "BCAST"]
out["bcast_spans"] = len(bc)
out["bcast_tagged"] = bool(bc) and all(
    "shard" in sp.meta and sp.meta.get("bytes", 0) > 0 for sp in bc)
rep = report.overlap(tr.spans)
out["bcast_s_pos"] = rep["bcast_s"] > 0
out["bcast_bytes_pos"] = rep["bcast_bytes"] > 0
out["bcast_frac"] = rep["bcast_hidden_frac"]
out["shard_lanes"] = len({sp.meta["shard"] for sp in tr.spans
                         if "shard" in sp.meta})
print("RESULT:" + json.dumps(out))
"""


_PROBE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
print("DEVICES:%d" % jax.local_device_count())
"""


def _available_devices() -> int:
    """Device count the child would see under the forced-8 XLA flag."""
    try:
        proc = subprocess.run([sys.executable, "-c", _PROBE],
                              capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired):
        return 0
    for line in proc.stdout.splitlines():
        if line.startswith("DEVICES:"):
            return int(line[len("DEVICES:"):])
    return 0


def _run_child(script: str) -> dict:
    ndev = _available_devices()
    if ndev < 8:
        pytest.skip(f"needs 8 local host devices, XLA provides {ndev}")
    env = dict(os.environ)
    root = os.path.join(os.path.dirname(__file__), "..")
    env["PYTHONPATH"] = os.path.join(root, "src")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT:"):
            return json.loads(line[len("RESULT:"):])
    raise RuntimeError(f"child failed:\n{proc.stdout[-2000:]}"
                       f"\n{proc.stderr[-3000:]}")


@pytest.fixture(scope="module")
def child_result():
    return _run_child(_CHILD)


@pytest.fixture(scope="module")
def matrix_result():
    return _run_child(_MATRIX_CHILD)


def test_distributed_lu_matches_reference(child_result):
    for la in (False, True):
        assert child_result[f"lu_la{la}_fac"] < 1e-12
        assert child_result[f"lu_la{la}_piv"]


def test_distributed_cholesky_matches_reference(child_result):
    for la in (False, True):
        assert child_result[f"chol_la{la}"] < 1e-12


def test_distributed_qr_matches_reference(child_result):
    for la in (False, True):
        assert child_result[f"qr_la{la}"] < 1e-12


def test_elastic_checkpoint_reshard(child_result):
    assert child_result["elastic_ok"]
    assert child_result["elastic_nshards"] == 2


# ---------------------------------------------------------------------------
# ISSUE-10 bitwise matrix.
# ---------------------------------------------------------------------------
def test_mesh_variants_bitwise(matrix_result):
    """Every (dmf, variant, dtype, n) cell is exactly equal — pivots too."""
    cells = {k: v for k, v in matrix_result.items()
             if any(k.startswith(d) for d in ("lu_", "cholesky_", "qr_"))}
    assert len(cells) == 3 * 3 * 2 * 2          # dmf × variant × dtype × n
    bad = [k for k, ok in cells.items() if not ok]
    assert not bad, bad


@pytest.mark.parametrize("kind", ["Explicit", "Auto"])
def test_mesh_axis_types(matrix_result, kind):
    """``AxisType.Explicit`` meshes (the ``jax.make_mesh`` default) and
    ``Auto`` ones both run the engine bitwise and round-trip a sharded
    block-cyclic array."""
    assert matrix_result[f"axis_{kind}_lu"]
    assert matrix_result[f"axis_{kind}_roundtrip"]


def test_solve_drivers_accept_mesh(matrix_result):
    assert matrix_result["gesv"]
    assert matrix_result["posv"]
    assert matrix_result["gels"]
    assert matrix_result["gels_pivot_rejected"]     # qrcp is mesh-excluded


def test_distributed_trace_bcast_accounting(matrix_result):
    assert matrix_result["bcast_spans"] > 0
    assert matrix_result["bcast_tagged"]
    assert matrix_result["bcast_s_pos"]
    assert matrix_result["bcast_bytes_pos"]
    assert 0.0 <= matrix_result["bcast_frac"] <= 1.0
    assert matrix_result["shard_lanes"] >= 2        # spans of two devices or more


# ---------------------------------------------------------------------------
# Fast single-device tests: layout round-trips + the bitwise contract the
# distributed trailing update is built on.  No mesh, no subprocess.
# ---------------------------------------------------------------------------
def test_block_cyclic_roundtrip_ragged():
    import jax.numpy as jnp
    import numpy as np

    from repro.core.distributed import from_block_cyclic, to_block_cyclic

    rng = np.random.default_rng(0)
    # (m, n, nd, b): exact tilings and every raggedness class —
    # n % b != 0, n % (nd*b) != 0, n < b, n < nd*b
    for m, n, nd, b in [(16, 16, 4, 16), (7, 13, 4, 3), (5, 33, 8, 4),
                        (9, 50, 4, 16), (3, 2, 4, 5), (11, 64, 4, 16)]:
        a = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
        cyc = to_block_cyclic(a, nd, b)
        assert cyc.shape[0] == nd and cyc.shape[1] == m
        assert cyc.shape[2] % b == 0
        back = from_block_cyclic(cyc, b, n=n)
        assert back.shape == a.shape
        assert bool((back == a).all()), (m, n, nd, b)


def test_block_cyclic_2d_roundtrip_ragged():
    import jax.numpy as jnp
    import numpy as np

    from repro.core.distributed import (from_block_cyclic_2d,
                                        to_block_cyclic_2d)

    rng = np.random.default_rng(1)
    for m, n, pr, pc, br, bc in [(16, 16, 2, 2, 4, 4), (7, 13, 2, 4, 3, 2),
                                 (33, 5, 4, 2, 4, 3), (50, 50, 2, 2, 16, 16)]:
        a = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
        cyc = to_block_cyclic_2d(a, (pr, pc), br, bc)
        assert cyc.shape[:2] == (pr, pc)
        back = from_block_cyclic_2d(cyc, br, bc, shape=(m, n))
        assert back.shape == a.shape
        assert bool((back == a).all()), (m, n, pr, pc, br, bc)


def test_update_kernels_column_decomposable():
    """gemm/trsm are bitwise column-decomposable — the property that makes
    the per-block distributed trailing update bit-identical to the wide
    single-device one (repro.core.distributed module docstring)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.backend import gemm_jnp, trsm_jnp

    rng = np.random.default_rng(2)
    for dt in (np.float32, np.float64):
        a = jnp.asarray(rng.standard_normal((48, 48)).astype(dt))
        b = jnp.asarray(rng.standard_normal((48, 80)).astype(dt))
        wide = gemm_jnp(a, b)
        lo = jnp.asarray(np.tril(
            rng.standard_normal((48, 48)).astype(dt)) + 4 * np.eye(48, dtype=dt))
        wide_t = trsm_jnp(lo, b, side="left", lower=True)
        for j0, j1 in [(0, 16), (16, 48), (48, 80), (0, 80), (7, 29)]:
            assert bool((gemm_jnp(a, b[:, j0:j1]) == wide[:, j0:j1]).all()), \
                (str(np.dtype(dt)), j0, j1)
            assert bool((trsm_jnp(lo, b[:, j0:j1], side="left", lower=True)
                         == wide_t[:, j0:j1]).all()), \
                (str(np.dtype(dt)), j0, j1)
