"""The generic engine vs the pre-refactor loops, and depth-d look-ahead.

Three contracts (ISSUE 3, DESIGN.md §10):

* **bitwise legacy equality** — for every migrated DMF, the engine-emitted
  ``mtb`` / ``rtm`` / ``la(depth=1)`` variants produce *bit-identical*
  output to the removed hand-written drivers (preserved verbatim in
  ``tests/legacy_reference.py``), for f32 and f64, ragged n, and
  non-uniform block schedules — the engine is a pure restructuring;
* **depth-d numerics** — ``la(depth=2)`` (and 3) matches ``la(depth=1)``:
  every trailing column receives the same updates in the same order, only
  the dependence structure changes;
* **depth through the stack** — ``get_variant(dmf, "la2")`` resolves and
  round-trip solves succeed via the ``repro.solve`` drivers' ``depth=``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import legacy_reference as legacy
from repro.core import cholesky as C
from repro.core import gauss_jordan as G
from repro.core import ldlt as D
from repro.core import lu as L
from repro.core import pipeline
from repro.core import qr as Q
from repro.core.lookahead import deepen, get_variant, parse_variant
from repro.kernels import ref

jax.config.update("jax_enable_x64", True)

N, B = 76, 24                        # ragged: 76 % 24 != 0
SCHEDULE = (32, 24, 12, 8)           # non-uniform, sums to 76


def _rand(n, seed, dtype=np.float64):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((n, n))
                       .astype(dtype))


def _spd(n, seed, dtype=np.float64):
    a = np.random.default_rng(seed).standard_normal((n, n)).astype(dtype)
    return jnp.asarray(a @ a.T + n * np.eye(n, dtype=dtype))


def _assert_tree_equal(ref_out, out):
    for r, o in zip(jax.tree_util.tree_leaves(ref_out),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o))


# (generator, legacy driver, engine driver) per (dmf, variant)
CASES = {
    ("lu", "mtb"): (_rand, legacy.lu_blocked, L.lu_blocked),
    ("lu", "rtm"): (_rand, legacy.lu_tiled, L.lu_tiled),
    ("lu", "la"): (_rand, legacy.lu_lookahead, L.lu_lookahead),
    ("cholesky", "mtb"): (_spd, legacy.cholesky_blocked, C.cholesky_blocked),
    ("cholesky", "rtm"): (_spd, legacy.cholesky_tiled, C.cholesky_tiled),
    ("cholesky", "la"): (_spd, legacy.cholesky_lookahead, C.cholesky_lookahead),
    ("qr", "mtb"): (_rand, legacy.qr_blocked, Q.qr_blocked),
    ("qr", "rtm"): (_rand, legacy.qr_tiled, Q.qr_tiled),
    ("qr", "la"): (_rand, legacy.qr_lookahead, Q.qr_lookahead),
    ("ldlt", "mtb"): (_spd, legacy.ldlt_blocked, D.ldlt_blocked),
    ("ldlt", "la"): (_spd, legacy.ldlt_lookahead, D.ldlt_lookahead),
    ("gauss_jordan", "mtb"): (_spd, legacy.gj_inverse_blocked,
                              G.gj_inverse_blocked),
    ("gauss_jordan", "la"): (_spd, legacy.gj_inverse_lookahead,
                             G.gj_inverse_lookahead),
}


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("dmf,variant", sorted(CASES))
def test_engine_bitwise_equals_legacy_ragged(dmf, variant, dtype):
    gen, legacy_fn, engine_fn = CASES[(dmf, variant)]
    a = gen(N, seed=5, dtype=dtype)
    _assert_tree_equal(legacy_fn(a, B), engine_fn(a, B))


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("dmf,variant", sorted(CASES))
def test_engine_bitwise_equals_legacy_nonuniform_schedule(dmf, variant, dtype):
    gen, legacy_fn, engine_fn = CASES[(dmf, variant)]
    a = gen(N, seed=9, dtype=dtype)
    _assert_tree_equal(legacy_fn(a, SCHEDULE), engine_fn(a, SCHEDULE))


def test_engine_bitwise_equals_legacy_tall_qr():
    # m > n exercises the QR row-exhaustion guards (stop/can_factor hooks)
    a = jnp.asarray(np.random.default_rng(3).standard_normal((96, 48)))
    for legacy_fn, engine_fn in [(legacy.qr_blocked, Q.qr_blocked),
                                 (legacy.qr_tiled, Q.qr_tiled),
                                 (legacy.qr_lookahead, Q.qr_lookahead)]:
        _assert_tree_equal(legacy_fn(a, 16), engine_fn(a, 16))


def test_wide_qr_lookahead_matches_blocked():
    # m < n is the one place the engine intentionally *diverges* from the
    # legacy loop: legacy qr_lookahead never applied the trailing update to
    # the first unfactorable panel's columns (stale R rows on wide inputs).
    # The engine folds them into TU_right, so every variant agrees again.
    a = jnp.asarray(np.random.default_rng(7).standard_normal((32, 64)))
    ref = Q.qr_blocked(a, 16)
    for out in (Q.qr_tiled(a, 16), Q.qr_lookahead(a, 16),
                Q.qr_lookahead(a, 16, depth=2)):
        for r, o in zip(jax.tree_util.tree_leaves(ref),
                        jax.tree_util.tree_leaves(out)):
            np.testing.assert_allclose(np.asarray(r), np.asarray(o),
                                       rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("dmf,fused", [
    ("lu", ref.fused_lu_panel_update),
    ("cholesky", ref.fused_cholesky_panel_update),
])
def test_engine_bitwise_equals_legacy_fused_pu(dmf, fused):
    # LA_MB dataflow against the legacy fused branch (jnp oracle kernels —
    # the Pallas kernels themselves are validated in test_kernels.py)
    gen, legacy_fn, engine_fn = CASES[(dmf, "la")]
    a = gen(64, seed=11, dtype=np.float32)
    _assert_tree_equal(legacy_fn(a, 16, fused_pu=fused),
                       engine_fn(a, 16, fused_pu=fused))


def test_engine_bitwise_equals_legacy_pallas_backend(pallas_n):
    # one capped pallas-interpret sweep: same backend on both sides
    from repro.kernels.ops import PALLAS_BACKEND

    a = _rand(pallas_n, seed=13, dtype=np.float32)
    _assert_tree_equal(
        legacy.lu_lookahead(a, 8, backend=PALLAS_BACKEND),
        L.lu_lookahead(a, 8, backend=PALLAS_BACKEND))


# ---------------------------------------------------------------------------
# Depth-d look-ahead.
# ---------------------------------------------------------------------------
DEPTH_DRIVERS = {
    "lu": (_rand, L.lu_lookahead),
    "cholesky": (_spd, C.cholesky_lookahead),
    "qr": (_rand, Q.qr_lookahead),
    "ldlt": (_spd, D.ldlt_lookahead),
    "gauss_jordan": (_spd, G.gj_inverse_lookahead),
}


@pytest.mark.parametrize("depth", [2, 3])
@pytest.mark.parametrize("dmf", sorted(DEPTH_DRIVERS))
def test_depth_d_matches_depth_1(dmf, depth):
    gen, fn = DEPTH_DRIVERS[dmf]
    a = gen(N, seed=21)
    r1 = fn(a, 16, depth=1)
    rd = fn(a, 16, depth=depth)
    for x, y in zip(jax.tree_util.tree_leaves(r1),
                    jax.tree_util.tree_leaves(rd)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-12, atol=1e-12)


def test_depth_clamps_beyond_panel_count():
    a = _rand(48, seed=2)
    _assert_tree_equal(L.lu_lookahead(a, 16, depth=1),
                       L.lu_lookahead(a, 16, depth=99))


def test_depth_composes_with_fused_pu():
    a = _rand(64, seed=4, dtype=np.float32)
    r1 = L.lu_lookahead(a, 16, fused_pu=ref.fused_lu_panel_update, depth=1)
    r2 = L.lu_lookahead(a, 16, fused_pu=ref.fused_lu_panel_update, depth=2)
    np.testing.assert_allclose(np.asarray(r1[0]), np.asarray(r2[0]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(r1[1]), np.asarray(r2[1]))


# ---------------------------------------------------------------------------
# Depth through the stack: registry names and solve drivers.
# ---------------------------------------------------------------------------
def test_parse_and_deepen_roundtrip():
    assert parse_variant("la") == ("la", 1)
    assert parse_variant("la3") == ("la", 3)
    assert parse_variant("la_mb2") == ("la_mb", 2)
    assert parse_variant("mtb") == ("mtb", 1)
    assert deepen("la", 2) == "la2"
    assert deepen("la_mb", 4) == "la_mb4"
    assert deepen("la", 1) == "la"
    with pytest.raises(ValueError):
        deepen("mtb", 2)
    with pytest.raises(ValueError):
        deepen("la2", 3)


def test_get_variant_resolves_depth_names():
    a = _rand(48, seed=6)
    base = get_variant("lu", "la")(a, 16)
    for name in ("la1", "la2", "la3"):
        out = get_variant("lu", name)(a, 16)
        np.testing.assert_allclose(np.asarray(base[0]), np.asarray(out[0]),
                                   rtol=1e-12, atol=1e-12)
        np.testing.assert_array_equal(np.asarray(base[1]), np.asarray(out[1]))
    # band reduction keeps its bespoke (two coupled panels) driver: no depth
    with pytest.raises(KeyError):
        get_variant("band_reduction", "la2")
    # an explicit depth= that contradicts the name would run a different
    # schedule than the label claims — rejected, matching deepen()
    with pytest.raises(ValueError):
        get_variant("lu", "la2")(a, 16, depth=3)
    out = get_variant("lu", "la2")(a, 16, depth=2)   # agreeing depth is fine
    np.testing.assert_array_equal(np.asarray(base[1]), np.asarray(out[1]))


def test_la2_round_trip_solves():
    from repro.solve import gels, gesv, posv

    n = 64
    rng = np.random.default_rng(17)
    b = jnp.asarray(rng.standard_normal((n, 4)))

    a = _rand(n, seed=30)
    x = gesv(a, b, 16, variant="la", depth=2)
    np.testing.assert_allclose(np.asarray(a @ x), np.asarray(b), atol=1e-8)

    s = _spd(n, seed=31)
    x = posv(s, b, 16, depth=2)
    np.testing.assert_allclose(np.asarray(s @ x), np.asarray(b), atol=1e-7)

    at = jnp.asarray(rng.standard_normal((96, n)))
    bt = jnp.asarray(rng.standard_normal((96, 4)))
    x = gels(at, bt, 16, depth=2)
    # least-squares optimality: residual orthogonal to range(A)
    r = np.asarray(at @ x - bt)
    np.testing.assert_allclose(np.asarray(at).T @ r, 0.0, atol=1e-8)


def test_engine_rejects_bad_requests():
    a = _rand(32, seed=1)
    with pytest.raises(ValueError):
        pipeline.factorize(L.LU_OPS, a, 16, variant="nope")
    with pytest.raises(ValueError):
        pipeline.factorize(L.LU_OPS, a, 16, variant="la", depth=0)
    with pytest.raises(ValueError):            # ldlt declares no rtm tiles
        pipeline.factorize(D.LDLT_OPS, a, 16, variant="rtm")


def test_make_variant_builds_standalone_drivers():
    # the registration path future StepOps DMFs use (ROADMAP: QRCP, Hessenberg)
    a = _rand(48, seed=8)
    drv = pipeline.make_variant(L.LU_OPS, "mtb")
    _assert_tree_equal(L.lu_blocked(a, 16), drv(a, 16))
    la = pipeline.make_variant(L.LU_OPS, "la")
    assert pipeline.supports_depth(la)
    _assert_tree_equal(L.lu_lookahead(a, 16, depth=2), la(a, 16, depth=2))


# ---------------------------------------------------------------------------
# Device-visible names (DESIGN.md §14): each hook runs under a
# ``repro.<CAT>`` scope and each backend kernel under ``repro.gemm`` /
# ``repro.trsm``.  Scopes are HLO metadata: the compiled factorization
# still equals the legacy loop bit for bit.
# ---------------------------------------------------------------------------
_SCOPED_PHASES = {"mtb": ("PF", "TU"), "rtm": ("PF", "TU"),
                  "la": ("PF", "PU", "TU")}


@pytest.mark.parametrize("dmf", ["lu", "cholesky"])
@pytest.mark.parametrize("variant", sorted(_SCOPED_PHASES))
def test_compiled_factor_carries_scopes_and_keeps_bits(dmf, variant):
    from repro.solve import drivers

    gen, legacy_fn, _ = CASES[(dmf, variant)]
    a = gen(64, seed=9, dtype=np.float32)
    if dmf == "lu":
        def fn(x):
            f = drivers.lu_factor(x, 16, variant=variant)
            return f.lu, f.ipiv
        phases = _SCOPED_PHASES[variant] + ("SWAP",)
    else:
        def fn(x):
            return drivers.cholesky_factor(x, 16, variant=variant).l
        phases = _SCOPED_PHASES[variant]
    compiled = jax.jit(fn).lower(a).compile()
    text = compiled.as_text()
    for scope in [f"repro.{cat}" for cat in phases] + ["repro.gemm",
                                                       "repro.trsm"]:
        assert f"/{scope}/" in text, scope
    if dmf == "cholesky":
        assert "/repro.PF/repro.trsm/" in text      # the panel's TRSM
    _assert_tree_equal(jax.jit(lambda x: legacy_fn(x, 16))(a), compiled(a))
