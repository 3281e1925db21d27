"""LU semantics pinned against independent references (scipy, inversion).

The per-variant residual sweep that used to live here moved into the
cross-DMF conformance harness (``tests/conformance.py`` — every (variant,
backend, dtype) × shape class, ISSUE 4); what remains is the LU-specific
ground truth no generic harness can express.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.linalg as sla

import legacy_reference as legacy
from repro.core import lu as L
from repro.core.blocking import panel_steps
from repro.core.lookahead import get_variant

jax.config.update("jax_enable_x64", True)


def _rand(n, seed=0, dtype=np.float64):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((n, n))
                       .astype(dtype))


def test_lu_matches_scipy_exactly():
    a = _rand(96, seed=7)
    fac, piv = L.lu_blocked(a, 32)
    ref_fac, ref_piv = sla.lu_factor(np.asarray(a))
    np.testing.assert_allclose(np.asarray(fac), ref_fac, atol=1e-10)
    assert (np.asarray(piv) == ref_piv).all()


def test_all_variants_agree_bitwise_pivots():
    a = _rand(128, seed=3)
    ref_fac, ref_piv = L.lu_blocked(a, 32)
    for variant in ("rtm", "la"):
        fac, piv = get_variant("lu", variant)(a, 32)
        assert (piv == ref_piv).all(), variant
        np.testing.assert_allclose(np.asarray(fac), np.asarray(ref_fac),
                                   atol=1e-10, err_msg=variant)


def test_unblocked_panel_rectangular():
    m, nb = 80, 16
    panel = jnp.asarray(
        np.random.default_rng(0).standard_normal((m, nb)))
    packed, piv = L.lu_unblocked(panel)
    # reconstruct: P·panel = L·U with L (m × nb) unit-lower, U (nb × nb)
    l = jnp.tril(packed, -1)[:, :nb] + jnp.eye(m, nb)
    u = jnp.triu(packed[:nb])
    perm = L.permutation_from_pivots(piv, m)
    err = jnp.linalg.norm(panel[perm] - l @ u)
    assert err < 1e-10


def test_laswp_roundtrip():
    a = _rand(32, seed=1)
    piv = jnp.asarray([5, 3, 2, 3], jnp.int32)
    swapped = L.laswp(a, piv)
    # applying the same sequence twice in reverse restores the original
    def unswap(a, piv):
        for j in range(piv.shape[0] - 1, -1, -1):
            p = int(piv[j])
            idx = jnp.asarray([j, p])
            a = a.at[idx].set(a[jnp.asarray([p, j])])
        return a
    np.testing.assert_allclose(np.asarray(unswap(swapped, piv)),
                               np.asarray(a))


# (n rows, w columns, offset, pivots): offset > 0, bk == m, w == 1, and
# pivots with piv[j] == j, repeated targets, targets inside the first bk rows.
LASWP_CASES = {
    "random": (32, 8, 0, [5, 3, 2, 31, 4, 30]),
    "identity": (24, 6, 4, [0, 1, 2, 3]),
    "repeated_targets": (40, 16, 12, [9, 9, 9, 20, 9, 27]),
    "targets_in_block": (20, 5, 3, [3, 2, 4, 3, 4, 5]),
    "bk_is_m": (16, 1, 8, [5, 7, 2, 7, 6, 5, 7, 7]),
    "w1_offset": (33, 1, 7, [25, 1, 14, 3, 25]),
}


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("case", sorted(LASWP_CASES))
def test_laswp_bitwise_equals_sequential_loop(case, dtype, jit):
    n, w, offset, piv = LASWP_CASES[case]
    a = jnp.asarray(np.random.default_rng(n).standard_normal((n, w))
                    .astype(dtype))
    piv = jnp.asarray(piv, jnp.int32)
    new, old = L.laswp, legacy.laswp
    if jit:
        new, old = (jax.jit(f, static_argnums=2) for f in (new, old))
    np.testing.assert_array_equal(np.asarray(new(a, piv, offset)),
                                  np.asarray(old(a, piv, offset)))


def _panel_state(n, b, i, dtype=np.float64):
    """LU's state just after PF of panel ``i`` (of width ``b``), its
    context and step: the input of the ``swap`` hook."""
    a = jnp.asarray(np.random.default_rng(i).standard_normal((n, n))
                    .astype(dtype))
    st = list(panel_steps(n, b))[i]
    state, ctx = L._factor(L._init(a), st, None, None)
    return state, ctx, st


@pytest.mark.parametrize("n,b,i", [(40, 8, 2), (36, 16, 0), (36, 16, 2)])
def test_swap_hook_full_rows_keep_panel_columns(n, b, i):
    state, ctx, st = _panel_state(n, b, i)
    a, ipiv = state
    want = a
    if st.k > 0:
        want = want.at[:, :st.k].set(
            legacy.laswp(want[:, :st.k], ctx.piv, offset=st.k))
    if st.k_next < n:
        want = want.at[:, st.k_next:].set(
            legacy.laswp(want[:, st.k_next:], ctx.piv, offset=st.k))
    got, got_ipiv = L._swap(state, ctx, st, None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got[:, st.k:st.k_next]),
                                  np.asarray(a[:, st.k:st.k_next]))
    assert got_ipiv is ipiv


def _loop_carries(jaxpr):
    """The carried dtypes of every loop (``while``, and ``scan``, which a
    ``fori_loop`` with static bounds becomes) in ``jaxpr`` and its
    sub-jaxprs."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            found.append([v.aval.dtype for v in eqn.outvars])
        elif eqn.primitive.name == "scan":
            carried = eqn.outvars[:eqn.params["num_carry"]]
            found.append([v.aval.dtype for v in carried])
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _loop_carries(sub)
    return found


@pytest.mark.parametrize("hook", ["laswp", "swap"])
def test_no_pivot_loop_carries_the_matrix(hook):
    # A per-pivot loop over the matrix copies it once per pivot on the TPU;
    # the interchanges are composed on integers and moved in one scatter.
    if hook == "laswp":
        a = jnp.zeros((48, 40), jnp.float32)
        fn = lambda a, piv: L.laswp(a, piv, 8)
        args = (a, jnp.arange(8, dtype=jnp.int32) + 8)
    else:
        state, ctx, st = _panel_state(40, 8, 2)
        fn = lambda state, ctx: L._swap(state, ctx, st, None)
        args = (state, ctx)
    carries = _loop_carries(jax.make_jaxpr(fn)(*args).jaxpr)
    assert len(carries) == 1, carries
    assert not any(jnp.issubdtype(d, jnp.floating) for d in carries[0])
    assert jnp.dtype(jnp.int32) in carries[0]
