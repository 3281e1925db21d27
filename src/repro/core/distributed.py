"""Distributed DMFs over a device mesh — the engine's ``mesh=`` axis.

This is the paper's §4 insight applied at device scale (DESIGN.md §2/§5/§17):
the panel factorization is the *serial* resource; on an ``nd``-way mesh the
per-shard trailing update shrinks ``nd``× while the panel (and now its
broadcast) does not, so hiding PF **and** the collective behind the bulk
update is worth far more than on the paper's 8 cores.

Layout: 1-D **column block-cyclic** over one mesh axis (ScaLAPACK style).
Column block ``j`` (width b) lives on device ``j % nd``, local slot
``j // nd``.  Every device owns *full columns*, so LU partial pivoting stays
local to the panel and the pivot sequence is **identical to single-device
GETRF** — the numerics-preserving property the paper contrasts with RTM
incremental pivoting (§3.3).  2-D block-cyclic layout helpers exist for the
layout layer (:func:`to_block_cyclic_2d`); the engine keeps the 1-D column
cycle precisely because full-column ownership is what keeps pivoting local.

Engine integration.  :func:`factorize_mesh` lowers the *same*
:class:`~repro.core.pipeline.StepOps` schedules (``mtb`` and depth-d ``la``)
that the single-device engine emits, via the per-DMF :class:`DistOps`
declarations in :data:`DIST_REGISTRY` — resolved by ``ops.name`` exactly like
``Backend.panel_fns``.  Each engine hook becomes one jitted ``shard_map``
step over the block-cyclic shards:

* **BCAST** — the updated, unfactored panel block is broadcast with
  ``lax.all_gather(...)[owner]``; a pure layout move (no arithmetic), so the
  replicated copy is bit-faithful (a masked ``psum`` would rewrite ``-0.0``).
* **PF** — the panel is factored *replicated* on every device by the exact
  single-device panel routine (``lu_unblocked`` / ``cholesky_panel`` /
  the hooked QR panel), trading a tiny redundant O(m·b²) computation for a
  second collective.
* **SWAP / PU / TU** — per-local-block applications of the single-device
  ``backend.trsm`` / ``backend.update`` / ``apply_qt_blocked`` ops.  The
  shape-canonical backend GEMM/TRSM are bitwise **column-decomposable**
  (``gemm(A, B)[:, j0:j1] == gemm(A, B[:, j0:j1])`` — pinned by
  ``tests/test_distributed.py``), so the local per-block updates reproduce
  the wide single-device trailing update bit-for-bit.

Together these make every mesh variant **bitwise identical** to the
single-device engine at the same schedule — pivots included.

Look-ahead at depth d issues the ``BCAST(k+1)`` + replicated ``PF(k+1)``
*before* the bulk ``TU_k^R`` dispatch — the collective and the redundant
panel work are data-independent of the bulk local GEMMs, the distributed
analogue of the paper's two parallel sections.  ``repro.obs`` spans tag the
broadcast with its owner shard and payload bytes, and
``report.overlap`` folds them into a broadcast-hidden fraction (structural,
like overlap-efficiency: the CPU backend serializes, a real mesh overlaps).

The same code runs on a TPU mesh (``chip_smoke.py --chips 4`` drives
``gesv``/``posv`` over four v5e chips) and on virtual CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``, the tests).  The
mesh may have ``Explicit`` axes (``jax.make_mesh``'s default) or ``Auto``
ones.  Bitwise equality with the single-device engine is pinned on the CPU,
where the canonical GEMM computes N in fixed-width tiles
(``repro.core.backend._gemm_impl``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.backend import Backend, JNP_BACKEND
from repro.core.blocking import BlockSpec, PanelStep, normalize_block, panel_steps
from repro.core.cholesky import CHOLESKY_OPS, cholesky_panel
from repro.core.lu import LU_OPS, laswp, lu_unblocked
from repro.core.pipeline import _hook
from repro.core.qr import QR_OPS, _Panel, _hooked_factor_panel, apply_qt_blocked
from repro.obs import tracer as _obs

__all__ = [
    "Layout",
    "DistOps",
    "DIST_REGISTRY",
    "resolve_axis",
    "factorize_mesh",
    "to_block_cyclic",
    "from_block_cyclic",
    "to_block_cyclic_2d",
    "from_block_cyclic_2d",
    "lu_block_cyclic",
    "cholesky_block_cyclic",
    "qr_block_cyclic",
]


# ---------------------------------------------------------------------------
# Layout descriptor + mesh-axis resolution (parallel.sharding Rules hook).
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Layout:
    """Block-cyclic layout selector for the engine's ``mesh=`` path.

    ``axis`` names the mesh axis carrying the 1-D column cycle; ``None``
    defers to the active :class:`repro.parallel.sharding.Rules` table
    (logical axis ``"panels"``) and then to ``"model"``.  ``row_axis`` is
    reserved for a 2-D process grid — the layout helpers support it
    (:func:`to_block_cyclic_2d`), the engine deliberately does not
    (full-column ownership is what keeps LU pivoting local, DESIGN.md §17).
    """

    axis: Optional[str] = None
    row_axis: Optional[str] = None


def resolve_axis(mesh: Mesh, layout: Optional[Layout] = None) -> str:
    """The mesh axis carrying the column cycle (layout > Rules > "model")."""
    if layout is not None and layout.axis is not None:
        if layout.axis not in mesh.axis_names:
            raise ValueError(f"layout axis {layout.axis!r} is not a mesh "
                             f"axis (have {tuple(mesh.axis_names)})")
        return layout.axis
    try:
        from repro.parallel.sharding import active_rules

        rules = active_rules()
    except Exception:                         # parallel layer absent/broken
        rules = None
    if rules is not None:
        ax = rules.table.get("panels")
        if isinstance(ax, str) and ax in mesh.axis_names:
            return ax
    if "model" in mesh.axis_names:
        return "model"
    return mesh.axis_names[0]


# ---------------------------------------------------------------------------
# Layout conversion — ragged-capable 1-D column block-cyclic, plus the 2-D
# generalization for the layout layer.
# ---------------------------------------------------------------------------
def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _padded_len(n: int, nd: int, b: int) -> int:
    """Columns after zero-padding ``n`` up to whole per-device block rows."""
    return _ceil_div(_ceil_div(n, b), nd) * nd * b


def _replicated(x):
    """``x`` gathered onto every device of its mesh, if it is sharded there.

    The conversions below are pure reshapes and transposes; on a mesh with
    ``AxisType.Explicit`` axes (what ``jax.make_mesh`` builds by default)
    merging a sharded dimension needs an output sharding, so a sharded
    input is first made replicated.  Plain and traced arrays pass through.
    """
    sh = getattr(x, "sharding", None) if isinstance(x, jax.Array) \
        and not isinstance(x, jax.core.Tracer) else None
    if isinstance(sh, NamedSharding) and not sh.is_fully_replicated:
        return jax.device_put(x, NamedSharding(sh.mesh, P()))
    return x


def to_block_cyclic(a: jnp.ndarray, nd: int, b: int) -> jnp.ndarray:
    """(m, n) → (nd, m, L): device-major column block-cyclic layout.

    Column block ``j`` goes to device ``j % nd``, local slot ``j // nd``.
    Shapes with ``n`` not divisible by ``nd·b`` are zero-padded on the right
    up to whole per-device block rows (``L = ceil(ceil(n/b)/nd)·b``);
    :func:`from_block_cyclic` with ``n=`` recovers the original columns.
    """
    a = _replicated(a)
    m, n = a.shape
    lp = _padded_len(n, nd, b)
    if lp != n:
        a = jnp.pad(a, ((0, 0), (0, lp - n)))
    lb = lp // (nd * b)
    return (a.reshape(m, lb, nd, b).transpose(2, 0, 1, 3)
            .reshape(nd, m, lb * b))


def from_block_cyclic(a_cyc: jnp.ndarray, b: int,
                      n: Optional[int] = None) -> jnp.ndarray:
    """Inverse of :func:`to_block_cyclic`; ``n`` drops the ragged padding."""
    a_cyc = _replicated(a_cyc)
    nd, m, l = a_cyc.shape
    out = (a_cyc.reshape(nd, m, l // b, b).transpose(1, 2, 0, 3)
           .reshape(m, nd * l))
    return out if n is None else out[:, :n]


def to_block_cyclic_2d(a: jnp.ndarray, grid: Tuple[int, int], br: int,
                       bc: int) -> jnp.ndarray:
    """(m, n) → (pr, pc, mloc, nloc): 2-D block-cyclic over a process grid.

    Row block ``i`` lives on process row ``i % pr``, column block ``j`` on
    process column ``j % pc`` (ScaLAPACK's general layout).  Ragged shapes
    are zero-padded like the 1-D case.  Layout-layer only: the engine keeps
    the 1-D column cycle (module docstring).
    """
    a = _replicated(a)
    pr, pc = grid
    m, n = a.shape
    mp, np_ = _padded_len(m, pr, br), _padded_len(n, pc, bc)
    if (mp, np_) != (m, n):
        a = jnp.pad(a, ((0, mp - m), (0, np_ - n)))
    mlb, nlb = mp // (pr * br), np_ // (pc * bc)
    return (a.reshape(mlb, pr, br, nlb, pc, bc).transpose(1, 4, 0, 2, 3, 5)
            .reshape(pr, pc, mlb * br, nlb * bc))


def from_block_cyclic_2d(a_cyc: jnp.ndarray, br: int, bc: int,
                         shape: Optional[Tuple[int, int]] = None
                         ) -> jnp.ndarray:
    """Inverse of :func:`to_block_cyclic_2d`; ``shape`` drops the padding."""
    a_cyc = _replicated(a_cyc)
    pr, pc, mloc, nloc = a_cyc.shape
    out = (a_cyc.reshape(pr, pc, mloc // br, br, nloc // bc, bc)
           .transpose(2, 0, 3, 4, 1, 5).reshape(pr * mloc, pc * nloc))
    if shape is not None:
        out = out[: shape[0], : shape[1]]
    return out


# ---------------------------------------------------------------------------
# Jitted shard_map step factories — one XLA executable per (site, shape),
# cached so repeated factorizations (benches, sweeps) pay zero retracing.
# The replication (VMA) checker is off: it mis-handles the replicated values
# these steps produce, and numerics do not depend on it.
# Every step mirrors one single-device engine hook; ``g = lj·nd + me`` is
# the global block index of local slot ``lj`` on device ``me``.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _bcast_step(mesh: Mesh, axis: str, slot: int, owner: int, b: int):
    """Broadcast column block ``slot·nd + owner`` to every device.

    ``all_gather(...)[owner]`` with a static owner is a pure layout move —
    bit-faithful, unlike a masked ``psum`` (``-0.0 + 0.0 == +0.0``).
    """

    def local(al):
        blk = al[0][:, slot * b : (slot + 1) * b]
        return lax.all_gather(blk, axis)[owner]

    return jax.jit(jax.shard_map(local, mesh=mesh, check_vma=False,
                              in_specs=(P(axis, None, None),),
                              out_specs=P()))


@functools.lru_cache(maxsize=None)
def _store_step(mesh: Mesh, axis: str, slot: int, owner: int, b: int):
    """Owner writes the replicated factored panel block into its shard."""

    def local(al, blk_new):
        a = al[0]
        me = lax.axis_index(axis)
        cur = a[:, slot * b : (slot + 1) * b]
        new = jnp.where(me == owner, blk_new, cur)
        return a.at[:, slot * b : (slot + 1) * b].set(new)[None]

    return jax.jit(jax.shard_map(local, mesh=mesh, check_vma=False,
                              in_specs=(P(axis, None, None), P()),
                              out_specs=P(axis, None, None)))


@functools.lru_cache(maxsize=None)
def _swap_step(mesh: Mesh, axis: str, nd: int, b: int, kb: int, k: int):
    """Panel ``kb``'s row interchanges on every local block except the panel
    itself (its rows were pivoted inside PF) — the engine's ``swap`` hook.
    Row swaps are columnwise-independent exact copies, so the per-block
    application equals the wide ``laswp`` bit-for-bit."""

    def local(al, piv):
        a = al[0]
        me = lax.axis_index(axis)
        lb = a.shape[1] // b
        for lj in range(lb):
            g = lj * nd + me
            blk = a[:, lj * b : (lj + 1) * b]
            blk = lax.cond(g == kb, lambda c: c,
                           lambda c: laswp(c, piv, offset=k), blk)
            a = a.at[:, lj * b : (lj + 1) * b].set(blk)
        return a[None]

    return jax.jit(jax.shard_map(local, mesh=mesh, check_vma=False,
                              in_specs=(P(axis, None, None), P()),
                              out_specs=P(axis, None, None)))


def _block_pred(mode: str, g, t: int):
    """The trailing-block guard: ``gt`` = bulk TU, ``eq`` = narrow PU."""
    return (g == t) if mode == "eq" else (g > t)


@functools.lru_cache(maxsize=None)
def _lu_update_step(mesh: Mesh, axis: str, nd: int, b: int, k: int, bk: int,
                    mode: str, t: int, backend: Backend):
    """LU TU_k on guarded local blocks: TRSM on the block row, GEMM below —
    the exact per-column-block slices of ``lu._update``."""
    k_next = k + bk

    def local(al, panel):
        a = al[0]
        me = lax.axis_index(axis)
        l11 = panel[k : k + bk, :bk]
        l21 = panel[k_next:, :bk]
        lb = a.shape[1] // b

        def do(c):
            u12 = backend.trsm(l11, c[k : k + bk], side="left", lower=True,
                               unit_diagonal=True)
            upd = backend.update(c[k_next:], l21, u12)
            return c.at[k : k + bk].set(u12).at[k_next:].set(upd)

        for lj in range(lb):
            g = lj * nd + me
            blk = a[:, lj * b : (lj + 1) * b]
            blk = lax.cond(_block_pred(mode, g, t), do, lambda c: c, blk)
            a = a.at[:, lj * b : (lj + 1) * b].set(blk)
        return a[None]

    return jax.jit(jax.shard_map(local, mesh=mesh, check_vma=False,
                              in_specs=(P(axis, None, None), P()),
                              out_specs=P(axis, None, None)))


@functools.lru_cache(maxsize=None)
def _chol_update_step(mesh: Mesh, axis: str, nd: int, b: int, k: int,
                      bk: int, c0: int, mode: str, t: int, backend: Backend):
    """Cholesky TU_k on guarded local blocks, rows from the call site's
    ``c0`` (``k_next`` for narrow PU, ``r0`` for the bulk) — mirroring
    ``cholesky._update``'s row origin exactly.  ``panel_pad`` is the
    factored panel block zero-padded to ``nd·lb·b`` rows so the traced
    per-block ``L`` row slice never clamps."""

    def local(al, panel_pad, panel):
        a = al[0]
        m = a.shape[0]
        me = lax.axis_index(axis)
        lb = a.shape[1] // b
        lcol = panel[c0:m, :bk]                  # L[c0:, k:k+bk], replicated

        for lj in range(lb):
            g = lj * nd + me

            def do(c, g=g):
                lrow = lax.dynamic_slice_in_dim(panel_pad, g * b, b, 0)[:, :bk]
                return c.at[c0:].set(backend.update(c[c0:], lcol, lrow.T))

            blk = a[:, lj * b : (lj + 1) * b]
            blk = lax.cond(_block_pred(mode, g, t), do, lambda c: c, blk)
            a = a.at[:, lj * b : (lj + 1) * b].set(blk)
        return a[None]

    return jax.jit(jax.shard_map(local, mesh=mesh, check_vma=False,
                              in_specs=(P(axis, None, None), P(), P()),
                              out_specs=P(axis, None, None)))


@functools.lru_cache(maxsize=None)
def _qr_update_step(mesh: Mesh, axis: str, nd: int, b: int, k: int,
                    mode: str, t: int, backend: Backend):
    """QR TU_k: the compact-WY block reflector applied to guarded local
    blocks — per-column-block ``qr._update``."""

    def local(al, v, tmat):
        a = al[0]
        me = lax.axis_index(axis)
        lb = a.shape[1] // b
        pnl = _Panel(v, tmat)

        def do(c):
            return c.at[k:].set(apply_qt_blocked(pnl, c[k:], backend))

        for lj in range(lb):
            g = lj * nd + me
            blk = a[:, lj * b : (lj + 1) * b]
            blk = lax.cond(_block_pred(mode, g, t), do, lambda c: c, blk)
            a = a.at[:, lj * b : (lj + 1) * b].set(blk)
        return a[None]

    return jax.jit(jax.shard_map(local, mesh=mesh, check_vma=False,
                              in_specs=(P(axis, None, None), P(), P()),
                              out_specs=P(axis, None, None)))


# ---------------------------------------------------------------------------
# Replicated panel factorizations — the single-device PF routines run on the
# broadcast block, so the factored values (pivots included) are trivially
# identical to the single-device engine's.
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("k", "bk", "panel_fn"))
def _lu_pf(blk, ipiv, *, k, bk, panel_fn):
    packed, piv = (panel_fn or lu_unblocked)(blk[k:, :bk])
    blk = blk.at[k:, :bk].set(packed)
    ipiv = ipiv.at[k : k + bk].set(piv + k)
    return blk, ipiv, piv


@functools.partial(jax.jit, static_argnames=("k", "bk", "backend", "panel_fn"))
def _chol_pf(blk, *, k, bk, backend, panel_fn):
    fn = panel_fn or cholesky_panel
    return blk.at[k:, :bk].set(fn(blk[k:, :bk], bk, backend))


@functools.partial(jax.jit, static_argnames=("k", "bk", "panel_fn"))
def _qr_pf(blk, taus, *, k, bk, panel_fn):
    packed, tau, pnl = _hooked_factor_panel(blk[k:, :bk], panel_fn)
    blk = blk.at[k:, :bk].set(packed)
    taus = taus.at[k : k + bk].set(tau[:bk])     # m >= n: all bk reflectors
    return blk, taus, pnl.v, pnl.t


# ---------------------------------------------------------------------------
# Per-DMF distributed lowering declarations, resolved by ``ops.name`` like
# ``Backend.panel_fns``.
# ---------------------------------------------------------------------------
class _Geom(NamedTuple):
    """Static geometry of one mesh factorization."""

    mesh: Mesh
    axis: str
    nd: int
    b: int
    m: int
    n: int
    lb: int            # local column blocks per device (padding included)

    @property
    def bcast_bytes(self) -> int:
        """Payload a panel broadcast moves off the owner shard."""
        return (self.nd - 1) * self.m * self.b


@dataclasses.dataclass(frozen=True)
class DistOps:
    """One DMF's mesh lowering: replicated PF + per-block local update.

    * ``validate(a)`` — shape preconditions of the mesh path.
    * ``init_aux(a)`` — replicated side output (``ipiv``/``taus``/None).
    * ``pf(blk, aux, st, backend, panel_fn, geom)`` →
      ``(blk_new, aux, ctx, piv)`` — factor the broadcast block replicated;
      ``ctx`` is the tuple of replicated operands the update steps consume,
      ``piv`` the swap payload (LU) or None.
    * ``update(geom, st, mode, t, c0, backend)`` → jitted step
      ``(al, *ctx) -> al`` applying panel ``st`` to local blocks guarded by
      ``mode``/``t`` (``"eq"``: narrow PU of block t; ``"gt"``: bulk TU of
      blocks > t), rows from ``c0`` where the DMF's update is row-ranged.
    * ``finalize(a, aux)`` — same packing as the StepOps ``finalize``.
    """

    name: str
    validate: Callable[[jnp.ndarray], None]
    init_aux: Callable[[jnp.ndarray], Any]
    pf: Callable[..., Tuple[jnp.ndarray, Any, Tuple, Any]]
    update: Callable[..., Callable]
    finalize: Callable[[jnp.ndarray, Any], Any]


def _require_square(what: str):
    def check(a):
        if a.shape[0] != a.shape[1]:
            raise ValueError(f"mesh {what} requires a square matrix, "
                             f"got {a.shape}")
    return check


def _qr_validate(a):
    if a.shape[0] < a.shape[1]:
        raise ValueError(
            f"mesh QR requires m >= n (got {a.shape}): on wide inputs the "
            f"traversal stops mid-matrix (StepOps.stop), which the "
            f"block-cyclic loop does not model — use the single-device "
            f"engine")


def _lu_dist_pf(blk, aux, st, backend, panel_fn, geom):
    blk, ipiv, piv = _lu_pf(blk, aux, k=st.k, bk=st.bk, panel_fn=panel_fn)
    return blk, ipiv, (blk,), piv


def _lu_dist_update(geom, st, mode, t, c0, backend):
    return _lu_update_step(geom.mesh, geom.axis, geom.nd, geom.b,
                           st.k, st.bk, mode, t, backend)


def _chol_dist_pf(blk, aux, st, backend, panel_fn, geom):
    blk = _chol_pf(blk, k=st.k, bk=st.bk, backend=backend, panel_fn=panel_fn)
    pad = geom.nd * geom.lb * geom.b - geom.m
    panel_pad = jnp.pad(blk, ((0, pad), (0, 0))) if pad else blk
    return blk, aux, (panel_pad, blk), None


def _chol_dist_update(geom, st, mode, t, c0, backend):
    return _chol_update_step(geom.mesh, geom.axis, geom.nd, geom.b,
                             st.k, st.bk, c0, mode, t, backend)


def _qr_dist_pf(blk, aux, st, backend, panel_fn, geom):
    blk, taus, v, tmat = _qr_pf(blk, aux, k=st.k, bk=st.bk, panel_fn=panel_fn)
    return blk, taus, (v, tmat), None


def _qr_dist_update(geom, st, mode, t, c0, backend):
    return _qr_update_step(geom.mesh, geom.axis, geom.nd, geom.b,
                           st.k, mode, t, backend)


DIST_REGISTRY = {
    "lu": DistOps(
        name="lu",
        validate=_require_square("LU"),
        init_aux=lambda a: jnp.zeros((min(a.shape),), jnp.int32),
        pf=_lu_dist_pf,
        update=_lu_dist_update,
        finalize=lambda a, aux: (a, aux),
    ),
    "cholesky": DistOps(
        name="cholesky",
        validate=_require_square("Cholesky"),
        init_aux=lambda a: None,
        pf=_chol_dist_pf,
        update=_chol_dist_update,
        finalize=lambda a, aux: jnp.tril(a),
    ),
    "qr": DistOps(
        name="qr",
        validate=_qr_validate,
        init_aux=lambda a: jnp.zeros((min(a.shape),), a.dtype),
        pf=_qr_dist_pf,
        update=_qr_dist_update,
        finalize=lambda a, aux: (a, aux),
    ),
}


# ---------------------------------------------------------------------------
# The mesh engine: mtb / la(depth-d) orders emitted over shard_map steps.
# ---------------------------------------------------------------------------
def factorize_mesh(
    ops,
    a: jnp.ndarray,
    b: BlockSpec = 128,
    *,
    variant: str = "la",
    depth: int = 1,
    backend: Backend = JNP_BACKEND,
    panel_fn: Optional[Callable] = None,
    fused_pu: Optional[Callable] = None,
    mesh: Mesh = None,
    layout: Optional[Layout] = None,
):
    """Run one mesh-scheduled variant of ``ops`` over block-cyclic shards.

    The distributed twin of :func:`repro.core.pipeline.factorize` — called
    by it when ``mesh=`` is passed.  Emits the same ``mtb``/``la(depth-d)``
    hook sequences from the DMF's :data:`DIST_REGISTRY` declaration; results
    are bitwise identical to the single-device engine at the same schedule
    (module docstring).
    """
    dist = DIST_REGISTRY.get(ops.name)
    if dist is None:
        reason = (f": {ops.la_unsafe}" if getattr(ops, "la_unsafe", None)
                  else "")
        raise ValueError(
            f"{ops.name!r} has no mesh lowering (supported: "
            f"{', '.join(sorted(DIST_REGISTRY))}){reason}")
    if variant not in ("mtb", "la"):
        raise ValueError(
            f"mesh scheduling supports variants 'mtb' and 'la', "
            f"got {variant!r}")
    if variant == "la" and depth < 1:
        raise ValueError(f"look-ahead depth must be >= 1, got {depth}")
    if fused_pu is not None:
        raise ValueError("fused_pu (la_mb) has no mesh lowering — the fused "
                         "kernel is a single-device VMEM residency play")
    bi = normalize_block(b)
    if not isinstance(bi, int):
        # a uniform schedule (what the tuner emits for scalar-b winners) is
        # just its leading width; genuinely non-uniform schedules cannot
        # align with a fixed-width block-cyclic layout
        widths = tuple(st.bk for st in panel_steps(a.shape[1], bi[0]))
        if tuple(bi) == widths:
            bi = int(bi[0])
        else:
            raise ValueError(
                f"mesh scheduling requires a uniform block size (panel "
                f"blocks must align with the block-cyclic layout), got "
                f"schedule {bi}")
    dist.validate(a)
    if panel_fn is None and backend.panel_fns is not None:
        panel_fn = backend.panel_fns.get(ops.name)

    axis = resolve_axis(mesh, layout)
    nd = mesh.shape[axis]
    m, n = a.shape[0], a.shape[1]
    steps = list(panel_steps(n, bi))

    a_cyc = to_block_cyclic(a, nd, bi)
    al = jax.device_put(a_cyc, NamedSharding(mesh, P(axis, None, None)))
    aux = dist.init_aux(a)
    if aux is not None:
        aux = jax.device_put(aux, NamedSharding(mesh, P()))
    geom = _Geom(mesh=mesh, axis=axis, nd=nd, b=bi, m=m, n=n,
                 lb=a_cyc.shape[2] // bi)

    tr = _obs.active()
    if variant == "mtb":
        al, aux = _run_mesh_mtb(dist, steps, al, aux, geom, backend,
                                panel_fn, tr)
    else:
        al, aux = _run_mesh_la(dist, steps, al, aux, geom, backend,
                               panel_fn, depth, tr)
    return dist.finalize(from_block_cyclic(al, bi, n=n), aux)


def _bcast_meta(geom, a_like):
    return geom.bcast_bytes * jnp.dtype(a_like.dtype).itemsize


def _run_mesh_mtb(dist, steps, al, aux, geom, backend, panel_fn, tr):
    """BCAST(k) ; replicated PF(k) ; store ; SWAP ; bulk TU — Listing 3 on
    shards (span tags mirror ``pipeline._run_mtb``)."""
    mesh, axis, nd, b, n = geom.mesh, geom.axis, geom.nd, geom.b, geom.n
    nbytes = _bcast_meta(geom, al)
    for i, st in enumerate(steps):
        owner, slot = i % nd, i // nd
        bc = _bcast_step(mesh, axis, slot, owner, b)
        blk = _hook(tr, "BCAST", f"BCAST({i})", lambda: bc(al),
                   step=i, it=i, shard=owner, bytes=nbytes)
        blk, aux, ctx, piv = _hook(
            tr, "PF", f"PF({i})",
            lambda: dist.pf(blk, aux, st, backend, panel_fn, geom),
            step=i, it=i, shard=owner)
        al = _store_step(mesh, axis, slot, owner, b)(al, blk)
        if piv is not None:
            sw = _swap_step(mesh, axis, nd, b, i, st.k)
            al = _hook(tr, "SWAP", f"SWAP({i})", lambda: sw(al, piv),
                      step=i, it=i)
        if st.k_next < n:
            upd = dist.update(geom, st, "gt", i, st.k_next, backend)
            al = _hook(tr, "TU", f"TU({i})", lambda: upd(al, *ctx),
                      step=i, it=i, cols=(st.k_next, n))
    return al, aux


def _run_mesh_la(dist, steps, al, aux, geom, backend, panel_fn, depth, tr):
    """Depth-d look-ahead on shards (span tags mirror ``pipeline._run_la``).

    Iteration i: deferred SWAP(i) → narrow PU(i→i+1) → **BCAST(i+1) +
    replicated PF(i+1)** (both data-independent of the bulk) → narrow
    PU(i→i+j), j ≥ 2 → bulk TU_right(i).  The broadcast and the redundant
    panel are issued before the bulk local GEMMs that hide them — the
    mesh-level two-parallel-sections of the paper's Listing 5.
    """
    mesh, axis, nd, b, n = geom.mesh, geom.axis, geom.nd, geom.b, geom.n
    nbytes = _bcast_meta(geom, al)
    nsteps = len(steps)

    # Prologue: broadcast + factor panel 0 ahead of the loop (it=-1).
    bc0 = _bcast_step(mesh, axis, 0, 0, b)
    blk = _hook(tr, "BCAST", "BCAST(0)", lambda: bc0(al),
               step=0, it=-1, depth=1, shard=0, bytes=nbytes)
    blk, aux, ctx, piv = _hook(
        tr, "PF", "PF(0)",
        lambda: dist.pf(blk, aux, steps[0], backend, panel_fn, geom),
        step=0, it=-1, depth=1, shard=0)
    al = _store_step(mesh, axis, 0, 0, b)(al, blk)

    for i, st in enumerate(steps):
        if piv is not None:
            sw = _swap_step(mesh, axis, nd, b, i, st.k)
            al = _hook(tr, "SWAP", f"SWAP({i})", lambda: sw(al, piv),
                      step=i, it=i)
        if st.k_next >= n:
            break
        dd = min(depth, nsteps - 1 - i)
        nctx = npiv = None
        for j in range(1, dd + 1):
            stj = steps[i + j]
            tb = i + j
            upd = dist.update(geom, st, "eq", tb, stj.k, backend)
            al = _hook(tr, "PU", f"PU({i}->{tb})",
                      lambda: upd(al, *ctx),
                      step=i, it=i, depth=j, cols=(stj.k, stj.k_next),
                      shard=tb % nd)
            if j == 1:
                owner, slot = tb % nd, tb // nd
                bc = _bcast_step(mesh, axis, slot, owner, b)
                blkj = _hook(tr, "BCAST", f"BCAST({tb})", lambda: bc(al),
                            step=tb, it=i, depth=1, shard=owner,
                            bytes=nbytes)
                blkj, aux, nctx, npiv = _hook(
                    tr, "PF", f"PF({tb})",
                    lambda: dist.pf(blkj, aux, stj, backend, panel_fn, geom),
                    step=tb, it=i, depth=1, shard=owner)
                al = _store_step(mesh, axis, slot, owner, b)(al, blkj)
        r0 = steps[i + dd].k_next if dd >= 1 else st.k_next
        if r0 < n:
            upd = dist.update(geom, st, "gt", i + dd, r0, backend)
            al = _hook(tr, "TU", f"TU({i})", lambda: upd(al, *ctx),
                      step=i, it=i, cols=(r0, n), inflight=dd)
        if nctx is not None:
            ctx, piv = nctx, npiv
    return al, aux


# ---------------------------------------------------------------------------
# Back-compat wrappers — the pre-engine standalone drivers, now emitted by
# the engine (and therefore bitwise vs the single-device variants, a
# strictly stronger contract than the old bespoke loops').
# ---------------------------------------------------------------------------
def lu_block_cyclic(a: jnp.ndarray, b: int, mesh: Mesh, *,
                    axis: str = "model", lookahead: bool = True):
    """Distributed LUpp.  Returns (packed LU (n, n), ipiv (n,))."""
    return factorize_mesh(LU_OPS, a, b,
                          variant="la" if lookahead else "mtb",
                          mesh=mesh, layout=Layout(axis=axis))


def cholesky_block_cyclic(a: jnp.ndarray, b: int, mesh: Mesh, *,
                          axis: str = "model", lookahead: bool = True):
    """Distributed Cholesky (lower).  Returns L (n, n)."""
    return factorize_mesh(CHOLESKY_OPS, a, b,
                          variant="la" if lookahead else "mtb",
                          mesh=mesh, layout=Layout(axis=axis))


def qr_block_cyclic(a: jnp.ndarray, b: int, mesh: Mesh, *,
                    axis: str = "model", lookahead: bool = True):
    """Distributed GEQRF.  Returns (packed (m, n), tau (n,))."""
    return factorize_mesh(QR_OPS, a, b,
                          variant="la" if lookahead else "mtb",
                          mesh=mesh, layout=Layout(axis=axis))
