"""The generic static look-ahead engine — one loop, nine DMFs, depth-d.

The paper's central claim (§4–§5) is that static look-ahead is *algorithm
independent*: the MTB / RTM / LA schedules are properties of the panel
traversal, not of the factorization.  Pre-refactor, every DMF module in
``repro/core`` re-implemented the same panel/trailing-update choreography by
hand.  This module factors the choreography out:

* a DMF declares its algorithm once as a :class:`StepOps` record — how to
  **factor** a panel, **apply** the panel's row interchanges (pivoted DMFs),
  and **update** a range of trailing columns with the panel's transform;
* the engine emits every scheduling variant from that declaration:

  - :func:`factorize(..., variant="mtb")` — one barrier-separated
    panel/update pair per iteration (paper Listing 3, fork–join BLAS);
  - ``variant="rtm"`` — the trailing update fragmented into per-tile tasks
    (paper Listing 4), via the optional :attr:`StepOps.tiles` hook;
  - ``variant="la", depth=d`` — static look-ahead with **d panels in
    flight** (paper Listing 5 for d=1; its §5 generalization for d≥2).

Depth-d dataflow.  At iteration k the trailing update ``TU_k`` splits into
``d`` narrow per-panel updates (columns of panels k+1 … k+d) plus the bulk
``TU_k^R``; ``PF(k+1)`` runs immediately after the first narrow update.
Each trailing column still receives every panel's update exactly once and in
panel order — column j gets panel k's transform via the narrow path when
``j ≤ k+d`` and via the bulk path otherwise — so the numerics are *identical*
to the blocked algorithm for every d (the property the paper highlights
against RTM incremental pivoting, §3.3).  What changes is the dependence
structure: ``PF(k+j)`` becomes data-independent of ``TU_k^R … TU_{k+j-1}^R``,
so up to d panel factorizations can hide under bulk updates — on TPU, XLA
sees d independent op chains instead of one (DESIGN.md §10).

Bit-compatibility contract: with ``depth=1`` the engine emits the *same op
sequence* (same slices, same order) as the removed hand-written loops, so
``la(d=1)`` is bit-for-bit the old ``*_lookahead``, and ``mtb``/``rtm``
reproduce the old ``*_blocked``/``*_tiled`` — ``tests/test_pipeline.py``
pins this against the verbatim legacy loops in ``tests/legacy_reference.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.backend import Backend, JNP_BACKEND
from repro.core.blocking import BlockSpec, PanelStep, panel_steps
from repro.obs import tracer as _obs

__all__ = ["StepOps", "factorize", "make_variant", "mark_depth_capable",
           "supports_depth"]

#: Engine state: ``(a, aux)`` — the matrix plus per-DMF side output
#: (``ipiv`` for LU, ``taus`` for QR, ``None`` otherwise).
State = Tuple[jnp.ndarray, Any]

# `ctx` values are per-DMF panel contexts (pivots, WY reflectors, the GJE
# M block, …) produced by `factor` and consumed by `swap`/`update`/`commit`.
_MISSING = object()


@dataclasses.dataclass(frozen=True)
class StepOps:
    """One DMF, declared as the operations of a single panel iteration.

    Required hooks (``st`` is the :class:`~repro.core.blocking.PanelStep`
    of the *panel being applied*, not of the columns being updated):

    * ``init(a) -> state`` — build ``(a, aux)``.
    * ``factor(state, st, backend, panel_fn) -> (state, ctx)`` — PF(k):
      factor panel ``st`` in place, record side output (pivots/taus) in
      ``aux``, return the panel context the updates need.  ``panel_fn``
      optionally replaces the DMF's default unblocked panel routine (the
      Pallas panel-kernel hook; per-DMF signature documented on the DMF's
      ``STEP_OPS``).
    * ``update(state, ctx, st, c0, c1, backend) -> state`` — apply panel
      ``st``'s transform to global columns ``[c0, c1)``, ``c0 >= st.k_next``.
    * ``finalize(state) -> result`` — packed output (``tril``, tuples …).

    Optional hooks (``None`` = not applicable to this DMF):

    * ``swap`` — row-interchange application to the columns *outside* the
      panel (LU's ``laswp``); called eagerly after ``factor`` under
      ``mtb``/``rtm`` and lazily at the next iteration under ``la`` —
      exactly the pivot deferral of paper Listing 5.
    * ``tiles`` — the RTM fragmentation of the full trailing update
      (per-column-panel, per-row-tile tasks).  A DMF without ``tiles`` has
      no ``rtm`` variant (matches the paper: RTM-QR would change the factor
      representation).
    * ``pu(state, ctx, st, st_next, backend, fused) -> (state, ctx_next)``
      — fused panel-update (``TU^L + PF`` in one VMEM-resident kernel, the
      LA_MB/malleable path).  Only consulted when the caller passes
      ``fused_pu=``; otherwise the engine composes ``update`` + ``factor``.
    * ``update_left`` — for algorithms whose per-iteration update touches
      columns *left* of the panel too (Gauss–Jordan inversion).
    * ``update_all(state, ctx, st, backend)`` — the whole iteration-k update
      (every column, left and right, plus the panel commit) as the mtb
      engine's **single bulk op**.  Only meaningful for two-sided-update
      algorithms (GJE): under mtb their update is one barrier-separated op,
      and XLA's matmul is not guaranteed bit-stable under column slicing —
      composing ``update_left`` + ``update`` + ``commit`` would change the
      emitted op at exactly the scheduling level mtb says has none.
    * ``commit(state, ctx, st, backend)`` — per-iteration epilogue writing
      the panel's final columns (GJE's ``I − M``).
    * ``stop(state, st) -> bool`` — abandon the traversal at ``st`` (QR on
      ``m < n`` inputs stops once the rows are exhausted).
    * ``can_factor(state, st) -> bool`` — whether panel ``st`` is
      factorable (same QR row-exhaustion rule, consulted by look-ahead
      before pre-factoring the next panel).
    * ``width(a) -> int`` — traversal width (``a.shape[1]`` for QR).
    * ``la_unsafe`` — a *reason string* declaring that this DMF's ``factor``
      reads trailing data beyond the panel columns (QRCP's global pivot
      norms, Hessenberg's ``A₀·v`` GEMVs), so pre-factoring ``PF(k+1)``
      ahead of ``TU_k^R`` would compute a **different factorization**, not
      a different schedule.  The engine refuses ``variant="la"`` for such a
      declaration and surfaces the reason (DESIGN.md §11).
    """

    name: str
    init: Callable[[jnp.ndarray], State]
    factor: Callable[..., Tuple[State, Any]]
    update: Callable[..., State]
    finalize: Callable[[State], Any]
    swap: Optional[Callable[..., State]] = None
    tiles: Optional[Callable[..., State]] = None
    pu: Optional[Callable[..., Tuple[State, Any]]] = None
    update_left: Optional[Callable[..., State]] = None
    update_all: Optional[Callable[..., State]] = None
    commit: Optional[Callable[..., State]] = None
    stop: Optional[Callable[[State, PanelStep], bool]] = None
    can_factor: Optional[Callable[[State, PanelStep], bool]] = None
    width: Callable[[jnp.ndarray], int] = lambda a: a.shape[0]
    la_unsafe: Optional[str] = None

    def _stop(self, state: State, st: PanelStep) -> bool:
        return self.stop is not None and self.stop(state, st)

    def _factorable(self, state: State, st: PanelStep) -> bool:
        return self.can_factor is None or self.can_factor(state, st)

    def _epilogue(self, state: State, ctx, st: PanelStep,
                  backend: Backend) -> State:
        if self.update_left is not None and st.k > 0:
            state = self.update_left(state, ctx, st, backend)
        if self.commit is not None:
            state = self.commit(state, ctx, st, backend)
        return state


def factorize(
    ops: StepOps,
    a: jnp.ndarray,
    b: BlockSpec = 128,
    *,
    variant: str = "la",
    depth: int = 1,
    backend: Backend = JNP_BACKEND,
    panel_fn: Optional[Callable] = None,
    fused_pu: Optional[Callable] = None,
    mesh=None,
    layout=None,
):
    """Run one scheduling variant of ``ops`` over ``a``.

    ``variant`` ∈ {``"mtb"``, ``"rtm"``, ``"la"``}; ``depth`` (``la`` only)
    is the number of panels kept in flight — ``depth=1`` is the paper's
    Listing 5, bit-identical to the pre-refactor ``*_lookahead`` drivers.

    ``mesh=`` (a ``jax.sharding.Mesh``) lowers the same schedule to a
    shard_map'd SPMD loop over 1-D column block-cyclic shards —
    :func:`repro.core.distributed.factorize_mesh` — bitwise identical to
    the single-device engine at the same schedule, pivots included
    (DESIGN.md §17).  ``layout=`` (a ``distributed.Layout``) selects the
    mesh axis; by default the active ``parallel.sharding`` Rules table's
    ``"panels"`` entry decides.

    When the caller passes no ``panel_fn``, the backend's per-DMF panel
    registry (``Backend.panel_fns``, keyed by ``ops.name``) supplies the
    default — this is how ``backend="pallas"`` routes every variant through
    the VMEM-resident panel kernels.  Bitwise-invisible on the interpret
    backend: each Pallas panel traces the DMF's default op sequence (and
    falls back to it beyond the VMEM budget).  ``fused_pu`` stays an
    explicit opt-in (the ``la_mb`` variant resolves it from the backend's
    ``fused_pu`` registry) so plain ``la`` keeps the composed
    update+factor PU chain — the tuner arbitrates fused-vs-composed as the
    ``la``-vs-``la_mb`` axis.
    """
    if mesh is not None:
        from repro.core import distributed as _dist

        return _dist.factorize_mesh(ops, a, b, variant=variant, depth=depth,
                                    backend=backend, panel_fn=panel_fn,
                                    fused_pu=fused_pu, mesh=mesh,
                                    layout=layout)
    if layout is not None:
        raise ValueError("layout= is a mesh-path parameter; pass mesh= too")
    if panel_fn is None and backend.panel_fns is not None:
        panel_fn = backend.panel_fns.get(ops.name)
    if variant == "mtb":
        return _run_mtb(ops, a, b, backend, panel_fn)
    if variant == "rtm":
        if ops.tiles is None:
            raise ValueError(f"{ops.name!r} has no RTM (tiled) fragmentation")
        return _run_rtm(ops, a, b, backend, panel_fn)
    if variant == "la":
        if ops.la_unsafe is not None:
            raise ValueError(
                f"{ops.name!r} cannot be scheduled with look-ahead: "
                f"{ops.la_unsafe}")
        if depth < 1:
            raise ValueError(f"look-ahead depth must be >= 1, got {depth}")
        return _run_la(ops, a, b, depth, backend, panel_fn, fused_pu)
    raise ValueError(
        f"unknown scheduling variant {variant!r}; expected mtb/rtm/la")


# ---------------------------------------------------------------------------
# Observability (DESIGN.md §14): every hook invocation in the three loops
# below runs through :func:`_hook`, which opens the device-visible scope
# ``repro.<CAT>`` (``PF``, ``SWAP``, ``PU``, ``TU``, ``EPI``) around it.  A
# scope is HLO metadata only — it names the ops in the compiled program's
# ``op_name`` and so in the profiler's device trace, adds no op and changes
# no bit.  Only when a host tracer is installed (``repro.obs.tracer.
# trace()``) does the call also become a span; with none, the tracer is
# never touched.  Span tags: ``step`` = panel index k, ``it`` = the
# iteration that ran the work, ``depth`` = step − it, the in-flight
# distance that makes la(d) overlap visible in a timeline.
# ---------------------------------------------------------------------------
def _hook(tr, cat, name, thunk, **tags):
    """Run one engine hook under ``repro.<cat>``; a span too when traced."""
    with jax.named_scope(f"repro.{cat}"):
        if tr is None:
            return thunk()
        return tr.wrap(cat, name, thunk, **tags)


def _epilogue(tr, ops, state, ctx, st, backend, i):
    """The per-iteration epilogue, scoped only when it does real work."""
    if ops.update_left is None and ops.commit is None:
        return state
    return _hook(tr, "EPI", f"EPI({i})",
                 lambda: ops._epilogue(state, ctx, st, backend),
                 step=i, it=i)


# ---------------------------------------------------------------------------
# MTB: PF(k) ; barrier ; TU(k) over the whole trailing matrix (Listing 3).
# ---------------------------------------------------------------------------
def _run_mtb(ops, a, b, backend, panel_fn):
    tr = _obs.active()
    n = ops.width(a)
    state = ops.init(a)
    for i, st in enumerate(panel_steps(n, b)):
        if ops._stop(state, st):
            break
        state, ctx = _hook(tr, "PF", f"PF({i})",
                           lambda: ops.factor(state, st, backend, panel_fn),
                           step=i, it=i)
        if ops.swap is not None:
            state = _hook(tr, "SWAP", f"SWAP({i})",
                          lambda: ops.swap(state, ctx, st, backend),
                          step=i, it=i)
        if ops.update_all is not None:
            state = _hook(tr, "TU", f"TU({i})",
                          lambda: ops.update_all(state, ctx, st, backend),
                          step=i, it=i, cols=(0, n))
            continue
        if st.k_next < n:
            state = _hook(
                tr, "TU", f"TU({i})",
                lambda: ops.update(state, ctx, st, st.k_next, n, backend),
                step=i, it=i, cols=(st.k_next, n))
        state = _epilogue(tr, ops, state, ctx, st, backend, i)
    return ops.finalize(state)


# ---------------------------------------------------------------------------
# RTM: PF(k) ; TU(k) fragmented into per-tile tasks (Listing 4).
# ---------------------------------------------------------------------------
def _run_rtm(ops, a, b, backend, panel_fn):
    tr = _obs.active()
    n = ops.width(a)
    state = ops.init(a)
    for i, st in enumerate(panel_steps(n, b)):
        if ops._stop(state, st):
            break
        state, ctx = _hook(tr, "PF", f"PF({i})",
                           lambda: ops.factor(state, st, backend, panel_fn),
                           step=i, it=i)
        if ops.swap is not None:
            state = _hook(tr, "SWAP", f"SWAP({i})",
                          lambda: ops.swap(state, ctx, st, backend),
                          step=i, it=i)
        if st.k_next < n:
            state = _hook(tr, "TU", f"TU({i})",
                          lambda: ops.tiles(state, ctx, st, backend),
                          step=i, it=i, tiles=True, cols=(st.k_next, n))
        state = _epilogue(tr, ops, state, ctx, st, backend, i)
    return ops.finalize(state)


# ---------------------------------------------------------------------------
# LA(depth=d): PF(k+1) hides under TU_k^R; d panels in flight (Listing 5).
# ---------------------------------------------------------------------------
def _run_la(ops, a, b, depth, backend, panel_fn, fused_pu):
    tr = _obs.active()
    n = ops.width(a)
    state = ops.init(a)
    steps = list(panel_steps(n, b))

    # PF(0) runs before the pipelined loop (Listing 5 prologue).  Span tag
    # it=-1: it runs ahead of every iteration (nothing to hide under yet).
    ctx = None
    if ops._factorable(state, steps[0]):
        state, ctx = _hook(
            tr, "PF", "PF(0)",
            lambda: ops.factor(state, steps[0], backend, panel_fn),
            step=0, it=-1, depth=1)

    for i, st in enumerate(steps):
        # Panel-i interchanges, deferred from the iteration that factored it
        # (i−1): applied to every column outside panel i before any
        # iteration-i update touches them.
        if ops.swap is not None:
            state = _hook(tr, "SWAP", f"SWAP({i})",
                          lambda: ops.swap(state, ctx, st, backend),
                          step=i, it=i)
        if ops._stop(state, st):
            break
        if st.k_next >= n:
            state = _epilogue(tr, ops, state, ctx, st, backend, i)
            break

        # PU chain: narrow updates of the next `dd` panels' columns; PF(i+1)
        # fires right after the first one (optionally fused: LA_MB).
        dd = min(depth, len(steps) - 1 - i)
        if dd >= 1 and not ops._factorable(state, steps[i + 1]):
            # Next panel starts beyond the factorable range (QR row
            # exhaustion on m < n inputs): nothing to pre-factor, so there
            # is no look-ahead split — the whole trailing range is TU_right,
            # as under mtb.  (The legacy qr_lookahead skipped these columns'
            # update entirely, leaving stale R rows on wide inputs; the
            # engine restores identical-output-across-variants semantics.)
            dd = 0
        nctx = _MISSING
        for j in range(1, dd + 1):
            stj = steps[i + j]
            if j == 1 and fused_pu is not None and ops.pu is not None:
                # one fused VMEM kernel does TU^L + PF — a single scope and
                # span; its PF share is not separable, so overlap
                # accounting treats it as chain (PU) time.
                state, nctx = _hook(
                    tr, "PU", f"PU+PF({i}->{i + 1})",
                    lambda: ops.pu(state, ctx, st, stj, backend, fused_pu),
                    step=i, it=i, depth=1, fused=True,
                    cols=(stj.k, stj.k_next))
                continue
            state = _hook(
                tr, "PU", f"PU({i}->{i + j})",
                lambda: ops.update(state, ctx, st, stj.k, stj.k_next,
                                   backend),
                step=i, it=i, depth=j, cols=(stj.k, stj.k_next))
            if j == 1:
                state, nctx = _hook(
                    tr, "PF", f"PF({i + j})",
                    lambda: ops.factor(state, stj, backend, panel_fn),
                    step=i + j, it=i, depth=j)

        # TU_right(i): the bulk update — data-independent of the PU chain.
        r0 = steps[i + dd].k_next if dd >= 1 else st.k_next
        if r0 < n:
            state = _hook(tr, "TU", f"TU({i})",
                          lambda: ops.update(state, ctx, st, r0, n, backend),
                          step=i, it=i, cols=(r0, n), inflight=dd)

        state = _epilogue(tr, ops, state, ctx, st, backend, i)
        if nctx is not _MISSING:
            ctx = nctx
    return ops.finalize(state)


# ---------------------------------------------------------------------------
# Driver construction helpers (the DMF modules' public wrappers use these).
# ---------------------------------------------------------------------------
def mark_depth_capable(fn: Callable) -> Callable:
    """Tag a driver as accepting ``depth=`` (pipeline-backed look-ahead).

    The variant registry resolves ``"la2"``/``"la3"`` only for tagged
    drivers — ``band_reduction_lookahead`` keeps its bespoke loop and stays
    depth-1 (DESIGN.md §10).
    """
    fn.supports_depth = True
    return fn


def supports_depth(fn: Callable) -> bool:
    return getattr(fn, "supports_depth", False)


def make_variant(ops: StepOps, variant: str, **fixed) -> Callable:
    """A standalone ``(a, b=128, **kw)`` driver for one scheduling variant.

    Convenience for registering *new* StepOps-based DMFs (QR with column
    pivoting, blocked Hessenberg) without writing wrapper boilerplate.
    Refuses to build an ``"la"`` driver for a declaration that marked
    itself ``la_unsafe`` — the call would only ever raise.
    """
    if variant == "la" and ops.la_unsafe is not None:
        raise ValueError(
            f"cannot build an 'la' driver for {ops.name!r}: {ops.la_unsafe}")

    def driver(a, b: BlockSpec = 128, **kw):
        return factorize(ops, a, b, variant=variant, **{**fixed, **kw})

    driver.__name__ = f"{ops.name}_{variant}"
    driver.__qualname__ = driver.__name__
    driver.__doc__ = f"{variant!r} scheduling of the {ops.name!r} StepOps."
    if variant == "la":
        mark_depth_capable(driver)
    return driver
