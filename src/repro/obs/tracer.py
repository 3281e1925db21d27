"""Execution-trace span recorder for the look-ahead engine (DESIGN.md §14).

The paper's central evidence is *execution traces*: thread timelines showing
the panel factorization PF(k+1) hidden under the bulk trailing update
TU_k^R once static look-ahead is embedded (§4–§6).  This module records the
same evidence from our engine: every hook invocation of
:mod:`repro.core.pipeline` (and the driver / sweep / serve layers above it)
becomes a :class:`Span` tagged with its category (``PF``/``TU``/``PU``/…),
panel index, owning iteration, and **in-flight depth** — how many
iterations ahead of its owning iteration a panel was pre-factored, the
quantity that makes ``la(d)`` overlap visible in the profiler's timeline.

Design constraints (the contract the tests pin):

* **Zero dependencies.**  Pure stdlib; ``jax`` is imported lazily, when a
  span needs to fence device work or opens its profiler annotation.
* **On the profiler's clock.**  Each span is also a
  ``jax.profiler.TraceAnnotation("repro.<cat>")`` carrying its name, step
  and depth, so under ``jax.profiler.trace`` the eager engine's, the
  mesh's and the server's spans land beside the device ops.
* **Disabled is free and bitwise-invisible.**  No tracer installed ⇒ every
  instrumented site runs its original code path guarded by a single
  ``tracer.active() is None`` predicate — same ops, same order, bitwise
  identical outputs (``tests/test_obs.py`` pins this over dmf × variant).
* **Spans observe, never reorder.**  Enabling tracing adds only timestamps
  and (optionally) ``jax.block_until_ready`` fences around the *already
  emitted* op sequence; the numerics are unchanged — fencing synchronizes,
  it does not compute.
* **Injectable clock** so span math is unit-testable deterministically.

Fencing.  With ``fence=True`` (default) each span calls
``jax.block_until_ready`` on the instrumented call's result before taking
the end timestamp, so the span measures *device* work, not dispatch.  This
serializes XLA's async dispatch — exactly what you want for per-op
attainment accounting (model-vs-measured, :mod:`repro.obs.report`), and on
the single-threaded CPU/interpret backends it is how the ops run anyway.
With ``fence=False`` spans measure dispatch only; pair it with one final
``block_until_ready`` to compare wall clock against the span sum on
devices with real async overlap.

Tracing under ``jax.jit`` is meaningless by construction (hook calls fire
once at trace time and measure tracing, not execution); install the tracer
around **eager** driver calls — the backend-level jit entry points
(``repro.core.backend``) keep eager runs one-cached-executable-per-shape
fast.  An accidentally traced jit still produces correct *results*, and
instead of silently fabricating wall times the recorder now **detects**
abstract (tracer) values at the fence point: the span is tagged
``meta["traced"] = True`` (so reports can drop it) and a one-time
``RuntimeWarning`` points at the eager entry points
(``tests/test_obs.py`` pins both).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from typing import Any, Callable, Dict, List, Optional

__all__ = ["Span", "Tracer", "trace", "active"]

#: Span categories emitted by the instrumented layers.  Engine categories
#: mirror the paper's task names (``TILE`` = one tile-DAG task,
#: DESIGN.md §16); the outer layers add their own lanes.
CATEGORIES = ("PF", "TU", "PU", "SWAP", "EPI", "BCAST", "TILE", "panel",
              "drive", "sweep", "serve")

#: The currently installed tracer (None = tracing disabled, the default).
#: Instrumented sites read this through :func:`active` — one predicate
#: check is the entire disabled-path cost.
_ACTIVE: Optional["Tracer"] = None


def active() -> Optional["Tracer"]:
    """The installed tracer, or None when tracing is disabled."""
    return _ACTIVE


@dataclasses.dataclass
class Span:
    """One timed interval of the instrumented execution.

    ``step`` is the panel index the work belongs to (the ``k`` in PF(k)),
    ``it`` the outer iteration that *ran* it, and ``depth`` the in-flight
    distance ``step - it`` for look-ahead pre-factorizations (0 for work
    owned by its own iteration; the prologue PF(0) carries ``it=-1``,
    ``depth=1`` — it runs ahead of the whole loop).
    """

    cat: str
    name: str
    t0: float
    t1: float
    step: int = -1
    it: int = -1
    depth: int = 0
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


#: One-time latch for the trace-under-jit warning (per process; reset via
#: :func:`_reset_traced_warning` in tests).
_TRACED_WARNED = False


def _reset_traced_warning() -> None:
    global _TRACED_WARNED
    _TRACED_WARNED = False


def _is_abstract(value: Any) -> bool:
    """True when ``value`` contains abstract (jit-trace-time) leaves."""
    try:
        import jax

        return any(isinstance(leaf, jax.core.Tracer)
                   for leaf in jax.tree_util.tree_leaves(value))
    except Exception:
        return False


def _note_traced(name: str) -> None:
    """One-time warning that spans are being recorded at jit-trace time."""
    global _TRACED_WARNED
    if not _TRACED_WARNED:
        _TRACED_WARNED = True
        warnings.warn(
            f"repro.obs: span {name!r} recorded under jit tracing — its "
            f"times measure tracing, not execution (span tagged "
            f"traced=True).  Install the tracer around eager driver calls; "
            f"the jit entry points in repro.core.backend keep eager runs "
            f"fast.",
            RuntimeWarning, stacklevel=4)


def _fence(value: Any) -> None:
    """Block until ``value``'s arrays are computed; silently a no-op for
    non-array pytrees.

    Sharded-safe: ``jax.block_until_ready`` waits on *every* shard of a
    multi-device array (it fences the underlying per-device buffers), so
    the distributed engine (:mod:`repro.core.distributed`) can span its
    shard_map steps with the same wrapper — a BCAST/TU span's end stamp
    bounds the slowest participating device, not just the addressable
    shard.  The try/except keeps non-jax values (ints, pivot tuples,
    host-side aux) free."""
    try:
        import jax

        jax.block_until_ready(value)
    except Exception:
        pass


def _annotation(cat: str, name: str, step: int, depth: int):
    """``jax.profiler.TraceAnnotation("repro.<cat>")`` for one span, with
    its name, step and depth as metadata: while a profiler runs, the span
    lands in its trace on the same clock as the device ops.  Outside a
    profile it costs one check."""
    import jax

    return jax.profiler.TraceAnnotation(f"repro.{cat}", name=name,
                                        step=step, depth=depth)


class Tracer:
    """Span recorder with injectable clock and optional metrics registry.

    ``metrics`` may be a :class:`repro.obs.metrics.Metrics` registry; every
    finished span then also feeds a ``span.<cat>`` duration histogram, so
    engine traces and serve summaries share one registry (DESIGN.md §14 —
    pass ``SolveServer.metrics`` here to unify them).
    """

    def __init__(self, *, clock: Callable[[], float] = time.perf_counter,
                 fence: bool = True, metrics=None) -> None:
        self.clock = clock
        self.fence = fence
        self.metrics = metrics
        self.spans: List[Span] = []

    # -- recording ------------------------------------------------------
    def add(self, span: Span) -> Span:
        """Record an externally built span (synthetic spans in tests)."""
        self.spans.append(span)
        if self.metrics is not None:
            self.metrics.histogram(f"span.{span.cat}").record(span.dur)
        return span

    def wrap(self, cat: str, name: str, thunk: Callable[[], Any], *,
             step: int = -1, it: int = -1, depth: int = 0,
             **meta) -> Any:
        """Run ``thunk`` inside a span and return its result.

        The span's end timestamp is taken after fencing the result (when
        ``fence=True``), so it bounds the device work the thunk launched.
        This is the engine-side entry point: one call per instrumented
        hook, no context-manager overhead in the loop body.
        """
        t0 = self.clock()
        meta = dict(meta)
        with _annotation(cat, name, step, depth):
            out = thunk()
            if _is_abstract(out):
                # under jit: fencing is impossible and the timestamps would
                # be trace-time fabrications — tag the span and warn once
                meta["traced"] = True
                _note_traced(name)
            elif self.fence:
                _fence(out)
            t1 = self.clock()
        self.add(Span(cat, name, t0, t1, step=step, it=it, depth=depth,
                      meta=meta))
        return out

    @contextlib.contextmanager
    def span(self, cat: str, name: str, *, step: int = -1, it: int = -1,
             depth: int = 0, fence_on: Any = None, **meta):
        """Context-manager form for block-shaped sites (serve flushes,
        driver bodies).  ``fence_on`` optionally names the value to fence
        before the end timestamp."""
        t0 = self.clock()
        with _annotation(cat, name, step, depth):
            try:
                yield
            finally:
                meta = dict(meta)
                if fence_on is not None and _is_abstract(fence_on):
                    meta["traced"] = True
                    _note_traced(name)
                elif self.fence and fence_on is not None:
                    _fence(fence_on)
                self.add(Span(cat, name, t0, self.clock(), step=step, it=it,
                              depth=depth, meta=meta))

    # -- queries --------------------------------------------------------
    def by_cat(self, cat: str) -> List[Span]:
        return [s for s in self.spans if s.cat == cat]

    def total(self, cat: Optional[str] = None) -> float:
        return sum(s.dur for s in (self.spans if cat is None
                                   else self.by_cat(cat)))

    def clear(self) -> None:
        self.spans.clear()


@contextlib.contextmanager
def trace(tracer: Optional[Tracer] = None, **kw):
    """Install a tracer for the dynamic extent of the block.

        with obs.trace() as tr:
            lu_lookahead(a, 128, depth=2)
        report.overlap(tr.spans)

    Nesting installs are allowed; the previous tracer is restored on exit.
    ``**kw`` forwards to the :class:`Tracer` constructor when none is given.
    """
    global _ACTIVE
    if tracer is None:
        tracer = Tracer(**kw)
    prev = _ACTIVE
    _ACTIVE = tracer
    try:
        yield tracer
    finally:
        _ACTIVE = prev
