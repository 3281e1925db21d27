"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

What a TPU trace holds (read by hand on a v5e): one plane per chip,
``/device:TPU:<i>``, whose line ``XLA Ops`` has one event per executed HLO
instruction, named by the instruction's text (``%fusion.12 = f32[...]
fusion(...)``), with start and duration on the host's clock.  The host's
plane ``/host:CPU`` has a line ``python`` with the benchmark's own
``jax.profiler.TraceAnnotation`` spans.  The events carry no scope path, so
an op's scope comes from the compiled program's HLO text, whose
instructions carry ``metadata={op_name="jit(step)/bench.factor/..."}``.
Instructions the compiler added (copies, some fusions) have no op_name;
such an op is charged to the scope of the op that ran before it in the
same program run (line ``XLA Modules``), and the share charged so is
reported as ``inherited``.

Every time here is a union of intervals, so events that overlap or nest
are never counted twice.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Iterable, Optional

#: Line of a device plane that holds one event per executed HLO op.
OPS_LINE = "XLA Ops"
#: Line of a device plane that holds one event per program run.
MODULES_LINE = "XLA Modules"
#: Prefix of the scopes the benchmark opens with ``jax.named_scope``.
SCOPE_PREFIX = "bench."
#: Scope given to an op that neither has one nor follows one.
NO_SCOPE = "(none)"

_INSTR = re.compile(r'^\s*(?:ROOT )?%(\S+) = .*?op_name="([^"]*)"', re.M)
_EVENT_NAME = re.compile(r"^%(\S+) = ")
_DIGITS = re.compile(r"[.\-]\d+$")
_MODULE = re.compile(r"^(?:HloModule )?([A-Za-z0-9_.\-]+)")


def scope_of(op_name: str) -> Optional[str]:
    """First ``bench.*`` segment of an op_name path, or None."""
    for part in op_name.split("/"):
        if part.startswith(SCOPE_PREFIX):
            return part
    return None


def scopes_from_hlo(hlo_text: str) -> dict[str, str]:
    """Instruction name → benchmark scope, for instructions that have one."""
    out = {}
    for name, op_name in _INSTR.findall(hlo_text):
        s = scope_of(op_name)
        if s is not None:
            out[name] = s
    return out


def union_ns(intervals: Iterable[tuple[float, float]]) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[tuple[float, float]], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    """Stretches of ``[lo, hi]`` that no interval covers, longest first."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    out = [(s, e) for s, e in out if e > s]
    return sorted(out, key=lambda g: g[0] - g[1])


@dataclasses.dataclass(slots=True)
class Op:
    chip: int
    start_ns: float
    end_ns: float
    name: str       # instruction name, e.g. "fusion.12"
    scope: str
    inherited: bool


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Summary:
    """What the per-layer metric readers read (``bench/metrics/*.py``)."""

    steps: int                 # timed steps inside the traced window
    step_runs: int             # runs of the step program seen on chip 0
    window_s: float            # length of the traced window
    chips: int                 # device planes with ops
    busy_s: float              # union of op intervals, mean over chips
    scope_s: dict[str, float]  # union per scope, mean over chips
    inherited_s: float         # part of scope_s charged by the neighbour rule
    top_ops: list[tuple[str, float]]   # (scope/op kind, self seconds)
    idle_gaps: list[tuple[str, float]]


def module_name(text: str) -> str:
    """``jit_step`` of an ``XLA Modules`` event name or an HLO text."""
    m = _MODULE.match(text)
    return m.group(1) if m else text


def read_xplane(source, programs: dict[str, str]
                ) -> tuple[list[Op], list[Span], list[Span]]:
    """Device ops, host ``bench.*`` spans and chip 0's program runs (as
    spans named by module) of one ``.xplane.pb``, given as a path or as
    the file's bytes.

    ``programs`` maps a module name (``jit_step``) to its compiled HLO
    text.  An op is looked up in the module whose ``XLA Modules`` event
    holds it; the neighbour rule starts afresh in each module run.
    """
    from jax.profiler import ProfileData

    scopes = {name: scopes_from_hlo(text) for name, text in programs.items()}
    data = (ProfileData.from_serialized_xspace(source)
            if isinstance(source, bytes) else ProfileData.from_file(source))
    ops, spans, module_runs = [], [], []
    chip = 0
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            runs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           module_name(ev.name))
                          for ev in (lines[MODULES_LINE].events
                                     if MODULES_LINE in lines else ()))
            ops.extend(_ops(chip, lines[OPS_LINE].events, runs, scopes))
            if chip == 0:
                module_runs = [Span(m, s, e) for s, e, m in runs]
            chip += 1
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SCOPE_PREFIX):
                        spans.append(Span(ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns))
    return ops, spans, module_runs


def _ops(chip: int, events, runs, scopes) -> list[Op]:
    out, prev, r = [], NO_SCOPE, -1
    evs = sorted((ev.start_ns, ev.duration_ns, ev.name) for ev in events)
    table: dict[str, str] = {}
    for start, dur, text in evs:
        # advance to the module run that holds this op, if any
        moved = False
        while r + 1 < len(runs) and runs[r + 1][0] <= start:
            r, moved = r + 1, True
        if moved:
            prev = NO_SCOPE
            table = scopes.get(runs[r][2], {})
        if r < 0 or start > runs[r][1]:
            table = {}
        m = _EVENT_NAME.match(text)
        name = m.group(1) if m else text
        own = table.get(name)
        scope = own or prev
        out.append(Op(chip, start, start + dur, name, scope, own is None))
        prev = scope
    return out


def _self_ns(ops: list[Op], clip: list[tuple[float, float]]) -> list[float]:
    """Each op's time less the ops nested in it on its chip (a ``while``
    op's event spans its body's ops)."""
    self_t = [e - s for s, e in clip]
    order = sorted(range(len(ops)),
                   key=lambda k: (ops[k].chip, clip[k][0], -clip[k][1]))
    stack: list[int] = []
    for k in order:
        s, e = clip[k]
        while stack and (ops[stack[-1]].chip != ops[k].chip
                         or clip[stack[-1]][1] <= s):
            stack.pop()
        if stack:
            self_t[stack[-1]] -= min(e, clip[stack[-1]][1]) - s
        stack.append(k)
    return self_t


def summarize(ops: list[Op], spans: list[Span], *, window: Span,
              steps: int, module_runs: list[Span] = (),
              step_module: str = "", top: int = 10) -> Summary:
    """Reduce ops inside ``window`` to a :class:`Summary`.

    ``window`` is the host span around the timed steps, widened to hold
    every program run in the trace (the trace starts just before the
    window and stops just after it, so every run belongs to it; device and
    host clocks differ by up to a millisecond or so).  Ops are clipped to
    it.  Each idle gap is named by the host span that covers most of it,
    ``(none)`` if none does.
    """
    lo = min([window.start_ns] + [r.start_ns for r in module_runs])
    hi = max([window.end_ns] + [r.end_ns for r in module_runs])
    inside = [o for o in ops if o.end_ns > lo and o.start_ns < hi]
    clip = [(max(o.start_ns, lo), min(o.end_ns, hi)) for o in inside]
    chips = sorted({o.chip for o in inside})
    busy = 0.0
    for c in chips:
        busy += union_ns(iv for o, iv in zip(inside, clip) if o.chip == c)
    nchips = max(len(chips), 1)

    groups: dict[tuple[str, int], list] = {}
    for o, iv in zip(inside, clip):
        groups.setdefault((o.scope, o.chip), []).append((iv, o.inherited))
    scope_ns: dict[str, float] = {}
    inherited_ns = 0.0
    for (s, _), sel in groups.items():
        scope_ns[s] = scope_ns.get(s, 0.0) + union_ns(iv for iv, _ in sel)
        inherited_ns += union_ns(iv for iv, inh in sel if inh)

    by_kind: dict[str, float] = {}
    for o, t in zip(inside, _self_ns(inside, clip)):
        key = f"{o.scope}/{_DIGITS.sub('', o.name)}"
        by_kind[key] = by_kind.get(key, 0.0) + t
    top_ops = sorted(by_kind.items(), key=lambda kv: -kv[1])[:top]

    idle = []
    host = [sp for sp in spans if sp.end_ns > lo and sp.start_ns < hi
            and sp is not window and sp.name != window.name]
    chip0 = [iv for o, iv in zip(inside, clip) if o.chip == (chips or [0])[0]]
    for s, e in gaps(chip0, lo, hi)[:top]:
        best, name = 0.0, NO_SCOPE
        for sp in host:
            cover = min(e, sp.end_ns) - max(s, sp.start_ns)
            if cover > best:
                best, name = cover, sp.name
        idle.append((name, (e - s) * 1e-9))

    step_runs = sum(1 for r in module_runs if r.name == step_module)
    return Summary(
        steps=steps, step_runs=step_runs, window_s=(hi - lo) * 1e-9, chips=len(chips),
        busy_s=busy * 1e-9 / nchips,
        scope_s={k: v * 1e-9 / nchips for k, v in scope_ns.items()},
        inherited_s=inherited_ns * 1e-9 / nchips,
        top_ops=[(k, v * 1e-9) for k, v in top_ops],
        idle_gaps=idle)
