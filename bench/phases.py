#!/usr/bin/env python3
"""Split a recorded device trace by the program's own names.

    python bench/phases.py DIR

``DIR`` holds what ``bench/tests/record_trace.py`` writes for a cell:
``trace.xplane.pb.gz`` and one ``<module>.hlo.txt.gz`` per program.  The
script prints, per step of the step program, the device seconds of each
engine phase and backend kernel, and the five per-layer numbers they
give (``panel_dev_s``, ``swap_dev_s``, ``update_dev_s``, ``gemm_dev_s``,
``trsm_dev_s``).

The program names its work with ``jax.named_scope`` (DESIGN.md §14):
phases ``repro.PF``, ``repro.SWAP``, ``repro.PU``, ``repro.TU``,
``repro.EPI``, ``repro.BCAST`` around each engine hook
(``repro/core/pipeline.py``), kernels ``repro.gemm`` and ``repro.trsm``
around the backend's GEMM and TRSM (``repro/core/backend.py``).  An op's
phase is the outermost ``repro.<CAT>`` segment of its ``op_name``, keyed
under its ``bench.*`` scope (``bench.factor/repro.SWAP``); its kernel is
the innermost ``repro.gemm`` / ``repro.trsm`` segment.  An instruction
the compiler added without op_name takes, first, the op_name of the
instruction that calls the HLO computation it sits in (a ``copy`` in a
``while`` body takes the ``while``'s), walking outwards (the caller
rule); failing that, the names of the op that ran before it in the same
program run (the neighbour rule of :mod:`bench.trace`).  The seconds
each rule charged are printed.

Times are unions of intervals inside the window of :mod:`bench.trace`
(the host's ``bench.window`` span widened to every program run), mean
over chips.  This module reads the same trace as :mod:`bench.trace` and
changes nothing of what that module reports.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
import sys
from typing import Optional

if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))]

from bench import trace  # noqa: E402

#: Prefix of the program's own scopes.
PROGRAM_PREFIX = "repro."
#: The program's kernel scopes; every other ``repro.*`` scope is a phase.
KERNELS = ("repro.gemm", "repro.trsm")
#: Rule names, as keys of :attr:`Split.rule_s`.
CALLER, NEIGHBOUR = "caller", "neighbour"

_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|false_computation"
    r"|branch_computations|called_computations)=\{?([^,}\s]+(?:, ?%[^,}\s]+)*)")
_EVENT_NAME = re.compile(r"^%(\S+) = ")
_NONE = f"{trace.NO_SCOPE}/{trace.NO_SCOPE}"


def phase_of(op_name: str) -> str:
    """``<bench scope>/<outermost repro phase>`` of an op_name path, with
    ``(none)`` for a part it lacks."""
    phase = next((p for p in op_name.split("/")
                  if p.startswith(PROGRAM_PREFIX) and p not in KERNELS),
                 trace.NO_SCOPE)
    return f"{trace.scope_of(op_name) or trace.NO_SCOPE}/{phase}"


def kernel_of(op_name: str) -> Optional[str]:
    """``<bench scope>/<innermost repro kernel>`` of an op_name path, or
    None outside both kernels."""
    kernel = next((p for p in reversed(op_name.split("/")) if p in KERNELS),
                  None)
    if kernel is None:
        return None
    return f"{trace.scope_of(op_name) or trace.NO_SCOPE}/{kernel}"


def paths_from_hlo(hlo_text: str) -> dict[str, tuple[str, bool]]:
    """Instruction name → (op_name, whether it came from a caller).

    An instruction without op_name takes that of the instruction calling
    its computation, walking outwards until one has it; instructions that
    find none are left out.
    """
    comp_of: dict[str, str] = {}
    own: dict[str, str] = {}
    caller: dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        comp_of[name] = comp
        o = _OP_NAME.search(line)
        if o:
            own[name] = o.group(1)
        for group in _CALLED.findall(line):
            for callee in group.split(","):
                caller.setdefault(callee.strip().lstrip("%"), name)
    out = {name: (op, False) for name, op in own.items()}
    for name, c in comp_of.items():
        seen = set()
        while name not in out and c in caller and c not in seen:
            seen.add(c)
            up = caller[c]
            if up in own:
                out[name] = (own[up], True)
            c = comp_of.get(up)
    return out


@dataclasses.dataclass(slots=True)
class Op:
    chip: int
    start_ns: float
    end_ns: float
    phase: str                 # "bench.factor/repro.SWAP"
    kernel: Optional[str]      # "bench.solve/repro.trsm" or None
    rule: str                  # "", CALLER or NEIGHBOUR


def name_ops(chip: int, events, runs, paths) -> list[Op]:
    """Name each device event of one chip.  ``runs`` are the chip's
    ``(start, end, module)`` program runs, sorted; ``paths`` maps a module
    to :func:`paths_from_hlo` of its program."""
    out, r, memo = [], -1, {}
    where: dict[str, tuple[str, bool]] = {}
    prev: tuple[str, Optional[str]] = (_NONE, None)
    for start, dur, text in sorted((ev.start_ns, ev.duration_ns, ev.name)
                                   for ev in events):
        moved = False
        while r + 1 < len(runs) and runs[r + 1][0] <= start:
            r, moved = r + 1, True
        if moved:
            where, prev = paths.get(runs[r][2], {}), (_NONE, None)
        if r < 0 or start > runs[r][1]:
            where = {}
        m = _EVENT_NAME.match(text)
        path = where.get(m.group(1) if m else text)
        if path is None:
            names, rule = prev, NEIGHBOUR
        else:
            if path not in memo:     # a step runs each instruction often
                memo[path] = (phase_of(path[0]), kernel_of(path[0]))
            names, rule = memo[path], CALLER if path[1] else ""
        out.append(Op(chip, start, start + dur, names[0], names[1], rule))
        prev = names
    return out


@dataclasses.dataclass
class Split:
    """Device seconds inside the window, mean over chips."""

    steps: int                      # runs of the step program on chip 0
    phase_s: dict[str, float]       # "bench.factor/repro.PF" → s
    kernel_s: dict[str, float]      # "bench.solve/repro.trsm" → s
    rule_s: dict[str, float]        # CALLER / NEIGHBOUR → s they charged
    phase_ops: dict[str, float]     # device ops per step per phase

    def per_step(self) -> dict[str, Optional[float]]:
        """The five per-layer numbers, device seconds per step."""
        def kernel(k):
            return sum(v for key, v in self.kernel_s.items()
                       if key.startswith(trace.SCOPE_PREFIX)
                       and key.endswith("/" + k))
        t = {"panel_dev_s": self.phase_s.get("bench.factor/repro.PF", 0.0),
             "swap_dev_s": self.phase_s.get("bench.factor/repro.SWAP", 0.0),
             "update_dev_s": sum(self.phase_s.get(f"bench.factor/repro.{c}",
                                                  0.0) for c in ("TU", "PU")),
             "gemm_dev_s": kernel("repro.gemm"),
             "trsm_dev_s": kernel("repro.trsm")}
        return {k: (v / self.steps if v and self.steps else None)
                for k, v in t.items()}


def read(source, programs: dict[str, str],
         step_module: str = "jit_step") -> Split:
    """:class:`Split` of one ``.xplane.pb`` (a path or the file's bytes);
    ``programs`` maps a module name to its compiled HLO text."""
    from jax.profiler import ProfileData

    paths = {name: paths_from_hlo(text) for name, text in programs.items()}
    data = (ProfileData.from_serialized_xspace(source)
            if isinstance(source, bytes) else ProfileData.from_file(source))
    ops, windows, chip0_runs, chip = [], [], [], 0
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if trace.OPS_LINE not in lines:
                continue
            runs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           trace.module_name(ev.name))
                          for ev in (lines[trace.MODULES_LINE].events
                                     if trace.MODULES_LINE in lines else ()))
            ops += name_ops(chip, lines[trace.OPS_LINE].events, runs, paths)
            if chip == 0:
                chip0_runs = runs
            chip += 1
        elif plane.name.startswith("/host:"):
            windows += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                        for line in plane.lines for ev in line.events
                        if ev.name == "bench.window"]
    if len(windows) != 1:
        raise ValueError(f"expected one bench.window span, found "
                         f"{len(windows)}")
    steps = sum(1 for r in chip0_runs if r[2] == step_module)
    return split(ops, windows[0], [r[:2] for r in chip0_runs], steps)


def split(ops: list[Op], window: tuple[float, float],
          runs: list[tuple[float, float]], steps: int) -> Split:
    """Reduce named ops to a :class:`Split`: ``window`` widened to hold
    every program run, ops clipped to it."""
    lo = min([window[0]] + [s for s, _ in runs])
    hi = max([window[1]] + [e for _, e in runs])
    inside = [(o, (max(o.start_ns, lo), min(o.end_ns, hi)))
              for o in ops if o.end_ns > lo and o.start_ns < hi]
    nchips = max(len({o.chip for o, _ in inside}), 1)

    def per_key(key) -> dict[str, float]:
        by: dict[tuple[str, int], list] = {}
        for o, iv in inside:
            k = key(o)
            if k:
                by.setdefault((k, o.chip), []).append(iv)
        out: dict[str, float] = {}
        for (k, _), ivs in by.items():
            out[k] = out.get(k, 0.0) + trace.union_ns(ivs) * 1e-9 / nchips
        return out

    counts: dict[str, int] = {}
    for o, _ in inside:
        counts[o.phase] = counts.get(o.phase, 0) + 1
    return Split(steps=steps,
                 phase_s=per_key(lambda o: o.phase),
                 kernel_s=per_key(lambda o: o.kernel),
                 rule_s=per_key(lambda o: o.rule),
                 phase_ops={k: v / nchips / max(steps, 1)
                            for k, v in counts.items()})


def load(directory: str) -> Split:
    """:func:`read` of what ``record_trace.py`` wrote into ``directory``."""
    programs = {}
    for path in glob.glob(os.path.join(directory, "*.hlo.txt.gz")):
        with gzip.open(path, "rt") as f:
            text = f.read()
        programs[trace.module_name(text)] = text
    with gzip.open(os.path.join(directory, "trace.xplane.pb.gz"), "rb") as f:
        return read(f.read(), programs)


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    s = load(args[0])
    print(json.dumps({"steps": s.steps, "per_step": s.per_step(),
                      "phase_s": s.phase_s, "kernel_s": s.kernel_s,
                      "rule_s": s.rule_s, "phase_ops": s.phase_ops},
                     indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
