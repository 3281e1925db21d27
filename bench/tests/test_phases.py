"""The program's own names in the device trace: phases (outermost
``repro.<CAT>``), kernels (innermost ``repro.gemm`` / ``repro.trsm``), and
the caller rule before the neighbour rule; then a small trace recorded on
a TPU v5e with the program's scopes."""
from __future__ import annotations

import os
from types import SimpleNamespace as Ev

import pytest

from bench import phases, trace

DATA = os.path.join(os.path.dirname(__file__), "data")

HLO = """HloModule jit_step, entry_computation_layout={()->f32[8]}

%inner.2 (q: f32[8]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  ROOT %copy.7 = f32[8]{0} copy(%q)
}

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %copy.5 = f32[8]{0} copy(%p)
  %call.6 = f32[8]{0} call(%copy.5), to_apply=%inner.2
  ROOT %tuple.9 = (s32[], f32[8]) tuple(%p, %call.6)
}

%cond.3 (p: (s32[], f32[8])) -> pred[] {
  %p.1 = (s32[], f32[8]) parameter(0)
  ROOT %lt.1 = pred[] constant(false)
}

ENTRY %main.4 () -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(step)/bench.factor/repro.PF/repro.trsm/jit(_trsm_impl)/mul"}
  %while.2 = (s32[], f32[8]) while(%tuple.0), condition=%cond.3, body=%body.1, metadata={op_name="jit(step)/bench.factor/repro.SWAP/while"}
  %copy.3 = f32[8]{0} copy(%fusion.1)
  %fusion.4 = f32[8]{0} fusion(), kind=kOutput, metadata={op_name="jit(step)/bench.factor/repro.PU/repro.gemm/jit(_gemm_impl)/dot_general"}
  ROOT %fusion.8 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(step)/bench.solve/repro.trsm/jit(_trsm_impl)/repro.gemm/jit(_gemm_impl)/jit(step)/bench.solve/repro.trsm/sub"}
}
"""


def _ev(start, dur, name):
    return Ev(start_ns=float(start), duration_ns=float(dur),
              name=f"%{name} = f32[8]{{0}} op()")


def test_phase_and_kernel_of_an_op_name():
    assert phases.phase_of("jit(step)/bench.factor/repro.TU/repro.gemm/"
                           "jit(_gemm_impl)/repro.PF/dot") \
        == "bench.factor/repro.TU"                 # outermost phase
    assert phases.kernel_of("jit(step)/bench.solve/repro.trsm/x/repro.gemm"
                            "/y/repro.trsm/sub") == "bench.solve/repro.trsm"
    assert phases.kernel_of("jit(step)/bench.factor/repro.SWAP/while") is None
    assert phases.phase_of("jit(step)/bench.solve/repro.trsm/sub") \
        == f"bench.solve/{trace.NO_SCOPE}"
    assert phases.phase_of("reduce") == f"{trace.NO_SCOPE}/{trace.NO_SCOPE}"


def test_caller_rule_walks_out_to_the_calling_instruction():
    paths = phases.paths_from_hlo(HLO)
    swap = "jit(step)/bench.factor/repro.SWAP/while"
    assert paths["copy.5"] == (swap, True)      # in the while body
    assert paths["copy.7"] == (swap, True)      # body -> call -> inner
    assert paths["while.2"] == (swap, False)
    assert "copy.3" not in paths                # entry: nothing calls it
    assert paths["fusion.1"][1] is False


def test_caller_rule_before_the_neighbour_rule():
    events = [_ev(0, 10, "fusion.1"), _ev(10, 50, "while.2"),
              _ev(20, 10, "copy.5"), _ev(35, 10, "copy.7"),
              _ev(60, 20, "copy.3"), _ev(80, 30, "fusion.4"),
              _ev(110, 40, "fusion.8")]
    runs = [(0.0, 150.0, "jit_step")]
    ops = phases.name_ops(0, events, runs,
                          {"jit_step": phases.paths_from_hlo(HLO)})
    by = dict(zip(["fusion.1", "while.2", "copy.5", "copy.7", "copy.3",
                   "fusion.4", "fusion.8"], ops))
    assert (by["copy.5"].phase, by["copy.5"].rule) \
        == ("bench.factor/repro.SWAP", phases.CALLER)
    # copy.3 has no caller: the op before it (copy.7, SWAP) lends its names
    assert (by["copy.3"].phase, by["copy.3"].rule) \
        == ("bench.factor/repro.SWAP", phases.NEIGHBOUR)
    assert by["fusion.1"].kernel == "bench.factor/repro.trsm"
    assert by["fusion.8"].kernel == "bench.solve/repro.trsm"
    assert by["fusion.8"].phase == f"bench.solve/{trace.NO_SCOPE}"

    s = phases.split(ops, (0.0, 200.0), [(0.0, 150.0)], steps=1)
    assert s.phase_s == {
        "bench.factor/repro.PF": pytest.approx(10e-9),
        "bench.factor/repro.SWAP": pytest.approx(70e-9),   # 10..80
        "bench.factor/repro.PU": pytest.approx(30e-9),
        f"bench.solve/{trace.NO_SCOPE}": pytest.approx(40e-9)}
    assert s.kernel_s == {"bench.factor/repro.trsm": pytest.approx(10e-9),
                          "bench.factor/repro.gemm": pytest.approx(30e-9),
                          "bench.solve/repro.trsm": pytest.approx(40e-9)}
    assert s.rule_s == {phases.CALLER: pytest.approx(20e-9),
                        phases.NEIGHBOUR: pytest.approx(20e-9)}
    assert s.phase_ops["bench.factor/repro.SWAP"] == 4
    assert s.per_step() == pytest.approx({
        "panel_dev_s": 10e-9, "swap_dev_s": 70e-9, "update_dev_s": 30e-9,
        "gemm_dev_s": 30e-9, "trsm_dev_s": 50e-9})


def test_trace_without_program_scopes_has_no_phases():
    # recorded before the program named its work: every op is (none)
    s = phases.load(DATA)
    assert s.steps == 2
    assert all(k.endswith(f"/{trace.NO_SCOPE}") for k in s.phase_s)
    assert s.kernel_s == {}
    assert set(s.per_step().values()) == {None}


def test_scoped_trace_splits_factor_and_solve():
    # hpl_lu.solve at n=128, nb=32, recorded on a TPU v5e with the
    # program's phase and kernel scopes (record_trace.py)
    s = phases.load(os.path.join(DATA, "scoped"))
    assert s.steps == 2
    assert s.per_step() == pytest.approx({
        "panel_dev_s": 0.001672662 / 2,
        "swap_dev_s": 0.0009813810000000001 / 2,
        "update_dev_s": (7.563700000000001e-05 + 4.4178e-05) / 2,
        "gemm_dev_s": (2.6540000000000003e-06 + 2.2300000000000002e-06) / 2,
        "trsm_dev_s": (7.7099e-05 + 0.001103634) / 2}, rel=1e-9)
    # the phases and the unscoped rest (the pivots' permutation, the
    # factor object) make up bench.factor as bench.trace reads it, but for
    # the few ops the caller rule gives elsewhere than the neighbour rule
    factor = sum(v for k, v in s.phase_s.items()
                 if k.startswith("bench.factor/"))
    assert factor == pytest.approx(0.0034951550000000002, rel=1e-9)
    assert factor == pytest.approx(2 * 0.0017411865, rel=0.01)
    assert s.phase_s[f"bench.factor/{trace.NO_SCOPE}"] \
        == pytest.approx(0.0007212970000000001, rel=1e-9)
    # the caller rule charges the copies in loop bodies, the neighbour
    # rule what is left
    assert s.rule_s == pytest.approx({
        phases.CALLER: 0.001414092,
        phases.NEIGHBOUR: 2.4310000000000003e-05}, rel=1e-9)
    assert s.phase_ops["bench.factor/repro.SWAP"] == 1569.0
    assert s.phase_ops["bench.factor/repro.PF"] == 2204.0
