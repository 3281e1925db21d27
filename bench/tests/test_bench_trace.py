"""The trace reduction: busy time as a union, scope attribution from the
compiled HLO text with the neighbour rule, idle gaps named by host spans;
then the same on a small trace recorded on a TPU v5e."""
from __future__ import annotations

import glob
import gzip
import os
from types import SimpleNamespace as Ev

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")

HLO = """HloModule jit_step, entry_computation_layout={()->f32[]}

ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(step)/bench.factor/mul"}
  %copy.2 = f32[8]{0} copy(%fusion.1)
  %fusion.3 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(step)/bench.solve/sub"}
  ROOT %dot.4 = f32[] dot(), metadata={op_name="jit(step)/bench.solve/dot_general"}
}
"""


def _ev(start, dur, name):
    return Ev(start_ns=float(start), duration_ns=float(dur),
              name=f"%{name} = f32[8]{{0}} op()")


def test_union_counts_overlap_and_nesting_once():
    assert trace.union_ns([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25
    assert trace.union_ns([]) == 0
    assert trace.union_ns([(0, 10), (10, 12)]) == 12


def test_self_time_leaves_out_nested_ops():
    ops = [trace.Op(0, 0, 100, "while.1", "bench.factor", False),
           trace.Op(0, 10, 30, "copy.2", "bench.factor", True),
           trace.Op(0, 40, 90, "copy.3", "bench.factor", True),
           trace.Op(0, 120, 130, "fusion.4", "bench.solve", False)]
    clip = [(o.start_ns, o.end_ns) for o in ops]
    assert trace._self_ns(ops, clip) == [30, 20, 50, 10]
    s = trace.summarize(ops, [], window=trace.Span("bench.window", 0, 130),
                        steps=1)
    assert s.top_ops[0] == ("bench.factor/copy", pytest.approx(70e-9))
    assert s.busy_s == pytest.approx(110e-9)


def test_gaps_longest_first_and_clipped_to_the_window():
    got = trace.gaps([(10, 20), (25, 30)], 0, 40)
    assert got == [(0, 10), (30, 40), (20, 25)]


def test_scopes_from_hlo_and_neighbour_rule():
    scopes = trace.scopes_from_hlo(HLO)
    assert scopes == {"fusion.1": "bench.factor", "fusion.3": "bench.solve",
                      "dot.4": "bench.solve"}
    runs = [(0.0, 100.0, "jit_step"), (200.0, 300.0, "jit_step")]
    events = [_ev(0, 10, "fusion.1"), _ev(5, 20, "copy.2"),
              _ev(40, 10, "fusion.3"), _ev(60, 5, "dot.4"),
              _ev(200, 10, "copy.2"), _ev(220, 10, "fusion.3")]
    ops = trace._ops(0, events, runs, {"jit_step": scopes})
    assert [o.scope for o in ops] == ["bench.factor", "bench.factor",
                                      "bench.solve", "bench.solve",
                                      trace.NO_SCOPE, "bench.solve"]
    assert [o.inherited for o in ops] == [False, True, False, False, True,
                                          False]
    spans = [trace.Span("bench.window", 0, 300),
             trace.Span("bench.dispatch", 0, 100),
             trace.Span("bench.inputs", 150, 190)]
    s = trace.summarize(ops, spans, window=spans[0], steps=2)
    assert s.busy_s == pytest.approx((25 + 10 + 5 + 10 + 10) * 1e-9)
    assert s.scope_s["bench.factor"] == pytest.approx(25e-9)
    assert s.scope_s["bench.solve"] == pytest.approx(25e-9)
    assert s.inherited_s == pytest.approx(30e-9)   # copy.2 twice: 20 + 10
    # the longest idle gap, 65..200, is covered most by the inputs span
    assert s.idle_gaps[0] == ("bench.inputs", pytest.approx(135e-9))


@pytest.fixture(scope="module")
def recorded():
    files = glob.glob(os.path.join(DATA, "*.hlo.txt.gz"))
    programs = {}
    for path in files:
        with gzip.open(path, "rt") as f:
            text = f.read()
        programs[trace.module_name(text)] = text
    with gzip.open(os.path.join(DATA, "trace.xplane.pb.gz"), "rb") as f:
        ops, spans, runs = trace.read_xplane(f.read(), programs)
    (window,) = [s for s in spans if s.name == "bench.window"]
    return ops, spans, trace.summarize(ops, spans, window=window, steps=2,
                                       module_runs=runs,
                                       step_module="jit_step")


def test_recorded_trace_has_device_ops_and_host_spans(recorded):
    ops, spans, s = recorded
    assert s.chips == 1 and len(ops) > 1000
    assert {"bench.window", "bench.inputs", "bench.dispatch"} <= {
        sp.name for sp in spans}
    assert 0 < s.busy_s <= s.window_s
    assert s.step_runs == 2          # both steps' programs are in the trace


def test_recorded_trace_attributes_scopes(recorded):
    _, _, s = recorded
    for scope in ("bench.inputs", "bench.factor", "bench.solve"):
        assert s.scope_s.get(scope, 0) > 0, s.scope_s
    # every op runs inside a program, so nothing is left without a scope
    assert s.scope_s.get(trace.NO_SCOPE, 0) < 0.01 * s.busy_s
    assert sum(s.scope_s.values()) == pytest.approx(s.busy_s, rel=1e-6)
    assert s.inherited_s < 0.5 * s.busy_s
