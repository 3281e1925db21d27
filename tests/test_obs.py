"""repro.obs contract tests (DESIGN.md §14).

Pins the four guarantees the observability layer makes:

* span math is deterministic and unit-testable (fake clock, synthetic
  spans → exact overlap-efficiency / critical-path numbers);
* tracing **disabled** is bitwise invisible — instrumented sites never
  touch the tracer (a raising tracer proves it) and outputs across
  dmf × variant equal the traced outputs bit for bit;
* tracing **enabled** changes no numerics (same sweep);
* the report/benchmark plumbing round-trips: BENCH row validation,
  HLO-accounting fallback warnings, and the serve/tracer shared metrics
  registry; spans reach the profiler's trace as host annotations.
"""
import json

import jax
import numpy as np
import pytest

from conformance import make_input
from repro.core.lookahead import get_variant, list_variants
from repro.obs import Metrics, Span, Tracer, active, trace
from repro.obs import report as obs_report
from repro.obs import tracer as obs_tracer


class FakeClock:
    """Deterministic clock: returns queued times, then increments by 1."""

    def __init__(self, *times):
        self.times = list(times)
        self.t = times[-1] if times else 0.0

    def __call__(self):
        if self.times:
            self.t = self.times.pop(0)
            return self.t
        self.t += 1.0
        return self.t


# ---------------------------------------------------------------------------
# Tracer core.
# ---------------------------------------------------------------------------
def test_active_is_none_by_default():
    assert active() is None


def test_wrap_records_duration_and_tags():
    tr = Tracer(clock=FakeClock(10.0, 13.5), fence=False)
    out = tr.wrap("PF", "PF(2)", lambda: 42, step=2, it=1, depth=1, cols=3)
    assert out == 42
    (s,) = tr.spans
    assert (s.cat, s.name, s.step, s.it, s.depth) == ("PF", "PF(2)", 2, 1, 1)
    assert s.t0 == 10.0 and s.t1 == 13.5 and s.dur == 3.5
    assert s.meta == {"cols": 3}


def test_span_context_manager_and_nesting():
    tr = Tracer(clock=FakeClock(0.0, 1.0, 2.0, 5.0), fence=False)
    with tr.span("drive", "outer"):
        with tr.span("PF", "inner"):
            pass
    # inner closes first (ts 1→2), outer spans the whole block (0→5)
    assert [(s.name, s.t0, s.t1) for s in tr.spans] \
        == [("inner", 1.0, 2.0), ("outer", 0.0, 5.0)]
    assert tr.total("PF") == 1.0 and tr.total() == 6.0
    assert [s.name for s in tr.by_cat("drive")] == ["outer"]


def test_trace_installs_and_restores():
    outer = Tracer()
    with trace(outer) as t1:
        assert active() is outer is t1
        with trace() as t2:               # nested install, fresh tracer
            assert active() is t2 is not outer
        assert active() is outer
    assert active() is None


def test_tracer_feeds_shared_metrics_registry():
    m = Metrics()
    tr = Tracer(clock=FakeClock(0.0, 2.0), fence=False, metrics=m)
    tr.wrap("PF", "PF(0)", lambda: None)
    snap = m.snapshot()
    assert snap["hist.span.PF.count"] == 1.0
    assert snap["hist.span.PF.mean"] == 2.0


def test_serve_metrics_is_the_obs_registry():
    # satellite: one percentile implementation — the serve module re-exports
    # the obs primitives rather than keeping its own copies
    from repro.obs import metrics as obs_metrics
    from repro.serve import metrics as serve_metrics

    assert serve_metrics.Histogram is obs_metrics.Histogram
    assert serve_metrics.Metrics is obs_metrics.Metrics


# ---------------------------------------------------------------------------
# Overlap / critical-path math on synthetic spans.
# ---------------------------------------------------------------------------
def _syn(cat, t0, t1, *, step=-1, it=-1, depth=0):
    return Span(cat, f"{cat}({step})", t0, t1, step=step, it=it, depth=depth)


def test_overlap_efficiency_synthetic():
    spans = [
        _syn("PF", 0.0, 3.0, step=0, it=-1, depth=1),    # prologue
        _syn("TU", 3.0, 13.0, step=0, it=0),             # iter 0 bulk
        _syn("PF", 3.0, 7.0, step=1, it=0, depth=1),     # pre-factor PF(1)
        _syn("TU", 13.0, 15.0, step=1, it=1),            # iter 1 bulk
        _syn("PF", 15.0, 20.0, step=2, it=1, depth=1),   # pre-factor PF(2)
    ]
    ov = obs_report.overlap(spans)
    # hidden = min(4, 10) + min(5, 2) = 6 of 12 s total panel time;
    # the prologue (it = -1) runs before any update exists — never hidden
    assert ov["hidden_s"] == pytest.approx(6.0)
    assert ov["panel_s"] == pytest.approx(12.0)
    assert ov["overlap_efficiency"] == pytest.approx(0.5)
    # critical path: max-lane per iteration — 3 (prologue) + 10 + 5
    assert ov["critical_path_s"] == pytest.approx(18.0)
    assert ov["serialized_s"] == pytest.approx(24.0)
    assert ov["ideal_speedup"] == pytest.approx(24.0 / 18.0)
    assert ov["n_iters"] == 2.0 and ov["max_inflight"] == 1.0


def test_overlap_ignores_non_engine_spans():
    spans = [_syn("TU", 0.0, 4.0, step=0, it=0),
             Span("drive", "lu_factor", 0.0, 100.0)]
    ov = obs_report.overlap(spans)
    assert ov["serialized_s"] == pytest.approx(4.0)
    assert ov["n_spans"] == 1.0


def test_mtb_trace_has_no_lookahead_depth():
    a = make_input("lu", 48, 48, seed=7, dtype="float32")
    with trace() as tr:
        get_variant("lu", "mtb")(a, 16)
    eng = [s for s in tr.spans if s.cat in obs_report.ENGINE_CATS]
    assert eng and all(s.depth == 0 for s in eng)
    assert obs_report.overlap(tr.spans)["overlap_efficiency"] == 0.0


def test_la_trace_shows_inflight_depth():
    a = make_input("lu", 64, 64, seed=3, dtype="float32")
    with trace() as tr:
        get_variant("lu", "la")(a, 16)
    pf = [s for s in tr.spans if s.cat == "PF"]
    assert any(s.depth >= 1 for s in pf)
    ov = obs_report.overlap(tr.spans)
    assert ov["max_inflight"] >= 1.0
    assert 0.0 <= ov["overlap_efficiency"] <= 1.0


# ---------------------------------------------------------------------------
# Bitwise contracts: disabled == enabled, and disabled never touches the
# tracer at all.
# ---------------------------------------------------------------------------
_BITWISE_DMFS = ("lu", "cholesky", "qr", "ldlt")


def _bitwise_cases():
    cases = []
    for dmf in _BITWISE_DMFS:
        for variant in list_variants(dmf):
            if variant == "tuned" or "mb" in variant:
                # tuned reads machine-local cache; fused kernels belong to
                # the pallas CI lane (conftest auto-marker)
                continue
            cases.append((dmf, variant))
    return cases


@pytest.mark.parametrize("dmf,variant", _bitwise_cases(),
                         ids=lambda v: str(v))
def test_tracing_is_bitwise_invisible(dmf, variant):
    a = make_input(dmf, 48, 48, seed=11, dtype="float32")
    fn = get_variant(dmf, variant)
    base = fn(a, 16)
    with trace() as tr:
        traced = fn(a, 16)
    assert tr.spans, "tracer installed but no spans recorded"
    for x, y in zip(jax.tree_util.tree_leaves(base),
                    jax.tree_util.tree_leaves(traced)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_disabled_path_never_calls_the_tracer(monkeypatch):
    # the disabled-path budget is a single `active() is None` predicate:
    # make every Tracer entry point explode; with no tracer installed the
    # engine, drivers, and panel kernels must still run clean.
    def boom(*a, **k):
        raise AssertionError("tracer touched while disabled")

    monkeypatch.setattr(obs_tracer.Tracer, "wrap", boom)
    monkeypatch.setattr(obs_tracer.Tracer, "span", boom)
    monkeypatch.setattr(obs_tracer.Tracer, "add", boom)
    assert active() is None
    a = make_input("lu", 48, 48, seed=5, dtype="float32")
    get_variant("lu", "la")(a, 16)

    from repro.kernels import panels
    panels.lu_panel(a[:, :16])

    from repro.solve import drivers
    drivers.lu_factor(a, 16)


# ---------------------------------------------------------------------------
# The profiler's trace: host spans as annotations, kernels as scopes.
# ---------------------------------------------------------------------------
def test_eager_spans_reach_the_profiler_trace(tmp_path):
    import glob

    from jax.profiler import ProfileData
    from repro.solve import drivers

    a = make_input("lu", 64, 64, seed=3, dtype="float32")
    with jax.profiler.trace(str(tmp_path)):
        with trace() as tr:
            jax.block_until_ready(drivers.lu_factor(a, 16))
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    events = [ev for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("repro.")]
    pf = [ev for ev in events if ev.name == "repro.PF"]
    assert len(pf) == len(tr.by_cat("PF")) == 4
    assert {dict(ev.stats)["name"] for ev in pf} \
        == {s.name for s in tr.by_cat("PF")}
    assert any(ev.name == "repro.drive" for ev in events)


def test_backend_kernels_are_scoped_and_backends_stay_equal():
    from repro.core import backend as be

    jnp_be = be.Backend(name="jnp", gemm=be.gemm_jnp, trsm=be.trsm_jnp)
    assert jnp_be == be.JNP_BACKEND and hash(jnp_be) == hash(be.JNP_BACKEND)
    assert jnp_be.gemm.scope == be.GEMM_SCOPE and jnp_be.gemm.fn is be.gemm_jnp
    assert jnp_be.trsm.scope == be.TRSM_SCOPE
    import dataclasses
    again = dataclasses.replace(jnp_be, name="jnp")
    assert again.gemm == jnp_be.gemm and again.gemm.fn is be.gemm_jnp
    text = jax.jit(jnp_be.update).lower(
        np.ones((8, 4), np.float32), np.ones((8, 2), np.float32),
        np.ones((2, 4), np.float32)).as_text(debug_info=True)
    assert "repro.gemm" in text


# ---------------------------------------------------------------------------
# BENCH row validation (benchmarks.common).
# ---------------------------------------------------------------------------
def _good_row(**over):
    row = {"bench": "obs", "commit": "abc1234", "ts": 100.0, "wall": 0.5,
           "n": 512, "b": 128, "variant": "la2", "gflops": 1.25,
           "extra_key": "fine"}
    row.update(over)
    return row


def test_validate_rows_accepts_schema_rows():
    from benchmarks.common import validate_rows
    rows = [_good_row(), _good_row(ts=101.0, n=None, gflops=None)]
    assert validate_rows(rows) is rows


@pytest.mark.parametrize("bad", [
    {"bench": None},                     # required wrong type
    {"wall": "0.5"},                     # string where number required
    {"wall": -1.0},                      # negative wall
    {"n": "512"},                        # optional wrong type
    {"gflops": True},                    # bool is not a number here
])
def test_validate_rows_rejects_bad_rows(bad):
    from benchmarks.common import validate_rows
    with pytest.raises(ValueError):
        validate_rows([_good_row(**bad)])


def test_validate_rows_rejects_missing_key_and_decreasing_ts():
    from benchmarks.common import validate_rows
    row = _good_row()
    del row["ts"]
    with pytest.raises(ValueError, match="missing required key"):
        validate_rows([row])
    with pytest.raises(ValueError, match="monotone"):
        validate_rows([_good_row(ts=100.0), _good_row(ts=99.0)])


def test_write_json_rows_stamps_ts(tmp_path):
    from benchmarks.common import write_json_rows
    path = tmp_path / "BENCH_unit.json"
    write_json_rows(str(path), ["lu_la_n512_b128,1234.5,12.3GFLOPS"],
                    commit="deadbee")
    (rec,) = [json.loads(line) for line in path.read_text().splitlines()]
    assert rec["bench"] == "lu" and rec["variant"] == "la"
    assert rec["n"] == 512 and rec["b"] == 128
    assert rec["gflops"] == pytest.approx(12.3)
    assert rec["ts"] > 0


# ---------------------------------------------------------------------------
# HLO accounting fallbacks (launch.hlo_accounting hardening).
# ---------------------------------------------------------------------------
_HLO_FALLBACKS = """\
HloModule m

%bodyc (p: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %p = (s32[], f32[4,4]) parameter(0)
  %g = f32[4,4] get-tuple-element(%p), index=1
  %dd = f32[4,4] dot(%g, %g), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %r = (s32[], f32[4,4]) tuple(%g, %dd)
}

%condc (p: (s32[], f32[4,4])) -> pred[] {
  %p2 = (s32[], f32[4,4]) parameter(0)
  ROOT %lt = pred[] compare(%p2, %p2), direction=LT
}

ENTRY %main (a: f32[4,4]) -> f32[4,4] {
  %a = f32[4,4] parameter(0)
  %odd = u4[4,4] copy(%a)
  %w = (s32[], f32[4,4]) while((s32[], f32[4,4]) %a), condition=%condc, body=%bodyc
  ROOT %d = f32[4,4] dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""


def test_analyze_hlo_records_fallback_warnings():
    from repro.launch.hlo_accounting import analyze_hlo

    acct = analyze_hlo(_HLO_FALLBACKS)
    warns = acct["warnings"]
    assert any("unknown dtype 'u4'" in w for w in warns)
    assert any("counted once" in w and "bodyc" in w for w in warns)
    # entry dot (128 flops) + while body dot counted exactly once (128)
    assert acct["flops"] == pytest.approx(256.0)


def test_analyze_hlo_known_trip_count_no_warning():
    from repro.launch.hlo_accounting import analyze_hlo

    hlo = _HLO_FALLBACKS.replace(
        "condition=%condc, body=%bodyc",
        'condition=%condc, body=%bodyc, backend_config={"known_trip_count":'
        '{"n":"4"}}').replace("  %odd = u4[4,4] copy(%a)\n", "")
    acct = analyze_hlo(hlo)
    assert acct["warnings"] == []
    assert acct["flops"] == pytest.approx(128.0 + 4 * 128.0)


def test_attainment_row_joins_model_and_hlo_warnings():
    a = make_input("lu", 48, 48, seed=2, dtype="float32")
    with trace() as tr:
        get_variant("lu", "la")(a, 16)
    row = obs_report.attainment_row("lu", 48, "la", 16, tr.spans,
                                    hlo_text=_HLO_FALLBACKS)
    assert row["measured_s"] > 0
    assert row["model_s"] is None or row["model_s"] > 0
    assert row["hlo_flops"] == pytest.approx(256.0)
    assert any("counted once" in w for w in row["hlo_warnings"])
    table = obs_report.format_attainment([row])
    assert "lu" in table and "counted once" in table


# ---------------------------------------------------------------------------
# Sweep + serve integration.
# ---------------------------------------------------------------------------
def test_sweep_trace_sink_records_candidate_traces(tmp_path):
    from repro import tune
    from repro.tune import sweep

    sink = []
    cache = tune.TuneCache(tmp_path / "tune.json")
    sweep.search("lu", 32, blocks=(16,), variants=("la",), repeats=1,
                 cache=cache, force=True, trace_sink=sink)
    assert sink, "trace_sink stayed empty"
    ct = sink[0]
    assert isinstance(ct, sweep.CandidateTrace)
    assert ct.dmf == "lu" and ct.n == 32
    assert ct.spans and ct.measured_s > 0
    assert "overlap_efficiency" in ct.overlap
    assert ct.predicted_s is None or ct.predicted_s > 0
    # sweeping with a tracer must not have left one installed
    assert active() is None


def test_serve_flush_spans_share_server_registry():
    from repro.serve import ServerConfig, SolveServer

    srv = SolveServer(ServerConfig(max_batch=4, max_wait_s=0.0))
    rng = np.random.default_rng(0)
    a = rng.standard_normal((16, 16)).astype(np.float32)
    b = rng.standard_normal((16, 1)).astype(np.float32)

    tr = Tracer(metrics=srv.metrics)
    with trace(tr):
        rid = srv.submit("gesv", a, b)
        srv.drain()
        resp = srv.take(rid)
    assert resp is not None
    serve_spans = tr.by_cat("serve")
    assert serve_spans and "gesv" in serve_spans[0].name
    snap = srv.metrics.snapshot()
    assert snap["hist.span.serve.count"] >= 1.0


# ---------------------------------------------------------------------------
# Trace-under-jit detection (ISSUE 9 satellite 2).
# ---------------------------------------------------------------------------
def test_trace_under_jit_warns_once_and_tags_spans():
    import warnings as _warnings

    obs_tracer._reset_traced_warning()
    a = np.eye(8, dtype=np.float32) * 4.0
    chol = get_variant("cholesky", "mtb")

    with trace() as tr:
        # a FRESH jit wrapper forces a retrace with the tracer installed;
        # the instrumented sites see jax.core.Tracer values, not numbers
        with pytest.warns(RuntimeWarning, match="under jit tracing"):
            out = jax.jit(lambda x: chol(x, 4))(a)
    assert np.allclose(out, 2.0 * np.eye(8))
    traced = [s for s in tr.spans if s.meta.get("traced")]
    assert traced, "expected spans tagged traced=True under jit"
    # times under tracing measure trace time, never fenced execution
    for s in traced:
        assert s.meta["traced"] is True

    # the warning is a one-time latch: a second traced run stays silent
    with trace():
        with _warnings.catch_warnings(record=True) as rec:
            _warnings.simplefilter("always")
            jax.jit(lambda x: chol(x, 4))(a)  # fresh lambda → fresh trace
    assert not [w for w in rec if "under jit tracing" in str(w.message)]
    obs_tracer._reset_traced_warning()


def test_eager_trace_does_not_warn_or_tag():
    import warnings as _warnings

    obs_tracer._reset_traced_warning()
    a = jax.numpy.asarray(np.eye(8, dtype=np.float32) * 4.0)
    with trace() as tr:
        with _warnings.catch_warnings(record=True) as rec:
            _warnings.simplefilter("always")
            get_variant("cholesky", "mtb")(a, 4)
    assert not [w for w in rec if "under jit tracing" in str(w.message)]
    assert not [s for s in tr.spans if s.meta.get("traced")]


# ---------------------------------------------------------------------------
# Tile-DAG critical-path report (ISSUE 9 tentpole, synthetic spans).
# ---------------------------------------------------------------------------
def _tile_span(kind, t0, t1, *, wave, traced=False):
    meta = {"kind": kind, "dag_depth": wave}
    if traced:
        meta["traced"] = True
    return Span("TILE", f"{kind}(. . .)", t0, t1, step=0, it=wave, meta=meta)


def test_tile_dag_report_synthetic():
    spans = [
        _tile_span("GEQRT", 0.0, 1.0, wave=0),
        _tile_span("UNMQR", 1.0, 3.0, wave=1),
        _tile_span("TSQRT", 3.0, 3.5, wave=1),
        # a span recorded under jit tracing must not pollute the numbers
        _tile_span("GEQRT", 0.0, 50.0, wave=0, traced=True),
        # nor does non-TILE engine work
        Span("drive", "qr_factor", 0.0, 100.0),
    ]
    rep = obs_report.tile_dag(spans)
    assert rep["serialized_s"] == pytest.approx(3.5)
    # per-wave max: 1.0 (wave 0) + 2.0 (wave 1)
    assert rep["critical_path_s"] == pytest.approx(3.0)
    assert rep["ideal_speedup"] == pytest.approx(3.5 / 3.0)
    assert rep["wall_s"] == pytest.approx(3.5)
    assert rep["n_tasks"] == 3.0
    assert rep["n_waves"] == 2.0
    assert rep["max_wave_width"] == 2.0
    assert rep["kind_s"] == {"GEQRT": pytest.approx(1.0),
                             "UNMQR": pytest.approx(2.0),
                             "TSQRT": pytest.approx(0.5)}


def test_tile_dag_report_empty():
    rep = obs_report.tile_dag([Span("drive", "qr_factor", 0.0, 1.0)])
    assert rep["n_tasks"] == 0.0
    assert rep["ideal_speedup"] == 1.0
