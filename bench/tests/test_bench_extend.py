"""What a new configuration, cell or per-layer metric gets from the
harness: the summary its reader reads (the trace's numbers, the phase and
kernel split, the configuration as run, the traffic, the device's peaks),
the roofline share, and a configuration added by new files alone."""
from __future__ import annotations

import dataclasses
import glob
import gzip
import io
import json
import os
import shutil
import time

import pytest

from bench import harness, phases, readings, roofline, trace
from bench.tests.test_bench_harness import FAULTS, planted

ROOT = harness.ROOT
SCOPED = os.path.join(os.path.dirname(__file__), "data", "scoped")
V5E = harness.peaks("TPU v5 lite")


def _programs(directory: str) -> list[str]:
    """The HLO texts of a recorded directory, the step program last."""
    texts = []
    for path in sorted(glob.glob(os.path.join(directory, "*.hlo.txt.gz"))):
        with gzip.open(path, "rt") as f:
            texts.append(f.read())
    return sorted(texts, key=lambda t: trace.module_name(t) == "jit_step")


@pytest.fixture(scope="module")
def scoped():
    """The summary of the trace of ``hpl_lu.solve`` at n=128, nb=32 that
    ``record_trace.py`` recorded on a TPU v5e, as a traced run builds it."""
    with gzip.open(os.path.join(SCOPED, "trace.xplane.pb.gz"), "rb") as f:
        raw = f.read()
    texts = _programs(SCOPED)
    config = {"name": "recorded", "n": 128, "nb": 32}
    summary = harness.summarize_file(raw, texts, 2, config=config,
                                     traffic={"check": "all"}, peaks=V5E,
                                     module=None, log=io.StringIO())
    return raw, texts, summary


def test_summary_carries_the_split_config_and_peaks(scoped):
    raw, texts, s = scoped
    assert s.split == phases.read(raw, {trace.module_name(t): t
                                        for t in texts})
    assert s.split == phases.load(SCOPED)
    assert s.config == {"name": "recorded", "n": 128, "nb": 32}
    assert s.traffic == {"check": "all"}
    assert s.peaks is V5E and s.peaks["hbm_bytes_per_s"] == 819e9
    # the trace's own numbers are bench/trace.py's, as before the split
    ops, spans, runs = trace.read_xplane(raw, {trace.module_name(t): t
                                               for t in texts})
    (window,) = [sp for sp in spans if sp.name == "bench.window"]
    plain = trace.summarize(ops, spans, window=window, steps=2,
                            module_runs=runs, step_module="jit_step")
    assert dataclasses.replace(s, split=None, config=None, traffic=None,
                               peaks=None) == plain


def test_each_per_layer_reader_reads_the_summary(scoped):
    _, _, s = scoped
    split = s.split.per_step()
    got = {}
    for m in harness.load_spec()["per_layer"]:
        read = harness._module(os.path.join(ROOT, "bench", "metrics",
                                            m["name"] + ".py"), m["name"]).read
        got[m["name"]] = read(s)
        if m["name"] in split:
            assert got[m["name"]] == split[m["name"]]
    assert got["factor_dev_s"] == s.scope_s["bench.factor"] / 2
    assert got["solve_dev_s"] == s.scope_s["bench.solve"] / 2
    assert got["device_idle_frac"] == 1 - s.busy_s / s.window_s
    assert all(v is not None and v > 0 for v in got.values()), got


def test_roofline_share_by_hand_and_above_the_peak():
    # 197 GFLOP in 2 ms at 197 TFLOP/s is half the peak; 1.638 GB in 2 ms
    # at 819 GB/s is all of the bandwidth, and the larger bound counts
    assert roofline.share(197e9, 0, 2e-3, V5E, "bf16_flop_per_s") \
        == pytest.approx(0.5, rel=1e-12)
    assert roofline.share(197e9, 1.638e9, 2e-3, V5E, "bf16_flop_per_s") \
        == pytest.approx(1.0, rel=1e-12)
    assert roofline.share(0, 1.638e9 * 1.04, 2e-3, V5E,
                          "bf16_flop_per_s") == pytest.approx(1.04)
    with pytest.raises(ValueError, match="roofline share"):
        roofline.share(197e9 * 1.06, 0, 1e-3, V5E, "bf16_flop_per_s")
    with pytest.raises(ValueError, match="positive"):
        roofline.share(1.0, 1.0, 0.0, V5E, "bf16_flop_per_s")


#: A reader of the throwaway configuration: its solves' TRSM share of the
#: roofline, from counts its configuration's module keeps.
_READER = '''from bench import roofline


def read(summary):
    t = summary.split.kernel_s.get("bench.solve/repro.trsm")
    if not t:
        return None
    flops, nbytes = summary.module.solve_trsm_counts(summary.config)
    return 100 * roofline.share(flops, nbytes, t / summary.split.steps,
                                summary.peaks, "bf16_flop_per_s")
'''

#: Appended to the throwaway configuration's module.
_COUNTS = '''

def solve_trsm_counts(cfg):
    """Two triangular solves of one right-hand side: n*n flops and the
    triangle's n*n/2 float32 numbers read, each."""
    n = cfg["n"]
    return 2.0 * n * n, 4.0 * n * n
'''


def test_a_configuration_added_by_new_files_alone(tmp_path, scoped):
    """A copy of the benchmark takes a new configuration, traffic, cell and
    reader from new files and new BENCHMARK.json entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    spec = json.loads((root / "BENCHMARK.json").read_text())
    template = spec["configs"][[c["name"] for c in spec["configs"]]
                               .index("gp_rbf")]
    cfg = json.loads((root / template["file"]).read_text())
    cfg["name"] = "gp_copy"
    # limits from CPU readings at n=64, nb=16 (program / `high` control, 8
    # and 3 seeds): α 0.036-0.092 / 1.87-2.17 ε, log det 0.12-0.81 /
    # 76-108 ε
    cpu = {"why": "CPU sizes of the throwaway configuration", "n": 64,
           "nb": 16, "limits": {"alpha_backward_error_eps": 0.4,
                                "logdet_rel_error_eps": 8.0}}
    traffic = json.loads((root / "bench" / "traffic" / "fit.json")
                         .read_text())
    module = (root / template["file"][: -len(".json")]).with_suffix(".py")
    new = {"bench/configs/gp_copy.json": json.dumps(cfg),
           "bench/configs/gp_copy.cpu.json": json.dumps(cpu),
           "bench/configs/gp_copy.py": module.read_text() + _COUNTS,
           "bench/traffic/fit_copy.json": json.dumps(traffic),
           "bench/metrics/copy_trsm_roofline.py": _READER}
    for rel, text in new.items():
        assert not (root / rel).exists()
        (root / rel).write_text(text)
    spec["configs"].append({**template, "name": "gp_copy",
                            "file": "bench/configs/gp_copy.json"})
    spec["workloads"].append({"name": "gp_copy.fit_copy",
                              "config": "gp_copy", "traffic": "fit_copy",
                              "chips": 1, "why": "throwaway"})
    spec["per_layer"].append({
        "name": "copy_trsm_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "Kernels", "moves": "step_s",
        "workloads": ["gp_copy.fit_copy"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    assert all(p.read_bytes() == b for p, b in before.items()
               if p.name != "BENCHMARK.json")

    wl, root = "gp_copy.fit_copy", str(root)
    res = harness.resolve(harness.load_spec(root), wl, root)
    assert res.config_file["n"] == 8192 and res.traffic == traffic
    assert [m["name"] for m, _ in res.per_layer] == ["copy_trsm_roofline"]

    # the reader, on a recorded summary given this configuration's module
    _, _, s = scoped
    (_, read), = res.per_layer
    share = read(dataclasses.replace(s, module=res.config_module))
    t = s.split.kernel_s["bench.solve/repro.trsm"] / 2
    assert share == pytest.approx(
        100 * max(2 * 128 ** 2 / 197e12, 4 * 128 ** 2 / 819e9) / t)
    assert 0 < share <= 105

    over = harness.cpu_overrides(wl, root)
    assert over == {k: v for k, v in cpu.items() if k != "why"}

    def run(**kw):
        return harness.run(wl, 2**33 + 9, 0.2, False,
                           t0=time.perf_counter(), root=root,
                           require_chip=False, overrides=over, **kw)

    assert run()["correct"] is True
    for fault in FAULTS:
        assert run(wrap_step=planted(fault))["correct"] is False, fault

    rows = []
    readings.readings(wl, [13], 1, ["high"], [13], over, require_chip=False,
                      emit=rows.append, root=root)
    limits = over["limits"]
    (prog,) = [r for r in rows if r["who"] == "program"]
    (ctrl,) = [r for r in rows if r["who"] == "control:high"]
    assert all(prog[k] <= lim for k, lim in limits.items())
    assert any(not ctrl[k] <= lim for k, lim in limits.items())
