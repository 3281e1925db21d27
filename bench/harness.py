"""The benchmark's one harness: resolve a cell by name, set it up, time it,
trace it, check it, and build the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by its name in ``BENCHMARK.json``:

* ``bench/configs/<config>.json`` — the configuration's sizes, its check
  limits and what was cut; ``bench/configs/<config>.py`` beside it — a
  ``Cell`` class: ``prepare(key)`` (per-run data, made on the device),
  ``inputs(key, i, data)`` (step i's operands, drawn on the device),
  ``step(data, ops)`` (the timed program, with its ``bench.*`` scopes),
  ``control(data, ops, precision)`` (the plain reference in the program's
  place), ``check(data, ops, out)`` (the numbers compared with
  ``limits``) and ``plant(kind, data, ops, out)`` (``out`` with a fault
  planted, ``"state_unchanged"`` or ``"answer_altered"``, for the CPU
  tests); ``bench/configs/<config>.cpu.json`` — the sizes and limits the
  CPU tests and rehearsals run at (:func:`cpu_overrides`), never read by a
  benchmark run;
* ``bench/traffic/<traffic>.json`` — the mix's parameters, read by this
  module's closed loop and by the cell;
* ``bench/metrics/<metric>.py`` — ``read(summary)`` for one per-layer
  metric, returning a number or None.  The summary
  (:class:`bench.trace.Summary`) carries the trace's numbers, the phase
  and kernel split (:mod:`bench.phases`), the configuration as run, the
  traffic, the device's peaks and the configuration's module; a roofline
  share comes from :func:`bench.roofline.share`, with the kernel's
  operations and bytes counted by a function of that module.
"""
from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Callable, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

#: Where the traced run writes its profile, inside the checkout; removed
#: once it has been read.
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
#: Seconds the traced run waits after starting the profiler and before
#: stopping it, outside the window, so that no program run of the window
#: falls outside the device tracer's time: one traced gp_rbf.fit run of
#: three lost one of its five step runs without them (PERF.md §3).
TRACE_SETTLE_S = 0.5


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# Resolving a cell by name.
# ---------------------------------------------------------------------------
def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Resolved:
    workload: dict
    config: dict           # the BENCHMARK.json entry
    config_file: dict      # bench/configs/<config>.json
    config_module: object  # bench/configs/<config>.py
    traffic: dict          # bench/traffic/<traffic>.json
    end_to_end: list[dict]
    per_layer: list[tuple[dict, Callable]]  # (entry, read)


def _reports(metric: dict, workload: str, end_to_end_names: set) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves", metric["name"]) in end_to_end_names


def resolve(spec: dict, workload: str, root: str = ROOT) -> Resolved:
    """Find every file a cell needs, by the names in ``spec``."""
    wl = {w["name"]: w for w in spec["workloads"]}
    if workload not in wl:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(wl)})")
    w = wl[workload]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cfg_path = os.path.join(root, cfg["file"])
    with open(cfg_path) as f:
        cfg_file = json.load(f)
    mod = _module(cfg_path[: -len(".json")] + ".py",
                  f"bench_config_{cfg['name']}")
    with open(os.path.join(root, "bench", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = []
    for m in spec["per_layer"]:
        if _reports(m, workload, names):
            reader = _module(os.path.join(root, "bench", "metrics",
                                          m["name"] + ".py"),
                             f"bench_metric_{m['name']}")
            per_layer.append((m, reader.read))
    return Resolved(w, cfg, cfg_file, mod, traffic, e2e, per_layer)


def cpu_overrides(workload: str, root: str = ROOT) -> dict:
    """The overrides of ``bench/configs/<config>.cpu.json`` (its ``why``
    left out): the sizes and limits the CPU tests and rehearsals use."""
    res = resolve(load_spec(root), workload, root)
    path = os.path.join(root, res.config["file"])[: -len(".json")]
    with open(path + ".cpu.json") as f:
        over = json.load(f)
    over.pop("why", None)
    return over


# ---------------------------------------------------------------------------
# Device, peaks, seeds.
# ---------------------------------------------------------------------------
def peaks(device_kind: str, root: str = ROOT) -> dict:
    """The published peaks of ``device_kind``; an unknown device raises."""
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]


def devices(chips: int, require_chip: bool = True):
    """The first ``chips`` devices; :class:`NoChip` where they are not
    accelerators or too few."""
    import jax

    devs = jax.devices()
    if require_chip:
        if devs[0].platform == "cpu":
            raise NoChip("JAX found no accelerator (platform 'cpu')")
        if len(devs) < chips:
            raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                         f"{len(devs)}")
        peaks(devs[0].device_kind)
    return devs[:chips]


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 64 bits of it."""
    import jax

    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def check_sample(traffic: dict, steps: int, seed: int) -> list[int]:
    """Window steps (1..steps) whose answers are compared: all, or a
    sample of ``traffic["check"]`` drawn from the seed."""
    every = list(range(1, steps + 1))
    k = traffic.get("check", "all")
    if k == "all" or k >= steps:
        return every
    rng = np.random.default_rng(seed)
    return sorted(int(i) for i in rng.choice(every, size=k, replace=False))


# ---------------------------------------------------------------------------
# One run.
# ---------------------------------------------------------------------------
class _CompileCounter:
    """Counts backend compiles, so a compile inside the window shows."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


_COUNTER: Optional[_CompileCounter] = None


def _compiles() -> _CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = _CompileCounter()
    return _COUNTER


def build(workload: str, overrides: Optional[dict] = None,
          require_chip: bool = True, root: str = ROOT):
    """``(resolved, devices, config, cell)`` for one workload; ``config``
    is the configuration as run.

    ``overrides`` replaces keys of the configuration file (sizes, limits);
    the CPU tests and rehearsals use it, a benchmark run never does.
    """
    res = resolve(load_spec(root), workload, root)
    devs = devices(res.workload["chips"], require_chip)
    cfg = {**res.config_file, **(overrides or {})}
    return res, devs, cfg, res.config_module.Cell(cfg, res.traffic)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t0: float, root: str = ROOT, require_chip: bool = True,
        overrides: Optional[dict] = None,
        wrap_step: Optional[Callable] = None,
        log=sys.stderr) -> dict:
    """One run of one cell; the result line.  See ``bench/run.py``.

    ``overrides`` (see :func:`build`) and ``wrap_step`` (a function of the
    cell returning the step to time) exist for the CPU tests, which run the
    harness at tiny sizes and with faults planted.
    """
    import jax

    res, devs, cfg, cell = build(workload, overrides, require_chip, root)
    key = seed_key(seed)
    counter = _compiles()

    data = cell.prepare(key)
    inputs = jax.jit(cell.inputs).lower(key, np.int32(0), data).compile()
    step_fn = cell.step if wrap_step is None else wrap_step(cell)
    ops = inputs(key, np.int32(0), data)
    compiled = jax.jit(step_fn).lower(data, ops).compile()
    jax.block_until_ready(compiled(data, ops))           # warm-up step
    del ops
    setup_s = time.perf_counter() - t0

    # A traced window stops after the traffic's ``trace_steps``: the
    # profiler drops events past a few million, and a step of the solve
    # engine runs some 10^5 device ops.
    max_steps = res.traffic["trace_steps"] if trace else None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
        time.sleep(TRACE_SETTLE_S)
    compiles_before = counter.n
    outs = []
    start = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        i = 0
        while True:
            i += 1
            with jax.profiler.TraceAnnotation("bench.inputs"):
                ops = inputs(key, np.int32(i), data)
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                out = jax.block_until_ready(compiled(data, ops))
            outs.append(out)
            if (time.perf_counter() - start >= seconds
                    or len(outs) == max_steps):
                break
    window_s = time.perf_counter() - start
    if trace:
        time.sleep(TRACE_SETTLE_S)
        jax.profiler.stop_trace()
    del ops
    compiles_in_window = counter.n - compiles_before
    steps = len(outs)

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs) or None
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}

    metrics, breakdown = {}, None
    if trace:
        summary = _summarize([inputs.as_text(), compiled.as_text()], steps,
                             log, config=cfg, traffic=res.traffic,
                             peaks=peaks(device["kind"], root),
                             module=res.config_module)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        for m, read in res.per_layer:
            v = read(summary)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": [[k, v] for k, v in summary.top_ops],
                     "idle_gaps": [[k, v] for k, v in summary.idle_gaps]}
    else:
        e2e = {"step_s": window_s / steps, "setup_s": setup_s}
        for m in res.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    # The check runs once the window has closed and the peak is read.
    with jax.profiler.TraceAnnotation("bench.check"):
        worst, failed, checked = _check(cell, key, data, inputs, outs,
                                        check_sample(res.traffic, steps,
                                                     seed))
    if compiles_in_window:
        print(f"bench: {compiles_in_window} compile(s) inside the window",
              file=log)
    line = {"correct": bool(worst) and failed == 0
            and compiles_in_window == 0,
            "attempted": steps, "failed": failed, "metrics": metrics,
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compiles_in_window"] = compiles_in_window
    line["checked"] = len(checked)
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, v, lim in worst}
    return line


def _check(cell, key, data, inputs, outs, sample):
    """Compare the sampled steps' outputs; (worst per number, failed, ids)."""
    import jax

    worst: dict[str, float] = {}
    failed = 0
    for i in sample:
        ops = inputs(key, np.int32(i), data)
        got = cell.check(data, ops, jax.device_get(outs[i - 1]))
        del ops
        bad = False
        for name, value in got.items():
            lim = cell.limits[name]
            if not (np.isfinite(value) and value <= lim):
                bad = True
            prev = worst.get(name)
            if prev is None or not np.isfinite(value) or value > prev:
                worst[name] = float(value)
        failed += bad
    return ([(k, v, cell.limits[k]) for k, v in worst.items()], failed,
            sample)


def _summarize(hlo_texts: list[str], steps: int, log, **context):
    files = glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file under {TRACE_DIR}, "
                           f"found {len(files)}")
    try:
        return summarize_file(files[0], hlo_texts, steps, log=log, **context)
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)


def summarize_file(source, hlo_texts: list[str], steps: int, *,
                   log=sys.stderr, **context):
    """The :class:`bench.trace.Summary` the readers get, from one
    ``.xplane.pb`` (a path or the file's bytes) of a traced window of
    ``steps`` steps: the trace's numbers, its phase and kernel split, and
    the run's ``context`` (``config``, ``traffic``, ``peaks``, ``module``)
    as given.  ``hlo_texts`` are the compiled programs, the step program
    last."""
    from bench import phases
    from bench import trace as tr

    programs = {tr.module_name(t): t for t in hlo_texts}
    step_module = tr.module_name(hlo_texts[-1])
    ops, spans, runs = tr.read_xplane(source, programs)
    windows = [s for s in spans if s.name == "bench.window"]
    if len(windows) != 1:
        raise RuntimeError(f"expected one bench.window span, found "
                           f"{len(windows)}")
    summary = tr.summarize(ops, spans, window=windows[0], steps=steps,
                           module_runs=runs, step_module=step_module)
    if summary.busy_s <= 0:
        raise RuntimeError("the trace holds no device op inside the window")
    if summary.step_runs != steps:
        w = windows[0]
        seen = [(r.name, (r.start_ns - w.start_ns) * 1e-9,
                 (r.end_ns - r.start_ns) * 1e-9) for r in runs]
        raise RuntimeError(f"the trace holds {summary.step_runs} runs of the "
                           f"step program, the window ran {steps}: events "
                           f"were dropped ({len(ops)} ops; runs as (module, "
                           f"start s from the window's, s): {seen})")
    print(f"bench: trace {len(ops)} device ops; per-scope device s "
          f"{json.dumps(summary.scope_s)}; charged by the neighbour rule "
          f"{summary.inherited_s!r} s", file=log)
    t = time.perf_counter()
    split = phases.read(source, programs, step_module)
    print(f"bench: phase split {json.dumps(split.per_step())} s a step; "
          f"read in {time.perf_counter() - t!r} s", file=log)
    return dataclasses.replace(summary, split=split, **context)


def print_line(line: dict, log=sys.stderr) -> None:
    """The result line last on stdout; each number compared, beside its
    limit, last on stderr."""
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=log)
    log.flush()
    print(json.dumps(line), flush=True)
