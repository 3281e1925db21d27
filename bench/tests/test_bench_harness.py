"""CPU tests of the benchmark harness: names resolve, the contract's
shape holds, runs at tiny sizes are correct, the controls and planted
faults are not, and a run without a chip prints no result."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness, readings

ROOT = harness.ROOT
SPEC = harness.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
#: The kinds of fault each configuration's ``Cell.plant`` plants.
FAULTS = ["state_unchanged", "answer_altered"]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _run(cell: str, seed: int = 2**33 + 7, **kw) -> dict:
    """One untraced run at the configuration's CPU sizes
    (``bench/configs/<config>.cpu.json``)."""
    return harness.run(cell, seed, 0.2, False, t0=time.perf_counter(),
                       require_chip=False,
                       overrides=harness.cpu_overrides(cell), **kw)


def test_spec_has_the_contract_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    named = (SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"]
             + SPEC["per_layer"])
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    res = harness.resolve(SPEC, cell)
    assert res.config_file["name"] == res.config["name"]
    for key in res.config["reduced"]:
        assert key in res.config_file["reduced"]
    assert hasattr(res.config_module, "Cell")
    assert {m["name"] for m in res.end_to_end} >= {"setup_s", "step_s"}
    assert res.per_layer, "every cell reports a per-layer metric"
    for path in (res.config["file"], f"bench/traffic/{res.workload['traffic']}.json"):
        assert os.path.exists(os.path.join(ROOT, path))
    # the CPU sizes: a file of their own beside the configuration's, which
    # changes only keys the configuration has and names its every limit
    assert os.path.exists(os.path.join(
        ROOT, res.config["file"][: -len(".json")] + ".cpu.json"))
    over = harness.cpu_overrides(cell)
    assert set(over) <= set(res.config_file) - {"name", "reduced"}
    assert set(over.get("limits", res.config_file["limits"])) \
        == set(res.config_file["limits"])


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_run_is_correct(cell):
    line = _run(cell)
    assert line["correct"] is True, line
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"step_s", "setup_s"}
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == set(
        harness.resolve(SPEC, cell).config_file["limits"])
    assert all(c["limit"] == harness.cpu_overrides(cell)["limits"][name]
               for name, c in line["checks"].items())


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("precision", ["high", "bf16"])
def test_control_fails_the_check(cell, precision):
    """The plain reference in the program's place, at the precision below
    the configuration's (``high``) and at bf16, fails a limit."""
    over = harness.cpu_overrides(cell)
    rows = []
    readings.readings(cell, [11], 1, [precision], [11], over,
                      require_chip=False, emit=rows.append)
    limits = over["limits"]
    prog = [r for r in rows if r["who"] == "program"]
    ctrl = [r for r in rows if r["who"] != "program"]
    assert all(r[k] <= lim for r in prog for k, lim in limits.items())
    assert all(any(not r[k] <= lim for k, lim in limits.items())
               for r in ctrl), ctrl


@pytest.mark.parametrize("cell", CELLS)
def test_program_on_bf16_operands_fails_the_check(cell):
    def wrap(c):
        def bf16_step(data, ops):
            out = c.step(data, jax.tree.map(
                lambda a: a.astype(jnp.bfloat16), ops))
            return jax.tree.map(lambda a: a.astype(jnp.float32), out)
        return bf16_step

    assert _run(cell, wrap_step=wrap)["correct"] is False


def planted(fault: str):
    """``wrap_step`` for :func:`harness.run`: the cell's step with the
    cell's own ``plant(fault, ...)`` applied to its output."""
    def wrap(c):
        def faulty_step(data, ops):
            return c.plant(fault, data, ops, c.step(data, ops))
        return faulty_step
    return wrap


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_fails_the_check(cell, fault):
    assert _run(cell, wrap_step=planted(fault))["correct"] is False


def test_run_without_a_chip_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "5", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_peaks_are_keyed_by_device_kind_and_unknown_raises():
    v5e = harness.peaks("TPU v5 lite")
    assert v5e["bf16_flop_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("TPU v99")


def test_seed_key_takes_seeds_past_32_bits():
    keys = [harness.seed_key(s) for s in (5, 2**32 + 5, 2**31 + 5)]
    data = [jax.random.key_data(k).tolist() for k in keys]
    assert len({str(d) for d in data}) == 3
    assert str(jax.random.key_data(harness.seed_key(2**32 + 5)).tolist()) == str(data[1])
