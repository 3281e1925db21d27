"""``chip_smoke.py`` and the rules of the device path, checked on the CPU.

* The smoke refuses to run without a TPU, and without the repository, and
  prints no result line then.
* Its backward-error bound separates f32 work from bf16 work: the same
  solves with inputs and GEMM operands rounded to bf16 (what a TPU's
  default-precision f32 dot does) exceed it.
* On a TPU the Pallas wrappers never interpret, refuse f64 by name, and
  name the kernels that have no TPU lowering yet.
* The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, or to a
  fixed directory in the checkout.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(cwd, env):
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _assert_refused(proc):
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert '"phase"' not in proc.stdout


def test_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run(ROOT, env)
    _assert_refused(proc)
    assert "no TPU" in proc.stderr


def test_smoke_refuses_without_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    _assert_refused(_run(tmp_path, env))


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(x.dtype)


def _bf16_backend():
    from repro.core.backend import Backend, gemm_jnp, trsm_jnp

    return Backend(name="bf16", gemm=lambda a, b: gemm_jnp(_bf16(a),
                                                           _bf16(b)),
                   trsm=trsm_jnp)


@pytest.mark.parametrize("driver", ["gesv", "posv", "gels"])
def test_bound_separates_f32_from_bf16(smoke, driver):
    from repro.solve import drivers

    rng = np.random.default_rng(0)
    n, nrhs, block = 256, 4, 32
    if driver == "gels":
        a = rng.standard_normal((2 * n, n // 4)).astype(np.float32)
    else:
        a = rng.standard_normal((n, n)).astype(np.float32)
        if driver == "posv":
            a = (a @ a.T / n + 0.1 * np.eye(n)).astype(np.float32)
    b = rng.standard_normal((a.shape[0], nrhs)).astype(np.float32)
    error = smoke.lstsq_error if driver == "gels" else smoke.solve_error
    fn = getattr(drivers, driver)

    x32 = fn(jnp.asarray(a), jnp.asarray(b), block)
    assert error(a, x32, b) <= smoke.BOUND
    x16 = fn(_bf16(jnp.asarray(a)), _bf16(jnp.asarray(b)), block,
             backend=_bf16_backend())
    assert error(a, x16, b) > smoke.BOUND


def _on_tpu(monkeypatch):
    from repro.kernels import ops as kops

    monkeypatch.setattr(kops, "_on_tpu", lambda: True)
    return kops


def test_kernels_never_interpret_on_tpu(monkeypatch):
    from repro.kernels import ops as kops

    x = jnp.zeros((8, 8), jnp.float32)
    assert kops._interpret(x)                 # the CPU: interpret mode
    _on_tpu(monkeypatch)
    assert not kops._interpret(x)


def test_f64_on_tpu_raises_by_name(monkeypatch):
    kops = _on_tpu(monkeypatch)
    with pytest.raises(kops.KernelDtypeError, match="float64"):
        kops._interpret(np.zeros((8, 8), np.float64))


@pytest.mark.parametrize("call", [
    lambda k: k.qr_panel(jnp.zeros((16, 8), jnp.float32)),
    lambda k: k.qrcp_panel(jnp.zeros((16, 16), jnp.float32), 8),
    lambda k: k.hessenberg_panel(jnp.zeros((16, 16), jnp.float32), 0, 8),
], ids=["qr", "qrcp", "hessenberg"])
def test_unlowered_kernels_raise_on_tpu(monkeypatch, call):
    kops = _on_tpu(monkeypatch)
    with pytest.raises(kops.KernelNotLoweredError):
        call(kops)


def _recording_jax(monkeypatch):
    from repro import compile_cache

    calls = []
    fake = types.SimpleNamespace(config=types.SimpleNamespace(
        update=lambda key, value: calls.append((key, value))))
    monkeypatch.setattr(compile_cache, "jax", fake)
    return compile_cache, calls


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    compile_cache, calls = _recording_jax(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []                        # JAX reads the variable itself


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    compile_cache, calls = _recording_jax(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_smoke_result_line_shape(smoke, capsys):
    smoke._report("x", first_s=1.0)
    line = capsys.readouterr().out.strip()
    assert json.loads(line) == {"phase": "x", "first_s": 1.0}
