"""Compile the main path for a described TPU v5e, without a chip.

The TPU compiler is installed with JAX, and it compiles for a topology that
is described rather than attached.  These tests compile the Pallas kernels
of the ``backend="pallas"`` LU path at real widths, a jitted ``gesv``, and
the mesh engine's steps on four chips, so a kernel or step the chip's
compiler would refuse fails here.  Nothing runs: results and times need the
chip (``chip_smoke.py``).

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")    # else logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def chip_config():
    """Compile as the chip's programs run: with 32-bit types (other test
    modules turn ``jax_enable_x64`` on for the whole process) and without
    the persistent cache, which a compile for a described chip is written
    to but cannot be read back from."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = (jax.config.jax_enable_x64, jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_x64", was[0])
    jax.config.update("jax_enable_compilation_cache", was[1])
    cc.reset_cache()


def _spec(sharding, *shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()      # a Mosaic kernel
    return compiled


def test_blis_gemm_4096(one_chip):
    from repro.kernels.blis_gemm import blis_gemm

    s = _spec(one_chip, 4096, 4096)
    _compile(blis_gemm, s, s)


def test_lu_panel_4096x256(one_chip):
    from repro.kernels.panel_lu import lu_panel

    _compile(lu_panel, _spec(one_chip, 4096, 256))


def test_trsm_left_lower(one_chip):
    from repro.kernels.trsm import trsm_left_lower

    _compile(trsm_left_lower, _spec(one_chip, 256, 256),
             _spec(one_chip, 256, 4096))


def test_lu_solve_small(one_chip):
    from repro.kernels.trsm import lu_solve_small

    _compile(lu_solve_small, _spec(one_chip, 1024, 1024),
             _spec(one_chip, 1024, 8))


def test_fused_lu_panel_update(one_chip):
    from repro.kernels.fused_panel_update import fused_lu_panel_update

    _compile(fused_lu_panel_update, _spec(one_chip, 256, 256),
             _spec(one_chip, 4096, 256), _spec(one_chip, 256, 256),
             _spec(one_chip, 4096, 256))


def test_fused_cholesky_panel_update(one_chip):
    from repro.kernels.fused_panel_update import fused_cholesky_panel_update

    _compile(fused_cholesky_panel_update, _spec(one_chip, 256, 256),
             _spec(one_chip, 4096, 256), _spec(one_chip, 4096, 256))


def test_jitted_gesv_1024(one_chip):
    """The ``backend="jnp"`` solve as the chip traces it."""
    from repro.solve import gesv

    compiled = jax.jit(functools.partial(gesv, block=256)).lower(
        _spec(one_chip, 1024, 1024), _spec(one_chip, 1024, 8)).compile()
    assert compiled.memory_analysis() is not None


def test_mesh_steps_four_chips(topo):
    """The ``chip_smoke.py --chips 4`` mesh steps on four described chips:
    the panel broadcast is an all-gather, the update a local program."""
    from repro.core import backend, distributed as dist

    mesh = Mesh(np.asarray(topo.devices[:4]), ("model",))
    n, b = 2048, 256
    nd = 4
    local = _spec(NamedSharding(mesh, P("model", None, None)),
                  nd, n, n // nd)
    replicated = NamedSharding(mesh, P())
    bcast = dist._bcast_step(mesh, "model", 1, 2, b)
    text = bcast.lower(local).compile().as_text()
    assert "all-gather" in text
    update = dist._lu_update_step(mesh, "model", nd, b, 0, b, "gt", 0,
                                  backend.JNP_BACKEND)
    update.lower(local, _spec(replicated, n, b)).compile()
    chol = dist._chol_update_step(mesh, "model", nd, b, 0, b, b, "gt", 0,
                                  backend.JNP_BACKEND)
    chol.lower(local, _spec(replicated, n, b),
               _spec(replicated, n, b)).compile()
