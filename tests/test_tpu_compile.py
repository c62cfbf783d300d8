"""Compile the main path for a described TPU v5e chip, at real sizes.

Nothing runs: the TPU compiler refuses here what the chip would refuse
(misaligned blocks, VMEM overruns, scalar stores Mosaic cannot lower),
at the sizes ``chip_smoke.py`` runs.  Every Pallas kernel must lower to
a ``tpu_custom_call``; the float64 solvers must compile under
``backend.x64()``.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import backend, desync_batch, sharing
from repro.kernels import ops

N = 1 << 26          # 256 MiB per f32 stream
WIDTH = 8192         # Jacobi grid edge: 256 MiB per f32 grid
SOLVER_B = 1 << 16   # scenarios per sweep solve
DESYNC = (256, 64, 64, 4, 8)   # (B, R, L, K, D) of the desync runner


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_hlo(topo):
    """``compile_hlo(fn, *shapes)`` -> the compiled HLO text of ``jax.jit(
    fn)`` for one v5e chip, with the persistent compile cache off (an
    entry compiled for a described chip cannot be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache
    one_chip = SingleDeviceSharding(topo.devices[0])
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_hlo(fn, *shapes):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_hlo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


F32 = jnp.float32


@pytest.mark.parametrize("name,n_in", [("dcopy", 1), ("stream", 2),
                                       ("schoenauer", 3)])
def test_map_stream_lowers_to_kernel(compile_hlo, name, n_in):
    hlo = compile_hlo(
        lambda s, *a: ops.stream_map(name, s, *a, impl="pallas"),
        ((), F32), *[((N,), F32)] * n_in)
    assert "tpu_custom_call" in hlo


def test_reduce_stream_lowers_to_kernel(compile_hlo):
    hlo = compile_hlo(lambda a, b: ops.stream_reduce("ddot2", a, b,
                                                     impl="pallas"),
                      ((N,), F32), ((N,), F32))
    assert "tpu_custom_call" in hlo


def test_jacobi_v1_lowers_to_kernel(compile_hlo):
    hlo = compile_hlo(lambda a: ops.jacobi_v1(a, 0.25, impl="pallas"),
                      ((WIDTH, WIDTH), F32))
    assert "tpu_custom_call" in hlo


def test_jacobi_v2_lowers_to_kernel(compile_hlo):
    hlo = compile_hlo(
        lambda a, f: ops.jacobi_v2(a, f, ax=0.4, ay=0.6, b1=2.0, relax=0.9,
                                   impl="pallas"),
        ((WIDTH, WIDTH), F32), ((WIDTH, WIDTH), F32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("mode,n_max", [("recursion", 16), ("queue", 0)])
def test_f64_sharing_solver_compiles(compile_hlo, mode, n_max):
    G = 4
    solver = sharing._build_jax_solver(mode, n_max, (SOLVER_B, G))
    flat = ((SOLVER_B * G,), jnp.float64)
    with backend.x64():
        hlo = compile_hlo(solver, flat, flat, flat, ((), jnp.float64))
    assert "f64" in hlo


def test_f64_sharing_solver_compiles_with_flat_inputs(compile_hlo):
    # Three groups a row, as the placed sweeps lay out their groups.  The
    # program solves them as three planes of full (., 128) tiles: no
    # array of the compiled program has a minor dimension of three.
    rows, G = SOLVER_B, 3
    solver = sharing._build_jax_solver("recursion", 16, (rows, G))
    flat = ((rows * G,), jnp.float64)
    with backend.x64():
        hlo = compile_hlo(solver, flat, flat, flat, ((), jnp.float64))
    assert "f64" in hlo
    assert f"[3,{rows // 128},128]" in hlo
    assert not re.search(r"\[\d+,3\]", hlo)


def test_f64_desync_runner_compiles(compile_hlo):
    B, R, L, K, D = DESYNC
    runner = desync_batch._build_jax_runner(B, R, L, K, D)
    i32, f64 = jnp.int32, jnp.float64
    with backend.x64():
        hlo = compile_hlo(runner, ((B, R, L), i32), ((B, R, L), f64),
                          ((B, R, L), i32), ((B, R), i32), ((R,), i32),
                          ((K,), f64), ((K,), f64), ((), f64),
                          ((), jnp.int64))
    assert "while" in hlo
