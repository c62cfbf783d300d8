"""Pallas interpret-mode vs pure-jnp oracle: Table II streaming suite."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops

jax.config.update("jax_enable_x64", False)

MAP_CASES = {
    "dscal": 1, "daxpy": 2, "add": 2, "stream": 2, "waxpby": 2,
    "dcopy": 1, "schoenauer": 3,
}
REDUCE_CASES = {"vectorsum": 1, "ddot1": 1, "ddot2": 2, "ddot3": 3}


def _arrays(n_arrays, n, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal(n), dtype) for _ in range(n_arrays)]


def _scalar(name):
    if name == "waxpby":
        return jnp.asarray([1.7, -0.3], jnp.float32)
    return jnp.asarray(0.7, jnp.float32)


@pytest.mark.parametrize("name,n_in", sorted(MAP_CASES.items()))
# 128 * 2725: several VMEM-sized blocks, the last one overhanging.
@pytest.mark.parametrize("n", [128, 1024, 128 * 300, 128 * 2725])
def test_map_kernels_match_ref(name, n_in, n):
    arrays = _arrays(n_in, n, jnp.float32)
    s = _scalar(name)
    got = ops.stream_map(name, s, *arrays, impl="interpret")
    want = ops.stream_map(name, s, *arrays, impl="jnp")
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("name,n_in", sorted(REDUCE_CASES.items()))
@pytest.mark.parametrize("n", [128, 2048, 128 * 300, 128 * 2725])
def test_reduce_kernels_match_ref(name, n_in, n):
    arrays = _arrays(n_in, n, jnp.float32, seed=1)
    got = ops.stream_reduce(name, *arrays, impl="interpret")
    want = ops.stream_reduce(name, *arrays, impl="jnp")
    np.testing.assert_allclose(got, want, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_map_dtypes(dtype):
    arrays = _arrays(2, 512, dtype)
    got = ops.stream_map("stream", jnp.asarray(0.5, dtype), *arrays,
                         impl="interpret")
    want = ops.stream_map("stream", jnp.asarray(0.5, dtype), *arrays,
                          impl="jnp")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-6)


@given(rows=st.integers(min_value=1, max_value=64),
       block=st.sampled_from([1, 2, 4, 8]),
       name=st.sampled_from(sorted(MAP_CASES)))
@settings(max_examples=25, deadline=None)
def test_map_shape_sweep(rows, block, name):
    if rows % block:
        rows = block * max(1, rows // block)
    n = rows * 128
    arrays = _arrays(MAP_CASES[name], n, jnp.float32, seed=rows)
    s = _scalar(name)
    got = ops.stream_map(name, s, *arrays, impl="interpret")
    want = ops.stream_map(name, s, *arrays, impl="jnp")
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_non_multiple_of_lanes_raises():
    with pytest.raises(ValueError, match="multiple"):
        from repro.kernels.stream import map_stream
        map_stream("dcopy", jnp.asarray(0.0), jnp.ones(100),
                   interpret=True)
