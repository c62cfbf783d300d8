"""Pallas interpret-mode vs oracle: flash attention, decode attention,
jacobi stencils, rmsnorm."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops


def _qkv(b, h, kv, s, d, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype) * 0.3
    k = jnp.asarray(rng.standard_normal((b, kv, s, d)), dtype) * 0.3
    v = jnp.asarray(rng.standard_normal((b, kv, s, d)), dtype) * 0.3
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("b,h,kv,s,d", [
    (1, 2, 2, 128, 64),      # MHA
    (2, 4, 2, 256, 64),      # GQA group 2
    (1, 8, 1, 128, 128),     # MQA
])
def test_flash_attention_matches_ref(b, h, kv, s, d, causal):
    q, k, v = _qkv(b, h, kv, s, d)
    got = ops.attention(q, k, v, causal=causal, impl="interpret",
                        block_q=64, block_k=64)
    want = ops.attention(q, k, v, causal=causal, impl="jnp")
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@given(bq=st.sampled_from([32, 64, 128]), bk=st.sampled_from([32, 64, 128]),
       s=st.sampled_from([128, 256]))
@settings(max_examples=10, deadline=None)
def test_flash_attention_block_sweep(bq, bk, s):
    q, k, v = _qkv(1, 2, 1, s, 64, seed=s + bq)
    got = ops.attention(q, k, v, causal=True, impl="interpret",
                        block_q=bq, block_k=bk)
    want = ops.attention(q, k, v, causal=True, impl="jnp")
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_flash_attention_bf16():
    q, k, v = _qkv(1, 2, 2, 128, 64, dtype=jnp.bfloat16)
    got = ops.attention(q, k, v, causal=True, impl="interpret",
                        block_q=64, block_k=64)
    want = ops.attention(q, k, v, causal=True, impl="jnp")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("b,h,kv,s,d,blk", [
    (2, 4, 2, 512, 64, 128),
    (1, 8, 8, 256, 64, 256),
    (3, 4, 1, 1024, 128, 512),
])
def test_decode_attention_matches_ref(b, h, kv, s, d, blk):
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32) * 0.3
    kc = jnp.asarray(rng.standard_normal((b, kv, s, d)), jnp.float32) * 0.3
    vc = jnp.asarray(rng.standard_normal((b, kv, s, d)), jnp.float32) * 0.3
    lengths = jnp.asarray(rng.integers(1, s + 1, size=b), jnp.int32)
    got = ops.decode_attention(q, kc, vc, lengths, impl="interpret",
                               block_k=blk)
    want = ops.decode_attention(q, kc, vc, lengths, impl="jnp")
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_decode_attention_length_masking():
    """Entries beyond lengths[b] must not affect the result."""
    b, h, kv, s, d = 2, 4, 2, 256, 64
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((b, kv, s, d)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((b, kv, s, d)), jnp.float32)
    lengths = jnp.asarray([100, 17], jnp.int32)
    base = ops.decode_attention(q, kc, vc, lengths, impl="interpret",
                                block_k=128)
    kc2 = kc.at[:, :, 200:].set(1e4)
    vc2 = vc.at[:, :, 200:].set(-1e4)
    poisoned = ops.decode_attention(q, kc2, vc2, lengths, impl="interpret",
                                    block_k=128)
    np.testing.assert_allclose(base, poisoned, rtol=1e-6)


# --------------------------------------------------------------------------
# Jacobi
# --------------------------------------------------------------------------


# (53, 4096): several VMEM-sized row blocks, the last one overhanging.
@pytest.mark.parametrize("h,w", [(18, 128), (66, 256), (130, 384),
                                 (53, 4096)])
def test_jacobi_v1_matches_ref(h, w):
    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.standard_normal((h, w)), jnp.float32)
    got = ops.jacobi_v1(a, 0.25, impl="interpret")
    want = ops.jacobi_v1(a, 0.25, impl="jnp")
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("h,w", [(18, 128), (34, 256), (53, 4096)])
def test_jacobi_v2_matches_ref(h, w):
    rng = np.random.default_rng(11)
    a = jnp.asarray(rng.standard_normal((h, w)), jnp.float32)
    f = jnp.asarray(rng.standard_normal((h, w)), jnp.float32)
    kw = dict(ax=0.4, ay=0.6, b1=2.0, relax=0.9)
    got_b, got_r = ops.jacobi_v2(a, f, impl="interpret", **kw)
    want_b, want_r = ops.jacobi_v2(a, f, impl="jnp", **kw)
    np.testing.assert_allclose(got_b, want_b, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_r, want_r, rtol=1e-4)


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------


@pytest.mark.parametrize("shape,hidden", [((4, 64), 512), ((2, 16), 1024),
                                          ((128,), 896)])
def test_rmsnorm_matches_ref(shape, hidden):
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.standard_normal((*shape, hidden)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(hidden), jnp.float32)
    got = ops.rmsnorm(x, w, impl="interpret")
    want = ops.rmsnorm(x, w, impl="jnp")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_rmsnorm_residual_matches_ref():
    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.standard_normal((8, 32, 896)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((8, 32, 896)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(896), jnp.float32)
    got_y, got_h = ops.rmsnorm_residual(x, r, w, impl="interpret")
    want_y, want_h = ops.rmsnorm_residual(x, r, w, impl="jnp")
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_h, want_h, rtol=1e-6)
