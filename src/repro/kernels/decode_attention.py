"""Pallas TPU decode-attention kernel: one new token against a KV cache.

Decode is the memory-bound kernel par excellence — arithmetic intensity
~O(1) flop/byte, so it is the TPU analogue of the paper's streaming suite
and a first-class citizen of the bandwidth-sharing analysis (the
``decode_32k`` / ``long_500k`` shapes).

Grid: (batch, kv_heads, kv_blocks); the kv dimension is innermost and
sequential, carrying online-softmax state in VMEM scratch.  All query heads
in a GQA group are processed together as a (group, d) tile — the cache block
is loaded once per group rather than once per head, cutting HBM traffic by
the group factor (this IS the GQA bandwidth win, expressed as a BlockSpec).
Positions beyond ``lengths[b]`` are masked via a scalar-prefetch operand.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
STATS_LANES = 128


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, out_ref,
                   m_ref, l_ref, acc_ref, *, scale: float, bk: int,
                   n_kv_blocks: int):
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    @pl.when(ik * bk < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (group, d)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)               # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (group, bk)
        k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        out_ref[0, 0] = (acc_ref[...] / l).astype(out_ref.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *, scale: float | None = None,
                     block_k: int = 512, interpret: bool
                     ) -> jax.Array:
    """Single-token attention against a KV cache.

    Args:
      q: (B, H, D) — current-step queries.
      k_cache, v_cache: (B, KV, S, D).
      lengths: (B,) int32 — valid cache length per sequence.
    Returns:
      (B, H, D).
    """
    b, h, d = q.shape
    _, kv, s, _ = k_cache.shape
    if h % kv:
        raise ValueError(f"H={h} not a multiple of KV={kv}")
    group = h // kv
    scale = (d ** -0.5) if scale is None else scale
    bk = min(block_k, s)
    if s % bk:
        raise ValueError(f"cache len {s} not divisible by block {bk}")
    n_k = s // bk

    # (B, KV, group, D): all query heads of one kv group contiguous.
    qg = q.reshape(b, kv, group, d)

    # With num_scalar_prefetch=1, every index_map receives the prefetched
    # scalar ref as an extra trailing argument.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kv, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, group, d),
                         lambda ib, ih, ik, lens: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, ik, lens: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, ik, lens: (ib, ih, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, d),
                               lambda ib, ih, ik, lens: (ib, ih, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, STATS_LANES), jnp.float32),
            pltpu.VMEM((group, STATS_LANES), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )

    kernel = functools.partial(_decode_kernel, scale=scale, bk=bk,
                               n_kv_blocks=n_k)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, group, d), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(b, h, d)
