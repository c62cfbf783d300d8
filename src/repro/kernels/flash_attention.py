"""Pallas TPU flash-attention (prefill) kernel with GQA support.

TPU-native tiling: the grid is (batch*heads, q_blocks, kv_blocks) with the
kv dimension innermost — TPU grids execute sequentially, so the online-
softmax state (row max ``m``, row sum ``l``, accumulator ``acc``) lives in
VMEM scratch and carries across kv steps.  Causal blocks strictly above the
diagonal are skipped with ``pl.when`` (no data is even DMA'd for them when
the compiler can prove it).  Block shapes are MXU-aligned (multiples of 128
on the contraction and lane axes).

The kernel computes one (1, bq, d) output tile per (bh, iq) pair; GQA maps
query head h to kv head h // (H // KV) inside the BlockSpec index maps, so
no KV replication ever materializes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
STATS_LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, bq: int, bk: int,
                  n_kv_blocks: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _body():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

        if causal:
            q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)

        m_prev = m_ref[:, :1]                             # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                            # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                   # (bq, 1)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # Skip blocks entirely above the diagonal.
        pl.when(ik * bk <= iq * bq + bq - 1)(_body)
    else:
        _body()

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        out_ref[0] = (acc_ref[...] / l).astype(out_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool) -> jax.Array:
    """Flash attention.

    Args:
      q: (B, H, S, D) queries.
      k, v: (B, KV, S, D) keys/values; H must be a multiple of KV (GQA).
    Returns:
      (B, H, S, D) attention output.
    """
    b, h, s, d = q.shape
    _, kv, sk, _ = k.shape
    if h % kv:
        raise ValueError(f"H={h} not a multiple of KV={kv}")
    group = h // kv
    scale = (d ** -0.5) if scale is None else scale
    bq = min(block_q, s)
    bk = min(block_k, sk)
    if s % bq or sk % bk:
        raise ValueError(f"seq lengths ({s},{sk}) not divisible by blocks "
                         f"({bq},{bk})")
    n_q, n_k = s // bq, sk // bk

    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * kv, sk, d)
    vf = v.reshape(b * kv, sk, d)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
        n_kv_blocks=n_k)

    def kv_index(bh, iq, ik):
        return ((bh // h) * kv + (bh % h) // group, ik, 0)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, STATS_LANES), jnp.float32),
            pltpu.VMEM((bq, STATS_LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s, d)
