"""Fused RMSNorm (+ optional residual add) Pallas TPU kernel.

Unfused, RMSNorm is three HBM round-trips (read x, read x for the reduce,
write y); fused it is one read + one write — a pure bandwidth optimization,
i.e. exactly the kind of ``f``-reducing transform the paper's model values.
Rows are tiled into VMEM as (block_rows, hidden) tiles; hidden stays whole
per tile so the row reduction needs no cross-block state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 64


def _rmsnorm_kernel(x_ref, w_ref, out_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps) * w_ref[...].astype(jnp.float32)
    out_ref[...] = y.astype(out_ref.dtype)


def _rmsnorm_res_kernel(x_ref, res_ref, w_ref, out_ref, newres_ref, *,
                        eps: float):
    h = x_ref[...].astype(jnp.float32) + res_ref[...].astype(jnp.float32)
    newres_ref[...] = h.astype(newres_ref.dtype)
    ms = jnp.mean(h * h, axis=-1, keepdims=True)
    y = h * jax.lax.rsqrt(ms + eps) * w_ref[...].astype(jnp.float32)
    out_ref[...] = y.astype(out_ref.dtype)


def _blocks(rows: int, block_rows: int) -> tuple[int, int]:
    block_rows = min(block_rows, rows)
    while rows % block_rows:
        block_rows -= 1
    return rows // block_rows, block_rows


def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
            block_rows: int = DEFAULT_BLOCK_ROWS,
            interpret: bool) -> jax.Array:
    """y = x / rms(x) * w over the last axis.  x: (..., hidden)."""
    shape = x.shape
    hidden = shape[-1]
    rows = x.size // hidden
    xf = x.reshape(rows, hidden)
    nblk, br = _blocks(rows, block_rows)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((br, hidden), lambda i: (i, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, hidden), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, hidden), x.dtype),
        interpret=interpret,
    )(xf, w.reshape(1, hidden))
    return out.reshape(shape)


def rmsnorm_residual(x: jax.Array, residual: jax.Array, w: jax.Array, *,
                     eps: float = 1e-6, block_rows: int = DEFAULT_BLOCK_ROWS,
                     interpret: bool) -> tuple[jax.Array, jax.Array]:
    """Fused h = x + residual; y = rmsnorm(h) * w.  Returns (y, h)."""
    shape = x.shape
    hidden = shape[-1]
    rows = x.size // hidden
    xf = x.reshape(rows, hidden)
    rf = residual.reshape(rows, hidden)
    nblk, br = _blocks(rows, block_rows)
    y, h = pl.pallas_call(
        functools.partial(_rmsnorm_res_kernel, eps=eps),
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((br, hidden), lambda i: (i, 0)),
            pl.BlockSpec((br, hidden), lambda i: (i, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, hidden), lambda i: (i, 0)),
            pl.BlockSpec((br, hidden), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, hidden), x.dtype),
            jax.ShapeDtypeStruct((rows, hidden), x.dtype),
        ],
        interpret=interpret,
    )(xf, rf, w.reshape(1, hidden))
    return y.reshape(shape), h.reshape(shape)
