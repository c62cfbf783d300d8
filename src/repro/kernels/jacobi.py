"""Pallas TPU kernel for the paper's 2-D 5-point Jacobi stencils (Table II).

TPU adaptation of the layer-condition idea: on x86 the LC decides whether
three grid rows fit in L2; on TPU we tile rows into VMEM explicitly, so the
"layer condition" is *enforced by construction* — each grid step holds a
``block + 2`` row window of the source grid (the halo rows) in VMEM, with
``block`` sized from the grid width by :func:`repro.kernels.stream.row_block`.
The up/mid/down row views are materialized by the wrapper as shifted inputs
sharing one BlockSpec shape, which keeps the kernel body free of
inter-block halo logic.  XLA copies each view into a buffer of its own
before the kernel runs (three grid-sized copies in HBM), so on the chip
the kernel reads three streams, not one.

v1:  b[j][i] = (a[j][i-1] + a[j][i+1] + a[j-1][i] + a[j+1][i]) * s
v2:  r = (ax*(A[j][i-1]+A[j][i+1]) + ay*(A[j-1][i]+A[j+1][i])
          + b1*A[j][i] - F[j][i]) / b1
     B[j][i] = A[j][i] - relax * r ;  residual += r*r
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .stream import row_block


def _v1_kernel(up, mid, down, s_ref, out):
    s = s_ref[0, 0]
    m = mid[...]
    left = jnp.roll(m, 1, axis=1)
    right = jnp.roll(m, -1, axis=1)
    res = (left + right + up[...] + down[...]) * s
    # Interior columns only; boundary columns copy the source (Dirichlet).
    col = jax.lax.broadcasted_iota(jnp.int32, m.shape, 1)
    w = m.shape[1]
    out[...] = jnp.where((col > 0) & (col < w - 1), res, m)


def _v2_kernel(rows, block, up, mid, down, f, coef, out_b, out_r):
    ax, ay, b1, relax = coef[0, 0], coef[0, 1], coef[0, 2], coef[0, 3]
    m = mid[...]
    left = jnp.roll(m, 1, axis=1)
    right = jnp.roll(m, -1, axis=1)
    r1 = (ax * (left + right) + ay * (up[...] + down[...])
          + b1 * m - f[...]) / b1
    col = jax.lax.broadcasted_iota(jnp.int32, m.shape, 1)
    w = m.shape[1]
    interior = (col > 0) & (col < w - 1)
    r1 = jnp.where(interior, r1, 0.0)
    out_b[...] = jnp.where(interior, m - relax * r1, m)
    i = pl.program_id(0)
    if rows % block:  # the last block overhangs the grid: drop its pad
        row = i * block + jax.lax.broadcasted_iota(jnp.int32, m.shape, 0)
        r1 = jnp.where(row < rows, r1, 0.0)

    @pl.when(i == 0)
    def _init():
        out_r[0, 0] = jnp.zeros((), out_r.dtype)

    out_r[0, 0] += jnp.sum(r1 * r1).astype(out_r.dtype)


def _shifted_views(a: jax.Array):
    """up/mid/down row views over the interior rows of ``a``."""
    return a[:-2], a[1:-1], a[2:]


def _row_blocks(a: jax.Array, streams: int) -> tuple[int, int]:
    """(interior rows, rows per grid step) for ``streams`` row-tiled
    arrays as wide as ``a``."""
    rows = a.shape[0] - 2
    itemsize = a.dtype.itemsize
    return rows, row_block(rows, a.shape[1] * itemsize, streams, itemsize)


def jacobi_v1(a: jax.Array, s: float | jax.Array, *,
              interpret: bool) -> jax.Array:
    """One Jacobi-v1 sweep on the interior of ``a``; returns the full grid
    with boundary rows copied through."""
    w = a.shape[1]
    up, mid, down = _shifted_views(a)
    rows, block = _row_blocks(a, 4)
    s2d = jnp.full((1, 1), s, a.dtype)

    inner = pl.pallas_call(
        _v1_kernel,
        grid=(pl.cdiv(rows, block),),
        in_specs=[
            *[pl.BlockSpec((block, w), lambda i: (i, 0))
              for _ in range(3)],
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, w), a.dtype),
        interpret=interpret,
    )(up, mid, down, s2d)
    return jnp.concatenate([a[:1], inner, a[-1:]], axis=0)


def jacobi_v2(a: jax.Array, f: jax.Array, *, ax: float, ay: float, b1: float,
              relax: float, interpret: bool) -> tuple[jax.Array, jax.Array]:
    """One Jacobi-v2 sweep; returns (updated grid, residual sum-of-squares)."""
    w = a.shape[1]
    up, mid, down = _shifted_views(a)
    f_in = f[1:-1]
    rows, block = _row_blocks(a, 5)
    coef = jnp.array([[ax, ay, b1, relax]], a.dtype)

    inner, res = pl.pallas_call(
        functools.partial(_v2_kernel, rows, block),
        grid=(pl.cdiv(rows, block),),
        in_specs=[
            *[pl.BlockSpec((block, w), lambda i: (i, 0))
              for _ in range(4)],
            pl.BlockSpec((1, 4), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, w), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, w), a.dtype),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(up, mid, down, f_in, coef)
    full = jnp.concatenate([a[:1], inner, a[-1:]], axis=0)
    return full, res[0, 0]
