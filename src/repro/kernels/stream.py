"""Pallas TPU kernels for the paper's Table II streaming suite.

These are the calibration workloads of the reproduction: the paper measured
(f, b_s) for each of these loops on x86; on TPU they characterize the HBM
interface the same way.  Each kernel is tiled for VMEM with explicit
BlockSpecs: 1-D arrays are viewed as (rows, LANES) with LANES = 128 (the VPU
lane count) and the grid walks row-blocks sized by :func:`row_block` to keep
the double-buffered blocks of all streams within a VMEM budget.

Map kernels (DSCAL/DAXPY/ADD/STREAM/WAXPBY/DCOPY/Schoenauer) write one output
stream; reduction kernels (vectorSUM/DDOT1/2/3) accumulate a scalar across
grid steps in a (1, 1) SMEM output pinned to the same location (TPU grid is
sequential, so cross-step accumulation is well-defined).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128

#: VMEM the double-buffered blocks of all of a kernel's streams may take
#: together.  The default scoped-VMEM limit of a v5e core is 16 MiB; the
#: rest is left to the temporaries of the kernel body.
VMEM_STREAM_BUDGET = 4 << 20


def row_block(rows: int, row_bytes: int, streams: int, itemsize: int) -> int:
    """Rows per grid step for ``streams`` row-tiled arrays of ``rows`` rows.

    The block is the whole array when every stream, double-buffered, fits
    :data:`VMEM_STREAM_BUDGET`; otherwise the largest multiple of the
    dtype's sublane tile (8 rows of 32-bit, 16 of 16-bit) that fits.  A
    block need not divide ``rows``: callers walk ``pl.cdiv(rows, block)``
    steps, Pallas pads the overhanging last block and drops its writes
    past the array, and reductions mask its rows (:func:`_reduce_kernel`).
    """
    fit = VMEM_STREAM_BUDGET // (2 * streams * row_bytes)
    if rows <= fit:
        return rows
    sublanes = 8 * max(1, 4 // itemsize)
    block = fit // sublanes * sublanes
    if block == 0:
        raise ValueError(
            f"rows of {row_bytes} B across {streams} double-buffered "
            f"streams exceed the {VMEM_STREAM_BUDGET} B VMEM budget even "
            f"at {sublanes} rows per block")
    return block


# ---------------------------------------------------------------------------
# Map kernels: out = expr(*ins)
# ---------------------------------------------------------------------------

_MAP_EXPRS = {
    "dscal":      lambda s, a: s[0] * a,
    "daxpy":      lambda s, a, b: a + s[0] * b,
    "add":        lambda s, a, b: a + b,
    "stream":     lambda s, a, b: a + s[0] * b,        # STREAM triad
    "waxpby":     lambda s, a, b: s[0] * a + s[1] * b,
    "dcopy":      lambda s, a: a,
    "schoenauer": lambda s, a, b, c: a + b * c,
}


def _map_kernel(expr, scalar_ref, *refs):
    *ins, out = refs
    # The scalars are f32 in SMEM (a 16-bit scalar cannot be broadcast
    # into a vector); the expression is evaluated in the wider dtype.
    s = [scalar_ref[0, j] for j in range(scalar_ref.shape[1])]
    out[...] = expr(s, *[r[...] for r in ins]).astype(out.dtype)


#: Kernels whose Table II form writes back into a read operand
#: (``a[i] = s*a[i]``, ``a[i] = a[i] + s*b[i]``): the value maps the
#: kernel name to the index of the overwritten array operand.
_INPLACE_TARGET = {"dscal": 0, "daxpy": 0}


def _row_view(arrays) -> tuple[int, int, list]:
    """(rows, block, (rows, LANES) views) of equal-shaped 1-D arrays,
    the block sized for the arrays plus one output stream."""
    n = arrays[0].shape[0]
    if n % LANES:
        raise ValueError(f"size {n} not a multiple of {LANES}")
    rows = n // LANES
    itemsize = arrays[0].dtype.itemsize
    block = row_block(rows, LANES * itemsize, len(arrays) + 1, itemsize)
    return rows, block, [a.reshape(rows, LANES) for a in arrays]


def map_stream(name: str, scalar: jax.Array, *arrays: jax.Array,
               interpret: bool, in_place: bool = False) -> jax.Array:
    """Run one Table II map kernel over equal-shaped 1-D arrays.

    ``in_place=True`` declares the paper's C semantics for the kernels
    that overwrite a read operand (DSCAL/DAXPY): the output buffer
    aliases that input via ``input_output_aliases``, so the written
    cache lines are already present and no write-allocate (RFO) stream
    exists — which is exactly what the static traffic auditor derives
    from the alias declaration.  Functionally identical to the default
    out-of-place form.
    """
    expr = _MAP_EXPRS[name]
    n = arrays[0].shape[0]
    rows, block, views = _row_view(arrays)
    scalar2d = jnp.atleast_1d(scalar).astype(jnp.float32).reshape(1, -1)
    extra = {}
    if in_place:
        target = _INPLACE_TARGET.get(name)
        if target is None:
            raise ValueError(
                f"in_place=True is only meaningful for the kernels that "
                f"overwrite a read operand "
                f"({sorted(_INPLACE_TARGET)}); {name!r} writes a "
                f"distinct output array")
        # +1 skips the scalar operand in the pallas input numbering.
        extra["input_output_aliases"] = {1 + target: 0}

    out = pl.pallas_call(
        functools.partial(_map_kernel, expr),
        grid=(pl.cdiv(rows, block),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            *[pl.BlockSpec((block, LANES), lambda i: (i, 0))
              for _ in views],
        ],
        out_specs=pl.BlockSpec((block, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), arrays[0].dtype),
        interpret=interpret,
        **extra,
    )(scalar2d, *views)
    return out.reshape(n)


# ---------------------------------------------------------------------------
# Reduction kernels: scalar += expr(*ins)
# ---------------------------------------------------------------------------

_REDUCE_EXPRS = {
    "vectorsum": lambda a: a,
    "ddot1":     lambda a: a * a,
    "ddot2":     lambda a, b: a * b,
    "ddot3":     lambda a, b, c: a * b * c,
}


def _reduce_kernel(expr, rows, block, *refs):
    *ins, out = refs
    i = pl.program_id(0)
    vals = expr(*[r[...] for r in ins])
    if rows % block:  # the last block overhangs the array: drop its pad
        row = i * block + jax.lax.broadcasted_iota(jnp.int32, vals.shape, 0)
        vals = jnp.where(row < rows, vals, 0)

    @pl.when(i == 0)
    def _init():
        out[0, 0] = jnp.zeros((), out.dtype)

    out[0, 0] += jnp.sum(vals).astype(out.dtype)


def reduce_stream(name: str, *arrays: jax.Array,
                  interpret: bool) -> jax.Array:
    """Run one Table II reduction kernel; returns a scalar."""
    expr = _REDUCE_EXPRS[name]
    rows, block, views = _row_view(arrays)

    out = pl.pallas_call(
        functools.partial(_reduce_kernel, expr, rows, block),
        grid=(pl.cdiv(rows, block),),
        in_specs=[pl.BlockSpec((block, LANES), lambda i: (i, 0))
                  for _ in views],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        interpret=interpret,
    )(*views)
    return out[0, 0]
