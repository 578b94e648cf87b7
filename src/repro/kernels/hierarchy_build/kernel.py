"""Pallas TPU kernel: one hierarchy-build level (chunked min-reduce).

Paper §4.1/§5.6: "a group of g adjacent threads reduces a chunk of c
adjacent entries via warp reductions to a single summary".  The TPU
realization tiles the level through VMEM: each program DMAs a block of
``rows_out * c`` chunks HBM→VMEM and reduces every chunk on the VPU —
``rows_out * c`` chunk reductions per program instead of the GPU's
one-warp-per-chunk.

Layout: a level arrives as ``(chunks, c)``, one chunk per row, and its
chunk minima leave lane-dense as ``(chunks / c, c)``.  Each ``(c, c)``
slab of rows is transposed on the XLU so that a sublane min puts chunk
``j``'s minimum in lane ``j`` — the TPU tiles 1-D arrays in runs of 1024
entries, so neither a ``(tile_out * c,)`` input slice nor a ``(tile_out,)``
output block of a 1-D level compiles.  A grid step covers ``rows_out``
output rows: 8 (one f32 sublane tile) or the whole level when it is
smaller.

Positions use the ``min(pos where value == min)`` form rather than a
gather at the argmin (Mosaic has no in-kernel gather).  Carried positions
increase strictly across a chunk's non-padding entries and padding holds
``PAD_POS``, so the two forms agree bit-exactly with the leftmost-argmin
oracle (the argument of ``kernels/hierarchy_fused``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.constants import PAD_POS

# Output rows (of c minima each) per grid step: one f32 sublane tile.
DEFAULT_ROWS_OUT = 8


def slab_min(xt, pt=None):
    """Column minima of a transposed ``(c, c)`` slab (chunk ``j`` in
    column ``j``) as ``(1, c)`` rows: values, and the leftmost positions
    when the slab's position plane ``pt`` is given."""
    m = jnp.min(xt, axis=0, keepdims=True)
    if pt is None:
        return m, None
    pad = jnp.array(PAD_POS, pt.dtype)
    return m, jnp.min(jnp.where(xt == m, pt, pad), axis=0, keepdims=True)


def _min_kernel(x_ref, o_ref, *, c: int, rows_out: int):
    for r in range(rows_out):
        xt = x_ref[pl.ds(r * c, c), :].T           # chunk j -> column j
        o_ref[pl.ds(r, 1), :] = slab_min(xt)[0]


def _argmin_kernel(x_ref, p_ref, o_ref, po_ref, *, c: int, rows_out: int):
    for r in range(rows_out):
        m, pm = slab_min(x_ref[pl.ds(r * c, c), :].T,
                         p_ref[pl.ds(r * c, c), :].T)
        o_ref[pl.ds(r, 1), :] = m
        po_ref[pl.ds(r, 1), :] = pm


def _row_block(i):
    """Row block ``i``, column block 0, both int32 (a literal 0 would
    trace as int64 under x64, which Mosaic refuses)."""
    return i, jnp.zeros_like(i)


def _specs(chunks: int, c: int, rows_out: int):
    """Grid and blocks: ``rows_out * c`` input rows -> ``rows_out`` rows."""
    assert chunks % (rows_out * c) == 0, (chunks, rows_out, c)
    grid = (chunks // (rows_out * c),)
    in_spec = pl.BlockSpec((rows_out * c, c), _row_block)
    out_spec = pl.BlockSpec((rows_out, c), _row_block)
    return grid, in_spec, out_spec


@functools.partial(
    jax.jit, static_argnames=("c", "rows_out", "interpret")
)
def build_level(
    values: jax.Array,
    c: int,
    rows_out: int = DEFAULT_ROWS_OUT,
    interpret: bool = False,
) -> jax.Array:
    """Chunk minima of a level: ``(chunks, c) -> (chunks / c, c)``.

    ``chunks`` must be a multiple of ``rows_out * c`` (ops.py pads the
    level with +inf); ``rows_out`` must be a multiple of 8 or the whole
    output on TPU.
    """
    chunks = values.shape[0]
    grid, in_spec, out_spec = _specs(chunks, c, rows_out)
    return pl.pallas_call(
        functools.partial(_min_kernel, c=c, rows_out=rows_out),
        grid=grid,
        in_specs=[in_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((chunks // c, c), values.dtype),
        interpret=interpret,
        name="hierarchy_build",
    )(values)


@functools.partial(
    jax.jit, static_argnames=("c", "rows_out", "interpret")
)
def build_level_with_positions(
    values: jax.Array,
    positions: jax.Array,
    c: int,
    rows_out: int = DEFAULT_ROWS_OUT,
    interpret: bool = False,
):
    """Chunk-min with carried original-array positions (for RMQ_index)."""
    chunks = values.shape[0]
    grid, in_spec, out_spec = _specs(chunks, c, rows_out)
    return pl.pallas_call(
        functools.partial(_argmin_kernel, c=c, rows_out=rows_out),
        grid=grid,
        in_specs=[in_spec, in_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((chunks // c, c), values.dtype),
            jax.ShapeDtypeStruct((chunks // c, c), positions.dtype),
        ],
        interpret=interpret,
        name="hierarchy_build",
    )(values, positions)
