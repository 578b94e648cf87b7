"""Pallas TPU kernel: scattered chunk re-reduction for hierarchy updates.

A streaming update touches an arbitrary *set* of chunks per level (the
deduped ``idx // c**k`` of the update batch).  Each grid step repairs a
block of touched chunks: per chunk id it DMAs exactly that chunk of the
source level HBM→VMEM and the VPU re-reduces it to a single summary —
the update-time mirror of the ``hierarchy_build`` kernel, which walks
chunks densely.

Tie-breaking note: the position output is computed as
``min(pos where value == min)`` rather than ``pos[argmin]``.  Within a
chunk, carried positions are strictly increasing across non-padding
entries (each entry summarizes an earlier subtree than its right
neighbour) and padding positions are ``INT32_MAX``, so the two forms agree
bit-exactly with the leftmost-argmin oracle while avoiding a dynamic
gather in the kernel.

Layout notes:
* the source level is read as ``(rows, c)``, one chunk per row, and each
  touched chunk is one row DMA into a double-buffered VMEM window (the
  next chunk's copy overlaps the current reduction) — the TPU tiles a
  1-D level in runs of 1024 entries, so neither a ``(c,)`` block nor a
  ``c``-wide slice of it compiles;
* chunk ids and the summaries travel in SMEM blocks of ``qb`` per grid
  step, the query kernels' bounds/results layout (``kernels.common``);
* ``c >= 128`` keeps each row a whole lane row; smaller ``c`` works (and
  is exercised in interpret mode) but underfills the VPU on hardware.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.constants import PAD_POS as _PAD_POS
from repro.kernels import common


def _update_kernel(ids_ref, x_hbm, p_hbm, o_ref, po_ref, xwin, pwin, sems,
                   *, c: int, qb: int, cap, pos_dtype):
    """Re-reduce the ``qb`` chunks named by this step's ids.

    ``p_hbm`` is the carried position plane (upper levels) or ``None``;
    with ``cap`` set instead, positions are level 0's absolute indices,
    synthesized from the chunk id (+inf padding beyond capacity gets the
    ``_PAD_POS`` sentinel, as in the build).
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)

    def copies(i, slot):
        row = pl.ds(ids_ref[i], 1)
        out = [pltpu.make_async_copy(
            x_hbm.at[row], xwin.at[slot], sems.at[slot, 0])]
        if p_hbm is not None:
            out.append(pltpu.make_async_copy(
                p_hbm.at[row], pwin.at[slot], sems.at[slot, 1]))
        return out

    for cp in copies(0, 0):
        cp.start()

    def body(i, _):
        slot = jax.lax.rem(i, 2)
        for cp in copies(i, slot):
            cp.wait()

        @pl.when(i + 1 < qb)
        def _prefetch():
            for cp in copies(i + 1, 1 - slot):
                cp.start()

        x = xwin[slot]
        m = jnp.min(x)
        o_ref[i] = m
        if po_ref is not None:
            if p_hbm is not None:
                p = pwin[slot]
            else:
                idx = ids_ref[i] * c + lane
                p = jnp.where(idx < cap, idx, _PAD_POS).astype(pos_dtype)
            po_ref[i] = jnp.min(
                jnp.where(x == m, p, jnp.array(_PAD_POS, pos_dtype)))
        return 0

    jax.lax.fori_loop(0, qb, body, 0)


def _launch(values, positions, ids, c, cap, pos_dtype, interpret,
            qb=common.DEFAULT_QUERY_BLOCK):
    """Chunk minima (and positions when ``pos_dtype``) of chunks ``ids``
    of a 1-D level padded to a multiple of ``c``."""
    assert values.shape[0] % c == 0, (values.shape, c)
    b = ids.shape[0]
    qb, b_pad = common.query_grid(b, qb, interpret)
    ids = jnp.pad(ids.astype(jnp.int32), (0, b_pad - b))  # pad: chunk 0
    track = pos_dtype is not None
    smem = pl.BlockSpec((qb,), lambda i: (i,), memory_space=pltpu.SMEM)
    operands = [ids, values.reshape(-1, c)]
    in_specs = [smem, pl.BlockSpec(memory_space=pl.ANY)]
    out_specs = [smem]
    out_shape = [jax.ShapeDtypeStruct((b_pad,), values.dtype)]
    scratch = [pltpu.VMEM((2, 1, c), values.dtype)]
    if track:
        pos_dtype = jnp.dtype(pos_dtype)
        out_specs.append(smem)
        out_shape.append(jax.ShapeDtypeStruct((b_pad,), pos_dtype))
    if positions is not None:
        operands.append(positions.reshape(-1, c))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        scratch.append(pltpu.VMEM((2, 1, c), positions.dtype))
    scratch.append(pltpu.SemaphoreType.DMA((2, 2)))

    body = functools.partial(
        _update_kernel, c=c, qb=qb, cap=cap, pos_dtype=pos_dtype)

    def kernel(*refs):
        # refs: ids, x, [p], o, [po], xwin, [pwin], sems
        refs = list(refs)
        ids_ref, x_hbm = refs.pop(0), refs.pop(0)
        p_hbm = refs.pop(0) if positions is not None else None
        o_ref = refs.pop(0)
        po_ref = refs.pop(0) if track else None
        xwin = refs.pop(0)
        pwin = refs.pop(0) if positions is not None else None
        body(ids_ref, x_hbm, p_hbm, o_ref, po_ref, xwin, pwin, refs.pop(0))

    out = pl.pallas_call(
        kernel,
        grid=(b_pad // qb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="hierarchy_update",
    )(*operands)
    if track:
        return out[0][:b], out[1][:b]
    return out[0][:b]


@functools.partial(jax.jit, static_argnames=("c", "interpret"))
def update_level(
    values: jax.Array,
    ids: jax.Array,
    c: int,
    interpret: bool = False,
) -> jax.Array:
    """Re-reduce chunks ``ids`` of a level: gather + min, ``(B,)`` out.

    ``values`` is the full source level, padded to a multiple of ``c``
    (ops.py pads with +inf).  ``ids`` are chunk indices into it.
    """
    return _launch(values, None, ids, c, None, None, interpret)


@functools.partial(jax.jit, static_argnames=("c", "interpret"))
def update_level_with_positions(
    values: jax.Array,
    positions: jax.Array,
    ids: jax.Array,
    c: int,
    interpret: bool = False,
):
    """Chunk re-reduction carrying original-array positions (upper levels)."""
    return _launch(values, positions, ids, c, None, positions.dtype,
                   interpret)


@functools.partial(
    jax.jit, static_argnames=("c", "cap", "pos_dtype", "interpret")
)
def update_level0_with_positions(
    values: jax.Array,
    ids: jax.Array,
    c: int,
    cap: int,
    pos_dtype,
    interpret: bool = False,
):
    """Level-1 repair from level 0: positions synthesized from chunk ids."""
    return _launch(values, None, ids, c, cap, pos_dtype, interpret)
