"""Pallas TPU kernel: the whole upper hierarchy in ONE launch.

The per-level build kernel (``kernels/hierarchy_build``) issues one
``pallas_call`` per level with host-side pad/slice glue between launches;
the paper's construction story ("a handful of fused parallel reductions")
is a single pass.  This kernel realizes that on TPU:

* the grid streams **level 0** through VMEM tile by tile — each step
  DMAs ``rows_out * c`` chunks (one chunk per row) HBM→VMEM and reduces
  them to ``rows_out`` lane-dense rows of level-1 summaries, exactly the
  per-level kernel's inner step (a ``(c, c)`` slab is transposed on the
  XLU so that a sublane min puts chunk ``j``'s minimum in lane ``j``);
* the contiguous ``upper`` buffer, as ``(rows, c)``, is the kernel's only
  output and stays **VMEM-resident for the entire launch** (whole-array
  BlockSpec), so every level's summaries are written directly at its
  ``plan.offsets`` row — no intermediate per-level arrays, no
  concatenate;
* the **final grid step** folds the remaining levels bottom-up entirely
  in VMEM, each fold reading the level just written from the output
  buffer itself — no HBM round-trip exists between levels.  Level
  offsets and sizes are static plan metadata, so every fold slice is
  static;
* level-0 **positions are synthesized in-kernel** (a masked iota from the
  grid step id) — the per-level path materializes a ``(capacity,)`` iota
  in HBM first, roughly doubling its build-time input traffic for
  position-tracking builds.

Tie-breaking note: position outputs use the ``min(pos where value ==
min)`` form rather than ``pos[argmin]``.  Carried positions increase
strictly across a chunk's non-padding entries (each summarizes an earlier
subtree than its right neighbour; padding holds ``PAD_POS = INT32_MAX``),
so the two forms agree bit-exactly with the leftmost-argmin oracle while
avoiding a dynamic gather — same argument as ``kernels/hierarchy_update``.

Padding contract: the buffer is +inf / ``PAD_POS``-filled on the first
grid step, and only live entries are overwritten — so each level's stored
padding (out to a multiple of ``c``) matches the oracle's by construction.
The buffer carries ``slack`` rows past ``plan.upper_size / c`` (all-inf
level-1 rows of the tile-aligned grid, and room for a fold's last
partial ``(c, c)`` slab); ops.py drops them.

VMEM budget: the whole ``upper`` buffer (≈ capacity/(c-1) entries per
plane, placed in VMEM by the compiler) plus one double-buffered input
tile must fit; ops.py enforces the measured limit before launching and
points callers past it at the per-level backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.constants import PAD_POS
from repro.core.plan import HierarchyPlan
from repro.kernels.hierarchy_build.kernel import DEFAULT_ROWS_OUT, slab_min


def _fold_upper_levels(o_ref, po_ref, *, plan: HierarchyPlan, pos_dtype):
    """Bottom-up folds for levels >= 2, entirely on the VMEM-resident
    output buffer.  Each row of level k-1 is one chunk of level k, so
    reducing its whole *padded* extent yields exactly the next level's
    live length (``padded_lens[k-2] / c == level_lens[k]``): the fold
    writes only live entries and the initialization padding survives."""
    c = plan.c
    inf = jnp.array(jnp.inf, o_ref.dtype)
    pad_pos = jnp.array(PAD_POS, pos_dtype) if po_ref is not None else None
    sub = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    for k in range(2, plan.num_levels):
        src = plan.offsets[k - 2] // c
        src_rows = plan.padded_lens[k - 2] // c   # == plan.level_lens[k]
        dst = plan.offsets[k - 1] // c
        full, live = divmod(src_rows, c)

        def slab(g, live, src=src, dst=dst):
            """Fold rows ``src + g*c ..`` (the first ``live`` of them are
            level k-1) into row ``dst + g`` of level k."""
            rows = pl.ds(src + g * c, c)
            xt = o_ref[rows, :]
            if live < c:
                # a partial last slab reads into what follows; mask it
                xt = jnp.where(sub < live, xt, inf)
            xt = xt.T
            pt = None
            if po_ref is not None:
                pt = po_ref[rows, :].T
                if live < c:
                    pt = jnp.where(lane < live, pt, pad_pos)
            m, pm = slab_min(xt, pt)
            # lanes past `live` are +inf / PAD_POS, exactly the padding
            o_ref[pl.ds(dst + g, 1), :] = m
            if po_ref is not None:
                po_ref[pl.ds(dst + g, 1), :] = pm

        if full:
            def body(g, carry):
                slab(g, c)
                return carry

            jax.lax.fori_loop(0, full, body, 0)
        if live:
            slab(full, live)


def _fused_kernel(x_ref, o_ref, po_ref, *, plan: HierarchyPlan,
                  rows_out: int, pos_dtype):
    c = plan.c
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.full(o_ref.shape, jnp.inf, o_ref.dtype)
        if po_ref is not None:
            po_ref[...] = jnp.full(po_ref.shape, PAD_POS, pos_dtype)

    if po_ref is not None:
        sub = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    for r in range(rows_out):
        xt = x_ref[pl.ds(r * c, c), :].T          # chunk j -> column j
        pt = None
        if po_ref is not None:
            # Level-0 positions are the absolute indices, synthesized
            # from the grid step (+inf padding past capacity gets the
            # PAD_POS sentinel, matching the oracle's padded iota).
            first = (i * rows_out + r) * (c * c)
            gidx = first + lane * c + sub
            pt = jnp.where(gidx < plan.capacity, gidx, PAD_POS).astype(
                pos_dtype)
        m, pm = slab_min(xt, pt)
        row = pl.ds(i * rows_out + r, 1)   # level 1 starts at row 0
        o_ref[row, :] = m
        if po_ref is not None:
            po_ref[row, :] = pm

    @pl.when(i == pl.num_programs(0) - 1)
    def _fold():
        _fold_upper_levels(o_ref, po_ref, plan=plan, pos_dtype=pos_dtype)


def fused_build(
    values: jax.Array,
    plan: HierarchyPlan,
    rows_out: int,
    slack: int,
    pos_dtype=None,
    interpret: bool = False,
):
    """ALL upper levels from level 0, one launch.

    ``values`` is level 0 as ``(chunks, c)``, +inf-padded so that
    ``rows_out * c`` divides ``chunks`` (ops.py arranges it).  Returns
    the upper buffer as ``(plan.upper_size / c + slack, c)``, and its
    position plane alike when ``pos_dtype`` is given (else ``None``).
    """
    c = plan.c
    chunks = values.shape[0]
    assert chunks % (rows_out * c) == 0, (chunks, rows_out, c)
    assert chunks // c <= plan.upper_size // c + slack, (chunks, slack)
    shape = (plan.upper_size // c + slack, c)
    track = pos_dtype is not None
    out_shape = [jax.ShapeDtypeStruct(shape, values.dtype)]
    if track:
        pos_dtype = jnp.dtype(pos_dtype)
        out_shape.append(jax.ShapeDtypeStruct(shape, pos_dtype))
    kernel = functools.partial(
        _fused_kernel, plan=plan, rows_out=rows_out, pos_dtype=pos_dtype)
    out = pl.pallas_call(
        kernel if track else (lambda x, o: kernel(x, o, None)),
        grid=(chunks // (rows_out * c),),
        in_specs=[pl.BlockSpec((rows_out * c, c), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * len(out_shape),
        out_shape=out_shape,
        interpret=interpret,
        name="hierarchy_fused",
    )(values)
    return out[0], (out[1] if track else None)
