"""Pallas TPU kernel: short-span RMQ via a direct two-chunk level-0 scan.

The full query kernel (``repro.kernels.rmq_scan``) pays a *constant*
``2c(L-1) + ct`` scanned lanes per query — the branch-free walk's price
for range-size independence.  For the paper's "small" range class that
constant is almost all waste: a query spanning at most two aligned
chunks (``r // c - l // c <= 1``, the engine planner's SHORT predicate)
is answered exactly by the two level-0 chunks it touches.  This kernel
skips the hierarchy entirely:

* bounds for a ``qb``-query tile arrive in SMEM via one block DMA (the
  WLQ analogue, same as rmq_scan);
* per query, the two aligned chunks ``floor(l/c)`` and ``floor(l/c)+1``
  are DMA'd HBM→VMEM into a double buffer, prefetching query ``i+1``'s
  chunks while the VPU scans query ``i``;
* a masked min over the two rows produces the value, and —
  because level 0 is the original array — the leftmost-minimum
  *position* falls out of the same scan as the masked index min.  No
  ``upper_pos`` planes, so ``RMQ_index`` works even on value-only
  builds.

Level 0 arrives as ``(rows, c)``, one chunk per row, so each chunk is one
row DMA.  The anchor row is clamped to ``rows - 2``, which covers the
same entries as the ref oracle's ``capacity - 2c`` clamp; the kernel
requires ``capacity >= 2c`` and ``ops.py`` falls back to the ref below
that.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.plan import HierarchyPlan
from repro.kernels.common import DEFAULT_QUERY_BLOCK

from repro.core.constants import POS_INF_I32 as _POS_INF_I32



def _rmq_short_kernel(
    # inputs
    l_ref,       # SMEM (qb,) i32
    r_ref,       # SMEM (qb,) i32
    base_hbm,    # ANY  (rows0, c) level 0, one chunk per row, in HBM
    # outputs
    out_ref,     # SMEM (qb,) f32
    out_pos_ref, # SMEM (qb,) i32 or None (closure decides)
    # scratch
    win_ref,     # VMEM (2, 2, 1, c) double-buffered two-chunk windows
    sems,        # DMA semaphores (2, 2)
    *,
    plan: HierarchyPlan,
    qb: int,
    track_pos: bool,
):
    c = plan.c
    rows0 = -(-plan.capacity // c)  # level-0 rows (+inf tail past capacity)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)

    def anchor_of(i):
        """First of the two level-0 rows scanned for query i."""
        return jnp.clip(l_ref[i] // c, 0, rows0 - 2)

    def copy(a, slot, side):
        return pltpu.make_async_copy(
            base_hbm.at[pl.ds(a + side, 1)], win_ref.at[slot, side],
            sems.at[slot, side],
        )

    def issue(i, slot):
        a = anchor_of(i)
        for side in range(2):
            copy(a, slot, side).start()

    def wait(i, slot):
        a = anchor_of(i)
        for side in range(2):
            copy(a, slot, side).wait()

    issue(0, 0)

    def body(i, _):
        slot = jax.lax.rem(i, 2)
        wait(i, slot)

        @pl.when(i + 1 < qb)
        def _prefetch():
            issue(i + 1, 1 - slot)

        l = l_ref[i]
        r = r_ref[i]
        a = anchor_of(i)
        m = jnp.float32(jnp.inf).astype(out_ref.dtype)
        p = jnp.int32(_POS_INF_I32)
        for side in range(2):
            idx = (a + side) * c + lane       # absolute level-0 indices
            mask = (idx >= l) & (idx <= r)
            masked = jnp.where(mask, win_ref[slot, side], jnp.inf)
            m2 = jnp.min(masked)
            p2 = jnp.min(jnp.where(mask & (masked == m2), idx, _POS_INF_I32))
            # lexicographic (value, position) merge keeps ties leftmost
            take2 = (m2 < m) | ((m2 == m) & (p2 < p))
            m = jnp.where(take2, m2, m)
            p = jnp.where(take2, p2, p)
        out_ref[i] = m
        if track_pos:
            out_pos_ref[i] = p
        return 0

    jax.lax.fori_loop(0, qb, body, 0)


def rmq_short_pallas(
    base: jax.Array,
    ls: jax.Array,
    rs: jax.Array,
    plan: HierarchyPlan,
    qb: int = DEFAULT_QUERY_BLOCK,
    track_pos: bool = False,
    interpret: bool = False,
):
    """Launch the short-span kernel.  ``ls.shape[0]`` must be qb-aligned.

    Returns ``(values, positions)``; positions are INT32_MAX when
    ``track_pos=False``.  Requires ``plan.capacity >= 2 * plan.c``.
    """
    m = ls.shape[0]
    assert m % qb == 0, (m, qb)
    assert plan.capacity >= 2 * plan.c, (plan.capacity, plan.c)
    grid = (m // qb,)
    c = plan.c

    kernel = functools.partial(
        _rmq_short_kernel, plan=plan, qb=qb, track_pos=track_pos
    )

    in_specs = [
        pl.BlockSpec((qb,), lambda i: (i,), memory_space=pltpu.SMEM),
        pl.BlockSpec((qb,), lambda i: (i,), memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pl.ANY),       # base stays in HBM
    ]
    out_specs = [
        pl.BlockSpec((qb,), lambda i: (i,), memory_space=pltpu.SMEM),
    ]
    out_shape = [jax.ShapeDtypeStruct((m,), base.dtype)]

    if track_pos:
        out_specs.append(
            pl.BlockSpec((qb,), lambda i: (i,), memory_space=pltpu.SMEM)
        )
        out_shape.append(jax.ShapeDtypeStruct((m,), jnp.int32))

        def kern(l_ref, r_ref, base_h, o_ref, opos_ref, win, sems):
            kernel(l_ref, r_ref, base_h, o_ref, opos_ref, win, sems)
    else:

        def kern(l_ref, r_ref, base_h, o_ref, win, sems):
            kernel(l_ref, r_ref, base_h, o_ref, None, win, sems)

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((2, 2, 1, c), base.dtype),  # [slot][chunk] rows
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        interpret=interpret,
    )(ls, rs, base)
    if track_pos:
        return out[0], out[1]
    return out[0], None
