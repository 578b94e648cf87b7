"""Pallas TPU kernel: a whole mixed query batch in ONE launch.

PR 4 collapsed *construction* into a single ``pallas_call``
(``kernels/hierarchy_fused``); this kernel completes the symmetry on the
query side — the paper's "only the relevant portions of the hierarchy are
then processed in an optimized massively-parallel scan operation" as one
launch for the entire batch, with no host-side span-class split:

* **in-kernel span decomposition.**  Each query is decomposed inside the
  kernel into a prefix-chunk scan + per-level boundary lookups + suffix-
  chunk scan — the branch-free walk of ``kernels/rmq_scan``, whose masks
  go empty exactly where the paper's early break fires.  Short spans
  (<= two aligned level-0 chunks) are answered entirely by the level-0
  windows — the upper-level masks are empty by construction — so the
  engine's short/mid/long classification becomes unnecessary: one kernel
  serves the whole mix.
* **level offsets via scalar prefetch.**  The ``plan.offsets`` table
  (the layout the construction kernels write) arrives as a scalar-
  prefetch operand (``pltpu.PrefetchScalarGridSpec``): each level's slot
  in the contiguous ``upper`` buffer is indexed *dynamically* while every
  slice size stays static from the plan — the construction and query
  kernels address the hierarchy through one layout contract.
* **value AND index ops in the same launch.**  The position-tracking
  variant emits two planes — minima and leftmost-tie positions — so a
  batch mixing ``RMQ_value`` and ``RMQ_index`` requests needs one launch;
  the host selects the requested plane per query.
* **query-tile staging + double-buffered boundary DMA.**  As in
  ``rmq_scan``: bounds arrive in SMEM per tile, level-0 boundary chunks
  (one row each of the ``(rows, c)`` level 0) are DMA'd HBM→VMEM with a
  two-slot pipeline, the upper buffer is VMEM-resident for the whole
  launch.

Tie-breaking: the ``min(pos where value == min)`` form everywhere, which
is bit-identical to the leftmost-argmin oracle (same argument as the
construction kernels).  The padding contract makes the reserved
``capacity > n`` tail (+inf / ``PAD_POS``) unable to win any query.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.constants import POS_INF_I32 as _POS_INF_I32
from repro.core.plan import HierarchyPlan
from repro.kernels.common import DEFAULT_QUERY_BLOCK



def _masked_min_2d(vals, idx, lo, hi, pos=None):
    """(min, leftmost-pos) over ``vals`` where ``lo <= idx < hi``."""
    inf = jnp.array(jnp.inf, dtype=vals.dtype)
    mask = (idx >= lo) & (idx < hi)
    masked = jnp.where(mask, vals, inf)
    m = jnp.min(masked)
    if pos is None:
        return m, jnp.int32(_POS_INF_I32)
    cand = jnp.where(mask & (masked == m), pos, _POS_INF_I32)
    return m, jnp.min(cand)


def _merge(m, p, m2, p2):
    take2 = (m2 < m) | ((m2 == m) & (p2 < p))
    return jnp.where(take2, m2, m), jnp.where(take2, p2, p)


def _rmq_fused_kernel(
    # scalar prefetch
    offs_ref,       # SMEM (L-1,) i32: plan.offsets (entry units)
    # inputs
    l_ref,          # SMEM (qb,) i32
    r_ref,          # SMEM (qb,) i32
    base_hbm,       # ANY  (rows0, c) level 0, one chunk per row, in HBM
    upper_ref,      # VMEM (rows, c): all upper levels, one chunk per row
    upper_pos_ref,  # VMEM (rows, c) i32 or None (closure decides)
    # outputs
    out_ref,        # SMEM (qb,) values
    out_pos_ref,    # SMEM (qb,) i32 or None
    # scratch
    win_ref,        # VMEM (2, 2, 1, c) double-buffered boundary windows
    sems,           # DMA semaphores (2, 2)
    *,
    plan: HierarchyPlan,
    qb: int,
    track_pos: bool,
):
    c = plan.c
    rows0 = -(-plan.capacity // c)  # level-0 rows (+inf tail past capacity)
    num_levels = plan.num_levels

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)

    def window_rows(i):
        """Level-0 rows holding query i's boundary chunks."""
        a_row = jnp.clip(l_ref[i] // c, 0, rows0 - 1)
        b_row = jnp.clip((r_ref[i] + 1) // c, 0, rows0 - 1)
        return a_row, b_row

    def copy(row, slot, side):
        return pltpu.make_async_copy(
            base_hbm.at[pl.ds(row, 1)], win_ref.at[slot, side],
            sems.at[slot, side],
        )

    def issue(i, slot):
        a_row, b_row = window_rows(i)
        copy(a_row, slot, 0).start()
        copy(b_row, slot, 1).start()

    def wait(i, slot):
        a_row, b_row = window_rows(i)
        copy(a_row, slot, 0).wait()
        copy(b_row, slot, 1).wait()

    issue(0, 0)

    def body(i, _):
        slot = jax.lax.rem(i, 2)
        wait(i, slot)

        @pl.when(i + 1 < qb)
        def _prefetch():
            issue(i + 1, 1 - slot)

        l = l_ref[i]
        r = r_ref[i] + 1  # exclusive
        a_row, b_row = window_rows(i)

        next_l = ((l + c - 1) // c) * c
        prev_r = (r // c) * c

        # ---- level 0: the prefix / suffix chunk scans -------------------
        # A short span's two windows cover [l, r) outright; the ascended
        # range below is then empty and every upper mask stays empty —
        # the kernel-internal equivalent of the planner's SHORT route.
        idx_a = a_row * c + lane
        idx_b = b_row * c + lane
        pos_a = idx_a if track_pos else None
        pos_b = idx_b if track_pos else None
        m, p = _masked_min_2d(
            win_ref[slot, 0], idx_a, l, jnp.minimum(next_l, r), pos_a,
        )
        m2, p2 = _masked_min_2d(
            win_ref[slot, 1], idx_b, jnp.maximum(prev_r, l), r, pos_b,
        )
        m, p = _merge(m, p, m2, p2)

        l_k = (l + c - 1) // c   # ceil
        r_k = r // c             # floor

        # ---- upper levels: dynamic offsets from the prefetched table ----
        for level in range(1, num_levels):
            # Offsets are multiples of c (padded_lens are), so entry
            # offset / c is that level's first sublane row.
            off_rows = offs_ref[level - 1] // c
            padded_rows = plan.padded_lens[level - 1] // c
            is_last = level == num_levels - 1
            if is_last:
                # masked scan of the whole (small, VMEM-resident) top
                rows = padded_rows
                vals = upper_ref[pl.ds(off_rows, rows), :]
                idx = (
                    jax.lax.broadcasted_iota(jnp.int32, (rows, c), 0) * c
                    + jax.lax.broadcasted_iota(jnp.int32, (rows, c), 1)
                )
                pos = (
                    upper_pos_ref[pl.ds(off_rows, rows), :]
                    if track_pos
                    else None
                )
                m2, p2 = _masked_min_2d(vals, idx, l_k, r_k, pos)
                m, p = _merge(m, p, m2, p2)
            else:
                a_row = jnp.clip(l_k // c, 0, padded_rows - 1)
                b_row = jnp.clip(r_k // c, 0, padded_rows - 1)
                nl = ((l_k + c - 1) // c) * c
                pr = (r_k // c) * c
                va = upper_ref[pl.ds(off_rows + a_row, 1), :]
                vb = upper_ref[pl.ds(off_rows + b_row, 1), :]
                ia = a_row * c + lane
                ib = b_row * c + lane
                pa = (
                    upper_pos_ref[pl.ds(off_rows + a_row, 1), :]
                    if track_pos
                    else None
                )
                pb = (
                    upper_pos_ref[pl.ds(off_rows + b_row, 1), :]
                    if track_pos
                    else None
                )
                m2, p2 = _masked_min_2d(va, ia, l_k, jnp.minimum(nl, r_k), pa)
                m, p = _merge(m, p, m2, p2)
                m2, p2 = _masked_min_2d(vb, ib, jnp.maximum(pr, l_k), r_k, pb)
                m, p = _merge(m, p, m2, p2)
                l_k = (l_k + c - 1) // c
                r_k = r_k // c

        out_ref[i] = m
        if track_pos:
            out_pos_ref[i] = p
        return 0

    jax.lax.fori_loop(0, qb, body, 0)


def rmq_fused_pallas(
    base: jax.Array,
    upper2d: jax.Array,
    upper_pos2d: Optional[jax.Array],
    offsets: jax.Array,
    ls: jax.Array,
    rs: jax.Array,
    plan: HierarchyPlan,
    qb: int = DEFAULT_QUERY_BLOCK,
    track_pos: bool = False,
    interpret: bool = False,
):
    """Launch the fused query kernel.  ``ls.shape[0]`` must divide by qb.

    ``base`` is level 0 as ``(rows, c)`` (``kernels.common.chunk_rows``);
    ``upper2d`` is the contiguous upper buffer reshaped ``(rows, c)``;
    ``offsets`` is the int32 ``plan.offsets`` table (entry units),
    consumed via scalar prefetch.  Returns ``(values, positions)`` —
    both planes from the one launch when ``track_pos``, positions
    ``INT32_MAX`` otherwise.
    """
    m = ls.shape[0]
    assert m % qb == 0, (m, qb)
    rows = upper2d.shape[0]
    c = plan.c

    kernel = functools.partial(
        _rmq_fused_kernel, plan=plan, qb=qb, track_pos=track_pos
    )

    in_specs = [
        pl.BlockSpec((qb,), lambda i, offs: (i,), memory_space=pltpu.SMEM),
        pl.BlockSpec((qb,), lambda i, offs: (i,), memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pl.ANY),              # base stays in HBM
        pl.BlockSpec((rows, c), lambda i, offs: (0, 0)),  # upper: resident
    ]
    out_specs = [
        pl.BlockSpec((qb,), lambda i, offs: (i,), memory_space=pltpu.SMEM),
    ]
    out_shape = [jax.ShapeDtypeStruct((m,), base.dtype)]

    if track_pos:
        in_specs.append(pl.BlockSpec((rows, c), lambda i, offs: (0, 0)))
        out_specs.append(
            pl.BlockSpec((qb,), lambda i, offs: (i,),
                         memory_space=pltpu.SMEM)
        )
        out_shape.append(jax.ShapeDtypeStruct((m,), jnp.int32))
        args = (ls, rs, base, upper2d, upper_pos2d)

        def kern(offs_ref, l_ref, r_ref, base_h, up_ref, upos_ref, o_ref,
                 opos_ref, win, sems):
            kernel(offs_ref, l_ref, r_ref, base_h, up_ref, upos_ref,
                   o_ref, opos_ref, win, sems)
    else:
        args = (ls, rs, base, upper2d)

        def kern(offs_ref, l_ref, r_ref, base_h, up_ref, o_ref, win, sems):
            kernel(offs_ref, l_ref, r_ref, base_h, up_ref, None, o_ref,
                   None, win, sems)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // qb,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, 2, 1, c), base.dtype),  # [slot][side] rows
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(offsets.astype(jnp.int32), *args)
    if track_pos:
        return out[0], out[1]
    return out[0], None
