"""Pallas TPU kernel: batched hierarchical RMQ queries (paper §4.2–§4.3).

TPU adaptation of the paper's coalesced-loading (CL) scan + warp-local
queuing (WLQ):

* **Query-tile staging (WLQ analogue).** Each program owns a tile of
  ``QUERY_BLOCK`` queries whose bounds arrive in SMEM via one block DMA —
  the analogue of WLQ's "load bounds once, recirculate through the group"
  (multi-load, the unoptimized strategy, is ``QUERY_BLOCK=1``: one program
  and one bounds transfer per query).
* **Chunk-aligned windows (CL analogue).** Every level access reads one
  aligned ``c``-wide chunk — the paper's "random but cache-aligned chunk
  accesses".  Every level is read as ``(rows, c)`` so a chunk is exactly
  one sublane row; level 0 chunks are row-DMA'd HBM→VMEM per query (the
  GPU's coalesced global load becomes an explicit DMA).
* **VMEM-resident upper levels (L2 analogue).** The whole upper buffer is
  a single VMEM block with a constant index_map, fetched once and reused
  by every grid step — the role the 100 MB L2 plays in the paper's
  profiling (§5.8: upper levels are cache-resident, so large and small
  queries cost alike).
* **Branch-free level walk (TPU-specific change).** The paper's early
  break (``r - l <= 2c``) is replaced by masks that go empty once the
  remaining range collapses: on a GPU the break saves divergent work; on
  the VPU a fixed-shape masked scan is cheaper than control flow.  Cost
  per query is a *constant* ``2c·(L-1) + c·t`` lanes regardless of range
  size — the extreme version of the paper's Fig. 16 observation that
  GPU-RMQ's latency is nearly range-size independent.
  Correctness of the overlap case (range inside one chunk): the two
  boundary masks may cover the same entries — min is idempotent, and the
  (value, leftmost-pos) merge is associative/commutative/idempotent too.

Index math invariants (with ``r`` exclusive):
  left window anchor  = floor(l / c) * c      (covers [l, min(ceil(l/c)*c, r)))
  right window anchor = floor(r / c) * c      (covers [max(anchor, l), r))
  ascend:  l' = ceil(l / c), r' = floor(r / c)   (empty ranges stay empty)
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.plan import HierarchyPlan
from repro.kernels.common import DEFAULT_QUERY_BLOCK

from repro.core.constants import POS_INF_I32 as _POS_INF_I32



def _masked_min_2d(vals, idx, lo, hi, pos=None):
    """(min, leftmost-pos) over ``vals`` where ``lo <= idx < hi``.

    ``vals``/``idx``/``pos`` are (rows, c); returns two scalars.
    """
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


def _rmq_query_kernel(
    # inputs
    l_ref,          # SMEM (qb,) i32
    r_ref,          # SMEM (qb,) i32
    base_hbm,       # ANY  (rows0, c) level 0, one chunk per row, in HBM
    upper_ref,      # VMEM (rows, c) all upper levels, chunk per row
    upper_pos_ref,  # VMEM (rows, c) i32 or None (closure decides)
    # outputs
    out_ref,        # SMEM (qb,) f32
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
        """Start both boundary-window DMAs for query i into buffer slot."""
        a_row, b_row = window_rows(i)
        copy(a_row, slot, 0).start()
        copy(b_row, slot, 1).start()

    def wait(i, slot):
        a_row, b_row = window_rows(i)
        copy(a_row, slot, 0).wait()
        copy(b_row, slot, 1).wait()

    # ---- software pipeline: prefetch query i+1's level-0 windows while
    # the VPU scans query i (DESIGN.md §2.1 — the DMA engines play the
    # role of the paper's "other compute unit"; this is the overlap
    # insight of the RT-core hybrid, realized with TPU-native hardware).
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

        # ---- upper levels: aligned single-row loads from VMEM ----------
        for level in range(1, num_levels):
            off_rows = plan.offsets[level - 1] // c
            padded_rows = plan.padded_lens[level - 1] // c
            is_last = level == num_levels - 1
            if is_last:
                # static full-top masked scan
                rows = padded_rows
                vals = upper_ref[off_rows : off_rows + rows, :]
                idx = (
                    jax.lax.broadcasted_iota(jnp.int32, (rows, c), 0) * c
                    + jax.lax.broadcasted_iota(jnp.int32, (rows, c), 1)
                )
                pos = (
                    upper_pos_ref[off_rows : off_rows + rows, :]
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


def rmq_query_pallas(
    base: jax.Array,
    upper2d: jax.Array,
    upper_pos2d: Optional[jax.Array],
    ls: jax.Array,
    rs: jax.Array,
    plan: HierarchyPlan,
    qb: int = DEFAULT_QUERY_BLOCK,
    track_pos: bool = False,
    interpret: bool = False,
):
    """Launch the query kernel.  ``ls.shape[0]`` must be a multiple of qb.

    ``base`` is level 0 as ``(rows, c)`` (``kernels.common.chunk_rows``);
    ``upper2d`` is the contiguous upper buffer reshaped to ``(rows, c)``
    (one chunk per sublane row).  Returns ``(values, positions)``;
    positions are INT32_MAX when ``track_pos=False``.
    """
    m = ls.shape[0]
    assert m % qb == 0, (m, qb)
    grid = (m // qb,)
    rows = upper2d.shape[0]
    c = plan.c

    kernel = functools.partial(
        _rmq_query_kernel, plan=plan, qb=qb, track_pos=track_pos
    )

    in_specs = [
        pl.BlockSpec((qb,), lambda i: (i,), memory_space=pltpu.SMEM),
        pl.BlockSpec((qb,), lambda i: (i,), memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pl.ANY),          # base stays in HBM
        pl.BlockSpec((rows, c), lambda i: (0, 0)),     # upper: whole, reused
    ]
    out_specs = [
        pl.BlockSpec((qb,), lambda i: (i,), memory_space=pltpu.SMEM),
    ]
    out_shape = [jax.ShapeDtypeStruct((m,), base.dtype)]

    if track_pos:
        in_specs.append(pl.BlockSpec((rows, c), lambda i: (0, 0)))
        out_specs.append(
            pl.BlockSpec((qb,), lambda i: (i,), memory_space=pltpu.SMEM)
        )
        out_shape.append(jax.ShapeDtypeStruct((m,), jnp.int32))
        args = (ls, rs, base, upper2d, upper_pos2d)

        def kern(l_ref, r_ref, base_h, up_ref, upos_ref, o_ref, opos_ref,
                 win, sems):
            kernel(l_ref, r_ref, base_h, up_ref, upos_ref, o_ref, opos_ref,
                   win, sems)
    else:
        args = (ls, rs, base, upper2d)

        def kern(l_ref, r_ref, base_h, up_ref, o_ref, win, sems):
            kernel(l_ref, r_ref, base_h, up_ref, None, o_ref, None,
                   win, sems)

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((2, 2, 1, c), base.dtype),  # [slot][side] rows
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        interpret=interpret,
        name="rmq_scan",
    )(*args)
    if track_pos:
        return out[0], out[1]
    return out[0], None
