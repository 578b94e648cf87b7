"""Jitted wrappers: full multi-level hierarchy updates via Pallas.

Mirrors ``repro.streaming.updates`` (the oracle) exactly — same last-wins
base scatter, same chunk dedupe — swapping only the per-level chunk
re-reduction for the scalar-prefetch Pallas kernel.  Tests assert
bit-identical hierarchies from both paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.hierarchy import Hierarchy, _pad_to, pos_dtype_for
from repro.core.plan import HierarchyPlan
from repro.kernels import common, profiling
from repro.kernels.hierarchy_update import kernel as K
from repro.streaming.updates import scatter_base, touched_chunk_ids

__all__ = ["update_hierarchy_pallas", "append_hierarchy_pallas"]


def _propagate_pallas(
    plan: HierarchyPlan,
    base: jax.Array,
    upper: jax.Array,
    upper_pos,
    idxs: jax.Array,
    interpret: bool,
):
    c = plan.c
    cap = plan.capacity
    track = upper_pos is not None
    idxs = idxs.astype(jnp.int32)
    # Same out-of-range sanitization as the pure-JAX oracle: dropped
    # writes must not re-reduce (and clamp-scatter over) foreign chunks.
    idxs = jnp.where((idxs >= 0) & (idxs < cap), idxs, 0)
    ids = idxs // c
    for level in range(1, plan.num_levels):
        ids = touched_chunk_ids(ids, plan.level_lens[level])
        src_len = (plan.level_lens[1] * c if level == 1
                   else plan.level_slice(level - 1)[1])
        profiling.record_launch(
            "hierarchy_update",
            lowering="pallas",
            level=level,
            touched=int(ids.shape[0]),
            with_positions=bool(track),
            operand_bytes=(src_len * base.dtype.itemsize
                           + profiling.operand_bytes(ids)),
        )
        if level == 1:
            # Level 0 is capacity-long; align it to the chunk grid so the
            # kernel's block DMA stays in range.
            src = _pad_to(
                base, plan.level_lens[1] * c,
                jnp.array(jnp.inf, base.dtype),
            )
            if track:
                nv, np_ = K.update_level0_with_positions(
                    src, ids, c=c, cap=cap,
                    pos_dtype=pos_dtype_for(cap), interpret=interpret,
                )
            else:
                nv = K.update_level(src, ids, c=c, interpret=interpret)
                np_ = None
        else:
            off, padded = plan.level_slice(level - 1)
            src = jax.lax.slice(upper, (off,), (off + padded,))
            if track:
                src_p = jax.lax.slice(upper_pos, (off,), (off + padded,))
                nv, np_ = K.update_level_with_positions(
                    src, src_p, ids, c=c, interpret=interpret
                )
            else:
                nv = K.update_level(src, ids, c=c, interpret=interpret)
                np_ = None
        off_out = plan.offsets[level - 1]
        upper = upper.at[off_out + ids].set(nv)
        if track:
            upper_pos = upper_pos.at[off_out + ids].set(np_)
        ids = ids // c
    return upper, upper_pos


@functools.partial(jax.jit, static_argnames=("interpret",))
def _update_jit(h, idxs, vals, interpret):
    idxs = idxs.astype(jnp.int32)
    base = scatter_base(h.base, idxs, vals)
    upper, upper_pos = _propagate_pallas(
        h.plan, base, h.upper, h.upper_pos, idxs, interpret
    )
    return Hierarchy(base=base, upper=upper, upper_pos=upper_pos,
                     plan=h.plan)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _append_jit(h, vals, start, interpret):
    vals = vals.astype(h.base.dtype)
    start = jnp.asarray(start, jnp.int32)
    base = jax.lax.dynamic_update_slice(h.base, vals, (start,))
    idxs = start + jnp.arange(vals.shape[0], dtype=jnp.int32)
    upper, upper_pos = _propagate_pallas(
        h.plan, base, h.upper, h.upper_pos, idxs, interpret
    )
    return Hierarchy(base=base, upper=upper, upper_pos=upper_pos,
                     plan=h.plan)


def _jax_path_only(h: Hierarchy) -> bool:
    """Layouts the per-level kernel cannot re-reduce in place.

    Packed planes store chunk-local bit fields (the kernel writes
    absolute positions) and bf16 summaries need the exact level-0
    recompare; both route through the pure-JAX oracle, which handles
    them natively — same bit-identical contract, different lowering.
    """
    return bool(h.plan.packed_pos) or h.upper.dtype != h.base.dtype


def update_hierarchy_pallas(
    h: Hierarchy,
    idxs: jax.Array,
    vals: jax.Array,
    interpret: bool = None,
) -> Hierarchy:
    """Batched point updates with Pallas chunk re-reductions."""
    from repro.core.protocol import check_capacity_limit

    # The level-0 kernel synthesizes absolute positions in int32; larger
    # capacities must use the pure-JAX update path (x64).
    check_capacity_limit(h.plan.capacity)
    if _jax_path_only(h):
        from repro.streaming import updates as U

        return U.update_hierarchy(h, idxs, vals)
    return _update_jit(h, idxs, vals, common.resolve_interpret(interpret))


def append_hierarchy_pallas(
    h: Hierarchy,
    vals: jax.Array,
    start,
    interpret: bool = None,
) -> Hierarchy:
    """Append ``vals`` at ``start`` with Pallas chunk re-reductions."""
    from repro.core.protocol import check_capacity_limit

    check_capacity_limit(h.plan.capacity)
    if _jax_path_only(h):
        from repro.streaming import updates as U

        return U.append_hierarchy(h, vals, start)
    return _append_jit(
        h, vals, start, common.resolve_interpret(interpret)
    )
