"""Hybrid RMQ: hierarchy lower levels + O(1) sparse-table top (paper §4.5).

The paper's §4.5 replaces the top-level linear scan with a different index
engine (an RT-core triangle scene).  The portable version of that design
question is: *does a constant-time index over the top level beat scanning
it?*  This module implements the hybrid faithfully with a sparse table:

* levels 0..L-2: the standard boundary-chunk walk (identical cost);
* top level: one O(1) sparse-table lookup instead of an O(c·t) scan.

Trade-off surface (mirrors the paper's Fig. 13 analysis):
* extra memory: the top level has T <= c·t entries ⇒ table is
  T·log2(T) entries — tiny in absolute terms but up to log2(T)× the top
  level itself;
* extra build: one log2(T)-pass table build after the hierarchy build;
* query win: replaces the ct-entry masked scan with 2 loads — only pays
  off when c·t is large (exactly the paper's conclusion: with a small,
  cache/VMEM-resident top level there is little to win back, which is why
  RT cores lost; with a LARGE t — which the hybrid enables, paper §4.5
  implication (1) — the hybrid frontier shifts).

The paper's hybrid is value-only (RTXRMQ triangles encode values).  Ours
goes past that: built ``with_positions=True`` (or from a
position-tracking hierarchy via :meth:`from_hierarchy`), the sparse
table also tracks leftmost-minimum *positions*, so ``query_index``
gets the same O(1) top — this is what lets the batched query engine
(``repro.qe``) route long-span ``RMQ_index`` queries here instead of
falling back to the full walk.

:meth:`from_hierarchy` wraps an *existing* hierarchy without rebuilding
it — the engine uses this to add a hybrid top to a live index for the
cost of one tiny (<= c·t entries) table build.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.baselines import SparseTable
from repro.core.constants import POS_INF_I32 as _POS_INF_I32
from repro.core.hierarchy import Hierarchy
from repro.core.plan import HierarchyPlan, make_plan
# the shared lexicographic (value, leftmost-position) merge: the engine's
# parity contract needs identical tie-breaking across all paths
from repro.core.query import _masked_window_scan, _merge, row_view_rows

__all__ = ["HybridRMQ", "row_levels"]


@dataclasses.dataclass(frozen=True)
class HybridRMQ:
    """Minima hierarchy with a sparse-table top level."""

    hierarchy: Hierarchy
    top_table: SparseTable

    @staticmethod
    def build(
        x,
        c: int = 128,
        t: int = 1024,
        with_positions: bool = False,
        backend: str = "auto",
        packed_pos: Optional[bool] = None,
        summary_dtype: Optional[str] = None,
    ) -> "HybridRMQ":
        """Note the default t is 16x the scan version's: the O(1) top
        makes large tops free at query time (paper §4.5 implication (1)),
        which in turn removes one hierarchy level.

        ``backend`` selects the hierarchy construction path (the shared
        ``'fused'``/``'pallas'``/``'jax'`` pipeline); the hybrid walk
        itself is pure JAX regardless.  ``packed_pos`` selects the
        bit-packed position plane (the table top reads it through the
        shared unpack helpers); ``summary_dtype='bfloat16'`` is refused
        — the sparse-table top would compare quantized values.
        """
        from repro.core import protocol as px

        x = px.coerce_values(x)
        plan = make_plan(int(x.shape[0]), c=c, t=t,
                         packed_pos=packed_pos,
                         summary_dtype=summary_dtype)
        h = px.build_hierarchy_with_backend(
            x, plan, with_positions=with_positions,
            backend=px.resolve_backend(backend),
        )
        return HybridRMQ.from_hierarchy(h)

    @staticmethod
    def from_hierarchy(h: Hierarchy) -> "HybridRMQ":
        """Add a sparse-table top to an existing hierarchy (no rebuild).

        Position tracking follows the hierarchy: a ``with_positions``
        build gets an index-tracking table, a value-only build gets a
        value-only table (and ``query_index`` raises).
        """
        plan = h.plan
        if h.upper.dtype != h.base.dtype:
            raise ValueError(
                "HybridRMQ does not support bf16 summaries: the sparse-"
                "table top would compare quantized values; query bf16 "
                "indexes through the exact-recovery walk/fused paths"
            )
        if plan.num_levels == 1:
            top = h.base
            top_pos = (
                jnp.arange(h.base.shape[0], dtype=jnp.int32)
                if h.with_positions
                else None
            )
        else:
            off, _ = plan.level_slice(plan.num_levels - 1)
            top = h.upper[off : off + plan.top_len]
            if not h.with_positions:
                top_pos = None
            elif plan.packed_pos:
                # The packed plane has no sliceable absolute view; walk
                # the top entries' offset chains down to level 0.
                top_pos = _packed_top_positions(h.upper_pos, plan)
            else:
                top_pos = h.upper_pos[off : off + plan.top_len]
        return HybridRMQ(
            hierarchy=h, top_table=SparseTable.build(top, positions=top_pos)
        )

    # -- protocol surface (repro.core.protocol.RMQIndex) -------------------
    # The hybrid is read-only (no update/append): a point update could move
    # the top level's minima, invalidating sparse-table rows wholesale.
    # Mutating workloads should hold a mutable index and let the engine
    # re-derive the hybrid top per generation (LongSpanExecutor does).
    backend = "jax"  # the hybrid walk is pure JAX on every backend
    generation = 0

    @property
    def plan(self) -> HierarchyPlan:
        return self.hierarchy.plan

    @property
    def length(self) -> int:
        return self.plan.n

    @property
    def capacity(self) -> int:
        return self.plan.capacity

    @property
    def value_dtype(self):
        return self.hierarchy.base.dtype

    def engine(self, **kwargs):
        """A span-routed :class:`repro.qe.QueryEngine` over this index."""
        from repro.core.protocol import make_engine

        return make_engine(self, **kwargs)

    @property
    def with_positions(self) -> bool:
        return self.top_table.with_positions

    def auxiliary_bytes(self) -> int:
        return (
            self.hierarchy.auxiliary_bytes()
            + self.top_table.auxiliary_bytes()
        )

    def query(self, ls, rs) -> jax.Array:
        ls = jnp.asarray(ls, jnp.int32)
        rs = jnp.asarray(rs, jnp.int32)
        m, _ = _hybrid_batch(
            self.plan, self.hierarchy.base, self.hierarchy.upper, None,
            self.top_table.table, None, ls, rs, track_pos=False,
        )
        return m

    def query_index(self, ls, rs) -> jax.Array:
        """Leftmost-minimum positions with the O(1) sparse-table top."""
        if not self.with_positions:
            raise ValueError(
                "hybrid built value-only; build with with_positions=True "
                "(or from a position-tracking hierarchy)"
            )
        ls = jnp.asarray(ls, jnp.int32)
        rs = jnp.asarray(rs, jnp.int32)
        _, p = _hybrid_batch(
            self.plan, self.hierarchy.base, self.hierarchy.upper,
            self.hierarchy.upper_pos, self.top_table.table,
            self.top_table.pos, ls, rs, track_pos=True,
        )
        return p

    # protocol spellings (RMQIndex): same entry points, canonical names
    query_value_batch = query
    query_index_batch = query_index


@functools.partial(jax.jit, static_argnames=("plan",))
def _packed_top_positions(words, plan):
    """Absolute level-0 positions of the top level's live entries."""
    from repro.core import bitpack
    from repro.core.hierarchy import pos_dtype_for

    coord = pos_dtype_for(plan.capacity, strict=False)
    ids = jnp.arange(plan.top_len, dtype=jnp.int32)
    return bitpack.gather_absolute(
        words, plan, plan.num_levels - 1, ids, coord
    )


@functools.partial(jax.jit, static_argnames=("plan", "track_pos"))
def _hybrid_batch(plan, base, upper, upper_pos, top_table, top_pos, ls, rs,
                  track_pos):
    from repro.core import bitpack

    upper_pos = bitpack.resolve_positions(upper_pos, plan)
    return jax.vmap(
        lambda l, r: _hybrid_single(
            plan, base, upper, upper_pos, top_table, top_pos, l, r,
            track_pos,
        )
    )(ls, rs)


def row_levels(plan: HierarchyPlan) -> int:
    """Walk levels (of ``plan.num_levels - 1``) whose chunk windows
    :func:`_hybrid_single` reads by a row gather, not a ``dynamic_slice``."""
    lens = [plan.capacity] + [
        plan.level_slice(level)[1] for level in range(1, plan.num_levels - 1)
    ]
    return sum(row_view_rows(m, plan.c, plan.c, aligned=True) > 0
               for m in lens)


def _hybrid_single(plan: HierarchyPlan, base, upper, upper_pos, top_table,
                   top_pos, l, r, track_pos):
    """Branch-free walk for levels 0..L-2 + O(1) table lookup at the top.

    Each boundary window is the ``c``-entry chunk at a multiple of ``c``:
    one row of the level's ``(rows, c)`` view (:func:`row_levels`).
    """
    c = plan.c
    window = functools.partial(_masked_window_scan, window=c,
                               track_pos=track_pos, row=c, aligned=True)
    l = l.astype(jnp.int32)
    r = (r + 1).astype(jnp.int32)
    m = jnp.float32(jnp.inf)
    p = jnp.int32(_POS_INF_I32)

    for level in range(plan.num_levels - 1):
        if level == 0:
            arr, pos_arr = base, None  # level-0 positions are the indices
        else:
            off, padded = plan.level_slice(level)
            arr = jax.lax.slice(upper, (off,), (off + padded,))
            pos_arr = (
                jax.lax.slice(upper_pos, (off,), (off + padded,))
                if track_pos
                else None
            )
        next_l = ((l + c - 1) // c) * c
        prev_r = (r // c) * c
        m2, p2 = window(arr, pos_arr, (l // c) * c, l,
                        jnp.minimum(next_l, r))
        m, p = _merge(m, p, m2, p2)
        m2, p2 = window(arr, pos_arr, prev_r, jnp.maximum(prev_r, l), r)
        m, p = _merge(m, p, m2, p2)
        l = (l + c - 1) // c
        r = r // c

    # --- O(1) top: sparse table on [l, r) (empty range -> +inf) ---------
    nonempty = r > l
    rr = jnp.maximum(r - 1, l)          # inclusive, clamped
    span = rr - l + 1
    j = (31 - jax.lax.clz(span.astype(jnp.int32))).astype(jnp.int32)
    r2 = rr + 1 - (1 << j.astype(jnp.uint32)).astype(jnp.int32)
    vl = top_table[j, l]
    vr = top_table[j, r2]
    if track_pos:
        pl_ = top_pos[j, l]
        pr_ = top_pos[j, r2]
        tm, tp = _merge(vl, pl_, vr, pr_)
    else:
        tm, tp = jnp.minimum(vl, vr), jnp.int32(_POS_INF_I32)
    tm = jnp.where(nonempty, tm, jnp.inf)
    tp = jnp.where(nonempty, tp, _POS_INF_I32)
    return _merge(m, p, tm, tp)
