"""User-facing RMQ facade: backend selection (pure JAX vs. Pallas kernels).

``backend="auto"`` uses the Pallas query/build kernels when running on TPU
and the pure-JAX reference elsewhere (the kernels also run under
``interpret=True`` on CPU, which the test suite exercises; interpret mode is
a correctness tool, not a performance path, so "auto" avoids it at runtime).
``backend="fused"`` selects the single-launch pipelines end to end:
construction in ONE kernel launch (``repro.kernels.hierarchy_fused``) and
batched queries in ONE launch per batch (``repro.kernels.rmq_fused`` —
every span class, value and index ops alike, no host-side class split).
Updates/appends have no fused lowering and run through the platform
default; results are bit-identical on every backend.

The index is not frozen at build time: ``update`` applies batched point
mutations and ``append`` grows the array into reserved capacity, both in
O(batch · log_c n) chunk re-reductions (see ``repro.streaming`` for the
full streaming structure with sliding-window retirement).

``RMQ`` implements the :class:`repro.core.protocol.RMQIndex` /
``MutableRMQIndex`` protocol — the common surface shared with
``StreamingRMQ``, ``HybridRMQ`` and ``DistributedRMQ`` that the batched
query engine routes over.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import protocol as px
from repro.core.hierarchy import Hierarchy
from repro.core.plan import HierarchyPlan, make_plan
from repro.core.query import check_query_args
from repro.obs import trace

__all__ = ["RMQ"]


@dataclasses.dataclass(frozen=True)
class RMQ:
    """A built range-minimum index (paper §4) with incremental updates."""

    hierarchy: Hierarchy
    backend: str
    # Live length; None means "the build length" (plan.n).  Tracked
    # host-side so appends never invalidate jit specializations.
    length: Optional[int] = None
    # Monotonic mutation counter: every update/append returns a successor
    # with generation + 1.  Host-side metadata (never traced) used by the
    # query engine's result cache to invalidate entries that were computed
    # against an older version of the array.
    generation: int = 0

    # -- construction -----------------------------------------------------
    @staticmethod
    def build(
        x,
        c=128,
        t: int = 64,
        with_positions: bool = False,
        backend: str = "auto",
        plan: Optional[HierarchyPlan] = None,
        capacity: Optional[int] = None,
        tuning=None,
        span_mix: str = "mixed",
        packed_pos: Optional[bool] = None,
        summary_dtype: Optional[str] = None,
    ) -> "RMQ":
        """Build over ``x``; pass ``capacity > len(x)`` to allow appends.

        ``c="auto"`` resolves geometry from the tuning cache (``tuning``
        — default: the committed ``repro.tune.default_cache()`` — keyed
        by platform × size bucket × ``span_mix``) and attaches the
        winner's ``LevelSplit`` to the plan; with ``backend="auto"`` the
        tuned *query* backend is adopted too (hierarchies are
        bit-identical across backends, so this only changes which
        lowering answers queries).  A cache miss falls back to today's
        defaults (``c=128, t=64``, platform backend) bit-identically.

        ``packed_pos`` / ``summary_dtype`` select the compact plane
        layouts (bit-packed chunk-local positions, bf16 value summaries
        with exact recovery — see ``make_plan``); ``None`` defers to the
        tuning cache, then the classic layout.
        """
        with trace.span("build") as sp:
            x = px.coerce_values(x)
            if plan is not None and capacity is not None:
                raise ValueError(
                    "pass capacity via make_plan(..., capacity=...) when "
                    "supplying an explicit plan"
                )
            tuned_cfg = None
            with trace.span("build_plan"):
                if plan is None:
                    plan, tuned_cfg = _resolve_plan(
                        int(x.shape[0]), c, t, capacity, tuning, span_mix,
                        packed_pos, summary_dtype,
                    )
            if backend == "auto" and tuned_cfg is not None:
                backend = tuned_cfg.backend
            backend = px.resolve_backend(backend)
            h = px.build_hierarchy_with_backend(
                x, plan, with_positions=with_positions, backend=backend
            )
            if sp is not None:
                sp.args.update(n=int(x.shape[0]), backend=backend)
        return RMQ(hierarchy=h, backend=backend, length=plan.n)

    @staticmethod
    def build_out_of_core(
        source,
        n: int,
        c: int = 128,
        t: int = 64,
        with_positions: bool = False,
        capacity: Optional[int] = None,
        segment_size: Optional[int] = None,
        packed_pos: Optional[bool] = None,
        summary_dtype: Optional[str] = None,
        backend: str = "jax",
    ) -> "RMQ":
        """Build by streaming fixed-size segments through the fused kernel.

        ``source`` is a sliceable array-like (numpy memmap, array) or a
        callable ``source(start, stop) -> values`` of logical length
        ``n`` — the input never has to exist as one device array during
        level-1 construction
        (:func:`repro.kernels.hierarchy_fused.ops.build_hierarchy_streamed`).
        Under jax x64 mode, position-tracking builds past ``2**31``
        elements store an int64 coordinate plane and queries route
        through the int64-aware pure-JAX walk; without x64 they refuse
        loudly.  Results are bit-identical to :meth:`build`.

        ``backend`` selects the *query* lowering of the returned index
        (default ``'jax'`` — the only walk that is coordinate-exact past
        ``2**31``).
        """
        plan = make_plan(
            n, c=c, t=t, capacity=capacity,
            packed_pos=packed_pos, summary_dtype=summary_dtype,
        )
        from repro.kernels.hierarchy_fused.ops import (
            build_hierarchy_streamed,
        )

        h = build_hierarchy_streamed(
            source, plan, with_positions=with_positions,
            segment_size=segment_size,
        )
        return RMQ(
            hierarchy=h, backend=px.resolve_backend(backend), length=n
        )

    # -- incremental maintenance ------------------------------------------
    def update(self, idxs, vals) -> "RMQ":
        """Batched point updates ``a[idxs] = vals`` (last wins on dups).

        Touches one chunk per level per distinct index — O(B log_c n) —
        instead of rebuilding.
        """
        idxs, vals = px.validate_update_batch(idxs, vals, n=self.n)
        if idxs.shape[0] == 0:
            return self
        h = px.dispatch_update(self.hierarchy, idxs, vals, self.backend)
        return dataclasses.replace(
            self, hierarchy=h, generation=self.generation + 1
        )

    def append(self, vals) -> "RMQ":
        """Grow the array with ``vals`` inside the reserved capacity."""
        vals = px.validate_append_batch(
            vals, length=self.n, capacity=self.plan.capacity
        )
        b = int(vals.shape[0])
        if b == 0:
            return self
        h = px.dispatch_append(
            self.hierarchy, vals, jnp.int32(self.n), self.backend
        )
        return dataclasses.replace(
            self,
            hierarchy=h,
            length=self.n + b,
            generation=self.generation + 1,
        )

    # -- queries ----------------------------------------------------------
    def query(self, ls, rs) -> jax.Array:
        """Batched ``RMQ_value`` over inclusive ranges."""
        ls, rs = check_query_args(ls, rs, self.n)
        return px.dispatch_query_value(self.hierarchy, ls, rs, self.backend)

    def query_index(self, ls, rs) -> jax.Array:
        """Batched ``RMQ_index`` (leftmost minimum) over inclusive ranges."""
        ls, rs = check_query_args(ls, rs, self.n)
        return px.dispatch_query_index(self.hierarchy, ls, rs, self.backend)

    # protocol spellings (RMQIndex): same entry points, canonical names
    query_value_batch = query
    query_index_batch = query_index

    # -- adaptive batched engine -------------------------------------------
    def engine(self, **kwargs) -> "object":
        """A span-routed :class:`repro.qe.QueryEngine` over this index.

        The engine classifies each query by span (short / mid / long),
        executes every class on the cheapest applicable path, dedups
        duplicate queries, and caches results keyed by ``generation`` —
        so it must be re-attached (``engine.attach(new_rmq)``) after
        ``update``/``append``, which return a *successor* index.  See
        ``repro.qe`` for knobs (``cache_size``, ``short_cutoff_chunks``,
        ``long_cutoff``...).
        """
        return px.make_engine(self, **kwargs)

    # -- introspection ----------------------------------------------------
    @property
    def n(self) -> int:
        """Live array length (grows with ``append``)."""
        return self.plan.n if self.length is None else self.length

    @property
    def plan(self) -> HierarchyPlan:
        return self.hierarchy.plan

    @property
    def capacity(self) -> int:
        return self.plan.capacity

    @property
    def with_positions(self) -> bool:
        return self.hierarchy.with_positions

    @property
    def value_dtype(self):
        return self.hierarchy.base.dtype

    def memory_bytes(self) -> int:
        return self.hierarchy.memory_bytes()

    def auxiliary_bytes(self) -> int:
        return self.hierarchy.auxiliary_bytes()


def _resolve_plan(n, c, t, capacity, tuning, span_mix, packed_pos,
                  summary_dtype):
    """``(plan, tuned config or None)`` for a build of ``n`` values: the
    tuning cache's entry when ``c="auto"`` or ``tuning`` is given and it
    has one, else the explicit geometry (``c="auto"`` -> 128)."""
    tuned_cfg = None
    if c == "auto" or tuning is not None:
        from repro.tune import cache as _tc

        store = tuning if tuning is not None else _tc.default_cache()
        tuned_cfg = store.lookup(_tc.current_platform(), n, span_mix)
    if tuned_cfg is None:
        plan = make_plan(
            n, c=128 if c == "auto" else c, t=t, capacity=capacity,
            packed_pos=packed_pos, summary_dtype=summary_dtype,
        )
        return plan, None
    if packed_pos is None:
        packed_pos = getattr(tuned_cfg, "packed_pos", None)
    if summary_dtype is None:
        summary_dtype = getattr(tuned_cfg, "summary_dtype", None)
    plan = make_plan(
        n, c=tuned_cfg.c, t=tuned_cfg.t, capacity=capacity,
        level_split=tuned_cfg.level_split(),
        packed_pos=packed_pos, summary_dtype=summary_dtype,
    )
    return plan, tuned_cfg
