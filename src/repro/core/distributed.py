"""Distributed RMQ: segment-sharded hierarchies + min all-reduce.

This is the piece that removes the paper's central limitation — the single
GPU's memory ceiling (LCA/RTXRMQ die at n = 2^28..2^29 on 24 GB; GPU-RMQ
itself is capped at n = 2^31 on a 4090, §5.5).  We shard the input array
into contiguous segments across a mesh axis (default ``"model"``); each
device owns one segment plus its private minima hierarchy (auxiliary
memory stays n_local/(c-1) per device).  A query batch is sharded across
the remaining axes (``"data"``, ``"pod"``) and *replicated* across the
segment axis; every device answers the intersection of each query with its
segment using the paper's algorithm, and a single ``pmin`` over the segment
axis combines per-segment minima.

Communication cost per batch: one all-reduce(min) of ``batch_local``
floats over the segment axis — independent of n — and, for index
queries, two more of int32 segment ids and segment-local positions
(:func:`_combine`).  Per-device memory scales down linearly with the
number of segments, so the index is bounded by the mesh's memory, not
one device's: past 2^31 global coordinates run in int64 under x64
while each segment's own coordinates stay int32.

``build`` takes the input already sharded over the segment axis (a
``jax.Array`` with that sharding, or host data, which goes to each
device as its own slice): no device ever holds more than its segment.

``DistributedRMQ`` implements the full
:class:`repro.core.protocol.MutableRMQIndex` protocol:

* **streaming mutation** — :meth:`update` and :meth:`append` route each
  batch to the owning segment under the same ``shard_map`` and re-reduce
  shard-locally through the ``repro.streaming`` update machinery
  (scatter + O(batch · log_c n_local) chunk re-reductions).  The batch is
  replicated over the segment axis and every non-owned index is dropped by
  the scatter's out-of-range semantics, so updates need **zero**
  cross-segment communication and never rebuild.  Mutators return a
  successor with ``generation + 1``.
* **engine routing** — ``repro.qe``'s engine accepts a ``DistributedRMQ``
  through the same ``attach()``/``register()`` surface as every other
  index; spans that fall entirely inside one segment are answered
  segment-locally (:meth:`_query_grouped` — no ``pmin`` at all), only
  segment-crossing spans pay the all-reduce.

Reserve headroom for appends with ``build(..., capacity=)``: each segment
reserves ``ceil(capacity / S)`` +inf-padded slots and element ``g`` lives
in segment ``g // segment_capacity`` — appends land on the tail segments.

The same code path runs on the production meshes via ``shard_map`` and on
a single CPU device (1×1 mesh) for tests.  Query/position arithmetic runs
in a *coordinate dtype* derived from the total capacity: int32 below
2**31 (bit-identical to the historical stack), int64 past it **when jax
x64 mode is on** — segment starts and globalized positions widen
together, so the paper's index-space ceiling lifts with the memory
ceiling; each segment's own coordinates stay int32.  Without x64,
``build`` (and the engine's ``attach``) refuse total capacities at or
past 2**31 (the shared ``repro.core.protocol.check_capacity_limit``
guard) rather than letting bounds wrap silently.

Compact layouts ride along: ``build(..., packed_pos=True)`` stores each
segment's position plane as log2(c)-bit packed words and
``summary_dtype='bfloat16'`` halves the upper value planes (the sharded
walks carry the position plane even for value-only batches then — exact
recovery re-reads level 0 through the stored positions).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import protocol as px
from repro.core.hierarchy import pos_dtype_for
from repro.core.plan import HierarchyPlan, make_plan
from repro.core.query import _rmq_batch, check_query_args

__all__ = ["DistributedRMQ"]


def _num_segments(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


# ---------------------------------------------------------------------------
# persistent jitted collectives, one per (mesh, geometry) — successor
# indices produced by update/append reuse the same compiled executables
# instead of retracing per call.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=64)
def _build_fn(mesh: Mesh, seg: str, plan: HierarchyPlan,
              with_positions: bool, backend: str):
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=P(seg),
        out_specs=(
            P(seg),
            P(seg),
            P(seg) if with_positions else P(),
        ),
        check_vma=False,
    )
    def build_local(x_local):
        # Shard-local construction through the shared pipeline: with
        # backend='fused' every device builds its whole segment hierarchy
        # in ONE kernel launch under the same shard_map.
        h = px.build_hierarchy_with_backend(
            x_local, plan, with_positions=with_positions, backend=backend
        )
        pos = (
            h.upper_pos
            if with_positions
            else jnp.zeros((), dtype=jnp.int32)
        )
        return h.base, h.upper, pos

    return jax.jit(build_local)


def _segment_sharded(x, sharding: NamedSharding, length: int) -> jax.Array:
    """``x`` as a ``(length,)`` array sharded over the segment axis,
    padded with +inf, with no device ever holding more than its segment.

    A ``jax.Array`` laid out so already is used as it is; another is
    padded and resharded by one jitted program with a sharded output.
    Host data goes to each device as that device's own slice.
    """
    if isinstance(x, jax.Array):
        if x.dtype not in px.VALUE_DTYPES:
            x = x.astype(jnp.float32)
        if x.shape[0] == length and x.sharding.is_equivalent_to(sharding, 1):
            return x
        pad = length - x.shape[0]
        return jax.jit(
            lambda v: jnp.pad(v, (0, pad), constant_values=jnp.inf),
            out_shardings=sharding,
        )(x)
    dtype = jax.dtypes.canonicalize_dtype(x.dtype)
    if dtype not in px.VALUE_DTYPES:
        dtype = np.dtype(np.float32)
    n = x.shape[0]

    def piece(index):
        lo, hi, _ = index[0].indices(length)
        out = np.full(hi - lo, np.inf, dtype)
        live = x[lo:min(hi, n)]
        out[: live.shape[0]] = live
        return out

    return jax.make_array_from_callback((length,), sharding, piece)


def _need_pos_plane(plan: HierarchyPlan, track: bool) -> bool:
    """Whether the sharded walk must carry the position plane.

    bf16 summaries need it even for value-only batches: exact recovery
    re-reads level 0 through the stored positions.
    """
    return track or plan.summary_dtype == "bfloat16"


def _local_rmq(plan: HierarchyPlan, base_l, upper_l, pos_l, ls, rs,
               track: bool, backend: str):
    """Shard-local batched RMQ behind the sharded walks.

    ``backend='fused'`` routes through ``kernels/rmq_fused`` — each
    device answers its whole (sub)batch in ONE fused dispatch (the
    engine's segment-contained fast path then costs one launch per
    device and still no collective); every other backend takes the
    pure-JAX walk.  Results are bit-identical either way.
    """
    need_pos = _need_pos_plane(plan, track)
    if backend == "fused":
        from repro.core.hierarchy import Hierarchy
        from repro.kernels.rmq_fused import ops as fused_ops

        h = Hierarchy(
            base=base_l,
            upper=upper_l,
            upper_pos=pos_l if need_pos else None,
            plan=plan,
        )
        m, p = fused_ops.rmq_fused_batch(h, ls, rs, track_pos=track)
        if not track:
            p = jnp.zeros_like(ls)
        return m, p
    return _rmq_batch(
        plan, base_l, upper_l, pos_l if need_pos else None, ls, rs,
        track_pos=track,
    )


@functools.lru_cache(maxsize=64)
def _allreduce_query_fn(mesh: Mesh, seg: str, qaxes: Tuple[str, ...],
                        plan: HierarchyPlan, track: bool, backend: str):
    """The monolithic query path: every segment answers its intersection,
    one ``pmin`` over the segment axis combines."""
    n_local = plan.capacity
    # Coordinate dtype of the GLOBAL index space: int64 past 2**31 under
    # x64, int32 (the historical arithmetic, bit-identical) below.
    nseg = mesh.shape[seg]
    coord = pos_dtype_for(n_local * nseg, strict=False)
    lcoord = pos_dtype_for(n_local, strict=False)
    need_pos = _need_pos_plane(plan, track)
    qspec = P(qaxes)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(seg),
            P(seg),
            P(seg) if need_pos else P(),
            qspec,
            qspec,
        ),
        out_specs=(qspec, qspec),
        check_vma=False,
    )
    def go(base_l, upper_l, pos_l, ls_l, rs_l):
        seg_idx = jax.lax.axis_index(seg).astype(jnp.int32)
        # Widen BEFORE the multiply: seg_idx * n_local wraps int32 past
        # 2**31 even when every operand fits individually.
        seg_start = seg_idx.astype(coord) * n_local
        ls_c = ls_l.astype(coord)
        rs_c = rs_l.astype(coord)
        # Intersect each global range with this segment; clip in the
        # global coordinate dtype, THEN narrow (a bare cast could wrap a
        # far-away bound back into local range).
        ll = jnp.clip(ls_c - seg_start, 0, n_local - 1).astype(lcoord)
        rr = jnp.clip(rs_c - seg_start, 0, n_local - 1).astype(lcoord)
        nonempty = (rs_c >= seg_start) & (ls_c < seg_start + n_local)
        m, p = _local_rmq(
            plan, base_l, upper_l, pos_l, ll, rr, track, backend
        )
        inf = jnp.array(jnp.inf, dtype=m.dtype)
        m = jnp.where(nonempty, m, inf)
        mins, win, p = _combine(m, p, nonempty, seg_idx, seg, nseg, track)
        if track:
            return mins, win.astype(coord) * n_local + p.astype(coord)
        return mins, jnp.zeros_like(ls_l)

    return jax.jit(go)


def _combine(m, p, nonempty, seg_idx, seg: str, nseg: int, track: bool):
    """Combine per-segment ``(value, local position)`` candidates.

    Returns the minimum and, when ``track``, the leftmost segment that
    holds it and that segment's leftmost local position.  Segments are
    ordered by their global starts, so (segment, local position) is the
    leftmost global position.  The collectives carry values, segment ids
    and segment-local positions, never global coordinates (int32 unless
    one segment passes 2^31); the caller globalizes.  The scope names the
    all-reduces in device traces.
    """
    with jax.named_scope("rmq_pmin"):
        mins = jax.lax.pmin(m, seg)
        if not track:
            return mins, None, None
        holds = nonempty & (m == mins)
        win = jax.lax.pmin(jnp.where(holds, seg_idx, nseg), seg)
        big = jnp.array(jnp.iinfo(p.dtype).max, p.dtype)
        p = jax.lax.pmin(jnp.where(seg_idx == win, p, big), seg)
    return mins, win, p


@functools.lru_cache(maxsize=64)
def _grouped_query_fn(mesh: Mesh, seg: str, plan: HierarchyPlan,
                      track: bool, backend: str):
    """Segment-local answering: the query batch arrives pre-grouped by
    owning segment as ``(S, k)`` *local* bounds sharded over the segment
    axis, each device answers only its own row, and no collective runs at
    all — this is the engine's fast path for spans contained in one
    segment."""
    n_local = plan.capacity
    coord = pos_dtype_for(n_local * mesh.shape[seg], strict=False)
    need_pos = _need_pos_plane(plan, track)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(seg),
            P(seg),
            P(seg) if need_pos else P(),
            P(seg),
            P(seg),
        ),
        out_specs=(P(seg), P(seg)),
        check_vma=False,
    )
    def go(base_l, upper_l, pos_l, ls_l, rs_l):
        seg_idx = jax.lax.axis_index(seg)
        seg_start = seg_idx.astype(coord) * n_local
        m, p = _local_rmq(
            plan, base_l, upper_l, pos_l, ls_l[0], rs_l[0], track, backend
        )
        if track:
            p = p.astype(coord) + seg_start  # globalize leftmost positions
        else:
            p = jnp.zeros_like(m, dtype=jnp.int32)
        return m[None, :], p[None, :]

    return jax.jit(go)


@functools.lru_cache(maxsize=64)
def _mutate_fn(mesh: Mesh, seg: str, plan: HierarchyPlan, track: bool):
    """Sharded batched point mutation: the (idxs, vals) batch is replicated
    over the segment axis; each device localizes the indices, the base
    scatter drops everything outside its segment, and the streaming
    machinery re-reduces only the touched shard-local chunks.  No
    collective — updates are communication-free."""
    from repro.streaming.updates import propagate_updates, scatter_base

    n_local = plan.capacity
    coord = pos_dtype_for(n_local * mesh.shape[seg], strict=False)
    lcoord = pos_dtype_for(n_local, strict=False)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(seg),
            P(seg),
            P(seg) if track else P(),
            P(),
            P(),
        ),
        out_specs=(
            P(seg),
            P(seg),
            P(seg) if track else P(),
        ),
        check_vma=False,
    )
    def go(base_l, upper_l, pos_l, idxs, vals):
        seg_idx = jax.lax.axis_index(seg)
        seg_start = seg_idx.astype(coord) * n_local
        # Localize in the global coordinate dtype, clamp out-of-segment
        # indices to the dropped sentinels BEFORE narrowing — a bare
        # int64->int32 cast could wrap a foreign index back into range.
        local = jnp.clip(
            idxs.astype(coord) - seg_start, -1, n_local
        ).astype(lcoord)
        # scatter_base drops local indices outside [0, n_local) — i.e.
        # every index another segment owns; propagate_updates routes their
        # chunk ids to an idempotent chunk-0 re-reduction, so each device
        # does identical-shape work on its own slice only.
        base2 = scatter_base(base_l, local, vals)
        upper2, pos2 = propagate_updates(
            plan, base2, upper_l, pos_l if track else None, local
        )
        if not track:
            pos2 = jnp.zeros((), dtype=jnp.int32)
        return base2, upper2, pos2

    return jax.jit(go)


@dataclasses.dataclass(frozen=True)
class DistributedRMQ:
    """Segment-sharded RMQ index living on a device mesh."""

    base: jax.Array          # (S * segment_capacity,) sharded over seg axis
    upper: jax.Array         # (S * upper_local,) sharded over seg axis
    upper_pos: Optional[jax.Array]
    local_plan: HierarchyPlan
    mesh: Mesh
    segment_axis: str
    query_axes: Tuple[str, ...]
    n: int                   # logical (unpadded) live length
    # Monotonic mutation counter (host-side, never traced): bumped by
    # update/append so engine result caches invalidate correctly.
    generation: int = 0
    # Runtime backend of the shard-local query walks: 'fused' answers
    # each device's (sub)batch in one rmq_fused dispatch, everything
    # else takes the pure-JAX walk under the same shard_map.  Mutations
    # are pure JAX on every backend.
    backend: str = "jax"

    # protocol marker: the engine routes distributed indices through the
    # segment-local/crossing executor instead of the span executors.
    distributed = True

    # -- construction -----------------------------------------------------
    @staticmethod
    def build(
        x,
        mesh: Mesh,
        segment_axis: str = "model",
        query_axes: Tuple[str, ...] = ("data",),
        c: int = 128,
        t: int = 64,
        with_positions: bool = False,
        capacity: Optional[int] = None,
        backend: str = "auto",
        packed_pos: Optional[bool] = None,
        summary_dtype: Optional[str] = None,
    ) -> "DistributedRMQ":
        """Build over ``x``; pass ``capacity > len(x)`` to allow appends.

        ``x`` is host data or a ``jax.Array``; one already sharded over
        ``segment_axis`` with the padded length is used in place, so an
        array past one device's memory is made segment by segment on
        the devices and handed in as it is.

        ``capacity`` is the *global* reservation: each segment reserves
        ``ceil(capacity / S)`` +inf-padded slots and the level geometry is
        derived from that, so appends up to ``capacity`` reuse every jit
        specialization (same contract as ``RMQ``/``StreamingRMQ``).

        ``backend`` selects the shard-local *construction* path (the
        shared ``'fused'``/``'pallas'``/``'jax'`` pipeline) and, for
        ``'fused'``, the shard-local *query* lowering too: each device
        answers its (sub)batch in one ``kernels/rmq_fused`` dispatch
        under the same ``shard_map``.  Updates/appends are pure JAX on
        every backend.

        ``packed_pos``/``summary_dtype`` select the compact per-segment
        layouts (log2(c)-bit packed position planes, bf16 value
        summaries with exact recovery) — same semantics as
        ``make_plan``; ``None`` defers to the tuning cache.
        """
        if not isinstance(x, jax.Array):
            x = np.asarray(x)
        if x.ndim != 1:
            raise ValueError(f"input must be rank-1, got shape {x.shape}")
        n = int(x.shape[0])
        s = _num_segments(mesh, segment_axis)
        if capacity is None:
            capacity = n
        if capacity < n:
            raise ValueError(f"capacity {capacity} < n {n}")
        cap_local = -(-capacity // s)
        cap_padded = cap_local * s
        # Bounds, positions and update indices flow through the
        # coordinate dtype — int32 below 2**31, int64 past it under x64.
        # Without x64 the shared guard refuses loudly rather than wrap
        # (mirrors the engine's attach-time contract).
        px.check_capacity_limit(cap_padded, allow_x64=True)
        local_plan = make_plan(
            cap_local, c=c, t=t,
            packed_pos=packed_pos, summary_dtype=summary_dtype,
        )

        backend = px.resolve_backend(backend)
        x = _segment_sharded(
            x, NamedSharding(mesh, P(segment_axis)), cap_padded)
        base, upper, pos = _build_fn(
            mesh, segment_axis, local_plan, with_positions, backend
        )(x)
        return DistributedRMQ(
            base=base,
            upper=upper,
            upper_pos=pos if with_positions else None,
            local_plan=local_plan,
            mesh=mesh,
            segment_axis=segment_axis,
            query_axes=tuple(query_axes),
            n=n,
            backend=backend,
        )

    # -- incremental maintenance ------------------------------------------
    def _mutate(self, idxs, vals) -> Tuple[jax.Array, ...]:
        """Run the sharded scatter + shard-local re-reduction."""
        track = self.with_positions
        repl = NamedSharding(self.mesh, P())
        coord = pos_dtype_for(self.capacity, strict=False)
        idxs = jax.device_put(jnp.asarray(idxs, coord), repl)
        vals = jax.device_put(jnp.asarray(vals), repl)
        pos_in = (
            self.upper_pos if track else jnp.zeros((), dtype=jnp.int32)
        )
        return _mutate_fn(
            self.mesh, self.segment_axis, self.local_plan, track
        )(self.base, self.upper, pos_in, idxs, vals)

    def update(self, idxs, vals) -> "DistributedRMQ":
        """Batched point updates ``a[idxs] = vals`` (last wins on dups).

        Global indices; each lands on its owning segment and re-reduces
        O(log_c n_local) shard-local chunks.  No cross-segment traffic.
        """
        idxs, vals = px.validate_update_batch(idxs, vals, n=self.n)
        if idxs.shape[0] == 0:
            return self
        base, upper, pos = self._mutate(idxs, vals)
        return dataclasses.replace(
            self,
            base=base,
            upper=upper,
            upper_pos=pos if self.with_positions else None,
            generation=self.generation + 1,
        )

    def append(self, vals) -> "DistributedRMQ":
        """Grow the array with ``vals`` inside the reserved capacity.

        Appends are point updates over the +inf-reserved tail: positions
        ``[n, n + B)`` are routed to their owning segment(s) — a batch may
        straddle a segment boundary — and repaired shard-locally.
        """
        vals = px.validate_append_batch(
            vals, length=self.n, capacity=self.capacity
        )
        b = int(vals.shape[0])
        if b == 0:
            return self
        coord = pos_dtype_for(self.capacity, strict=False)
        idxs = self.n + jnp.arange(b, dtype=coord)
        base, upper, pos = self._mutate(idxs, vals)
        return dataclasses.replace(
            self,
            base=base,
            upper=upper,
            upper_pos=pos if self.with_positions else None,
            n=self.n + b,
            generation=self.generation + 1,
        )

    # -- queries ----------------------------------------------------------
    def query(self, ls, rs) -> jax.Array:
        """Batched RMQ_value over global inclusive ranges."""
        return self._query(ls, rs, track_pos=False)[0]

    def query_index(self, ls, rs) -> jax.Array:
        if self.upper_pos is None:
            raise ValueError("built without positions")
        return self._query(ls, rs, track_pos=True)[1]

    # protocol spellings (RMQIndex): same entry points, canonical names
    query_value_batch = query
    query_index_batch = query_index

    def _query(self, ls, rs, track_pos: bool):
        ls, rs = check_query_args(ls, rs, self.n)
        mesh = self.mesh
        qspec = P(self.query_axes)
        coord = pos_dtype_for(self.capacity, strict=False)
        ls = jnp.asarray(ls, dtype=coord)
        rs = jnp.asarray(rs, dtype=coord)
        # The batch is sharded over the query axes, so its size must
        # divide evenly; pad with (0, 0) sentinels (valid on any
        # non-empty array) and slice the results back.
        m = int(ls.shape[0])
        q = 1
        for a in self.query_axes:
            q *= mesh.shape[a]
        pad = (-m) % q
        if pad:
            ls = jnp.pad(ls, (0, pad))
            rs = jnp.pad(rs, (0, pad))
        ls = jax.device_put(ls, NamedSharding(mesh, qspec))
        rs = jax.device_put(rs, NamedSharding(mesh, qspec))
        pos_in = (
            self.upper_pos
            if _need_pos_plane(self.local_plan, track_pos)
            else jnp.zeros((0,), dtype=jnp.int32)
        )
        fn = _allreduce_query_fn(
            mesh, self.segment_axis, self.query_axes, self.local_plan,
            track_pos, self.backend,
        )
        vals, poss = fn(self.base, self.upper, pos_in, ls, rs)
        if pad:
            vals, poss = vals[:m], poss[:m]
        return vals, poss

    def _query_grouped(self, ls_local, rs_local, track_pos: bool):
        """Answer pre-grouped segment-local queries without the all-reduce.

        ``ls_local``/``rs_local`` are ``(S, k)`` arrays of *segment-local*
        inclusive bounds — row ``i`` holds only queries whose global range
        falls entirely inside segment ``i`` (pad unused slots with
        ``(0, 0)``; their results are garbage to be dropped by the
        caller).  Returns ``(S, k)`` values and *global* leftmost
        positions.  This is the engine's fast path: zero cross-device
        communication.
        """
        if track_pos and self.upper_pos is None:
            raise ValueError("built without positions")
        mesh = self.mesh
        seg = self.segment_axis
        s = self.num_segments
        ls_local = jnp.asarray(ls_local, jnp.int32)
        rs_local = jnp.asarray(rs_local, jnp.int32)
        if ls_local.ndim != 2 or ls_local.shape[0] != s:
            raise ValueError(
                f"grouped bounds must be (num_segments={s}, k), got "
                f"{ls_local.shape}"
            )
        sh = NamedSharding(mesh, P(seg))
        ls_local = jax.device_put(ls_local, sh)
        rs_local = jax.device_put(rs_local, sh)
        pos_in = (
            self.upper_pos
            if _need_pos_plane(self.local_plan, track_pos)
            else jnp.zeros((0,), dtype=jnp.int32)
        )
        fn = _grouped_query_fn(
            mesh, seg, self.local_plan, track_pos, self.backend
        )
        return fn(self.base, self.upper, pos_in, ls_local, rs_local)

    # -- adaptive batched engine -------------------------------------------
    def engine(self, **kwargs):
        """A :class:`repro.qe.QueryEngine` routed over this sharded index.

        Spans contained in one segment are answered segment-locally (no
        all-reduce); crossing spans take the ``pmin`` path.  Results are
        bit-identical to :meth:`query`/:meth:`query_index`.  Re-attach
        after ``update``/``append`` (successors bump ``generation``).
        """
        return px.make_engine(self, **kwargs)

    # -- introspection ------------------------------------------------------
    @property
    def plan(self) -> HierarchyPlan:
        """The *per-segment* plan (see ``capacity`` for the global space)."""
        return self.local_plan

    @property
    def length(self) -> int:
        return self.n

    @property
    def num_segments(self) -> int:
        return _num_segments(self.mesh, self.segment_axis)

    @property
    def segment_capacity(self) -> int:
        """Slots per segment; element ``g`` lives in segment
        ``g // segment_capacity``."""
        return self.local_plan.capacity

    @property
    def capacity(self) -> int:
        """Total reserved (appendable) index space across segments."""
        return self.segment_capacity * self.num_segments

    @property
    def with_positions(self) -> bool:
        return self.upper_pos is not None

    @property
    def value_dtype(self):
        return self.base.dtype

    def memory_bytes_per_device(self) -> int:
        s = self.num_segments
        total = self.base.size * self.base.dtype.itemsize
        total += self.upper.size * self.upper.dtype.itemsize
        if self.upper_pos is not None:
            total += self.upper_pos.size * self.upper_pos.dtype.itemsize
        return total // s
