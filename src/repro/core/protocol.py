"""The common index protocol every RMQ implementation speaks.

Four index implementations grew up around the paper's hierarchy —
:class:`repro.core.api.RMQ` (the facade), :class:`repro.streaming.StreamingRMQ`
(sliding windows), :class:`repro.core.hybrid.HybridRMQ` (O(1) sparse-table
top), and :class:`repro.core.distributed.DistributedRMQ` (segment-sharded
across a mesh) — each initially with its own private query/validation/
backend-selection plumbing.  This module is the contract that unifies them
so the layers above (``repro.qe``'s engine/service, ``repro.serve``) route
over *capabilities*, not concrete types:

* :class:`RMQIndex` — the read surface: static ``plan`` geometry, live
  ``length``, a monotonic ``generation`` counter (the cache-invalidation
  key), and the two batched query entry points
  ``query_value_batch`` / ``query_index_batch`` (aliases of the historical
  ``query`` / ``query_index`` names, which remain).
* :class:`MutableRMQIndex` — the optional mutation surface: batched point
  ``update`` and ``append`` into reserved capacity, both returning a
  *successor* index with ``generation + 1`` (every implementation is
  pure-functional).  Probe with :func:`supports_mutation`.
* shared helpers — the previously-duplicated plumbing, now in one place:
  backend resolution (:func:`resolve_backend` /
  :func:`runtime_backend`), input dtype coercion (:func:`coerce_values`),
  the single construction entry point every implementation builds through
  (:func:`build_hierarchy_with_backend`, backends ``'fused'`` /
  ``'pallas'`` / ``'jax'``, plus the vmapped :func:`build_many`),
  query/update backend dispatch (:func:`dispatch_query_value`,
  :func:`dispatch_query_index`, :func:`dispatch_update`,
  :func:`dispatch_append`) and batch validation
  (:func:`validate_update_batch`, :func:`validate_append_batch`).

Which implementation to pick (see README "Choosing an index"):

=================  ==========================================================
``RMQ``            default: build + query + incremental update/append.
``StreamingRMQ``   online arrays: adds sliding-window ``retire``.
``HybridRMQ``      long-span-heavy read-only workloads (O(1) top); usually
                   reached *through* the engine's long-span route instead.
``DistributedRMQ`` arrays past one device's memory: segment-sharded, same
                   protocol (including update/append), engine-routable.
=================  ==========================================================
"""

from __future__ import annotations

from typing import Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core.hierarchy import (
    Hierarchy,
    build_hierarchy,
    build_many,
    finalize_compact,
)
from repro.core.plan import HierarchyPlan
from repro.core.query import _debug_checks_enabled
from repro.obs import trace

__all__ = [
    "RMQIndex",
    "MutableRMQIndex",
    "default_backend",
    "resolve_backend",
    "runtime_backend",
    "mutation_backend",
    "coerce_values",
    "build_hierarchy_with_backend",
    "build_many",
    "capacity_limit_message",
    "check_capacity_limit",
    "dispatch_query_value",
    "dispatch_query_index",
    "dispatch_update",
    "dispatch_append",
    "validate_update_batch",
    "validate_append_batch",
    "live_length",
    "is_distributed",
    "supports_mutation",
    "make_engine",
]

VALUE_DTYPES = (jnp.float32, jnp.bfloat16, jnp.float64)


# ---------------------------------------------------------------------------
# the one capacity guard (previously four slightly-different copies)
# ---------------------------------------------------------------------------
def capacity_limit_message(capacity: int) -> str:
    """The canonical int32-capacity error text, shared by every guard site.

    Pinned byte-identical in ``test_protocol.py`` — the engine, the
    distributed build, and both Pallas kernel packages must all raise
    exactly this string (guard drift across those sites is how capacity
    bugs hid before the guard was centralized).
    """
    return (
        f"capacity {capacity} exceeds the int32 query index space; "
        "capacities >= 2**31 need jax x64 mode and the int64-coordinate "
        "jax path (DistributedRMQ or backend='jax' builds)"
    )


def check_capacity_limit(capacity: int, allow_x64: bool = False) -> None:
    """Reject capacities past the int32 query index space.

    ``allow_x64=True`` marks call sites that *can* serve int64
    coordinates (the distributed build, and the engine over a sharded
    index: its keys widen past 2^31 and its segment-local coordinates
    stay int32): they pass when x64 mode is enabled.  Strict sites (the
    Pallas kernels, the engine over a single hierarchy) always reject —
    their lowerings index in int32.
    """
    if capacity < 2**31:
        return
    if allow_x64 and jax.config.x64_enabled:
        return
    raise ValueError(capacity_limit_message(capacity))


# ---------------------------------------------------------------------------
# the protocol
# ---------------------------------------------------------------------------
@runtime_checkable
class RMQIndex(Protocol):
    """Read surface shared by every RMQ index implementation.

    ``plan`` is the static level geometry (for the sharded index: the
    *per-segment* plan — use ``capacity`` for the total addressable index
    space).  ``length`` is the live element count (may be ``None`` on
    implementations whose live length equals the build length; use
    :func:`live_length` to normalize).  ``generation`` increments on every
    mutation, keying engine result caches to the array version.
    """

    backend: str

    @property
    def plan(self) -> HierarchyPlan: ...

    @property
    def length(self) -> Optional[int]: ...

    @property
    def generation(self) -> int: ...

    @property
    def value_dtype(self): ...

    @property
    def capacity(self) -> int: ...

    @property
    def with_positions(self) -> bool: ...

    def query_value_batch(self, ls, rs) -> jax.Array: ...

    def query_index_batch(self, ls, rs) -> jax.Array: ...


@runtime_checkable
class MutableRMQIndex(RMQIndex, Protocol):
    """Optional mutation surface: pure-functional batched maintenance.

    Both mutators return a *successor* index sharing unmodified buffers,
    with ``generation`` bumped by one; the receiver is unchanged.  Cost is
    O(batch · log_c n) chunk re-reductions — never a rebuild.
    """

    def update(self, idxs, vals) -> "MutableRMQIndex": ...

    def append(self, vals) -> "MutableRMQIndex": ...


def supports_mutation(index) -> bool:
    """Does ``index`` expose the ``update``/``append`` capability?"""
    return isinstance(index, MutableRMQIndex)


def is_distributed(index) -> bool:
    """Is ``index`` a mesh-sharded implementation (no local hierarchy)?

    Distributed indices answer queries through sharded per-segment
    hierarchies; the engine routes them through the distributed executor
    (segment-local fast path + all-reduce for crossing spans) instead of
    the single-hierarchy span executors.
    """
    return bool(getattr(index, "distributed", False))


def live_length(index) -> int:
    """The live element count, normalized across implementations.

    ``RMQ`` permits ``length=None`` meaning "the build length" (on
    directly-constructed instances; ``RMQ.build`` always sets it), so a
    plain ``.length`` read is not universally an int — use this helper.
    """
    length = getattr(index, "length", None)
    if length is not None:
        return int(length)
    n = getattr(index, "n", None)
    if n is not None:
        return int(n)
    return int(index.plan.n)


# ---------------------------------------------------------------------------
# backend selection + input coercion (previously duplicated per facade)
# ---------------------------------------------------------------------------
def default_backend() -> str:
    """Pallas kernels on TPU, the pure-JAX reference elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "jax"


def resolve_backend(backend: str) -> str:
    """Normalize a user-facing backend name (``"auto"`` included).

    ``"fused"`` selects the single-launch pipelines on both phases:
    construction through ``kernels/hierarchy_fused`` (one launch per
    build) and queries through ``kernels/rmq_fused`` (one launch per
    batch, every span class, value and index ops alike).  Incremental
    updates/appends have no fused lowering and fall through to the
    platform default (:func:`mutation_backend`).
    """
    if backend == "auto":
        return default_backend()
    if backend not in ("jax", "pallas", "fused"):
        raise ValueError(f"unknown backend {backend!r}")
    return backend


def runtime_backend(backend: str) -> str:
    """The query lowering behind a resolved backend name.

    ``"fused"`` is a *runtime* backend since the fused query kernel
    landed: batched queries on a fused index run through
    ``kernels/rmq_fused`` (the whole batch in one launch), so it passes
    through unchanged — as do ``"jax"``/``"pallas"``.  (Historically
    ``"fused"`` was construction-only and degraded to the platform
    default here.)  Mutations still degrade: see
    :func:`mutation_backend`.
    """
    return backend


def mutation_backend(backend: str) -> str:
    """The update/append lowering behind a resolved backend name.

    The fused pipelines cover construction and queries; incremental
    chunk re-reductions are per-touched-chunk work with no single-launch
    shape to exploit, so ``"fused"`` indexes mutate through the platform
    default (``hierarchy_update`` on TPU, pure JAX elsewhere) — the
    successor hierarchy is bit-identical either way.
    """
    if backend == "fused":
        return default_backend()
    return backend


def coerce_values(x) -> jax.Array:
    """The input array as a supported 1-D float dtype."""
    x = jnp.asarray(x)
    if x.ndim != 1:
        raise ValueError(f"input must be rank-1, got shape {x.shape}")
    if x.dtype not in VALUE_DTYPES:
        x = x.astype(jnp.float32)
    return x


def build_hierarchy_with_backend(
    x: jax.Array,
    plan: HierarchyPlan,
    with_positions: bool,
    backend: str,
) -> Hierarchy:
    """The one construction entry point every index implementation uses.

    All three backends produce bit-identical hierarchies (values,
    leftmost-tie positions, and padding):

    * ``"fused"`` — ``kernels/hierarchy_fused``: every upper level in ONE
      Pallas launch, the ``upper`` buffer VMEM-resident throughout;
    * ``"pallas"`` — ``kernels/hierarchy_build``: one launch per level;
    * ``"jax"`` — the pure-JAX oracle (single fused pass into a
      preallocated buffer since the pipeline refactor).

    Compact plane layouts (``plan.packed_pos`` / ``plan.summary_dtype``)
    are applied uniformly: the jax oracle builds them natively; the
    Pallas backends build the classic layout and run through
    :func:`repro.core.hierarchy.finalize_compact`.
    """
    from repro.core.hierarchy import _check_compact_build

    _check_compact_build(plan, with_positions, x.dtype)
    # device work is asynchronous: the span is the host's dispatch alone
    with trace.span("build_dispatch", backend=backend):
        if backend == "fused":
            from repro.kernels.hierarchy_fused import ops as fused_ops

            return finalize_compact(fused_ops.build_hierarchy_fused(
                x, plan, with_positions=with_positions
            ))
        if backend == "pallas":
            from repro.kernels.hierarchy_build import ops as build_ops

            return finalize_compact(build_ops.build_hierarchy_pallas(
                x, plan, with_positions=with_positions
            ))
        if backend == "jax":
            return build_hierarchy(x, plan, with_positions=with_positions)
    raise ValueError(f"unknown backend {backend!r}")




# ---------------------------------------------------------------------------
# query dispatch (previously duplicated in api.py / structure.py)
# ---------------------------------------------------------------------------
def _run_dispatch(kind: str, backend: str, fn, *args) -> jax.Array:
    # guarded span (not trace.span): dispatch helpers sit on the per-call
    # query path, so with tracing disabled this must stay one global load
    tr = trace.current()
    if tr is None:
        return fn(*args)
    sp = tr.begin("dispatch")
    out = fn(*args)
    tr.end(sp, kind=kind, backend=backend)
    return out


def dispatch_query_value(h: Hierarchy, ls, rs, backend: str) -> jax.Array:
    """Batched ``RMQ_value`` through the chosen backend."""
    backend = runtime_backend(backend)
    if backend == "fused":
        from repro.kernels.rmq_fused import ops as fused_ops

        fn = fused_ops.rmq_fused_value_batch
    elif backend == "pallas":
        from repro.kernels.rmq_scan import ops as scan_ops

        fn = scan_ops.rmq_value_batch_pallas
    else:
        from repro.core.query import rmq_value_batch

        fn = rmq_value_batch
    return _run_dispatch("query_value", backend, fn, h, ls, rs)


def dispatch_query_index(h: Hierarchy, ls, rs, backend: str) -> jax.Array:
    """Batched ``RMQ_index`` (leftmost minimum) through the chosen backend."""
    backend = runtime_backend(backend)
    if backend == "fused":
        from repro.kernels.rmq_fused import ops as fused_ops

        fn = fused_ops.rmq_fused_index_batch
    elif backend == "pallas":
        from repro.kernels.rmq_scan import ops as scan_ops

        fn = scan_ops.rmq_index_batch_pallas
    else:
        from repro.core.query import rmq_index_batch

        fn = rmq_index_batch
    return _run_dispatch("query_index", backend, fn, h, ls, rs)


# ---------------------------------------------------------------------------
# mutation dispatch + validation (shared by all mutable implementations)
# ---------------------------------------------------------------------------
def dispatch_update(h: Hierarchy, idxs, vals, backend: str) -> Hierarchy:
    """Backend dispatch for batched point updates."""
    backend = mutation_backend(backend)
    if backend == "pallas":
        from repro.kernels.hierarchy_update import ops as upd_ops

        fn = upd_ops.update_hierarchy_pallas
    else:
        from repro.streaming import updates as U

        fn = U.update_hierarchy
    return _run_dispatch("update", backend, fn, h, idxs, vals)


def dispatch_append(h: Hierarchy, vals, start, backend: str) -> Hierarchy:
    """Backend dispatch for appends at live offset ``start``."""
    backend = mutation_backend(backend)
    if backend == "pallas":
        from repro.kernels.hierarchy_update import ops as upd_ops

        fn = upd_ops.append_hierarchy_pallas
    else:
        from repro.streaming import updates as U

        fn = U.append_hierarchy
    return _run_dispatch("append", backend, fn, h, vals, start)


def validate_update_batch(idxs, vals, n: Optional[int] = None):
    """Shared idxs/vals checking for every ``update`` entry point.

    Out-of-range indices are dropped silently in normal operation (a
    jit-friendly contract); under ``REPRO_RMQ_DEBUG=1`` concrete batches
    are value-checked against the live length ``n`` so indexing bugs
    fail loudly instead of as stale minima — mirroring query validation.
    """
    idxs = jnp.asarray(idxs)
    vals = jnp.asarray(vals)
    if idxs.ndim != 1 or idxs.shape != vals.shape:
        raise ValueError(
            f"idxs/vals must be matching 1-D batches, got "
            f"{idxs.shape} vs {vals.shape}"
        )
    if not jnp.issubdtype(idxs.dtype, jnp.integer):
        raise TypeError(f"idxs must be integers, got {idxs.dtype}")
    if (
        n is not None
        and _debug_checks_enabled()
        and not isinstance(idxs, jax.core.Tracer)
    ):
        import numpy as np

        i_np = np.asarray(idxs)
        bad = (i_np < 0) | (i_np >= n)
        if bad.any():
            j = int(np.argmax(bad))
            raise ValueError(
                f"update index {j} = {i_np.flat[j]} out of range for "
                f"live length {n}"
            )
    return idxs, vals


def validate_append_batch(vals, length: int, capacity: int) -> jax.Array:
    """Shared vals checking for every ``append`` entry point.

    Rejects non-1-D batches and appends that would overflow the reserved
    capacity (the level geometry is capacity-derived, so growing past it
    would need a new plan — i.e. a rebuild, which ``append`` must never
    silently do).
    """
    vals = jnp.asarray(vals)
    if vals.ndim != 1:
        raise ValueError(f"vals must be 1-D, got shape {vals.shape}")
    b = int(vals.shape[0])
    if length + b > capacity:
        raise ValueError(
            f"append of {b} overflows capacity {capacity} (live length "
            f"{length}); build with a larger capacity reservation"
        )
    return vals


# ---------------------------------------------------------------------------
# engine hook (shared by every implementation's .engine())
# ---------------------------------------------------------------------------
def make_engine(index, **kwargs):
    """A span-routed :class:`repro.qe.QueryEngine` over ``index``.

    The engine classifies queries, executes each class on the cheapest
    applicable path (for distributed indices: segment-local answering
    without the all-reduce where possible), dedups duplicates, and caches
    results keyed by ``generation`` — re-attach (``engine.attach``) after
    any mutation, which returns a *successor* index.
    """
    from repro.qe import QueryEngine

    return QueryEngine.for_index(index, **kwargs)
