"""Per-span-class executors holding persistent jitted callables.

Each executor owns the dispatch for one planner class and keeps a table
of bound callables keyed by ``(op, bucket shape)`` — the underlying
functions are module-level ``jax.jit`` specializations (static plan +
shape), so a (plan, shape, op) triple traces exactly once and every
later bucket with the same shape reuses the compiled executable.  The
table doubles as the retrace ledger surfaced in engine stats.

Backend dispatch mirrors the facade: ``backend="pallas"`` routes short
spans to the ``rmq_short`` kernel and mid spans to the ``rmq_scan``
kernel; ``backend="jax"`` uses the pure-JAX paths.  The long executor's
hybrid walk is pure JAX on either backend (its win is algorithmic — an
O(1) top — not a lowering).

``backend="fused"`` replaces the whole per-class trio with
:class:`FusedExecutor`: one ``kernels/rmq_fused`` dispatch answers the
entire bucket — every span class, and (with op ``"mixed"``) value and
index ops in the same launch.

:class:`RoutedExecutor` answers the engine's misses over one hierarchy.
Every bucket loop of the package runs through :func:`dispatch`, which
launches every bucket of a batch before it fetches the first: the host's
launches overlap the device's work instead of alternating with it.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hierarchy import Hierarchy, pos_dtype_for
from repro.obs import trace
from repro.qe.planner import FUSED, LONG, MID, SHORT, _next_pow2

__all__ = [
    "ShortSpanExecutor",
    "MidSpanExecutor",
    "LongSpanExecutor",
    "FusedExecutor",
    "RoutedExecutor",
    "BulkExecutor",
    "dispatch",
]

VALUE = "value"
INDEX = "index"
MIXED = "mixed"


def out_dtype(index, op: str) -> np.dtype:
    """Positions in the index's coordinate dtype, values in its own."""
    if op == INDEX:
        return np.dtype(pos_dtype_for(index.capacity, strict=False))
    return np.dtype(index.value_dtype)


def dispatch(jobs, launch, fetch, args) -> None:
    """The one bucket loop: ``launch(job)`` starts each job on the
    device, all of them before ``fetch(job, result)`` waits for the first
    and copies it back.  Each call runs in a ``launch`` / ``fetch`` span
    holding ``args(job)``; the caller holds the ``execute`` span."""
    tr = trace.current()
    jobs = list(jobs)
    pending, spans = [], []
    for job in jobs:
        sp = tr.begin("launch") if tr is not None else None
        pending.append(launch(job))
        if tr is not None:
            spans.append(args(job))
            tr.end(sp, **spans[-1])
    for i, (job, res) in enumerate(zip(jobs, pending)):
        sp = tr.begin("fetch") if tr is not None else None
        fetch(job, res)
        if tr is not None:
            tr.end(sp, **spans[i])


def _copy_back(res):
    """Start the device-to-host copy of each of ``res``'s planes (an
    array or a tuple of them), so a later ``np.asarray`` finds the bytes
    on the host; returns ``res``."""
    for plane in res if isinstance(res, tuple) else (res,):
        plane.copy_to_host_async()
    return res


class _ExecutorBase:
    """Shared bookkeeping: the (op, shape) -> callable table and stats."""

    def __init__(self, backend: Optional[str] = None,
                 interpret: Optional[bool] = None):
        self.backend = backend
        self.interpret = interpret
        self._compiled: Dict[Tuple[str, int], Callable] = {}
        self.calls = 0
        self.queries = 0

    def _bind(self, op: str, shape: int, make: Callable) -> Callable:
        key = (op, shape)
        fn = self._compiled.get(key)
        if fn is None:
            fn = make()
            self._compiled[key] = fn
        return fn

    def run(self, h: Hierarchy, ls, rs, op: str) -> jax.Array:
        self.calls += 1
        self.queries += int(ls.shape[0])
        fn = self._bind(op, int(ls.shape[0]), lambda: self._make(h, op))
        return fn(h, ls, rs)

    def stats(self) -> dict:
        return {
            "calls": self.calls,
            "queries": self.queries,
            "specializations": len(self._compiled),
        }

    def invalidate(self) -> None:
        """Drop state tied to a particular index version (default: none)."""

    def span_args(self, h: Hierarchy) -> dict:
        """Args this executor adds to a bucket's ``launch`` span."""
        return {}


class ShortSpanExecutor(_ExecutorBase):
    """Two-chunk level-0 scan; never touches the hierarchy."""

    def _make(self, h: Hierarchy, op: str) -> Callable:
        from repro.kernels.rmq_short import ops as short_ops

        if self.backend == "pallas":
            return functools.partial(
                short_ops.rmq_short_value_batch_pallas if op == VALUE
                else short_ops.rmq_short_index_batch_pallas,
                interpret=self.interpret)
        if op == VALUE:
            return short_ops.rmq_short_value_batch
        return short_ops.rmq_short_index_batch


class MidSpanExecutor(_ExecutorBase):
    """The standard full hierarchy walk (the previous monolithic path)."""

    def _make(self, h: Hierarchy, op: str) -> Callable:
        if self.backend == "pallas":
            from repro.kernels.rmq_scan import ops as scan_ops

            return functools.partial(
                scan_ops.rmq_value_batch_pallas if op == VALUE
                else scan_ops.rmq_index_batch_pallas,
                interpret=self.interpret)
        from repro.core.query import rmq_index_batch, rmq_value_batch

        return rmq_value_batch if op == VALUE else rmq_index_batch


class LongSpanExecutor(_ExecutorBase):
    """Hybrid sparse-table top: O(1) instead of the c·t top scan.

    The hybrid wraps the engine's *live* hierarchy
    (``HybridRMQ.from_hierarchy`` — no rebuild; one <= c·t-entry table
    build), so it must be re-derived when the index mutates: the engine
    calls :meth:`invalidate` on every attach.
    """

    def __init__(self):
        super().__init__()
        self._hybrid = None

    def invalidate(self) -> None:
        self._hybrid = None

    def _hybrid_for(self, h: Hierarchy):
        if self._hybrid is None or self._hybrid.hierarchy is not h:
            from repro.core.hybrid import HybridRMQ

            self._hybrid = HybridRMQ.from_hierarchy(h)
        return self._hybrid

    def _make(self, h: Hierarchy, op: str) -> Callable:
        if op == VALUE:
            return lambda h, ls, rs: self._hybrid_for(h).query(ls, rs)
        return lambda h, ls, rs: self._hybrid_for(h).query_index(ls, rs)

    def span_args(self, h: Hierarchy) -> dict:
        """``row_levels``: the walk levels read by a row gather, of L-1."""
        from repro.core.hybrid import row_levels

        return {"row_levels": row_levels(h.plan)}


class FusedExecutor(_ExecutorBase):
    """The whole span mix in one ``rmq_fused`` dispatch per bucket.

    No class routing: the kernel decomposes each span internally
    (prefix-chunk scan + offset-table level lookups + suffix-chunk scan;
    short spans resolve entirely on its level-0 path).  Op ``"mixed"``
    returns *both* output planes from one launch, which is how a batch
    mixing value and index ops avoids a second dispatch.
    """

    def _make(self, h: Hierarchy, op: str) -> Callable:
        from repro.kernels.rmq_fused import ops as fused_ops

        if op == MIXED:
            # one launch, both planes (positions imply track_pos)
            return functools.partial(fused_ops.rmq_fused_batch,
                                     track_pos=True,
                                     interpret=self.interpret)
        return functools.partial(
            fused_ops.rmq_fused_value_batch if op == VALUE
            else fused_ops.rmq_fused_index_batch,
            interpret=self.interpret)


class RoutedExecutor:
    """The engine's miss executor over one hierarchy: its ``planner``
    (set at each attach) packs a deduped miss batch into span-class
    buckets, each run on its class's executor.  Every bucket launches,
    and starts the copy of its answers back, before the first is
    fetched; so the whole batch is in flight on the device at once: at
    most 12 B a padded query for a value or index op (bounds in, answer
    out), 16 B for a mixed one (12.6 MB at 2^20 value queries).
    ``lanes``: the engine's padding metrics, if any."""

    def __init__(self, backend: str, interpret: Optional[bool] = None):
        self.interpret = interpret
        self.planner = None
        self.lanes = None
        self.class_counts = {SHORT: 0, MID: 0, LONG: 0, FUSED: 0}
        self.configure(backend)

    def configure(self, backend: str) -> None:
        """(Re)build the per-class table for ``backend``, dropping the
        old backend's compiled callables."""
        self.executors = {
            SHORT: ShortSpanExecutor(backend, self.interpret),
            MID: MidSpanExecutor(backend, self.interpret),
            LONG: LongSpanExecutor(),
        }
        if backend == "fused":
            # the whole span mix in one launch per bucket: the planner
            # emits FUSED buckets only, so the trio above never runs
            self.executors[FUSED] = FusedExecutor(interpret=self.interpret)

    def run(self, index, ls, rs, op: str) -> np.ndarray:
        """Answers of ``(ls, rs)`` for ``op``, in order."""
        return self._run(index, ls, rs, op)[0]

    def run_mixed(self, index, ls, rs):
        """``(values, positions)`` of ``(ls, rs)``, both planes from one
        fused launch per bucket."""
        return tuple(self._run(index, ls, rs, MIXED))

    def _run(self, index, ls, rs, op):
        h = index.hierarchy
        ops = (VALUE, INDEX) if op == MIXED else (op,)
        outs = [np.empty(ls.shape, out_dtype(index, o)) for o in ops]
        tr = trace.current()
        sp = tr.begin("plan") if tr is not None else None
        buckets = self.planner.plan(ls, rs)
        if tr is not None:
            tr.end(sp, misses=int(ls.shape[0]), buckets=len(buckets), op=op)
        for b in buckets:
            self.class_counts[b.cls] += b.count
            if self.lanes is not None:
                waste, padded, live = self.lanes
                waste.record(b.padding)
                padded.inc(b.padding)
                live.inc(b.count)

        def launch(b):
            return _copy_back(self.executors[b.cls].run(
                h, jnp.asarray(b.ls), jnp.asarray(b.rs), op))

        def fetch(b, res):
            for out, plane in zip(outs, res if op == MIXED else [res]):
                out[b.idxs] = np.asarray(plane)[: b.count]

        sp = tr.begin("execute") if tr is not None else None
        dispatch(buckets, launch, fetch,
                 lambda b: dict(cls=b.cls, count=b.count, shape=b.shape,
                                op=op,
                                **self.executors[b.cls].span_args(h)))
        if tr is not None:
            tr.end(sp, buckets=len(buckets), count=int(ls.shape[0]), op=op)
        return outs

    def stats(self) -> dict:
        return {cls: ex.stats() for cls, ex in self.executors.items()}

    def invalidate(self) -> None:
        self.executors[LONG].invalidate()


class BulkExecutor(_ExecutorBase):
    """Offline bulk-analytics sweep: sort, bucket, one launch per bucket.

    The executor owns the host-side choreography of the
    ``kernels/rmq_bulk`` pass: the whole ``(ls, rs)`` batch is sorted by
    ``(chunk(l), chunk(r))`` so queries sharing boundary chunks become
    adjacent, split into buckets of at most ``max_bucket`` (pow2-padded
    with ``(0, 0)`` sentinel queries, so bucket shapes — and therefore
    traces — come from a tiny set), each bucket answered by a single
    level-0-coalesced dispatch, and the results inverse-permuted back to
    submission order.  One ``rmq_bulk`` launch per bucket is the
    CI-gated contract.  As in :class:`RoutedExecutor`, every bucket
    launches and starts its copy back before the first is fetched (12 B
    a padded query in flight).

    No dedup and no LRU interplay here — at the 10^6+ batch sizes where
    the bulk pass wins, per-query caching is pure overhead; the engine's
    ``query_bulk`` sends smaller batches down its routed path instead
    (dedup, the LRU and the bound miss executor).

    ``max_bucket`` is deliberately large (default 2^20): the jnp
    lowering rebuilds the shared chunk ladder per dispatch, so bigger
    buckets amortize it further; the kernel path has no per-dispatch
    setup worth splitting for.
    """

    def __init__(
        self,
        interpret: Optional[bool] = None,
        max_bucket: int = 1 << 20,
        min_bucket: int = 16,
    ):
        super().__init__(interpret=interpret)
        if max_bucket < min_bucket or min_bucket < 1:
            raise ValueError(
                f"need max_bucket >= min_bucket >= 1, got "
                f"{max_bucket}, {min_bucket}"
            )
        self.max_bucket = int(max_bucket)
        self.min_bucket = int(min_bucket)

    def _make(self, h: Hierarchy, op: str) -> Callable:
        from repro.kernels.rmq_bulk import ops as bulk_ops

        return functools.partial(
            bulk_ops.rmq_bulk_value_batch if op == VALUE
            else bulk_ops.rmq_bulk_index_batch,
            interpret=self.interpret)

    def run(self, h: Hierarchy, ls, rs, op: str) -> np.ndarray:
        """Answer the whole batch; returns results in submission order."""
        ls = np.asarray(ls, np.int32).ravel()
        rs = np.asarray(rs, np.int32).ravel()
        m = ls.shape[0]
        dtype = np.int32 if op == INDEX else np.dtype(h.base.dtype)
        if m == 0:
            return np.zeros((0,), dtype)
        c = h.plan.c
        self.queries += m

        tr = trace.current()
        sp = tr.begin("plan") if tr is not None else None
        # last lexsort key is primary: chunk(l) major, chunk(r) minor
        order = np.lexsort((rs // c, ls // c))
        sls, srs = ls[order], rs[order]
        # (start, live count, padded shape) of each bucket
        starts = range(0, m, self.max_bucket)
        counts = [min(self.max_bucket, m - start) for start in starts]
        jobs = [(start, cnt, max(_next_pow2(cnt), self.min_bucket))
                for start, cnt in zip(starts, counts)]
        if tr is not None:
            tr.end(sp, queries=m, buckets=len(jobs), op=op,
                   strategy="bulk")

        def launch(job):
            start, count, k = job
            bl = np.zeros((k,), np.int32)
            br = np.zeros((k,), np.int32)
            bl[:count] = sls[start:start + count]
            br[:count] = srs[start:start + count]
            self.calls += 1
            fn = self._bind(op, k, lambda: self._make(h, op))
            return _copy_back(fn(h, jnp.asarray(bl), jnp.asarray(br)))

        sorted_res = np.empty((m,), dtype)

        def fetch(job, res):
            start, count, _ = job
            sorted_res[start:start + count] = np.asarray(res)[:count]

        sp = tr.begin("execute") if tr is not None else None
        dispatch(jobs, launch, fetch,
                 lambda job: dict(cls="bulk", count=job[1], shape=job[2],
                                  op=op))
        if tr is not None:
            tr.end(sp, buckets=len(jobs), count=m, op=op)

        sp = tr.begin("scatter") if tr is not None else None
        out = np.empty((m,), dtype)
        out[order] = sorted_res
        if tr is not None:
            tr.end(sp, queries=m, unique=m, op=op)
        return out
