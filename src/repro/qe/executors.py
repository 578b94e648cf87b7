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
entire bucket — every span class, and (via :meth:`FusedExecutor.run_mixed`)
value and index ops in the same launch.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hierarchy import Hierarchy
from repro.obs import trace

__all__ = [
    "ShortSpanExecutor",
    "MidSpanExecutor",
    "LongSpanExecutor",
    "FusedExecutor",
    "BulkExecutor",
]

VALUE = "value"
INDEX = "index"
MIXED = "mixed"


class _ExecutorBase:
    """Shared bookkeeping: the (op, shape) -> callable table and stats."""

    def __init__(self):
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
        """Args this executor adds to a bucket's ``execute`` span."""
        return {}


class ShortSpanExecutor(_ExecutorBase):
    """Two-chunk level-0 scan; never touches the hierarchy."""

    def __init__(self, backend: str, interpret: Optional[bool] = None):
        super().__init__()
        self.backend = backend
        self.interpret = interpret

    def _make(self, h: Hierarchy, op: str) -> Callable:
        from repro.kernels.rmq_short import ops as short_ops

        if self.backend == "pallas":
            if op == VALUE:
                return lambda h, ls, rs: short_ops.rmq_short_value_batch_pallas(
                    h, ls, rs, interpret=self.interpret
                )
            return lambda h, ls, rs: short_ops.rmq_short_index_batch_pallas(
                h, ls, rs, interpret=self.interpret
            )
        if op == VALUE:
            return short_ops.rmq_short_value_batch
        return short_ops.rmq_short_index_batch


class MidSpanExecutor(_ExecutorBase):
    """The standard full hierarchy walk (the previous monolithic path)."""

    def __init__(self, backend: str, interpret: Optional[bool] = None):
        super().__init__()
        self.backend = backend
        self.interpret = interpret

    def _make(self, h: Hierarchy, op: str) -> Callable:
        if self.backend == "pallas":
            from repro.kernels.rmq_scan import ops as scan_ops

            if op == VALUE:
                return lambda h, ls, rs: scan_ops.rmq_value_batch_pallas(
                    h, ls, rs, interpret=self.interpret
                )
            return lambda h, ls, rs: scan_ops.rmq_index_batch_pallas(
                h, ls, rs, interpret=self.interpret
            )
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
    short spans resolve entirely on its level-0 path).  ``run`` serves
    the engine's per-op path; :meth:`run_mixed` returns *both* output
    planes from one launch, which is how a batch mixing value and index
    ops avoids a second dispatch.
    """

    def __init__(self, interpret: Optional[bool] = None):
        super().__init__()
        self.interpret = interpret

    def _make(self, h: Hierarchy, op: str) -> Callable:
        from repro.kernels.rmq_fused import ops as fused_ops

        if op == MIXED:
            # one launch, both planes (positions imply track_pos)
            return lambda h, ls, rs: fused_ops.rmq_fused_batch(
                h, ls, rs, track_pos=True, interpret=self.interpret
            )
        if op == VALUE:
            return lambda h, ls, rs: fused_ops.rmq_fused_value_batch(
                h, ls, rs, interpret=self.interpret
            )
        return lambda h, ls, rs: fused_ops.rmq_fused_index_batch(
            h, ls, rs, interpret=self.interpret
        )

    def run_mixed(self, h: Hierarchy, ls, rs):
        """``(values, positions)`` for the whole bucket, one launch."""
        self.calls += 1
        self.queries += int(ls.shape[0])
        fn = self._bind(MIXED, int(ls.shape[0]),
                        lambda: self._make(h, MIXED))
        return fn(h, ls, rs)


def _next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


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
    CI-gated contract.

    No dedup and no LRU interplay here — at the 10^6+ batch sizes where
    bulk beats fused, per-query caching is pure overhead; the engine's
    ``query_bulk`` routes small batches to the fused path instead.

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
        super().__init__()
        if max_bucket < min_bucket or min_bucket < 1:
            raise ValueError(
                f"need max_bucket >= min_bucket >= 1, got "
                f"{max_bucket}, {min_bucket}"
            )
        self.interpret = interpret
        self.max_bucket = int(max_bucket)
        self.min_bucket = int(min_bucket)

    def _make(self, h: Hierarchy, op: str) -> Callable:
        from repro.kernels.rmq_bulk import ops as bulk_ops

        if op == VALUE:
            return lambda h, ls, rs: bulk_ops.rmq_bulk_value_batch(
                h, ls, rs, interpret=self.interpret
            )
        return lambda h, ls, rs: bulk_ops.rmq_bulk_index_batch(
            h, ls, rs, interpret=self.interpret
        )

    def run(self, h: Hierarchy, ls, rs, op: str) -> np.ndarray:
        """Answer the whole batch; returns results in submission order."""
        ls = np.asarray(ls, np.int32).ravel()
        rs = np.asarray(rs, np.int32).ravel()
        m = ls.shape[0]
        out_dtype = np.int32 if op == INDEX else np.dtype(h.base.dtype)
        if m == 0:
            return np.zeros((0,), out_dtype)
        c = h.plan.c
        self.queries += m

        tr = trace.current()
        sp = tr.begin("plan") if tr is not None else None
        # last lexsort key is primary: chunk(l) major, chunk(r) minor
        order = np.lexsort((rs // c, ls // c))
        sls, srs = ls[order], rs[order]
        n_buckets = -(-m // self.max_bucket)
        if tr is not None:
            tr.end(sp, queries=m, buckets=n_buckets, op=op,
                   strategy="bulk")

        sorted_res = np.empty((m,), out_dtype)
        for start in range(0, m, self.max_bucket):
            stop = min(start + self.max_bucket, m)
            count = stop - start
            k = max(_next_pow2(count), self.min_bucket)
            bl = np.zeros((k,), np.int32)
            br = np.zeros((k,), np.int32)
            bl[:count] = sls[start:stop]
            br[:count] = srs[start:stop]
            self.calls += 1
            fn = self._bind(op, k, lambda: self._make(h, op))
            sp = tr.begin("execute") if tr is not None else None
            sub = tr.begin("launch") if tr is not None else None
            res = fn(h, jnp.asarray(bl), jnp.asarray(br))
            if tr is not None:
                tr.end(sub)
                sub = tr.begin("fetch")
            sorted_res[start:stop] = np.asarray(res)[:count].astype(
                out_dtype, copy=False
            )
            if tr is not None:
                tr.end(sub)
                tr.end(sp, cls="bulk", count=count, shape=k, op=op)

        sp = tr.begin("scatter") if tr is not None else None
        out = np.empty((m,), out_dtype)
        out[order] = sorted_res
        if tr is not None:
            tr.end(sp, queries=m, unique=m, op=op)
        return out
