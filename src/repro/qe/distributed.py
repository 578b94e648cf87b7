"""Distributed executor: segment-aware routing for sharded indices.

A :class:`repro.core.distributed.DistributedRMQ` has no single local
hierarchy, so the span executors (short/mid/long) don't apply.  What *does*
transfer is the engine's core observation — different queries want
different execution — with a sharding-native routing predicate:

* **seg_local** — the span falls entirely inside one segment
  (``l // segment_capacity == r // segment_capacity``).  The batch is
  grouped by owning segment on the host, localized, packed into one
  ``(S, k)`` array sharded over the segment axis, and each device answers
  only its own row — **no all-reduce at all** (zero cross-device
  communication, vs. one ``pmin`` per batch on the monolithic path).
  Short and mid spans land here with probability ``≈ 1 - span/seg_cap``.
* **crossing** — the span straddles a segment boundary; routed to the
  monolithic all-reduce path (``DistributedRMQ._query``), which is the
  engine's oracle.

Both paths produce values and leftmost-tie positions bit-identical to
``DistributedRMQ.query``/``query_index``.  Shapes are padded to powers of
two (``(0, 0)`` sentinel queries, dropped at scatter-back) so the set of
jit specializations stays bounded as batch composition shifts — the same
discipline as the planner's buckets.  Each class dispatches all its
buckets before it waits for the first answer.

Global bounds and positions keep the engine's coordinate dtype (int64
once the capacity passes 2^31); segment-local bounds are int32.

Spans (``repro.obs.trace``): ``route`` (the host split; ``queries``,
``seg_local``, ``crossing``), then per class an ``execute`` holding one
``launch`` and one ``fetch`` per bucket, each with ``cls``.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from repro.obs import trace
from repro.qe.executors import INDEX, dispatch, out_dtype
from repro.qe.planner import _next_pow2

__all__ = ["SEG_LOCAL", "CROSSING", "DistributedExecutor"]

SEG_LOCAL = "seg_local"
CROSSING = "crossing"


class DistributedExecutor:
    """Routes one deduped miss batch over a segment-sharded index."""

    def __init__(self, min_bucket: int = 16, max_bucket: int = 4096):
        self.min_bucket = min_bucket
        self.max_bucket = max_bucket
        self.calls = 0
        self.queries = 0
        self.class_counts: Dict[str, int] = {SEG_LOCAL: 0, CROSSING: 0}

    def run(self, index, ls: np.ndarray, rs: np.ndarray,
            op: str) -> np.ndarray:
        """Answer ``(ls, rs)`` (deduped global bounds) against ``index``."""
        return self._route(index, ls, rs, op, bulk=False)

    def run_bulk(self, index, ls: np.ndarray, rs: np.ndarray,
                 op: str) -> np.ndarray:
        """Bulk-analytics route: the endpoint sort groups by owner too.

        Same routing predicate as :meth:`run`, but segment-contained
        queries are pre-sorted by ``(owner segment, chunk(l), chunk(r))``
        in segment-local coordinates before the grouped shard-local
        execution — the one sort simultaneously (a) packs each segment's
        queries contiguously so ``_run_seg_local``'s stable owner sort
        is an identity pass, and (b) makes every shard's row
        endpoint-sorted, the locality the bulk regime is after.  The
        grouped path runs with **zero collectives**; only
        boundary-crossing spans (a ``span/segment_capacity`` fraction of
        a uniform batch) pay the ``pmin`` oracle.  No dedup, no LRU —
        bulk-scale batches bypass both by design.
        """
        return self._route(index, ls, rs, op, bulk=True)

    def _route(self, index, ls, rs, op, bulk: bool) -> np.ndarray:
        self.calls += 1
        m = ls.shape[0]
        self.queries += m
        cap = index.segment_capacity
        dtype = out_dtype(index, op)
        out = np.empty((m,), dtype)

        tr = trace.current()
        sp = tr.begin("route") if tr is not None else None
        owner = ls // cap
        local = owner == (rs // cap)
        n_local = int(local.sum())
        self.class_counts[SEG_LOCAL] += n_local
        self.class_counts[CROSSING] += m - n_local
        local_idx = np.nonzero(local)[0]
        cross_idx = np.nonzero(~local)[0]
        if bulk:
            c = index.plan.c
            osub = owner[local_idx]
            lloc = ls[local_idx] - osub * cap
            rloc = rs[local_idx] - osub * cap
            local_idx = local_idx[np.lexsort((rloc // c, lloc // c, osub))]
        if tr is not None:
            tr.end(sp, queries=m, seg_local=n_local, crossing=m - n_local,
                   op=op, **({"strategy": "bulk"} if bulk else {}))
        if cross_idx.shape[0]:
            with trace.span("execute", cls=CROSSING,
                            count=int(cross_idx.shape[0]), op=op):
                out[cross_idx] = self._run_crossing(
                    index, ls[cross_idx], rs[cross_idx], op, dtype)
        if local_idx.shape[0]:
            with trace.span("execute", cls=SEG_LOCAL,
                            count=int(local_idx.shape[0]), op=op):
                res = self._run_seg_local(
                    index, ls[local_idx], rs[local_idx], owner[local_idx],
                    op, dtype)
            sp = tr.begin("scatter") if tr is not None and bulk else None
            out[local_idx] = res
            if sp is not None:
                tr.end(sp, queries=m, unique=m, op=op)
        return out

    # -- crossing spans: the pmin oracle, padded to bounded shapes --------
    def _run_crossing(self, index, ls, rs, op, dtype) -> np.ndarray:
        k = ls.shape[0]
        shape = min(
            max(_next_pow2(k), self.min_bucket), self.max_bucket
        )
        res = np.empty((k,), dtype)

        def launch(lo):
            cnt = min(shape, k - lo)
            pl = np.zeros((shape,), ls.dtype)
            pr = np.zeros((shape,), rs.dtype)
            pl[:cnt] = ls[lo : lo + cnt]
            pr[:cnt] = rs[lo : lo + cnt]
            return index.query_index(pl, pr) if op == INDEX \
                else index.query(pl, pr)

        def fetch(lo, r):
            cnt = min(shape, k - lo)
            res[lo : lo + cnt] = np.asarray(r)[:cnt]

        dispatch(range(0, k, shape), launch, fetch,
                 lambda lo: {"cls": CROSSING})
        return res

    # -- contained spans: grouped per owner, answered without collectives -
    def _run_seg_local(self, index, ls, rs, owner, op, dtype) -> np.ndarray:
        cap = index.segment_capacity
        s = index.num_segments
        # stable sort by owner -> contiguous per-segment runs; row_pos is
        # each query's slot inside its segment's row
        order = np.argsort(owner, kind="stable")
        so = owner[order]
        counts = np.bincount(so, minlength=s)
        starts = np.cumsum(counts) - counts
        row_pos = np.arange(so.shape[0]) - starts[so]
        # localize in the global dtype, then narrow: local bounds < cap
        lloc = (ls[order] - so * cap).astype(np.int32)
        rloc = (rs[order] - so * cap).astype(np.int32)
        picked = np.empty((so.shape[0],), dtype)

        # row width is bounded at max_bucket (same discipline as the
        # planner's buckets): a skewed batch runs in several rounds of
        # already-compiled shapes instead of tracing one giant one
        def launch(lo):
            sel = (row_pos >= lo) & (row_pos < lo + self.max_bucket)
            rp = row_pos[sel] - lo
            k = max(_next_pow2(int(rp.max()) + 1), self.min_bucket)
            gl = np.zeros((s, k), np.int32)
            gr = np.zeros((s, k), np.int32)
            gl[so[sel], rp] = lloc[sel]
            gr[so[sel], rp] = rloc[sel]
            vals, poss = index._query_grouped(
                gl, gr, track_pos=(op == INDEX)
            )
            return sel, rp, poss if op == INDEX else vals

        def fetch(lo, pending):
            sel, rp, r = pending
            picked[sel] = np.asarray(r)[so[sel], rp]

        dispatch(range(0, int(counts.max()), self.max_bucket), launch,
                 fetch, lambda lo: {"cls": SEG_LOCAL})
        res = np.empty((ls.shape[0],), dtype)
        res[order] = picked
        return res

    def stats(self) -> dict:
        return {
            "calls": self.calls,
            "queries": self.queries,
            "class_counts": dict(self.class_counts),
        }

    def invalidate(self) -> None:
        """No per-index state (the sharded fns are cached by geometry)."""
