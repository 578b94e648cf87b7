"""``QueryEngine`` — span-routed, deduped, cached batched RMQ execution.

One engine serves one index — anything implementing the
:class:`repro.core.protocol.RMQIndex` protocol: ``RMQ``, ``StreamingRMQ``,
``HybridRMQ``, or the mesh-sharded ``DistributedRMQ``.  The engine is a
*host-side* orchestration layer: classification, packing, dedup and cache
bookkeeping run in numpy; only the packed buckets touch the device,
through persistent jitted callables (see :mod:`repro.qe.executors`).

Every query method (``query``, ``query_index``, ``query_mixed``, and
``query_bulk`` below its crossover) runs one pipeline, ``_answer``::

    validate on the host -> pack keys ((l << 31) | r, one int64 per
                            query; past 2^31 a key space beside it)
             -> dedup on (l, r) pairs (np.unique on the keys)
             -> LRU lookup of the (pair, op) entries needed (get_many)
             -> the misses to the miss executor, once per op
             -> LRU insert (put_many) -> scatter-back

Every host step is an array operation over the batch; none runs Python
per query.  :meth:`attach` binds the miss executor: over one hierarchy
:class:`repro.qe.executors.RoutedExecutor` (short / mid / long span
buckets); over a sharded index the segment-aware
:class:`repro.qe.distributed.DistributedExecutor` (contained spans
shard-locally with no all-reduce, crossing spans through ``pmin``).
With the **fused** runtime backend the planner degrades to a single
bucket class (``kernels/rmq_fused`` decomposes spans in-kernel) and a
batch mixing value and index ops runs its misses as one launch per
bucket with both output planes.

Results are bit-identical — values *and* leftmost-tie positions — to
the index's monolithic oracles (``rmq_value_batch``/``rmq_index_batch``,
or ``DistributedRMQ.query``/``query_index``): every routed path computes
the exact lexicographic (value, position) minimum over the same range,
just over a cheaper decomposition.

Mutation protocol: the index is pure-functional, so ``update``/
``append`` return a *successor* with ``generation + 1``.  Call
:meth:`attach` with the successor; cached results keyed to older
generations can then never be served (and age out of the LRU).
Attaching an index that is not a successor of the current one (its
generation did not strictly increase, or its plan differs) clears the
cache outright.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core.hierarchy import pos_dtype_for
from repro.core.protocol import (
    check_capacity_limit,
    is_distributed,
    live_length,
    runtime_backend,
)
from repro.core.query import check_host_bounds
from repro.kernels.common import check_query_vmem, resolve_interpret
from repro.kernels.profiling import record_config
from repro.obs import trace
from repro.obs.metrics import SIZE_BUCKETS, Metrics
from repro.qe.cache import (
    OP_BITS,
    ResultCache,
    entry_keys,
    from_bits,
    join_keys,
    pack_keys,
    to_bits,
    unique_wide,
    unpack_keys,
)
from repro.qe.distributed import DistributedExecutor
from repro.qe.executors import (
    INDEX,
    MIXED,
    VALUE,
    BulkExecutor,
    RoutedExecutor,
    out_dtype,
)
from repro.qe.planner import FUSED, LONG, MID, SHORT, QueryPlanner

__all__ = ["QueryEngine"]


def _quantized(index) -> bool:
    """Does ``index`` store bf16 value summaries (exact-recovery walks)?"""
    return (
        getattr(index.plan, "summary_dtype", "float32") == "bfloat16"
    )


def _pick(a, rows):
    """``a[rows]`` for ascending distinct ``rows``, without the copy where
    they are all of ``a``."""
    return a if rows.shape[0] == a.shape[0] else a[rows]


def _join(parts):
    """``np.concatenate(parts)``, without the copy where only one part
    holds anything."""
    full = [p for p in parts if p.shape[0]]
    return full[0] if len(full) == 1 else np.concatenate(parts)


class QueryEngine:
    """Adaptive batched execution over one RMQ index."""

    def __init__(
        self,
        index,
        cache_size: int = 8192,
        long_enabled: bool = True,
        long_cutoff: Optional[int] = None,
        min_bucket: int = 16,
        max_bucket: int = 4096,
        backend: Optional[str] = None,
        interpret: Optional[bool] = None,
        metrics: Optional[Metrics] = None,
        tuning=None,
        span_mix: str = "mixed",
        bulk_crossover: Optional[int] = None,
    ):
        # Config precedence (most- to least-specific), resolved per
        # attach by _resolve_config:
        #   explicit ctor kwargs > ``tuning`` cache lookup
        #   > plan.level_split (baked at build) > analytic defaults.
        self._tuning = tuning
        self._span_mix = span_mix
        self._explicit_backend = backend
        self._long_enabled = long_enabled
        self._long_cutoff = long_cutoff
        self._min_bucket = min_bucket
        self._max_bucket = max_bucket
        self._interpret = interpret
        self._bulk_crossover = bulk_crossover
        if bulk_crossover is not None and bulk_crossover < 1:
            raise ValueError(
                f"bulk_crossover must be >= 1, got {bulk_crossover}"
            )
        self.bulk_crossover: int = 1  # resolved per attach
        self._bulk = BulkExecutor(interpret=interpret)
        self.cache = ResultCache(cache_size)
        self.tuned: Optional[dict] = None  # resolved config provenance
        self.backend = self._resolve_backend(index)
        self._routed = RoutedExecutor(self.backend, interpret=interpret)
        self.batches = 0
        self.queries_in = 0
        self.dedup_saved = 0
        self._index = None
        self.distributed: Optional[DistributedExecutor] = None
        self._miss = self._routed      # the bound miss executor
        self.metrics: Optional[Metrics] = None
        self._coord = np.dtype(np.int32)  # coordinate dtype, per attach
        self._m_tuned = None
        if metrics is not None:
            self._register_metrics(metrics)
        self.attach(index)

    # -- tuned-config resolution ------------------------------------------
    def _tuned_lookup(self, index):
        """The tuning-cache entry for this index, or ``None``."""
        if self._tuning is None or is_distributed(index):
            return None
        from repro.tune.cache import current_platform

        return self._tuning.lookup(
            current_platform(), live_length(index), self._span_mix
        )

    def _resolve_backend(self, index) -> str:
        """Query lowering per the precedence ladder (hierarchies are
        bit-identical across backends, so adopting a tuned backend over
        any build only changes which lowering answers)."""
        if self._explicit_backend is not None:
            return runtime_backend(self._explicit_backend)
        cfg = self._tuned_lookup(index)
        if cfg is not None:
            return runtime_backend(cfg.backend)
        split = getattr(index.plan, "level_split", None)
        if split is not None and split.fused:
            return "fused"
        return runtime_backend(index.backend)

    def _resolve_config(self, index) -> dict:
        """Planner knobs + provenance for ``index`` (non-distributed)."""
        cfg = self._tuned_lookup(index)
        split = getattr(index.plan, "level_split", None)
        source = "default"
        long_cutoff = self._long_cutoff
        scan_chunks = 2
        sparse_top = True
        if split is not None:
            source = "plan"
            scan_chunks = split.scan_chunks
            sparse_top = split.sparse_top
            if long_cutoff is None:
                long_cutoff = split.long_cutoff
        if cfg is not None:
            source = "cache"
            scan_chunks = cfg.scan_chunks
            sparse_top = cfg.sparse_top
            if self._long_cutoff is None:
                long_cutoff = cfg.long_cutoff
        if self._long_cutoff is not None:
            long_cutoff = self._long_cutoff
            if source != "default":
                source += "+override"
        # bf16 summaries: the long-span hybrid's sparse-table top would
        # compare quantized values (HybridRMQ refuses to build one);
        # long spans route through the exact mid-span walk instead.
        long_ok = not _quantized(index)
        return {
            "backend": self.backend,
            "planner": "fused" if self.backend == "fused" else "routed",
            "long_cutoff": long_cutoff,
            "scan_chunks": scan_chunks,
            "long_enabled": self._long_enabled and sparse_top and long_ok,
            "source": source,
        }

    def _resolve_bulk_crossover(self, index) -> int:
        """Batch size at which :meth:`query_bulk` leaves the routed path.

        Same precedence as the rest of the config: explicit ctor kwarg >
        tuned cache (``bulk_crossover`` measured by the Autotuner) >
        analytic model.  The analytic fallback charges the bulk pass its
        fixed per-dispatch cost — the shared chunk ladder is ~log2(c)
        full passes over the ``capacity/c`` chunk grid, worth paying
        once the batch is of the same order — and floors at 1024 so tiny
        indexes never bulk-route micro-batches.
        """
        if self._bulk_crossover is not None:
            return self._bulk_crossover
        cfg = self._tuned_lookup(index)
        if cfg is not None and getattr(cfg, "bulk_crossover", None):
            return int(cfg.bulk_crossover)
        plan = index.plan
        rows = max(index.capacity // plan.c, 1)
        return max(1024, rows * max(plan.c.bit_length() - 1, 1))

    def _register_metrics(self, metrics: Metrics) -> None:
        """Export engine state into ``metrics``.

        Hot-path counters stay plain attributes — the gauges read them
        through callbacks at export time, so enabling metrics adds no
        per-query locking.  The only per-bucket write is the
        padding-waste histogram (one lock per *bucket*, not per query).
        """
        self.metrics = metrics
        cache = self.cache
        metrics.gauge("cache_hits", fn=lambda: cache.hits)
        metrics.gauge("cache_misses", fn=lambda: cache.misses)
        metrics.gauge("cache_hit_rate", fn=cache.hit_rate)
        metrics.gauge("cache_entries", fn=cache.__len__)
        metrics.gauge("cache_evictions", fn=lambda: cache.evictions)
        metrics.gauge("batches", fn=lambda: self.batches)
        metrics.gauge("queries", fn=lambda: self.queries_in)
        metrics.gauge("dedup_saved", fn=lambda: self.dedup_saved)
        counts = self._routed.class_counts
        for cls in (SHORT, MID, LONG, FUSED):
            metrics.gauge(f"span_class_{cls}", fn=lambda c=cls: counts[c])
        self._routed.lanes = (
            metrics.histogram("bucket_padding_waste", SIZE_BUCKETS),
            metrics.counter("padded_lanes"),
            metrics.counter("live_lanes"),
        )
        self._m_tuned = metrics.info("tuned_config")
        if self.tuned is not None:
            self._m_tuned.set({k: str(v) for k, v in self.tuned.items()})

    @classmethod
    def for_index(cls, index, **kwargs) -> "QueryEngine":
        return cls(index, **kwargs)

    # -- index binding ----------------------------------------------------
    @property
    def index(self):
        return self._index

    @property
    def generation(self) -> int:
        return getattr(self._index, "generation", 0)

    @property
    def planner(self) -> Optional[QueryPlanner]:
        """The routed executor's planner (``None`` on a sharded index)."""
        return self._routed.planner

    def attach(self, index, reset_cache: Optional[bool] = None) -> None:
        """Bind a (successor) index.

        ``reset_cache=None`` keeps cached results only when ``index``
        looks like a successor of the current binding: same plan and a
        strictly larger generation (old entries are then unreachable by
        key).  Pass ``True``/``False`` to override.
        """
        prev = self._index
        if reset_cache is None:
            reset_cache = not (
                prev is not None
                and index.plan == prev.plan
                and getattr(index, "generation", 0)
                > getattr(prev, "generation", 0)
            )
        if reset_cache:
            self.cache.clear()
        plan = index.plan
        # Over one hierarchy, query bounds and positions flow through
        # int32 index space (planner packing, the short kernel's iota):
        # refuse capacities past it loudly rather than wrap.  A sharded
        # index under x64 serves any capacity: global coordinates are
        # int64 (keys split as ``cache.split_keys``), segment-local ones
        # int32.  ``capacity`` is the total addressable space — for
        # sharded indices segments * per-segment capacity, not the
        # (per-segment) plan's.
        check_capacity_limit(index.capacity, allow_x64=is_distributed(index))
        self._coord = np.dtype(pos_dtype_for(index.capacity, strict=False))
        if is_distributed(index):
            # Sharded index: routing is by segment containment, not span
            # class — the planner and span executors never run.
            self._routed.planner = None
            self.tuned = None
            self.bulk_crossover = self._resolve_bulk_crossover(index)
            if self.distributed is None:
                self.distributed = DistributedExecutor(
                    min_bucket=self._min_bucket,
                    max_bucket=self._max_bucket,
                )
            self._miss = self.distributed
        else:
            self.distributed = None
            self._miss = self._routed
            # Re-resolve the tuned config against the new binding: a
            # successor index may carry a different plan (and cache
            # lookups key on the live length).  Adopting a different
            # tuned backend rebuilds the executor table.
            backend = self._resolve_backend(index)
            if backend != self.backend:
                self.backend = backend
                self._routed.configure(backend)
            if backend in ("pallas", "fused") and not resolve_interpret(
                self._interpret
            ):
                # the compiled walk kernels hold the upper buffer in VMEM:
                # refuse a geometry past it here, not at the first query
                check_query_vmem(
                    plan, index.with_positions,
                    jnp.dtype(index.value_dtype).itemsize,
                )
            resolved = self._resolve_config(index)
            self.bulk_crossover = self._resolve_bulk_crossover(index)
            resolved["bulk_crossover"] = self.bulk_crossover
            self._routed.planner = QueryPlanner(
                c=plan.c,
                num_levels=plan.num_levels,
                long_cutoff=resolved["long_cutoff"],
                long_enabled=resolved["long_enabled"],
                min_bucket=self._min_bucket,
                max_bucket=self._max_bucket,
                fused=self.backend == "fused",
                scan_chunks=resolved["scan_chunks"],
            )
            self._record_tuned(index, resolved)
        self._index = index
        self._routed.invalidate()

    def _record_tuned(self, index, resolved: dict) -> None:
        """Expose the chosen config: ``stats()["tuned"]``, the launch
        registry (``engine_tuned_config`` records), and the metrics tree
        (``repro_..._tuned_config`` info gauge labels)."""
        plan = index.plan
        tuned = {
            "c": plan.c,
            "t": plan.t,
            "n": live_length(index),
            **{k: resolved[k] for k in
               ("backend", "planner", "long_cutoff", "scan_chunks",
                "long_enabled", "bulk_crossover", "source")},
        }
        if tuned == self.tuned:
            return
        self.tuned = tuned
        record_config("engine_tuned_config", **tuned)
        if self._m_tuned is not None:
            self._m_tuned.set(
                {k: str(v) for k, v in tuned.items()}
            )

    # -- public query surface ---------------------------------------------
    def query(self, ls, rs) -> jnp.ndarray:
        """Batched ``RMQ_value``; bit-identical to the index's oracle."""
        return self._answer(*self._bounds(ls, rs), VALUE)

    def query_index(self, ls, rs) -> jnp.ndarray:
        """Batched ``RMQ_index``; bit-identical to the index's oracle."""
        self._require_positions()
        return self._answer(*self._bounds(ls, rs), INDEX)

    def query_bulk(self, ls, rs, op: str = VALUE) -> jnp.ndarray:
        """Offline bulk-analytics batch (``op`` = ``"value"``/``"index"``).

        The execution strategy for the 10^6+-query regime: the batch is
        sorted by ``(chunk(l), chunk(r))`` and answered in single
        level-0-coalesced ``kernels/rmq_bulk`` dispatches that share
        chunk reads across queries (:class:`BulkExecutor`), results
        inverse-permuted back to submission order.  Bit-identical to
        :meth:`query` / :meth:`query_index` — values and leftmost-tie
        positions — at any batch size.

        Batches below :attr:`bulk_crossover` (explicit kwarg > autotuned
        cache > analytic model) take the routed pipeline instead (dedup,
        the LRU, the miss executor; fused only on a fused engine): below
        the crossover the bulk pass's fixed ladder cost loses, and
        dedup + the LRU still pay for themselves.  At and above it both
        are skipped — per-query caching is pure overhead at bulk scale.
        On a distributed index the endpoint sort also groups queries by
        owning segment, so segment-contained spans run shard-locally
        with zero collectives
        (:meth:`~repro.qe.distributed.DistributedExecutor.run_bulk`).
        """
        if op not in (VALUE, INDEX):
            raise ValueError(
                f"op must be {VALUE!r} or {INDEX!r}, got {op!r}"
            )
        if op == INDEX:
            self._require_positions()
        index = self._index
        # the root span of one batch: every engine span below nests in it
        tr = trace.current()
        sp = tr.begin("query_bulk") if tr is not None else None
        try:
            ls, rs = self._bounds(ls, rs)
            # bf16 summaries: the coalesced bulk sweep compares quantized
            # level-1 values with no exact-recovery pass, so bf16 indexes
            # always take the routed path (whose walks re-read level 0).
            routed = ls.shape[0] < self.bulk_crossover or (
                self.distributed is None and _quantized(index)
            )
            if sp is not None:
                sp.args.update(queries=int(ls.shape[0]),
                               route="routed" if routed else "bulk")
            if routed:
                return self._answer(ls, rs, op)
            self.batches += 1
            self.queries_in += ls.shape[0]
            if self.distributed is not None:
                res = self.distributed.run_bulk(index, ls, rs, op)
            else:
                res = self._bulk.run(index.hierarchy, ls, rs, op)
            return jnp.asarray(np.asarray(res).astype(
                out_dtype(index, op), copy=False))
        finally:
            if sp is not None:
                tr.end(sp)

    @property
    def supports_mixed(self) -> bool:
        """Can a value+index mix execute as ONE launch per bucket?

        True on fused-backend engines over a single hierarchy (the
        kernel emits both output planes); the service uses this to
        coalesce a registered index's value and index groups into one
        execution instead of two.
        """
        return self.distributed is None and FUSED in self._routed.executors

    def query_mixed(self, ls, rs, is_index) -> tuple:
        """Answer a batch mixing ``RMQ_value`` and ``RMQ_index`` ops.

        ``is_index[i]`` selects row ``i``'s op.  Returns ``(values,
        positions)`` numpy arrays of the batch length; only the plane
        selected by ``is_index`` is meaningful per row (the other
        plane's entry is unspecified).  On a fused engine the misses of
        a batch holding both ops run with both planes from one launch;
        elsewhere the miss executor runs once per op.  Results (and
        cache entries) are those of :meth:`query` / :meth:`query_index`.
        """
        is_index = np.asarray(is_index, bool).ravel()
        if is_index.any():
            self._require_positions()
        ls, rs = self._bounds(ls, rs)
        if ls.shape != is_index.shape:
            raise ValueError(
                f"is_index must match the batch, got {is_index.shape} "
                f"vs {ls.shape}"
            )
        return self._answer(ls, rs, is_index)

    # -- execution --------------------------------------------------------
    def _require_positions(self) -> None:
        if not self._index.with_positions:
            raise ValueError(
                "index was built without positions; rebuild it with "
                "with_positions=True to serve RMQ_index queries"
            )

    def _bounds(self, ls, rs):
        """The batch's bounds checked on the host (no device copy) and
        flattened in the coordinate dtype."""
        ls, rs = check_host_bounds(ls, rs, live_length(self._index))
        return (ls.astype(self._coord, copy=False).ravel(),
                rs.astype(self._coord, copy=False).ravel())

    def _answer(self, ls, rs, ops):
        """The pipeline of every query method over validated bounds.
        ``ops``: ``VALUE`` or ``INDEX`` for the whole batch (one device
        array back), or a bool array, ``True`` where a row asks for its
        position (``(values, positions)`` numpy arrays back)."""
        index = self._index
        mixed = not isinstance(ops, str)
        # one plane per op, in OP_BITS order
        dtypes = [out_dtype(index, VALUE), out_dtype(index, INDEX)]
        m = ls.shape[0]
        if m == 0:
            out = [np.zeros((0,), dt) for dt in dtypes]
            return tuple(out) if mixed else jnp.asarray(out[OP_BITS[ops]])
        self.batches += 1
        self.queries_in += m

        tr = trace.current()
        sp = tr.begin("dedup") if tr is not None else None
        spaces = None       # wide keys' key spaces (coordinates >= 2^31)
        if self._coord == np.int32:
            ukeys, inverse = np.unique(pack_keys(ls, rs),
                                       return_inverse=True)
        else:
            spaces, ukeys, inverse = unique_wide(ls, rs)
        k = ukeys.shape[0]
        self.dedup_saved += m - k
        if tr is not None:
            tr.end(sp, queries=m, unique=k)

        # the pairs asked for each op (in OP_BITS order), ascending; a
        # pair's cache entry for an op is entry_keys(its key, op bit)
        need = np.zeros((2, k), bool)
        if mixed:
            need[ops.astype(np.intp), inverse] = True
        else:
            need[OP_BITS[ops]] = True
        want = [np.flatnonzero(row) for row in need]
        planes = [np.zeros((k,), dt) for dt in dtypes]
        missing = want
        gen = self.generation
        if self.cache.capacity > 0:
            # one lookup: the value entries, then the index entries
            sp = tr.begin("cache_get") if tr is not None else None
            ekeys = _join([entry_keys(_pick(ukeys, w), b)
                           for b, w in enumerate(want)])
            espaces = None if spaces is None else _join(
                [_pick(spaces, w) for w in want])
            raw, hit = self.cache.get_many(gen, ekeys, spaces=espaces)
            cut = [want[0].shape[0]]
            missing = []
            for w, plane, r, h in zip(want, planes, np.split(raw, cut),
                                      np.split(hit, cut)):
                plane[_pick(w, np.flatnonzero(h))] = from_bits(r[h],
                                                               plane.dtype)
                missing.append(_pick(w, np.flatnonzero(~h)))
            if tr is not None:
                hits = int(hit.sum())
                tr.end(sp, lookups=hit.shape[0], hits=hits,
                       misses=hit.shape[0] - hits)

        if missing[0].shape[0] or missing[1].shape[0]:
            def bounds(pairs):
                if spaces is None:
                    return unpack_keys(_pick(ukeys, pairs))
                return join_keys(_pick(spaces, pairs), _pick(ukeys, pairs))

            if mixed and self.supports_mixed and 0 < ops.sum() < m:
                # both planes of every missed pair from one launch
                pairs = np.union1d(*missing)
                planes[0][pairs], planes[1][pairs] = self._miss.run_mixed(
                    index, *bounds(pairs))
            else:
                for b, (pairs, op) in enumerate(zip(missing, OP_BITS)):
                    if pairs.shape[0] == k:     # every pair: no scatter
                        planes[b] = self._miss.run(index, *bounds(pairs), op)
                    elif pairs.shape[0]:
                        planes[b][pairs] = self._miss.run(
                            index, *bounds(pairs), op)
            if self.cache.capacity > 0:
                sp = tr.begin("cache_put") if tr is not None else None
                put = _join([to_bits(_pick(plane, pairs))
                             for plane, pairs in zip(planes, missing)])
                self.cache.put_many(
                    gen, ekeys[~hit], put,
                    None if espaces is None else espaces[~hit])
                if tr is not None:
                    tr.end(sp, entries=int(put.shape[0]))

        sp = tr.begin("scatter") if tr is not None else None
        if mixed:
            out = planes[0][inverse], planes[1][inverse]
        else:
            out = jnp.asarray(planes[OP_BITS[ops]][inverse])
        if tr is not None:
            tr.end(sp, queries=m, unique=k, op=MIXED if mixed else ops)
        return out

    # -- introspection ----------------------------------------------------
    def stats(self) -> dict:
        counts = dict(self._miss.class_counts)
        executors = self._routed.stats()
        if self.distributed is not None:
            executors = {"distributed": self.distributed.stats()}
        if self._bulk.calls:
            executors["bulk"] = self._bulk.stats()
        return {
            "backend": self.backend,
            "generation": self.generation,
            "batches": self.batches,
            "queries": self.queries_in,
            "dedup_saved": self.dedup_saved,
            "class_counts": counts,
            "cache": self.cache.stats(),
            "executors": executors,
            "tuned": dict(self.tuned) if self.tuned else None,
        }
