"""``QueryEngine`` — span-routed, deduped, cached batched RMQ execution.

One engine serves one index — anything implementing the
:class:`repro.core.protocol.RMQIndex` protocol: ``RMQ``, ``StreamingRMQ``,
``HybridRMQ``, or the mesh-sharded ``DistributedRMQ``.  The engine is a
*host-side* orchestration layer: classification, packing, dedup and cache
bookkeeping run in numpy; only the packed buckets touch the device,
through persistent jitted callables (see :mod:`repro.qe.executors`).

Execution pipeline per batch::

    validate -> pack keys ((l << 31) | r, one int64 per query;
                          past 2^31 a key space beside it)
             -> dedup (np.unique on the keys)
             -> LRU lookup (ResultCache.get_many: one call per batch)
             -> planner buckets -> per-class executors
             -> LRU insert (ResultCache.put_many) -> scatter-back

Every host step is an array operation over the batch; none runs Python
per query.

For single-hierarchy indices the miss classes are short / mid / long span
buckets; for distributed indices the planner is replaced by the
segment-aware :class:`repro.qe.distributed.DistributedExecutor`
(segment-contained spans answered shard-locally with no all-reduce,
crossing spans through the ``pmin`` path).

With the **fused** runtime backend the engine prefers the
:class:`repro.qe.executors.FusedExecutor`: the planner degrades to a
single bucket class (``kernels/rmq_fused`` decomposes spans in-kernel,
so the short/mid/long split buys nothing) and each bucket is one
launch; :meth:`QueryEngine.query_mixed` additionally serves a batch
mixing value and index ops from that same single launch (both output
planes come out of one kernel call).  Dedup, the LRU result cache, and
the service's coalescing all operate unchanged on top.

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
from repro.core.query import check_query_args
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
    VALUE,
    BulkExecutor,
    FusedExecutor,
    LongSpanExecutor,
    MidSpanExecutor,
    ShortSpanExecutor,
)
from repro.qe.planner import FUSED, LONG, MID, SHORT, QueryPlanner

__all__ = ["QueryEngine"]


def _quantized(index) -> bool:
    """Does ``index`` store bf16 value summaries (exact-recovery walks)?"""
    return (
        getattr(index.plan, "summary_dtype", "float32") == "bfloat16"
    )


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
        self._configure_executors(self.backend)
        self.batches = 0
        self.queries_in = 0
        self.dedup_saved = 0
        self.class_counts = {SHORT: 0, MID: 0, LONG: 0, FUSED: 0}
        self._index = None
        self.planner: Optional[QueryPlanner] = None
        self.distributed: Optional[DistributedExecutor] = None
        self.metrics: Optional[Metrics] = None
        self._coord = np.dtype(np.int32)  # coordinate dtype, per attach
        self._m_padding = None
        self._m_padded_lanes = None
        self._m_live_lanes = None
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
        """Batch size at which :meth:`query_bulk` leaves the fused path.

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

    def _configure_executors(self, backend: str) -> None:
        """(Re)build the executor table for ``backend`` — called at
        construction and when an attach adopts a different tuned
        backend (dropping the old backend's compiled tables)."""
        self.executors = {
            SHORT: ShortSpanExecutor(backend, interpret=self._interpret),
            MID: MidSpanExecutor(backend, interpret=self._interpret),
            LONG: LongSpanExecutor(),
        }
        if backend == "fused":
            # the whole span mix in one launch per bucket — the per-class
            # executors above never run (the planner emits FUSED only)
            self.executors[FUSED] = FusedExecutor(interpret=self._interpret)

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
        for cls in (SHORT, MID, LONG, FUSED):
            metrics.gauge(f"span_class_{cls}",
                          fn=lambda c=cls: self.class_counts[c])
        self._m_padding = metrics.histogram(
            "bucket_padding_waste", SIZE_BUCKETS)
        self._m_padded_lanes = metrics.counter("padded_lanes")
        self._m_live_lanes = metrics.counter("live_lanes")
        self._m_tuned = metrics.info("tuned_config")
        if self.tuned is not None:
            self._m_tuned.set({k: str(v) for k, v in self.tuned.items()})

    def _note_bucket(self, bucket) -> None:
        """Per-bucket accounting shared by both execution paths."""
        self.class_counts[bucket.cls] += bucket.count
        if self._m_padding is not None:
            self._m_padding.record(bucket.padding)
            self._m_padded_lanes.inc(bucket.padding)
            self._m_live_lanes.inc(bucket.count)

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
            self.planner = None
            self.tuned = None
            self.bulk_crossover = self._resolve_bulk_crossover(index)
            if self.distributed is None:
                self.distributed = DistributedExecutor(
                    min_bucket=self._min_bucket,
                    max_bucket=self._max_bucket,
                )
        else:
            self.distributed = None
            # Re-resolve the tuned config against the new binding: a
            # successor index may carry a different plan (and cache
            # lookups key on the live length).  Adopting a different
            # tuned backend rebuilds the executor table.
            backend = self._resolve_backend(index)
            if backend != self.backend:
                self.backend = backend
                self._configure_executors(backend)
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
            planner = QueryPlanner(
                c=plan.c,
                num_levels=plan.num_levels,
                long_cutoff=resolved["long_cutoff"],
                long_enabled=resolved["long_enabled"],
                min_bucket=self._min_bucket,
                max_bucket=self._max_bucket,
                fused=self.backend == "fused",
                scan_chunks=resolved["scan_chunks"],
            )
            if planner != self.planner:
                self.planner = planner
            self._record_tuned(index, resolved)
        self._index = index
        self.executors[LONG].invalidate()

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
        return self._execute(ls, rs, VALUE)

    def query_index(self, ls, rs) -> jnp.ndarray:
        """Batched ``RMQ_index``; bit-identical to the index's oracle."""
        if not self._index.with_positions:
            raise ValueError(
                "index was built without positions; rebuild it with "
                "with_positions=True to serve RMQ_index queries"
            )
        return self._execute(ls, rs, INDEX)

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
        cache > analytic model) take the standard fused path instead:
        below the crossover the bulk pass's fixed ladder cost loses, and
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
        index = self._index
        if op == INDEX and not index.with_positions:
            raise ValueError(
                "index was built without positions; rebuild it with "
                "with_positions=True to serve RMQ_index queries"
            )
        # the root span of one batch: every engine span below nests in it
        tr = trace.current()
        sp = tr.begin("query_bulk") if tr is not None else None
        try:
            n = live_length(index)
            ls, rs = check_query_args(ls, rs, n)
            ls = np.asarray(ls, self._coord).ravel()
            rs = np.asarray(rs, self._coord).ravel()
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
                return self._execute(ls, rs, op)
            self.batches += 1
            self.queries_in += ls.shape[0]
            if self.distributed is not None:
                res = self.distributed.run_bulk(index, ls, rs, op)
            else:
                res = self._bulk.run(index.hierarchy, ls, rs, op)
            out_dtype = (
                self._coord if op == INDEX else np.dtype(index.value_dtype)
            )
            return jnp.asarray(
                np.asarray(res).astype(out_dtype, copy=False))
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
        return FUSED in self.executors and self.distributed is None

    def query_mixed(self, ls, rs, is_index) -> tuple:
        """Answer a batch mixing ``RMQ_value`` and ``RMQ_index`` ops.

        ``is_index[i]`` selects row ``i``'s op.  Returns ``(values,
        positions)`` numpy arrays of the batch length; only the plane
        selected by ``is_index`` is meaningful per row (the other
        plane's entry is unspecified).  On a fused engine the whole
        deduped miss batch executes through :class:`FusedExecutor` with
        both planes from the same launch; elsewhere it falls back to one
        standard execution per op.  Results are bit-identical to
        :meth:`query` / :meth:`query_index` row-wise.
        """
        index = self._index
        is_index = np.asarray(is_index, bool).ravel()
        if is_index.any() and not index.with_positions:
            raise ValueError(
                "index was built without positions; rebuild it with "
                "with_positions=True to serve RMQ_index queries"
            )
        n = live_length(index)
        ls, rs = check_query_args(ls, rs, n)
        ls = np.asarray(ls, self._coord).ravel()
        rs = np.asarray(rs, self._coord).ravel()
        if ls.shape != is_index.shape:
            raise ValueError(
                f"is_index must match the batch, got {is_index.shape} "
                f"vs {ls.shape}"
            )
        m = ls.shape[0]
        val_dtype = np.dtype(index.value_dtype)
        vals_out = np.zeros((m,), val_dtype)
        pos_out = np.zeros((m,), self._coord)
        if m == 0:
            return vals_out, pos_out

        single_op = is_index.all() or not is_index.any()
        if not self.supports_mixed or single_op:
            # per-op path: also taken by genuinely single-op batches on
            # fused engines — the dual-plane launch would waste the
            # unused plane (and track positions value-only builds lack)
            vi = np.nonzero(~is_index)[0]
            ii = np.nonzero(is_index)[0]
            if vi.shape[0]:
                vals_out[vi] = np.asarray(
                    self._execute(ls[vi], rs[vi], VALUE)
                )
            if ii.shape[0]:
                pos_out[ii] = np.asarray(
                    self._execute(ls[ii], rs[ii], INDEX)
                )
            return vals_out, pos_out

        self.batches += 1
        self.queries_in += m

        # Dedup on (l, r) pairs — the fused launch computes both planes
        # for every query anyway, so value and index requests for the
        # same range share one execution.
        tr = trace.current()
        sp = tr.begin("dedup") if tr is not None else None
        ukeys, inverse = np.unique(pack_keys(ls, rs), return_inverse=True)
        k = ukeys.shape[0]
        self.dedup_saved += m - k
        if tr is not None:
            tr.end(sp, queries=m, unique=k)
        uv = np.zeros((k,), val_dtype)
        up = np.zeros((k,), np.int32)
        need_val = np.zeros((k,), bool)
        need_pos = np.zeros((k,), bool)
        need_val[inverse[~is_index]] = True
        need_pos[inverse[is_index]] = True

        # one cache entry per (op, pair) needed: pair i's value entry,
        # then its index entry, in pair order.  Flat index e = 2 * pair
        # + op bit (OP_BITS: value 0, index 1), so the entry keys
        # 2 * key + bit ascend with e.
        need = np.stack([need_val, need_pos], axis=1)
        gen = self.generation
        if self.cache.capacity > 0:
            sp = self._begin_cache_get(tr)
            e = np.flatnonzero(need)
            rows, is_pos = e >> 1, (e & 1).astype(bool)
            bits, hit = self.cache.get_many(
                gen, entry_keys(ukeys[rows], e & 1))
            sel = hit & ~is_pos
            uv[rows[sel]] = from_bits(bits[sel], val_dtype)
            sel = hit & is_pos
            up[rows[sel]] = from_bits(bits[sel], np.int32)
            missing = np.zeros((k,), bool)
            missing[rows[~hit]] = True
            miss_idx = np.flatnonzero(missing)
            if tr is not None:
                self._end_cache_get(tr, sp)
        else:
            miss_idx = np.arange(k)

        if miss_idx.shape[0]:
            h = index.hierarchy
            fused = self.executors[FUSED]
            mls, mrs = unpack_keys(ukeys[miss_idx])
            sp = tr.begin("plan") if tr is not None else None
            buckets = self.planner.plan(mls, mrs)
            if tr is not None:
                tr.end(sp, misses=int(miss_idx.shape[0]),
                       buckets=len(buckets), op="mixed")
            for bucket in buckets:
                if bucket.count == 0:
                    continue
                self._note_bucket(bucket)
                sp = tr.begin("execute") if tr is not None else None
                sub = tr.begin("launch") if tr is not None else None
                bv, bp = fused.run_mixed(
                    h, jnp.asarray(bucket.ls), jnp.asarray(bucket.rs)
                )
                if tr is not None:
                    tr.end(sub)
                    sub = tr.begin("fetch")
                rows = miss_idx[bucket.idxs]
                uv[rows] = np.asarray(bv)[: bucket.count].astype(
                    val_dtype, copy=False
                )
                up[rows] = np.asarray(bp)[: bucket.count]
                if tr is not None:
                    tr.end(sub)
                    tr.end(sp, cls=bucket.cls, count=bucket.count,
                           shape=bucket.shape, op="mixed")
            if self.cache.capacity > 0:
                sp = tr.begin("cache_put") if tr is not None else None
                e = np.flatnonzero(need[miss_idx])
                rows = miss_idx[e >> 1]
                bits = np.where(e & 1, to_bits(up[rows]),
                                to_bits(uv[rows]))
                self.cache.put_many(gen, entry_keys(ukeys[rows], e & 1),
                                    bits)
                if tr is not None:
                    tr.end(sp, entries=int(miss_idx.shape[0]))

        sp = tr.begin("scatter") if tr is not None else None
        out = uv[inverse], up[inverse]
        if tr is not None:
            tr.end(sp, queries=m, unique=k, op="mixed")
        return out

    # -- execution --------------------------------------------------------
    def _begin_cache_get(self, tr):
        """Open the ``cache_get`` span holding the cache's counters; its
        end replaces them by their deltas (nothing counts per query)."""
        if tr is None:
            return None
        sp = tr.begin("cache_get")
        sp.args.update(hits=self.cache.hits, misses=self.cache.misses)
        return sp

    def _end_cache_get(self, tr, sp) -> None:
        hits = self.cache.hits - sp.args["hits"]
        misses = self.cache.misses - sp.args["misses"]
        tr.end(sp, lookups=hits + misses, hits=hits, misses=misses)

    # NOTE: query_mixed above carries a dual-plane variant of this
    # dedup -> LRU -> bucket-execute -> cache-writeback pipeline (its
    # cache entries are per-op, its execution per-(l,r) pair); cache or
    # dedup semantics changed here must change there too.
    def _execute(self, ls, rs, op: str) -> jnp.ndarray:
        index = self._index
        n = live_length(index)
        ls, rs = check_query_args(ls, rs, n)
        ls = np.asarray(ls, self._coord).ravel()
        rs = np.asarray(rs, self._coord).ravel()
        m = ls.shape[0]
        out_dtype = (
            self._coord if op == INDEX else np.dtype(index.value_dtype)
        )
        if m == 0:
            return jnp.zeros((0,), out_dtype)

        self.batches += 1
        self.queries_in += m

        # -- within-batch dedup -------------------------------------------
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
        uniq_res = np.empty((k,), out_dtype)

        # -- LRU lookup ---------------------------------------------------
        gen = self.generation
        if self.cache.capacity > 0:
            sp = self._begin_cache_get(tr)
            ekeys = entry_keys(ukeys, OP_BITS[op])
            vals, hit = self.cache.get_many(gen, ekeys, out_dtype, spaces)
            uniq_res[hit] = vals[hit]
            miss_idx = np.flatnonzero(~hit)
            if tr is not None:
                self._end_cache_get(tr, sp)
        else:
            miss_idx = np.arange(k)

        # -- plan + execute the misses ------------------------------------
        if miss_idx.shape[0]:
            if spaces is None:
                mls, mrs = unpack_keys(ukeys[miss_idx])
            else:
                mls, mrs = join_keys(spaces[miss_idx], ukeys[miss_idx])
            if self.distributed is not None:
                res = self.distributed.run(index, mls, mrs, op)
                uniq_res[miss_idx] = res.astype(out_dtype, copy=False)
            else:
                h = index.hierarchy
                sp = tr.begin("plan") if tr is not None else None
                buckets = self.planner.plan(mls, mrs)
                if tr is not None:
                    tr.end(sp, misses=int(miss_idx.shape[0]),
                           buckets=len(buckets), op=op)
                for bucket in buckets:
                    if bucket.count == 0:
                        continue
                    self._note_bucket(bucket)
                    sp = tr.begin("execute") if tr is not None else None
                    sub = tr.begin("launch") if tr is not None else None
                    res = self.executors[bucket.cls].run(
                        h, jnp.asarray(bucket.ls), jnp.asarray(bucket.rs),
                        op,
                    )
                    if tr is not None:
                        tr.end(sub)
                        sub = tr.begin("fetch")
                    res = np.asarray(res)[: bucket.count].astype(
                        out_dtype, copy=False
                    )
                    if tr is not None:
                        tr.end(sub)
                        tr.end(sp, cls=bucket.cls, count=bucket.count,
                               shape=bucket.shape, op=op,
                               **self.executors[bucket.cls].span_args(h))
                    uniq_res[miss_idx[bucket.idxs]] = res
            if self.cache.capacity > 0:
                sp = tr.begin("cache_put") if tr is not None else None
                self.cache.put_many(
                    gen, ekeys[miss_idx], uniq_res[miss_idx],
                    None if spaces is None else spaces[miss_idx])
                if tr is not None:
                    tr.end(sp, entries=int(miss_idx.shape[0]))

        sp = tr.begin("scatter") if tr is not None else None
        out = jnp.asarray(uniq_res[inverse])
        if tr is not None:
            tr.end(sp, queries=m, unique=k, op=op)
        return out

    # -- introspection ----------------------------------------------------
    def stats(self) -> dict:
        counts = dict(self.class_counts)
        executors = {
            cls: ex.stats() for cls, ex in self.executors.items()
        }
        if self.distributed is not None:
            counts = dict(self.distributed.class_counts)
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
