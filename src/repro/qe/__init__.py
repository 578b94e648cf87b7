"""Adaptive batched query engine (the system layer above the kernels).

The paper's throughput data is *non-uniform in query span* (Fig. 16
reports per-range-class throughput; §4.5's hybrid exists because long
queries want a different engine than short ones).  This package turns
that observation into an execution layer:

* :class:`QueryPlanner` — classifies each query by span into
  short / mid / long and packs each class into fixed padded bucket
  shapes (bounded set of shapes ⇒ bounded jit retraces as batch
  composition shifts);
* executors (:mod:`repro.qe.executors`) — one per class, holding
  persistent jitted callables: short spans skip the hierarchy via the
  ``rmq_short`` two-chunk kernel, mid spans take the standard walk,
  long spans use the :class:`~repro.core.hybrid.HybridRMQ` O(1)
  sparse-table top; with the fused runtime backend the per-class trio
  is replaced by the :class:`~repro.qe.executors.FusedExecutor` — the
  whole span mix (and both value/index output planes) in ONE
  ``kernels/rmq_fused`` launch per bucket, the planner degrading to a
  single ``FUSED`` class;
* :class:`ResultCache` — an exact LRU keyed by ``(op, index generation,
  l, r)``, stored as arrays and read and written a batch per call
  (``get_many``/``put_many``) on one packed int64 key per query, the key
  the engine's within-batch dedup also sorts; ``RMQ.update``/``append``
  bump the generation so streaming mutations invalidate correctly;
* :class:`QueryEngine` — ties the three together for one index
  (``RMQ.engine()`` on the facade); any
  :class:`repro.core.protocol.RMQIndex` attaches, including the
  mesh-sharded ``DistributedRMQ``, whose batches route through
  :class:`DistributedExecutor` instead (segment-contained spans answered
  shard-locally with no all-reduce, crossing spans via ``pmin``);
* :class:`QueryService` — a multi-index registry with a micro-batching
  admission queue that coalesces small requests into one padded
  execution with per-request scatter-back;
* :class:`BulkExecutor` — the offline analytics path
  (``QueryEngine.query_bulk`` / ``QueryService.submit_bulk``): the
  whole batch endpoint-sorted by ``(chunk(l), chunk(r))`` and answered
  in single level-0-coalesced ``kernels/rmq_bulk`` dispatches that
  share chunk reads across queries, with an autotuned size crossover
  back to the engine's routed path for smaller batches.
"""

from repro.qe.cache import ResultCache
from repro.qe.distributed import CROSSING, SEG_LOCAL, DistributedExecutor
from repro.qe.engine import QueryEngine
from repro.qe.executors import BulkExecutor, FusedExecutor
from repro.qe.planner import FUSED, LONG, MID, SHORT, Bucket, QueryPlanner
from repro.qe.service import QueryService

__all__ = [
    "Bucket",
    "BulkExecutor",
    "CROSSING",
    "DistributedExecutor",
    "FUSED",
    "FusedExecutor",
    "LONG",
    "MID",
    "SEG_LOCAL",
    "SHORT",
    "QueryEngine",
    "QueryPlanner",
    "QueryService",
    "ResultCache",
]
