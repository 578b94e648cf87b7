"""repro.obs — unified observability: tracing, metrics, launch registry.

Three surfaces, one import point:

* :mod:`repro.obs.trace` — spans of the request lifecycle
  (``submit → admission → queue → snapshot_swap → plan → execute →
  scatter``), of a bulk batch (``query_bulk → dedup, cache_get, plan,
  execute → {launch, fetch}, cache_put, scatter``), of a build
  (``build → build_plan, build_dispatch``) and of runtime pauses
  (``gc``, ``compile``), with Chrome-trace/Perfetto export and a
  ``repro.*`` mirror into the ``jax.profiler`` trace;
* :mod:`repro.obs.metrics` — counters/gauges/histograms with dict and
  Prometheus text exposition (promoted from ``repro.serving.metrics``);
* :mod:`repro.kernels.profiling` — the kernel launch registry
  (lives next to the kernels it instruments; re-exported here).

All three follow the same discipline: a single module-global check on
the hot path, zero locks and zero allocations when disabled.
"""

from repro.kernels.profiling import (
    LaunchRecord,
    LaunchRegistry,
    count_launches,
    launch_registry,
    record_launch,
)
from repro.obs.metrics import (
    LATENCY_BUCKETS,
    SIZE_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    Metrics,
)
from repro.obs.trace import Span, Tracer, set_tracer, use_tracer

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "LATENCY_BUCKETS",
    "LaunchRecord",
    "LaunchRegistry",
    "Metrics",
    "SIZE_BUCKETS",
    "Span",
    "Tracer",
    "count_launches",
    "launch_registry",
    "record_launch",
    "set_tracer",
    "use_tracer",
]
