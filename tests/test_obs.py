"""Unified observability layer: tracer, launch registry, metrics.

Covers the obs subsystem's contracts directly (span nesting under a fake
clock, Chrome-trace schema, Prometheus exposition, registry attribution,
the Histogram torn-read regression) plus the end-to-end wiring: one
ServingTier flush must produce the full span tree and one metrics tree
must export engine cache/span-class/padding series.
"""

from __future__ import annotations

import gc
import json
import threading

import numpy as np
import pytest

from repro.core.api import RMQ
from repro.kernels.profiling import (
    count_launches,
    launch_registry,
    operand_bytes,
)
from repro.obs import trace
from repro.obs.metrics import Counter, Gauge, Histogram, Metrics
from repro.obs.trace import Tracer, use_tracer
from repro.qe import QueryService
from repro.qe.cache import ResultCache
from repro.qe.executors import INDEX, VALUE
from repro.serving import ServingTier


# spans the runtime hooks add while a tracer is installed
RUNTIME = {"gc", "compile"}


class FakeClock:
    """Deterministic monotonic clock for exact span-time assertions."""

    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------
class TestTracer:
    def test_nesting_and_ordering_under_fake_clock(self):
        clock = FakeClock()
        tr = Tracer(clock=clock)
        outer = tr.begin("flush")
        clock.advance(1.0)
        inner = tr.begin("plan")
        clock.advance(0.5)
        tr.end(inner, buckets=2)
        clock.advance(0.25)
        tr.end(outer, tenant="a")
        spans = tr.spans()
        # completion order: children close before parents
        assert [s.name for s in spans] == ["plan", "flush"]
        plan, flush = spans
        assert plan.parent_id == flush.span_id
        assert flush.parent_id is None
        assert (plan.start, plan.end) == (101.0, 101.5)
        assert (flush.start, flush.end) == (100.0, 101.75)
        assert plan.duration == pytest.approx(0.5)
        assert plan.args == {"buckets": 2}
        assert flush.args == {"tenant": "a"}

    def test_sibling_spans_share_parent(self):
        tr = Tracer(clock=FakeClock())
        root = tr.begin("root")
        a = tr.begin("a")
        tr.end(a)
        b = tr.begin("b")
        tr.end(b)
        tr.end(root)
        assert a.parent_id == root.span_id
        assert b.parent_id == root.span_id

    def test_span_context_manager(self):
        tr = Tracer(clock=FakeClock())
        with tr.span("execute", cls="fused") as sp:
            pass
        assert sp.end is not None
        assert tr.spans()[0].args == {"cls": "fused"}

    def test_threads_keep_separate_parent_stacks(self):
        tr = Tracer(clock=FakeClock())
        root = tr.begin("root")

        def worker():
            sp = tr.begin("worker_span")
            tr.end(sp)

        t = threading.Thread(target=worker, name="obs-worker")
        t.start()
        t.join()
        tr.end(root)
        worker_sp = next(s for s in tr.spans() if s.name == "worker_span")
        # never adopts another thread's open span as parent
        assert worker_sp.parent_id is None
        assert worker_sp.thread == "obs-worker"

    def test_unbalanced_end_truncates_descendants(self):
        tr = Tracer(clock=FakeClock())
        outer = tr.begin("outer")
        tr.begin("leaked")          # never explicitly ended
        tr.end(outer)
        nxt = tr.begin("next")
        tr.end(nxt)
        assert nxt.parent_id is None

    def test_ring_buffer_bounds_and_dropped(self):
        tr = Tracer(clock=FakeClock(), capacity=4)
        for i in range(6):
            tr.instant(f"e{i}")
        spans = tr.spans()
        assert len(spans) == 4
        assert [s.name for s in spans] == ["e2", "e3", "e4", "e5"]
        assert tr.dropped == 2
        tr.clear()
        assert tr.spans() == [] and tr.dropped == 0

    def test_record_explicit_timestamps(self):
        tr = Tracer(clock=FakeClock())
        parent = tr.begin("flush")
        sp = tr.record("queue", 10.0, 12.5, parent=parent, queries=3)
        tr.end(parent)
        assert sp.start == 10.0 and sp.end == 12.5
        assert sp.parent_id == parent.span_id
        assert sp.args == {"queries": 3}

    def test_chrome_trace_schema(self, tmp_path):
        clock = FakeClock(0.0)
        tr = Tracer(clock=clock)
        outer = tr.begin("flush")
        clock.advance(0.002)
        inner = tr.begin("plan")
        clock.advance(0.001)
        tr.end(inner)
        tr.end(outer, tenant="a")
        doc = tr.to_chrome_trace()
        assert doc["displayTimeUnit"] == "ms"
        events = doc["traceEvents"]
        assert len(events) == 2
        for e in events:
            assert e["ph"] == "X" and e["cat"] == "repro"
            assert set(e) >= {"name", "ts", "dur", "pid", "tid", "args"}
            assert "span_id" in e["args"]
        plan = next(e for e in events if e["name"] == "plan")
        flush = next(e for e in events if e["name"] == "flush")
        assert plan["ts"] == pytest.approx(2000.0)      # microseconds
        assert plan["dur"] == pytest.approx(1000.0)
        assert plan["args"]["parent_id"] == flush["args"]["span_id"]
        assert flush["args"]["tenant"] == "a"
        # round-trips through the file export
        path = tmp_path / "trace.json"
        tr.save_chrome_trace(str(path))
        assert json.loads(path.read_text()) == doc

    def test_disabled_tracing_is_noop(self):
        assert trace.current() is None
        # module helpers: shared null context, no spans anywhere
        assert trace.span("x") is trace.span("y")
        with trace.span("x") as sp:
            assert sp is None
        assert trace.instant("x") is None
        assert trace.record("x", 0.0, 1.0) is None

    def test_use_tracer_installs_and_restores(self):
        tr = Tracer(clock=FakeClock())
        with use_tracer(tr) as got:
            assert got is tr and trace.current() is tr
            trace.instant("inside")
        assert trace.current() is None
        # a collection may land in the block: its span is the hook's
        assert [s.name for s in tr.spans() if s.name not in RUNTIME] == [
            "inside"]


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------
class TestMetrics:
    def test_prometheus_counter_and_gauge(self):
        m = Metrics()
        m.counter("requests").inc(3)
        m.gauge("depth").set(7)
        text = m.to_prometheus()
        assert "# TYPE repro_requests_total counter" in text
        assert "repro_requests_total 3.0" in text
        assert "# TYPE repro_depth gauge" in text
        assert "repro_depth 7.0" in text
        assert text.endswith("\n")

    def test_gauge_callback_and_failure(self):
        m = Metrics()
        state = {"v": 2}
        g = m.gauge("live", fn=lambda: state["v"])
        assert g.value == 2.0
        state["v"] = 5
        assert g.value == 5.0
        g.set_fn(lambda: 1 / 0)
        assert g.value == 0.0          # a broken callback must not poison
        g.set(9)                       # explicit set clears the callback
        assert g.value == 9.0

    def test_prometheus_histogram_cumulative_buckets(self):
        m = Metrics()
        h = m.histogram("lat", bounds=(1.0, 2.0))
        for v in (0.5, 1.5, 5.0):
            h.record(v)
        text = m.to_prometheus()
        assert "# TYPE repro_lat histogram" in text
        assert 'repro_lat_bucket{le="1.0"} 1' in text
        assert 'repro_lat_bucket{le="2.0"} 2' in text
        assert 'repro_lat_bucket{le="+Inf"} 3' in text
        assert "repro_lat_sum 7.0" in text
        assert "repro_lat_count 3" in text

    def test_labeled_scopes(self):
        m = Metrics()
        tenants = m.scope("tenants", child_label="tenant")
        tenants.scope("search").counter("submits").inc()
        tenants.scope("ads").counter("submits").inc(2)
        text = m.to_prometheus()
        assert 'repro_tenants_submits_total{tenant="search"} 1.0' in text
        assert 'repro_tenants_submits_total{tenant="ads"} 2.0' in text
        # one TYPE line for the shared series
        assert text.count("# TYPE repro_tenants_submits_total") == 1
        # nested dict export keeps the tree shape
        assert m.as_dict()["tenants"]["ads"]["submits"] == 2

    def test_name_collisions_rejected(self):
        m = Metrics()
        m.counter("x")
        with pytest.raises(ValueError):
            m.scope("x")
        with pytest.raises(ValueError):
            m.gauge("x")
        m.scope("s")
        with pytest.raises(ValueError):
            m.counter("s")

    def test_histogram_percentiles(self):
        h = Histogram(bounds=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 3.0, 3.5):
            h.record(v)
        assert h.percentile(0.0) == 0.0 or h.percentile(0.0) <= 1.0
        assert h.percentile(1.0) == 3.5       # clamped to observed max
        d = h.as_dict()
        assert d["count"] == 4 and d["sum"] == pytest.approx(8.5)
        assert d["min"] == 0.5 and d["max"] == 3.5

    def test_histogram_as_dict_torn_read_regression(self):
        """A concurrent record() must never yield count/sum out of sync
        (the old implementation re-read attributes after the lock)."""
        h = Histogram(bounds=(1.0,))
        stop = threading.Event()

        def writer():
            while not stop.is_set():
                h.record(1.0)

        t = threading.Thread(target=writer)
        t.start()
        try:
            for _ in range(2000):
                d = h.as_dict()
                assert d["sum"] == float(d["count"])
                assert d["mean"] in (0.0, 1.0)
        finally:
            stop.set()
            t.join()

    def test_concurrent_recording_stress(self):
        m = Metrics()
        c = m.counter("c")
        h = m.histogram("h", bounds=(0.5,))
        g = m.gauge("g")

        def work():
            for i in range(1000):
                c.inc()
                h.record(1.0)
                g.set(i)

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 8000
        snap_counts, count, total, _, _ = h.snapshot()
        assert count == 8000 and sum(snap_counts) == 8000
        assert total == pytest.approx(8000.0)

    def test_serving_metrics_shim_reexports(self):
        # back-compat: the old import path must expose the same classes
        from repro.serving import metrics as old
        assert old.Counter is Counter
        assert old.Gauge is Gauge
        assert old.Histogram is Histogram
        assert old.Metrics is Metrics


# ---------------------------------------------------------------------------
# ResultCache thread safety
# ---------------------------------------------------------------------------
class TestResultCache:
    def test_capacity_zero_counts_misses(self):
        cache = ResultCache(0)
        cache.put(VALUE, 0, 1, 2, 3.0)
        assert cache.get(VALUE, 0, 1, 2) is None
        assert cache.stats()["misses"] == 1
        assert cache.hit_rate() == 0.0

    def test_concurrent_counters_consistent(self):
        cache = ResultCache(64)
        per_thread = 500

        def work(seed):
            rng = np.random.default_rng(seed)
            for _ in range(per_thread):
                k = int(rng.integers(0, 32))
                if cache.get(VALUE, 0, k, k + 1) is None:
                    cache.put(VALUE, 0, k, k + 1, float(k))

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        s = cache.stats()
        assert s["hits"] + s["misses"] == 8 * per_thread
        assert cache.hit_rate() == pytest.approx(
            s["hits"] / (8 * per_thread))


# ---------------------------------------------------------------------------
# Launch registry
# ---------------------------------------------------------------------------
class TestLaunchRegistry:
    def test_operand_bytes_helper(self):
        a = np.zeros((4, 8), np.float32)
        b = np.zeros(3, np.int32)
        assert operand_bytes(a, None, b) == 4 * 8 * 4 + 3 * 4

    def test_count_launches_contract_unchanged(self):
        # unique geometry: trace-time records fire on first trace only
        rng = np.random.default_rng(0)
        x = rng.random(2897).astype(np.float32)
        engine = RMQ.build(x, c=8, t=8, backend="fused").engine(
            cache_size=0)
        ls = np.array([1, 10, 100], np.int32)
        rs = np.array([5, 200, 2000], np.int32)
        with count_launches() as counts:
            engine.query(ls, rs)
        assert counts == {"rmq_fused": 1}

    def test_registry_attribution_build_and_query(self):
        rng = np.random.default_rng(1)
        x = rng.random(3331).astype(np.float32)
        ls = np.array([0, 7, 31], np.int32)
        rs = np.array([6, 300, 3000], np.int32)
        with launch_registry() as reg:
            engine = RMQ.build(
                x, c=8, t=8, with_positions=True, backend="fused"
            ).engine(cache_size=0)
            engine.query(ls, rs)
        assert reg.counts == {"hierarchy_fused": 1, "rmq_fused": 1}
        by_name = {r.name: r for r in reg.records}
        build = by_name["hierarchy_fused"].meta
        assert build["lowering"] == "pallas"
        assert build["levels"] >= 2
        assert build["operand_bytes"] > 3331 * 4
        query = by_name["rmq_fused"].meta
        # the engine pads batches to pow2 bucket lanes before dispatch,
        # so the recorded count is the bucket shape, not the raw batch
        assert query["queries"] >= 3
        assert query["operand_bytes"] > 0
        ob = reg.operand_bytes()
        assert set(ob) == {"hierarchy_fused", "rmq_fused"}
        dump = reg.as_dict()
        assert dump["counts"] == reg.counts
        assert len(dump["launches"]) == 2


# ---------------------------------------------------------------------------
# Program spans: an engine batch, a build, runtime pauses, the mirror
# ---------------------------------------------------------------------------
N_SPANS = 4096


def _children(spans):
    """``{parent id: [child spans]}`` without the runtime hooks' spans."""
    kids = {}
    for s in spans:
        if s.name not in RUNTIME:
            kids.setdefault(s.parent_id, []).append(s)
    return kids


def _engine(**kw):
    rng = np.random.default_rng(11)
    x = rng.random(N_SPANS).astype(np.float32)
    engine = RMQ.build(x, c=16, t=4, backend="jax").engine(**kw)
    ls = rng.integers(0, N_SPANS - 100, 300).astype(np.int32)
    rs = (ls + rng.integers(0, 100, 300)).astype(np.int32)
    # repeated pairs, so dedup has work
    return engine, np.concatenate([ls, ls[:50]]), np.concatenate(
        [rs, rs[:50]])


class TestProgramSpans:
    def test_engine_batch_span_tree(self):
        engine, ls, rs = _engine(cache_size=64, bulk_crossover=1 << 20)
        engine.query_bulk(ls, rs)          # compiles and fills the cache
        hits, misses = engine.cache.hits, engine.cache.misses
        tr = Tracer(clock=FakeClock())
        with use_tracer(tr):
            engine.query_bulk(ls, rs)
        hits = engine.cache.hits - hits
        misses = engine.cache.misses - misses
        kids = _children(tr.spans())
        (root,) = kids[None]
        assert root.name == "query_bulk"
        assert root.args == {"queries": 350, "route": "routed"}
        names = [s.name for s in kids[root.span_id]]
        assert names == ["dedup", "cache_get", "plan", "execute",
                         "cache_put", "scatter"]
        plan, ex = kids[root.span_id][2:4]
        k = plan.args["buckets"]
        assert plan.args == {"misses": misses, "buckets": k, "op": "value"}
        assert k >= 1
        assert ex.args == {"buckets": k, "count": misses, "op": "value"}
        assert [c.name for c in kids[ex.span_id]] == (
            ["launch"] * k + ["fetch"] * k)
        dedup, get = kids[root.span_id][:2]
        unique = np.unique(np.stack([ls, rs]), axis=1).shape[1]
        assert dedup.args == {"queries": 350, "unique": unique}
        assert get.args == {"lookups": hits + misses, "hits": hits,
                            "misses": misses}
        assert get.args["lookups"] == unique and 0 < hits < unique
        put = kids[root.span_id][-2]
        assert put.args == {"entries": misses}

    def test_bulk_route_spans_nest_under_the_batch(self):
        engine, ls, rs = _engine(cache_size=64, bulk_crossover=16)
        engine.query_bulk(ls, rs)
        tr = Tracer(clock=FakeClock())
        with use_tracer(tr):
            engine.query_bulk(ls, rs)
        kids = _children(tr.spans())
        (root,) = kids[None]
        assert root.args == {"queries": 350, "route": "bulk"}
        assert [s.name for s in kids[root.span_id]] == [
            "plan", "execute", "scatter"]
        ex = kids[root.span_id][1]
        assert [c.name for c in kids[ex.span_id]] == ["launch", "fetch"]

    def test_build_span_tree(self):
        x = np.random.default_rng(5).random(3000).astype(np.float32)
        tr = Tracer(clock=FakeClock())
        with use_tracer(tr):
            RMQ.build(x, c=8, t=4, backend="jax")
        kids = _children(tr.spans())
        (root,) = kids[None]
        assert root.name == "build"
        assert root.args == {"n": 3000, "backend": "jax"}
        plan, dispatch = kids[root.span_id]
        assert (plan.name, dispatch.name) == ("build_plan", "build_dispatch")
        assert dispatch.args == {"backend": "jax"}

    def test_gc_span_only_while_installed(self):
        tr = Tracer()
        with use_tracer(tr):
            assert trace._on_gc in gc.callbacks
            gc.collect()
        full = [s for s in tr.spans()
                if s.name == "gc" and s.args["generation"] == 2]
        assert full and full[-1].end >= full[-1].start
        assert full[-1].args["collected"] >= 0
        assert trace._on_gc not in gc.callbacks
        count = len(tr.spans())
        gc.collect()
        assert len(tr.spans()) == count

    def test_compile_span_only_while_installed(self):
        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda a: a * 3 + 1)
        a, b = jnp.zeros((7, 3)), jnp.zeros((9, 3))
        tr = Tracer(clock=FakeClock())
        with use_tracer(tr):
            outer = tr.begin("launch")
            f(a).block_until_ready()
            tr.end(outer)
        comp = [s for s in tr.spans() if s.name == "compile"]
        assert {s.args["stage"] for s in comp
                if "lambda" in s.args["fun_name"]} == {
            "jaxpr_trace", "to_mlir", "backend_compile"}
        assert all(s.parent_id == outer.span_id for s in comp)
        # mapped onto the tracer's clock: over by the time it is recorded
        assert all(s.start <= s.end <= 100.0 for s in comp)
        count = len(tr.spans())
        f(b).block_until_ready()          # a fresh shape, tracer removed
        assert len(tr.spans()) == count

    def test_mirror_exits_truncated_annotations_innermost_first(
            self, monkeypatch):
        log = []

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                log.append(("enter", self.name))

            def __exit__(self, *exc):
                log.append(("exit", self.name))

        trace._install_hooks()
        monkeypatch.setattr(trace, "_annotation", Annotation)
        tr = Tracer(clock=FakeClock())
        with use_tracer(tr):
            outer = tr.begin("outer")
            tr.begin("mid")
            tr.begin("inner")           # both left open
            tr.end(outer)
            tr.record("queue", 99.0, 100.0)     # after the fact: no mirror
        assert [e for e in log if e[1] != "repro.gc"] == [
            ("enter", "repro.outer"), ("enter", "repro.mid"),
            ("enter", "repro.inner"), ("exit", "repro.inner"),
            ("exit", "repro.mid"), ("exit", "repro.outer")]
        log.clear()
        tr.end(tr.begin("after"))       # removed: no mirror
        assert log == []

    def test_gc_inside_the_tracer_lock_closes_its_span(self):
        # a collection can start while this thread holds the buffer's
        # lock; its span must still be recorded, not deadlock
        tr = Tracer()
        done = []

        def collect_under_lock():
            with tr._lock:
                gc.collect()
            done.append(True)

        with use_tracer(tr):
            t = threading.Thread(target=collect_under_lock, daemon=True)
            t.start()
            t.join(timeout=30)
        assert not t.is_alive() and done
        assert any(s.name == "gc" and s.args["generation"] == 2
                   for s in tr.spans())

    def test_gc_spans_under_thread_stress(self):
        # collections land between any two bytecodes, also while a thread
        # holds the tracer's lock: it must not deadlock or lose spans
        import sys

        tr = Tracer(capacity=1 << 20)
        threads, per = 16, 300
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            with use_tracer(tr):
                def work():
                    for _ in range(per):
                        with tr.span("outer"):
                            junk = [[i] for i in range(50)]  # noqa: F841
                            tr.end(tr.begin("inner"))

                ts = [threading.Thread(target=work) for _ in range(threads)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(timeout=60)
                assert not any(t.is_alive() for t in ts)
        finally:
            sys.setswitchinterval(old)
        spans = tr.spans()
        by_id = {s.span_id: s for s in spans}
        assert sum(s.name == "outer" for s in spans) == threads * per
        assert sum(s.name == "inner" for s in spans) == threads * per
        gcs = [s for s in spans if s.name == "gc"]
        assert gcs
        for s in gcs:
            assert s.end >= s.start
            parent = by_id.get(s.parent_id)
            assert parent is None or parent.thread == s.thread

    def test_profiler_mirror_holds_program_spans(self, tmp_path):
        import jax
        from jax.profiler import ProfileData

        engine, ls, rs = _engine(cache_size=64, bulk_crossover=1 << 20)
        engine.query_bulk(ls, rs)
        tr = Tracer()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with use_tracer(tr):
                engine.query_bulk(ls, rs)
        finally:
            jax.profiler.stop_trace()
        (path,) = tmp_path.rglob("*.xplane.pb")
        got = {}
        for plane in ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("repro."):
                        got.setdefault(ev.name, []).append(
                            (ev.end_ns - ev.start_ns) / 1e9)
        want = {}
        for s in tr.spans():
            want.setdefault("repro." + s.name, []).append(s.duration)
        for name in ("query_bulk", "dedup", "cache_get", "plan", "execute",
                     "launch", "fetch", "cache_put", "scatter"):
            name = "repro." + name
            assert len(got[name]) == len(want[name]), name
            assert sum(got[name]) == pytest.approx(
                sum(want[name]), rel=0.05, abs=1e-3), name
        assert "repro.compile" not in got


# ---------------------------------------------------------------------------
# End-to-end wiring
# ---------------------------------------------------------------------------
class TestEndToEnd:
    def test_service_engine_metrics_export(self):
        m = Metrics()
        svc = QueryService(auto_flush=False, metrics=m)
        x = np.random.default_rng(2).random(512).astype(np.float32)
        svc.register("idx", RMQ.build(x, c=8, t=8, backend="jax"),
                     cache_size=16)
        tk = svc.submit("idx", np.array([1, 5]), np.array([3, 9]), VALUE)
        svc.flush(names=("idx",))
        np.asarray(svc.take(tk))
        prom = m.to_prometheus()
        assert 'repro_engines_cache_hit_rate{index="idx"}' in prom
        assert 'repro_engines_span_class_short{index="idx"}' in prom
        assert "repro_engines_bucket_padding_waste_bucket" in prom
        assert "repro_flushes" in prom
        d = m.as_dict()
        assert d["engines"]["idx"]["queries"] >= 2
        assert d["engines"]["idx"]["span_class_short"] >= 2

    def test_tier_flush_produces_full_span_tree(self):
        clock = FakeClock()
        tracer = Tracer(clock=clock)
        tier = ServingTier(clock=clock)
        x = np.random.default_rng(3).random(256).astype(np.float32)
        tier.register_tenant(
            "t", RMQ.build(x, c=8, t=8, with_positions=True,
                           backend="fused"),
            slo_ms=5.0, cache_size=0,
        )
        with use_tracer(tracer):
            for op in (VALUE, INDEX):
                tier.submit("t", np.array([1, 9], np.int32),
                            np.array([6, 200], np.int32), op)
                clock.advance(0.001)
            tier.drain("t")
        spans = tracer.spans()
        by_id = {s.span_id: s for s in spans}
        names = {s.name for s in spans}
        assert {"submit", "admission", "queue", "flush", "snapshot_swap",
                "service_flush", "plan", "execute", "scatter"} <= names

        flush = next(s for s in spans if s.name == "flush")
        assert flush.args["requests"] == 2
        # admission nests under submit on the caller thread
        admission = next(s for s in spans if s.name == "admission")
        assert by_id[admission.parent_id].name == "submit"
        assert admission.args["admitted"] is True
        # retroactive queue spans hang off the flush and carry the real
        # submit->drain wait on the shared clock
        queues = [s for s in spans if s.name == "queue"]
        assert len(queues) == 2
        for q in queues:
            assert q.parent_id == flush.span_id
            assert q.end - q.start > 0
        # engine spans reach the flush through the parent chain
        scatter = next(s for s in spans if s.name == "scatter")
        chain = []
        cur = scatter
        while cur.parent_id is not None:
            cur = by_id[cur.parent_id]
            chain.append(cur.name)
        assert chain == ["service_flush", "flush"]
        # the whole thing exports as a valid Chrome trace
        doc = tracer.to_chrome_trace()
        assert len(doc["traceEvents"]) == len(spans)
