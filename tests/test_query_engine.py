"""Adaptive batched query engine (repro.qe): parity, cache, service.

The engine's contract is *bit-identical* results — values and
leftmost-tie positions — to the monolithic ``rmq_value_batch`` /
``rmq_index_batch`` oracles, across all span classes, before and after
streaming mutations.  Must-run coverage is written as numpy RNG loops;
hypothesis adds randomized depth when installed (tier-1 environments
without it skip those only).
"""

import numpy as np
import pytest
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st

from repro.core.api import RMQ
from repro.core.query import rmq_index_batch, rmq_value_batch
from repro.qe import (FUSED, LONG, MID, SHORT, QueryEngine, QueryPlanner,
                     QueryService)
from repro.qe.cache import ResultCache, pack_keys, unpack_keys


def _mixed_queries(rng, n, c, m):
    """Bounds spread across all three span classes, with ties upstream."""
    spans = np.concatenate([
        rng.integers(1, 2 * c + 1, m // 3 + 1),          # short-ish
        rng.integers(2 * c + 1, max(n // 4, 2 * c + 2), m // 3 + 1),
        rng.integers(max(n // 2, 2), n + 1, m // 3 + 1),  # long
    ])[:m]
    rng.shuffle(spans)
    ls = (rng.random(m) * np.maximum(n - spans + 1, 1)).astype(np.int64)
    rs = np.minimum(ls + spans - 1, n - 1)
    return ls.astype(np.int32), rs.astype(np.int32)


def _build(n, c, t, seed=0, ties=True, **kw):
    rng = np.random.default_rng(seed)
    x = rng.random(n).astype(np.float32)
    if ties:
        x[rng.integers(0, n, max(n // 8, 1))] = 0.5
    rmq = RMQ.build(x, c=c, t=t, with_positions=True, backend="jax", **kw)
    return rng, x, rmq


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------
class TestPlanner:
    def test_classification(self):
        p = QueryPlanner(c=128, num_levels=3)
        ls = np.array([0, 100, 127, 0, 0], np.int32)
        rs = np.array([255, 300, 128, 50_000, 2**20], np.int32)
        labels = p.classify(ls, rs)
        # (0,255): chunks 0..1; (100,300): chunks 0..2 -> mid-or-long;
        # (127,128): crosses one boundary
        assert labels[0] == SHORT and labels[2] == SHORT
        assert labels[1] == MID
        assert labels[4] == LONG
        assert p.effective_long_cutoff() == 2 * 128 * 128

    def test_long_disabled_for_single_level(self):
        p = QueryPlanner(c=128, num_levels=1)
        labels = p.classify(np.array([0]), np.array([2**20]))
        assert labels[0] == MID

    def test_bucket_shapes_bounded_pow2(self):
        p = QueryPlanner(c=8, num_levels=2, min_bucket=16, max_bucket=64)
        rng = np.random.default_rng(0)
        ls = rng.integers(0, 1000, 333).astype(np.int32)
        rs = np.minimum(ls + rng.integers(1, 500, 333), 999).astype(np.int32)
        buckets = p.plan(ls, rs)
        covered = np.concatenate([b.idxs for b in buckets])
        assert sorted(covered.tolist()) == list(range(333))
        for b in buckets:
            assert b.shape in (16, 32, 64)
            assert b.count <= b.shape
            # padded slots hold the (0, 0) sentinel
            assert (b.ls[b.count:] == 0).all() and (b.rs[b.count:] == 0).all()

    def test_long_cutoff_override_boundary(self):
        # the cutoff is inclusive: span == cutoff routes LONG, one less
        # routes MID (chunk-misaligned so neither is SHORT)
        cutoff = 1000
        p = QueryPlanner(c=128, num_levels=3, long_cutoff=cutoff)
        assert p.effective_long_cutoff() == cutoff
        ls = np.array([1, 1], np.int32)
        rs = np.array([1 + cutoff - 1, 1 + cutoff - 2], np.int32)
        labels = p.classify(ls, rs)
        assert labels[0] == LONG    # span == cutoff exactly
        assert labels[1] == MID     # span == cutoff - 1

    def test_long_cutoff_larger_than_n(self):
        # a cutoff no span can reach: the long route exists but never
        # fires — everything walks (or short-scans)
        n = 10_000
        p = QueryPlanner(c=128, num_levels=3, long_cutoff=n + 1)
        ls = np.zeros(3, np.int32)
        rs = np.array([n - 1, n // 2, 100], np.int32)
        labels = p.classify(ls, rs)
        assert LONG not in labels
        assert labels[0] == MID and labels[2] == SHORT

    def test_analytic_default_boundary(self):
        # with no override the cutoff is the analytic 2c * c^(L-2)
        p = QueryPlanner(c=8, num_levels=3)
        cutoff = 2 * 8 * 8
        assert p.effective_long_cutoff() == cutoff
        ls = np.array([1, 1], np.int32)
        rs = np.array([cutoff, cutoff - 1], np.int32)
        assert list(p.classify(ls, rs)) == [LONG, MID]

    def test_scan_chunks_one(self):
        # scan_chunks=1: only strictly chunk-contained spans are SHORT
        p = QueryPlanner(c=128, num_levels=2, scan_chunks=1)
        ls = np.array([0, 100], np.int32)
        rs = np.array([127, 200], np.int32)   # contained / crossing
        assert list(p.classify(ls, rs)) == [SHORT, MID]

    def test_cache_fed_cutoff_round_trip_through_engine(self):
        # a tuned long_cutoff from a TuningCache must land in the
        # engine's planner — and results stay bit-identical
        from repro.tune import TunedConfig, TuningCache

        n, c, t = 50_000, 128, 4
        rng, x, rmq = _build(n, c, t, seed=3)
        cutoff = 2_000
        cache = TuningCache()
        cache.put("cpu", n, "mixed", TunedConfig(
            c=c, t=t, backend="jax", planner="routed",
            long_cutoff=cutoff))
        engine = QueryEngine(rmq, cache_size=0, tuning=cache,
                             span_mix="mixed")
        assert engine.planner.effective_long_cutoff() == cutoff
        assert engine.tuned["long_cutoff"] == cutoff
        assert engine.tuned["source"] == "cache"
        ls, rs = _mixed_queries(rng, n, c, 400)
        np.testing.assert_array_equal(
            np.asarray(engine.query(ls, rs)),
            np.asarray(rmq_value_batch(
                rmq.hierarchy, jnp.asarray(ls), jnp.asarray(rs))),
        )
        # spans past the tuned cutoff actually took the long route
        assert engine.stats()["class_counts"][LONG] > 0
        # an explicit ctor override outranks the cache
        engine2 = QueryEngine(rmq, cache_size=0, tuning=cache,
                              span_mix="mixed", long_cutoff=5_000)
        assert engine2.planner.effective_long_cutoff() == 5_000


# ---------------------------------------------------------------------------
# engine parity (the acceptance contract)
# ---------------------------------------------------------------------------
class TestEngineParity:
    @pytest.mark.parametrize("n,c,t", [
        (100_000, 128, 4),   # 3 levels: all classes populated
        (50_000, 128, 64),   # 2 levels: mid structurally empty
        (4096, 8, 4),        # deep hierarchy, tiny chunks
        (700, 16, 2),
        (300, 128, 64),      # single level: everything mid/short
    ])
    def test_bit_identical_mixed_spans(self, n, c, t):
        rng, x, rmq = _build(n, c, t, seed=n)
        engine = rmq.engine()
        ls, rs = _mixed_queries(rng, n, c, 600)
        # inject duplicates to exercise dedup scatter-back
        ls[50:80], rs[50:80] = ls[0], rs[0]
        lsj, rsj = jnp.asarray(ls), jnp.asarray(rs)
        np.testing.assert_array_equal(
            np.asarray(engine.query(ls, rs)),
            np.asarray(rmq_value_batch(rmq.hierarchy, lsj, rsj)),
        )
        np.testing.assert_array_equal(
            np.asarray(engine.query_index(ls, rs)),
            np.asarray(rmq_index_batch(rmq.hierarchy, lsj, rsj)),
        )

    def test_all_classes_exercised(self):
        rng, x, rmq = _build(100_000, 128, 4, seed=1)
        engine = rmq.engine(cache_size=0)
        ls, rs = _mixed_queries(rng, 100_000, 128, 900)
        engine.query(ls, rs)
        counts = engine.stats()["class_counts"]
        assert counts[SHORT] > 0 and counts[MID] > 0 and counts[LONG] > 0

    def test_pallas_backend_interpret(self):
        """Routing through the Pallas kernels (interpret mode) matches."""
        rng, x, rmq = _build(20_000, 128, 4, seed=2)
        engine = QueryEngine(rmq, backend="pallas", interpret=True,
                             cache_size=0, max_bucket=256)
        ls, rs = _mixed_queries(rng, 20_000, 128, 120)
        lsj, rsj = jnp.asarray(ls), jnp.asarray(rs)
        np.testing.assert_array_equal(
            np.asarray(engine.query(ls, rs)),
            np.asarray(rmq_value_batch(rmq.hierarchy, lsj, rsj)),
        )
        np.testing.assert_array_equal(
            np.asarray(engine.query_index(ls, rs)),
            np.asarray(rmq_index_batch(rmq.hierarchy, lsj, rsj)),
        )

    def test_value_only_index_raises(self):
        x = np.random.default_rng(0).random(5000).astype(np.float32)
        rmq = RMQ.build(x, c=16, t=4, backend="jax")
        with pytest.raises(ValueError, match="without positions"):
            rmq.engine().query_index(np.array([0]), np.array([10]))

    @pytest.mark.parametrize("crossover", [1 << 20, 1])
    @pytest.mark.parametrize("case", ["float", "shape", "range"])
    def test_query_bulk_rejects_bad_bounds(self, case, crossover,
                                           monkeypatch):
        """query_bulk checks its bounds on the host before routing."""
        _, _, rmq = _build(1000, 16, 4)
        engine = rmq.engine(bulk_crossover=crossover)
        ls = np.array([0, 5, 17], np.int32)
        rs = np.array([9, 700, 999], np.int32)
        if case == "float":
            with pytest.raises(TypeError, match="integers"):
                engine.query_bulk(ls, rs.astype(np.float32))
        elif case == "shape":
            with pytest.raises(ValueError, match="shape"):
                engine.query_bulk(ls, rs[:2])
        else:
            monkeypatch.setenv("REPRO_RMQ_DEBUG", "1")
            with pytest.raises(ValueError, match="violates"):
                engine.query_bulk(ls, rs + 1, "index")
        assert engine.batches == 0

    def test_empty_batch(self):
        _, _, rmq = _build(1000, 16, 4)
        out = rmq.engine().query(np.zeros((0,), np.int32),
                                 np.zeros((0,), np.int32))
        assert out.shape == (0,)

    def test_int32_capacity_guard(self):
        """Capacities past int32 index space are refused loudly (the
        query stack — planner packing, short kernel, core walk — does
        int32 index math; silent wraps would break parity)."""
        import dataclasses as dc

        _, _, rmq = _build(1000, 16, 4)
        huge_plan = dc.replace(rmq.plan, capacity=2**31)
        huge = dc.replace(
            rmq, hierarchy=dc.replace(rmq.hierarchy, plan=huge_plan)
        )
        with pytest.raises(ValueError, match="int32 query index space"):
            QueryEngine(huge)

    @settings(max_examples=25, deadline=None)
    @given(
        n=st.integers(min_value=2, max_value=3000),
        seed=st.integers(min_value=0, max_value=2**31 - 1),
    )
    def test_property_parity(self, n, seed):
        rng = np.random.default_rng(seed)
        x = rng.integers(-4, 4, n).astype(np.float32)  # heavy ties
        rmq = RMQ.build(x, c=8, t=2, with_positions=True, backend="jax")
        engine = rmq.engine()
        m = 64
        ls = rng.integers(0, n, m)
        rs = np.minimum(ls + rng.integers(0, n, m), n - 1)
        ls = np.minimum(ls, rs).astype(np.int32)
        rs = np.maximum(ls, rs).astype(np.int32)
        lsj, rsj = jnp.asarray(ls), jnp.asarray(rs)
        np.testing.assert_array_equal(
            np.asarray(engine.query(ls, rs)),
            np.asarray(rmq_value_batch(rmq.hierarchy, lsj, rsj)),
        )
        np.testing.assert_array_equal(
            np.asarray(engine.query_index(ls, rs)),
            np.asarray(rmq_index_batch(rmq.hierarchy, lsj, rsj)),
        )


# ---------------------------------------------------------------------------
# streaming mutations + cache invalidation
# ---------------------------------------------------------------------------
class TestMutationInvalidation:
    def test_update_invalidates_cached_result(self):
        """The stale-cache regression: same (l, r) before/after update."""
        rng, x, rmq = _build(50_000, 128, 4, seed=3, ties=False)
        engine = rmq.engine()
        l, r = 1000, 30_000
        before = float(engine.query(np.array([l]), np.array([r]))[0])
        assert before == x[l : r + 1].min()
        # repeat -> served from cache
        h0 = engine.cache.hits
        engine.query(np.array([l]), np.array([r]))
        assert engine.cache.hits == h0 + 1
        # mutate: plant a new global minimum inside the range
        pos = 17_000
        rmq2 = rmq.update(np.array([pos]), np.array([-3.0], np.float32))
        assert rmq2.generation == rmq.generation + 1
        engine.attach(rmq2)
        after = engine.query(np.array([l]), np.array([r]))
        assert float(after[0]) == -3.0
        assert int(engine.query_index(np.array([l]), np.array([r]))[0]) \
            == pos

    def test_append_invalidates_and_extends(self):
        rng, x, rmq = _build(5000, 64, 4, seed=4, capacity=8192)
        engine = rmq.engine()
        v0 = float(engine.query(np.array([0]), np.array([4999]))[0])
        rmq2 = rmq.append(np.array([-7.0], np.float32))
        engine.attach(rmq2)
        # old range: unchanged result, new range: sees the appended min
        assert float(engine.query(np.array([0]), np.array([4999]))[0]) == v0
        assert float(engine.query(np.array([0]), np.array([5000]))[0]) \
            == -7.0

    def test_parity_after_interleaved_mutations(self):
        """Bit-identical to the oracle after update+append interleavings."""
        rng, x, rmq = _build(20_000, 128, 4, seed=5, capacity=30_000)
        engine = rmq.engine()
        for step in range(4):
            idxs = rng.integers(0, rmq.n, 50)
            vals = rng.random(50).astype(np.float32) - 0.5
            rmq = rmq.update(idxs, vals)
            rmq = rmq.append(rng.random(100).astype(np.float32))
            engine.attach(rmq)
            ls, rs = _mixed_queries(rng, rmq.n, 128, 300)
            lsj, rsj = jnp.asarray(ls), jnp.asarray(rs)
            np.testing.assert_array_equal(
                np.asarray(engine.query(ls, rs)),
                np.asarray(rmq_value_batch(rmq.hierarchy, lsj, rsj)),
            )
            np.testing.assert_array_equal(
                np.asarray(engine.query_index(ls, rs)),
                np.asarray(rmq_index_batch(rmq.hierarchy, lsj, rsj)),
            )

    def test_attach_non_successor_clears_cache(self):
        _, _, rmq_a = _build(3000, 16, 4, seed=6)
        _, _, rmq_b = _build(3000, 16, 4, seed=7)
        engine = rmq_a.engine()
        engine.query(np.array([0]), np.array([100]))
        assert len(engine.cache) > 0
        engine.attach(rmq_b)   # same generation (0): not a successor
        assert len(engine.cache) == 0


# ---------------------------------------------------------------------------
# cache + dedup accounting
# ---------------------------------------------------------------------------
class TestCache:
    def test_lru_eviction_order(self):
        cache = ResultCache(capacity=2)
        cache.put("value", 0, 0, 1, 1.0)
        cache.put("value", 0, 0, 2, 2.0)
        assert cache.get("value", 0, 0, 1) == 1.0   # refresh (0,1)
        cache.put("value", 0, 0, 3, 3.0)            # evicts (0,2)
        assert cache.get("value", 0, 0, 2) is None
        assert cache.get("value", 0, 0, 1) == 1.0
        assert cache.stats()["evictions"] == 1

    def test_capacity_zero_disables(self):
        cache = ResultCache(capacity=0)
        cache.put("value", 0, 0, 1, 1.0)
        assert cache.get("value", 0, 0, 1) is None
        assert len(cache) == 0

    def test_engine_dedup_and_hits(self):
        rng, x, rmq = _build(10_000, 64, 4, seed=8)
        engine = rmq.engine()
        ls = np.full((64,), 10, np.int32)
        rs = np.full((64,), 500, np.int32)
        out = np.asarray(engine.query(ls, rs))
        assert (out == out[0]).all()
        s = engine.stats()
        assert s["dedup_saved"] == 63           # 64 copies, 1 executed
        out2 = np.asarray(engine.query(ls, rs))
        np.testing.assert_array_equal(out, out2)
        assert engine.stats()["cache"]["hits"] >= 1
        # value and index results are cached under distinct ops
        engine.query_index(ls[:1], rs[:1])
        assert np.asarray(engine.query(ls[:1], rs[:1]))[0] == out[0]


# ---------------------------------------------------------------------------
# packed query keys: dedup order and cached values
# ---------------------------------------------------------------------------
def _bits(a):
    a = np.asarray(a)
    return a.view(f"u{a.dtype.itemsize}")


def _signed_zero_values(rng, n):
    """Values with ties, +0.0 and -0.0: cached answers must keep bits."""
    x = rng.random(n).astype(np.float32)
    x[rng.integers(0, n, n // 16)] = 0.0
    x[rng.integers(0, n, n // 16)] = -0.0
    return x


class TestPackedKeys:
    def test_dedup_matches_axis1_unique(self):
        """np.unique on (l << 31) | r gives the same unique order and
        inverse as np.unique over the (l, r) columns, edge bounds too."""
        rng = np.random.default_rng(11)
        top = 2**31 - 1
        ls = rng.integers(0, top, 4000)
        rs = np.minimum(ls + rng.integers(0, 1 << 20, 4000), top)
        ls = np.concatenate([ls, ls[:500], [0, 0, top, 0, top, 0]])
        rs = np.concatenate([rs, rs[:500], [0, top, top, top, top, 1]])
        ls, rs = ls.astype(np.int32), rs.astype(np.int32)
        perm = rng.permutation(ls.shape[0])
        ls, rs = ls[perm], rs[perm]
        uniq, inverse = np.unique(np.stack([ls, rs]), axis=1,
                                  return_inverse=True)
        ukeys, inv = np.unique(pack_keys(ls, rs), return_inverse=True)
        uls, urs = unpack_keys(ukeys)
        np.testing.assert_array_equal(uls, uniq[0])
        np.testing.assert_array_equal(urs, uniq[1])
        np.testing.assert_array_equal(inv, inverse.ravel())
        assert uls.dtype == urs.dtype == np.int32

    @pytest.mark.parametrize("layout", ["float32", "bfloat16_summaries",
                                        "index"])
    def test_cache_hits_bit_identical(self, layout):
        """A repeated batch is served from the cache bit for bit."""
        rng = np.random.default_rng(12)
        n = 20_000
        x = _signed_zero_values(rng, n)
        kw = {"summary_dtype": "bfloat16"} \
            if layout == "bfloat16_summaries" else {}
        rmq = RMQ.build(x, c=64, t=4, with_positions=True, backend="jax",
                        **kw)
        engine = rmq.engine()
        ls, rs = _mixed_queries(rng, n, 64, 600)
        ls, rs = np.concatenate([ls, ls[:100]]), np.concatenate([rs, rs[:100]])
        run = engine.query_index if layout == "index" else engine.query
        oracle = rmq_index_batch if layout == "index" else rmq_value_batch
        executed = np.asarray(run(ls, rs))
        unique = np.unique(pack_keys(ls, rs)).shape[0]
        h0, m0 = engine.cache.hits, engine.cache.misses
        cached = np.asarray(run(ls, rs))
        assert engine.cache.hits - h0 == unique
        assert engine.cache.misses == m0
        assert cached.dtype == executed.dtype
        np.testing.assert_array_equal(_bits(cached), _bits(executed))
        want = oracle(rmq.hierarchy, jnp.asarray(ls), jnp.asarray(rs))
        np.testing.assert_array_equal(_bits(executed), _bits(want))

    @pytest.mark.parametrize("backend", ["fused", "jax"])
    def test_query_mixed_cache_hits_bit_identical(self, backend):
        """A mixed batch is deduped once and its entries are the ones
        ``query`` / ``query_index`` read: after it, both are all hits."""
        rng = np.random.default_rng(13)
        n = 3000
        x = _signed_zero_values(rng, n)
        rmq = RMQ.build(x, c=8, t=8, with_positions=True, backend=backend)
        engine = rmq.engine()
        assert engine.supports_mixed == (backend == "fused")
        ls, rs = _mixed_queries(rng, n, 8, 300)
        is_index = rng.random(ls.shape[0]) < 0.5
        # pairs asked for both ops in one batch, and repeated
        ls[:40], rs[:40] = ls[40:80], rs[40:80]
        is_index[:40] = ~is_index[40:80]
        keys = pack_keys(ls, rs)
        b0 = engine.batches
        v1, p1 = engine.query_mixed(ls, rs, is_index)
        assert engine.batches == b0 + 1
        assert engine.dedup_saved == ls.shape[0] - np.unique(keys).shape[0]
        needed = (np.unique(keys[~is_index]).shape[0]
                  + np.unique(keys[is_index]).shape[0])
        h0, m0 = engine.cache.hits, engine.cache.misses
        assert m0 == needed
        v2, p2 = engine.query_mixed(ls, rs, is_index)
        assert engine.cache.hits - h0 == needed
        assert engine.cache.misses == m0
        np.testing.assert_array_equal(_bits(v2[~is_index]),
                                      _bits(v1[~is_index]))
        np.testing.assert_array_equal(p2[is_index], p1[is_index])
        h0 = engine.cache.hits
        v3 = np.asarray(engine.query(ls[~is_index], rs[~is_index]))
        p3 = np.asarray(engine.query_index(ls[is_index], rs[is_index]))
        assert engine.cache.hits - h0 == needed
        assert engine.cache.misses == m0
        np.testing.assert_array_equal(_bits(v3), _bits(v1[~is_index]))
        np.testing.assert_array_equal(p3, p1[is_index])
        lsj, rsj = jnp.asarray(ls), jnp.asarray(rs)
        np.testing.assert_array_equal(
            v1[~is_index],
            np.asarray(rmq_value_batch(rmq.hierarchy, lsj, rsj))[~is_index])
        np.testing.assert_array_equal(
            p1[is_index],
            np.asarray(rmq_index_batch(rmq.hierarchy, lsj, rsj))[is_index])


# ---------------------------------------------------------------------------
# the routed bucket loop: every launch before the first fetch
# ---------------------------------------------------------------------------
def _oracle(x, ls, rs):
    """Plain numpy answers: ``(values, leftmost positions)``."""
    pos = np.array([l + int(np.argmin(x[l : r + 1]))
                    for l, r in zip(ls, rs)], np.int32)
    return x[pos], pos


class TestLaunchOrder:
    def test_every_bucket_launches_before_the_first_fetch(self,
                                                          monkeypatch):
        from repro.obs.trace import Tracer, use_tracer
        from repro.qe import executors

        n = 100_000
        rng, x, rmq = _build(n, 128, 4, seed=7)
        engine = QueryEngine(rmq, cache_size=0, max_bucket=16)
        ls, rs = _mixed_queries(rng, n, 128, 240)
        engine.query(ls, rs)                      # compiles every shape
        uls, urs = unpack_keys(np.unique(pack_keys(ls, rs)))
        planned = engine.planner.plan(uls, urs)
        assert {b.cls for b in planned} == {SHORT, MID, LONG}
        assert len(planned) >= 3 * 4

        tr = Tracer()
        copies = []                 # fetch spans closed at each copy start
        copy_back = executors._copy_back

        def spy(res):
            copies.append(sum(s.name == "fetch" for s in tr.spans()))
            return copy_back(res)

        monkeypatch.setattr(executors, "_copy_back", spy)
        with use_tracer(tr):
            engine.query(ls, rs)
        spans = tr.spans()
        (ex,) = [s for s in spans if s.name == "execute"]
        (plan,) = [s for s in spans if s.name == "plan"]
        kids = sorted((s for s in spans if s.parent_id == ex.span_id),
                      key=lambda s: s.span_id)
        k = len(planned)
        assert [s.name for s in kids] == ["launch"] * k + ["fetch"] * k
        assert kids[k - 1].end <= kids[k].start
        assert copies == [0] * k
        assert ex.args == {"buckets": k, "count": uls.shape[0],
                           "op": "value"}
        assert plan.args["buckets"] == k
        rows = [s.args for s in kids[:k]]
        assert [(a["cls"], a["count"], a["shape"]) for a in rows] == [
            (b.cls, b.count, b.shape) for b in planned]
        assert all(a["op"] == "value" for a in rows)
        assert all(("row_levels" in a) == (a["cls"] == LONG) for a in rows)

    @pytest.mark.parametrize("backend", ["jax", "pallas", "fused"])
    def test_many_buckets_match_the_oracle(self, backend):
        n = 20_000
        rng, x, rmq = _build(n, 128, 4, seed=8)
        engine = QueryEngine(rmq, backend=backend, interpret=True,
                             cache_size=0, max_bucket=16)
        ls, rs = _mixed_queries(rng, n, 128, 150)
        want_v, want_p = _oracle(x, ls, rs)
        np.testing.assert_array_equal(_bits(engine.query(ls, rs)),
                                      _bits(want_v))
        np.testing.assert_array_equal(
            np.asarray(engine.query_index(ls, rs)), want_p)
        is_index = rng.random(ls.shape[0]) < 0.5
        v, p = engine.query_mixed(ls, rs, is_index)
        np.testing.assert_array_equal(_bits(v[~is_index]),
                                      _bits(want_v[~is_index]))
        np.testing.assert_array_equal(p[is_index], want_p[is_index])
        assert engine.stats()["executors"][
            FUSED if backend == "fused" else MID]["calls"] > 3


# ---------------------------------------------------------------------------
# service: registry + micro-batching
# ---------------------------------------------------------------------------
class TestService:
    def test_coalesce_and_scatter_back(self):
        rng, xa, rmq_a = _build(20_000, 128, 4, seed=9)
        _, xb, rmq_b = _build(3000, 16, 4, seed=10)
        svc = QueryService()
        svc.register("a", rmq_a)
        svc.register("b", rmq_b)
        la, ra = _mixed_queries(rng, 20_000, 128, 40)
        t1 = svc.submit("a", la[:25], ra[:25])
        t2 = svc.submit("a", la[25:], ra[25:])
        t3 = svc.submit("b", np.array([5]), np.array([2500]), op="index")
        res = svc.flush()
        want = np.asarray(rmq_value_batch(
            rmq_a.hierarchy, jnp.asarray(la), jnp.asarray(ra)
        ))
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(res[t1]), np.asarray(res[t2])]),
            want,
        )
        assert int(res[t3][0]) == 5 + int(np.argmin(xb[5:2501]))
        s = svc.stats()
        assert s["coalesced_batches"] == 1      # the two "a" requests
        assert s["requests"] == 3 and s["flushes"] == 1
        # one engine batch served both "a" requests
        assert s["engines"]["a"]["batches"] == 1

    def test_auto_flush_on_max_pending(self):
        _, x, rmq = _build(5000, 64, 4, seed=11)
        svc = QueryService(max_pending=8)
        svc.register("a", rmq)
        tickets = [
            svc.submit("a", np.array([i]), np.array([i + 100]))
            for i in range(8)
        ]
        assert svc.stats()["pending_queries"] == 0   # auto-flushed
        got = np.array([float(svc.take(t)[0]) for t in tickets])
        want = np.array([x[i : i + 101].min() for i in range(8)])
        np.testing.assert_array_equal(got, want)

    def test_unknown_name_and_pending_unregister(self):
        _, _, rmq = _build(1000, 16, 4, seed=12)
        svc = QueryService()
        svc.register("a", rmq)
        with pytest.raises(KeyError, match="no index registered"):
            svc.submit("zzz", np.array([0]), np.array([1]))
        svc.submit("a", np.array([0]), np.array([1]))
        with pytest.raises(ValueError, match="pending"):
            svc.unregister("a")
        with pytest.raises(ValueError, match="pending"):
            svc.register("a", rmq)   # replacement would orphan tickets
        svc.flush()
        svc.unregister("a")

    def test_submit_rejects_index_op_on_value_only(self):
        """Bad requests fail at admission, not detached at flush time."""
        x = np.random.default_rng(15).random(2000).astype(np.float32)
        rmq = RMQ.build(x, c=16, t=4, backend="jax")   # value-only
        svc = QueryService()
        svc.register("a", rmq)
        with pytest.raises(ValueError, match="without positions"):
            svc.submit("a", np.array([0]), np.array([10]), op="index")

    def test_flush_isolates_failing_group(self):
        """One group failing must not lose other groups' results."""
        _, xa, rmq_a = _build(3000, 16, 4, seed=16)
        _, _, rmq_b = _build(3000, 16, 4, seed=17)
        x_plain = np.random.default_rng(18).random(3000).astype(np.float32)
        value_only = RMQ.build(x_plain, c=16, t=4, backend="jax")
        svc = QueryService()
        svc.register("a", rmq_a)
        svc.register("b", rmq_b)
        t_a = svc.submit("a", np.array([0]), np.array([2999]))
        t_b = svc.submit("b", np.array([1]), np.array([50]), op="index")
        # admission-time check passed for "b", but the binding races:
        # a value-only successor lands before the flush
        svc.attach("b", value_only, reset_cache=True)
        with pytest.raises(RuntimeError, match="claimable"):
            svc.flush()
        # group "a" executed and its result survived the failure
        assert float(svc.take(t_a)[0]) == xa.min()
        with pytest.raises(KeyError):
            svc.take(t_b)

    def test_sync_query_survives_unrelated_group_failure(self):
        """query()'s own stored result must be returned even when an
        unrelated (index, op) group fails in the same flush."""
        _, xa, rmq_a = _build(3000, 16, 4, seed=19)
        _, _, rmq_b = _build(3000, 16, 4, seed=20)
        x_plain = np.random.default_rng(21).random(3000).astype(np.float32)
        value_only = RMQ.build(x_plain, c=16, t=4, backend="jax")
        svc = QueryService()
        svc.register("a", rmq_a)
        svc.register("b", rmq_b)
        # queue a request that will fail at flush time (value-only
        # successor lands after admission)
        t_b = svc.submit("b", np.array([1]), np.array([50]), op="index")
        svc.attach("b", value_only, reset_cache=True)
        got = float(svc.query("a", np.array([0]), np.array([2999]))[0])
        assert got == xa.min()
        with pytest.raises(KeyError):
            svc.take(t_b)   # the failed group's ticket stays unanswered

    def test_unclaimed_results_bounded(self):
        """Unconsumed flush results age out instead of leaking forever."""
        _, _, rmq = _build(1000, 16, 4, seed=14)
        svc = QueryService(max_unclaimed=3)
        svc.register("a", rmq)
        tickets = []
        for i in range(6):
            tickets.append(svc.submit("a", np.array([i]), np.array([i + 5])))
            svc.flush()
        s = svc.stats()
        assert s["unclaimed_results"] == 3
        assert s["dropped_results"] == 3
        with pytest.raises(KeyError, match="aged out|no result"):
            svc.take(tickets[0])
        svc.take(tickets[-1])   # recent results still claimable

    def test_attach_successor_via_service(self):
        _, x, rmq = _build(5000, 64, 4, seed=13, ties=False)
        svc = QueryService()
        svc.register("a", rmq)
        before = float(svc.query("a", np.array([0]), np.array([4999]))[0])
        assert before == x.min()
        pos = int(np.argmax(x))
        svc.attach("a", rmq.update(np.array([pos]),
                                   np.array([-2.0], np.float32)))
        after = float(svc.query("a", np.array([0]), np.array([4999]))[0])
        assert after == -2.0
