"""Unit + property tests for the core GPU-RMQ hierarchy (paper §4.1–§4.4)."""

import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st
from _lowering import window_reads

from repro.core import (
    RMQ,
    build_hierarchy,
    make_plan,
    rmq_index_batch,
    rmq_value_batch,
)
from repro.core import theory
from repro.core.baselines import FullScan, SparseTable, TwoLevelBlocks


def _random_queries(rng, n, m):
    ls = rng.integers(0, n, m)
    rs = np.minimum(ls + rng.integers(0, n, m), n - 1)
    return (
        np.minimum(ls, rs).astype(np.int32),
        np.maximum(ls, rs).astype(np.int32),
    )


def _naive(x, ls, rs):
    return np.array([x[l : r + 1].min() for l, r in zip(ls, rs)])


def _naive_idx(x, ls, rs):
    return np.array([l + np.argmin(x[l : r + 1]) for l, r in zip(ls, rs)])


# ---------------------------------------------------------------------------
# Plan geometry
# ---------------------------------------------------------------------------
class TestPlan:
    def test_cutoff_respected(self):
        for n in [10, 1000, 1 << 20]:
            for c in [2, 8, 128]:
                for t in [1, 4, 64]:
                    plan = make_plan(n, c=c, t=t)
                    assert plan.top_len <= c * t
                    # every non-top level violates the cutoff (else the
                    # build would have stopped earlier)
                    for ln in plan.level_lens[:-1]:
                        assert ln > c * t or plan.num_levels == 1

    def test_level_lens_are_ceil_chain(self):
        plan = make_plan(100_000, c=8, t=4)
        for a, b in zip(plan.level_lens, plan.level_lens[1:]):
            assert b == -(-a // 8)

    def test_invalid_params_rejected(self):
        with pytest.raises(ValueError):
            make_plan(0)
        with pytest.raises(ValueError):
            make_plan(100, c=3)  # not a power of two
        with pytest.raises(ValueError):
            make_plan(100, c=128, t=0)

    def test_memory_bound_paper_4_1(self):
        """Auxiliary entries <= n/(c-1) + num_levels (ceil-corrected)."""
        for n in [17, 1000, 123_457, 1 << 22]:
            for c in [2, 4, 32, 128]:
                plan = make_plan(n, c=c, t=2)
                logical_aux = sum(plan.level_lens[1:])
                assert logical_aux <= theory.aux_entries_bound_ceil(
                    n, c, plan.num_levels
                )

    def test_scan_bound_paper_4_1(self):
        plan = make_plan(1 << 24, c=32, t=16)
        assert plan.max_scanned_entries() == 32 * 16 + 2 * 32 * (
            plan.num_levels - 1
        )
        # O(log n): far below n
        assert plan.max_scanned_entries() < 4096


# ---------------------------------------------------------------------------
# Hierarchy construction
# ---------------------------------------------------------------------------
class TestBuild:
    def test_upper_levels_are_chunk_minima(self):
        rng = np.random.default_rng(0)
        n, c = 1000, 8
        x = rng.random(n).astype(np.float32)
        plan = make_plan(n, c=c, t=2)
        h = build_hierarchy(jnp.asarray(x), plan)
        off, padded = plan.level_slice(1)
        lvl1 = np.asarray(h.upper[off : off + padded])
        for i in range(plan.level_lens[1]):
            chunk = x[i * c : (i + 1) * c]
            assert lvl1[i] == chunk.min()
        # padding is +inf
        assert np.all(np.isinf(lvl1[plan.level_lens[1] :]))

    def test_positions_point_at_leftmost_minimum(self):
        x = np.array([5, 3, 3, 7, 3, 9, 1, 1], dtype=np.float32)
        plan = make_plan(8, c=2, t=1)
        h = build_hierarchy(jnp.asarray(x), plan, with_positions=True)
        off, _ = plan.level_slice(1)
        # level 1 = min of pairs: [3, 3, 3, 1]; leftmost positions 1, 2, 4, 6
        assert np.asarray(h.upper_pos[off : off + 4]).tolist() == [1, 2, 4, 6]

    def test_memory_accounting(self):
        n = 1 << 20
        plan = make_plan(n, c=128, t=64)
        h = build_hierarchy(jnp.ones(n, jnp.float32), plan)
        assert h.auxiliary_bytes() == h.upper.size * 4
        # paper Fig. 15: aux memory a small fraction of the input for c=128
        assert h.auxiliary_bytes() < 0.02 * n * 4


# ---------------------------------------------------------------------------
# Query correctness (fixed cases + property-based)
# ---------------------------------------------------------------------------
class TestQuery:
    @pytest.mark.parametrize("n,c,t", [
        (17, 2, 1),      # paper's running example size
        (1, 2, 1),       # single element
        (2, 2, 1),
        (1000, 4, 2),
        (4096, 8, 4),    # power-of-c
        (100_003, 128, 64),  # prime n, production params
    ])
    def test_matches_naive(self, n, c, t):
        rng = np.random.default_rng(n)
        x = rng.random(n).astype(np.float32)
        h = build_hierarchy(jnp.asarray(x), make_plan(n, c=c, t=t),
                            with_positions=True)
        ls, rs = _random_queries(rng, n, 256)
        got = np.asarray(rmq_value_batch(h, jnp.asarray(ls), jnp.asarray(rs)))
        np.testing.assert_allclose(got, _naive(x, ls, rs))
        gotp = np.asarray(rmq_index_batch(h, jnp.asarray(ls), jnp.asarray(rs)))
        np.testing.assert_array_equal(gotp, _naive_idx(x, ls, rs))

    def test_paper_figure2_example(self):
        """The paper's Fig. 2: RMQ(3, 14) on a 17-element array -> 8 at idx 5."""
        x = np.array(
            [4, 20, 18, 18, 23, 8, 35, 43, 43, 36, 68, 63, 22, 51, 81, 75, 9],
            dtype=np.float32,
        )
        for c, t in [(2, 1), (2, 4), (4, 1)]:
            h = build_hierarchy(jnp.asarray(x), make_plan(17, c=c, t=t),
                                with_positions=True)
            assert float(rmq_value_batch(h, jnp.array([3]), jnp.array([14]))[0]) == 8.0
            assert int(rmq_index_batch(h, jnp.array([3]), jnp.array([14]))[0]) == 5

    def test_full_range_and_point_queries(self):
        rng = np.random.default_rng(7)
        n = 999
        x = rng.random(n).astype(np.float32)
        h = build_hierarchy(jnp.asarray(x), make_plan(n, c=8, t=2),
                            with_positions=True)
        # full range
        assert float(rmq_value_batch(h, jnp.array([0]), jnp.array([n - 1]))[0]) == x.min()
        # every point query returns the element itself (sampled)
        pts = rng.integers(0, n, 64).astype(np.int32)
        got = np.asarray(rmq_value_batch(h, jnp.asarray(pts), jnp.asarray(pts)))
        np.testing.assert_allclose(got, x[pts])

    def test_ties_return_leftmost(self):
        x = np.zeros(100, dtype=np.float32)  # all ties
        h = build_hierarchy(jnp.asarray(x), make_plan(100, c=4, t=1),
                            with_positions=True)
        ls = np.array([0, 10, 55], dtype=np.int32)
        rs = np.array([99, 88, 56], dtype=np.int32)
        got = np.asarray(rmq_index_batch(h, jnp.asarray(ls), jnp.asarray(rs)))
        np.testing.assert_array_equal(got, ls)

    @settings(max_examples=60, deadline=None)
    @given(
        data=st.data(),
        n=st.integers(min_value=1, max_value=2000),
        c_exp=st.integers(min_value=1, max_value=5),
        t=st.integers(min_value=1, max_value=8),
    )
    def test_property_hierarchical_equals_naive(self, data, n, c_exp, t):
        """∀ arrays, ∀ (l, r): hierarchy answer == naive scan answer."""
        c = 1 << c_exp
        vals = data.draw(
            st.lists(
                st.floats(
                    min_value=-1e6, max_value=1e6,
                    allow_nan=False, width=32,
                ),
                min_size=n, max_size=n,
            )
        )
        x = np.asarray(vals, dtype=np.float32)
        l = data.draw(st.integers(min_value=0, max_value=n - 1))
        r = data.draw(st.integers(min_value=l, max_value=n - 1))
        h = build_hierarchy(jnp.asarray(x), make_plan(n, c=c, t=t),
                            with_positions=True)
        got = float(rmq_value_batch(h, jnp.array([l]), jnp.array([r]))[0])
        assert got == x[l : r + 1].min()
        gotp = int(rmq_index_batch(h, jnp.array([l]), jnp.array([r]))[0])
        assert gotp == l + int(np.argmin(x[l : r + 1]))

    @settings(max_examples=25, deadline=None)
    @given(
        n=st.integers(min_value=2, max_value=500),
        c_exp=st.integers(min_value=1, max_value=4),
        seed=st.integers(min_value=0, max_value=2**31 - 1),
    )
    def test_property_duplicates_and_negatives(self, n, c_exp, seed):
        """Arrays with heavy duplication / negative values."""
        rng = np.random.default_rng(seed)
        x = rng.integers(-3, 3, n).astype(np.float32)
        h = build_hierarchy(jnp.asarray(x), make_plan(n, c=1 << c_exp, t=1),
                            with_positions=True)
        ls, rs = _random_queries(rng, n, 32)
        got = np.asarray(rmq_value_batch(h, jnp.asarray(ls), jnp.asarray(rs)))
        np.testing.assert_allclose(got, _naive(x, ls, rs))
        gotp = np.asarray(rmq_index_batch(h, jnp.asarray(ls), jnp.asarray(rs)))
        np.testing.assert_array_equal(gotp, _naive_idx(x, ls, rs))


# ---------------------------------------------------------------------------
# Facade + baselines
# ---------------------------------------------------------------------------
class TestFacadeAndBaselines:
    def test_rmq_facade_roundtrip(self):
        rng = np.random.default_rng(11)
        x = rng.random(3000).astype(np.float32)
        r = RMQ.build(x, c=16, t=8, with_positions=True, backend="jax")
        ls, rs = _random_queries(rng, 3000, 64)
        np.testing.assert_allclose(
            np.asarray(r.query(ls, rs)), _naive(x, ls, rs)
        )
        np.testing.assert_array_equal(
            np.asarray(r.query_index(ls, rs)), _naive_idx(x, ls, rs)
        )
        assert r.auxiliary_bytes() > 0
        assert r.memory_bytes() >= 3000 * 4

    @pytest.mark.parametrize("method", ["full_scan", "sparse_table", "two_level"])
    def test_baselines_match_naive(self, method):
        rng = np.random.default_rng(13)
        n = 4097
        x = rng.random(n).astype(np.float32)
        b = {
            "full_scan": lambda: FullScan.build(jnp.asarray(x)),
            "sparse_table": lambda: SparseTable.build(jnp.asarray(x)),
            "two_level": lambda: TwoLevelBlocks.build(jnp.asarray(x), c=64),
        }[method]()
        ls, rs = _random_queries(rng, n, 128)
        got = np.asarray(b.query_batch(jnp.asarray(ls), jnp.asarray(rs)))
        np.testing.assert_allclose(got, _naive(x, ls, rs))

    def test_memory_profiles_match_paper_fig15_ordering(self):
        """full scan < GPU-RMQ << sparse table (the LCA/RTXRMQ profile)."""
        n = 1 << 16
        x = jnp.asarray(np.random.default_rng(0).random(n), jnp.float32)
        full = FullScan.build(x)
        ours = RMQ.build(x, c=128, t=64, backend="jax")
        sparse = SparseTable.build(x)
        assert full.auxiliary_bytes() == 0
        assert ours.auxiliary_bytes() < 0.02 * n * 4
        assert sparse.auxiliary_bytes() > 10 * n * 4
        # paper: GPU-RMQ needs at most ~30% more memory than full scan
        assert ours.memory_bytes() < 1.3 * full.memory_bytes()


class TestQueryValidation:
    """RMQ.query/query_index input checking (0 <= l <= r < n)."""

    def _rmq(self, n=500):
        rng = np.random.default_rng(2)
        x = rng.random(n).astype(np.float32)
        return x, RMQ.build(x, c=8, t=2, with_positions=True, backend="jax")

    def test_non_integer_bounds_rejected(self):
        _, r = self._rmq()
        with pytest.raises(TypeError, match="integer"):
            r.query(jnp.zeros(3), jnp.zeros(3, jnp.int32))
        with pytest.raises(TypeError, match="integer"):
            r.query_index(jnp.zeros(3, jnp.int32), jnp.zeros(3))

    def test_shape_mismatch_rejected(self):
        _, r = self._rmq()
        with pytest.raises(ValueError, match="shape"):
            r.query(jnp.zeros(3, jnp.int32), jnp.zeros(4, jnp.int32))

    def test_out_of_range_rejected_in_debug_mode(self, monkeypatch):
        monkeypatch.setenv("REPRO_RMQ_DEBUG", "1")
        n = 500
        _, r = self._rmq(n)
        cases = [
            ([-1], [3]),        # negative l
            ([5], [4]),         # l > r
            ([0], [n]),         # r out of range
        ]
        for ls, rs in cases:
            with pytest.raises(ValueError, match="violates"):
                r.query(np.asarray(ls, np.int32), np.asarray(rs, np.int32))
            with pytest.raises(ValueError, match="violates"):
                r.query_index(np.asarray(ls, np.int32),
                              np.asarray(rs, np.int32))

    def test_degenerate_point_queries_pass_validation(self, monkeypatch):
        """l == r is valid (window of one) and returns the element."""
        monkeypatch.setenv("REPRO_RMQ_DEBUG", "1")
        x, r = self._rmq()
        pts = np.array([0, 7, 499], np.int32)
        np.testing.assert_allclose(np.asarray(r.query(pts, pts)), x[pts])
        np.testing.assert_array_equal(
            np.asarray(r.query_index(pts, pts)), pts
        )

    def test_full_range_passes_validation(self, monkeypatch):
        monkeypatch.setenv("REPRO_RMQ_DEBUG", "1")
        x, r = self._rmq()
        ls = np.array([0], np.int32)
        rs = np.array([499], np.int32)
        assert float(r.query(ls, rs)[0]) == x.min()
        assert int(r.query_index(ls, rs)[0]) == int(np.argmin(x))


class TestBf16Values:
    """Beyond-paper: bf16 input values halve index memory on TPU.

    The paper is f32-only (§5.1); the hierarchy/query algebra only needs
    a totally-ordered dtype with an +inf identity, which bf16 has.
    """

    def test_bf16_hierarchy_and_query(self):
        rng = np.random.default_rng(0)
        n = 20_000
        x32 = rng.random(n).astype(np.float32)
        x16 = jnp.asarray(x32, jnp.bfloat16)
        h = build_hierarchy(x16, make_plan(n, c=64, t=8),
                            with_positions=True)
        assert h.upper.dtype == jnp.bfloat16
        ls, rs = _random_queries(rng, n, 128)
        got = rmq_value_batch(h, jnp.asarray(ls), jnp.asarray(rs))
        want = np.array([
            np.asarray(x16, np.float32)[l : r + 1].min()
            for l, r in zip(ls, rs)
        ])
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), want
        )
        # index variant: leftmost argmin in bf16-rounded space
        gotp = np.asarray(
            rmq_index_batch(h, jnp.asarray(ls), jnp.asarray(rs))
        )
        x16np = np.asarray(x16, np.float32)
        wantp = np.array([
            l + int(np.argmin(x16np[l : r + 1])) for l, r in zip(ls, rs)
        ])
        np.testing.assert_array_equal(gotp, wantp)

    def test_bf16_pallas_kernels(self):
        from repro.kernels.hierarchy_build.ops import build_hierarchy_pallas
        from repro.kernels.rmq_scan.ops import rmq_value_batch_pallas

        rng = np.random.default_rng(1)
        n = 50_000
        x = jnp.asarray(rng.random(n), jnp.bfloat16)
        plan = make_plan(n, c=128, t=2)
        h = build_hierarchy_pallas(x, plan, interpret=True)
        ls, rs = _random_queries(rng, n, 64)
        got = rmq_value_batch_pallas(
            h, jnp.asarray(ls), jnp.asarray(rs), qb=16, interpret=True
        )
        want = np.array([
            np.asarray(x, np.float32)[l : r + 1].min()
            for l, r in zip(ls, rs)
        ])
        np.testing.assert_array_equal(np.asarray(got, np.float32), want)


# ---------------------------------------------------------------------------
# The window reader shared by the jnp walk, the hybrid walk and rmq_bulk
# ---------------------------------------------------------------------------
class TestWindowReader:
    """``_masked_window_scan`` against numpy, one case per lowering."""

    @pytest.mark.parametrize("n,c,window,aligned,rows", [
        ((1 << 20) + 8 * 16, 16, 16, False, 2),   # row view free
        ((1 << 20) + 8 * 16, 16, 16, True, 1),    # ... one row, aligned
        (16 * 13, 16, 32, False, 3),              # row view a cheap copy
        (16 * 13, 16, 16, True, 1),               # ... one row, aligned
        (16 * 13 + 5, 16, 16, False, 0),          # dynamic_slice
        (16 * 13 + 5, 16, 16, True, 0),           # ... aligned changes nothing
        ((1 << 20) + 16, 16, 16, True, 0),        # too big to copy
    ])
    @pytest.mark.parametrize("with_pos_plane", [False, True])
    def test_matches_numpy(self, n, c, window, aligned, rows, with_pos_plane):
        from repro.core.query import _masked_window_scan, row_view_rows

        assert row_view_rows(n, window, c, aligned) == rows
        rng = np.random.default_rng(n + window + aligned)
        x = rng.integers(0, 4, n).astype(np.float32)   # ties everywhere
        # a monotone position plane: its min over a tie is the leftmost
        pos_plane = (np.arange(n) * 3 + 7).astype(np.int32)
        m = 512
        if aligned:
            # chunk anchors, past both ends too (clamped in range)
            starts = rng.integers(-2, n // c + 2, m) * c
        else:
            starts = rng.integers(-window, n + window, m)
        lo = starts + rng.integers(-window, window + 1, m)
        hi = lo + rng.integers(-4, window + 1, m)      # some empty masks
        lo[:4] = [0, n - 1, n, 0]
        hi[:4] = [n, n, n, 0]
        starts[:4] = [0, n - window, n, -window]

        pos_arr = jnp.asarray(pos_plane) if with_pos_plane else None
        scan = jax.jit(jax.vmap(lambda s, a, b: _masked_window_scan(
            jnp.asarray(x), pos_arr, s, a, b, window, True, c,
            aligned=aligned)))
        got_m, got_p = map(np.asarray, scan(
            jnp.asarray(starts, jnp.int32), jnp.asarray(lo, jnp.int32),
            jnp.asarray(hi, jnp.int32)))
        reads = window_reads(jax.make_jaxpr(scan)(
            jnp.asarray(starts, jnp.int32), jnp.asarray(lo, jnp.int32),
            jnp.asarray(hi, jnp.int32)))
        one = ("row", rows) if rows else ("slice", window)
        assert reads == [one] * (2 if with_pos_plane else 1)

        for i in range(m):
            s = min(max(int(starts[i]), 0), n - window)
            a, b = max(int(lo[i]), s), min(int(hi[i]), s + window)
            if a >= b:
                assert got_m[i] == np.inf
                assert got_p[i] == np.iinfo(np.int32).max
                continue
            j = a + int(np.argmin(x[a:b]))
            assert got_m[i] == x[j]
            assert got_p[i] == (pos_plane[j] if with_pos_plane else j)

    def test_aligned_needs_whole_rows(self):
        from repro.core.query import row_view_rows

        with pytest.raises(ValueError, match="whole number of rows"):
            row_view_rows(1024, 24, 16, aligned=True)
