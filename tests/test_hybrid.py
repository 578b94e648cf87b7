"""Hybrid (sparse-table top) RMQ — paper §4.5 as a selectable backend."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st

from _lowering import window_reads
from repro.core.api import RMQ
from repro.core.hybrid import HybridRMQ, _hybrid_batch, row_levels

# Shapes by how the walk reads level 0 (upper levels are whole chunks,
# so always rows): a free (rows, c) view, a cheap copy, a dynamic_slice
# for a length that is not a multiple of c, and one for a length too
# large to copy that is not whole (8, c) tiles.
LOWERINGS = [
    ((1 << 20) + 8 * 16, 16, 64),
    (1 << 16, 128, 4),
    (4097, 16, 8),
    ((1 << 20) + 16, 16, 64),
]


def _queries(rng, n, m):
    """``m`` random inclusive ranges, then some that end at ``n - 1``."""
    ls = rng.integers(0, n, m)
    rs = np.minimum(ls + rng.integers(0, n, m), n - 1)
    ls, rs = np.minimum(ls, rs), np.maximum(ls, rs)
    tail = rng.integers(0, n, 16)
    return (np.concatenate([ls, tail, [0, n - 1]]),
            np.concatenate([rs, np.full(18, n - 1)]))


@pytest.mark.parametrize("n,c,t", [
    (4097, 16, 8),
    (100_000, 128, 1024),
    (1 << 18, 128, 4096),
    (513, 4, 2),
    *LOWERINGS,
])
def test_hybrid_matches_naive(n, c, t):
    rng = np.random.default_rng(n)
    x = rng.random(n).astype(np.float32)
    h = HybridRMQ.build(x, c=c, t=t)
    ls, rs = _queries(rng, n, 256)
    got = np.asarray(h.query(ls, rs))
    want = np.array([x[l : r + 1].min() for l, r in zip(ls, rs)])
    np.testing.assert_array_equal(got, want)


def test_hybrid_enables_larger_t_with_fewer_levels():
    """Paper §4.5 implication (1): the O(1) top makes large t free, which
    removes hierarchy levels."""
    n = 1 << 20
    rng = np.random.default_rng(0)
    x = rng.random(n).astype(np.float32)
    scan_version = RMQ.build(x, c=128, t=8, backend="jax")
    hybrid = HybridRMQ.build(x, c=128, t=4096)
    assert hybrid.plan.num_levels < scan_version.plan.num_levels
    # and still answers correctly
    assert float(hybrid.query(np.array([0]), np.array([n - 1]))[0]) == \
        x.min()


@settings(max_examples=30, deadline=None)
@given(
    n=st.integers(min_value=2, max_value=1500),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_hybrid_property(n, seed):
    rng = np.random.default_rng(seed)
    x = rng.integers(-5, 5, n).astype(np.float32)
    h = HybridRMQ.build(x, c=8, t=4)
    l = int(rng.integers(0, n))
    r = int(rng.integers(l, n))
    got = float(h.query(np.array([l]), np.array([r]))[0])
    assert got == x[l : r + 1].min()


@pytest.mark.parametrize("packed_pos", [False, True])
@pytest.mark.parametrize("n,c,t", [
    (50_000, 128, 2),
    (4096, 8, 4),
    (999, 8, 2),
    (600, 1024, 64),   # single-level plan: table directly over the input
    *LOWERINGS,
])
def test_hybrid_index_tracking_matches_naive(n, c, t, packed_pos):
    """Index-tracking hybrid: leftmost-tie positions, incl. tie storms."""
    rng = np.random.default_rng(n + 7)
    x = rng.random(n).astype(np.float32)
    x[rng.integers(0, n, n // 8)] = 0.25   # force ties
    x[rng.integers(0, n, n // 64)] = 0.0   # ... at the minimum of long spans
    h = HybridRMQ.build(x, c=c, t=t, with_positions=True,
                        packed_pos=packed_pos)
    assert h.with_positions
    ls, rs = _queries(rng, n, 256)
    got = np.asarray(h.query_index(ls, rs))
    want = np.array([l + np.argmin(x[l : r + 1]) for l, r in zip(ls, rs)])
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.asarray(h.query(ls, rs)), x[want])


@pytest.mark.parametrize("n,c,t", LOWERINGS)
def test_hybrid_walk_reads_chunk_rows(n, c, t):
    """Each level's windows are one row of its (rows, c) view where the
    view is free or cheap; the long ``launch`` span counts those levels."""
    from repro.obs.trace import Tracer, use_tracer

    x = np.random.default_rng(1).random(n).astype(np.float32)
    idx = RMQ.build(x, c=c, t=t, backend="jax", with_positions=True)
    hyb = HybridRMQ.from_hierarchy(idx.hierarchy)
    h, plan = hyb.hierarchy, hyb.plan
    walk = plan.num_levels - 1
    rows = row_levels(plan)
    level0_rows = n % c == 0 and (n % (8 * c) == 0 or n <= 1 << 20)
    assert rows == walk - (not level0_rows)

    q = jnp.zeros((64,), jnp.int32)
    for track_pos in (False, True):
        reads = window_reads(jax.make_jaxpr(
            lambda ls, rs: _hybrid_batch(
                plan, h.base, h.upper, h.upper_pos, hyb.top_table.table,
                hyb.top_table.pos, ls, rs, track_pos=track_pos))(q, q))
        # two windows a level; upper levels read positions beside values
        level0 = ("row", 1) if level0_rows else ("slice", c)
        upper = [("row", 1)] * (walk - 1) * (4 if track_pos else 2)
        assert sorted(reads) == sorted([level0] * 2 + upper)

    engine = idx.engine(cache_size=0, backend="jax")
    ls = np.array([0, 1, 2], np.int32)
    rs = np.full(3, n - 1, np.int32)
    tr = Tracer()
    with use_tracer(tr):
        engine.query(ls, rs)
    (long_span,) = [s for s in tr.spans()
                    if s.name == "launch" and s.args["cls"] == "long"]
    assert long_span.args["row_levels"] == rows


def test_hybrid_from_hierarchy_reuses_levels():
    """from_hierarchy wraps an existing build — no hierarchy rebuild."""
    from repro.core.hierarchy import build_hierarchy
    from repro.core.plan import make_plan

    rng = np.random.default_rng(3)
    n = 30_000
    x = rng.random(n).astype(np.float32)
    h = build_hierarchy(jnp.asarray(x), make_plan(n, c=64, t=4),
                        with_positions=True)
    hyb = HybridRMQ.from_hierarchy(h)
    assert hyb.hierarchy is h
    assert hyb.with_positions
    ls = rng.integers(0, n, 128)
    rs = np.minimum(ls + rng.integers(0, n, 128), n - 1)
    ls, rs = np.minimum(ls, rs), np.maximum(ls, rs)
    want = np.array([x[l : r + 1].min() for l, r in zip(ls, rs)])
    np.testing.assert_array_equal(np.asarray(hyb.query(ls, rs)), want)


def test_hybrid_value_only_query_index_raises():
    x = np.random.default_rng(0).random(5000).astype(np.float32)
    h = HybridRMQ.build(x, c=16, t=8)
    with pytest.raises(ValueError, match="value-only"):
        h.query_index(np.array([0]), np.array([10]))
