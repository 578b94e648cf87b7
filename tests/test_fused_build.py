"""Fused single-launch construction pipeline: parity + launch accounting.

The contract under test (ISSUE 4 acceptance criteria):

* the fused build is bit-identical to the ``build_hierarchy`` oracle —
  values, leftmost-tie positions, and padding — across ragged geometries
  (``n % c != 0``, ``capacity > n``, single-level plans, positions
  on/off, f32/f64);
* every index implementation (``RMQ``, ``StreamingRMQ``,
  ``HybridRMQ.from_hierarchy``, ``DistributedRMQ``) builds through the
  one shared pipeline and answers identically regardless of the
  construction backend;
* the fused path issues exactly ONE kernel launch per build (the
  per-level path issues one per upper level), asserted via the
  trace-time launch counter.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import RMQ, build_hierarchy, build_many, make_plan
from repro.core.distributed import DistributedRMQ
from repro.core.hybrid import HybridRMQ
from repro.core.protocol import resolve_backend, runtime_backend
from repro.kernels.hierarchy_build.ops import build_hierarchy_pallas
from repro.kernels.hierarchy_fused.ops import build_hierarchy_fused
from repro.kernels.hierarchy_fused.ref import fused_build_ref
from repro.kernels.profiling import count_launches
from repro.streaming import StreamingRMQ

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st


def _tied_input(rng, n, dtype=np.float32):
    """Random values with deliberate ties so leftmost-position breaks
    are actually exercised."""
    x = rng.random(n).astype(dtype)
    x[rng.integers(0, n, max(n // 8, 1))] = 0.5
    return x


def _assert_hierarchies_identical(h_ref, h_got):
    np.testing.assert_array_equal(
        np.asarray(h_ref.base), np.asarray(h_got.base)
    )
    np.testing.assert_array_equal(
        np.asarray(h_ref.upper), np.asarray(h_got.upper)
    )
    assert h_ref.with_positions == h_got.with_positions
    if h_ref.with_positions:
        assert h_ref.upper_pos.dtype == h_got.upper_pos.dtype
        np.testing.assert_array_equal(
            np.asarray(h_ref.upper_pos), np.asarray(h_got.upper_pos)
        )


# geometries: ragged tails, reserved capacity, single-level, deep plans
GEOMETRIES = [
    (1000, 8, 2, None),     # n % c != 0
    (4096, 8, 2, 8192),     # capacity > n (aligned)
    (999, 2, 1, 1500),      # ragged + ragged capacity, 10 upper levels
    (12_345, 16, 4, None),  # ragged, mid-depth
    (700, 128, 64, None),   # single-level plan (n <= c*t): no launch
    (300, 16, 2, 1000),     # capacity-derived levels from a tiny n
]


class TestFusedBuildParity:
    @pytest.mark.parametrize("n,c,t,cap", GEOMETRIES)
    @pytest.mark.parametrize("with_pos", [False, True])
    def test_matches_oracle_and_per_level(self, n, c, t, cap, with_pos):
        rng = np.random.default_rng(n + c)
        x = jnp.asarray(_tied_input(rng, n))
        plan = make_plan(n, c=c, t=t, capacity=cap)
        h_ref = build_hierarchy(x, plan, with_positions=with_pos)
        h_fused = build_hierarchy_fused(
            x, plan, with_positions=with_pos, interpret=True
        )
        h_level = build_hierarchy_pallas(
            x, plan, with_positions=with_pos, interpret=True
        )
        _assert_hierarchies_identical(h_ref, h_fused)
        _assert_hierarchies_identical(h_ref, h_level)
        # the package's pure-jnp ref oracle agrees too
        u, p = fused_build_ref(h_ref.base, plan, with_positions=with_pos)
        np.testing.assert_array_equal(
            np.asarray(h_ref.upper), np.asarray(u)
        )
        if with_pos:
            np.testing.assert_array_equal(
                np.asarray(h_ref.upper_pos), np.asarray(p)
            )

    @pytest.mark.parametrize("n,c,t,cap", [(777, 4, 2, 1024)])
    def test_f64_parity(self, n, c, t, cap):
        """x64 mode: f64 values with int64 positions, all backends."""
        with jax.enable_x64(True):
            rng = np.random.default_rng(7)
            x = jnp.asarray(_tied_input(rng, n, np.float64))
            assert x.dtype == jnp.float64
            plan = make_plan(n, c=c, t=t, capacity=cap)
            h_ref = build_hierarchy(x, plan, with_positions=True)
            assert h_ref.upper.dtype == jnp.float64
            h_fused = build_hierarchy_fused(
                x, plan, with_positions=True, interpret=True
            )
            h_level = build_hierarchy_pallas(
                x, plan, with_positions=True, interpret=True
            )
            _assert_hierarchies_identical(h_ref, h_fused)
            _assert_hierarchies_identical(h_ref, h_level)

    @pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis")
    @settings(max_examples=25, deadline=None)
    @given(
        n=st.integers(min_value=1, max_value=3000),
        log_c=st.integers(min_value=1, max_value=6),
        t=st.integers(min_value=1, max_value=8),
        headroom=st.integers(min_value=0, max_value=500),
        with_pos=st.booleans(),
    )
    def test_property_random_geometry(self, n, log_c, t, headroom,
                                      with_pos):
        c = 2 ** log_c
        rng = np.random.default_rng(n * 31 + c)
        x = jnp.asarray(_tied_input(rng, n))
        plan = make_plan(n, c=c, t=t, capacity=n + headroom)
        h_ref = build_hierarchy(x, plan, with_positions=with_pos)
        h_fused = build_hierarchy_fused(
            x, plan, with_positions=with_pos, interpret=True
        )
        _assert_hierarchies_identical(h_ref, h_fused)


class TestLaunchAccounting:
    def test_fused_is_one_launch_per_level_is_many(self):
        # a geometry no other test builds, so tracing is fresh here
        n, c, t = 4999, 8, 4
        plan = make_plan(n, c=c, t=t)
        assert plan.num_levels == 4  # 3 upper levels
        x = jnp.asarray(np.random.default_rng(0).random(n, np.float32))
        with count_launches() as fused:
            build_hierarchy_fused(x, plan, interpret=True)
        assert fused == {"hierarchy_fused": 1}
        with count_launches() as per_level:
            build_hierarchy_pallas(x, plan, interpret=True)
        assert per_level == {"hierarchy_build": plan.num_levels - 1}

    def test_single_level_plan_launches_nothing(self):
        plan = make_plan(701, c=128, t=64)
        assert plan.num_levels == 1
        x = jnp.asarray(np.random.default_rng(1).random(701, np.float32))
        with count_launches() as counts:
            h = build_hierarchy_fused(x, plan, interpret=True)
        assert counts == {}
        assert h.upper.shape == (0,)


class TestBackendRouting:
    def test_resolve_and_runtime(self):
        from repro.core.protocol import mutation_backend

        assert resolve_backend("fused") == "fused"
        # since the fused QUERY kernel landed, 'fused' is a runtime
        # backend (one launch per batch); only mutations degrade
        assert runtime_backend("fused") == "fused"
        assert runtime_backend("jax") == "jax"
        assert runtime_backend("pallas") == "pallas"
        assert mutation_backend("fused") in ("jax", "pallas")
        assert mutation_backend("jax") == "jax"
        assert mutation_backend("pallas") == "pallas"
        with pytest.raises(ValueError):
            resolve_backend("cuda")

    def test_all_four_indexes_build_fused(self):
        """RMQ / StreamingRMQ / HybridRMQ.from_hierarchy / DistributedRMQ
        all construct through the fused pipeline and answer bit-identically
        to the jax-built oracle (values AND leftmost-tie positions).

        2-level plan (t=16): the first compile of a 3-level *distributed*
        walk is minutes on CPU XLA (see test_distributed_rmq.py)."""
        n, c, t, cap = 3000, 16, 16, 4000
        rng = np.random.default_rng(42)
        x = _tied_input(rng, n)
        xj = jnp.asarray(x)
        ls = rng.integers(0, n, 96)
        rs = np.minimum(ls + rng.integers(0, n, 96), n - 1)
        ls, rs = np.minimum(ls, rs), np.maximum(ls, rs)
        want = np.array([x[l : r + 1].min() for l, r in zip(ls, rs)])
        wantp = np.array(
            [l + np.argmin(x[l : r + 1]) for l, r in zip(ls, rs)]
        )

        r_f = RMQ.build(
            xj, c=c, t=t, with_positions=True, backend="fused",
            capacity=cap,
        )
        assert r_f.backend == "fused"
        np.testing.assert_array_equal(np.asarray(r_f.query(ls, rs)), want)
        np.testing.assert_array_equal(
            np.asarray(r_f.query_index(ls, rs)), wantp
        )

        s_f = StreamingRMQ.from_array(
            xj, c=c, t=t, with_positions=True, backend="fused",
            capacity=cap,
        )
        np.testing.assert_array_equal(np.asarray(s_f.query(ls, rs)), want)
        np.testing.assert_array_equal(
            np.asarray(s_f.query_index(ls, rs)), wantp
        )

        hyb = HybridRMQ.from_hierarchy(r_f.hierarchy)
        np.testing.assert_array_equal(np.asarray(hyb.query(ls, rs)), want)
        np.testing.assert_array_equal(
            np.asarray(hyb.query_index(ls, rs)), wantp
        )

        mesh = jax.make_mesh((1, 1), ("data", "model"))
        d_f = DistributedRMQ.build(
            x, mesh, c=c, t=t, with_positions=True, backend="fused"
        )
        np.testing.assert_array_equal(np.asarray(d_f.query(ls, rs)), want)
        np.testing.assert_array_equal(
            np.asarray(d_f.query_index(ls, rs)), wantp
        )

        # the engine routes a fused-built index like any other
        eng = r_f.engine(cache_size=64)
        np.testing.assert_array_equal(np.asarray(eng.query(ls, rs)), want)
        np.testing.assert_array_equal(
            np.asarray(eng.query_index(ls, rs)), wantp
        )

    def test_fused_built_index_mutates_like_oracle(self):
        """update/append on a fused-built index dispatch through the
        runtime backend and stay bit-identical to a fresh build."""
        n, cap = 1200, 2000
        rng = np.random.default_rng(3)
        x = _tied_input(rng, n)
        r = RMQ.build(
            jnp.asarray(x), c=8, t=2, with_positions=True,
            backend="fused", capacity=cap,
        )
        idxs = rng.integers(0, n, 40)
        vals = rng.random(40).astype(np.float32)
        tail = rng.random(64).astype(np.float32)
        r2 = r.update(idxs, vals).append(tail)
        x2 = x.copy()
        x2[idxs] = vals  # numpy setitem is last-wins, like the contract
        x2 = np.concatenate([x2, tail])
        ref = RMQ.build(
            jnp.asarray(x2), c=8, t=2, with_positions=True,
            plan=make_plan(len(x2), c=8, t=2, capacity=cap),
        )
        _assert_hierarchies_identical(ref.hierarchy, r2.hierarchy)


class TestBatchedBuild:
    def test_build_many_rows_match_solo_builds(self):
        rng = np.random.default_rng(11)
        xs = np.stack([_tied_input(rng, 5000) for _ in range(4)])
        plan = make_plan(5000, c=16, t=4, capacity=6000)
        batched = build_many(
            jnp.asarray(xs), plan, with_positions=True
        )
        for i in range(4):
            solo = build_hierarchy(
                jnp.asarray(xs[i]), plan, with_positions=True
            )
            np.testing.assert_array_equal(
                np.asarray(batched.base[i]), np.asarray(solo.base)
            )
            np.testing.assert_array_equal(
                np.asarray(batched.upper[i]), np.asarray(solo.upper)
            )
            np.testing.assert_array_equal(
                np.asarray(batched.upper_pos[i]),
                np.asarray(solo.upper_pos),
            )

    def test_build_many_rejects_bad_rank(self):
        plan = make_plan(64, c=8, t=2)
        with pytest.raises(ValueError, match="rank-2"):
            build_many(jnp.zeros((64,)), plan)

    def test_service_register_many(self):
        from repro.qe import QueryService

        rng = np.random.default_rng(5)
        n = 2000
        arrays = {f"idx{i}": _tied_input(rng, n) for i in range(3)}
        svc = QueryService()
        engines = svc.register_many(
            arrays, c=16, t=4, with_positions=True
        )
        assert set(engines) == set(arrays)
        ls = rng.integers(0, n, 32)
        rs = np.minimum(ls + rng.integers(0, n, 32), n - 1)
        ls, rs = np.minimum(ls, rs), np.maximum(ls, rs)
        for name, x in arrays.items():
            want = np.array(
                [x[l : r + 1].min() for l, r in zip(ls, rs)]
            )
            wantp = np.array(
                [l + np.argmin(x[l : r + 1]) for l, r in zip(ls, rs)]
            )
            np.testing.assert_array_equal(
                np.asarray(svc.query(name, ls, rs)), want
            )
            np.testing.assert_array_equal(
                np.asarray(svc.query_index(name, ls, rs)), wantp
            )

    def test_service_register_many_rejects_ragged(self):
        from repro.qe import QueryService

        svc = QueryService()
        with pytest.raises(ValueError, match="equal lengths"):
            svc.register_many(
                {"a": np.zeros(10, np.float32),
                 "b": np.zeros(11, np.float32)}
            )

    def test_service_register_many_all_or_nothing_on_pending(self):
        """A pending ticket for ANY requested name fails the whole call
        before any engine is replaced."""
        from repro.qe import QueryService

        rng = np.random.default_rng(9)
        x = _tied_input(rng, 512)
        svc = QueryService()
        svc.register_many({"a": x, "b": x}, c=16, t=4)
        old_engine = svc.engine("a")
        svc.submit("b", [0], [10])
        with pytest.raises(ValueError, match="pending"):
            svc.register_many({"a": x, "b": x}, c=16, t=4)
        assert svc.engine("a") is old_engine  # 'a' was not re-registered
        svc.flush()
