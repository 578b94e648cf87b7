"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.hierarchy import build_hierarchy
from repro.core.plan import make_plan
from repro.core.query import rmq_index_batch, rmq_value_batch


def _queries(rng, n, m):
    ls = rng.integers(0, n, m)
    rs = np.minimum(ls + rng.integers(0, n, m), n - 1)
    return (
        np.minimum(ls, rs).astype(np.int32),
        np.maximum(ls, rs).astype(np.int32),
    )


# ---------------------------------------------------------------------------
# hierarchy_build
# ---------------------------------------------------------------------------
class TestHierarchyBuildKernel:
    @pytest.mark.parametrize("n,c,t", [
        (100_000, 128, 64),
        (4096, 8, 2),
        (999, 2, 1),
        (1 << 18, 256, 16),
        (12_345, 16, 4),
    ])
    @pytest.mark.parametrize("with_pos", [False, True])
    def test_matches_oracle(self, n, c, t, with_pos):
        from repro.kernels.hierarchy_build.ops import build_hierarchy_pallas

        rng = np.random.default_rng(n + c)
        x = jnp.asarray(rng.random(n).astype(np.float32))
        plan = make_plan(n, c=c, t=t)
        h_ref = build_hierarchy(x, plan, with_positions=with_pos)
        h_pal = build_hierarchy_pallas(
            x, plan, with_positions=with_pos, interpret=True
        )
        u1, u2 = np.asarray(h_ref.upper), np.asarray(h_pal.upper)
        finite = np.isfinite(u1)
        np.testing.assert_array_equal(finite, np.isfinite(u2))
        np.testing.assert_array_equal(u1[finite], u2[finite])
        if with_pos:
            np.testing.assert_array_equal(
                np.asarray(h_ref.upper_pos), np.asarray(h_pal.upper_pos)
            )

    def test_level_kernel_direct(self):
        from repro.kernels.hierarchy_build.kernel import build_level
        from repro.kernels.hierarchy_build.ref import build_level_ref

        rng = np.random.default_rng(0)
        for c, rows_out in [(128, 8), (256, 2), (8, 8)]:
            x = jnp.asarray(
                rng.random(c * c * rows_out * 4).astype(np.float32)
            )
            got = build_level(
                x.reshape(-1, c), c=c, rows_out=rows_out, interpret=True
            )
            assert got.shape == (x.shape[0] // (c * c), c)
            np.testing.assert_array_equal(
                np.asarray(got).reshape(-1),
                np.asarray(build_level_ref(x, c)),
            )


# ---------------------------------------------------------------------------
# rmq_scan
# ---------------------------------------------------------------------------
class TestRmqScanKernel:
    @pytest.mark.parametrize("n,c,t,qb", [
        (100_000, 128, 4, 64),
        (65_536, 256, 2, 32),
        (5_000, 128, 1, 16),
        (300_000, 128, 2, 64),   # 4 levels
    ])
    def test_matches_naive(self, n, c, t, qb):
        from repro.kernels.rmq_scan.ops import (
            rmq_index_batch_pallas,
            rmq_value_batch_pallas,
        )

        rng = np.random.default_rng(n)
        x = rng.random(n).astype(np.float32)
        plan = make_plan(n, c=c, t=t)
        h = build_hierarchy(jnp.asarray(x), plan, with_positions=True)
        ls, rs = _queries(rng, n, 128)
        want = np.array([x[l : r + 1].min() for l, r in zip(ls, rs)])
        wantp = np.array([l + np.argmin(x[l : r + 1]) for l, r in zip(ls, rs)])
        got = np.asarray(
            rmq_value_batch_pallas(
                h, jnp.asarray(ls), jnp.asarray(rs), qb=qb, interpret=True
            )
        )
        np.testing.assert_allclose(got, want)
        gotp = np.asarray(
            rmq_index_batch_pallas(
                h, jnp.asarray(ls), jnp.asarray(rs), qb=qb, interpret=True
            )
        )
        np.testing.assert_array_equal(gotp, wantp)

    def test_branchfree_oracle_equals_core(self):
        """Algorithm cross-check: branch-free walk == Listing-2 walk."""
        from repro.kernels.rmq_scan.ref import rmq_branchfree_batch

        rng = np.random.default_rng(33)
        n = 50_000
        x = jnp.asarray(rng.random(n).astype(np.float32))
        plan = make_plan(n, c=128, t=2)
        h = build_hierarchy(x, plan, with_positions=True)
        ls, rs = _queries(rng, n, 512)
        v1 = rmq_value_batch(h, jnp.asarray(ls), jnp.asarray(rs))
        p1 = rmq_index_batch(h, jnp.asarray(ls), jnp.asarray(rs))
        v2, p2 = rmq_branchfree_batch(
            plan, h.base, h.upper, h.upper_pos,
            jnp.asarray(ls), jnp.asarray(rs), track_pos=True,
        )
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
        np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))

    def test_query_batch_padding(self):
        """Batch sizes not divisible by qb are padded and sliced correctly."""
        from repro.kernels.rmq_scan.ops import rmq_value_batch_pallas

        rng = np.random.default_rng(5)
        n = 10_000
        x = rng.random(n).astype(np.float32)
        h = build_hierarchy(jnp.asarray(x), make_plan(n, c=128, t=1))
        ls, rs = _queries(rng, n, 37)  # prime batch size
        got = np.asarray(
            rmq_value_batch_pallas(
                h, jnp.asarray(ls), jnp.asarray(rs), qb=16, interpret=True
            )
        )
        want = np.array([x[l : r + 1].min() for l, r in zip(ls, rs)])
        np.testing.assert_allclose(got, want)


# ---------------------------------------------------------------------------
# rmq_fused (whole mixed batch, one launch, both output planes)
# ---------------------------------------------------------------------------
class TestRmqFusedKernel:
    @pytest.mark.parametrize("n,c,t,qb", [
        (100_000, 128, 4, 64),
        (65_536, 256, 2, 32),
        (300_000, 128, 2, 64),   # 4 levels
        (5_000, 16, 4, 16),      # 3 levels, small chunks
    ])
    def test_both_planes_match_naive(self, n, c, t, qb):
        """One interpret-mode launch returns values AND leftmost-tie
        positions matching the naive oracle (the production off-TPU
        lowering is the jnp program — covered by test_differential;
        this pins the pallas kernel itself)."""
        from repro.kernels.rmq_fused.ops import rmq_fused_batch

        rng = np.random.default_rng(n)
        x = rng.random(n).astype(np.float32)
        x[rng.integers(0, n, n // 8)] = 0.5  # ties
        plan = make_plan(n, c=c, t=t)
        h = build_hierarchy(jnp.asarray(x), plan, with_positions=True)
        ls, rs = _queries(rng, n, 128)
        want = np.array([x[l : r + 1].min() for l, r in zip(ls, rs)])
        wantp = np.array(
            [l + np.argmin(x[l : r + 1]) for l, r in zip(ls, rs)]
        )
        vals, pos = rmq_fused_batch(
            h, jnp.asarray(ls), jnp.asarray(rs), track_pos=True, qb=qb,
            interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(vals), want)
        np.testing.assert_array_equal(np.asarray(pos), wantp)

    def test_kernel_equals_package_ref_and_jnp_lowering(self):
        """Kernel vs the package's pure-jnp oracle vs the one-dispatch
        jnp production lowering: all three bit-identical."""
        from repro.kernels.rmq_fused.ops import _fused_jnp, rmq_fused_batch
        from repro.kernels.rmq_fused.ref import rmq_fused_batch_ref

        rng = np.random.default_rng(77)
        n, cap = 20_000, 26_000   # reserved +inf tail in play
        x = rng.integers(-4, 4, n).astype(np.float32)
        plan = make_plan(n, c=64, t=2, capacity=cap)
        h = build_hierarchy(jnp.asarray(x), plan, with_positions=True)
        ls, rs = _queries(rng, n, 96)
        lsj, rsj = jnp.asarray(ls), jnp.asarray(rs)
        kv, kp = rmq_fused_batch(h, lsj, rsj, track_pos=True, qb=32,
                                 interpret=True)
        rv, rp = rmq_fused_batch_ref(plan, h.base, h.upper, h.upper_pos,
                                     lsj, rsj, track_pos=True)
        jv, jp = _fused_jnp(h.base, h.upper, h.upper_pos,
                            lsj.astype(jnp.int32), rsj.astype(jnp.int32),
                            plan, True)
        for got in ((kv, kp), (jv, jp)):
            np.testing.assert_array_equal(np.asarray(got[0]),
                                          np.asarray(rv))
            np.testing.assert_array_equal(np.asarray(got[1]),
                                          np.asarray(rp))

    def test_value_only_and_padding(self):
        """Value-only launches and batch sizes not divisible by qb."""
        from repro.kernels.rmq_fused.ops import rmq_fused_value_batch

        rng = np.random.default_rng(6)
        n = 10_000
        x = rng.random(n).astype(np.float32)
        h = build_hierarchy(jnp.asarray(x), make_plan(n, c=128, t=1))
        ls, rs = _queries(rng, n, 37)  # prime batch size
        got = np.asarray(
            rmq_fused_value_batch(
                h, jnp.asarray(ls), jnp.asarray(rs), qb=16, interpret=True
            )
        )
        want = np.array([x[l : r + 1].min() for l, r in zip(ls, rs)])
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# rmq_short (two-chunk short-span scan)
# ---------------------------------------------------------------------------
class TestRmqShortKernel:
    @staticmethod
    def _short_queries(rng, n, c, m):
        """Random queries satisfying the SHORT predicate (<= 2 chunks)."""
        ls = rng.integers(0, n, m)
        rs = np.minimum(ls + rng.integers(1, 2 * c + 1, m) - 1, n - 1)
        keep = (rs // c) - (ls // c) <= 1
        return ls[keep].astype(np.int32), rs[keep].astype(np.int32)

    @pytest.mark.parametrize("n,c,qb", [
        (100_000, 128, 64),
        (4096, 8, 16),
        (777, 128, 32),     # capacity > 2c but unaligned tail
        (100, 64, 16),      # capacity < 2c -> ref fallback
    ])
    def test_matches_naive_and_walk(self, n, c, qb):
        from repro.kernels.rmq_short.ops import (
            rmq_short_index_batch_pallas,
            rmq_short_value_batch_pallas,
        )

        rng = np.random.default_rng(n + c)
        x = rng.random(n).astype(np.float32)
        x[rng.integers(0, n, n // 8)] = 0.5   # ties: leftmost must win
        h = build_hierarchy(jnp.asarray(x), make_plan(n, c=c, t=4),
                            with_positions=True)
        ls, rs = self._short_queries(rng, n, c, 300)
        want = np.array([x[l : r + 1].min() for l, r in zip(ls, rs)])
        wantp = np.array(
            [l + np.argmin(x[l : r + 1]) for l, r in zip(ls, rs)]
        )
        got = np.asarray(rmq_short_value_batch_pallas(
            h, jnp.asarray(ls), jnp.asarray(rs), qb=qb, interpret=True
        ))
        np.testing.assert_array_equal(got, want)
        gotp = np.asarray(rmq_short_index_batch_pallas(
            h, jnp.asarray(ls), jnp.asarray(rs), qb=qb, interpret=True
        ))
        np.testing.assert_array_equal(gotp, wantp)
        # and bit-identical to the full-walk oracle (engine parity contract)
        np.testing.assert_array_equal(
            got, np.asarray(rmq_value_batch(h, jnp.asarray(ls),
                                            jnp.asarray(rs)))
        )
        np.testing.assert_array_equal(
            gotp, np.asarray(rmq_index_batch(h, jnp.asarray(ls),
                                             jnp.asarray(rs)))
        )

    def test_index_without_positions(self):
        """Level-0 positions are indices: works on value-only builds."""
        from repro.kernels.rmq_short.ops import rmq_short_index_batch_pallas

        rng = np.random.default_rng(9)
        n, c = 20_000, 128
        x = rng.random(n).astype(np.float32)
        h = build_hierarchy(jnp.asarray(x), make_plan(n, c=c, t=2))
        assert not h.with_positions
        ls, rs = self._short_queries(rng, n, c, 100)
        gotp = np.asarray(rmq_short_index_batch_pallas(
            h, jnp.asarray(ls), jnp.asarray(rs), qb=16, interpret=True
        ))
        wantp = np.array(
            [l + np.argmin(x[l : r + 1]) for l, r in zip(ls, rs)]
        )
        np.testing.assert_array_equal(gotp, wantp)

    def test_query_batch_padding(self):
        from repro.kernels.rmq_short.ops import rmq_short_value_batch_pallas

        rng = np.random.default_rng(5)
        n, c = 10_000, 128
        x = rng.random(n).astype(np.float32)
        h = build_hierarchy(jnp.asarray(x), make_plan(n, c=c, t=1))
        ls, rs = self._short_queries(rng, n, c, 41)  # not qb-aligned
        got = np.asarray(rmq_short_value_batch_pallas(
            h, jnp.asarray(ls), jnp.asarray(rs), qb=16, interpret=True
        ))
        want = np.array([x[l : r + 1].min() for l, r in zip(ls, rs)])
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------
class TestFlashAttentionKernel:
    @pytest.mark.parametrize("batch,hq,hkv,s,d", [
        (2, 4, 2, 256, 64),
        (1, 8, 8, 128, 128),   # MHA
        (1, 8, 1, 256, 64),    # MQA
        (2, 2, 2, 512, 32),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, batch, hq, hkv, s, d, dtype):
        from repro.kernels.flash_attention.kernel import flash_attention
        from repro.kernels.flash_attention.ref import attention_ref

        rng = np.random.default_rng(hq * s)
        q = jnp.asarray(rng.standard_normal((batch, hq, s, d)), dtype)
        k = jnp.asarray(rng.standard_normal((batch, hkv, s, d)), dtype)
        v = jnp.asarray(rng.standard_normal((batch, hkv, s, d)), dtype)
        got = flash_attention(q, k, v, interpret=True)
        want = attention_ref(q, k, v)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=tol, rtol=tol,
        )

    @pytest.mark.parametrize("window", [128, 256, 1024])
    def test_sliding_window(self, window):
        from repro.kernels.flash_attention.kernel import flash_attention
        from repro.kernels.flash_attention.ref import attention_ref

        rng = np.random.default_rng(window)
        q = jnp.asarray(rng.standard_normal((1, 2, 512, 64)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 2, 512, 64)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 2, 512, 64)), jnp.float32)
        got = flash_attention(q, k, v, window=window, interpret=True)
        want = attention_ref(q, k, v, window=window)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )

    def test_first_token_attends_only_to_itself(self):
        from repro.kernels.flash_attention.kernel import flash_attention

        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((1, 1, 128, 64)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 1, 128, 64)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 1, 128, 64)), jnp.float32)
        out = flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out[0, 0, 0]), np.asarray(v[0, 0, 0]), rtol=1e-5
        )


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------
class TestSsdScanKernel:
    @pytest.mark.parametrize("batch,l,h,p,n", [
        (2, 256, 4, 64, 128),   # mamba2 geometry
        (1, 128, 2, 64, 16),    # hymba geometry
        (1, 512, 1, 32, 64),
    ])
    def test_chunked_and_pallas_match_naive(self, batch, l, h, p, n):
        from repro.kernels.ssd_scan.kernel import ssd_scan
        from repro.kernels.ssd_scan.ref import ssd_chunked_ref, ssd_ref

        rng = np.random.default_rng(l * h)
        dtx = jnp.asarray(rng.standard_normal((batch, l, h, p)) * 0.1,
                          jnp.float32)
        la = jnp.asarray(-np.abs(rng.standard_normal((batch, l, h))) * 0.1,
                         jnp.float32)
        Bm = jnp.asarray(rng.standard_normal((batch, l, n)) * 0.3, jnp.float32)
        Cm = jnp.asarray(rng.standard_normal((batch, l, n)) * 0.3, jnp.float32)
        y0, s0 = ssd_ref(dtx, la, Bm, Cm)
        y1, s1 = ssd_chunked_ref(dtx, la, Bm, Cm, chunk=128)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(s0), np.asarray(s1),
                                   atol=1e-4, rtol=1e-4)
        y2 = ssd_scan(dtx, la, Bm, Cm, chunk=128, interpret=True)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y2),
                                   atol=1e-4, rtol=1e-4)

    def test_state_continuity_across_calls(self):
        """Chunked ref with init_state == one long naive scan."""
        from repro.kernels.ssd_scan.ref import ssd_chunked_ref, ssd_ref

        rng = np.random.default_rng(7)
        B, L, H, P, N = 1, 256, 2, 32, 64
        dtx = jnp.asarray(rng.standard_normal((B, L, H, P)) * 0.1, jnp.float32)
        la = jnp.asarray(-np.abs(rng.standard_normal((B, L, H))) * 0.1,
                         jnp.float32)
        Bm = jnp.asarray(rng.standard_normal((B, L, N)) * 0.3, jnp.float32)
        Cm = jnp.asarray(rng.standard_normal((B, L, N)) * 0.3, jnp.float32)
        y_full, s_full = ssd_ref(dtx, la, Bm, Cm)
        half = L // 2
        y_a, s_a = ssd_chunked_ref(
            dtx[:, :half], la[:, :half], Bm[:, :half], Cm[:, :half], chunk=64
        )
        y_b, s_b = ssd_chunked_ref(
            dtx[:, half:], la[:, half:], Bm[:, half:], Cm[:, half:],
            chunk=64, init_state=s_a,
        )
        np.testing.assert_allclose(
            np.asarray(jnp.concatenate([y_a, y_b], axis=1)),
            np.asarray(y_full), atol=1e-4, rtol=1e-4,
        )
        np.testing.assert_allclose(np.asarray(s_b), np.asarray(s_full),
                                   atol=1e-4, rtol=1e-4)
