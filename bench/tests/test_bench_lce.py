"""The four-chip LCE cell on the CPU: its data, reference, byte count,
readers, and the whole cell at a tiny size on four fake devices."""

from __future__ import annotations

import numpy as np
import pytest

import cellkit
import lcekit
from rmqbench import bytecount, harness, lcpdata, tracing
from rmqbench.reference import RangeMinRef
from rmqbench.segments import SegmentedRangeMinRef, segment_query_bytes
from repro.obs.trace import Span

GENOME = 6_199_705_600


def _law(n, k):
    """P(LCP >= k), by its series where 1 - exp(-lam) would cancel."""
    lam = n * 4.0 ** -np.asarray(k, float)
    series = lam / 2 - lam**2 / 6 + lam**3 / 24 - lam**4 / 120
    return np.where(lam < 1e-3, series, 1.0 - (1.0 - np.exp(-lam)) / lam)


def test_survival_is_the_adjacent_suffix_law():
    s = lcpdata.survival(GENOME)
    ks = np.arange(1, s.shape[0] + 1)
    # float32 thresholds need no more than 1e-7 of the law
    np.testing.assert_allclose(s, _law(GENOME, ks), rtol=1e-7)
    assert (np.diff(s) < 0).all() and s[0] > 1 - 1e-8
    assert 2.0**-40 <= s[-1] < 2.0**-38     # stops at 2^-40
    # the median is near log4 n, about 16
    assert 15 <= int((s >= 0.5).sum()) <= 17


def _t64(thr):
    return (thr[0].astype(np.uint64) << np.uint64(32)) | thr[1].astype(
        np.uint64)


def test_thresholds_hold_the_law_to_64_bits():
    s = lcpdata.survival(GENOME)
    t = _t64(lcpdata.thresholds(GENOME))
    assert t.shape == s.shape and (np.diff(t.astype(float)) < 0).all()
    ks = np.arange(1, s.shape[0] + 1)
    lam = GENOME * 4.0 ** -ks
    tail = s < 0.5
    np.testing.assert_allclose(t[tail].astype(float) / 2**64, s[tail],
                               rtol=1e-12, atol=2.0**-64)
    # near 1 the complement: P(LCP < k) = (1 - e^-lam) / lam
    q = (2**64 - t[~tail].astype(object)).astype(float) / 2**64
    np.testing.assert_allclose(q, -np.expm1(-lam[~tail]) / lam[~tail],
                               rtol=1e-9)
    # the rare small LCPs that decide long spans keep their mass: about
    # four entries of 0 and sixteen of 1 in the genome's array
    assert GENOME * q[0] == pytest.approx(4.0, rel=1e-6)
    assert GENOME * (q[1] - q[0]) == pytest.approx(12.0, rel=1e-6)


def test_lcp_of_bits_matches_a_uint64_reference():
    import jax.numpy as jnp

    thr = lcpdata.thresholds(GENOME)
    t = _t64(thr)
    rng = np.random.default_rng(3)
    u = rng.integers(0, 2**64, 1 << 14, dtype=np.uint64, endpoint=False)
    edge = np.concatenate([t - np.uint64(1), t, t + np.uint64(1),
                           np.array([0, 2**64 - 1], np.uint64)])
    u = np.concatenate([u, edge])
    want = (u[:, None] < t[None, :]).sum(axis=1)
    hi = (u >> np.uint64(32)).astype(np.uint32)
    lo = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    got = np.asarray(lcpdata.lcp_of_bits(jnp.asarray(hi), jnp.asarray(lo),
                                         jnp.asarray(thr)))
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, want.astype(np.float32))


def test_lcp_of_bits_follows_the_cdf():
    import jax
    import jax.numpy as jnp

    m = 1 << 20
    bits = jax.random.bits(jax.random.key(1), (2, m), jnp.uint32)
    thr = jnp.asarray(lcpdata.thresholds(GENOME))
    v = np.asarray(lcpdata.lcp_of_bits(bits[0], bits[1], thr))
    assert v.dtype == np.float32 and (v == np.round(v)).all()
    for k in range(1, 30):
        p = _law(GENOME, k)
        got = float((v >= k).mean())
        assert abs(got - p) <= 5 * np.sqrt(p * (1 - p) / m) + 1e-6, k


def test_device_lcp_repeats_per_seed_and_follows_the_cdf():
    import jax

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    n = 1 << 18
    a = np.asarray(lcpdata.device_lcp(2**33 + 1, n, mesh, "model",
                                      block=1 << 14))
    b = np.asarray(lcpdata.device_lcp(2**33 + 1, n, mesh, "model",
                                      block=1 << 14))
    c = np.asarray(lcpdata.device_lcp(2**33 + 2, n, mesh, "model",
                                      block=1 << 14))
    np.testing.assert_array_equal(a, b)
    assert (a != c).any()
    for k in range(1, 12):
        p = _law(n, k)
        assert abs(float((a >= k).mean()) - p) <= 5 * np.sqrt(
            p * (1 - p) / n) + 1e-6, k
    tail = np.asarray(lcpdata.device_lcp(5, 1000, mesh, "model", block=64))
    assert tail.shape == (1000,) and (tail >= 0).all()


def test_rank_pairs_are_sorted_distinct_and_mostly_crossing():
    n = GENOME
    gen = np.random.default_rng(8)
    ls, rs = lcpdata.rank_pairs(n, 1 << 16, gen)
    assert ls.dtype == np.int64 and (ls < rs).all()
    assert ls.min() >= 0 and rs.max() < n and rs.max() > 2**32
    seg = n // 4
    crossing = float((ls // seg != rs // seg).mean())
    assert abs(crossing - 0.75) < 0.01
    ls2, _ = lcpdata.rank_pairs(n, 1 << 16, np.random.default_rng(8))
    np.testing.assert_array_equal(ls, ls2)
    # a tiny range forces ties to be redrawn
    ls, rs = lcpdata.rank_pairs(2, 1000, gen)
    assert (ls == 0).all() and (rs == 1).all()


def test_segmented_reference_matches_one_reference_with_ties():
    rng = np.random.default_rng(2)
    x = rng.integers(0, 6, 4 * 3000).astype(np.float32)
    segs = np.split(x, 4)
    whole, parts = RangeMinRef(x), SegmentedRangeMinRef(segs)
    ls = rng.integers(0, x.size, 5000)
    rs = np.minimum(ls + rng.integers(0, x.size, 5000), x.size - 1)
    ls = np.concatenate([ls, [0, 2999, 3000, 5999]])
    rs = np.concatenate([rs, [x.size - 1, 3000, 8999, 6000]])
    want_v, want_p = whole.query(ls, rs)
    got_v, got_p = parts.query(ls, rs)
    np.testing.assert_array_equal(got_v, want_v)
    np.testing.assert_array_equal(got_p, want_p)


@pytest.mark.parametrize("seg_len,c,t", [(4096, 16, 4), (3000, 8, 2)])
def test_segment_bytes_are_each_segments_walk(seg_len, c, t):
    rng = np.random.default_rng(seg_len)
    n = 4 * seg_len
    ls = rng.integers(0, n, 400)
    rs = np.minimum(ls + rng.integers(0, n, 400), n - 1)
    got = segment_query_bytes(ls, rs, seg_len, 4, c, t)
    want = 0
    for j in range(4):
        lo, hi = j * seg_len, (j + 1) * seg_len - 1
        sel = (ls <= hi) & (rs >= lo)
        want += bytecount.query_bytes(np.maximum(ls[sel], lo) - lo,
                                      np.minimum(rs[sel], hi) - lo,
                                      seg_len, c, t)
    assert got == want > 0
    one = segment_query_bytes(ls, rs, n, 1, c, t)
    assert one == bytecount.query_bytes(ls, rs, n, c, t)


# -- readers ------------------------------------------------------------------
LCE = {
    "engine.self_ms.lce": 1000.0 * ((4.0 - 1.5) + (3.0 - 0.75)) / 2,
    "dist.route_ms.lce": 1000.0 * (0.5 + 0.25) / 2,
    "dist.crossing_ms.lce": 1000.0 * (1.5 + 0.5) / 2,
    "dist.crossing_share.lce": 100.0 * (75 + 70) / (100 + 100),
    "engine.dedup_ms.lce": 1000.0 * 0.5 / 2,
    "engine.cache_ms.lce": 1000.0 * (0.5 + 0.25) / 2,
    "engine.fetch_ms.lce": 1000.0 * 1.0 / 2,
}
# readers that sum spans below each batch: 0 where a batch has none
SUMS = {"dist.route_ms.lce", "engine.dedup_ms.lce", "engine.cache_ms.lce",
        "engine.fetch_ms.lce"}


class Ctx:
    def __init__(self, spans=(), bench=(), device=None, record=None,
                 peaks=None, config=None):
        self.program_spans = list(spans)
        self.bench_spans = list(bench)
        self.device = device
        self.record = record or {}
        self.peaks = peaks
        self.config = config or {}


def _spans(rows):
    import threading

    th = threading.main_thread().name
    return [Span(name=n, start=s, end=e, span_id=i, parent_id=p, thread=th,
                 args=dict(a)) for n, s, e, i, p, a in rows]


BATCHES = _spans([
    ("dedup", 0.0, 0.5, 2, 1, {}),
    ("route", 0.5, 1.0, 3, 1, {"queries": 100, "seg_local": 25,
                                "crossing": 75}),
    ("launch", 1.0, 1.5, 5, 4, {"cls": "crossing"}),
    ("fetch", 1.5, 2.5, 6, 4, {"cls": "crossing"}),
    ("execute", 1.0, 2.5, 4, 1, {"cls": "crossing"}),
    ("query_bulk", 0.0, 4.0, 1, None, {}),
    ("route", 10.0, 10.25, 13, 11, {"queries": 100, "seg_local": 30,
                                    "crossing": 70}),
    ("execute", 10.25, 10.75, 14, 11, {"cls": "crossing"}),
    ("execute", 10.75, 11.0, 15, 11, {"cls": "seg_local"}),
    ("cache_get", 11.0, 11.5, 16, 11, {"lookups": 100, "hits": 0}),
    ("cache_put", 11.5, 11.75, 17, 11, {"entries": 100}),
    ("query_bulk", 10.0, 13.0, 11, None, {}),
])
BENCH_BATCHES = [("batch", 0.0, 4.0), ("batch", 10.0, 13.0)]


def _reader(name):
    return harness.Cell(cellkit.ROOT, lcekit.CELL).reader(name)


@pytest.mark.parametrize("name", sorted(LCE))
def test_lce_span_reader_sums_hand_made_spans(name):
    got = _reader(name)(Ctx(BATCHES, BENCH_BATCHES))
    assert got == pytest.approx(LCE[name])


@pytest.mark.parametrize("name", sorted(LCE))
def test_lce_span_reader_reads_nothing_without_the_spans(name):
    # a program without the router's spans: batches and an execute only
    old = _spans([("execute", 0.5, 1.0, 2, 1, {}),
                  ("query_bulk", 0.0, 2.0, 1, None, {})])
    value = _reader(name)(Ctx(old, BENCH_BATCHES))
    if name == "engine.self_ms.lce":
        assert value == pytest.approx(1000.0 * (4.0 - 0.5 + 3.0) / 2)
    elif name in SUMS:
        assert value == 0.0
    else:
        assert value is None
    assert _reader(name)(Ctx()) is None


def _trace(ops, busy_s=2.0, chips=4):
    return tracing.DeviceTrace(
        window_s=4.0, busy_s=busy_s, chips=chips, t0_ns=0, t1_ns=4 * 10**9,
        busy=[[(0, int(busy_s * 1e9))]] * chips, modules={}, ops=ops,
        annotations=[])


def test_lce_device_readers_on_a_hand_made_trace():
    dt = _trace({"pmin": 0.6, "all-reduce-done": 0.2, "fusion": 5.0})
    assert _reader("collective_share.lce")(Ctx(device=dt)) == \
        pytest.approx(100.0 * 0.8 / 8.0)
    assert _reader("idle_share.lce")(Ctx(device=dt)) == pytest.approx(50.0)
    cfg = {"n": 4 * 4096, "segments": 4, "c": 16, "t": 4}
    batch = (np.array([0, 100, 5000]), np.array([16383, 200, 9000]))
    want = segment_query_bytes(batch[0], batch[1], 4096, 4, 16, 4)
    got = _reader("query_roofline.lce")(Ctx(
        device=dt, record={"traced_batch": batch},
        peaks={"hbm_bytes_per_s": 819e9}, config=cfg))
    assert got == pytest.approx(100.0 * want / 819e9 / 8.0)
    for name in ("collective_share.lce", "idle_share.lce",
                 "query_roofline.lce"):
        assert _reader(name)(Ctx(record={}, config=cfg)) is None
    assert _reader("collective_share.lce")(Ctx(device=_trace(None))) is None


# -- the tiny cell on four fake devices ---------------------------------------
def test_tiny_lce_cell_is_correct(tmp_path):
    out = lcekit.run_tiny(tmp_path)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"qps", "setup_s"} <= set(out["metrics"])
    assert list(out)[-1] == "checks"


def test_tiny_lce_cell_traced_reads_the_span_metrics(tmp_path):
    out = lcekit.run_tiny(tmp_path, trace=True)
    assert out["correct"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    for name in LCE:
        assert got[name] is not None, name
    assert 60.0 <= got["dist.crossing_share.lce"] <= 90.0
    assert got["dist.crossing_ms.lce"] > 0


def test_tiny_lce_cell_catches_an_altered_answer(tmp_path):
    patch = (
        "import numpy as np, jax.numpy as jnp\n"
        "from repro.qe.engine import QueryEngine\n"
        "orig = QueryEngine.query_bulk\n"
        "def bad(self, ls, rs, op='value'):\n"
        "    out = np.array(orig(self, ls, rs, op)); out[::97] += 1\n"
        "    return jnp.asarray(out)\n"
        "QueryEngine.query_bulk = bad\n")
    out = lcekit.run_tiny(tmp_path, patch=patch)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("control", ["owner_only", "drop_last_piece"])
def test_tiny_lce_cell_catches_a_broken_guarantee(tmp_path, control):
    # the program answers each crossing span from one segment, or
    # without the piece of the segment that owns r
    seg_len = lcekit.TINY["n"] // 4
    patch = (
        "from repro.qe.engine import QueryEngine\n"
        "from rmqbench import segments\n"
        "orig = QueryEngine.query_bulk\n"
        "def bad(self, ls, rs, op='value'):\n"
        f"    ls, rs = segments.CONTROLS[{control!r}](ls, rs, {seg_len})\n"
        "    return orig(self, ls, rs, op)\n"
        "QueryEngine.query_bulk = bad\n")
    out = lcekit.run_tiny(tmp_path, patch=patch)
    assert not out["correct"], out["checks"]
    assert out["checks"]["wrong_answers"]["value"] > 0
