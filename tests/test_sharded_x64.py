"""Sharded indexes past 2^31: wide engine keys, 64-bit routing, sharded build.

An index of 2^31 or more entries cannot be held on the CPU here, so the
engine's wide-key dedup and cache and the distributed executor's routing
run against a stub sharded index of 2^33 slots whose answers are a
formula of the bounds; the real ``DistributedRMQ`` runs on a fake
four-device mesh under x64 (in subprocesses, so the device flag never
reaches this process) and is compared with the plain reference.
"""

import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.plan import make_plan
from repro.qe import QueryEngine
from repro.qe.distributed import CROSSING, SEG_LOCAL, DistributedExecutor
from test_result_cache import RefLRU

ROOT = Path(__file__).resolve().parents[1]
CAP = 2**31          # the stub's segment capacity
SEGS = 4


def _value(ls, rs):
    ls, rs = np.asarray(ls, np.int64), np.asarray(rs, np.int64)
    return (((ls * 1_000_003) ^ rs) % 4093).astype(np.float32)


def _position(ls, rs):
    ls, rs = np.asarray(ls, np.int64), np.asarray(rs, np.int64)
    return ls + (rs - ls) // 3


class WideStub:
    """A sharded index of ``SEGS`` segments of 2^31 slots; answers are
    ``_value`` / ``_position`` of the global bounds, and every call the
    executor makes is recorded."""

    distributed = True
    backend = "jax"
    generation = 0
    with_positions = True
    value_dtype = np.dtype(np.float32)
    num_segments = SEGS
    segment_capacity = CAP
    capacity = SEGS * CAP
    length = SEGS * CAP
    plan = make_plan(4096, c=128, t=64)

    def __init__(self):
        self.crossing = []       # global bounds of each pmin-path call
        self.grouped = []        # local bounds of each grouped call

    def query(self, ls, rs):
        self.crossing.append((np.array(ls), np.array(rs)))
        return _value(ls, rs)

    def query_index(self, ls, rs):
        self.crossing.append((np.array(ls), np.array(rs)))
        return _position(ls, rs)

    def _query_grouped(self, gl, gr, track_pos):
        assert gl.dtype == np.int32 and gl.shape[0] == SEGS
        assert (gl >= 0).all() and (gr < CAP).all()
        self.grouped.append((np.array(gl), np.array(gr)))
        start = np.arange(SEGS, dtype=np.int64)[:, None] * CAP
        ls, rs = gl.astype(np.int64) + start, gr.astype(np.int64) + start
        return _value(ls, rs), _position(ls, rs)


def _wide_queries(rng, m, lo=2**31, hi=2**33):
    ls = rng.integers(lo, hi, m)
    rs = np.minimum(ls + rng.integers(0, 2**31, m), hi - 1)
    return ls, rs


def _reference_order(ls, rs):
    """The distinct queries in the order the engine documents for wide
    keys: by the bits from 2^31 up of (l, r), then by the bits below."""
    pairs = np.unique(np.stack([ls, rs], axis=1), axis=0)
    l, r = pairs[:, 0], pairs[:, 1]
    m = (1 << 31) - 1
    order = np.lexsort((r & m, l & m, r >> 31, l >> 31))
    return l[order], r[order]


@pytest.mark.parametrize("op", ["value", "index", "mixed"])
def test_wide_engine_keys_match_ordered_dict_lru(op):
    """Dedup and the exact LRU over coordinates in [2^31, 2^33): answers,
    hits, misses, evictions and size equal the scalar reference's.  A
    mixed batch looks up its value entries, then its index entries."""
    rng = np.random.default_rng(3)
    with jax.enable_x64(True):
        stub = WideStub()
        eng = QueryEngine(stub, cache_size=48)
        ref = RefLRU(48)
        pool_l, pool_r = _wide_queries(rng, 90)
        for _ in range(12):
            pick = rng.integers(0, pool_l.shape[0], 40)   # repeats inside
            ls, rs = pool_l[pick], pool_r[pick]
            if op == "mixed":
                flags = rng.random(40) < 0.5
                vals, poss = eng.query_mixed(ls, rs, flags)
                np.testing.assert_array_equal(vals[~flags],
                                              _value(ls, rs)[~flags])
                np.testing.assert_array_equal(poss[flags],
                                              _position(ls, rs)[flags])
                assert (vals.dtype, poss.dtype) == (np.float32, np.int64)
            else:
                run = eng.query if op == "value" else eng.query_index
                want_fn = _value if op == "value" else _position
                got = np.asarray(run(ls, rs))
                np.testing.assert_array_equal(got, want_fn(ls, rs))
                assert got.dtype == (np.float32 if op == "value"
                                     else np.int64)
                flags = np.full(40, op == "index")
            ul, ur = _reference_order(ls, rs)
            asked = set(zip(ls.tolist(), rs.tolist(), flags.tolist()))
            entries = [(o, l, r) for o, f in (("value", False),
                                              ("index", True))
                       for l, r in zip(ul.tolist(), ur.tolist())
                       if (l, r, f) in asked]
            missed = [e for e in entries if ref.get(e[0], 0, *e[1:]) is None]
            for o, l, r in missed:
                ref.put(o, 0, l, r, 0)
            c = eng.cache
            assert (c.hits, c.misses, c.evictions, len(c)) == (
                ref.hits, ref.misses, ref.evictions, len(ref))


def test_wide_keys_of_two_generations_stay_apart():
    """An entry of one generation is never served for another: the key
    space ids are per (generation, space)."""
    with jax.enable_x64(True):
        stub = WideStub()
        eng = QueryEngine(stub, cache_size=64)
        ls = np.array([2**32 + 5, 3 * 2**31 + 1])
        rs = np.array([2**32 + 9, 2**33 - 1])
        eng.query(ls, rs)
        assert eng.cache.misses == 2
        eng.query(ls, rs)
        assert eng.cache.hits == 2
        succ = WideStub()
        succ.generation = 1
        eng.attach(succ)
        np.testing.assert_array_equal(np.asarray(eng.query(ls, rs)),
                                      _value(ls, rs))
        assert eng.cache.hits == 2 and eng.cache.misses == 4


def test_executor_routes_and_localizes_past_2_31():
    """owner * segment_capacity passes 2^31: contained spans reach their
    segment as int32 local bounds, crossing spans keep int64 global ones,
    and every answer comes back in submission order."""
    rng = np.random.default_rng(5)
    owner = rng.integers(0, SEGS, 300)
    local = rng.integers(0, CAP - 1000, 300)
    ls = owner * CAP + local
    rs = ls + rng.integers(0, 1000, 300)                  # contained
    cl = rng.integers(CAP, 2 * CAP, 100)
    cr = rng.integers(2 * CAP, SEGS * CAP, 100)            # crossing
    ls, rs = np.concatenate([ls, cl]), np.concatenate([rs, cr])
    perm = rng.permutation(ls.shape[0])
    ls, rs = ls[perm], rs[perm]
    for op, want in (("value", _value(ls, rs)),
                     ("index", _position(ls, rs))):
        for bulk in (False, True):
            stub = WideStub()
            ex = DistributedExecutor(min_bucket=16, max_bucket=64)
            with jax.enable_x64(True):
                out = (ex.run_bulk if bulk else ex.run)(stub, ls, rs, op)
            np.testing.assert_array_equal(out, want)
            assert out.dtype == want.dtype
            assert ex.class_counts == {SEG_LOCAL: 300, CROSSING: 100}
            sent = np.concatenate([g[0].ravel() for g in stub.grouped])
            assert np.isin(local, sent).all()
            cross = np.concatenate([c[0] for c in stub.crossing])
            assert cross.dtype == np.int64 and cross.max() >= CAP


def test_engine_refuses_wide_index_without_x64():
    with jax.enable_x64(False):
        with pytest.raises(ValueError, match="int32 query index space"):
            QueryEngine(WideStub())


def _run(prog: str) -> str:
    res = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
             "PYTHONPATH": f"{ROOT / 'src'}:{ROOT / 'bench'}",
             "PATH": "/usr/bin:/bin:/usr/local/bin",
             "JAX_PLATFORMS": "cpu"},
        cwd=ROOT, timeout=600)
    assert "SUBPROCESS_OK" in res.stdout, res.stdout + res.stderr
    return res.stdout


_ENGINE_PROG = r"""
import numpy as np, jax
jax.config.update("jax_enable_x64", True)
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.distributed import DistributedRMQ
from rmqbench.reference import RangeMinRef

mesh = jax.make_mesh((1, 4), ("data", "model"))
rng = np.random.default_rng(9)
n = 4000                                     # 4 segments of 1000, 2 levels
x = rng.integers(0, 40, n).astype(np.float32)
x[[150, 1150, 2150, 3150]] = -5.0            # one tie in every segment
ref = RangeMinRef(x)
sharded = jax.device_put(x, NamedSharding(mesh, P("model")))
for src in (sharded, x):
    d = DistributedRMQ.build(src, mesh, c=16, t=4, with_positions=True)
    assert d.num_segments == 4 and d.segment_capacity == 1000
    m = 600
    ls = rng.integers(0, n, m)
    rs = np.minimum(ls + rng.integers(0, 400, m), n - 1)   # mostly contained
    wl = rng.integers(0, 1000, 200)
    wr = rng.integers(2000, n, 200)                         # crossing
    ls = np.concatenate([ls, wl, [0, 100, 151]])
    rs = np.concatenate([rs, wr, [n - 1, 3999, 3999]])      # ties: leftmost
    want_v, want_p = ref.query(ls, rs)
    for kw in ({}, {"bulk_crossover": 1}):
        eng = d.engine(cache_size=256, **kw)
        for call in (lambda: eng.query(ls, rs),
                     lambda: eng.query_bulk(ls, rs, "value")):
            np.testing.assert_array_equal(np.asarray(call()), want_v)
        for call in (lambda: eng.query_index(ls, rs),
                     lambda: eng.query_bulk(ls, rs, "index")):
            got = np.asarray(call())
            np.testing.assert_array_equal(got, want_p)
        cc = eng.stats()["class_counts"]
        assert cc["seg_local"] > 0 and cc["crossing"] > 0, cc
    assert np.asarray(d.query_index(np.array([0, 151]),
                                    np.array([n - 1, n - 1]))).tolist() \
        == [150, 1150]
print("SUBPROCESS_OK")
"""


_MIXED_PROG = r"""
import numpy as np, jax
jax.config.update("jax_enable_x64", True)
from repro.core.distributed import DistributedRMQ

mesh = jax.make_mesh((1, 4), ("data", "model"))
rng = np.random.default_rng(12)
n = 4000                                     # 4 segments of 1000
x = rng.integers(0, 40, n).astype(np.float32)
x[[150, 1150, 2150, 3150]] = -5.0
d = DistributedRMQ.build(x, mesh, c=16, t=4, with_positions=True)
ls = rng.integers(0, n, 500)
rs = np.minimum(ls + rng.integers(0, 1500, 500), n - 1)    # both classes
ls, rs = np.concatenate([ls, ls[:60]]), np.concatenate([rs, rs[:60]])
flags = rng.random(ls.shape[0]) < 0.5
flags[500:] = ~flags[:60]                    # pairs asked for both ops
want_v = np.asarray(d.query(ls, rs))
want_p = np.asarray(d.query_index(ls, rs))
eng = d.engine(cache_size=4096)
assert not eng.supports_mixed
for _ in range(2):                           # executed, then cached
    v, p = eng.query_mixed(ls, rs, flags)
    assert v.view(np.uint32)[~flags].tolist() \
        == want_v.view(np.uint32)[~flags].tolist()
    np.testing.assert_array_equal(p[flags], want_p[flags])
keys = ls * n + rs
needed = np.unique(keys[~flags]).size + np.unique(keys[flags]).size
c = eng.cache
assert (c.hits, c.misses) == (needed, needed), (c.hits, c.misses, needed)
assert eng.stats()["batches"] == 2
assert eng.stats()["dedup_saved"] == 2 * (ls.size - np.unique(keys).size)
cc = eng.stats()["class_counts"]
assert cc["seg_local"] > 0 and cc["crossing"] > 0, cc
print("SUBPROCESS_OK")
"""


def test_sharded_query_mixed_under_x64_matches_the_index():
    """query_mixed on the fake four-device mesh under x64: one dedup,
    the router run once per op, answers bit-identical to
    ``DistributedRMQ.query`` / ``query_index``, cached after one pass."""
    _run(_MIXED_PROG)


def test_sharded_engine_under_x64_matches_reference():
    """query, query_index and query_bulk (routed and bulk) on a fake
    four-device mesh under x64: values and leftmost positions, crossing
    and contained spans, ties across segments."""
    _run(_ENGINE_PROG)


_BUILD_PROG = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import distributed as D

mesh = jax.make_mesh((1, 4), ("data", "model"))
sh = NamedSharding(mesh, P("model"))
rng = np.random.default_rng(4)
x = rng.random(4096).astype(np.float32)
inner = D._build_fn
seen = {}

def watched(*key):
    fn = inner(*key)
    def call(xs):
        # every array alive at the build that the build made, the input
        # among them: no device holds more than one segment of it
        for a in jax.live_arrays():
            if id(a) in seen["before"]:
                continue
            for s in a.addressable_shards:
                assert s.data.size <= seen["seg"], (a.shape, s.data.shape)
        assert xs.sharding.is_equivalent_to(sh, 1)
        seen["calls"] += 1
        return fn(xs)
    return call

D._build_fn = watched
for src, cap, seg in ((jax.device_put(x, sh), None, 1024),
                      (jax.device_put(x, sh), 6000, 1500),
                      (x, None, 1024), (x[:4001], None, 1001)):
    seen.update(before={id(a) for a in jax.live_arrays()}, seg=seg, calls=0)
    d = D.DistributedRMQ.build(src, mesh, c=16, t=4, capacity=cap)
    assert seen["calls"] == 1 and d.segment_capacity == seg
    for s in d.base.addressable_shards:
        assert s.data.shape == (seg,)
    live = np.asarray(src)
    ls = np.array([0, 5, 1000, 17])
    rs = np.array([live.size - 1, 3000, 1100, 17])
    want = [live[l:r + 1].min() for l, r in zip(ls, rs)]
    np.testing.assert_array_equal(np.asarray(d.query(ls, rs)), want)
print("SUBPROCESS_OK")
"""


def test_sharded_build_never_holds_more_than_a_segment_per_device():
    """Build from a segment-sharded array (used in place, or padded for
    reserved capacity) and from host data: the build sees only arrays
    whose every shard is at most one segment."""
    _run(_BUILD_PROG)
