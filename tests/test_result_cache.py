"""ResultCache against an ``OrderedDict`` LRU, call for call.

The reference below is the scalar LRU the array cache replaced.  Both
are driven with the same random sequences of scalar and batched gets and
puts (one op or two interleaved ops, two generations, batches larger
than the capacity, keys repeated within a get or distinct and sorted);
after every call the returned values, hit masks and the ``hits`` /
``misses`` / ``evictions`` / ``len()`` counters must agree.
"""

from collections import OrderedDict

import numpy as np
import pytest

from repro.qe.cache import (
    OP_BITS,
    ResultCache,
    entry_keys,
    from_bits,
    pack_keys,
    to_bits,
    unpack_keys,
)

VALUE, INDEX = "value", "index"
OPS = {b: op for op, b in OP_BITS.items()}
SCALAR = {VALUE: float, INDEX: int}      # what the scalar API holds
DTYPES = {VALUE: np.float64, INDEX: np.int64}


class RefLRU:
    def __init__(self, capacity):
        self.capacity = capacity
        self.d = OrderedDict()
        self.hits = self.misses = self.evictions = 0

    def __len__(self):
        return len(self.d)

    def get(self, op, gen, l, r):
        if self.capacity == 0:
            self.misses += 1
            return None
        key = (op, gen, l, r)
        val = self.d.get(key)
        if val is None:
            self.misses += 1
            return None
        self.d.move_to_end(key)
        self.hits += 1
        return val

    def put(self, op, gen, l, r, value):
        if self.capacity == 0:
            return
        key = (op, gen, l, r)
        self.d[key] = value
        self.d.move_to_end(key)
        while len(self.d) > self.capacity:
            self.d.popitem(last=False)
            self.evictions += 1


def _same_counters(cache, ref):
    assert (cache.hits, cache.misses, cache.evictions, len(cache)) == (
        ref.hits, ref.misses, ref.evictions, len(ref))


def _drive(capacity, seed, steps=120):
    rng = np.random.default_rng(seed)
    cache, ref = ResultCache(capacity), RefLRU(capacity)
    pool = max(2 * capacity, 6)
    for _ in range(steps):
        gen = int(rng.integers(0, 2))
        kind = rng.choice(["get", "put", "get_many", "put_many"])
        k = int(rng.integers(0, 3 * capacity + 6))
        ls = rng.integers(0, pool, k)
        rs = ls + rng.integers(0, 3, k)
        if rng.random() < 0.5:
            bits = np.full(k, rng.integers(0, 2))    # one op
        else:
            bits = rng.integers(0, 2, k)             # two ops interleaved
        keys = entry_keys(pack_keys(ls, rs), bits)
        if rng.random() < 0.3:
            # distinct and ascending, as the engine's deduped batches are
            keys = np.unique(keys)
        elif kind == "put_many":
            # a put names each key once: keep first occurrences
            keys = keys[np.sort(np.unique(keys, return_index=True)[1])]
        k = keys.shape[0]
        ls, rs = unpack_keys(keys >> 1)
        ops = [OPS[b] for b in keys & 1]
        if kind == "get":
            if not k:
                continue
            op, l, r = ops[0], int(ls[0]), int(rs[0])
            assert cache.get(op, gen, l, r) == ref.get(op, gen, l, r)
        elif kind == "put":
            if not k:
                continue
            op, l, r = ops[0], int(ls[0]), int(rs[0])
            v = SCALAR[op](rng.integers(-50, 50))
            cache.put(op, gen, l, r, v)
            ref.put(op, gen, l, r, v)
        elif kind == "put_many":
            vals = rng.integers(-50, 50, k)
            cache.put_many(gen, keys, np.where(
                keys & 1, to_bits(vals.astype(DTYPES[INDEX])),
                to_bits(vals.astype(DTYPES[VALUE]))))
            for i in range(k):
                ref.put(ops[i], gen, int(ls[i]), int(rs[i]),
                        SCALAR[ops[i]](vals[i]))
        else:
            got, hit = cache.get_many(gen, keys)
            for i in range(k):
                want = ref.get(ops[i], gen, int(ls[i]), int(rs[i]))
                assert hit[i] == (want is not None)
                if want is not None:
                    assert from_bits(got[i:i + 1], DTYPES[ops[i]])[0] == want
        _same_counters(cache, ref)


@pytest.mark.parametrize("capacity", [0, 1, 2, 7, 64])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_matches_ordered_dict_lru(capacity, seed):
    _drive(capacity, seed)


def _value_keys(ls, rs):
    return entry_keys(pack_keys(ls, rs), OP_BITS[VALUE])


@pytest.mark.parametrize("capacity", [1, 2, 7, 64])
def test_put_larger_than_capacity_keeps_last(capacity):
    """A put of >= capacity distinct keys keeps the last `capacity`."""
    cache, ref = ResultCache(capacity), RefLRU(capacity)
    for i in range(capacity):
        cache.put(VALUE, 0, i, i, float(i))
        ref.put(VALUE, 0, i, i, float(i))
    k = 3 * capacity + 1
    ls = np.arange(1000, 1000 + k)
    vals = np.arange(k, dtype=np.float64)
    cache.put_many(0, _value_keys(ls, ls), vals)
    for i in range(k):
        ref.put(VALUE, 0, int(ls[i]), int(ls[i]), vals[i].item())
    _same_counters(cache, ref)
    got, hit = cache.get_many(0, _value_keys(ls, ls), np.float64)
    assert (hit == (np.arange(k) >= k - capacity)).all()
    np.testing.assert_array_equal(got[hit], vals[-capacity:])


def test_refreshed_key_evicted_before_its_put():
    """A key present before a put call can be evicted inside the call by
    the call's own earlier inserts and then come back as an insert."""
    for capacity in (2, 3, 5):
        cache, ref = ResultCache(capacity), RefLRU(capacity)
        for i in range(capacity):
            cache.put(VALUE, 0, i, i, float(i))
            ref.put(VALUE, 0, i, i, float(i))
        # fresh keys first, then every old key again (newest first)
        ls = np.concatenate([np.arange(100, 100 + capacity - 1),
                             np.arange(capacity)[::-1]])
        vals = np.arange(ls.shape[0], dtype=np.float64)
        cache.put_many(0, _value_keys(ls, ls), vals)
        for i in range(ls.shape[0]):
            ref.put(VALUE, 0, int(ls[i]), int(ls[i]), vals[i].item())
        _same_counters(cache, ref)


def test_many_generations_age_out_together():
    """Entries of many generations share one table: a put evicts the
    oldest across all of them, as the dict did."""
    cache, ref = ResultCache(16), RefLRU(16)
    rng = np.random.default_rng(5)
    for gen in range(60):
        ls = np.unique(rng.integers(0, 40, 5))
        keys = _value_keys(ls, ls + 1)
        got, hit = cache.get_many(gen // 3, keys, np.float64)
        for i, l in enumerate(ls):
            want = ref.get(VALUE, gen // 3, int(l), int(l) + 1)
            assert hit[i] == (want is not None)
        vals = ls.astype(np.float64)
        cache.put_many(gen // 3, keys[~hit], vals[~hit])
        for l in ls[~hit]:
            ref.put(VALUE, gen // 3, int(l), int(l) + 1, float(l))
        _same_counters(cache, ref)
    assert sorted(ref.d) == sorted(
        (VALUE, int(g), int(key >> 1 >> 31), int(key >> 1 & (2**31 - 1)))
        for g, key in cache._table[:, :2])


def test_values_round_trip_bit_exact():
    """Every value dtype the engine serves comes back bit for bit."""
    import ml_dtypes

    rng = np.random.default_rng(3)
    cache = ResultCache(64)
    keys = _value_keys(np.arange(16), np.arange(16) + 5)
    for gen, dtype in enumerate((np.float32, ml_dtypes.bfloat16,
                                 np.float64, np.int32, np.int64)):
        vals = (rng.standard_normal(16) * 1e3).astype(dtype)
        if dtype not in (np.int32, np.int64):
            vals[:3] = np.array([-0.0, np.inf, -np.inf]).astype(dtype)
        cache.put_many(gen, keys, vals)
        got, hit = cache.get_many(gen, keys, dtype)
        assert hit.all() and got.dtype == np.dtype(dtype)
        np.testing.assert_array_equal(got.view(f"u{got.itemsize}"),
                                      vals.view(f"u{vals.itemsize}"))
    # the scalar API holds Python floats and ints exactly
    cache.put(VALUE, 9, 0, 5, -0.0)
    cache.put(INDEX, 9, 0, 5, 2**40 + 3)
    got = cache.get(VALUE, 9, 0, 5)
    assert got == 0.0 and np.signbit(got)
    assert cache.get(INDEX, 9, 0, 5) == 2**40 + 3


def test_packed_keys_round_trip_and_order():
    ls = np.array([0, 0, 2**31 - 1, 5, 0], np.int32)
    rs = np.array([2**31 - 1, 0, 2**31 - 1, 7, 1], np.int32)
    keys = pack_keys(ls, rs)
    uls, urs = unpack_keys(keys)
    np.testing.assert_array_equal(uls, ls)
    np.testing.assert_array_equal(urs, rs)
    order = np.lexsort((rs, ls))
    np.testing.assert_array_equal(np.argsort(keys, kind="stable"), order)
    # entry keys stay non-negative and sort by query, then op
    ek = entry_keys(np.repeat(keys, 2), np.tile([0, 1], keys.shape[0]))
    assert (ek >= 0).all()
    np.testing.assert_array_equal(
        np.argsort(ek, kind="stable"),
        np.stack([2 * order, 2 * order + 1], axis=1).ravel())


def test_scalar_bounds_checked():
    cache = ResultCache(4)
    with pytest.raises(ValueError):
        cache.put(VALUE, 0, -1, 3, 1.0)
    with pytest.raises(ValueError):
        cache.get(VALUE, 0, 0, 2**31)


def test_key_looked_up_twice_keeps_later_stamp():
    cache, ref = ResultCache(2), RefLRU(2)
    for c in (cache, ref):
        c.put(VALUE, 0, 1, 1, 1.0)
        c.put(VALUE, 0, 2, 2, 2.0)
    cache.get_many(0, _value_keys([1, 2, 1], [1, 2, 1]))
    for l in (1, 2, 1):
        ref.get(VALUE, 0, l, l)
    for c in (cache, ref):
        c.put(VALUE, 0, 3, 3, 3.0)     # evicts (2, 2), the older
    assert cache.get(VALUE, 0, 1, 1) == ref.get(VALUE, 0, 1, 1) == 1.0
    assert cache.get(VALUE, 0, 2, 2) is ref.get(VALUE, 0, 2, 2) is None
    _same_counters(cache, ref)


def test_put_refuses_a_key_twice():
    cache = ResultCache(8)
    keys = _value_keys([3, 1, 3], [4, 2, 4])
    with pytest.raises(ValueError):
        cache.put_many(0, keys, np.zeros(3, np.float32))
    with pytest.raises(ValueError):
        cache.put_many(0, keys[:2], np.zeros(3, np.float32))
    # the same query under two ops is two entries
    q = pack_keys([3, 3], [4, 4])
    bits = np.concatenate([to_bits(np.float64([1.0])), [7]])
    cache.put_many(0, entry_keys(q, [0, 1]), bits)
    assert len(cache) == 2
    assert cache.get(VALUE, 0, 3, 4) == 1.0
    assert cache.get(INDEX, 0, 3, 4) == 7


def test_batched_calls_from_many_threads():
    """Counters and storage stay consistent under concurrent batches."""
    import os
    import sys
    import threading

    cache = ResultCache(64)
    workers = 2 * (os.cpu_count() or 4)
    calls = 40
    lookups, errors = [], []

    def work(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(calls):
                keys = np.unique(_value_keys(rng.integers(0, 200, 16),
                                             rng.integers(0, 4, 16)))
                gen = int(rng.integers(0, 3))
                vals, hit = cache.get_many(gen, keys, np.float32)
                lookups.append(keys.shape[0])
                miss = keys[~hit]
                cache.put_many(gen, miss, (miss % 1000).astype(np.float32))
                if not (vals[hit] == keys[hit] % 1000).all():
                    errors.append(AssertionError("wrong value served"))
        except Exception as e:    # reported by the main thread below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    s = cache.stats()
    assert s["entries"] == len(cache) <= 64
    table = cache._table
    order = np.lexsort((table[:, 1], table[:, 0]))
    assert (order == np.arange(len(cache))).all()     # still sorted
    assert not errors, errors
    assert s["hits"] + s["misses"] == sum(lookups)
