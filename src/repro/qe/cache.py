"""Result cache for the batched query engine.

Two layers, both host-side (results are scalars — a float or an int —
so the cache never pins device memory):

* **within-batch dedup** lives in the engine (``np.unique`` over the
  packed query keys); this module only sees deduplicated queries;
* **cross-batch LRU** keyed by ``(op, generation, l, r)``.  The
  generation is the index's monotonic mutation counter —
  ``RMQ.update``/``append`` (and the streaming mutators) return a
  successor with ``generation + 1``, so entries computed against an
  older array version can never be returned for the new one.  Stale
  generations age out of the LRU naturally.

**Keys.** Below 2^31 a query ``(l, r)`` is one int64, ``(l << 31) | r``
(:func:`pack_keys`): keys fit in 62 bits and sort in ``(l, r)``
lexicographic order.  The same key serves the engine's dedup and the
cache.  A cache entry's key folds the op in as one more low bit,
``(key << 1) | OP_BITS[op]`` (:func:`entry_keys`), so one array of
entry keys can interleave value and index lookups and still sort by
query first.

**Wide keys.** An index of capacity 2^31 or more (a sharded one, under
x64) has coordinates that do not fit that key.  The engine then splits
each query into a *key space*, the bits of ``l`` and ``r`` from 2^31 up,
and the packed key of the bits below (:func:`split_keys`,
:func:`unique_wide`); dedup sorts by ``(space, key)``.  The cache takes
the spaces beside the keys (``spaces=`` of :meth:`ResultCache.get_many`
and :meth:`~ResultCache.put_many`) and gives each ``(generation,
space)`` pair an id of its own, which stands where the generation stands
for a narrow key.  Ids count down from -1, so they never meet a
generation, and are never reused.  The narrow path is untouched.

**Storage.** Arrays, not an ``OrderedDict``: one ``(n, 4)`` int64 table
of generation, entry key, the value's raw bits and a recency stamp,
sorted by ``(generation, entry key)``.  Values are stored as their raw
bytes zero-extended to 8 (:func:`to_bits`, bit-exact for every dtype up
to 8 bytes wide); a reader decodes them in the dtype it serves
(:func:`from_bits`).  A lookup is a ``searchsorted``; an insert merges
the new rows into the table; an eviction drops the oldest stamps.
Whatever the number of generations held, a call is one lookup and at
most one rebuild of the table.

**Batched API.** :meth:`ResultCache.get_many` / :meth:`put_many` take
one generation, an array of entry keys (with wide keys, their
spaces) and the lock once per call.  A put names each entry key once
(the engine puts deduplicated keys).  The scalar :meth:`get` /
:meth:`put` are one-key calls of the same code; they hold Python
numbers, a float for ``"value"`` and an int for ``"index"``.

**Exact LRU.** A batched call behaves exactly as the same scalar calls
made in the order of its keys (the engine's ascend within each op) on
an ``OrderedDict`` LRU: hits are refreshed and puts stamped in that
order, evictions remove the oldest stamps, and ``hits``,
``misses``, ``evictions`` and ``len()`` match it after every call.  The
contents of an LRU are the ``capacity`` most recently touched keys, so a
put keeps the newest stamps; its eviction count is the number of puts
that found their key absent less the growth in size.  A key found
present before the call is absent at its put iff ``capacity`` distinct
other keys were touched since it last was (:meth:`ResultCache._put`).

The cache is shared between the serving tier's flusher thread and any
caller thread that queries an engine directly, so every operation —
including the hit/miss bookkeeping, where ``x += 1`` is not atomic under
the GIL — runs under one lock.
"""

from __future__ import annotations

import threading
from typing import Tuple

import numpy as np

__all__ = ["OP_BITS", "ResultCache", "entry_keys", "from_bits",
           "join_keys", "pack_keys", "split_keys", "to_bits",
           "unique_wide", "unpack_keys"]

OP_BITS = {"value": 0, "index": 1}     # an entry key's low bit
_SCALAR_DTYPES = {"value": np.float64, "index": np.int64}
_R_MASK = np.int64((1 << 31) - 1)
_SPACE_R_MASK = np.int64((1 << 32) - 1)
_GEN, _KEY, _BITS, _STAMP = 0, 1, 2, 3      # columns of the table


def pack_keys(ls, rs) -> np.ndarray:
    """One int64 key per query, ``(l << 31) | r`` (``0 <= l, r < 2^31``)."""
    return (np.asarray(ls, np.int64) << 31) | np.asarray(rs, np.int64)


def unpack_keys(keys) -> Tuple[np.ndarray, np.ndarray]:
    """The ``(l, r)`` int32 bounds of packed ``keys``."""
    keys = np.asarray(keys, np.int64)
    return ((keys >> 31).astype(np.int32),
            (keys & _R_MASK).astype(np.int32))


def split_keys(ls, rs) -> Tuple[np.ndarray, np.ndarray]:
    """``(spaces, keys)`` of wide queries: the bits of ``l`` and ``r``
    from 2^31 up as ``((l >> 31) << 32) | (r >> 31)``, and the packed key
    of the bits below.  Exact for ``0 <= l, r < 2^62``."""
    ls = np.asarray(ls, np.int64)
    rs = np.asarray(rs, np.int64)
    return (((ls >> 31) << 32) | (rs >> 31),
            pack_keys(ls & _R_MASK, rs & _R_MASK))


def join_keys(spaces, keys) -> Tuple[np.ndarray, np.ndarray]:
    """The int64 ``(l, r)`` bounds of :func:`split_keys` output."""
    spaces = np.asarray(spaces, np.int64)
    keys = np.asarray(keys, np.int64)
    return (((spaces >> 32) << 31) | (keys >> 31),
            ((spaces & _SPACE_R_MASK) << 31) | (keys & _R_MASK))


def unique_wide(ls, rs):
    """``(spaces, keys, inverse)``: the distinct wide queries sorted by
    ``(space, key)``, and each query's row among them."""
    spaces, keys = split_keys(ls, rs)
    order = np.lexsort((keys, spaces))
    s, k = spaces[order], keys[order]
    first = np.ones(s.shape, bool)
    first[1:] = (s[1:] != s[:-1]) | (k[1:] != k[:-1])
    inverse = np.empty(s.shape, np.intp)
    inverse[order] = np.cumsum(first) - 1
    return s[first], k[first], inverse


def entry_keys(keys, op_bits) -> np.ndarray:
    """Cache keys of packed query ``keys`` under ``op_bits`` (one bit or
    an array of them, from :data:`OP_BITS`): ``(key << 1) | bit``."""
    return (np.asarray(keys, np.int64) << 1) | np.asarray(op_bits, np.int64)


def to_bits(values) -> np.ndarray:
    """Each value's raw bytes, zero-extended into an int64."""
    values = np.ascontiguousarray(values)
    size = values.dtype.itemsize
    if size > 8:
        raise ValueError(f"values wider than 8 bytes: {values.dtype}")
    return (values.view(f"u{size}").astype(np.uint64, copy=False)
            .view(np.int64))


def from_bits(bits, dtype) -> np.ndarray:
    """Inverse of :func:`to_bits` for values of ``dtype``."""
    dtype = np.dtype(dtype)
    return (np.asarray(bits, np.int64).view(np.uint64)
            .astype(f"u{dtype.itemsize}").view(dtype))


def _scalar_key(op: str, l: int, r: int) -> np.ndarray:
    if not (0 <= l < 2**31 and 0 <= r < 2**31):
        raise ValueError(f"bounds must lie in [0, 2^31), got ({l}, {r})")
    return entry_keys(pack_keys([l], [r]), OP_BITS[op])


def _ascending(keys: np.ndarray) -> bool:
    """Are ``keys`` strictly ascending (the engine's are)?  Raises on a
    key named twice."""
    if bool((keys[1:] > keys[:-1]).all()):
        return True
    s = np.sort(keys)
    if (s[1:] == s[:-1]).any():
        raise ValueError("put_many got a key twice")
    return False


def _as_items(table: np.ndarray) -> np.ndarray:
    """Each row of an ``(n, 4)`` int64 table as one 32-byte item."""
    return np.ascontiguousarray(table).view("V32").reshape(-1)


def _earlier_greater(r: np.ndarray) -> np.ndarray:
    """``out[i] = #{j < i : r[j] > r[i]}`` for distinct ints ``0 <= r <
    len(r)``: a merge count, one vectorised pass per level."""
    m = r.shape[0]
    out = np.zeros(m, np.int64)
    pos = np.arange(m)
    w = 1
    while w < m:
        block = pos // (2 * w)
        right = (pos // w) % 2 == 1
        left = np.sort(block[~right] * m + r[~right])
        q = block[right] * m + r[right]
        out[right] += (np.searchsorted(left, (block[right] + 1) * m)
                       - np.searchsorted(left, q, side="right"))
        w *= 2
    return out


class ResultCache:
    """Bounded LRU mapping ``(op, generation, l, r) -> scalar result``.

    Thread-safe: one lock covers the storage and the hit/miss/eviction
    counters, so ``stats()`` is always a consistent snapshot.
    """

    def __init__(self, capacity: int):
        if capacity < 0:
            raise ValueError(f"capacity must be >= 0, got {capacity}")
        self.capacity = capacity
        self._lock = threading.Lock()
        self._table = np.zeros((0, 4), np.int64)
        self._clock = 0            # next recency stamp
        self._space_ids = {}       # (generation, key space) -> id < 0
        self._next_space_id = -1
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return self._table.shape[0]

    # -- scalar API: one-key calls of the batched one ---------------------
    def get(self, op: str, generation: int, l: int, r: int):
        """The cached result, or None on miss (results are never None)."""
        key = _scalar_key(op, l, r)
        with self._lock:
            bits, hit = self._get(generation, key)
        if not hit[0]:
            return None
        return from_bits(bits, _SCALAR_DTYPES[op])[0].item()

    def put(self, op: str, generation: int, l: int, r: int, value) -> None:
        if self.capacity == 0:
            return
        key = _scalar_key(op, l, r)
        bits = to_bits(np.asarray([value], _SCALAR_DTYPES[op]))
        with self._lock:
            self._put(generation, key, bits)

    # -- batched API ------------------------------------------------------
    def get_many(self, generation: int, keys, dtype=np.int64,
                 spaces=None):
        """Look up entry ``keys`` in order: ``(values, hit)``.

        ``values`` holds each hit's value decoded as ``dtype`` (the raw
        bits with the default; unspecified where ``hit`` is False).
        ``spaces`` gives wide keys their key spaces (aligned with
        ``keys``).
        """
        keys = np.asarray(keys, np.int64).ravel()
        with self._lock:
            if spaces is None:
                bits, hit = self._get(generation, keys)
            else:
                bits = np.empty(keys.shape, np.int64)
                hit = np.empty(keys.shape, bool)
                for sid, a, b in self._space_runs(generation, spaces):
                    bits[a:b], hit[a:b] = self._get(sid, keys[a:b])
        return from_bits(bits, dtype), hit

    def put_many(self, generation: int, keys, values, spaces=None) -> None:
        """Insert entry ``keys`` in order, storing the raw bits of
        ``values`` (aligned with ``keys``; int64 values are taken as
        bits already).  The keys (with ``spaces``, as in
        :meth:`get_many`: the ``(space, key)`` pairs) must be distinct."""
        if self.capacity == 0:
            return
        keys = np.asarray(keys, np.int64).ravel()
        bits = to_bits(np.asarray(values).ravel())
        if bits.shape != keys.shape:
            raise ValueError(
                f"values must match keys, got {bits.shape} vs {keys.shape}")
        with self._lock:
            runs = ([(generation, 0, keys.shape[0])] if spaces is None
                    else self._space_runs(generation, spaces))
            ascending = [_ascending(keys[a:b]) for _, a, b in runs]
            for (sid, a, b), up in zip(runs, ascending):
                self._put(sid, keys[a:b], bits[a:b], up)

    def clear(self) -> None:
        with self._lock:
            self._table = np.zeros((0, 4), np.int64)
            self._space_ids = {}

    def hit_rate(self) -> float:
        """Hits / lookups over the cache's lifetime (0.0 when untouched)."""
        with self._lock:
            total = self.hits + self.misses
            return self.hits / total if total else 0.0

    def stats(self) -> dict:
        with self._lock:
            return {
                "capacity": self.capacity,
                "entries": len(self),
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
            }

    # -- internals (lock held) --------------------------------------------
    def _space_runs(self, generation: int, spaces):
        """``[(id, lo, hi)]``: the cache id of each run of equal values
        in ``spaces``.  Ids of pairs with no entry left are
        forgotten once they outnumber twice the capacity (a fresh id
        finds nothing either)."""
        spaces = np.asarray(spaces, np.int64).ravel()
        if spaces.shape[0] == 0:
            return []
        ids = self._space_ids
        if len(ids) > 2 * self.capacity + 64:
            live = set(np.unique(self._table[:, _GEN]).tolist())
            self._space_ids = ids = {
                k: v for k, v in ids.items() if v in live}
        cut = (np.flatnonzero(spaces[1:] != spaces[:-1]) + 1).tolist()
        runs = []
        for a, b in zip([0] + cut, cut + [spaces.shape[0]]):
            pair = (generation, int(spaces[a]))
            sid = ids.get(pair)
            if sid is None:
                sid = ids[pair] = self._next_space_id
                self._next_space_id -= 1
            runs.append((sid, a, b))
        return runs

    @staticmethod
    def _insert_at(table: np.ndarray, add: np.ndarray) -> np.ndarray:
        """Where the rows ``add`` of one generation, sorted by key, go in
        ``table`` to keep it sorted."""
        gen = add[0, _GEN]
        lo, hi = np.searchsorted(table[:, _GEN], [gen, gen + 1])
        return lo + np.searchsorted(table[lo:hi, _KEY], add[:, _KEY])

    def _replace_oldest(self, row: np.ndarray) -> None:
        """Evict the oldest entry and insert ``row`` in its sorted place,
        in place."""
        table = self._table
        i = int(table[:, _STAMP].argmin())
        p = int(self._insert_at(table, row[None])[0])
        if p > i:
            p -= 1
            table[i:p] = table[i + 1:p + 1]
        else:
            table[p + 1:i + 1] = table[p:i]
        table[p] = row

    def _find(self, generation: int, keys: np.ndarray):
        """``(rows, found)``: each key's row in the table, where found."""
        table = self._table
        k = keys.shape[0]
        gens = table[:, _GEN]
        lo, hi = np.searchsorted(gens, [generation, generation + 1])
        if lo == hi or k == 0:
            return np.zeros(k, np.int64), np.zeros(k, bool)
        col = table[lo:hi, _KEY]
        slots = np.minimum(np.searchsorted(col, keys), hi - lo - 1)
        return lo + slots, col[slots] == keys

    def _get(self, generation: int, keys: np.ndarray):
        """Look up; refresh hits in key order; count hits and misses."""
        k = keys.shape[0]
        rows, hit = self._find(generation, keys)
        bits = np.zeros(k, np.int64)
        at = np.flatnonzero(hit)
        rows = rows[at]
        bits[at] = self._table[rows, _BITS]
        # a key looked up twice keeps its later stamp
        np.maximum.at(self._table[:, _STAMP], rows, self._clock + at)
        self._clock += k
        n_hit = at.shape[0]
        self.hits += n_hit
        self.misses += k - n_hit
        return bits, hit

    def _put(self, generation: int, keys: np.ndarray, bits: np.ndarray,
             ascending: bool = True) -> None:
        """Put distinct ``keys``, stamped in call order."""
        table = self._table
        k = keys.shape[0]
        cap = self.capacity
        n0 = table.shape[0]
        clock = self._clock
        self._clock += k
        rows, found = self._find(generation, keys)
        at = np.flatnonzero(found)      # puts of keys present before
        rows = rows[at]
        refreshed = at.shape[0]
        if refreshed and n0 + (k - refreshed) > cap:
            # a present key is absent at its put (evicted by the call's
            # own inserts) iff `cap` distinct other keys were touched
            # since it last was: the entries newer than it, plus the
            # call's earlier puts, less those counted twice
            stamps = table[:, _STAMP]
            rank = np.searchsorted(np.sort(stamps), stamps[rows])
            dense = np.empty(refreshed, np.int64)
            dense[np.argsort(rank)] = np.arange(refreshed)
            touched = (n0 - 1 - rank) + at - _earlier_greater(dense)
            refreshed = int((touched < cap).sum())

        def new_rows(i):
            return np.stack([np.full(i.shape, generation, np.int64),
                             keys[i], bits[i], clock + i], axis=1)

        # the newest `cap` stamps stay: the call's last `cap` puts, then
        # the newest entries from before it.  Refresh in place first,
        # then drop the oldest entries and merge the new keys in.
        if at.shape[0]:
            table[rows] = new_rows(at)
        new = np.flatnonzero(~found)
        new = new[new >= k - cap]
        drop = n0 + new.shape[0] - cap
        add = new_rows(new)
        if not ascending:
            add = add[np.argsort(add[:, _KEY])]
        if drop == 1 and new.shape[0] == 1:
            # one in, one out (a small put on a full cache): shift the
            # rows between the two places, with no rebuild
            self._replace_oldest(add[0])
        else:
            items = _as_items(table)
            if drop > 0:
                stamps = table[:, _STAMP]
                floor = np.partition(stamps, drop - 1)[drop - 1]
                items = items[stamps > floor]
            if new.shape[0]:
                kept = items.view(np.int64).reshape(-1, 4)
                pos = self._insert_at(kept, add)
                items = np.insert(items, pos, _as_items(add))
            self._table = items.view(np.int64).reshape(-1, 4)
        size = n0 + new.shape[0] - max(drop, 0)
        self.evictions += (k - refreshed) - (size - n0)
