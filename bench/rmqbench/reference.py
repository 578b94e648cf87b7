"""The plain reference: range minimum and leftmost argmin in numpy.

Copied from the program's ``chip_smoke.py`` (``RangeMinRef``,
``reference_hierarchy``) and kept here so that no program change can move
the yardstick.  It imports nothing of the program and takes nothing the
program made: the data comes from the benchmark's own generator, and the
level geometry from :func:`level_geometry`, which follows the paper's
definition (levels of ``ceil(len / c)`` minima until a level holds at
most ``c * t`` entries; each upper level padded to whole chunks and
stored back to back).

The control (:func:`control_values`) is the same reference computed one
precision lower than the configuration states: float32 data rounded to
bfloat16 before the minimum is taken.
"""

from __future__ import annotations

import numpy as np

PAD_POS = np.iinfo(np.int32).max


def level_geometry(capacity: int, c: int, t: int):
    """``(level_lens, offsets, upper_size)`` of the chunked min-hierarchy."""
    lens = [capacity]
    while lens[-1] > c * t:
        lens.append(-(-lens[-1] // c))
    offsets, acc = [], 0
    for m in lens[1:]:
        offsets.append(acc)
        acc += -(-m // c) * c
    return tuple(lens), tuple(offsets), acc


def _lex_min(v1, p1, v2, p2):
    """Elementwise (value, position) minimum: ties go to the lower position."""
    take2 = (v2 < v1) | ((v2 == v1) & (p2 < p1))
    return np.where(take2, v2, v1), np.where(take2, p2, p1)


class RangeMinRef:
    """Range minimum and leftmost argmin over ``x`` in plain numpy.

    Blocks of ``block`` entries: a query scans its (at most two) boundary
    blocks directly and covers the blocks between them with a sparse
    table of block minima.
    """

    def __init__(self, x: np.ndarray, block: int = 1024):
        self.B = block
        nb = -(-x.shape[0] // block)
        xp = np.full(nb * block, np.inf, x.dtype)
        xp[: x.shape[0]] = x
        self.xb = xp.reshape(nb, block)
        bv = self.xb.min(axis=1)
        bp = self.xb.argmin(axis=1).astype(np.int64) + np.arange(nb) * block
        self.tv, self.tp = [bv], [bp]
        j = 1
        while (1 << j) <= nb:
            half = 1 << (j - 1)
            v, p = self.tv[-1], self.tp[-1]
            nv, np_ = _lex_min(v[:-half], p[:-half], v[half:], p[half:])
            self.tv.append(nv)
            self.tp.append(np_)
            j += 1

    def _piece(self, blk, lo, hi):
        """(min, leftmost pos) over [lo, hi] inside block ``blk``."""
        w = self.xb[blk]
        idx = blk[:, None] * self.B + np.arange(self.B)[None, :]
        mw = np.where((idx >= lo[:, None]) & (idx <= hi[:, None]), w, np.inf)
        a = mw.argmin(axis=1)
        return mw[np.arange(w.shape[0]), a], blk * self.B + a

    def query(self, ls, rs, batch: int = 8192):
        """``(values, positions)`` for inclusive ranges ``[ls, rs]``."""
        ls = np.asarray(ls, np.int64)
        rs = np.asarray(rs, np.int64)
        vals = np.empty(ls.shape[0], self.xb.dtype)
        pos = np.empty(ls.shape[0], np.int64)
        for s in range(0, ls.shape[0], batch):
            l, r = ls[s:s + batch], rs[s:s + batch]
            bl, br = l // self.B, r // self.B
            v, p = self._piece(bl, l, r)
            v2, p2 = self._piece(br, l, r)
            v, p = _lex_min(v, p, v2, p2)
            a, b = bl + 1, br - 1
            inner = b >= a
            cnt = np.where(inner, b - a + 1, 1)
            k = np.floor(np.log2(cnt)).astype(np.int64)
            a_ = np.where(inner, a, 0)
            b2 = np.where(inner, b - (1 << k) + 1, 0)
            mv = np.empty(l.shape[0], self.xb.dtype)
            mp = np.empty(l.shape[0], np.int64)
            for kk in np.unique(k):
                sel = k == kk
                mv[sel], mp[sel] = _lex_min(
                    self.tv[kk][a_[sel]], self.tp[kk][a_[sel]],
                    self.tv[kk][b2[sel]], self.tp[kk][b2[sel]],
                )
            mv = np.where(inner, mv, np.inf)
            v, p = _lex_min(v, p, mv, np.where(inner, mp, PAD_POS))
            vals[s:s + batch], pos[s:s + batch] = v, p
        return vals, pos


def reference_hierarchy(x: np.ndarray, capacity: int, c: int, t: int,
                        with_positions: bool = False):
    """Every upper level by numpy ``reshape(-1, c)`` min/argmin.

    Returns ``(upper values, upper positions or None)`` laid out as
    :func:`level_geometry` says; padding is ``+inf`` / ``PAD_POS``.
    """
    lens, offsets, upper_size = level_geometry(capacity, c, t)
    upper = np.full(upper_size, np.inf, x.dtype)
    upper_pos = np.full(upper_size, PAD_POS, np.int32)
    cur_v = np.full(capacity, np.inf, x.dtype)
    cur_v[: x.shape[0]] = x
    cur_p = np.arange(capacity, dtype=np.int32)
    for k in range(1, len(lens)):
        want = lens[k] * c
        v = np.full(want, np.inf, x.dtype)
        v[: cur_v.shape[0]] = cur_v
        p = np.full(want, PAD_POS, np.int32)
        p[: cur_p.shape[0]] = cur_p
        v, p = v.reshape(-1, c), p.reshape(-1, c)
        a = v.argmin(axis=1)                      # leftmost tie
        rows = np.arange(v.shape[0])
        cur_v, cur_p = v[rows, a], p[rows, a]
        off = offsets[k - 1]
        upper[off:off + cur_v.shape[0]] = cur_v
        upper_pos[off:off + cur_p.shape[0]] = cur_p
    return upper, (upper_pos if with_positions else None)


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """``x`` rounded to bfloat16 (nearest even), held as float32."""
    import ml_dtypes

    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def mismatches(got, want) -> int:
    """How many entries differ (NaN never equals anything)."""
    got = np.asarray(got)
    want = np.asarray(want)
    if got.shape != want.shape:
        return int(max(got.size, want.size))
    return int(np.count_nonzero(got != want))
