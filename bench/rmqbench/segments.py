"""The plain reference and the byte count for an index held in segments.

An array past one host buffer's comfort (the four-chip genome cell's 24.8
GB) is held as its consecutive segments.  :class:`SegmentedRangeMinRef`
builds one :class:`~rmqbench.reference.RangeMinRef` per segment and
answers a query as the leftmost minimum of its pieces, one per segment it
meets: plain numpy over the benchmark's own data, independent of the
program.  :func:`segment_query_bytes` applies the paper's walk
(:func:`~rmqbench.bytecount.query_chunk_pairs`) to each segment's piece
of each query in that segment's own coordinates.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Sequence

import numpy as np

from .bytecount import F32, query_chunk_pairs
from .reference import RangeMinRef

NO_POS = np.iinfo(np.int64).max


class SegmentedRangeMinRef:
    """Range minimum and leftmost argmin over consecutive segments."""

    def __init__(self, segments: Sequence[np.ndarray], block: int = 1024):
        lens = [s.shape[0] for s in segments]
        self.starts = np.concatenate([[0], np.cumsum(lens)[:-1]]).tolist()
        self.lens = lens
        # numpy releases the interpreter lock in the big passes
        with ThreadPoolExecutor(max(len(segments), 1)) as pool:
            self.refs = list(pool.map(lambda s: RangeMinRef(s, block),
                                      segments))
        self.dtype = segments[0].dtype

    def query(self, ls, rs):
        """``(values, positions)`` for inclusive global ranges."""
        ls = np.asarray(ls, np.int64)
        rs = np.asarray(rs, np.int64)
        vals = np.full(ls.shape[0], np.inf, self.dtype)
        pos = np.full(ls.shape[0], NO_POS, np.int64)
        # segments in ascending order: a later piece wins only when smaller
        for start, length, ref in zip(self.starts, self.lens, self.refs):
            idx = np.flatnonzero((ls < start + length) & (rs >= start))
            if not idx.size:
                continue
            lo = np.maximum(ls[idx] - start, 0)
            hi = np.minimum(rs[idx] - start, length - 1)
            v, p = ref.query(lo, hi)
            take = (v < vals[idx]) | (pos[idx] == NO_POS)
            vals[idx[take]] = v[take]
            pos[idx[take]] = p[take] + start
        return vals, pos


def owner_only(ls, rs, seg_len: int):
    """Control bounds: each query cut to the segment that owns ``l`` (a
    crossing span answered from its owning segment alone)."""
    ls = np.asarray(ls, np.int64)
    rs = np.asarray(rs, np.int64)
    return ls, np.minimum(rs, (ls // seg_len + 1) * seg_len - 1)


def drop_last_piece(ls, rs, seg_len: int):
    """Control bounds: a crossing query without the piece of the segment
    that owns ``r`` (one segment's piece lost before the combine)."""
    ls = np.asarray(ls, np.int64)
    rs = np.asarray(rs, np.int64)
    cross = ls // seg_len != rs // seg_len
    return ls, np.where(cross, rs // seg_len * seg_len - 1, rs)


# controls that break the guarantee of an exact range minimum over the
# whole span; a sound check reads more than 0 wrong on each
CONTROLS = {"owner_only": owner_only, "drop_last_piece": drop_last_piece}


def segment_query_bytes(ls, rs, seg_len: int, segments: int, c: int, t: int,
                        itemsize: int = F32) -> int:
    """Bytes of the distinct (segment, level, chunk) triples a batch needs:
    each query's intersection with each segment, walked in that
    segment's coordinates over a hierarchy of ``seg_len`` entries."""
    ls = np.asarray(ls, np.int64)
    rs = np.asarray(rs, np.int64)
    pairs = 0
    for j in range(segments):
        start = j * seg_len
        sel = (ls < start + seg_len) & (rs >= start)
        lo = np.maximum(ls[sel] - start, 0)
        hi = np.minimum(rs[sel] - start, seg_len - 1)
        pairs += query_chunk_pairs(lo, hi, seg_len, c, t)
    return pairs * c * itemsize
