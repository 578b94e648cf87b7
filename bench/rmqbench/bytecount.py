"""Bytes the algorithm needs, computed from shapes and query bounds.

These counts are of the work, not of any kernel's reads, so they stay
valid whichever path the program takes; a roofline share built on them
cannot pass 100% unless the time leaves out part of the work.
"""

from __future__ import annotations

import numpy as np

from .reference import level_geometry

F32 = 4


def query_chunk_pairs(ls, rs, capacity: int, c: int, t: int) -> int:
    """Distinct (level, chunk) pairs a batch needs under the paper's walk.

    At each level a range ``[a, b]`` that spans at most two chunks is
    answered from those chunks; a wider one reads its two boundary
    chunks and passes the chunks between them, ``[a//c + 1, b//c - 1]``,
    up a level.  At the top level every chunk the remaining range touches
    is read.  A chunk needed by many queries counts once.
    """
    lens, _, _ = level_geometry(capacity, c, t)
    a = np.asarray(ls, np.int64)
    b = np.asarray(rs, np.int64)
    pairs = 0
    for k in range(len(lens)):
        if a.size == 0:
            break
        ca, cb = a // c, b // c
        if k == len(lens) - 1:
            top = -(-lens[k] // c)
            mark = np.zeros(top + 1, np.int64)
            np.add.at(mark, ca, 1)
            np.add.at(mark, cb + 1, -1)
            pairs += int(np.count_nonzero(np.cumsum(mark)[:top]))
            break
        pairs += int(np.unique(np.concatenate([ca, cb])).size)
        up = cb - ca >= 2
        a, b = ca[up] + 1, cb[up] - 1
    return pairs


def query_bytes(ls, rs, capacity: int, c: int, t: int,
                itemsize: int = F32) -> int:
    """Bytes of the distinct chunks a batch needs (values only)."""
    return query_chunk_pairs(ls, rs, capacity, c, t) * c * itemsize


def build_bytes(n: int, capacity: int, c: int, t: int,
                with_positions: bool, itemsize: int = F32) -> int:
    """Level 0 read once plus every upper plane written once."""
    _, _, upper = level_geometry(capacity, c, t)
    per_entry = itemsize + (4 if with_positions else 0)
    return n * itemsize + upper * per_entry
