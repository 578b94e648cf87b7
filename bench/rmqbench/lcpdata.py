"""The LCP array of a DNA text and its rank-pair queries, from ``--seed``.

No genome is in the repository, and a real LCP array needs a suffix-array
build that no run's set-up could afford.  The entries are therefore drawn
i.i.d. from the law of the longest common prefix of two suffixes adjacent
in the suffix array of an i.i.d. uniform text of length ``n`` over four
letters: a suffix shares its first ``k`` letters with about
Poisson(``lam_k``) others, ``lam_k = n * 4^-k``, and its successor in
sorted order shares them unless it is the last of that group, so

    P(LCP >= k) = 1 - (1 - exp(-lam_k)) / lam_k.

The values are small integers, stored exactly in float32, and tie-heavy,
with a mode near ``log4 n``.  The smallest ones are the rarest (at the
genome's n, four entries of 0 in 6.2e9 are expected) and decide most long
spans, so each entry inverts the law from a 64-bit uniform, compared
word by word with 64-bit fixed-point thresholds (:func:`thresholds`): a
float32 uniform resolves only 2^-23 and would never draw an LCP below 4
there.  What the law leaves out of a real genome:
the correlation of neighbouring entries, and the repeat tail (Alu, LINE
and satellite repeats give LCPs of thousands).

The array is made on the devices, one segment per device of the mesh's
segment axis, each in blocks that depend on ``(seed, segment, block)``
alone: no device holds more than its segment.
"""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor
from fractions import Fraction
from typing import List

import numpy as np

from .data import seed_words

STREAM = 4            # the seed stream of the LCP values (data.seed_words)


def survival(n: int) -> np.ndarray:
    """``P(LCP >= k)`` for ``k = 1, 2, ...`` while it is at least 2^-40."""
    out = []
    k = 1
    while True:
        lam = n * 4.0 ** -k
        if lam < 1e-3:      # the series, where 1 - (1 - e^-lam) / lam cancels
            s = lam / 2 - lam**2 / 6 + lam**3 / 24 - lam**4 / 120
        else:
            s = 1.0 + np.expm1(-lam) / lam
        if s < 2.0 ** -40:
            return np.asarray(out)
        out.append(s)
        k += 1


def thresholds(n: int) -> np.ndarray:
    """``(2, K)`` uint32: the high and low words of ``floor(P(LCP >= k) *
    2^64)`` for the ``K`` values of :func:`survival`.  Where the law is
    near 1 its complement ``(1 - e^-lam) / lam`` is taken, which float64
    holds to its full relative precision."""
    out = []
    for k, s in enumerate(survival(n), start=1):
        lam = n * 4.0 ** -k
        if s < 0.5:
            t = math.floor(Fraction(float(s)) * 2**64)
        else:
            q = -np.expm1(-lam) / lam                  # P(LCP < k)
            t = 2**64 - math.ceil(Fraction(float(q)) * 2**64)
        out.append(min(max(t, 0), 2**64 - 1))
    return np.array([[t >> 32 for t in out], [t & 0xFFFFFFFF for t in out]],
                    np.uint32)


def lcp_of_bits(hi, lo, thr):
    """LCP values for the 64-bit uniforms ``(hi << 32) | lo`` (uint32
    words): ``#{k : u < T_k}`` for the thresholds ``thr`` of
    :func:`thresholds` (they decrease, so this inverts the law)."""
    import jax.numpy as jnp

    t_hi, t_lo = thr[0][None, :], thr[1][None, :]
    h = hi[:, None]
    below = (h < t_hi) | ((h == t_hi) & (lo[:, None] < t_lo))
    return jnp.sum(below, axis=1, dtype=jnp.int32).astype(jnp.float32)


def device_lcp(seed: int, n: int, mesh, axis: str, block: int = 1 << 20):
    """``n`` LCP values as float32, sharded over ``axis`` of ``mesh``.

    Each device writes its own segment of ``n / S`` entries block by
    block into its output; block ``i`` of segment ``j`` depends only on
    ``(seed, j, i)``.
    """
    import jax.numpy as jnp

    return lcp_program(n, mesh, axis, block)(
        jnp.asarray(seed_words(seed, STREAM)))


def lcp_program(n: int, mesh, axis: str, block: int = 1 << 20):
    """The jitted program of :func:`device_lcp`, taking the seed's two
    uint32 key words."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    s = mesh.shape[axis]
    if n % s:
        raise ValueError(f"n={n} does not split into {s} equal segments")
    seg = n // s
    block = min(block, seg)
    full, tail = divmod(seg, block)
    thr = jnp.asarray(thresholds(n))

    def local(words):
        key = jax.random.fold_in(jax.random.wrap_key_data(words),
                                 jax.lax.axis_index(axis))

        def piece(i, size):
            bits = jax.random.bits(jax.random.fold_in(key, i), (2, block),
                                   jnp.uint32)
            return lcp_of_bits(bits[0], bits[1], thr)[:size]

        def body(i, buf):
            return jax.lax.dynamic_update_slice(buf, piece(i, block),
                                                (i * block,))

        buf = jax.lax.fori_loop(0, full, body, jnp.zeros((seg,), jnp.float32))
        if tail:
            buf = jax.lax.dynamic_update_slice(buf, piece(full, tail),
                                               (full * block,))
        return buf

    gen = shard_map(local, mesh=mesh, in_specs=P(), out_specs=P(axis),
                    check_vma=False)
    return jax.jit(gen)


def host_segments(x) -> List[np.ndarray]:
    """The segments of a sharded 1-D array on the host, in order, one
    device-to-host copy each, made side by side."""
    by_start = {}
    for sh in x.addressable_shards:
        start = sh.index[0].start or 0
        if start not in by_start:
            by_start[start] = sh
    shards = [by_start[k] for k in sorted(by_start)]
    with ThreadPoolExecutor(len(shards)) as pool:
        return list(pool.map(lambda sh: np.asarray(sh.data), shards))


def rank_pairs(n: int, m: int, gen: np.random.Generator):
    """``m`` queries ``l < r``: two independent uniform ranks in [0, n),
    sorted (a pair that ties is drawn again).  int64 bounds."""
    a = gen.integers(0, n, m)
    b = gen.integers(0, n, m)
    tie = np.flatnonzero(a == b)
    while tie.size:
        b[tie] = gen.integers(0, n, tie.size)
        tie = tie[a[tie] == b[tie]]
    return np.minimum(a, b), np.maximum(a, b)
