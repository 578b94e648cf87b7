"""Batched hierarchical RMQ answering (paper §4.2–§4.4), pure-JAX reference.

This mirrors the paper's Listing 2 with JAX-compatible control flow: the
level walk is unrolled over the *static* number of levels from the
``HierarchyPlan``; the data-dependent early exit (``r - l <= 2c``) becomes a
``done`` predicate that masks later levels to no-ops.

Scans are fixed-size masked windows:

* boundary scans (levels we pass through) read one aligned ``c``-wide window
  on each side — exactly the paper's "random but cache-aligned chunk
  accesses";
* the stop-level scan reads a ``2c`` window starting at ``l`` (the paper
  guarantees ``r - l <= 2c`` there);
* the top level is scanned in full (``<= c*t`` entries), masked to
  ``[l, r)``.

This module is the *oracle* for the Pallas query kernel
(``repro.kernels.rmq_scan``) and is itself fast enough to serve as the
production path on non-TPU backends.

Query convention: ``(l, r)`` are **inclusive** bounds, ``0 <= l <= r < n``,
matching the paper's problem statement (§2.1).
"""

from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitpack
from repro.core.hierarchy import Hierarchy, pos_dtype_for
from repro.core.plan import HierarchyPlan

__all__ = [
    "rmq_value",
    "rmq_index",
    "rmq_value_batch",
    "rmq_index_batch",
    "check_query_args",
    "check_host_bounds",
]

from repro.core.constants import POS_INF_I32 as _POS_INF_I32  # noqa: E402


def _debug_checks_enabled() -> bool:
    return os.environ.get("REPRO_RMQ_DEBUG", "0") not in ("", "0")


def check_query_args(ls, rs, n: int, debug: bool = None):
    """Validate a query batch against the convention ``0 <= l <= r < n``.

    Dtype and shape problems are always rejected (they are cheap, static
    checks).  The batched *value* check materializes the arrays, so it
    only runs in debug mode — ``debug=True`` or env ``REPRO_RMQ_DEBUG=1``
    — and only on concrete (non-traced) inputs.  Returns ``(ls, rs)`` as
    arrays.
    """
    ls, rs = jnp.asarray(ls), jnp.asarray(rs)
    _check_bounds(ls, rs, n, debug)
    return ls, rs


def check_host_bounds(ls, rs, n: int, debug: bool = None):
    """:func:`check_query_args` for bounds that stay on the host: the same
    checks and errors, with no copy to the device.  Returns ``(ls, rs)``
    as numpy arrays."""
    ls, rs = np.asarray(ls), np.asarray(rs)
    _check_bounds(ls, rs, n, debug)
    return ls, rs


def _check_bounds(ls, rs, n: int, debug):
    for name, a in (("ls", ls), ("rs", rs)):
        if not jnp.issubdtype(a.dtype, jnp.integer):
            raise TypeError(
                f"query bounds {name} must be integers, got {a.dtype}"
            )
    if ls.shape != rs.shape:
        raise ValueError(
            f"query bounds must match in shape, got {ls.shape} vs {rs.shape}"
        )
    if debug is None:
        debug = _debug_checks_enabled()
    if debug and not (
        isinstance(ls, jax.core.Tracer) or isinstance(rs, jax.core.Tracer)
    ):
        l_np, r_np = np.asarray(ls), np.asarray(rs)
        bad = (l_np < 0) | (l_np > r_np) | (r_np >= n)
        if bad.any():
            i = int(np.argmax(bad))
            raise ValueError(
                f"query {i} = ({l_np.flat[i]}, {r_np.flat[i]}) violates "
                f"0 <= l <= r < n with n={n}"
            )


def _merge(m, p, m2, p2):
    """Combine two (min-value, leftmost-position) candidates."""
    take2 = (m2 < m) | ((m2 == m) & (p2 < p))
    return jnp.where(take2, m2, m), jnp.where(take2, p2, p)


# Largest array whose (rows, row) view may cost a copy (4 MB of float32).
_ROW_VIEW_COPY = 1 << 20


def row_view_rows(n: int, window: int, row: int,
                  aligned: bool = False) -> int:
    """Rows :func:`_masked_window_scan` gathers to read ``window`` entries
    of an ``n``-entry array, or 0 where it reads by ``dynamic_slice``.

    The ``(rows, row)`` view is used where it is free (whole ``(8,
    row)`` tiles) or cheap (at most ``_ROW_VIEW_COPY`` entries).  An
    unaligned start needs one row more than the window spans; an
    ``aligned`` one (``start`` and ``window`` multiples of ``row``)
    needs none.
    """
    window = min(window, n)
    if aligned and window % row:
        raise ValueError(
            f"an aligned read needs a whole number of rows, got window "
            f"{window} for rows of {row}")
    k = -(-window // row) + (0 if aligned else 1)
    if (n % row == 0 and n // row >= k
            and (n % (8 * row) == 0 or n <= _ROW_VIEW_COPY)):
        return k
    return 0


def _masked_window_scan(
    arr, pos_arr, start, lo, hi, window, track_pos, row,
    coord=jnp.int32, exact_src=None, aligned=False,
):
    """min over ``arr[i]`` for ``i in [lo, hi) ∩ [start, start+window)``.

    ``start`` is clamped so that the window lies inside ``arr``; masking
    uses the *absolute* indices actually read, so clamping and reading
    more than the window are both safe.  Where :func:`row_view_rows`
    allows it, the window is read by a gather of the ``row``-entry rows
    that cover it, one per window for the whole batch under the batch's
    ``vmap``; else by a ``dynamic_slice``, which lowers on TPU to a loop
    over the queries (~13× slower on v5e, but an index gather is slower
    still).  ``aligned`` is the caller's promise that ``start`` and
    ``window`` are multiples of ``row``: the gather then reads just the
    window's rows, not one more.
    Returns ``(min_value, min_position)`` with +inf / INTmax identities;
    positions (and the scan coordinates) use dtype ``coord`` — int64 for
    capacities past 2^31 under x64.

    ``exact_src`` (the level-0 array) switches on bf16-summary recovery:
    the window min over ``arr`` is then quantized, so every candidate
    tied at the quantized min is re-read *exactly* from level 0 through
    its stored position, and the exact values pick the winner — the true
    minimum always survives into the tied set because bf16 rounding is
    monotone.
    """
    n = arr.shape[0]
    window = min(window, n)
    start = jnp.clip(start, 0, max(n - window, 0)).astype(coord)
    k = row_view_rows(n, window, row, aligned)
    if k:
        r0 = jnp.clip(start // row, 0, n // row - k)
        idx = r0 * row + jnp.arange(k * row, dtype=coord)
        rows = r0 + jnp.arange(k, dtype=coord)

        def read(a):
            return a.reshape(-1, row).at[rows].get(
                mode="promise_in_bounds").reshape(-1)

        inside = (idx >= start) & (idx < start + window)
    else:
        idx = start + jnp.arange(window, dtype=coord)

        def read(a):
            return jax.lax.dynamic_slice(a, (start,), (window,))

        inside = True
    vals = read(arr)
    mask = inside & (idx >= lo) & (idx < hi)
    ident = jnp.array(jnp.iinfo(coord).max, dtype=coord)
    if exact_src is None:
        inf = jnp.array(jnp.inf, dtype=arr.dtype)
        masked = jnp.where(mask, vals, inf)
        m = jnp.min(masked)
        if track_pos:
            if pos_arr is None:
                pos = idx  # level 0: position is the index itself
            else:
                pos = read(pos_arr)
            cand = jnp.where(mask & (masked == m), pos, ident)
            p = jnp.min(cand).astype(coord)
        else:
            p = ident
        return m, p
    masked = jnp.where(mask, vals, jnp.array(jnp.inf, dtype=arr.dtype))
    mq = jnp.min(masked)  # quantized (bf16) window minimum
    pos = read(pos_arr)
    tied = mask & (masked == mq)
    safe = jnp.clip(pos, 0, exact_src.shape[0] - 1)
    exact_inf = jnp.array(jnp.inf, dtype=exact_src.dtype)
    ex = jnp.where(tied, exact_src[safe], exact_inf)
    m = jnp.min(ex)
    cand = jnp.where(tied & (ex == m), pos, ident)
    p = jnp.min(cand).astype(coord)
    return m, p


def _rmq_single(
    plan: HierarchyPlan,
    base: jax.Array,
    upper: jax.Array,
    upper_pos,
    l: jax.Array,
    r: jax.Array,
    track_pos: bool,
) -> Tuple[jax.Array, jax.Array]:
    """Answer a single RMQ; vmapped over the batch by the public API."""
    c = plan.c
    # All scan coordinates, merge identities, and returned positions use
    # the plan's position dtype — int32 everywhere except capacities past
    # 2^31 under x64 (int32 plans are byte-identical to the historical
    # hardcoded-int32 walk).
    coord = pos_dtype_for(plan.capacity, strict=False)
    ident = jnp.array(jnp.iinfo(coord).max, dtype=coord)
    # bf16 summaries: upper-level scans re-compare their quantized-tied
    # candidates against level 0 so results stay exact (positions are
    # required and tracked internally even for value-only queries).
    exact = upper.dtype != base.dtype and upper_pos is not None
    track = track_pos or exact
    inf = jnp.array(jnp.inf, dtype=base.dtype)
    m = inf
    p = ident
    l = l.astype(coord)
    r = (r + 1).astype(coord)  # make exclusive, as in Listing 2
    done = jnp.array(False)

    def level_arrays(level: int):
        if level == 0:
            return base, None, plan.n
        off, padded = plan.level_slice(level)
        vals = jax.lax.slice(upper, (off,), (off + padded,))
        pos = (
            None
            if upper_pos is None
            else jax.lax.slice(upper_pos, (off,), (off + padded,))
        )
        return vals, pos, plan.level_lens[level]

    for level in range(plan.num_levels):
        arr, pos_arr, _ = level_arrays(level)
        is_last = level == plan.num_levels - 1
        ex_src = base if (exact and level > 0) else None

        if is_last:
            stop_here = ~done
        else:
            stop_here = (~done) & ((r - l) <= 2 * c)

        # --- stop-level scan -------------------------------------------
        if is_last:
            # Scan the whole (small) top level, masked to [l, r).
            idx = jnp.arange(arr.shape[0], dtype=coord)
            mask = stop_here & (idx >= l) & (idx < r)
            masked = jnp.where(mask, arr, jnp.array(jnp.inf, arr.dtype))
            smq = jnp.min(masked)
            if ex_src is not None:
                tied = mask & (masked == smq)
                safe = jnp.clip(pos_arr, 0, ex_src.shape[0] - 1)
                ex = jnp.where(tied, ex_src[safe], inf)
                sm = jnp.min(ex)
                cand = jnp.where(tied & (ex == sm), pos_arr, ident)
                sp = jnp.min(cand).astype(coord)
            else:
                sm = smq
                if track:
                    if pos_arr is None:
                        pos = idx
                    else:
                        pos = pos_arr
                    cand = jnp.where(mask & (masked == sm), pos, ident)
                    sp = jnp.min(cand).astype(coord)
                else:
                    sp = ident
        else:
            # r - l <= 2c here, so a 2c window starting at l covers [l, r).
            sm, sp = _masked_window_scan(
                arr, pos_arr, l, l, jnp.where(stop_here, r, l), 2 * c,
                track, coord=coord, exact_src=ex_src, row=c,
            )
        m, p = _merge(m, p, jnp.where(stop_here, sm, inf),
                      jnp.where(stop_here, sp, ident))
        done = done | stop_here

        if is_last:
            break

        # --- boundary scans + ascend ------------------------------------
        advance = ~done
        next_l = ((l + c - 1) // c) * c  # next multiple of c >= l
        prev_r = (r // c) * c            # largest multiple of c <= r

        # Left partial chunk: [l, next_l) ⊂ [next_l - c, next_l).
        lm, lp = _masked_window_scan(
            arr, pos_arr, next_l - c, l, jnp.where(advance, next_l, l),
            c, track, coord=coord, exact_src=ex_src, row=c,
        )
        # Right partial chunk: [prev_r, r) ⊂ [prev_r, prev_r + c).
        rm, rp = _masked_window_scan(
            arr, pos_arr, prev_r, jnp.where(advance, prev_r, r), r,
            c, track, coord=coord, exact_src=ex_src, row=c,
        )
        m, p = _merge(m, p, jnp.where(advance, lm, inf),
                      jnp.where(advance, lp, ident))
        m, p = _merge(m, p, jnp.where(advance, rm, inf),
                      jnp.where(advance, rp, ident))

        l = jnp.where(advance, next_l // c, l)
        r = jnp.where(advance, prev_r // c, r)

    return m, p


def _rmq_batch_impl(plan, base, upper, upper_pos, ls, rs, track_pos: bool):
    """Un-jitted batch walk body (reused inside other jitted lowerings).

    Packed position planes are unpacked once per batch, outside the
    per-query vmap, so the transient absolute plane is shared by every
    lane of the launch.
    """
    upper_pos = bitpack.resolve_positions(upper_pos, plan)
    fn = functools.partial(_rmq_single, plan, base, upper, upper_pos,
                           track_pos=track_pos)
    return jax.vmap(lambda l, r: fn(l=l, r=r))(ls, rs)


@functools.partial(jax.jit, static_argnames=("plan", "track_pos"))
def _rmq_batch(plan, base, upper, upper_pos, ls, rs, track_pos: bool = True):
    return _rmq_batch_impl(plan, base, upper, upper_pos, ls, rs, track_pos)


def rmq_value_batch(h: Hierarchy, ls: jax.Array, rs: jax.Array) -> jax.Array:
    """``RMQ_value`` for a batch of inclusive ranges."""
    # bf16 summaries need the position plane even for value queries (the
    # exact re-compare reads level 0 through stored positions).
    pos = h.upper_pos if h.upper.dtype != h.base.dtype else None
    m, _ = _rmq_batch(h.plan, h.base, h.upper, pos, ls, rs, track_pos=False)
    return m


def rmq_index_batch(h: Hierarchy, ls: jax.Array, rs: jax.Array) -> jax.Array:
    """``RMQ_index`` (leftmost minimum position) for a batch of ranges."""
    if not h.with_positions:
        raise ValueError(
            "hierarchy was built without positions; "
            "use build_hierarchy(..., with_positions=True)"
        )
    _, p = _rmq_batch(h.plan, h.base, h.upper, h.upper_pos, ls, rs,
                      track_pos=True)
    return p


def rmq_value(h: Hierarchy, l, r) -> jax.Array:
    """Single-query convenience wrapper."""
    return rmq_value_batch(h, jnp.asarray([l]), jnp.asarray([r]))[0]


def rmq_index(h: Hierarchy, l, r) -> jax.Array:
    return rmq_index_batch(h, jnp.asarray([l]), jnp.asarray([r]))[0]
