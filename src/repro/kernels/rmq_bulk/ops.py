"""Jitted wrappers for the bulk (endpoint-sorted, coalesced) query pass.

One call = one device dispatch for an entire bucket of the sorted batch:

* **TPU** — the ``kernel.py`` ``pallas_call``: the fused query kernel
  with *conditional* level-0 DMA, so runs of queries sharing a boundary
  chunk copy it HBM→VMEM once instead of once per query.
* **elsewhere** — a single end-to-end-jitted jnp program realizing the
  same traffic contract: level 0 is read ONCE into a shared per-chunk
  sparse **ladder** (``ladder[j][row, i] = min`` over ``2^j`` in-chunk
  entries), built per dispatch and amortized over the whole bucket.
  Each query's prefix/suffix chunk pieces then cost two O(1) ladder
  lookups instead of two ``c``-wide masked window scans — the CPU
  analogue of the kernel's chunk reuse (every query sharing a chunk
  reads the same ladder rows).  Mid/long interiors are resolved through
  the *existing hierarchy*: the standard boundary walk over levels
  ``1..L-2`` plus an in-program sparse table over the hierarchy's own
  top level (exactly ``rmq_fused``'s top treatment, <= c·t entries).

Results are bit-identical to ``rmq_fused`` — values and leftmost-tie
positions.  The decompositions differ at chunk-aligned endpoints (bulk
covers a boundary chunk via the ladder where the walk covers it at
level 1), but both cover each query's range exactly with exact pieces
and merge lexicographically, so the (min, leftmost-pos) result is
identical; float min has no rounding, making overlap harmless.

Launch accounting: both lowerings call
:func:`repro.kernels.profiling.record_launch` (``"rmq_bulk"``) from
inside their traced bodies — one recorded launch per bucket is the
contract the CI smoke asserts via ``count_launches()``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import bitpack
from repro.core.baselines import SparseTable
from repro.core.constants import POS_INF_I32 as _POS_INF_I32
from repro.core.hierarchy import Hierarchy
from repro.core.plan import HierarchyPlan
from repro.kernels import common, profiling
from repro.kernels.rmq_bulk import kernel as K
from repro.kernels.rmq_scan.ref import _merge, _window

__all__ = [
    "rmq_bulk_batch",
    "rmq_bulk_value_batch",
    "rmq_bulk_index_batch",
]


def _kernel_applicable(plan: HierarchyPlan) -> bool:
    return plan.num_levels >= 2 and plan.capacity >= plan.c


@functools.partial(jax.jit, static_argnames=("plan", "track_pos"))
def _bulk_jnp(base, upper, upper_pos, ls, rs, plan, track_pos):
    """One-dispatch jnp lowering: shared chunk ladder + hierarchy interior."""
    c = plan.c
    rows = -(-plan.capacity // c)
    profiling.record_launch(
        "rmq_bulk",
        lowering="jnp",
        queries=int(ls.shape[0]),
        levels=plan.num_levels,
        chunk=int(c),
        chunk_rows=int(rows),
        track_pos=bool(track_pos),
        operand_bytes=profiling.operand_bytes(
            base, upper, upper_pos, ls, rs),
    )
    num_levels = plan.num_levels
    logc = c.bit_length() - 1  # c is a power of two
    inf = jnp.array(jnp.inf, dtype=base.dtype)
    pos_inf = jnp.int32(_POS_INF_I32)
    # Packed planes unpack to absolute positions inside this same program.
    upper_pos = bitpack.resolve_positions(upper_pos, plan)

    # -- the shared per-chunk sparse ladder (the one level-0 read) --------
    # ladder[j][row, i] = min(chunk_row[i : i + 2^j]) clipped to the chunk
    # (the +inf shift-fill truncates at the chunk edge); positions carry
    # absolute level-0 indices so leftmost ties survive the merges.
    pad = rows * c - plan.capacity
    basep = (
        jnp.concatenate([base, jnp.full((pad,), inf, base.dtype)])
        if pad
        else base
    )
    chunks = basep.reshape(rows, c)
    lad = [chunks]
    plad = None
    if track_pos:
        abs_idx = (
            jax.lax.broadcasted_iota(jnp.int32, (rows, c), 0) * c
            + jax.lax.broadcasted_iota(jnp.int32, (rows, c), 1)
        )
        plad = [abs_idx]
    for j in range(1, logc + 1):
        half = 1 << (j - 1)
        prev = lad[-1]
        shifted = jnp.concatenate(
            [prev[:, half:], jnp.full((rows, half), inf, base.dtype)],
            axis=1,
        )
        if track_pos:
            pprev = plad[-1]
            pshift = jnp.concatenate(
                [pprev[:, half:],
                 jnp.full((rows, half), pos_inf, jnp.int32)],
                axis=1,
            )
            take2 = (shifted < prev) | ((shifted == prev) & (pshift < pprev))
            plad.append(jnp.where(take2, pshift, pprev))
        lad.append(jnp.minimum(prev, shifted))
    ladder = jnp.stack(lad)                     # (logc+1, rows, c)
    pladder = jnp.stack(plad) if track_pos else None

    # -- interior top: the hierarchy's own top level as a sparse table ----
    # (same in-program table as _fused_jnp; for a degenerate single-level
    # plan the "hierarchy top" for chunk-granular interiors is the chunk
    # minima, which the finished ladder already holds in column 0)
    if num_levels == 1:
        top = ladder[logc, :, 0]
        top_pos = pladder[logc, :, 0] if track_pos else None
    else:
        off, _ = plan.level_slice(num_levels - 1)
        top = jax.lax.slice(upper, (off,), (off + plan.top_len,))
        top_pos = (
            jax.lax.slice(upper_pos, (off,), (off + plan.top_len,))
            if track_pos
            else None
        )
    tbl = SparseTable.build(top, positions=top_pos)

    def chunk_lookup(chunk, lo, hi):
        """Exact (min, pos) over absolute ``[lo, hi)`` inside ``chunk``.

        Caller guarantees the range is nonempty and chunk-contained, so
        both pow2 lookups stay fully inside the chunk: two O(1) gathers
        replace a ``c``-wide masked window scan.
        """
        a = lo - chunk * c
        b = hi - 1 - chunk * c
        span = b - a + 1
        k = (31 - jax.lax.clz(span)).astype(jnp.int32)
        i2 = b + 1 - (1 << k.astype(jnp.uint32)).astype(jnp.int32)
        v1 = ladder[k, chunk, a]
        v2 = ladder[k, chunk, i2]
        if track_pos:
            return _merge(v1, pladder[k, chunk, a], v2, pladder[k, chunk, i2])
        return jnp.minimum(v1, v2), pos_inf

    def one(l, r):
        l = l.astype(jnp.int32)
        re = (r + 1).astype(jnp.int32)  # exclusive
        cla = l // c
        clb = (re - 1) // c
        # prefix / suffix pieces (always nonempty; same-chunk queries
        # cover the whole range twice — overlap is exact, so harmless)
        m, p = chunk_lookup(cla, l, jnp.minimum((cla + 1) * c, re))
        m2, p2 = chunk_lookup(clb, jnp.maximum(clb * c, l), re)
        m, p = _merge(m, p, m2, p2)

        # interior chunks [cla+1, clb) at level-1 coordinates, resolved
        # via the existing hierarchy: the boundary walk for levels
        # 1..L-2 (masks empty when the interior is), then the O(1) top
        li = cla + 1
        ri = clb
        for level in range(1, num_levels - 1):
            off, padded = plan.level_slice(level)
            arr = jax.lax.slice(upper, (off,), (off + padded,))
            pos_arr = (
                jax.lax.slice(upper_pos, (off,), (off + padded,))
                if track_pos
                else None
            )
            next_l = ((li + c - 1) // c) * c
            prev_r = (ri // c) * c
            m2, p2 = _window(arr, pos_arr, (li // c) * c, li,
                             jnp.minimum(next_l, ri), c, track_pos)
            m, p = _merge(m, p, m2, p2)
            m2, p2 = _window(arr, pos_arr, prev_r, jnp.maximum(prev_r, li),
                             ri, c, track_pos)
            m, p = _merge(m, p, m2, p2)
            li = (li + c - 1) // c
            ri = ri // c

        # O(1) sparse top over [li, ri) (empty range -> +inf, like hybrid)
        nonempty = ri > li
        rr = jnp.maximum(ri - 1, li)
        span = rr - li + 1
        j = (31 - jax.lax.clz(span)).astype(jnp.int32)
        r2 = rr + 1 - (1 << j.astype(jnp.uint32)).astype(jnp.int32)
        vl = tbl.table[j, li]
        vr = tbl.table[j, r2]
        if track_pos:
            tm, tp = _merge(vl, tbl.pos[j, li], vr, tbl.pos[j, r2])
        else:
            tm, tp = jnp.minimum(vl, vr), pos_inf
        tm = jnp.where(nonempty, tm, inf)
        tp = jnp.where(nonempty, tp, pos_inf)
        return _merge(m, p, tm, tp)

    vals, poss = jax.vmap(one)(ls, rs)
    if track_pos:
        return vals, poss
    return vals, None


@functools.partial(
    jax.jit, static_argnames=("plan", "qb", "track_pos", "interpret")
)
def _run_kernel(base, upper, upper_pos, ls, rs, plan, qb, track_pos,
                interpret):
    m = ls.shape[0]
    qb, m_pad = common.query_grid(m, qb, interpret)
    profiling.record_launch(
        "rmq_bulk",
        lowering="pallas",
        queries=int(m),
        grid=int(m_pad // qb),
        levels=plan.num_levels,
        chunk=int(plan.c),
        track_pos=bool(track_pos),
        operand_bytes=profiling.operand_bytes(
            base, upper, upper_pos, ls, rs),
    )
    if m_pad != m:
        ls = jnp.pad(ls, (0, m_pad - m))
        rs = jnp.pad(rs, (0, m_pad - m))
    upper_pos = bitpack.resolve_positions(upper_pos, plan)
    upper2d = upper.reshape(-1, plan.c)
    upos2d = upper_pos.reshape(-1, plan.c) if track_pos else None
    offs = jnp.asarray(plan.offsets, jnp.int32)
    vals, pos = K.rmq_bulk_pallas(
        common.chunk_rows(base, plan.c),
        upper2d,
        upos2d,
        offs,
        ls.astype(jnp.int32),
        rs.astype(jnp.int32),
        plan,
        qb=qb,
        track_pos=track_pos,
        interpret=interpret,
    )
    if track_pos:
        return vals[:m], pos[:m]
    return vals[:m], None


def rmq_bulk_batch(
    h: Hierarchy,
    ls: jax.Array,
    rs: jax.Array,
    track_pos: bool = False,
    qb: int = K.DEFAULT_QUERY_BLOCK,
    interpret: bool | None = None,
):
    """``(values, positions)`` for one bucket, one device dispatch.

    ``positions`` is ``None`` unless ``track_pos``.  ``interpret=None``
    picks the production lowering (kernel on TPU, the jnp ladder program
    elsewhere); ``interpret=True`` forces the kernel in interpreter mode
    (the correctness tool the test suite uses off-TPU).  Best throughput
    when ``(ls, rs)`` is sorted by ``(chunk(l), chunk(r))`` — the
    ``BulkExecutor`` owns that sort; unsorted input stays correct.
    """
    ls = jnp.asarray(ls, jnp.int32)
    rs = jnp.asarray(rs, jnp.int32)
    if track_pos and not h.with_positions:
        raise ValueError(
            "hierarchy was built without positions; "
            "use build_hierarchy(..., with_positions=True)"
        )
    if h.upper.dtype != h.base.dtype:
        raise ValueError(
            "the bulk path does not support bf16 summaries; route bf16 "
            "indexes through the engine's walk/fused paths instead"
        )
    plan = h.plan
    use_kernel = _kernel_applicable(plan) and (
        bool(interpret) or common.on_tpu()
    )
    if use_kernel:
        itp = False if interpret is None else bool(interpret)
        if not itp:
            common.check_query_vmem(plan, track_pos, h.upper.dtype.itemsize)
        return _run_kernel(
            h.base, h.upper, h.upper_pos if track_pos else None,
            ls, rs, plan, qb, track_pos, itp,
        )
    return _bulk_jnp(
        h.base, h.upper, h.upper_pos if track_pos else None,
        ls, rs, plan, track_pos,
    )


def rmq_bulk_value_batch(
    h: Hierarchy, ls, rs, qb: int = K.DEFAULT_QUERY_BLOCK,
    interpret: bool | None = None,
) -> jax.Array:
    """Batched ``RMQ_value`` through the bulk coalesced path."""
    vals, _ = rmq_bulk_batch(
        h, ls, rs, track_pos=False, qb=qb, interpret=interpret
    )
    return vals


def rmq_bulk_index_batch(
    h: Hierarchy, ls, rs, qb: int = K.DEFAULT_QUERY_BLOCK,
    interpret: bool | None = None,
) -> jax.Array:
    """Batched ``RMQ_index`` (leftmost minimum) through the bulk path."""
    _, pos = rmq_bulk_batch(
        h, ls, rs, track_pos=True, qb=qb, interpret=interpret
    )
    return pos
