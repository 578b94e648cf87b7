"""Jitted wrapper: build a full Hierarchy in ONE fused Pallas launch.

Produces a ``Hierarchy`` pytree bit-identical to
``repro.core.hierarchy.build_hierarchy`` (the oracle) — values *and*
leftmost-tie positions, padding included — with exactly one kernel launch
per build (``repro.kernels.profiling`` makes that assertable).  The whole
entry point is end-to-end jitted: padding, the launch, and the pytree
assembly compile into one XLA program, so nothing bounces through the
host between levels.

Falls back to interpret mode off-TPU, like every kernel package here.
The resident upper buffer caps the geometries the compiled kernel admits
(:func:`vmem_bytes` against :data:`VMEM_BUDGET_BYTES`); past it the
build refuses with a ``ValueError``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.constants import PAD_POS
from repro.core.hierarchy import (
    Hierarchy,
    _check_compact_build,
    _pad_to,
    finalize_compact,
    pos_dtype_for,
)
from repro.core.plan import HierarchyPlan, make_plan
from repro.kernels import common, profiling
from repro.kernels.hierarchy_fused import kernel as K

__all__ = [
    "build_hierarchy_fused",
    "build_hierarchy_streamed",
    "VMEM_BUDGET_BYTES",
    "vmem_bytes",
]

def _geometry(plan: HierarchyPlan):
    """``(rows_out, chunks, slack)`` of the fused launch.

    Level 0 goes in as ``chunks`` rows of ``c`` (the level-1 extent,
    rounded up to whole grid steps); the output buffer carries ``slack``
    rows past the upper buffer for the grid's all-inf overhang and the
    folds' last partial slabs.
    """
    c = plan.c
    rows1 = plan.padded_lens[0] // c
    rows_out = min(rows1, K.DEFAULT_ROWS_OUT)
    chunks = -(-plan.padded_lens[0] // (rows_out * c)) * (rows_out * c)
    slack = max(c, chunks // c - plan.upper_size // c)
    return rows_out, chunks, slack


# Largest VMEM footprint (resident upper planes + the double-buffered
# level-0 tile) the v5e compiler accepts for the fused build, measured by
# compiling for a described chip: 56.25 MiB value-only, 75 MiB with the
# position plane.  At c=128 every capacity the int32 guard admits fits.
VMEM_BUDGET_BYTES = {False: 225 * 2**18, True: 75 * 2**20}


def vmem_bytes(plan: HierarchyPlan, with_positions: bool,
               itemsize: int = 4) -> int:
    """VMEM the fused launch allocates: the resident upper buffer (and
    its position plane) plus the double-buffered level-0 tile."""
    rows_out, _, slack = _geometry(plan)
    plane = common.tiled_bytes(
        plan.upper_size // plan.c + slack, plan.c, itemsize)
    tile = common.tiled_bytes(rows_out * plan.c, plan.c, itemsize)
    return (2 if with_positions else 1) * plane + 2 * tile


@functools.partial(
    jax.jit, static_argnames=("plan", "with_positions", "interpret")
)
def _fused_jit(x, plan, with_positions, interpret):
    c = plan.c
    inf = jnp.array(jnp.inf, x.dtype)
    base = _pad_to(x, plan.capacity, inf)
    rows_out, chunks, slack = _geometry(plan)
    # Tile-align level 0 for the kernel's block DMA; the over-pad is
    # all-inf chunks, which reduce to the same +inf / PAD_POS padding the
    # oracle stores.
    xin = _pad_to(base, chunks * c, inf).reshape(chunks, c)
    profiling.record_launch(
        "hierarchy_fused",
        lowering="pallas",
        levels=plan.num_levels,
        grid=int(chunks // (rows_out * c)),
        with_positions=bool(with_positions),
        operand_bytes=profiling.operand_bytes(xin),
    )
    upper, upper_pos = K.fused_build(
        xin, plan, rows_out, slack,
        pos_dtype=pos_dtype_for(plan.capacity) if with_positions else None,
        interpret=interpret,
    )
    upper = upper.reshape(-1)[: plan.upper_size]
    if with_positions:
        upper_pos = upper_pos.reshape(-1)[: plan.upper_size]
    return Hierarchy(base=base, upper=upper, upper_pos=upper_pos, plan=plan)


@functools.partial(jax.jit, static_argnames=("plan", "with_positions"))
def _single_level_jit(x, plan, with_positions):
    # n <= c*t: the plan is a pure scan, no upper levels and no launch.
    base = _pad_to(x, plan.capacity, jnp.array(jnp.inf, x.dtype))
    pos_dtype = pos_dtype_for(plan.capacity) if with_positions else None
    upper = jnp.full((0,), jnp.inf, x.dtype)
    upper_pos = (
        jnp.full((0,), 0, pos_dtype) if with_positions else None
    )
    return Hierarchy(base=base, upper=upper, upper_pos=upper_pos, plan=plan)


def build_hierarchy_fused(
    x: jax.Array,
    plan: HierarchyPlan,
    with_positions: bool = False,
    interpret: bool | None = None,
) -> Hierarchy:
    """Single-launch fused build (paper §4.1, all levels in one pass)."""
    from repro.core.protocol import check_capacity_limit

    interpret = common.resolve_interpret(interpret)
    _check_compact_build(plan, with_positions, jnp.asarray(x).dtype)
    if plan.num_levels == 1:
        return finalize_compact(_single_level_jit(x, plan, with_positions))
    if with_positions:
        # The kernel synthesizes absolute level-0 positions in int32 over
        # the tile-aligned input extent; x64 does not help here — route
        # larger arrays through backend='jax' or the streamed build.
        check_capacity_limit(plan.padded_lens[0] * plan.c)
    x = jnp.asarray(x)
    if not interpret:
        common.check_vmem(
            "the fused build",
            vmem_bytes(plan, with_positions, jnp.dtype(x.dtype).itemsize),
            VMEM_BUDGET_BYTES[bool(with_positions)],
            remedy="use the per-level backend='pallas' for this geometry",
        )
    return finalize_compact(
        _fused_jit(x, plan, with_positions, interpret)
    )


# --------------------------------------------------------------------------
# Out-of-core construction: stream fixed-size segments through the fused
# kernel, then finish the (tiny) levels >= 2 from the assembled level 1.
# --------------------------------------------------------------------------


def _segment_plan(segment_size: int, c: int) -> HierarchyPlan:
    """A two-level plan covering exactly one ``segment_size`` slab.

    ``t = ceil(S / c^2)`` makes level 1 (``S/c`` entries) the top level:
    each fused launch reduces its slab to chunk minima and stops, so the
    slab's VMEM footprint is ``S/c`` entries — independent of the full
    array's size.
    """
    t = max(1, -(-segment_size // (c * c)))
    seg = make_plan(segment_size, c=c, t=t)
    if seg.num_levels != 2 or seg.level_lens[1] * c != segment_size:
        raise AssertionError(
            f"segment plan for S={segment_size}, c={c} is not a clean "
            f"two-level reduction (levels={seg.num_levels})"
        )
    return seg


def _read_segment(source, start: int, stop: int):
    """One slab of input values: callable ``source(start, stop)`` or any
    sliceable array-like (memmap, numpy, jax array)."""
    if callable(source):
        return jnp.asarray(source(start, stop))
    return jnp.asarray(source[start:stop])


@functools.partial(jax.jit, static_argnames=("plan", "with_positions"))
def _finish_from_level1(base, l1_vals, l1_pos, plan, with_positions):
    """Replay the oracle's reduction loop from level 2 upward.

    ``l1_vals``/``l1_pos`` are level 1's live entries (``level_lens[1]``
    of them, positions absolute) exactly as the oracle would have stored
    them; everything above is bit-for-bit the
    :func:`repro.core.hierarchy.build_hierarchy` loop, so the streamed
    build inherits the oracle's full parity contract (padding, leftmost
    ties, compact finalization).
    """
    c = plan.c
    inf = jnp.array(jnp.inf, base.dtype)
    upper = jnp.full((plan.upper_size,), jnp.inf, dtype=base.dtype)
    upper = jax.lax.dynamic_update_slice(upper, l1_vals, (plan.offsets[0],))
    if with_positions:
        pos_dtype = l1_pos.dtype
        pad = jnp.array(PAD_POS, pos_dtype)
        upper_pos = jnp.full((plan.upper_size,), PAD_POS, dtype=pos_dtype)
        upper_pos = jax.lax.dynamic_update_slice(
            upper_pos, l1_pos, (plan.offsets[0],)
        )
    else:
        upper_pos = None
    cur_v, cur_p = l1_vals, l1_pos
    for k in range(2, plan.num_levels):
        want = plan.level_lens[k] * c
        v = _pad_to(cur_v, want, inf).reshape(-1, c)
        idx = jnp.argmin(v, axis=1)
        nxt_v = jnp.take_along_axis(v, idx[:, None], axis=1)[:, 0]
        off = plan.offsets[k - 1]
        upper = jax.lax.dynamic_update_slice(upper, nxt_v, (off,))
        if with_positions:
            p = _pad_to(cur_p, want, pad).reshape(-1, c)
            nxt_p = jnp.take_along_axis(p, idx[:, None], axis=1)[:, 0]
            upper_pos = jax.lax.dynamic_update_slice(
                upper_pos, nxt_p, (off,)
            )
            cur_p = nxt_p
        cur_v = nxt_v
    return finalize_compact(
        Hierarchy(base=base, upper=upper, upper_pos=upper_pos, plan=plan)
    )


def build_hierarchy_streamed(
    source,
    plan: HierarchyPlan,
    with_positions: bool = False,
    segment_size: int | None = None,
    interpret: bool | None = None,
) -> Hierarchy:
    """Out-of-core fused construction: one slab at a time.

    The monolithic fused build keeps the whole upper buffer VMEM-resident,
    which caps the capacities it admits.  This path streams fixed-size
    segments (``segment_size`` elements, a multiple of ``c``) through the
    fused kernel — each launch's working set is one slab plus its ``S/c``
    chunk minima — assembles the global level 1, and finishes the
    geometrically smaller levels >= 2 with the pure-JAX oracle loop.

    ``source`` is either a sliceable array-like (numpy memmap, array) or
    a callable ``source(start, stop) -> values`` producing slabs on
    demand, so the input never has to exist as one device array during
    level-1 construction.  Under x64, position-tracking builds past
    ``2**31`` elements get an int64 coordinate plane; without x64 they
    raise (the strict ``pos_dtype_for`` guard).

    The result is bit-identical to ``build_hierarchy(x, plan, ...)`` —
    values, leftmost-tie positions, padding, and any compact layout
    (``packed_pos`` / bf16 summaries) the plan selects.
    """
    c = plan.c
    cap = plan.capacity
    n = plan.n
    if segment_size is None:
        segment_size = min(c * 4096, -(-cap // c) * c)
        segment_size = max(segment_size, 2 * c)
    if segment_size % c != 0 or segment_size < 2 * c:
        raise ValueError(
            f"segment_size must be a multiple of c={c} and >= {2 * c}, "
            f"got {segment_size}"
        )
    probe = _read_segment(source, 0, min(n, segment_size))
    _check_compact_build(plan, with_positions, probe.dtype)
    if plan.num_levels == 1:
        # Pure-scan plans have no level 1 to assemble; the monolithic
        # path is already out-of-core-trivial.
        full = probe if probe.shape[0] >= n else _read_segment(source, 0, n)
        return build_hierarchy_fused(
            full, plan, with_positions, interpret=interpret,
        )
    # Strict: raises without x64 past 2**31 instead of wrapping silently.
    coord = pos_dtype_for(cap) if with_positions else None
    seg_plan = _segment_plan(segment_size, c)
    m_seg = segment_size // c
    l1_len = plan.level_lens[1]
    inf = jnp.array(jnp.inf, probe.dtype)

    nseg = -(-cap // segment_size)
    base_parts, v_parts, p_parts = [], [], []
    for i in range(nseg):
        s0 = i * segment_size
        stop = min(s0 + segment_size, n)
        if i == 0:
            seg = probe
        elif s0 < n:
            seg = _read_segment(source, s0, stop)
        else:
            seg = jnp.full((0,), jnp.inf, probe.dtype)
        seg = _pad_to(seg.astype(probe.dtype), segment_size, inf)
        h_seg = build_hierarchy_fused(
            seg, seg_plan, with_positions=with_positions,
            interpret=interpret,
        )
        base_parts.append(h_seg.base)
        v_parts.append(h_seg.upper[:m_seg])
        if with_positions:
            # Segment positions are slab-local int32; globalize in the
            # coordinate dtype BEFORE offsetting (no int32 wrap).
            p_parts.append(h_seg.upper_pos[:m_seg].astype(coord) + s0)

    base = jnp.concatenate(base_parts)[:cap]
    l1_vals = jnp.concatenate(v_parts)[:l1_len]
    l1_pos = (
        jnp.concatenate(p_parts)[:l1_len] if with_positions else None
    )
    return _finish_from_level1(base, l1_vals, l1_pos, plan, with_positions)
