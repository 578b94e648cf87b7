"""Jitted wrappers for the fused single-launch query path.

One call = one device dispatch for the *entire* mixed batch, every span
class, both output planes:

* **TPU** — the ``kernel.py`` ``pallas_call`` (offsets via scalar
  prefetch, VMEM-resident upper buffer, double-buffered level-0 DMA).
* **elsewhere** — a single end-to-end-jitted jnp program realizing the
  same contract: the branch-free walk for levels ``0..L-2`` plus a
  sparse-table top *built inside the program* from the hierarchy's own
  top level.  Building the (<= c·t entry) table per batch is the CPU
  analogue of the kernel keeping the top VMEM-resident: its cost
  amortizes over the batch and every top lookup becomes O(1) — which is
  what keeps fused long-span throughput at (or past) the routed engine's
  hybrid path without any host-side class split.  Results are
  bit-identical to the walk (the hybrid algebra's parity is part of the
  engine contract).

Launch accounting: both lowerings call
:func:`repro.kernels.profiling.record_launch` (``"rmq_fused"``) from
inside their traced bodies — one recorded launch per batch is the
assertable contract, regardless of lowering (on TPU it is literally one
``pallas_call``).  Degenerate-but-valid geometries (single-level plans,
``capacity < c``) run the jnp program on every backend: they have no
multi-level hierarchy for the kernel to exploit, but the one-dispatch
contract still holds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import bitpack
from repro.core.baselines import SparseTable
from repro.core.hierarchy import Hierarchy
from repro.core.hybrid import _hybrid_batch
from repro.core.plan import HierarchyPlan
from repro.core.query import _rmq_batch_impl
from repro.kernels import common, profiling
from repro.kernels.rmq_fused import kernel as K

__all__ = [
    "rmq_fused_batch",
    "rmq_fused_value_batch",
    "rmq_fused_index_batch",
]


def _kernel_applicable(plan: HierarchyPlan) -> bool:
    return plan.num_levels >= 2 and plan.capacity >= plan.c


@functools.partial(jax.jit, static_argnames=("plan", "track_pos"))
def _fused_jnp(base, upper, upper_pos, ls, rs, plan, track_pos):
    """The one-dispatch jnp lowering (walk + in-program sparse top)."""
    profiling.record_launch(
        "rmq_fused",
        lowering="jnp",
        queries=int(ls.shape[0]),
        levels=plan.num_levels,
        track_pos=bool(track_pos),
        operand_bytes=profiling.operand_bytes(
            base, upper, upper_pos, ls, rs),
    )
    if upper.dtype != base.dtype:
        # bf16 summaries: the hybrid algebra's sparse top would compare
        # quantized values, so the one dispatch is the exact-recovery
        # walk instead — same single-launch contract, exact results.
        return _rmq_batch_impl(plan, base, upper, upper_pos, ls, rs,
                               track_pos)
    upper_pos = bitpack.resolve_positions(upper_pos, plan)
    if plan.num_levels == 1:
        top = base  # the plan is a pure scan; the top level IS level 0
        top_pos = (
            jnp.arange(base.shape[0], dtype=jnp.int32)
            if track_pos
            else None
        )
    else:
        off, _ = plan.level_slice(plan.num_levels - 1)
        top = jax.lax.slice(upper, (off,), (off + plan.top_len,))
        top_pos = (
            jax.lax.slice(upper_pos, (off,), (off + plan.top_len,))
            if track_pos
            else None
        )
    tbl = SparseTable.build(top, positions=top_pos)
    return _hybrid_batch(
        plan, base, upper, upper_pos if track_pos else None,
        tbl.table, tbl.pos, ls, rs, track_pos,
    )


@functools.partial(
    jax.jit, static_argnames=("plan", "qb", "track_pos", "interpret")
)
def _run_kernel(base, upper, upper_pos, ls, rs, plan, qb, track_pos,
                interpret):
    m = ls.shape[0]
    qb, m_pad = common.query_grid(m, qb, interpret)
    profiling.record_launch(
        "rmq_fused",
        lowering="pallas",
        queries=int(m),
        grid=int(m_pad // qb),
        levels=plan.num_levels,
        track_pos=bool(track_pos),
        operand_bytes=profiling.operand_bytes(
            base, upper, upper_pos, ls, rs),
    )
    if m_pad != m:
        ls = jnp.pad(ls, (0, m_pad - m))
        rs = jnp.pad(rs, (0, m_pad - m))
    # Packed planes unpack to absolute positions inside this same
    # program; the kernel always consumes the classic (rows, c) layout.
    upper_pos = bitpack.resolve_positions(upper_pos, plan)
    upper2d = upper.reshape(-1, plan.c)
    upos2d = upper_pos.reshape(-1, plan.c) if track_pos else None
    offs = jnp.asarray(plan.offsets, jnp.int32)
    vals, pos = K.rmq_fused_pallas(
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


def rmq_fused_batch(
    h: Hierarchy,
    ls: jax.Array,
    rs: jax.Array,
    track_pos: bool = False,
    qb: int = K.DEFAULT_QUERY_BLOCK,
    interpret: bool | None = None,
):
    """``(values, positions)`` for the whole batch, one device dispatch.

    ``positions`` is ``None`` unless ``track_pos`` — with it, both
    planes come out of the same launch, so a batch mixing value and
    index ops pays one dispatch total.  ``interpret=None`` picks the
    production lowering (kernel on TPU, the jnp program elsewhere);
    ``interpret=True`` forces the kernel in interpreter mode (the
    correctness tool the test suite uses off-TPU).
    """
    ls = jnp.asarray(ls, jnp.int32)
    rs = jnp.asarray(rs, jnp.int32)
    if track_pos and not h.with_positions:
        raise ValueError(
            "hierarchy was built without positions; "
            "use build_hierarchy(..., with_positions=True)"
        )
    plan = h.plan
    quantized = h.upper.dtype != h.base.dtype
    use_kernel = _kernel_applicable(plan) and not quantized and (
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
    # bf16 summaries need the position plane even for value-only batches
    # (exact recovery reads level 0 through stored positions).
    pos_plane = h.upper_pos if (track_pos or quantized) else None
    return _fused_jnp(
        h.base, h.upper, pos_plane,
        ls, rs, plan, track_pos,
    )


def rmq_fused_value_batch(
    h: Hierarchy, ls, rs, qb: int = K.DEFAULT_QUERY_BLOCK,
    interpret: bool | None = None,
) -> jax.Array:
    """Batched ``RMQ_value`` through the fused single-launch path."""
    vals, _ = rmq_fused_batch(
        h, ls, rs, track_pos=False, qb=qb, interpret=interpret
    )
    return vals


def rmq_fused_index_batch(
    h: Hierarchy, ls, rs, qb: int = K.DEFAULT_QUERY_BLOCK,
    interpret: bool | None = None,
) -> jax.Array:
    """Batched ``RMQ_index`` (leftmost minimum) through the fused path."""
    _, pos = rmq_fused_batch(
        h, ls, rs, track_pos=True, qb=qb, interpret=interpret
    )
    return pos
