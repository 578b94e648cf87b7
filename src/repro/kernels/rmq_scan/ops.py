"""Jitted wrappers for the Pallas RMQ query kernel.

Handles: query-batch padding to the query block, the (rows, c) view of the
upper buffer, backend fallbacks (single-level plans and n < c degenerate
cases use the pure-JAX core path — they have no hierarchy to exploit).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import bitpack
from repro.core.hierarchy import Hierarchy
from repro.core.query import rmq_index_batch, rmq_value_batch
from repro.kernels import common, profiling
from repro.kernels.rmq_scan import kernel as K


def _kernel_applicable(h: Hierarchy) -> bool:
    return h.plan.num_levels >= 2 and h.plan.n >= h.plan.c


@functools.partial(
    jax.jit,
    static_argnames=("plan", "qb", "track_pos", "interpret"),
)
def _run(base, upper, upper_pos, ls, rs, plan, qb, track_pos, interpret):
    m = ls.shape[0]
    qb, m_pad = common.query_grid(m, qb, interpret)
    profiling.record_launch(
        "rmq_scan",
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
    upos2d = (
        upper_pos.reshape(-1, plan.c) if track_pos else None
    )
    vals, pos = K.rmq_query_pallas(
        common.chunk_rows(base, plan.c),
        upper2d,
        upos2d,
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


def rmq_value_batch_pallas(
    h: Hierarchy,
    ls: jax.Array,
    rs: jax.Array,
    qb: int = K.DEFAULT_QUERY_BLOCK,
    interpret: bool | None = None,
) -> jax.Array:
    if not _kernel_applicable(h) or h.upper.dtype != h.base.dtype:
        # bf16 summaries need the exact-recovery walk; the scan kernel
        # compares quantized values only.
        return rmq_value_batch(h, ls, rs)
    interpret = common.resolve_interpret(interpret)
    if not interpret:
        common.check_query_vmem(h.plan, False, h.upper.dtype.itemsize)
    vals, _ = _run(
        h.base, h.upper, None, ls, rs, h.plan, qb, False, interpret
    )
    return vals


def rmq_index_batch_pallas(
    h: Hierarchy,
    ls: jax.Array,
    rs: jax.Array,
    qb: int = K.DEFAULT_QUERY_BLOCK,
    interpret: bool | None = None,
) -> jax.Array:
    if not h.with_positions:
        raise ValueError("hierarchy built without positions")
    if not _kernel_applicable(h) or h.upper.dtype != h.base.dtype:
        return rmq_index_batch(h, ls, rs)
    interpret = common.resolve_interpret(interpret)
    if not interpret:
        common.check_query_vmem(h.plan, True, h.upper.dtype.itemsize)
    _, pos = _run(
        h.base, h.upper, h.upper_pos, ls, rs, h.plan, qb, True, interpret
    )
    return pos
