"""Jitted wrappers for the short-span RMQ kernel.

Handles query-batch padding to the query block and backend fallbacks:
degenerate geometries (``capacity < 2c``) use the pure-jnp ref, which is
also the production path on non-TPU backends.

Contract (both backends): every query must satisfy the engine planner's
SHORT predicate ``r // c - l // c <= 1`` — the answer for wider queries
would silently miss entries, so the engine owns the routing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.hierarchy import Hierarchy
from repro.kernels import common, profiling
from repro.kernels.rmq_short import kernel as K
from repro.kernels.rmq_short.ref import rmq_short_batch_ref


def _kernel_applicable(h: Hierarchy) -> bool:
    return h.plan.capacity >= 2 * h.plan.c


@functools.partial(
    jax.jit, static_argnames=("plan", "qb", "track_pos", "interpret")
)
def _run_rmq_short(base, ls, rs, plan, qb, track_pos, interpret):
    m = ls.shape[0]
    qb, m_pad = common.query_grid(m, qb, interpret)
    profiling.record_launch(
        "rmq_short",
        lowering="pallas",
        queries=int(m),
        grid=int(m_pad // qb),
        track_pos=bool(track_pos),
        operand_bytes=profiling.operand_bytes(base, ls, rs),
    )
    if m_pad != m:
        ls = jnp.pad(ls, (0, m_pad - m))
        rs = jnp.pad(rs, (0, m_pad - m))
    vals, pos = K.rmq_short_pallas(
        common.chunk_rows(base, plan.c),
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


def rmq_short_value_batch(h: Hierarchy, ls, rs) -> jax.Array:
    """Pure-JAX short-span values (the non-TPU production path)."""
    vals, _ = rmq_short_batch_ref(
        h.base, ls, rs, h.plan.c, h.plan.capacity, track_pos=False
    )
    return vals


def rmq_short_index_batch(h: Hierarchy, ls, rs) -> jax.Array:
    """Pure-JAX short-span leftmost-minimum positions.

    Works on value-only builds: level 0 positions are the indices
    themselves.
    """
    _, pos = rmq_short_batch_ref(
        h.base, ls, rs, h.plan.c, h.plan.capacity, track_pos=True
    )
    return pos


def rmq_short_value_batch_pallas(
    h: Hierarchy,
    ls: jax.Array,
    rs: jax.Array,
    qb: int = K.DEFAULT_QUERY_BLOCK,
    interpret: bool | None = None,
) -> jax.Array:
    if not _kernel_applicable(h):
        return rmq_short_value_batch(h, ls, rs)
    interpret = common.resolve_interpret(interpret)
    vals, _ = _run_rmq_short(
        h.base, jnp.asarray(ls), jnp.asarray(rs), h.plan, qb, False,
        interpret,
    )
    return vals


def rmq_short_index_batch_pallas(
    h: Hierarchy,
    ls: jax.Array,
    rs: jax.Array,
    qb: int = K.DEFAULT_QUERY_BLOCK,
    interpret: bool | None = None,
) -> jax.Array:
    if not _kernel_applicable(h):
        return rmq_short_index_batch(h, ls, rs)
    interpret = common.resolve_interpret(interpret)
    _, pos = _run_rmq_short(
        h.base, jnp.asarray(ls), jnp.asarray(rs), h.plan, qb, True,
        interpret,
    )
    return pos
