"""Jitted wrapper: build a full Hierarchy with the per-level Pallas kernel.

Produces a ``Hierarchy`` pytree bit-identical to
``repro.core.hierarchy.build_hierarchy`` (the oracle); tests assert this
across shape/dtype sweeps.

This is the historical one-launch-per-level path (L-1 launches; the glue
between levels — tile padding, slicing, the final assembly into the
contiguous ``upper`` buffer — is compiled into one XLA program around the
launches, so nothing bounces through the host).  The fused single-launch
pipeline lives in ``repro.kernels.hierarchy_fused``; keep this one for
geometries whose upper buffer exceeds the fused kernel's VMEM budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.constants import PAD_POS as _PAD_POS
from repro.core.hierarchy import Hierarchy, _pad_to, pos_dtype_for
from repro.core.plan import HierarchyPlan
from repro.kernels import common, profiling
from repro.kernels.hierarchy_build import kernel as K


def _rows_out(out_len: int, c: int) -> int:
    """Output rows per grid step: one sublane tile, or the whole level."""
    rows = -(-out_len // c)
    return rows if rows <= K.DEFAULT_ROWS_OUT else K.DEFAULT_ROWS_OUT


@functools.partial(
    jax.jit, static_argnames=("plan", "with_positions", "interpret")
)
def _build_jit(x, plan, with_positions, interpret):
    c = plan.c
    cap = plan.capacity
    pos_dtype = pos_dtype_for(cap) if with_positions else None
    inf = jnp.array(jnp.inf, dtype=x.dtype)

    levels_v, levels_p = [], []
    base = _pad_to(x, cap, inf)
    cur_v = base
    cur_p = jnp.arange(cap, dtype=pos_dtype) if with_positions else None

    for k in range(1, plan.num_levels):
        # consume ceil(len/c)*c entries, then tile-align for the kernel:
        # the level goes in as (chunks, c) and comes out as (chunks/c, c)
        out_len = plan.level_lens[k]
        rows_out = _rows_out(out_len, c)
        step = rows_out * c * c
        want_aligned = -(-out_len * c // step) * step
        v_in = _pad_to(cur_v, want_aligned, inf).reshape(-1, c)
        profiling.record_launch(
            "hierarchy_build",
            lowering="pallas",
            level=k,
            grid=int(want_aligned // step),
            with_positions=bool(with_positions),
            operand_bytes=profiling.operand_bytes(v_in),
        )
        if with_positions:
            p_in = _pad_to(
                cur_p, want_aligned, jnp.array(_PAD_POS, pos_dtype)
            ).reshape(-1, c)
            nxt_v, nxt_p = K.build_level_with_positions(
                v_in, p_in, c=c, rows_out=rows_out, interpret=interpret
            )
            nxt_v = nxt_v.reshape(-1)[:out_len]
            nxt_p = nxt_p.reshape(-1)[:out_len]
        else:
            nxt_v = K.build_level(
                v_in, c=c, rows_out=rows_out, interpret=interpret
            ).reshape(-1)[:out_len]
            nxt_p = None

        padded_len = plan.padded_lens[k - 1]
        levels_v.append(_pad_to(nxt_v, padded_len, inf))
        if with_positions:
            levels_p.append(
                _pad_to(nxt_p, padded_len, jnp.array(_PAD_POS, pos_dtype))
            )
        cur_v = nxt_v
        cur_p = nxt_p

    if levels_v:
        upper = jnp.concatenate(levels_v)
        upper_pos = jnp.concatenate(levels_p) if with_positions else None
    else:
        upper = jnp.zeros((0,), dtype=x.dtype)
        upper_pos = (
            jnp.zeros((0,), dtype=pos_dtype) if with_positions else None
        )
    return Hierarchy(base=base, upper=upper, upper_pos=upper_pos, plan=plan)


def build_hierarchy_pallas(
    x: jax.Array,
    plan: HierarchyPlan,
    with_positions: bool = False,
    interpret: bool | None = None,
) -> Hierarchy:
    """Level-by-level Pallas build (paper §4.1, bottom-up)."""
    return _build_jit(
        jnp.asarray(x), plan, with_positions,
        common.resolve_interpret(interpret),
    )
