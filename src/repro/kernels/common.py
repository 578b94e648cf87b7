"""Helpers shared by the RMQ kernel wrappers: lowering choice, the
chunk-per-row level-0 layout, and the VMEM budget guard.

**Layout.**  The TPU tiles a 1-D f32 array in HBM in runs of 1024
entries, so a ``c``-wide window of a 1-D level 0 cannot be DMA'd on its
own.  Every kernel therefore reads level 0 as ``(rows, c)`` — one chunk
per row, ``rows = ceil(capacity / c)`` with an +inf tail — and a chunk
is one row DMA.  :func:`chunk_rows` makes that view at the jit boundary;
``Hierarchy`` itself stays 1-D.

**VMEM.**  The fused build and the walk query kernels keep the whole
``upper`` buffer VMEM-resident for a launch.  Their budgets (below, and
the fused build's in its ``ops.py``) are what the v5e compiler accepted,
found by compiling each kernel for a described v5e chip across sizes;
:func:`check_vmem` turns a geometry past them into a ``ValueError``
before anything is traced, instead of a compiler error at the first
call.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "DEFAULT_QUERY_BLOCK",
    "VMEM_BUDGET_BYTES",
    "check_query_vmem",
    "check_vmem",
    "chunk_rows",
    "on_tpu",
    "query_grid",
    "query_vmem_bytes",
    "resolve_interpret",
    "tiled_bytes",
]

# Queries per grid step of the query kernels.  Bounds and results move
# in SMEM blocks of 1-D arrays, which the TPU tiles in runs of 1024.
DEFAULT_QUERY_BLOCK = 1024

# The compiler's default scoped-VMEM limit on TPU v5e (16 MiB): what a
# kernel launched without ``vmem_limit_bytes`` may allocate for its
# blocks and scratch.  The query kernels' resident (rows, c) upper planes
# count here once each (a constant-index block is not double-buffered).
VMEM_BUDGET_BYTES = 16 * 1024 * 1024


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret) -> bool:
    """``None`` -> the production lowering: compiled on TPU, the
    interpreter elsewhere.  Never resolves to interpret mode on TPU."""
    if interpret is None:
        return not on_tpu()
    return bool(interpret)


def chunk_rows(base: jax.Array, c: int) -> jax.Array:
    """Level 0 as ``(ceil(len / c), c)``: one chunk per row, +inf tail."""
    rows = -(-base.shape[0] // c)
    pad = rows * c - base.shape[0]
    if pad:
        base = jnp.pad(base, (0, pad), constant_values=jnp.inf)
    return base.reshape(rows, c)


def query_grid(m: int, qb: int, interpret: bool):
    """``(block, padded m)`` for a batch of ``m`` query bounds.

    Bounds and results travel in SMEM blocks of a 1-D array, which the
    TPU tiles in runs of 1024: a block is either the whole (unpadded)
    batch or a multiple of 1024.  Interpret mode takes any ``qb``.
    """
    if 0 < m <= qb:
        return m, m
    if not interpret and qb % 1024:
        raise ValueError(
            f"query block {qb} must be a multiple of 1024 on TPU for "
            f"batches larger than one block (m={m})"
        )
    return qb, -(-m // qb) * qb


def tiled_bytes(rows: int, c: int, itemsize: int = 4) -> int:
    """VMEM bytes of a ``(rows, c)`` buffer in (8, 128) tiles."""
    return (-(-rows // 8) * 8) * (-(-c // 128) * 128) * itemsize


def query_vmem_bytes(plan, planes: int, itemsize: int = 4) -> int:
    """Scoped VMEM of a hierarchy-walk query launch: ``planes`` resident
    ``(rows, c)`` upper planes plus the double-buffered level-0 windows."""
    rows = plan.upper_size // plan.c
    return (planes * tiled_bytes(rows, plan.c, itemsize)
            + 4 * tiled_bytes(1, plan.c, itemsize))


def check_vmem(kernel: str, need: int, budget: int = VMEM_BUDGET_BYTES,
               remedy: str = "use a larger chunk size c, or shard the "
                             "array (DistributedRMQ)") -> None:
    """Refuse a launch whose VMEM working set exceeds ``budget``."""
    if need > budget:
        raise ValueError(
            f"{kernel} needs ~{need} bytes of VMEM for this geometry "
            f"(budget {budget}); its upper buffer is VMEM-resident for "
            f"the launch — {remedy}"
        )


def check_query_vmem(plan, track_pos: bool, itemsize: int = 4) -> None:
    """The guard of the hierarchy-walk query kernels (scan/fused/bulk)."""
    if plan.num_levels >= 2:
        check_vmem(
            "the query kernel",
            query_vmem_bytes(plan, 2 if track_pos else 1, itemsize),
        )
