"""Trace-time kernel-launch accounting and the launch registry.

The fused construction pipeline's contract is *one* Pallas launch per
build (vs. one per level on the historical path).  That claim is easy to
bit-rot silently — a refactor that quietly adds a second ``pallas_call``
still produces correct values.  This module makes it assertable: each
kernel wrapper calls :func:`record_launch` from *inside its traced body*,
so tracing a build records exactly as many launches as the compiled
program will issue per call.

Because jitted functions trace once per (shape, static-args)
specialization, launches are only recorded the first time a given
geometry is traced — wrap the *first* build of a fresh geometry in
:func:`count_launches`:

    with count_launches() as counts:
        build_hierarchy_fused(x, plan)          # first call for this plan
    assert counts == {"hierarchy_fused": 1}

Outside a :func:`count_launches` scope, :func:`record_launch` is a no-op,
so production builds pay nothing.

A richer layer stacks on the same recording sites without changing the
:func:`count_launches` contract: :func:`launch_registry` collects
:class:`LaunchRecord`\\ s — kernel name plus whatever static metadata
the wrapper knows at trace time (grid/level count, operand bytes, query
count).  Wrappers pass these as keyword arguments to
:func:`record_launch`; when only the plain counter is active the kwargs
are ignored.  Run-time phases (dispatch, the host's wait on the device)
are spans of :mod:`repro.obs.trace`, not records here.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from typing import Any, Dict, Iterator, List, Optional

__all__ = [
    "LaunchRecord",
    "LaunchRegistry",
    "count_launches",
    "launch_registry",
    "operand_bytes",
    "record_config",
    "record_launch",
]


def operand_bytes(*arrays) -> int:
    """Total byte footprint of the given operands, from static shape/dtype.

    Safe to call on tracers inside a jitted body — only ``.shape`` and
    ``.dtype`` are touched, both static.  ``None`` operands (optional
    position planes) are skipped.
    """
    total = 0
    for a in arrays:
        if a is None:
            continue
        total += math.prod(a.shape) * a.dtype.itemsize
    return int(total)

_counts: Optional[Dict[str, int]] = None
_registry: Optional["LaunchRegistry"] = None


@dataclasses.dataclass
class LaunchRecord:
    """One recorded kernel launch (trace-time) with static metadata."""

    name: str
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def as_dict(self) -> dict:
        return {"name": self.name, **self.meta}


class LaunchRegistry:
    """Thread-safe collection of launch records and configuration
    decisions, keyed by kernel name."""

    def __init__(self):
        self._lock = threading.Lock()
        self.records: List[LaunchRecord] = []
        self.configs: List[LaunchRecord] = []

    # -- recording ---------------------------------------------------------
    def add(self, name: str, meta: Dict[str, Any]) -> None:
        with self._lock:
            self.records.append(LaunchRecord(name, dict(meta)))

    def add_config(self, name: str, meta: Dict[str, Any]) -> None:
        """File a configuration decision (e.g. an engine adopting a tuned
        geometry).  Configs live in their own table: they are *not*
        launches and never reach :func:`count_launches` counts or the
        per-kernel launch views."""
        with self._lock:
            self.configs.append(LaunchRecord(name, dict(meta)))

    # -- views -------------------------------------------------------------
    @property
    def counts(self) -> Dict[str, int]:
        """``{kernel name: launch count}`` over the recorded launches."""
        out: Dict[str, int] = {}
        with self._lock:
            for rec in self.records:
                out[rec.name] = out.get(rec.name, 0) + 1
        return out

    def operand_bytes(self) -> Dict[str, int]:
        """Total trace-time ``operand_bytes`` attributed per kernel."""
        out: Dict[str, int] = {}
        with self._lock:
            for rec in self.records:
                b = rec.meta.get("operand_bytes")
                if b is not None:
                    out[rec.name] = out.get(rec.name, 0) + int(b)
        return out

    def as_dict(self) -> dict:
        with self._lock:
            records = [r.as_dict() for r in self.records]
            configs = [r.as_dict() for r in self.configs]
        counts: Dict[str, int] = {}
        for r in records:
            counts[r["name"]] = counts.get(r["name"], 0) + 1
        out: dict = {"counts": counts, "launches": records}
        if configs:
            out["configs"] = configs
        return out


def record_launch(name: str, **meta: Any) -> None:
    """Record one kernel launch under ``name`` (no-op when not counting).

    Called from inside jitted traced bodies; ``meta`` carries static,
    trace-time facts only (level counts, operand bytes computed from
    ``.shape``/``.dtype`` — never traced values).  The plain counter
    contract is unchanged: under :func:`count_launches`, ``meta`` is
    ignored and only the count increments.
    """
    if _counts is not None:
        _counts[name] = _counts.get(name, 0) + 1
    if _registry is not None:
        _registry.add(name, meta)


def record_config(name: str, **meta: Any) -> None:
    """Record a configuration decision (no-op when no registry is active).

    Unlike :func:`record_launch` this NEVER touches the plain launch
    counter — :func:`count_launches` results stay byte-identical whether
    or not engines record their tuned configs — and only feeds an active
    :func:`launch_registry`'s ``configs`` table.
    """
    if _registry is not None:
        _registry.add_config(name, meta)


@contextlib.contextmanager
def count_launches() -> Iterator[Dict[str, int]]:
    """Collect ``{kernel name: launches}`` recorded while tracing inside."""
    global _counts
    prev = _counts
    _counts = {}
    try:
        yield _counts
    finally:
        _counts = prev


@contextlib.contextmanager
def launch_registry() -> Iterator[LaunchRegistry]:
    """Collect full :class:`LaunchRecord`\\ s for the duration of the
    block."""
    global _registry
    prev = _registry
    reg = LaunchRegistry()
    _registry = reg
    try:
        yield reg
    finally:
        _registry = prev
