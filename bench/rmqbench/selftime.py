"""Self time: a span's duration minus the part its inner spans cover."""

from __future__ import annotations

from typing import Iterable, List, Tuple

from .tracing import merge, total


def self_times(outer: Iterable[Tuple[str, float, float]],
               inner: Iterable[Tuple[str, float, float]]) -> List[float]:
    """For each ``(thread, start, end)`` in ``outer``, its length minus the
    union of the ``inner`` intervals of the same thread inside it."""
    by_thread = {}
    for th, s, e in inner:
        by_thread.setdefault(th, []).append((s, e))
    for th in by_thread:
        by_thread[th] = merge(by_thread[th])
    out = []
    for th, s, e in outer:
        covered = total([(max(a, s), min(b, e))
                         for a, b in by_thread.get(th, ())
                         if b > s and a < e])
        out.append((e - s) - covered)
    return out


def program(spans, name: str):
    """``(thread, start, end)`` of the program's finished spans ``name``."""
    return [(sp.thread, sp.start, sp.end) for sp in spans
            if sp.name == name and sp.end is not None]
