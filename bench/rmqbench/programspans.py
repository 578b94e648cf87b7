"""Sums over the program's own spans (``repro.obs.trace``), per root span.

A root is one finished span of a given name (``query_bulk``: one batch;
``build``: one build).  A span counts toward a root when the root is
among its ancestors, so time the program spends between roots (the
benchmark's own work, the profiler's shutdown) is left out.  A program
that emits no such root yields ``None``, never an error.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Tuple


def under(spans, root: str, names: Iterable[str]) -> Tuple[List, int]:
    """``(finished spans named in names below a root, number of roots)``."""
    names = set(names)
    by_id = {sp.span_id: sp for sp in spans}
    roots = {sp.span_id for sp in spans
             if sp.name == root and sp.end is not None}
    found = []
    for sp in spans:
        if sp.name not in names or sp.end is None:
            continue
        p = sp.parent_id
        while p is not None and p not in roots:
            parent = by_id.get(p)
            p = parent.parent_id if parent is not None else None
        if p is not None:
            found.append(sp)
    return found, len(roots)


def ms_per_root(spans, root: str, names: Iterable[str]) -> Optional[float]:
    """Milliseconds of the ``names`` spans below ``root``, per root."""
    found, n = under(spans, root, names)
    if not n:
        return None
    return sum(sp.end - sp.start for sp in found) / n * 1e3


def mean_ms(spans, name: str) -> Optional[float]:
    """Mean length of the finished ``name`` spans, in milliseconds."""
    found = [sp.end - sp.start for sp in spans
             if sp.name == name and sp.end is not None]
    if not found:
        return None
    return sum(found) / len(found) * 1e3
