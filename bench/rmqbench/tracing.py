"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

What the trace of a v5e chip holds (read by hand first, then coded):

* plane ``/device:TPU:<i>`` per chip, with lines ``XLA Modules`` (one
  event per executed program, named ``jit_<fn>(<fingerprint>)``) and
  ``XLA Ops`` (one event per HLO instruction, named by its HLO text,
  ``%<op>.<k> = <shape> ...``; a Pallas kernel is a ``custom-call``
  instruction named after the kernel's Python body, e.g. ``_run`` or
  ``build_level``);
* plane ``/host:CPU`` with one line per host thread; the benchmark's own
  ``jax.profiler.TraceAnnotation`` spans (named ``bench.*``) are events
  there.

Host and device events share one clock in the trace.  Busy time is the
union of the ``XLA Modules`` intervals: a program occupies the device
from its start to its end, whatever runs inside it.  The traced window is
the benchmark's ``bench.traced`` annotation.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.traced"
# Above this size a trace's per-instruction line is not walked (a while
# loop in a program can emit millions of events); module totals remain.
MAX_OPS_TRACE_BYTES = 64 << 20

_MODULE_SUFFIX = re.compile(r"\(\d+\)$")
_OP_NAME = re.compile(r"^%?([^\s=]+?)(?:\.\d+)?(?:\s=|$)")


def module_name(name: str) -> str:
    """``jit__run(1234)`` -> ``jit__run``."""
    return _MODULE_SUFFIX.sub("", name)


def op_name(name: str) -> str:
    """``%build_level.3 = f32[...] custom-call(...)`` -> ``build_level``."""
    m = _OP_NAME.match(name)
    return m.group(1) if m else name


def merge(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of ``[start, end)`` intervals, sorted and disjoint."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


@dataclasses.dataclass
class DeviceTrace:
    """One traced window, reduced."""

    window_s: float                      # length of the traced window
    busy_s: float                        # busy union, averaged over chips
    chips: int
    t0_ns: int                           # window on the trace's clock
    t1_ns: int
    busy: List[List[Tuple[int, int]]]    # per chip, clipped to the window
    modules: Dict[str, float]            # seconds per program name
    ops: Optional[Dict[str, float]]      # seconds per instruction name
    annotations: List[Tuple[str, int, int]]   # host bench.* events

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def gaps(self, chip: int = 0) -> List[Tuple[int, int]]:
        """Idle intervals of one chip inside the window."""
        if chip >= len(self.busy):
            return []
        out, cur = [], self.t0_ns
        for s, e in self.busy[chip]:
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
        if cur < self.t1_ns:
            out.append((cur, self.t1_ns))
        return out


def _events(line):
    for ev in line.events:
        yield ev.name, int(ev.start_ns), int(ev.end_ns)


def reduce_trace(path, window: str = WINDOW) -> DeviceTrace:
    """Read one ``.xplane.pb`` and reduce it to a :class:`DeviceTrace`."""
    from jax.profiler import ProfileData

    path = Path(path)
    walk_ops = path.stat().st_size <= MAX_OPS_TRACE_BYTES
    pd = ProfileData.from_file(str(path))
    annotations: List[Tuple[str, int, int]] = []
    device_planes = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name.startswith("bench."):
                        annotations.append((name, s, e))
    spans = [(s, e) for name, s, e in annotations if name == window]
    if not spans:
        raise ValueError(f"no {window!r} annotation in {path}")
    t0, t1 = min(s for s, _ in spans), max(e for _, e in spans)
    busy, modules = [], {}
    ops: Optional[Dict[str, float]] = {} if walk_ops else None
    for plane in sorted(device_planes, key=lambda p: p.name):
        mods = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for name, s, e in _events(line):
                    mods.append((s, e))
                    key = module_name(name)
                    d = total(clip([(s, e)], t0, t1)) / 1e9
                    modules[key] = modules.get(key, 0.0) + d
            elif line.name == "XLA Ops" and ops is not None:
                for name, s, e in _events(line):
                    d = total(clip([(s, e)], t0, t1)) / 1e9
                    if d:
                        key = op_name(name)
                        ops[key] = ops.get(key, 0.0) + d
        busy.append(clip(merge(mods), t0, t1))
    chips = max(len(busy), 1)
    busy_s = sum(total(b) for b in busy) / chips / 1e9
    annotations.sort(key=lambda a: a[1])
    return DeviceTrace(
        window_s=(t1 - t0) / 1e9, busy_s=busy_s, chips=len(busy),
        t0_ns=t0, t1_ns=t1, busy=busy, modules=modules, ops=ops,
        annotations=annotations,
    )


def top(d: Dict[str, float], k: int = 10) -> List[list]:
    return [[n, v] for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]]


def name_gaps(dt: DeviceTrace, host_spans=(), offset_ns: int = 0,
              k: int = 10) -> List[list]:
    """Idle time of chip 0 summed by what the host was doing in it.

    A gap is named by the innermost program span (``host_spans``: objects
    with ``name``, ``start``, ``end`` in seconds of the program's clock,
    mapped onto the trace's clock by ``offset_ns``) or else the innermost
    benchmark annotation covering its midpoint; ``"none"`` otherwise.
    """
    import numpy as np

    gaps = dt.gaps(0)
    if not gaps:
        return []
    mids = np.array([(s + e) // 2 for s, e in gaps], np.int64)
    order = np.argsort(mids)
    sm = mids[order]
    names = np.full(len(gaps), "none", dtype=object)
    named = np.zeros(len(gaps), bool)
    prog = [(sp.name, int(sp.start * 1e9) + offset_ns,
             int(sp.end * 1e9) + offset_ns)
            for sp in host_spans if sp.end is not None and sp.end > sp.start]
    bench = [a for a in dt.annotations if a[0] != WINDOW]
    for group in (prog, bench):
        best = np.full(len(gaps), np.iinfo(np.int64).max, np.int64)
        pick = np.full(len(gaps), None, dtype=object)
        for name, a, b in group:
            lo, hi = np.searchsorted(sm, [a, b], side="left")
            if hi <= lo:
                continue
            seg = np.arange(lo, hi)
            seg = seg[best[seg] > b - a]
            best[seg] = b - a
            pick[seg] = name
        take = ~named & (pick != None)  # noqa: E711
        names[take] = pick[take]
        named |= take
    sums: Dict[str, float] = {}
    for j, i in enumerate(order):
        s, e = gaps[i]
        sums[names[j]] = sums.get(names[j], 0.0) + (e - s) / 1e9
    return top(sums, k)


def find_xplane(logdir) -> Path:
    files = sorted(Path(logdir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]
