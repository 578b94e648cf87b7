"""The per-layer readers of the program's own spans, on the CPU.

Each reader runs on hand-made spans with known sums, on the spans of a
program that emits none of them (it must read ``None``, not raise), and
in tiny traced cells through the whole harness.
"""

from __future__ import annotations

import pytest

import cellkit
from rmqbench import harness
from repro.obs.trace import Span

BULK = "paper-mixed-bulk"
BUILD = "paper-build"
NEW = {
    "engine.dedup_ms.offline": BULK,
    "engine.cache_ms.offline": BULK,
    "engine.cache_hit_rate.offline": BULK,
    "engine.launch_ms.offline": BULK,
    "engine.fetch_ms.offline": BULK,
    "host.gc_ms.offline": BULK,
    "build.host_ms": BUILD,
}


class Ctx:
    def __init__(self, spans):
        self.program_spans = spans


def _reader(name):
    return harness.Cell(cellkit.ROOT, NEW[name]).reader(name)


def _spans(rows):
    """``(name, start, end, id, parent id, args)`` rows as spans."""
    return [Span(name=n, start=s, end=e, span_id=i, parent_id=p,
                 thread="MainThread", args=dict(a))
            for n, s, e, i, p, a in rows]


# two batches; times in seconds
BATCHES = _spans([
    ("dedup", 0.0, 0.5, 2, 1, {}),
    ("gc", 1.0, 1.25, 4, 3, {"generation": 0, "collected": 0}),
    ("cache_get", 0.5, 2.5, 3, 1, {"lookups": 100, "hits": 10}),
    ("plan", 2.5, 3.0, 5, 1, {}),
    ("launch", 3.0, 3.5, 7, 6, {}),
    ("fetch", 3.5, 6.0, 8, 6, {}),
    ("execute", 3.0, 6.0, 6, 1, {}),
    ("cache_put", 6.0, 7.0, 9, 1, {}),
    ("scatter", 7.0, 7.5, 10, 1, {}),
    ("query_bulk", 0.0, 8.0, 1, None, {}),
    # between batches: neither counts
    ("gc", 8.5, 9.5, 11, None, {"generation": 2, "collected": 5}),
    ("dedup", 9.5, 9.75, 12, None, {}),
    ("dedup", 10.0, 10.25, 22, 21, {}),
    ("cache_get", 10.25, 11.25, 23, 21, {"lookups": 60, "hits": 30}),
    ("launch", 11.25, 11.75, 27, 26, {}),
    ("gc", 11.5, 11.75, 28, 27, {"generation": 1, "collected": 2}),
    ("fetch", 11.75, 12.75, 29, 26, {}),
    ("execute", 11.25, 12.75, 26, 21, {}),
    ("cache_put", 12.75, 13.0, 30, 21, {}),
    ("query_bulk", 10.0, 13.5, 21, None, {}),
])
BUILDS = _spans([
    ("build_plan", 0.0, 0.0005, 2, 1, {}),
    ("build_dispatch", 0.0005, 0.0015, 3, 1, {}),
    ("build", 0.0, 0.002, 1, None, {"n": 64, "backend": "pallas"}),
    ("build", 1.0, 1.004, 4, None, {"n": 64, "backend": "pallas"}),
    ("build_dispatch", 2.0, 2.5, 5, None, {}),  # not RMQ.build: left out
])
WANT = {
    "engine.dedup_ms.offline": (0.5 + 0.25) / 2 * 1e3,
    "engine.cache_ms.offline": (2.0 + 1.0 + 1.0 + 0.25) / 2 * 1e3,
    "engine.cache_hit_rate.offline": 100.0 * 40 / 160,
    "engine.launch_ms.offline": (0.5 + 0.5) / 2 * 1e3,
    "engine.fetch_ms.offline": (2.5 + 1.0) / 2 * 1e3,
    "host.gc_ms.offline": (0.25 + 0.25) / 2 * 1e3,
    "build.host_ms": (2.0 + 4.0) / 2,
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_sums_hand_made_spans(name):
    spans = BUILDS if NEW[name] == BUILD else BATCHES
    assert _reader(name)(Ctx(spans)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_finds_nothing_without_the_spans(name):
    # what a program without these spans records: plan, execute and
    # scatter with no batch or build around them
    old = _spans([("plan", 0.0, 1.0, 1, None, {}),
                  ("execute", 1.0, 2.0, 2, None, {}),
                  ("scatter", 2.0, 3.0, 3, None, {})])
    assert _reader(name)(Ctx(old)) is None
    assert _reader(name)(Ctx([])) is None


def test_hit_rate_without_lookups_is_none_and_zero_hits_read_zero():
    read = _reader("engine.cache_hit_rate.offline")
    root = ("query_bulk", 0.0, 1.0, 1, None, {})
    assert read(Ctx(_spans([root]))) is None
    miss = ("cache_get", 0.0, 0.5, 2, 1, {"lookups": 8, "hits": 0})
    assert read(Ctx(_spans([miss, root]))) == 0.0


@pytest.mark.parametrize("workload", [BULK, BUILD])
def test_tiny_traced_cell_reports_every_new_metric(tmp_path, monkeypatch,
                                                   workload):
    # below the tiny index's bulk crossover (8192), so the batches take
    # the routed path (dedup, cache, buckets) as the cell's do on the
    # chip, and together above its 8192-entry cache, so they miss there
    monkeypatch.setitem(cellkit.TINY_TRAFFIC, "mixed-bulk",
                        dict(cellkit.TINY_TRAFFIC["mixed-bulk"],
                             batch=6000))
    out = cellkit.run_tiny(tmp_path, workload, seconds=0.6, trace=True)
    assert out["correct"]
    got = out["metrics"]
    for name in (n for n, cell in NEW.items() if cell == workload):
        assert got[name]["value"] is not None, name
    if workload == BULK:
        assert got["engine.dedup_ms.offline"]["value"] > 0
        assert got["engine.cache_ms.offline"]["value"] > 0
        assert got["engine.fetch_ms.offline"]["value"] > 0
        assert 0 <= got["engine.cache_hit_rate.offline"]["value"] <= 100
    else:
        assert got["build.host_ms"]["value"] > 0
