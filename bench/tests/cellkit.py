"""Tiny copies of the benchmark's cells, for tests on the CPU."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {
    "paper-uniform-n28": {"n": 1 << 15, "c": 16, "t": 4},
    "ycsb-column-n27": {"recordcount": (1 << 14) - 512, "capacity": 1 << 14,
                        "c": 16, "t": 4},
}
TINY_TRAFFIC = {
    "mixed-bulk": {"batch": 16384, "check_sample": 2048},
    "ycsb-e": {"rate_per_s": 150, "warm_flush_requests": 4,
               "trace_from_s": 0.2, "trace_requests": 50},
    "ycsb-c": {"rate_per_s": 150, "warm_flush_requests": 4,
               "trace_from_s": 0.2, "trace_requests": 50},
    "rebuild": {},
}


# The serving cells, kept out of BENCHMARK.json until their tails are
# steady (PERF.md, Open questions); their driver is still tested here.
SERVING = {
    "configs": [{"name": "ycsb-column-n27", "source": "x", "reduced": [],
                 "file": "bench/configs/ycsb-column-n27.json", "why": "x"}],
    "workloads": [{"name": n, "config": "ycsb-column-n27", "traffic": t,
                   "chips": 1, "why": "x"}
                  for n, t in (("ycsb-e-serve", "ycsb-e"),
                               ("ycsb-c-serve", "ycsb-c"))],
    "end_to_end": [{"name": "p99_ms", "unit": "ms", "better": "lower",
                    "bound": 0.25, "source": "host_clock",
                    "workloads": ["ycsb-e-serve", "ycsb-c-serve"]}],
    "per_layer": [{"name": n, "unit": "ms", "better": "lower",
                   "source": "program_span", "layer": "x", "moves": "p99_ms",
                   "workloads": ["ycsb-e-serve", "ycsb-c-serve"]}
                  for n in ("tier.flush_ms", "engine.self_ms.serve",
                            "idle_share.serve")],
}


def tiny_root(tmp: Path) -> Path:
    """A checkout-shaped directory whose cells are tiny copies."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for key, extra in SERVING.items():
        bench[key] = bench[key] + extra
    (tmp / "bench").mkdir(parents=True, exist_ok=True)
    for sub in ("drivers", "metrics"):
        shutil.copytree(BENCH / sub, tmp / "bench" / sub,
                        dirs_exist_ok=True)
    for sub, tiny in (("configs", None), ("traffic", TINY_TRAFFIC)):
        (tmp / "bench" / sub).mkdir(exist_ok=True)
    for cfg in bench["configs"]:
        doc = json.loads((ROOT / cfg["file"]).read_text())
        doc.update(TINY[cfg["name"]])
        (tmp / cfg["file"]).write_text(json.dumps(doc))
    for name, over in TINY_TRAFFIC.items():
        doc = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
        doc.update(over)
        (tmp / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(doc))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def run_tiny(tmp: Path, workload: str, seed: int = 7, seconds: float = 1.0,
             trace: bool = False) -> dict:
    import time

    from rmqbench import harness

    cell = harness.Cell(tiny_root(tmp), workload)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    return harness.run_cell(cell, seed, seconds, trace, time.monotonic(),
                            device, require_chip=False)
