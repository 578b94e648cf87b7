"""The benchmark harness: one cell, one seed, one run.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json`` — the deployment (sizes, tenant
  settings, guarantees, source, reductions, assumptions);
* ``bench/traffic/<mix>.json`` — the traffic mix; its ``driver`` names
  ``bench/drivers/<driver>.py``, which defines ``setup``, ``window`` and
  ``check``;
* ``bench/metrics/<metric>.py`` — one reader per per-layer metric, a
  function ``read(ctx)`` returning a number or ``None``.

A run checks for the chip, sets JAX's compile cache, lets the driver make
its data from the seed and warm up (set-up), measures for ``--seconds``,
reads the device's memory peak, lets the driver free the program's state
and compare what the window produced with the plain reference, and
prints the result as the last line of standard output.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]      # .../bench
ROOT = BENCH.parent                               # the checkout
CACHE_DIR = BENCH / ".cache"
# Idle margin kept outside the traced window on both sides: device events
# next to the profiler's start and stop can be missing from the trace.
EDGE_S = 0.05


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts programs JAX lowers (each new shape, cache hit or not)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.count = 0
        self.installed = False

    def install(self) -> None:
        import jax

        if not self.installed:
            jax.monitoring.register_event_duration_secs_listener(self._on)
            self.installed = True

    def _on(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.count += 1


# ---------------------------------------------------------------------------
# discovery by name
# ---------------------------------------------------------------------------
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One ``workloads`` entry with its configuration, mix and metrics."""

    def __init__(self, root: Path, name: str):
        self.root = root
        bench = load_json(root / "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
        self.workload = cells[name]
        self.name = name
        cfg = {c["name"]: c for c in bench["configs"]}[
            self.workload["config"]]
        self.config = load_json(root / cfg["file"])
        self.traffic = load_json(
            root / "bench" / "traffic" / f"{self.workload['traffic']}.json")
        self.chips = int(self.workload["chips"])
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def driver(self) -> ModuleType:
        d = self.traffic["driver"]
        return load_module(self.root / "bench" / "drivers" / f"{d}.py",
                           f"rmqbench_driver_{d}")

    def reader(self, metric: str) -> Callable:
        path = self.root / "bench" / "metrics" / f"{metric}.py"
        mod = load_module(path, "rmqbench_metric_" + metric.replace(".", "_"))
        return mod.read


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
class Run:
    """What a driver sees: the cell, the seed, the clock and the tracing."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.spans: List[tuple] = []       # (name, start, end), monotonic
        self.device_trace = None           # reduced profiler trace
        self.trace_offset_ns = 0
        self.program_tracer = None
        self._profile_dir: Optional[Path] = None
        self._traced_t0: Optional[float] = None
        self._window_ann = None
        self.log = log

    # -- the benchmark's own spans ---------------------------------------
    def span(self, name: str):
        """Record ``name`` on the host clock and, in a traced run, as a
        profiler annotation ``bench.<name>``."""
        return _BenchSpan(self, name)

    # -- the profiler window ---------------------------------------------
    def trace_start(self) -> None:
        """Start the profiler (traced runs only); once per run."""
        if not self.trace or self._profile_dir is not None:
            return
        import jax

        self._profile_dir = CACHE_DIR / "profile"
        shutil.rmtree(self._profile_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self._profile_dir),
                                 profiler_options=opts)
        time.sleep(EDGE_S)          # the device tracer's start-up
        self._window_ann = jax.profiler.TraceAnnotation("bench.traced")
        self._window_ann.__enter__()
        self._traced_t0 = time.monotonic()

    def trace_stop(self) -> None:
        """Stop the profiler and reduce what it recorded."""
        if self._window_ann is None:
            return
        import jax

        from .tracing import find_xplane, reduce_trace

        self._window_ann.__exit__(None, None, None)
        self._window_ann = None
        time.sleep(EDGE_S)          # the last device events' collection
        t0 = time.monotonic()
        jax.profiler.stop_trace()
        t1 = time.monotonic()
        path = find_xplane(self._profile_dir)
        self.device_trace = reduce_trace(path)
        self.trace_offset_ns = (self.device_trace.t0_ns
                                - int(self._traced_t0 * 1e9))
        log(f"trace: {path.stat().st_size} bytes, window "
            f"{self.device_trace.window_s:.3f} s, busy "
            f"{self.device_trace.busy_s:.3f} s; stopping took "
            f"{t1 - t0:.1f} s, reading {time.monotonic() - t1:.1f} s")
        shutil.rmtree(self._profile_dir, ignore_errors=True)

    @property
    def tracing(self) -> bool:
        return self._window_ann is not None


class _BenchSpan:
    __slots__ = ("run", "name", "t0", "ann")

    def __init__(self, run: Run, name: str):
        self.run, self.name = run, name
        self.ann = None

    def __enter__(self):
        if self.run.tracing:
            import jax

            self.ann = jax.profiler.TraceAnnotation(f"bench.{self.name}")
            self.ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.run.spans.append((self.name, self.t0, t1))


class Reading:
    """What a per-layer metric reader sees."""

    def __init__(self, run: Run, record: dict, peaks: Optional[dict]):
        self.config = run.config
        self.record = record
        self.device = run.device_trace
        self.peaks = peaks
        self.bench_spans = run.spans
        tr = run.program_tracer
        self.program_spans = tr.spans() if tr is not None else []


def device_info(chips_wanted: int, require_chip: bool):
    """``(device dict, error)``; error is set when the chip is missing."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    if require_chip and dev.platform != "tpu":
        return info, f"no TPU found (JAX platform {dev.platform!r})"
    if require_chip and len(devs) < chips_wanted:
        return info, (f"the cell needs {chips_wanted} chips, "
                      f"found {len(devs)}")
    return info, None


def memory_peak() -> Optional[int]:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def load_peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, device: dict, require_chip: bool = True
             ) -> Dict[str, Any]:
    """Set up, measure, check; return the result object."""
    driver = cell.driver()
    run = Run(cell, seed, seconds, trace)
    peaks = load_peaks(device["kind"]) if require_chip else None
    state = driver.setup(run)
    setup_s = time.monotonic() - t_start
    log(f"setup: {setup_s:.3f} s")
    if trace:
        from repro.obs import trace as obs_trace

        run.program_tracer = obs_trace.Tracer(capacity=1 << 22)
        obs_trace.set_tracer(run.program_tracer)
    compiled = COMPILES.count
    try:
        record = driver.window(run, state)
    finally:
        run.trace_stop()
        if trace:
            obs_trace.set_tracer(None)
    peak = memory_peak()
    record.setdefault("summary", {})["compiles_in_window"] = (
        COMPILES.count - compiled)
    log(f"window: {json.dumps(record['summary'])}")
    checks = driver.check(run, state, record)
    del state
    gc.collect()
    correct = all(c["value"] <= c["limit"] for c in checks)

    metrics: Dict[str, dict] = {}
    if trace:
        ctx = Reading(run, record, peaks)
        for m in cell.per_layer:
            value = cell.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = dict(record.get("end_to_end", {}))
        e2e["setup_s"] = setup_s
        if peak is not None:
            e2e["mem_ratio"] = peak / record["indexed_bytes"]
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    dev = dict(device)
    dev["memory_peak_bytes"] = peak
    if trace and run.device_trace is not None:
        dt = run.device_trace
        dev["busy_s"] = dt.busy_s
        dev["window_s"] = dt.window_s
    out = {
        "correct": correct,
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": metrics,
        "device": dev,
    }
    if trace and run.device_trace is not None:
        from .tracing import name_gaps, top

        dt = run.device_trace
        out["breakdown"] = {
            "device_ops": top(dt.ops if dt.ops else dt.modules),
            "idle_gaps": name_gaps(dt, ctx.program_spans,
                                   run.trace_offset_ns),
        }
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------
def parse_args(argv):
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once and print its result.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


COMPILES = CompileCounter()


def prepare_jax(root: Path) -> str:
    """Put the program on the path and JAX's compile cache in place."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(root / "src"))
    import jax

    COMPILES.install()
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CACHE_DIR / "jax")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def main(argv, t_start: float) -> int:
    args = parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"error: no program next to the benchmark ({ROOT / 'src'} "
            "is missing)")
        return 2
    try:
        cell = Cell(ROOT, args.workload)
    except (KeyError, FileNotFoundError) as e:
        log(f"error: {e}")
        return 2
    cache = prepare_jax(ROOT)
    device, err = device_info(cell.chips, require_chip=True)
    if err:
        log(f"error: {err}")
        return 1
    log(f"device: {device}; compile cache {cache}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_start, device)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0
