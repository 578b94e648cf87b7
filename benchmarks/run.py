"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the harness contract; the
roofline module additionally writes results/roofline.{md,json} from the
dry-run artifacts when present.

Usage::

    python benchmarks/run.py                 # run everything
    python benchmarks/run.py throughput tuning   # run a subset by name
    python benchmarks/run.py --json out.json     # machine-readable report

``--json <path>`` writes a structured report next to the CSV output:
per-module wall time and status, whatever dict payload each module's
``main()`` returns, and the kernel-launch registry captured around the
module (``repro.kernels.profiling.launch_registry`` — trace-time records,
so a module only shows the launches whose geometry it traced first).

JAX's persistent compilation cache is on for the run: in
``JAX_COMPILATION_CACHE_DIR`` when that is set, else ``<repo>/.jax_cache``
(``repro.launch.compile_cache``).

Set ``REPRO_BENCH_TINY=1`` to shrink problem sizes in the modules that
support it (CI smoke: exercises the harness without paper-scale runs).
"""

import json
import os
import sys
import time
import traceback

MODULES = [
    ("memory_footprint", "Fig. 15 memory footprint"),
    ("construction", "Fig. 17 construction time (jax/pallas/fused)"),
    ("update_throughput", "streaming updates vs full rebuild"),
    ("throughput", "Fig. 16 RMQ throughput by range class"),
    ("engine_throughput",
     "routed vs fused vs monolithic query paths (+ BENCH_query.json)"),
    ("distributed_engine", "distributed routing + sharded update cost"),
    ("serving_qps",
     "deadline-batched serving tier vs flush-per-request QPS/p99"),
    ("tuning", "Fig. 12 (c, t) tuning"),
    ("query_assignment", "Fig. 14 multi-load vs WLQ"),
    ("coalesced_access", "Fig. 4 access coalescing microbench"),
    ("bulk_queries",
     "offline bulk path: endpoint-sorted sweep vs fused (+ BENCH_bulk.json)"),
    ("overlap_ablation", "Fig. 13 hybrid top-level ablation"),
    ("roofline", "LM framework roofline (from dry-run artifacts)"),
]


def select(argv):
    """The (name, desc) list to run, honouring CLI module-name args."""
    if not argv:
        return MODULES
    by_name = dict(MODULES)
    unknown = [a for a in argv if a not in by_name]
    if unknown:
        names = ", ".join(name for name, _ in MODULES)
        raise SystemExit(
            f"unknown benchmark module(s) {unknown}; available: {names}"
        )
    # preserve registry order regardless of CLI order
    return [(n, d) for n, d in MODULES if n in set(argv)]


def _jsonable(o):
    """json.dump fallback for numpy scalars/arrays in module payloads."""
    if hasattr(o, "item") and getattr(o, "shape", None) in ((), None):
        return o.item()
    if hasattr(o, "tolist"):
        return o.tolist()
    return str(o)


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv):
            raise SystemExit("--json requires an output path")
        json_path = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]

    report = {
        "tiny": os.environ.get("REPRO_BENCH_TINY") == "1",
        "modules": [],
    }
    failures = []
    for mod_name, desc in select(argv):
        print(f"# === {mod_name}: {desc} ===", flush=True)
        entry = {"name": mod_name, "desc": desc}
        t0 = time.perf_counter()
        try:
            mod = __import__(f"benchmarks.{mod_name}",
                             fromlist=["main"])
            if json_path is not None:
                from repro.kernels.profiling import launch_registry
                with launch_registry() as reg:
                    payload = mod.main()
                entry["launches"] = reg.as_dict()
            else:
                payload = mod.main()
            entry["status"] = "ok"
            if isinstance(payload, dict):
                entry["payload"] = payload
        except Exception as e:
            failures.append((mod_name, e))
            entry["status"] = "failed"
            entry["error"] = f"{type(e).__name__}: {e}"
            print(f"# FAILED {mod_name}: {e}")
            traceback.print_exc()
        entry["seconds"] = round(time.perf_counter() - t0, 6)
        report["modules"].append(entry)

    if json_path is not None:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2, default=_jsonable)
            f.write("\n")
        print(f"# wrote {json_path}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
