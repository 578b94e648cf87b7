#!/usr/bin/env python3
"""The control: the plain reference, one precision lower, in the program's place.

    python3 bench/control.py --workload paper-mixed-bulk --seeds 1,2,3

For each seed this makes the cell's data and the queries its check
compares, at the cell's own size, and counts how many answers the
reference computed over bfloat16-rounded values gets wrong against the
float32 reference: the number the check compares, read from the control.
A sound program reads 0 there, and every limit is 0, so the control has
to read more than 0 to be caught.  The benchmark's own runs never run
this; it prints one JSON line per seed.
"""

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

from rmqbench import data, harness  # noqa: E402
from rmqbench.reference import (  # noqa: E402
    RangeMinRef,
    mismatches,
    reference_hierarchy,
    to_bfloat16,
)


def host_data(seed: int, n: int) -> np.ndarray:
    x = data.device_uniform(seed, n)
    xh = data.host_copy(x)
    del x
    return xh


def bulk(cell, seed: int) -> dict:
    cfg, mix = cell.config, cell.traffic
    n = int(cfg["n"])
    x = host_data(seed, n)
    gen = data.rng(seed, 2)
    batch = data.make_queries(n, int(mix["batch"]), mix["range_class"], gen)
    pick = data.rng(seed, 3).choice(batch[0].shape[0],
                                    int(mix["check_sample"]), replace=False)
    ls, rs = batch[0][pick], batch[1][pick]
    want = RangeMinRef(x).query(ls, rs)
    got = RangeMinRef(to_bfloat16(x)).query(ls, rs)
    k = 1 if mix["op"] == "index" else 0
    return {"wrong_answers": mismatches(got[k], want[k]),
            "compared": int(ls.shape[0])}


def build(cell, seed: int) -> dict:
    cfg = cell.config
    n, c, t = int(cfg["n"]), int(cfg["c"]), int(cfg["t"])
    x = host_data(seed, n)
    want, _ = reference_hierarchy(x, n, c, t)
    got, _ = reference_hierarchy(to_bfloat16(x), n, c, t)
    return {"wrong_upper": mismatches(got, want),
            "compared": int(want.shape[0])}


def serve(cell, seed: int, seconds: float) -> dict:
    cfg, mix = cell.config, cell.traffic
    records = int(cfg["recordcount"])
    x = host_data(seed, records)
    s = data.request_stream(dict(mix, seconds=seconds), records, seed)
    scans = ~s["insert"]
    ls, rs = s["ls"][scans], s["rs"][scans]
    _, want = RangeMinRef(x, block=256).query(ls, rs)
    _, got = RangeMinRef(to_bfloat16(x), block=256).query(ls, rs)
    return {"wrong_positions": mismatches(got, want),
            "compared": int(ls.shape[0])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window of a serving cell (default: run_seconds)")
    args = ap.parse_args(argv)
    cell = harness.Cell(harness.ROOT, args.workload)
    harness.prepare_jax(harness.ROOT)
    device, err = harness.device_info(cell.chips, require_chip=True)
    if err:
        harness.log(f"error: {err}")
        return 1
    seconds = args.seconds or harness.load_json(
        harness.ROOT / "BENCHMARK.json")["run_seconds"]
    kind = cell.traffic["driver"]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        if kind == "closed_batch":
            out = bulk(cell, seed)
        elif kind == "rebuild":
            out = build(cell, seed)
        else:
            out = serve(cell, seed, seconds)
        out.update(workload=cell.name, seed=seed, control="bfloat16",
                   seconds=time.monotonic() - t0, device=device)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
