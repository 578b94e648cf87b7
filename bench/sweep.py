#!/usr/bin/env python3
"""Find the highest rate an open-loop serving cell sustains, on the chip.

    python3 bench/sweep.py --workload ycsb-c-serve --seed 11 \
        --rates 200,400,600,800 --seconds 8

Sets the cell up once, then runs one window per rate, each with its own
request stream drawn from ``(seed, rate)``, and prints one JSON line per
rate: latency percentiles, refusals, the backlog of unanswered requests
when the window closed and how late the generator ran.  A rate is
sustained when nothing is refused and the backlog at the close is at most
one ``max_batch``.  The cells send at a fixed rate that this sweep found
once; the benchmark's own runs never search for it.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from rmqbench import data, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    cell = harness.Cell(harness.ROOT, args.workload)
    if cell.traffic["driver"] != "open_serve":
        harness.log("error: the sweep is for open-loop serving cells")
        return 2
    harness.prepare_jax(harness.ROOT)
    device, err = harness.device_info(cell.chips, require_chip=True)
    if err:
        harness.log(f"error: {err}")
        return 1
    driver = cell.driver()
    run = harness.Run(cell, args.seed, args.seconds, False)
    state = driver.setup(run)
    cap = int(cell.config["tenant"]["max_batch"])
    records = int(cell.config["recordcount"])
    for rate in (float(r) for r in args.rates.split(",")):
        run.traffic = dict(cell.traffic, rate_per_s=rate)
        state.stream = data.request_stream(
            dict(run.traffic, seconds=args.seconds), records,
            args.seed * 1000 + int(rate))
        before = harness.COMPILES.count
        record = driver.window(run, state)
        s = record["summary"]
        s["compiles_in_window"] = harness.COMPILES.count - before
        s["sustained"] = (s["refused"] == 0 and s["errored"] == 0
                          and s["backlog_at_close"] <= cap)
        s["device"] = device
        print(json.dumps(s), flush=True)
        time.sleep(2.0)                 # let the tier drain
    state.tier.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
