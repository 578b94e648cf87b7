"""Closed loop, one client: offline batches through ``QueryEngine.query_bulk``.

Set-up makes the array on the device from the seed, builds the index as
the configuration states, draws ``distinct_batches`` batches of the
mix's range class and answers each once, which compiles every bucket
shape the window will meet.  The window then sends the same batches in
turn, each as soon as the previous one is answered and its answers are
on the host, until ``--seconds`` have passed; the last batch ends the
window.  The check compares a sample of every answered batch, drawn from
the seed, with the plain reference.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from rmqbench import data
from rmqbench.reference import RangeMinRef, mismatches


class State:
    def __init__(self, index, engine, batches):
        self.index = index
        self.engine = engine
        self.batches = batches


def setup(run):
    import jax

    from repro.core import RMQ

    cfg, mix = run.config, run.traffic
    n = int(cfg["n"])
    x = data.device_uniform(run.seed, n)
    index = RMQ.build(x, c=cfg["c"], t=cfg["t"],
                      with_positions=cfg["with_positions"])
    jax.block_until_ready(index.hierarchy.upper)
    del x
    engine = index.engine()
    gen = data.rng(run.seed, 2)
    batches = [data.make_queries(n, int(mix["batch"]), mix["range_class"],
                                 gen)
               for _ in range(int(mix["distinct_batches"]))]
    for i, (ls, rs) in enumerate(batches):
        t0 = time.monotonic()
        np.asarray(engine.query_bulk(ls, rs, mix["op"]))
        run.log(f"warm-up batch {i}: {time.monotonic() - t0:.3f} s")
    return State(index, engine, batches)


def window(run, st):
    mix = run.traffic
    answers = []
    queries = 0
    t0 = time.monotonic()
    i = 0
    while True:
        b = i % len(st.batches)
        ls, rs = st.batches[b]
        if i == 0:
            run.trace_start()          # one whole batch is traced
        with run.span("batch"):
            out = np.asarray(st.engine.query_bulk(ls, rs, mix["op"]))
        if i == 0:
            run.trace_stop()
        answers.append((b, out))
        queries += int(ls.shape[0])
        i += 1
        if time.monotonic() - t0 >= run.seconds:
            break
    elapsed = time.monotonic() - t0
    times = [round(s[2] - s[1], 3) for s in run.spans if s[0] == "batch"]
    return {
        "end_to_end": {"qps": queries / elapsed},
        "indexed_bytes": int(run.config["n"]) * 4,
        "attempted": queries,
        "failed": 0,
        "answers": answers,
        "traced_batch": st.batches[0],
        "summary": {"batches": i, "queries": queries,
                    "elapsed_s": elapsed, "batch_s": times},
    }


def check(run, st, record):
    """Free the program's state, then compare a seeded sample."""
    cfg, mix = run.config, run.traffic
    batches = st.batches
    st.engine = st.index = None
    gc.collect()
    x = data.device_uniform(run.seed, int(cfg["n"]))
    xh = data.host_copy(x)
    del x
    ref = RangeMinRef(xh)
    gen = data.rng(run.seed, 3)
    answers = record["answers"]
    per = max(1, int(mix["check_sample"]) // len(answers))
    wrong = compared = 0
    for b, out in answers:
        ls, rs = batches[b]
        pick = gen.choice(ls.shape[0], min(per, ls.shape[0]), replace=False)
        want_v, want_p = ref.query(ls[pick], rs[pick])
        want = want_p if mix["op"] == "index" else want_v
        got = out[pick] if out.shape == ls.shape else out
        wrong += mismatches(got, want)
        compared += pick.shape[0]
    run.log(f"check: {compared} sampled answers of {len(answers)} batches")
    return [{"name": "wrong_answers", "value": wrong, "limit": 0}]
