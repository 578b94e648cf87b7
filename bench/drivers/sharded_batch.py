"""Closed loop, one client: offline batches through a sharded index's engine.

Set-up turns on JAX's 64-bit mode (global coordinates pass 2^31), makes
the configuration's LCP array on the devices, one segment per device of
a ``(1, segments)`` mesh (``rmqbench.lcpdata``), builds
``DistributedRMQ`` over it, attaches ``index.engine()``, draws
``distinct_batches`` batches of rank pairs and answers each once, which
compiles every shape the window will meet.  The window sends the same
batches in turn through ``QueryEngine.query_bulk``, each as soon as the
previous one is answered and its answers are on the host, until
``--seconds`` have passed; the last batch ends the window.  The check
frees the index, makes the array again, copies it to the host segment by
segment, and compares a sample of every answered batch, drawn from the
seed, with the plain reference (``rmqbench.segments``).  It also logs
what the same comparison reads for the reference's answers to the
controls' bounds (``segments.CONTROLS``: a crossing span answered from
one segment, or without one segment's piece), the readings a program
that broke the guarantee would give.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from rmqbench import data, lcpdata
from rmqbench.reference import mismatches
from rmqbench.segments import CONTROLS, SegmentedRangeMinRef


class State:
    def __init__(self, mesh, index, engine, batches):
        self.mesh = mesh
        self.index = index
        self.engine = engine
        self.batches = batches


def _mesh(cfg):
    import jax

    axis = cfg["segment_axis"]
    return jax.make_mesh((1, int(cfg["segments"])), ("data", axis))


def setup(run):
    import jax

    jax.config.update("jax_enable_x64", True)
    from repro.core.distributed import DistributedRMQ

    cfg, mix = run.config, run.traffic
    n = int(cfg["n"])
    mesh = _mesh(cfg)
    x = lcpdata.device_lcp(run.seed, n, mesh, cfg["segment_axis"])
    index = DistributedRMQ.build(
        x, mesh, segment_axis=cfg["segment_axis"], query_axes=("data",),
        c=cfg["c"], t=cfg["t"], with_positions=cfg["with_positions"])
    jax.block_until_ready(index.upper)
    del x
    engine = index.engine()
    gen = data.rng(run.seed, 2)
    batches = [lcpdata.rank_pairs(n, int(mix["batch"]), gen)
               for _ in range(int(mix["distinct_batches"]))]
    for i, (ls, rs) in enumerate(batches):
        t0 = time.monotonic()
        np.asarray(engine.query_bulk(ls, rs, mix["op"]))
        run.log(f"warm-up batch {i}: {time.monotonic() - t0:.3f} s")
    run.log(f"engine: {engine.stats()['class_counts']}")
    return State(mesh, index, engine, batches)


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
    seg_len = int(run.config["n"]) // int(run.config["segments"])
    return {
        "end_to_end": {"qps": queries / elapsed},
        # one segment: the memory peak is per chip
        "indexed_bytes": seg_len * 4,
        "attempted": queries,
        "failed": 0,
        "answers": answers,
        "traced_batch": st.batches[0],
        "summary": {"batches": i, "queries": queries,
                    "elapsed_s": elapsed, "batch_s": times},
    }


def check(run, st, record):
    """Free the program's state, then compare a seeded sample of every
    answered batch (the same rows of each answer of one batch)."""
    cfg, mix = run.config, run.traffic
    k = 1 if mix["op"] == "index" else 0
    batches = st.batches
    st.engine = st.index = None
    gc.collect()
    t0 = time.monotonic()
    x = lcpdata.device_lcp(run.seed, int(cfg["n"]), st.mesh,
                           cfg["segment_axis"])
    segs = lcpdata.host_segments(x)
    del x
    t1 = time.monotonic()
    ref = SegmentedRangeMinRef(segs)
    del segs
    t2 = time.monotonic()
    gen = data.rng(run.seed, 3)
    picks, wants = [], []
    for ls, rs in batches:
        pick = gen.choice(ls.shape[0], min(int(mix["check_sample"]),
                                           ls.shape[0]), replace=False)
        picks.append(pick)
        wants.append(ref.query(ls[pick], rs[pick])[k])
    wrong = compared = 0
    for b, out in record["answers"]:
        got = out[picks[b]] if out.shape == batches[b][0].shape else out
        wrong += mismatches(got, wants[b])
        compared += picks[b].shape[0]
    t3 = time.monotonic()
    seg_len = int(cfg["n"]) // int(cfg["segments"])
    for name, bounds in CONTROLS.items():
        bad = sum(mismatches(ref.query(*bounds(ls[pick], rs[pick],
                                                seg_len))[k], want)
                  for (ls, rs), pick, want in zip(batches, picks, wants))
        run.log(f"check control {name}: {bad} wrong of "
                f"{sum(p.shape[0] for p in picks)} sampled queries")
    run.log(f"check: {compared} sampled answers of "
            f"{len(record['answers'])} batches; host copy {t1 - t0:.1f} s, "
            f"reference {t2 - t1:.1f} s, compare {t3 - t2:.1f} s, "
            f"controls {time.monotonic() - t3:.1f} s")
    return [{"name": "wrong_answers", "value": wrong, "limit": 0}]
