"""Rebuild loop: ``RMQ.build`` over the device-resident array, back to back.

Set-up makes the array on the device from the seed and builds twice (the
first build compiles).  The window drops the previous index, builds
again and blocks until the new index's planes are complete, until
``--seconds`` have passed; the last build ends the window.  The check
compares the last index's planes (level 0 and every upper level) with
the plain reference's.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from rmqbench import data
from rmqbench.reference import level_geometry, mismatches, \
    reference_hierarchy


class State:
    def __init__(self, x):
        self.x = x


def _build(run, x):
    import jax

    from repro.core import RMQ

    cfg = run.config
    index = RMQ.build(x, c=cfg["c"], t=cfg["t"],
                      with_positions=cfg["with_positions"])
    h = index.hierarchy
    jax.block_until_ready([h.base, h.upper]
                          + ([h.upper_pos] if h.with_positions else []))
    return index


def setup(run):
    x = data.device_uniform(run.seed, int(run.config["n"]))
    for i in range(2):
        t0 = time.monotonic()
        index = _build(run, x)
        del index
        run.log(f"warm-up build {i}: {time.monotonic() - t0:.3f} s")
    return State(x)


def window(run, st):
    traced = int(run.traffic["traced_builds"])
    index = None
    builds = 0
    t0 = time.monotonic()
    while True:
        if builds == 0:
            run.trace_start()
        index = None                    # the previous index is dropped
        with run.span("build"):
            index = _build(run, st.x)
        builds += 1
        if builds == traced:
            run.trace_stop()
        if time.monotonic() - t0 >= run.seconds and builds >= traced:
            break
    elapsed = time.monotonic() - t0
    h = index.hierarchy
    produced = {
        "base": np.asarray(h.base),
        "upper": np.asarray(h.upper),
        "upper_pos": (np.asarray(h.upper_pos) if h.with_positions
                      else None),
    }
    return {
        "end_to_end": {"build_ms": elapsed / builds * 1e3},
        "indexed_bytes": int(run.config["n"]) * 4,
        "attempted": builds,
        "failed": 0,
        "produced": produced,
        "traced_builds": traced,
        "summary": {"builds": builds, "elapsed_s": elapsed},
    }


def check(run, st, record):
    """Free the program's state, then compare every plane."""
    cfg = run.config
    n, c, t = int(cfg["n"]), int(cfg["c"]), int(cfg["t"])
    st.x = None
    gc.collect()
    x = data.device_uniform(run.seed, n)
    xh = data.host_copy(x)
    del x
    capacity = n
    want_u, want_p = reference_hierarchy(xh, capacity, c, t,
                                         cfg["with_positions"])
    got = record["produced"]
    wrong_base = mismatches(got["base"][:n], xh) + int(
        np.count_nonzero(got["base"][n:] != np.inf))
    wrong_upper = mismatches(got["upper"], want_u)
    if want_p is not None:
        wrong_upper += mismatches(got["upper_pos"], want_p)
    _, _, upper_size = level_geometry(capacity, c, t)
    run.log(f"check: level 0 ({n} entries) and {upper_size} upper entries")
    return [{"name": "wrong_level0", "value": wrong_base, "limit": 0},
            {"name": "wrong_upper", "value": wrong_upper, "limit": 0}]
