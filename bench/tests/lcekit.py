"""Tiny sizes of the four-chip LCE cell, for tests on the CPU.

``cellkit`` keeps one table of tiny sizes per configuration and traffic
mix; :func:`register` adds this cell's to them (``conftest.py`` calls it
before any test runs), and :func:`run_tiny` runs the cell on a fake
four-device CPU mesh in a child process, whose result it returns.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import cellkit

CONFIG = "lce-grch38x2-4chip"
TRAFFIC = "lce-rank-bulk"
CELL = "lce-rank-bulk-4chip"
# four segments of 8,192 entries, a two-level walk on each; batches below
# the engine's bulk crossover (8,192 here), so they take the routed path
# as the cell's do on the chip, and together above its 8,192-entry cache
TINY = {"n": 32768, "c": 16, "t": 32}
TINY_TRAFFIC = {"batch": 6000, "check_sample": 1024}


def register() -> None:
    cellkit.TINY.setdefault(CONFIG, TINY)
    cellkit.TINY_TRAFFIC.setdefault(TRAFFIC, TINY_TRAFFIC)


_CHILD = r"""
import json, sys, pathlib
sys.path.insert(0, {tests!r})
import cellkit, lcekit
lcekit.register()
{patch}
out = cellkit.run_tiny(pathlib.Path({tmp!r}), lcekit.CELL, seed={seed},
                       seconds={seconds}, trace={trace})
print("RESULT " + json.dumps(out))
"""


def run_tiny(tmp, seed: int = 2**31 + 77, seconds: float = 0.6,
             trace: bool = False, patch: str = "") -> dict:
    """The tiny cell's result line, from a child process with four CPU
    devices (``patch`` is Python run there first, e.g. to plant a
    fault)."""
    prog = _CHILD.format(tests=str(cellkit.BENCH / "tests"), tmp=str(tmp),
                         seed=seed, seconds=seconds, trace=trace,
                         patch=patch)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, cwd=cellkit.ROOT, timeout=600)
    lines = [ln for ln in res.stdout.splitlines()
             if ln.startswith("RESULT ")]
    assert lines, res.stdout[-4000:] + res.stderr[-4000:]
    return json.loads(lines[-1][len("RESULT "):])
