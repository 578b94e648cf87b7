"""The benchmark's ``correct`` on the CPU, at tiny sizes.

Each cell runs whole (set-up, window, check) with the chip check skipped:
sound, it reads ``correct``; with the timed path broken underneath, or
with the control (the reference one precision lower) in the program's
place, it reads not correct.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np
import pytest

import cellkit
from rmqbench.reference import RangeMinRef, reference_hierarchy, to_bfloat16

CELLS = ["paper-mixed-bulk", "paper-build", "ycsb-e-serve", "ycsb-c-serve"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(tmp_path, workload):
    out = cellkit.run_tiny(tmp_path, workload, seconds=0.6)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(c["limit"] == 0 for c in out["checks"].values())
    assert list(out)[-1] == "checks"
    assert "setup_s" in out["metrics"]


@pytest.mark.parametrize("workload", ["paper-mixed-bulk", "ycsb-c-serve"])
def test_traced_run_reports_layer_metrics(tmp_path, workload):
    out = cellkit.run_tiny(tmp_path, workload, seconds=0.6, trace=True)
    assert out["correct"]
    assert "breakdown" in out and "window_s" in out["device"]
    want = {"paper-mixed-bulk": "engine.self_ms.offline",
            "ycsb-c-serve": "engine.self_ms.serve"}[workload]
    assert out["metrics"][want]["value"] > 0


# -- faults planted in the timed path ----------------------------------------
def _bulk_altered(orig):
    @functools.wraps(orig)
    def f(self, ls, rs, op="value"):
        out = np.array(orig(self, ls, rs, op))
        out[::50] += 1                          # an answer altered
        return jnp.asarray(out)
    return f


def _bulk_half(orig):
    @functools.wraps(orig)
    def f(self, ls, rs, op="value"):
        out = np.array(orig(self, ls, rs, op))
        out[out.shape[0] // 2:] = 0             # half of the batch left out
        return jnp.asarray(out)
    return f


def _build_unwritten(orig):
    @staticmethod
    def f(x, *a, **k):
        idx = orig(x, *a, **k)
        h = idx.hierarchy
        import dataclasses
        h2 = dataclasses.replace(h, upper=jnp.full_like(h.upper, jnp.inf))
        return dataclasses.replace(idx, hierarchy=h2)   # state unchanged
    return f


def _serve_altered(orig):
    @functools.wraps(orig)
    def f(self, ls, rs):
        out = np.array(orig(self, ls, rs))
        out[::7] += 1                           # an answer altered
        return jnp.asarray(out)
    return f


def _append_lost(orig):
    @functools.wraps(orig)
    def f(self, vals):
        return None                             # an insert acknowledged, lost
    return f


FAULTS = [
    ("paper-mixed-bulk", "repro.qe.engine", "QueryEngine", "query_bulk",
     _bulk_altered),
    ("paper-mixed-bulk", "repro.qe.engine", "QueryEngine", "query_bulk",
     _bulk_half),
    ("paper-build", "repro.core.api", "RMQ", "build", _build_unwritten),
    ("ycsb-c-serve", "repro.qe.engine", "QueryEngine", "query_index",
     _serve_altered),
    ("ycsb-e-serve", "repro.qe.engine", "QueryEngine", "query_index",
     _serve_altered),
    ("ycsb-e-serve", "repro.serving.snapshot", "SnapshotSlot",
     "stage_append", _append_lost),
]


@pytest.mark.parametrize("workload,module,cls,attr,fault", FAULTS,
                         ids=[f"{w}-{f.__name__}" for w, *_, f in FAULTS])
def test_fault_is_caught(tmp_path, monkeypatch, workload, module, cls, attr,
                         fault):
    import importlib

    owner = getattr(importlib.import_module(module), cls)
    orig = getattr(owner, attr)
    monkeypatch.setattr(owner, attr, fault(orig))
    out = cellkit.run_tiny(tmp_path, workload, seconds=0.6)
    assert not out["correct"], out["checks"]


# -- the control: the reference in bfloat16, in the program's place ----------
def _host(index):
    return np.asarray(index.hierarchy.base)[: index.n]


def _control_bulk(orig):
    def f(self, ls, rs, op="value"):
        v, p = RangeMinRef(to_bfloat16(_host(self.index))).query(ls, rs)
        return jnp.asarray(p if op == "index" else v)
    return f


def _control_build(orig):
    @staticmethod
    def f(x, *a, **k):
        import dataclasses
        idx = orig(x, *a, **k)
        plan = idx.plan
        up, _ = reference_hierarchy(to_bfloat16(np.asarray(x)),
                                    plan.capacity, plan.c, plan.t)
        h2 = dataclasses.replace(idx.hierarchy, upper=jnp.asarray(up))
        return dataclasses.replace(idx, hierarchy=h2)
    return f


@pytest.mark.parametrize("workload,module,cls,attr,control", [
    ("paper-mixed-bulk", "repro.qe.engine", "QueryEngine", "query_bulk",
     _control_bulk),
    ("paper-build", "repro.core.api", "RMQ", "build", _control_build),
])
def test_control_is_not_correct(tmp_path, monkeypatch, workload, module, cls,
                                attr, control):
    import importlib

    owner = getattr(importlib.import_module(module), cls)
    monkeypatch.setattr(owner, attr, control(getattr(owner, attr)))
    out = cellkit.run_tiny(tmp_path, workload, seconds=0.6)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("workload", ["ycsb-e-serve", "ycsb-c-serve"])
def test_serving_control_reads_above_the_limit(tmp_path, workload):
    """The serving control, at the cell's own load over a longer stream:
    bfloat16 rounding moves leftmost positions on a share of the scans."""
    import importlib.util

    from rmqbench import harness

    spec = importlib.util.spec_from_file_location(
        "bench_control", cellkit.BENCH / "control.py")
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    cell = harness.Cell(cellkit.tiny_root(tmp_path), workload)
    out = control.serve(cell, 11, seconds=60.0)
    assert out["compared"] > 5000
    assert out["wrong_positions"] > 0
