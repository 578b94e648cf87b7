"""Dry-run machinery tests (reduced scale; the production 512-device runs
live in launch/dryrun.py and are logged in EXPERIMENTS.md).

Runs in subprocesses so the fake-device flag never leaks into pytest."""

import json
import subprocess
import sys

import jax
import pytest

from repro.launch.cells import (
    SHAPES,
    cell_is_skipped,
    collective_bytes_from_hlo,
)
from repro.configs.base import ARCH_IDS


def test_shape_grid_is_the_assigned_40_cells():
    cells = [(a, s) for a in ARCH_IDS for s in SHAPES]
    assert len(cells) == 40
    skips = [c for c in cells if cell_is_skipped(*c)]
    assert len(skips) == 8  # long_500k for the 8 full-attention archs
    assert all(s == "long_500k" for _, s in skips)
    for arch in ("mamba2-1.3b", "hymba-1.5b"):
        assert cell_is_skipped(arch, "long_500k") is None


def test_collective_parser():
    hlo = """
  %ag = bf16[64,2816]{1,0} all-gather(bf16[4,2816]{1,0} %p), replica_groups={}
  %ar.1 = f32[128]{0} all-reduce(f32[128]{0} %x), to_apply=%add
  %rs = (f32[16]{0}, f32[16]{0}) reduce-scatter(f32[256]{0} %y, f32[256]{0} %z)
  %nothing = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b)
"""
    got = collective_bytes_from_hlo(hlo)
    assert got["all-gather"] == 64 * 2816 * 2
    assert got["all-reduce"] == 128 * 4
    assert got["reduce-scatter"] == 2 * 16 * 4
    assert "add" not in got


def test_process_sees_one_device():
    assert jax.device_count() == 1


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json, jax
from repro.launch.mesh import make_test_mesh
from repro.launch.cells import train_cell, decode_cell, collective_bytes_from_hlo
from repro.configs.base import get_smoke_config, TrainConfig

mesh = make_test_mesh((2, 4), ("data", "model"))
cfg = get_smoke_config("llama3.2-3b")

# train cell on the reduced config: lower + compile + analyses
tc = TrainConfig(seq_len=64, global_batch=8, remat_policy="full")
fn, args, _ = train_cell(cfg, mesh, 64, 8, tc=tc)
with mesh:
    lowered = jax.jit(fn, donate_argnums=(0,)).lower(*args)
    compiled = lowered.compile()
ca = compiled.cost_analysis()
ma = compiled.memory_analysis()
assert ca.get("flops", 0) > 0
assert ma.argument_size_in_bytes > 0
colls = collective_bytes_from_hlo(compiled.as_text())
assert sum(colls.values()) > 0, colls

# decode cell
fn, args = decode_cell(cfg, mesh, 128, 8)
with mesh:
    compiled = jax.jit(fn, donate_argnums=(2,)).lower(*args).compile()
assert compiled.cost_analysis().get("flops", 0) > 0
print("DRYRUN_SMOKE_OK")
"""


def test_dryrun_cells_compile_on_fake_mesh():
    res = subprocess.run(
        [sys.executable, "-c", _SUBPROC],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert "DRYRUN_SMOKE_OK" in res.stdout, res.stdout + res.stderr


def test_production_dryrun_artifacts_if_present():
    """When the full 512-device sweeps have been run, validate them."""
    import os

    path = "results/dryrun_multi.jsonl"
    if not os.path.exists(path):
        pytest.skip("full dry-run artifacts not generated in this checkout")
    recs = [json.loads(l) for l in open(path)]
    by_cell = {(r["arch"], r["shape"]): r for r in recs}
    assert len(by_cell) == 40
    for (arch, shape), r in by_cell.items():
        if cell_is_skipped(arch, shape):
            assert r.get("skipped"), (arch, shape)
        else:
            assert r.get("ok"), (arch, shape, r.get("error"))
            assert r["flops_per_device"] > 0


_CACHE_PROBE = r"""
import jax
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    """The cache goes where JAX_COMPILATION_CACHE_DIR says, else to the
    fixed <repo>/.jax_cache; nothing else is set in code."""
    import os
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    env = {"PYTHONPATH": str(root / "src"), "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": "cpu"}
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    res = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], capture_output=True,
        text=True, timeout=120, env=env, cwd=tmp_path,
    )
    assert res.returncode == 0, res.stderr
    want = str(tmp_path) if from_env else str(root / ".jax_cache")
    assert res.stdout.split() == [want, want]
    assert not os.listdir(tmp_path)  # nothing compiled, nothing written
