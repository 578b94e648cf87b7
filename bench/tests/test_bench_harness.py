"""CPU tests of the benchmark harness: contract, discovery, yardstick.

Run from the repository root with the rest of the suite; nothing here
needs a chip.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import cellkit  # noqa: F401  (puts bench/ and src/ on the path)
from rmqbench import bytecount, data, harness, tracing
from rmqbench.reference import (
    RangeMinRef,
    level_geometry,
    reference_hierarchy,
)

ROOT = cellkit.ROOT
BENCH_JSON = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")


# ---------------------------------------------------------------------------
# BENCHMARK.json against the contract
# ---------------------------------------------------------------------------
def test_top_level_keys():
    assert set(BENCH_JSON) == {"command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH_JSON["run_seconds"] <= 51
    assert len(json.dumps(BENCH_JSON)) <= 64 * 1024
    for p in BENCH_JSON["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    cmd = BENCH_JSON["command"]
    assert 1 <= len(cmd) <= 32
    assert all(LINE.match(w) for w in cmd)
    files = [w for w in cmd if "/" in w]
    assert all(any(f.startswith(p + "/") for p in BENCH_JSON["paths"])
               for f in files)


@pytest.mark.parametrize("section,keys", [
    ("configs", {"name", "source", "file", "reduced", "why"}),
    ("workloads", {"name", "config", "traffic", "chips", "why"}),
])
def test_entries_have_exactly_their_keys(section, keys):
    for entry in BENCH_JSON[section]:
        assert set(entry) == keys, entry["name"]
        assert NAME.match(entry["name"])
        assert LINE.match(entry["why"])


def test_names_units_and_metric_keys():
    seen = set()
    for m in BENCH_JSON["end_to_end"] + BENCH_JSON["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in seen
        seen.add(m["name"])
        assert UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH_JSON["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH_JSON["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert LINE.match(m["layer"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_configs_cells_and_traffic_names():
    configs = {c["name"]: c for c in BENCH_JSON["configs"]}
    pairs = set()
    for c in configs.values():
        assert c["file"].startswith("bench/")
        doc = json.loads((ROOT / c["file"]).read_text())
        for key in c["reduced"]:
            assert NAME.match(key) and key in doc, key
        assert LINE.match(c["source"])
    files = [c["file"] for c in configs.values()]
    assert len(set(files)) == len(files)
    for w in BENCH_JSON["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    used = {w["config"] for w in BENCH_JSON["workloads"]}
    assert used == set(configs), "every configuration keeps a cell"


def test_every_cell_reports_setup_another_metric_and_a_layer():
    cells = {w["name"] for w in BENCH_JSON["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in BENCH_JSON["end_to_end"]}
    assert e2e["setup_s"] == cells
    for cell in cells:
        assert any(cell in ws for n, ws in e2e.items() if n != "setup_s")
        assert any(cell in m.get("workloads", cells)
                   for m in BENCH_JSON["per_layer"])
    for ws in e2e.values():
        assert ws <= cells


def test_each_layer_metric_moves_a_metric_its_cells_report():
    cells = {w["name"] for w in BENCH_JSON["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in BENCH_JSON["end_to_end"]}
    for m in BENCH_JSON["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in e2e[m["moves"]], (m["name"], cell)
        reader = ROOT / "bench" / "metrics" / f"{m['name']}.py"
        assert reader.is_file(), reader


def test_config_geometry_matches_the_plan_it_states():
    for path in sorted((ROOT / "bench" / "configs").glob("*.json")):
        doc = json.loads(path.read_text())
        cap = doc.get("capacity", doc.get("n"))
        lens, _, upper = level_geometry(cap, doc["c"], doc["t"])
        assert len(lens) == doc["levels"]
        if "upper_entries" in doc:
            assert upper == doc["upper_entries"]


def test_peaks_are_keyed_by_device_kind():
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.load_peaks("some other chip")


# ---------------------------------------------------------------------------
# discovery by name
# ---------------------------------------------------------------------------
def test_cell_finds_its_files_by_name(tmp_path):
    root = cellkit.tiny_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "other.json").write_text(
        json.dumps({"n": 64, "c": 4, "t": 2, "with_positions": False}))
    (root / "bench" / "traffic" / "tiny-mix.json").write_text(
        json.dumps({"driver": "rebuild", "traced_builds": 1}))
    (root / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench["configs"].append({"name": "other", "source": "x",
                             "file": "bench/configs/other.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "other.tiny", "config": "other",
                               "traffic": "tiny-mix", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "new_metric", "unit": "%",
                               "better": "lower", "source": "device_trace",
                               "layer": "device", "moves": "build_ms",
                               "workloads": ["other.tiny"]})
    next(m for m in bench["end_to_end"]
         if m["name"] == "build_ms")["workloads"].append("other.tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.Cell(root, "other.tiny")
    assert cell.config["n"] == 64
    assert cell.driver().__name__.endswith("rebuild")
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert cell.reader("new_metric")(None) == 42.0
    assert {m["name"] for m in cell.end_to_end} == {"build_ms", "mem_ratio",
                                                    "setup_s"}
    with pytest.raises(KeyError):
        harness.Cell(root, "no-such-cell")


# ---------------------------------------------------------------------------
# generators against brute force
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["large", "medium", "small", "mixed"])
def test_paper_queries_stay_in_range_and_repeat(kind):
    n = 1 << 16
    a = data.make_queries(n, 3000, kind, data.rng(5, 2))
    b = data.make_queries(n, 3000, kind, data.rng(5, 2))
    assert all(np.array_equal(u, v) for u, v in zip(a, b))
    ls, rs = a
    assert ls.dtype == np.int32 and (ls >= 0).all() and (rs < n).all()
    assert (ls <= rs).all()
    span = np.median(rs.astype(np.int64) - ls + 1)
    want = {"large": n / 2, "medium": n ** 0.6, "small": n ** 0.3}
    if kind in want:
        assert 0.7 * want[kind] < span < 1.3 * want[kind]


def _fnv_brute(v: int) -> int:
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= v & 0xFF
        h = (h * 1099511628211) % (1 << 64)
        v >>= 8
    if h >= 1 << 63:
        h -= 1 << 64
    return abs(h)


def test_fnv_hash_matches_ycsb_definition():
    vals = np.array([0, 1, 2, 255, 256, 123456789, 10**10 - 1], np.int64)
    got = data.fnv_hash64(vals)
    assert [int(g) for g in got] == [_fnv_brute(int(v)) for v in vals]


def test_zipfian_favours_few_items_and_stays_in_range():
    keys = data.scrambled_zipfian(20000, 1000, 0.99, data.rng(1, 1))
    assert keys.min() >= 0 and keys.max() < 1000
    counts = np.sort(np.bincount(keys, minlength=1000))[::-1]
    assert counts[:10].sum() > 0.1 * keys.size      # a hot head
    raw = data._zipfian(np.array([0.0, 0.5 / data._YCSB_ZETAN]), 0.99)
    assert list(raw) == [0, 0]


def test_request_stream_is_a_function_of_the_seed():
    mix = json.loads((ROOT / "bench" / "traffic" / "ycsb-e.json").read_text())
    mix = dict(mix, seconds=4.0, rate_per_s=500)
    a = data.request_stream(mix, 10000, 2**33 + 5)
    b = data.request_stream(mix, 10000, 2**33 + 5)
    c = data.request_stream(mix, 10000, 6)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["ls"], c["ls"][: a["ls"].shape[0]])
    assert (a["at"] < 4.0).all() and np.all(np.diff(a["at"]) >= 0)
    assert 1500 < a["at"].shape[0] < 2500
    lens = a["rs"].astype(np.int64) - a["ls"] + 1
    assert lens.min() >= 1 and lens.max() <= 100 and a["rs"].max() < 10000
    assert 0.02 < a["insert"].mean() < 0.08


def test_device_data_repeats_and_is_uniform():
    x = data.host_copy(data.device_uniform(2**40 + 3, 5000, block=1024))
    y = data.host_copy(data.device_uniform(2**40 + 3, 5000, block=1024))
    z = data.host_copy(data.device_uniform(4, 5000, block=1024))
    assert np.array_equal(x, y) and not np.array_equal(x, z)
    assert x.dtype == np.float32 and (x >= 0).all() and (x < 1).all()
    assert abs(float(x.mean()) - 0.5) < 0.05


# ---------------------------------------------------------------------------
# the reference and the byte counts against brute force
# ---------------------------------------------------------------------------
def test_reference_matches_brute_force_with_ties():
    rng = np.random.default_rng(0)
    x = rng.integers(0, 5, 3000).astype(np.float32)      # many ties
    ls = rng.integers(0, 3000, 800)
    rs = np.minimum(ls + rng.integers(0, 2500, 800), 2999)
    v, p = RangeMinRef(x, block=64).query(ls, rs)
    for i in range(800):
        seg = x[ls[i]:rs[i] + 1]
        assert v[i] == seg.min() and p[i] == ls[i] + int(np.argmin(seg))


def test_reference_hierarchy_matches_level_minima():
    rng = np.random.default_rng(1)
    x = rng.random(1000).astype(np.float32)
    lens, offs, size = level_geometry(1000, 8, 2)
    up, pos = reference_hierarchy(x, 1000, 8, 2, with_positions=True)
    assert up.shape == (size,)
    lvl1 = up[offs[0]:offs[0] + lens[1]]
    want = np.array([x[i:i + 8].min() for i in range(0, 1000, 8)])
    assert np.array_equal(lvl1, want)
    assert np.array_equal(x[pos[offs[0]:offs[0] + lens[1]]], want)


def _pairs_brute(ls, rs, capacity, c, t):
    lens, _, _ = level_geometry(capacity, c, t)
    seen = set()
    for a, b in zip(ls.tolist(), rs.tolist()):
        for k in range(len(lens)):
            ca, cb = a // c, b // c
            if k == len(lens) - 1:
                seen.update((k, j) for j in range(ca, cb + 1))
                break
            seen.update({(k, ca), (k, cb)})
            if cb - ca <= 1:
                break
            a, b = ca + 1, cb - 1
    return len(seen)


@pytest.mark.parametrize("n,c,t", [(1000, 4, 2), (4096, 8, 4), (777, 2, 3)])
def test_query_bytes_match_brute_force(n, c, t):
    rng = np.random.default_rng(n)
    ls, rs = data.make_queries(n, 300, "mixed", rng)
    got = bytecount.query_chunk_pairs(ls, rs, n, c, t)
    assert got == _pairs_brute(ls, rs, n, c, t)
    assert bytecount.query_bytes(ls, rs, n, c, t) == got * c * 4


def test_build_bytes_count_level0_and_upper_planes():
    _, _, upper = level_geometry(1 << 28, 128, 64)
    assert upper == 2113664
    assert bytecount.build_bytes(1 << 28, 1 << 28, 128, 64, False) == (
        (1 << 30) + upper * 4)
    assert bytecount.build_bytes(100, 128, 8, 2, True) == 400 + (
        level_geometry(128, 8, 2)[2] * 8)


# ---------------------------------------------------------------------------
# the trace reduction, on a trace recorded on a v5e chip
# ---------------------------------------------------------------------------
TRACE = cellkit.BENCH / "testdata" / "small.xplane.pb"


def test_trace_reduces_to_fixed_numbers():
    dt = tracing.reduce_trace(TRACE)
    assert dt.chips == 1
    assert dt.t0_ns == 41806079 and dt.t1_ns == 66715284
    assert dt.busy == [[(46490176, 46510312)]]
    assert dt.busy_s == pytest.approx(2.0136e-05, abs=1e-12)
    assert dt.window_s == pytest.approx(0.024909205, abs=1e-12)
    assert dt.idle_share == pytest.approx(1 - 2.0136e-05 / 0.024909205)
    assert dt.modules == pytest.approx({"jit__build_jit": 2.0136e-05})
    assert dt.ops == pytest.approx({"build_level": 6.91e-06,
                                    "copy": 1.2955e-05})
    names = dict(tracing.name_gaps(dt))
    assert names == pytest.approx({"bench.batch": 0.020204972,
                                   "none": 0.004684097})


def test_interval_helpers():
    assert tracing.merge([(5, 9), (1, 3), (2, 4), (9, 10), (7, 7)]) == [
        (1, 4), (5, 10)]
    assert tracing.clip([(1, 4), (5, 10)], 3, 6) == [(3, 4), (5, 6)]
    assert tracing.op_name("%build_level.3 = f32[2] custom-call(x)") == \
        "build_level"
    assert tracing.op_name("%while.11 = (s32[]) while(x)") == "while"
    assert tracing.module_name("jit__run(123)") == "jit__run"


# ---------------------------------------------------------------------------
# the entry point refuses to run without a chip or without the program
# ---------------------------------------------------------------------------
def _run_py(cwd: Path, env_extra: dict):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper-build",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    out = _run_py(ROOT, {})
    assert out.returncode == 1, out.stderr[-2000:]
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_run_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(cellkit.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    out = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
