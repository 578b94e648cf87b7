"""Dry-run cells: (architecture × input shape × mesh) → lower/compile/analyse.

One *cell* = one entry of the assigned 40-cell grid.  For each cell this
module builds:

* the step function (``train_step`` for train shapes, ``prefill`` /
  ``serve_step`` for inference shapes),
* fully-sharded ``jax.ShapeDtypeStruct`` stand-ins for every input
  (weights, optimizer state, batches, KV caches — no allocation ever),
* the lower→compile pipeline, returning roofline raw numbers:
  per-device HLO FLOPs / bytes (``cost_analysis``), per-device memory
  (``memory_analysis``) and per-collective operand bytes parsed from the
  partitioned HLO.

Shape grid (assignment):
  train_4k     seq 4096   global_batch 256   -> train_step
  prefill_32k  seq 32768  global_batch 32    -> prefill
  decode_32k   seq 32768  global_batch 128   -> serve_step (1 new token)
  long_500k    seq 524288 global_batch 1     -> serve_step, SSM/hybrid only
"""

from __future__ import annotations

import dataclasses
import functools
import re
from collections import defaultdict
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (
    ARCH_IDS,
    ModelConfig,
    TrainConfig,
    get_config,
)
from repro.distributed.shardings import (
    batch_shardings,
    cache_shardings,
    make_sharder,
    param_shardings,
    train_state_shardings,
)
from repro.models.lm import decode_step, init_params, make_decode_cache, prefill
from repro.train.train_step import build_train_step, init_train_state

SHAPES = {
    "train_4k": dict(kind="train", seq_len=4096, global_batch=256),
    "prefill_32k": dict(kind="prefill", seq_len=32768, global_batch=32),
    "decode_32k": dict(kind="decode", seq_len=32768, global_batch=128),
    "long_500k": dict(kind="decode", seq_len=524288, global_batch=1),
}

# long_500k requires sub-quadratic attention: runs only for SSM/hybrid.
LONG_CONTEXT_ARCHS = ("mamba2-1.3b", "hymba-1.5b")

# Per-arch best configuration found by the §Perf hillclimb (EXPERIMENTS.md).
# layout: "fsdp" (pure ZeRO-3) wins for big dense models at ~1 seq/device;
# "tp_sp" (tensor parallel + Megatron-SP + shard_map MoE dispatch) wins
# for MoE and small/mid dense models.
BEST_CONFIG = {
    # fsdp (pure ZeRO-3) needs batch >= chips: right for command-r TRAIN
    # (256 seqs / 256 chips), wrong for its 32-seq prefill — layouts are
    # per (arch, shape-kind).
    ("command-r-plus-104b", "train"): dict(layout="fsdp", remat="full"),
}
DEFAULT_BEST = dict(layout="tp_sp", remat="full")


def best_config(arch: str, shape: Optional[str] = None,
                num_chips: int = 256):
    kind = SHAPES[shape]["kind"] if shape in SHAPES else None
    bc = BEST_CONFIG.get((arch, kind), DEFAULT_BEST)
    if bc["layout"] == "fsdp" and shape in SHAPES             and SHAPES[shape]["global_batch"] < num_chips:
        # pure ZeRO-3 needs batch >= chips; below that the model axis
        # would recompute every token redundantly — fall back to tp_sp
        return DEFAULT_BEST
    return bc


def cell_is_skipped(arch: str, shape: str) -> Optional[str]:
    if shape == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
        return (
            "long_500k needs sub-quadratic attention; "
            f"{arch} is a full-attention arch (DESIGN.md §6)"
        )
    return None


def all_cells():
    for arch in ARCH_IDS:
        for shape in SHAPES:
            yield arch, shape


# ---------------------------------------------------------------------------
# ShapeDtypeStruct builders (weak-type-correct, shardable, no allocation)
# ---------------------------------------------------------------------------
def _with_shardings(struct_tree, sharding_tree):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        struct_tree,
        sharding_tree,
    )


def train_cell(
    cfg: ModelConfig,
    mesh,
    seq_len: int,
    global_batch: int,
    tc: Optional[TrainConfig] = None,
    sequence_sharding: bool = True,
    unroll: bool = False,
    layout: str = "tp_sp",
):
    """Returns (fn, example_args) ready for jit(...).lower(*args)."""
    tc = tc or TrainConfig(
        seq_len=seq_len,
        global_batch=global_batch,
        remat_policy="minimal",
        optimizer_state_dtype=(
            "bfloat16" if cfg.num_params() > 2e11 else "float32"
        ),
        loss_chunk=(512 if (cfg.padded_vocab >= 65536
                    and cfg.num_params() > 5e10) else 0),
    )
    sharder = make_sharder(mesh, sequence_sharding=sequence_sharding,
                           layout=layout)
    state_struct = jax.eval_shape(
        lambda: init_train_state(cfg, tc, jax.random.PRNGKey(0))
    )
    state_sh = train_state_shardings(mesh, state_struct, layout)
    step_fn = build_train_step(cfg, tc, sharder=sharder, unroll=unroll,
                               grad_shardings=state_sh.params)
    state = _with_shardings(state_struct, state_sh)

    batch_struct = {
        "tokens": jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32),
    }
    if cfg.frontend:
        batch_struct["prefix"] = jax.ShapeDtypeStruct(
            (global_batch, cfg.frontend_tokens, cfg.d_model), jnp.float32
        )
    batch = _with_shardings(
        batch_struct, batch_shardings(mesh, batch_struct, layout)
    )
    return step_fn, (state, batch), tc


def params_struct_sharded(cfg: ModelConfig, mesh):
    p_struct = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))
    )
    return _with_shardings(p_struct, param_shardings(mesh, p_struct))


def prefill_cell(cfg: ModelConfig, mesh, seq_len: int, global_batch: int,
                 unroll: bool = False):
    sharder = make_sharder(mesh)
    cache_dtype = jnp.bfloat16

    def fn(params, tokens, prefix=None):
        return prefill(
            cfg, params, tokens,
            cache_len=seq_len,
            prefix_embeddings=prefix,
            cache_dtype=cache_dtype,
            sharder=sharder,
            unroll=unroll,
        )

    params = params_struct_sharded(cfg, mesh)
    tok_struct = {
        "tokens": jax.ShapeDtypeStruct(
            (global_batch, seq_len - (cfg.frontend_tokens if cfg.frontend
                                      else 0)),
            jnp.int32,
        )
    }
    if cfg.frontend:
        tok_struct["prefix"] = jax.ShapeDtypeStruct(
            (global_batch, cfg.frontend_tokens, cfg.d_model), jnp.float32
        )
    tok_sh = batch_shardings(mesh, tok_struct)
    tok = _with_shardings(tok_struct, tok_sh)
    args = (params, tok["tokens"])
    if cfg.frontend:
        args = args + (tok["prefix"],)
    return fn, args


def decode_cell(cfg: ModelConfig, mesh, seq_len: int, global_batch: int,
                unroll: bool = False):
    sharder = make_sharder(mesh)

    def fn(params, token, cache, pos):
        return decode_step(cfg, params, token, cache, pos, sharder=sharder,
                           unroll=unroll)

    params = params_struct_sharded(cfg, mesh)
    cache_struct = jax.eval_shape(
        lambda: make_decode_cache(cfg, global_batch, seq_len, jnp.bfloat16)
    )
    cache = _with_shardings(cache_struct, cache_shardings(mesh, cache_struct))
    token = jax.ShapeDtypeStruct((global_batch,), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return fn, (params, token, cache, pos)


# ---------------------------------------------------------------------------
# Lower + compile + analyse
# ---------------------------------------------------------------------------
# result type may be a tuple "(f32[..], f32[..])" for variadic collectives
_COLLECTIVE_RE = re.compile(
    r"(\S+)\s*=\s*(\(?[a-z0-9\[\]{},/_\s]*?\)?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, float]:
    """Sum result-shape bytes of every collective op in partitioned HLO.

    Uses the *result* shape of each collective as the per-device payload
    proxy (for all-reduce this is the operand size; for all-gather the
    gathered size; ring-transfer factors are applied by the roofline
    model, not here).  Ops inside while-loop bodies are counted once per
    occurrence; the roofline model multiplies by trip counts where known
    (layer-scan collectives dominate and scale with num_layers — see
    benchmarks/roofline.py).
    """
    totals: Dict[str, float] = defaultdict(float)
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue  # async pair: count the -start only
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        kind = m.group(3)
        # result type(s) sit between '=' and the op name
        eq = line.find("=")
        if eq < 0:
            continue
        rhs = line[eq + 1 : m.start(3)]
        shapes = _SHAPE_RE.findall(rhs)
        nbytes = 0.0
        for dt, dims in shapes:
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        totals[kind] += nbytes
    return dict(totals)


def while_trip_counts(hlo_text: str) -> int:
    """Best-effort count of while ops (layer scans) in the module."""
    return hlo_text.count(" while(")


@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh_desc: str
    flops_per_device: float
    bytes_per_device: float
    argument_bytes: float
    output_bytes: float
    temp_bytes: float
    collective_bytes: Dict[str, float]
    num_while_loops: int
    scan_length: int
    compile_seconds: float
    skipped: Optional[str] = None

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def run_cell(arch: str, shape: str, mesh, mesh_desc: str,
             sequence_sharding: bool = True,
             remat_policy: str = "minimal",
             microbatches: int = 1,
             layers_override: Optional[int] = None,
             unroll: bool = False,
             layout: str = "tp_sp") -> CellResult:
    import time

    skip = cell_is_skipped(arch, shape)
    if skip:
        return CellResult(
            arch=arch, shape=shape, mesh_desc=mesh_desc,
            flops_per_device=0, bytes_per_device=0, argument_bytes=0,
            output_bytes=0, temp_bytes=0, collective_bytes={},
            num_while_loops=0, scan_length=0, compile_seconds=0,
            skipped=skip,
        )

    cfg = get_config(arch)
    if layers_override is not None:
        cfg = dataclasses.replace(cfg, num_layers=layers_override)
    spec = SHAPES[shape]
    from repro.models.lm import _num_scan_steps

    if spec["kind"] == "train":
        tc = TrainConfig(
            seq_len=spec["seq_len"], global_batch=spec["global_batch"],
            remat_policy=remat_policy, microbatches=microbatches,
            optimizer_state_dtype=(
                "bfloat16" if cfg.num_params() > 2e11 else "float32"
            ),
            loss_chunk=(512 if (cfg.padded_vocab >= 65536
                    and cfg.num_params() > 5e10) else 0),
        )
        fn, args, _ = train_cell(
            cfg, mesh, spec["seq_len"], spec["global_batch"], tc=tc,
            sequence_sharding=sequence_sharding, unroll=unroll,
            layout=layout,
        )
        donate = (0,)   # donate TrainState: params/opt buffers reused
    elif spec["kind"] == "prefill":
        fn, args = prefill_cell(cfg, mesh, spec["seq_len"],
                                spec["global_batch"], unroll=unroll)
        donate = ()
    else:
        fn, args = decode_cell(cfg, mesh, spec["seq_len"],
                               spec["global_batch"], unroll=unroll)
        donate = (2,)   # donate the cache: decode updates in place

    t0 = time.time()
    with mesh:
        lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
        compiled = lowered.compile()
    dt = time.time() - t0

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    return CellResult(
        arch=arch,
        shape=shape,
        mesh_desc=mesh_desc,
        flops_per_device=float(ca.get("flops", 0.0)),
        bytes_per_device=float(ca.get("bytes accessed", 0.0)),
        argument_bytes=float(getattr(ma, "argument_size_in_bytes", 0)),
        output_bytes=float(getattr(ma, "output_size_in_bytes", 0)),
        temp_bytes=float(getattr(ma, "temp_size_in_bytes", 0)),
        collective_bytes=collective_bytes_from_hlo(hlo),
        num_while_loops=while_trip_counts(hlo),
        scan_length=_num_scan_steps(cfg),
        compile_seconds=dt,
    )


# ---------------------------------------------------------------------------
# Calibration: exact per-layer FLOPs/bytes/collectives via unrolled compiles
# ---------------------------------------------------------------------------
def calibrate_cell(arch: str, shape: str, mesh, mesh_desc: str,
                   sequence_sharding: bool = True,
                   remat_policy: str = "minimal",
                   microbatches: int = 1,
                   layout: str = "tp_sp") -> Dict[str, Any]:
    """XLA cost analysis counts while-loop (layer scan) bodies ONCE.

    Fix: compile the same cell with 2 and 4 layers, *unrolled* (no while),
    solve  F(L) = once + L * per_layer  exactly, and extrapolate to the
    production depth.  Layer bodies are depth-independent (same shapes), so
    the extrapolation is exact for FLOPs/bytes/collectives.  Memory numbers
    always come from the production compile (run_cell), never from here.
    """
    cfg = get_config(arch)
    period = 2 if (cfg.uses_moe and cfg.moe_layer_period == 2) else 1
    l_small, l_big = 2 * period, 4 * period

    res = {}
    for lo in (l_small, l_big):
        res[lo] = run_cell(
            arch, shape, mesh, mesh_desc,
            sequence_sharding=sequence_sharding,
            remat_policy=remat_policy,
            microbatches=microbatches,
            layers_override=lo, unroll=True,
            layout=layout,
        )

    dl = l_big - l_small
    per_layer_flops = (res[l_big].flops_per_device
                       - res[l_small].flops_per_device) / dl
    per_layer_bytes = (res[l_big].bytes_per_device
                       - res[l_small].bytes_per_device) / dl
    once_flops = res[l_small].flops_per_device - l_small * per_layer_flops
    once_bytes = res[l_small].bytes_per_device - l_small * per_layer_bytes

    coll_kinds = set(res[l_small].collective_bytes) | set(
        res[l_big].collective_bytes)
    per_layer_coll, once_coll = {}, {}
    for kind in coll_kinds:
        a = res[l_small].collective_bytes.get(kind, 0.0)
        b = res[l_big].collective_bytes.get(kind, 0.0)
        per_layer_coll[kind] = (b - a) / dl
        once_coll[kind] = a - l_small * per_layer_coll[kind]

    L = cfg.num_layers
    return {
        "arch": arch,
        "shape": shape,
        "mesh_desc": mesh_desc,
        "num_layers": L,
        "flops_per_device": once_flops + L * per_layer_flops,
        "bytes_per_device": once_bytes + L * per_layer_bytes,
        "collective_bytes": {
            k: once_coll[k] + L * per_layer_coll[k] for k in coll_kinds
        },
        "per_layer_flops": per_layer_flops,
        "once_flops": once_flops,
        "per_layer_bytes": per_layer_bytes,
        "once_bytes": once_bytes,
        "per_layer_collectives": per_layer_coll,
    }
