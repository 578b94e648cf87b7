"""Mixture-of-Experts layer: top-k routing, capacity dispatch, shared expert.

Dispatch strategy (TPU/GSPMD-conscious):

* routing + dispatch are *local to each data shard* — tokens never cross
  the data axis.  The dispatch buffer is built with gather/scatter of
  token vectors (memory O(T·k·D)), never a (T, E, C) one-hot tensor
  (which is O(T·E·C) and infeasible at production T).
* expert FFNs run as batched einsums over the expert dim, so expert
  weights can be sharded over the ``model`` axis on either the expert dim
  (EP) or the ``d_ff`` dim (TP); the sharding rules in
  ``repro.distributed.shardings`` pick TP-experts by default — the
  contraction then needs exactly one reduce over ``model``, the same
  collective pattern as a dense TP FFN (and GSPMD inserts it from the
  sharding constraints; no manual collectives needed here).
* capacity follows GShard: C = ceil(T·k·capacity_factor / E); overflow
  tokens fall back to the shared expert / residual (dropped from routed
  compute), underflow slots are zero-padded.

Router style notes per assigned arch:
* qwen2-moe: softmax router, top-4, renormalized, plus a 4×-width shared
  expert with a sigmoid shared-gate.
* llama4: top-1, sigmoid gate on the selected expert, plus a shared
  expert (always on); interleaved with dense layers (period 2).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.configs.base import ModelConfig
from repro.models.layers import cast, cdtype, dense_init, mlp, mlp_init


def moe_init(key, cfg: ModelConfig):
    k_r, k_g, k_u, k_d, k_s, k_sg = jax.random.split(key, 6)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": jax.random.normal(k_r, (d, e), jnp.float32) * scale,
        "w_gate": jax.random.normal(k_g, (e, d, f), jnp.float32) * scale,
        "w_up": jax.random.normal(k_u, (e, d, f), jnp.float32) * scale,
        "w_down": jax.random.normal(k_d, (e, f, d), jnp.float32)
        * (1.0 / math.sqrt(f)),
    }
    if cfg.shared_expert_d_ff:
        p["shared"] = mlp_init(k_s, cfg, d_ff=cfg.shared_expert_d_ff)
        p["shared_gate"] = jax.random.normal(k_sg, (d, 1), jnp.float32) * scale
    return p


def _capacity(cfg: ModelConfig, tokens: int) -> int:
    c = math.ceil(
        tokens * cfg.num_experts_per_tok * cfg.capacity_factor
        / cfg.num_experts
    )
    # multiple of 128: sublane-aligned AND shardable over dp axes (the
    # dispatch buffers carry explicit sharding constraints; see §Perf H1)
    return max(128, -(-c // 128) * 128) if tokens >= 4096 else \
        max(8, -(-c // 8) * 8)


def _dispatch_and_run(cfg, w_gate, w_up, w_down, xt, top_p, top_e,
                      cap: int):
    """Local capacity dispatch + expert FFNs.  Pure; no collectives.

    ``xt (T, D)`` are this shard's tokens; weights may be F-sharded (the
    caller reduces the partial output over the tensor axis).  Rank within
    expert comes from a stable argsort — O(n log n) — never the (T·k, E)
    one-hot cumsum (it lowers to a reduce-window XLA cost-counts
    quadratically: 50× FLOPs inflation on qwen2-moe, §Perf H1).
    """
    t, d = xt.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok

    flat_e = top_e.reshape(-1)                                   # (T*k,)
    counts = jnp.bincount(flat_e, length=e)
    order = jnp.argsort(flat_e, stable=True)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype)
    )
    offsets = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]]
    )
    rank = inv - jnp.take(offsets, flat_e)
    keep = rank < cap
    dest = jnp.minimum(
        jnp.where(keep, flat_e * cap + rank, e * cap - 1), e * cap - 1
    )

    tok_idx = jnp.arange(t * k) // k
    gathered = jnp.take(xt, tok_idx, axis=0)                     # (T*k, D)
    contrib = jnp.where(keep[:, None], gathered, 0)
    buf = jnp.zeros((e * cap, d), dtype=xt.dtype).at[dest].add(contrib)
    h = buf.reshape(e, cap, d)

    g = jnp.einsum("ecd,edf->ecf", h, w_gate)
    u = jnp.einsum("ecd,edf->ecf", h, w_up)
    a = jax.nn.silu(g) * u
    o = jnp.einsum("ecf,efd->ecd", a, w_down)                    # (E,C,D)

    per_tk = jnp.take(o.reshape(e * cap, d), dest, axis=0)       # (T*k, D)
    w = (top_p.reshape(-1) * keep.astype(jnp.float32)).astype(per_tk.dtype)
    return jnp.sum((per_tk * w[:, None]).reshape(t, k, d), axis=1)


def moe_apply(
    p,
    x,                       # (B, S, D) or (T, D)
    cfg: ModelConfig,
    sharder=None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output, aux_loss).  Output has the input's shape.

    Two execution paths:
    * **single-device / decode** — plain local dispatch.
    * **meshed (sharder carries a mesh)** — dispatch runs inside
      ``shard_map``: tokens stay on their data shard (capacity is
      per-shard, as in real MoE systems), expert weights are
      FSDP-all-gathered over the data axes *inside* the mapped function
      (one layer live at a time under the scan), the F-contraction
      partials are psum'd over ``model`` once, *after* the combine
      (deferring the reduce past the linear combine shrinks it from
      (E·C, D) to (T_local, D)).  No dispatch buffer ever replicates —
      this was an 80 GiB/device temp reduction on qwen2-moe train_4k
      (§Perf H1 iter 3).
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    xt = x.reshape(-1, d)
    t = xt.shape[0]
    e, k = cfg.num_experts, cfg.num_experts_per_tok

    logits = (xt @ cast(p["router"], cfg)).astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                       # (T, k)
    if k > 1:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # ---- load-balance auxiliary loss (Switch-style) ---------------------
    counts = jnp.bincount(top_e.reshape(-1), length=e)
    me = jnp.mean(probs, axis=0)
    ce = counts.astype(jnp.float32) / t
    aux_loss = cfg.router_aux_loss_coef * e * jnp.sum(me * ce)

    mesh = getattr(sharder, "mesh", None)
    wg, wu, wd = (cast(p["w_gate"], cfg), cast(p["w_up"], cfg),
                  cast(p["w_down"], cfg))

    if mesh is not None and "model" in mesh.shape and t >= 4096:
        from jax.sharding import PartitionSpec as P

        fsdp = tuple(a for a in ("pod", "data") if a in mesh.shape)
        n_dp = 1
        for a in fsdp:
            n_dp *= mesh.shape[a]
        cap = _capacity(cfg, t // n_dp)

        def mapped(xt_l, top_p_l, top_e_l, wg_l, wu_l, wd_l):
            # manual FSDP: gather the D-shard of this layer's experts
            if fsdp:
                wg_l = jax.lax.all_gather(wg_l, fsdp, axis=1, tiled=True)
                wu_l = jax.lax.all_gather(wu_l, fsdp, axis=1, tiled=True)
                wd_l = jax.lax.all_gather(wd_l, fsdp, axis=2, tiled=True)
            y = _dispatch_and_run(cfg, wg_l, wu_l, wd_l, xt_l,
                                  top_p_l, top_e_l, cap)
            return jax.lax.psum(y, "model")

        y = shard_map(
            mapped,
            mesh=mesh,
            in_specs=(
                P(fsdp, None), P(fsdp, None), P(fsdp, None),
                P(None, fsdp, "model"),
                P(None, fsdp, "model"),
                P(None, "model", fsdp),
            ),
            out_specs=P(fsdp, None),
            check_vma=False,
        )(xt, top_p, top_e, wg, wu, wd)
    else:
        cap = _capacity(cfg, t)
        y = _dispatch_and_run(cfg, wg, wu, wd, xt, top_p, top_e, cap)

    # ---- shared expert ----------------------------------------------------
    if "shared" in p:
        gate = jax.nn.sigmoid(
            (xt @ cast(p["shared_gate"], cfg)).astype(jnp.float32)
        ).astype(y.dtype)
        y = y + gate * mlp(p["shared"], xt, cfg)

    return y.reshape(orig_shape), aux_loss
