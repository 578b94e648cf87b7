#!/usr/bin/env python3
"""Chip smoke test: drive the RMQ serving path once on a TPU, at full size.

    python chip_smoke.py [--seed S]          # one chip
    python chip_smoke.py --chips 4 [--seed S]  # DistributedRMQ over 4 chips

One chip: 2^26 float32 values (256 MiB of level 0) are indexed with
``RMQ.build(..., c=128, t=64, with_positions=True)`` through both Pallas
construction backends; every level is checked against a numpy reference.
The normal query engine then answers a mixed short/mid/long batch of 2^16
value and index queries on each index, ``engine.query_bulk`` answers 2^20
queries through the bulk kernel, and one ``ServingTier`` tenant serves a
few tickets, stages a 4096-point update and serves again at the new
generation.  Every answer is checked against a plain numpy range-minimum
reference (leftmost ties), and every kernel launch on the path must have
run the compiled Pallas lowering.

``--chips 4`` runs only the sharded path: ``DistributedRMQ`` over a mesh
of four chips (2^26 values per chip), engine queries with segment-
contained and crossing spans, one update batch, all checked against numpy.

Progress goes to earlier lines; the last line of standard output is one
JSON object ``{"ok": true, "device": {...}}``.  Without a TPU, or when the
repository's ``src/`` is missing, the script exits non-zero and prints no
result.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
C, T = 128, 64
PAD_POS = np.iinfo(np.int32).max
EXPECTED_KERNELS = {
    1: {"hierarchy_build", "hierarchy_fused", "hierarchy_update",
        "rmq_short", "rmq_scan", "rmq_fused", "rmq_bulk"},
    4: {"hierarchy_build"},
}


def log(msg: str) -> None:
    print(msg, flush=True)


class Timer:
    """Wall-clock phase timings, printed as they finish."""

    def __init__(self):
        self.phases = {}

    def __call__(self, name, fn, *args, **kwargs):
        import jax

        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        # an index is not a pytree: wait on the device arrays it holds
        jax.block_until_ready(
            [getattr(out, a, None) for a in ("hierarchy", "base", "upper")]
            if hasattr(out, "plan") else out)
        dt = time.perf_counter() - t0
        self.phases[name] = dt
        log(f"  [{dt:9.3f} s] {name}")
        return out


# ---------------------------------------------------------------------------
# the numpy reference (independent of every backend of the repo)
# ---------------------------------------------------------------------------
def _lex_min(v1, p1, v2, p2):
    """Elementwise (value, position) minimum: ties go to the lower position."""
    take2 = (v2 < v1) | ((v2 == v1) & (p2 < p1))
    return np.where(take2, v2, v1), np.where(take2, p2, p1)


class RangeMinRef:
    """Range minimum and leftmost argmin over ``x`` in plain numpy.

    Blocks of ``B`` entries: a query scans its (at most two) boundary
    blocks directly and covers the blocks between them with a sparse
    table of block minima.
    """

    def __init__(self, x: np.ndarray, block: int = 1024):
        self.B = block
        nb = -(-x.shape[0] // block)
        xp = np.full(nb * block, np.inf, np.float32)
        xp[: x.shape[0]] = x
        self.xb = xp.reshape(nb, block)
        bv = self.xb.min(axis=1)
        bp = self.xb.argmin(axis=1).astype(np.int64) + np.arange(nb) * block
        self.tv, self.tp = [bv], [bp]
        j = 1
        while (1 << j) <= nb:
            half = 1 << (j - 1)
            v, p = self.tv[-1], self.tp[-1]
            nv, np_ = _lex_min(v[:-half], p[:-half], v[half:], p[half:])
            self.tv.append(nv)
            self.tp.append(np_)
            j += 1

    def _piece(self, blk, lo, hi):
        """(min, leftmost pos) over [lo, hi] inside block ``blk``."""
        w = self.xb[blk]
        idx = blk[:, None] * self.B + np.arange(self.B)[None, :]
        mw = np.where((idx >= lo[:, None]) & (idx <= hi[:, None]), w, np.inf)
        a = mw.argmin(axis=1)
        return mw[np.arange(w.shape[0]), a], blk * self.B + a

    def query(self, ls, rs, batch: int = 8192):
        """``(values, positions)`` for inclusive ranges ``[ls, rs]``."""
        ls = np.asarray(ls, np.int64)
        rs = np.asarray(rs, np.int64)
        vals = np.empty(ls.shape[0], np.float32)
        pos = np.empty(ls.shape[0], np.int64)
        for s in range(0, ls.shape[0], batch):
            l, r = ls[s:s + batch], rs[s:s + batch]
            bl, br = l // self.B, r // self.B
            v, p = self._piece(bl, l, r)
            v2, p2 = self._piece(br, l, r)
            v, p = _lex_min(v, p, v2, p2)
            a, b = bl + 1, br - 1
            inner = b >= a
            cnt = np.where(inner, b - a + 1, 1)
            k = np.floor(np.log2(cnt)).astype(np.int64)
            a_ = np.where(inner, a, 0)
            b2 = np.where(inner, b - (1 << k) + 1, 0)
            mv = np.empty(l.shape[0], np.float32)
            mp = np.empty(l.shape[0], np.int64)
            for kk in np.unique(k):
                sel = k == kk
                mv[sel], mp[sel] = _lex_min(
                    self.tv[kk][a_[sel]], self.tp[kk][a_[sel]],
                    self.tv[kk][b2[sel]], self.tp[kk][b2[sel]],
                )
            mv = np.where(inner, mv, np.inf)
            v, p = _lex_min(v, p, mv, np.where(inner, mp, PAD_POS))
            vals[s:s + batch], pos[s:s + batch] = v, p
        return vals, pos


def reference_hierarchy(x: np.ndarray, plan):
    """Every upper level by numpy ``reshape(-1, c)`` min/argmin."""
    c = plan.c
    upper = np.full(plan.upper_size, np.inf, np.float32)
    upper_pos = np.full(plan.upper_size, PAD_POS, np.int32)
    cur_v = np.full(plan.capacity, np.inf, np.float32)
    cur_v[: x.shape[0]] = x
    cur_p = np.arange(plan.capacity, dtype=np.int32)
    for k in range(1, plan.num_levels):
        want = plan.level_lens[k] * c
        v = np.full(want, np.inf, np.float32)
        v[: cur_v.shape[0]] = cur_v
        p = np.full(want, PAD_POS, np.int32)
        p[: cur_p.shape[0]] = cur_p
        v, p = v.reshape(-1, c), p.reshape(-1, c)
        a = v.argmin(axis=1)                      # leftmost tie
        rows = np.arange(v.shape[0])
        cur_v, cur_p = v[rows, a], p[rows, a]
        off = plan.offsets[k - 1]
        upper[off:off + cur_v.shape[0]] = cur_v
        upper_pos[off:off + cur_p.shape[0]] = cur_p
    return upper, upper_pos


def check_answers(what, got_v, got_p, ref, ls, rs):
    """Compare engine answers with the reference; raise on any mismatch."""
    want_v, want_p = ref.query(ls, rs)
    if got_v is not None:
        got_v = np.asarray(got_v)
        bad = np.nonzero(got_v != want_v)[0]
        if bad.size:
            i = bad[0]
            raise AssertionError(
                f"{what}: {bad.size} wrong values, first at query {i} "
                f"[{ls[i]}, {rs[i]}]: got {got_v[i]}, want {want_v[i]}")
    if got_p is not None:
        got_p = np.asarray(got_p)
        bad = np.nonzero(got_p != want_p)[0]
        if bad.size:
            i = bad[0]
            raise AssertionError(
                f"{what}: {bad.size} wrong positions, first at query {i} "
                f"[{ls[i]}, {rs[i]}]: got {got_p[i]}, want {want_p[i]}")
    log(f"  ok: {what} ({len(ls)} queries match numpy)")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def run_one_chip(seed: int, timer: Timer, n: int = 1 << 26,
                 m: int = 1 << 16, m_bulk: int = 1 << 20,
                 interpret=None) -> None:
    from repro.core import RMQ
    from repro.serving import ServingTier
    from repro.tune.measure import (
        make_input_array,
        make_queries,
        make_span_queries,
    )

    rng = np.random.default_rng(seed)
    log(f"phase data: n={n} float32 values from seed {seed}")
    x = timer("data: generate", make_input_array, n, seed)
    ref = timer("data: numpy reference", RangeMinRef, x)

    log("phase build: RMQ.build(c=128, t=64, with_positions=True)")
    indexes = {}
    for backend in ("pallas", "fused"):
        build = lambda: RMQ.build(x, c=C, t=T, with_positions=True,
                                  backend=backend)
        timer(f"build {backend}: first call (compile = set-up)", build)
        rmq = timer(f"build {backend}: second call", build)
        indexes[backend] = rmq
    plan = indexes["pallas"].plan
    want_u, want_p = reference_hierarchy(x, plan)
    for backend, rmq in indexes.items():
        h = rmq.hierarchy
        np.testing.assert_array_equal(np.asarray(h.base)[:n], x)
        np.testing.assert_array_equal(np.asarray(h.upper), want_u)
        np.testing.assert_array_equal(np.asarray(h.upper_pos), want_p)
        log(f"  ok: {backend} build, {plan.num_levels - 1} upper levels "
            f"({plan.upper_size} entries) match numpy, positions included")

    log(f"phase queries: engine, mixed short/mid/long batch of m={m}")
    ls, rs = make_span_queries(n, m, C, "mixed", seed=seed + 1)
    ls2, rs2 = make_span_queries(n, m, C, "mixed", seed=seed + 2)
    for backend, rmq in indexes.items():
        eng = rmq.engine(interpret=interpret)
        v = timer(f"query {backend}: value, first call (compile = set-up)",
                  eng.query, ls, rs)
        p = timer(f"query {backend}: index, first call (compile = set-up)",
                  eng.query_index, ls, rs)
        check_answers(f"engine[{backend}] value+index", v, p, ref, ls, rs)
        v2 = timer(f"query {backend}: value, second batch", eng.query,
                   ls2, rs2)
        p2 = timer(f"query {backend}: index, second batch",
                   eng.query_index, ls2, rs2)
        check_answers(f"engine[{backend}] second batch", v2, p2, ref,
                      ls2, rs2)
        log(f"  engine[{backend}] span classes: "
            f"{eng.stats()['class_counts']}")

    log(f"phase bulk: engine.query_bulk, m={m_bulk}")
    eng = indexes["fused"].engine(bulk_crossover=m_bulk,
                                  interpret=interpret)
    bl, br = make_queries(n, m_bulk, "mixed", seed=seed + 3)
    bv = timer("bulk: value (compile = set-up)", eng.query_bulk, bl, br)
    bp = timer("bulk: index", eng.query_bulk, bl, br, "index")
    if "bulk" not in eng.stats()["executors"]:
        raise AssertionError("query_bulk did not take the bulk path")
    sample = rng.choice(m_bulk, min(m_bulk, 1 << 16), replace=False)
    check_answers("bulk sample", np.asarray(bv)[sample],
                  np.asarray(bp)[sample], ref, bl[sample], br[sample])

    log("phase serving: ServingTier tenant, tickets, update, new generation")
    tier = ServingTier()
    tier.register_tenant("smoke", indexes["pallas"], interpret=interpret)
    sls, srs = make_span_queries(n, 4096, C, "mixed", seed=seed + 4)
    ops = ["value", "index", "value", "index"]
    parts = np.array_split(np.arange(4096), len(ops))

    def serve(tag, ref_now, generation):
        tickets = [tier.submit("smoke", sls[ix], srs[ix], op)
                   for ix, op in zip(parts, ops)]
        tier.drain("smoke")
        for t, ix, op in zip(tickets, parts, ops):
            res = t.result(timeout=600)
            if t.generation != generation:
                raise AssertionError(
                    f"ticket answered at generation {t.generation}, "
                    f"want {generation}")
            check_answers(
                f"{tag} ticket ({op}, generation {generation})",
                res if op == "value" else None,
                res if op == "index" else None,
                ref_now, sls[ix], srs[ix])

    timer("serving: 4 tickets, generation 0", serve, "tier", ref, 0)
    idx = rng.choice(n, 4096, replace=False).astype(np.int32)
    vals = (rng.random(4096, dtype=np.float32) - 0.5).astype(np.float32)
    tier.update("smoke", idx, vals)
    x2 = x.copy()
    x2[idx] = vals
    ref2 = RangeMinRef(x2)
    timer("serving: update + 4 tickets, generation 1", serve, "tier",
          ref2, 1)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def run_four_chips(seed: int, timer: Timer, n: int = 1 << 28,
                   m: int = 1 << 14) -> None:
    import jax
    from repro.core.distributed import DistributedRMQ
    from repro.tune.measure import (
        make_input_array,
        make_queries,
        make_span_queries,
    )

    devs = jax.devices()[:4]
    if len(devs) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh = jax.make_mesh((1, 4), ("data", "model"), devices=devs)
    rng = np.random.default_rng(seed)
    log(f"phase data: n={n} float32 values from seed {seed}")
    x = timer("data: generate", make_input_array, n, seed)
    ref = timer("data: numpy reference", RangeMinRef, x)

    log("phase build: DistributedRMQ.build over 4 segments")
    build = lambda: DistributedRMQ.build(
        x, mesh, segment_axis="model", query_axes=("data",), c=C, t=T,
        with_positions=True)
    d = timer("build: first call (compile = set-up)", build)
    shards = sorted(d.base.addressable_shards, key=lambda s: s.index[0].start)
    for s in shards:
        log(f"  shard {s.index[0].start}..{s.index[0].stop}: device "
            f"{s.device.id} ({s.device.device_kind}), shape {s.data.shape}")
    if len({s.device.id for s in shards}) != 4:
        raise AssertionError("segments are not on four distinct devices")

    seg = d.segment_capacity
    log(f"phase queries: engine, m={m} segment-contained + m crossing")
    cl, cr = make_span_queries(seg, m, C, "mixed", seed=seed + 1)
    off = rng.integers(0, 4, m) * seg
    cl, cr = cl + off, cr + off
    xl, xr = make_queries(n, m, "large", seed=seed + 2)
    crossing = (xl // seg) != (xr // seg)
    log(f"  {int(crossing.sum())} of {m} large spans cross segments")
    ls, rs = np.concatenate([cl, xl]), np.concatenate([cr, xr])
    eng = d.engine()
    v = timer("query: value (compile = set-up)", eng.query, ls, rs)
    p = timer("query: index", eng.query_index, ls, rs)
    check_answers("distributed engine", v, p, ref, ls, rs)
    log(f"  routing: {eng.stats()['class_counts']}")

    log("phase update: one 4096-point batch")
    idx = rng.choice(n, 4096, replace=False).astype(np.int32)
    vals = (rng.random(4096, dtype=np.float32) - 0.5).astype(np.float32)
    d2 = timer("update", d.update, idx, vals)
    x[idx] = vals
    ref2 = RangeMinRef(x)
    eng.attach(d2)
    v = timer("query after update: value", eng.query, ls, rs)
    p = timer("query after update: index", eng.query_index, ls, rs)
    check_answers("distributed engine after update", v, p, ref2, ls, rs)


# ---------------------------------------------------------------------------
def check_lowerings(reg, chips: int) -> None:
    """Every recorded launch ran the Pallas lowering; all expected ran."""
    lowered = {}
    for rec in reg.records:
        lowered.setdefault(rec.name, set()).add(rec.meta.get("lowering"))
    for name in sorted(lowered):
        log(f"  {name}: lowering {sorted(lowered[name])}, "
            f"{reg.counts[name]} traced launch(es)")
    wrong = {k: v for k, v in lowered.items() if v != {"pallas"}}
    missing = EXPECTED_KERNELS[chips] - set(lowered)
    if wrong or missing:
        raise AssertionError(
            f"lowering check failed: non-pallas {wrong}, missing {missing}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"error: no repository next to {__file__} (src/repro missing)",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"error: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    count = len(jax.devices())
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={count}; compile cache {cache}")

    from repro.kernels.profiling import launch_registry

    timer = Timer()
    t0 = time.perf_counter()
    with launch_registry() as reg:
        if args.chips == 4:
            run_four_chips(args.seed, timer)
        else:
            run_one_chip(args.seed, timer)
    log("phase report: kernel lowerings")
    check_lowerings(reg, args.chips)
    log(f"total {time.perf_counter() - t0:.3f} s")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": count},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
