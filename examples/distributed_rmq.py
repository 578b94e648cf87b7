"""Distributed RMQ: shard a large array across a device mesh and answer
query batches with per-segment hierarchies + a min all-reduce.

    PYTHONPATH=src python examples/distributed_rmq.py

The mesh has one segment per device that exists: the chips of a TPU
host, or — under ``JAX_PLATFORMS=cpu`` — 8 host devices forced before
jax is imported.  This is the piece that removes the paper's single-GPU
memory ceiling: capacity scales linearly in devices, communication per
batch is one all-reduce(min) of (batch,) floats — independent of n.
"""

import os

if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.distributed import DistributedRMQ


def main():
    segments = len(jax.devices())
    mesh = jax.make_mesh((1, segments), ("data", "model"))
    rng = np.random.default_rng(0)
    n = 1 << 22  # 4M elements, one segment per device
    x = rng.random(n, dtype=np.float32)

    d = DistributedRMQ.build(x, mesh, segment_axis="model",
                             query_axes=("data",), c=128, t=32,
                             with_positions=True)
    print(f"n = {n} sharded into {mesh.shape['model']} segments of "
          f"{d.local_plan.n}; per-device footprint "
          f"{d.memory_bytes_per_device() / 2**20:.1f} MiB")

    m = 1 << 12
    ls = rng.integers(0, n, m)
    rs = np.minimum(ls + rng.integers(1, n, m), n - 1)
    ls, rs = np.minimum(ls, rs), np.maximum(ls, rs)

    vals = np.asarray(d.query(ls, rs))
    idxs = np.asarray(d.query_index(ls, rs))
    # spot check vs naive
    for i in rng.integers(0, m, 16):
        seg = x[ls[i]:rs[i] + 1]
        assert vals[i] == seg.min()
        assert idxs[i] == ls[i] + int(np.argmin(seg))
    print(f"answered {m} cross-segment queries; spot-checks OK")
    print(f"example: RMQ({ls[0]}, {rs[0]}) = {vals[0]:.6f} @ {idxs[0]} "
          f"(spans segments {ls[0] // d.segment_capacity}.."
          f"{rs[0] // d.segment_capacity})")

    # --- sharded streaming: updates routed to their owning segment ------
    upd_at = rng.integers(0, n, 4096).astype(np.int32)
    d = d.update(upd_at, np.full(4096, 0.5, np.float32))
    d = d.update(np.array([n // 3], np.int32),
                 np.array([-1.0], np.float32))
    v, p = d.query(np.array([0]), np.array([n - 1])), \
        d.query_index(np.array([0]), np.array([n - 1]))
    assert float(v[0]) == -1.0 and int(p[0]) == n // 3
    print(f"sharded update batch applied (generation {d.generation}); "
          f"global min now {float(v[0])} @ {int(p[0])}")

    # --- engine routing: contained spans skip the all-reduce ------------
    engine = d.engine()
    ev = np.asarray(engine.query(ls, rs))
    ep = np.asarray(engine.query_index(ls, rs))
    ov = np.asarray(d.query(ls, rs))
    op = np.asarray(d.query_index(ls, rs))
    assert (ev == ov).all() and (ep == op).all()
    cc = engine.stats()["class_counts"]
    print(f"engine routed {cc['seg_local']} spans segment-locally "
          f"(no all-reduce) and {cc['crossing']} through the pmin path; "
          "bit-identical to the monolithic oracle")


if __name__ == "__main__":
    main()
