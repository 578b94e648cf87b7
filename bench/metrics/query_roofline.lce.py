"""Sharded query walk's share of the HBM roofline on the traced batch (%).

Bytes are the distinct (segment, level, chunk) triples that the traced
batch's queries need, each query's intersection with each segment walked
in that segment's coordinates under the paper's decomposition, times
``c`` float32 entries; time is the busy time summed over the chips, each
at its own 819 GB/s.  The window holds exactly one batch.
"""

from rmqbench.segments import segment_query_bytes


def read(ctx):
    dt, batch = ctx.device, ctx.record.get("traced_batch")
    if dt is None or batch is None or dt.busy_s <= 0 or ctx.peaks is None:
        return None
    cfg = ctx.config
    segments = int(cfg["segments"])
    need = segment_query_bytes(batch[0], batch[1],
                               int(cfg["n"]) // segments, segments,
                               int(cfg["c"]), int(cfg["t"]))
    return (100.0 * need / ctx.peaks["hbm_bytes_per_s"]
            / (dt.busy_s * dt.chips))
