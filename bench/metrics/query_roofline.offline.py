"""Query kernels' share of the HBM roofline on the traced batch (%).

Bytes are the distinct (level, chunk) pairs the batch's queries need
under the paper's decomposition, times ``c`` float32 entries; time is the
device's busy time in the traced window, which holds exactly one batch.
"""

from rmqbench.bytecount import query_bytes


def read(ctx):
    dt, batch = ctx.device, ctx.record.get("traced_batch")
    if dt is None or batch is None or dt.busy_s <= 0 or ctx.peaks is None:
        return None
    cfg = ctx.config
    need = query_bytes(batch[0], batch[1], int(cfg["n"]), int(cfg["c"]),
                       int(cfg["t"]))
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / dt.busy_s
