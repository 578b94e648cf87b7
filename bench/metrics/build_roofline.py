"""Build kernels' share of the HBM roofline over the traced builds (%).

Bytes are level 0 read once plus every upper plane written once, per
build; time is the device's busy time in the traced window, which holds
``traced_builds`` whole builds.
"""

from rmqbench.bytecount import build_bytes


def read(ctx):
    dt, builds = ctx.device, ctx.record.get("traced_builds")
    if dt is None or not builds or dt.busy_s <= 0 or ctx.peaks is None:
        return None
    cfg = ctx.config
    n = int(cfg["n"])
    need = builds * build_bytes(n, n, int(cfg["c"]), int(cfg["t"]),
                                bool(cfg["with_positions"]))
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / dt.busy_s
