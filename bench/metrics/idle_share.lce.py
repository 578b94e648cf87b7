"""Device idle share of the traced window (%): 1 - busy union / window,
the busy union taken per chip and averaged over the chips."""


def read(ctx):
    dt = ctx.device
    if dt is None or dt.window_s <= 0:
        return None
    return 100.0 * dt.idle_share
