"""Device time in the segment-combining all-reduces over busy time (%).

The traced window's per-instruction device time of the collectives,
summed over the chips, over the busy time summed over the chips (the
busy-weighted mean of the chips' shares).  The combine's all-reduces are
instructions named after JAX's ``pmin`` (``%pmin.<k> = ...
all-reduce(...)``, under the ``rmq_pmin`` scope); an instruction named
``all-reduce...`` counts too.  A trace too large to walk per
instruction reads nothing.
"""


def _collective(name: str) -> bool:
    return name == "pmin" or name.startswith("all-reduce")


def read(ctx):
    dt = ctx.device
    if dt is None or dt.ops is None or dt.busy_s <= 0:
        return None
    spent = sum(s for name, s in dt.ops.items() if _collective(name))
    return 100.0 * spent / (dt.busy_s * dt.chips)
