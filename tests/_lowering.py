"""How a jitted walk reads its chunk windows, read from its jaxpr.

Under ``vmap`` a ``dynamic_slice`` at a per-query start becomes a
``gather`` of a ``width``-entry slice of a 1-D array, which XLA on TPU
lowers to a loop over the queries; a row read is a ``gather`` of whole
rows of a 2-D ``(rows, row)`` view.
"""

from jax.extend.core import ClosedJaxpr, Jaxpr


def window_reads(closed_jaxpr):
    """``("slice", width)`` or ``("row", rows)`` for every window read."""
    out = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "dynamic_slice":
                out.append(("slice", e.params["slice_sizes"][0]))
            elif e.primitive.name == "gather":
                op = e.invars[0].aval.shape
                sizes = e.params["slice_sizes"]
                if len(op) == 1 and sizes[0] > 1:
                    out.append(("slice", sizes[0]))
                elif len(op) == 2 and sizes == (1, op[1]) and op[1] > 1:
                    out.append(("row", e.invars[1].aval.shape[-2]))
            for p in e.params.values():
                for sub in p if isinstance(p, (tuple, list)) else (p,):
                    if isinstance(sub, ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, Jaxpr):
                        walk(sub)

    walk(closed_jaxpr.jaxpr)
    return out
