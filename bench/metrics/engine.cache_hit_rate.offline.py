"""Result-cache hit rate of the offline batches (%).

The ``hits`` over the ``lookups`` that the program's ``cache_get`` spans
inside its ``query_bulk`` spans carry: deltas of the cache's own hit and
miss counters around each lookup loop.
"""

from rmqbench.programspans import under


def read(ctx):
    found, _ = under(ctx.program_spans, "query_bulk", ["cache_get"])
    lookups = sum(int(sp.args.get("lookups", 0)) for sp in found)
    if not lookups:
        return None
    return 100.0 * sum(int(sp.args.get("hits", 0)) for sp in found) / lookups
