"""Engine result-cache time per sharded LCE batch (ms/batch).

The program's ``cache_get`` and ``cache_put`` spans inside its
``query_bulk`` spans, per batch: the exact LRU's lookup and write-back
over wide keys, one table lookup per key space.
"""

from rmqbench.programspans import ms_per_root


def read(ctx):
    return ms_per_root(ctx.program_spans, "query_bulk",
                       ["cache_get", "cache_put"])
