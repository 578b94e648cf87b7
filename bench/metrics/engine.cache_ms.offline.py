"""Engine result-cache time per offline batch (ms/batch).

The program's ``cache_get`` (the LRU lookup loop) and ``cache_put`` (the
write-back loop) spans inside its ``query_bulk`` spans, per batch.
"""

from rmqbench.programspans import ms_per_root


def read(ctx):
    return ms_per_root(ctx.program_spans, "query_bulk",
                       ["cache_get", "cache_put"])
