"""Engine dedup time per sharded LCE batch (ms/batch).

The program's ``dedup`` spans inside its ``query_bulk`` spans, per
batch: past 2^31 a ``np.lexsort`` of each query's key space and packed
key, and the inverse that scatters the answers back.
"""

from rmqbench.programspans import ms_per_root


def read(ctx):
    return ms_per_root(ctx.program_spans, "query_bulk", ["dedup"])
