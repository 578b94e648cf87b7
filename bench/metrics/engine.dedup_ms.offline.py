"""Engine dedup time per offline batch (ms/batch).

The program's ``dedup`` spans (``np.unique`` over the batch's ``(l, r)``
pairs and its inverse) inside its ``query_bulk`` spans, per batch.
"""

from rmqbench.programspans import ms_per_root


def read(ctx):
    return ms_per_root(ctx.program_spans, "query_bulk", ["dedup"])
