"""Sharded router's host split per batch (ms/batch).

The program's ``route`` spans (``DistributedExecutor.run``: each query's
owning segment, and the split into segment-contained and crossing
spans) inside its ``query_bulk`` spans, per batch.
"""

from rmqbench.programspans import ms_per_root


def read(ctx):
    return ms_per_root(ctx.program_spans, "query_bulk", ["route"])
