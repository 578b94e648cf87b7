"""Python garbage-collection time per offline batch (ms/batch).

The program's ``gc`` spans (``gc.callbacks``, start to stop of each
collection) that open inside its ``query_bulk`` spans, per batch; 0 when
no collection ran in a batch.
"""

from rmqbench.programspans import ms_per_root


def read(ctx):
    return ms_per_root(ctx.program_spans, "query_bulk", ["gc"])
