"""Sharded router fetch time per LCE batch (ms/batch).

The program's ``fetch`` spans inside its ``query_bulk`` spans, per
batch: each crossing or segment-local bucket's wait for the four chips
and the device-to-host copy of its answers.
"""

from rmqbench.programspans import ms_per_root


def read(ctx):
    return ms_per_root(ctx.program_spans, "query_bulk", ["fetch"])
