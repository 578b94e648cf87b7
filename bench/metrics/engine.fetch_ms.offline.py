"""Executor fetch time per offline batch (ms/batch).

The program's ``fetch`` spans inside its ``query_bulk`` spans: each
bucket's wait for the device and the device-to-host copy of its answers.
"""

from rmqbench.programspans import ms_per_root


def read(ctx):
    return ms_per_root(ctx.program_spans, "query_bulk", ["fetch"])
