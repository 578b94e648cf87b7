"""Executor launch time per offline batch (ms/batch).

The program's ``launch`` spans inside its ``query_bulk`` spans: each
bucket's host-to-device input copy and jitted call, up to the return of
its (not yet computed) device array.
"""

from rmqbench.programspans import ms_per_root


def read(ctx):
    return ms_per_root(ctx.program_spans, "query_bulk", ["launch"])
