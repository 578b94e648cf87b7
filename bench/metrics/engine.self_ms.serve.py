"""Engine host time per flush (ms/flush).

The program's ``service_flush`` spans minus the ``execute`` spans inside
them on the same thread: per-ticket submit, dedup, result-cache lookups
and writes, planning and per-ticket result slicing on the host.
"""

from rmqbench.selftime import program, self_times


def read(ctx):
    flushes = program(ctx.program_spans, "service_flush")
    if not flushes:
        return None
    own = self_times(flushes, program(ctx.program_spans, "execute"))
    return sum(own) / len(own) * 1e3
