"""Engine host time per offline batch (ms/batch).

The benchmark's span around each ``query_bulk`` call minus the program's
``execute`` spans inside it (each a bucket dispatch and its device wait):
dedup, result-cache lookups and writes, planning and scatter on the host.
"""

import threading

from rmqbench.selftime import program, self_times


def read(ctx):
    main = threading.main_thread().name
    batches = [(main, s, e) for name, s, e in ctx.bench_spans
               if name == "batch"]
    execs = program(ctx.program_spans, "execute")
    if not batches or not execs:
        return None
    own = self_times(batches, execs)
    return sum(own) / len(own) * 1e3
