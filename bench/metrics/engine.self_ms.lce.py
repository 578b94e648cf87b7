"""Engine host time per sharded LCE batch (ms/batch).

The benchmark's span around each ``query_bulk`` call minus the program's
``execute`` spans inside it (one per routing class: the dispatch of its
buckets and the wait for their answers): dedup over wide keys,
result-cache lookups and writes, the sharded router's split, and the
scatter-back on the host.
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
