"""Host time of one index build (ms/build).

The mean length of the program's ``build`` spans: ``RMQ.build`` from
entry to return, i.e. plan resolution (``build_plan``) and the host's
dispatch of the build programs (``build_dispatch``); the device work
they start runs on after the span ends.
"""

from rmqbench.programspans import mean_ms


def read(ctx):
    return mean_ms(ctx.program_spans, "build")
