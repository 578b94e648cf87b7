"""Mean duration of the serving tier's ``flush`` spans (ms)."""

from rmqbench.selftime import program


def read(ctx):
    flushes = program(ctx.program_spans, "flush")
    if not flushes:
        return None
    return sum(e - s for _, s, e in flushes) / len(flushes) * 1e3
