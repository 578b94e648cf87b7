"""Crossing spans' execution per batch (ms/batch).

The program's ``execute`` spans with ``cls=crossing`` inside its
``query_bulk`` spans, per batch: the dispatch of every bucket of
segment-crossing spans (each answered by all segments and combined by
the ``pmin`` all-reduce) and the wait for their answers.
"""

from rmqbench.programspans import under


def read(ctx):
    found, roots = under(ctx.program_spans, "query_bulk", ["execute"])
    if not roots:
        return None
    cross = [sp for sp in found if sp.args.get("cls") == "crossing"]
    if not cross:
        return None
    return sum(sp.end - sp.start for sp in cross) / roots * 1e3
