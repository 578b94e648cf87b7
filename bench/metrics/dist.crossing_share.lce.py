"""Share of the routed queries that cross a segment boundary (%).

Σ ``crossing`` over Σ ``queries`` of the program's ``route`` spans: the
deduplicated cache misses the sharded router split, counted by the
program itself.
"""


def read(ctx):
    routes = [sp for sp in ctx.program_spans
              if sp.name == "route" and sp.end is not None]
    queries = sum(int(sp.args.get("queries", 0)) for sp in routes)
    if not queries:
        return None
    return 100.0 * sum(int(sp.args.get("crossing", 0))
                       for sp in routes) / queries
