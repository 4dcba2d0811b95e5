"""Share of the (layer, spatial choice) lookups of candidate enumeration
that found rows the unit of work had already built."""


def read(ctx):
    c = ctx["counters"]
    lookups = c.get("mapper_batch.enum_memo_lookups", 0)
    if not lookups:
        return None
    return 100.0 * c.get("mapper_batch.enum_memo_hits", 0) / lookups
