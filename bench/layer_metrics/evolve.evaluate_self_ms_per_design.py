"""Evaluator time outside the mapping search and the scoring engine (design
lowering, scorecard aggregation, area and power) per full-zoo evaluation
completed: every span subtracted lies inside ``dse.evaluate`` in a guided
search."""

INNER = ("mapper_batch.enumerate", "mapper_batch.pack",
         "mapper_batch.jax_execute", "mapper_batch.select",
         "mapper_batch.rescore", "mapper_cache.keys")


def read(ctx):
    s = ctx["spans"]
    if not ctx["designs"] or "dse.evaluate" not in s \
            or any(k not in s for k in INNER):
        return None
    return 1e3 * (s["dse.evaluate"] - sum(s[k] for k in INNER)) \
        / ctx["designs"]
