"""Scoring-engine dispatches per full-zoo evaluation completed (the
prefilter's dispatches included)."""


def read(ctx):
    n = ctx["counters"].get("mapper_batch.jax_dispatches", 0)
    if not ctx["designs"] or not n:
        return None
    return n / ctx["designs"]
