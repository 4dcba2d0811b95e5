"""Evaluator time (per-design mapping search included, prefilter
evaluations included) per full-zoo evaluation completed."""


def read(ctx):
    s = ctx["spans"].get("dse.evaluate")
    if not ctx["designs"] or s is None:
        return None
    return 1e3 * s / ctx["designs"]
