"""Evaluator time per completed design (scorecard assembly off the warm
mapping cache)."""


def read(ctx):
    s = ctx["spans"].get("dse.evaluate")
    if not ctx["designs"] or s is None:
        return None
    return 1e3 * s / ctx["designs"]
