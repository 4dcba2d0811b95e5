"""Candidate enumeration and row lowering (`build_batch`) per completed
design."""


def read(ctx):
    s = ctx["spans"].get("mapper_batch.enumerate")
    if not ctx["designs"] or s is None:
        return None
    return 1e3 * s / ctx["designs"]
