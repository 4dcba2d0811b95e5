"""Scoring-kernel launch and device work, waited for, per completed design."""


def read(ctx):
    s = ctx["spans"].get("mapper_batch.device_wait")
    if not ctx["designs"] or s is None:
        return None
    return 1e3 * s / ctx["designs"]
