"""Scoring-engine dispatch, device work and device-to-host copy per
completed design."""


def read(ctx):
    s = ctx["spans"].get("mapper_batch.jax_execute")
    if not ctx["designs"] or s is None:
        return None
    return 1e3 * s / ctx["designs"]
