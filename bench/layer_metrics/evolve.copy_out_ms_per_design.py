"""Device-to-host copies of the scoring kernel's outputs per full-zoo
evaluation completed."""


def read(ctx):
    s = ctx["spans"].get("mapper_batch.copy_out")
    if not ctx["designs"] or s is None:
        return None
    return 1e3 * s / ctx["designs"]
