"""Share of the rows the scoring kernel scored (designs x candidates, padded
to their buckets) that are real."""


def read(ctx):
    c = ctx["counters"]
    padded = c.get("mapper_batch.jax_rows_padded", 0)
    if not padded:
        return None
    return 100.0 * c.get("mapper_batch.jax_candidates", 0) / padded
