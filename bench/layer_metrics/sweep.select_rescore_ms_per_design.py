"""Winner selection and NumPy re-scoring of the winners (with their
`Mapping` objects) per completed design."""


def read(ctx):
    s = ctx["spans"]
    if not ctx["designs"] or "mapper_batch.select" not in s \
            or "mapper_batch.rescore" not in s:
        return None
    return 1e3 * (s["mapper_batch.select"] + s["mapper_batch.rescore"]) \
        / ctx["designs"]
