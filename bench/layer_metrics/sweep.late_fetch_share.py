"""Share of the scoring kernel's outputs left on the device by a dispatch
that something read later, and so copied after all."""


def read(ctx):
    c = ctx["counters"]
    deferred = c.get("mapper_batch.outputs_deferred", 0)
    if not deferred:
        return None
    return 100.0 * c.get("mapper_batch.outputs_fetched_late", 0) / deferred
