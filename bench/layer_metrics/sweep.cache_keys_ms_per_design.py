"""Mapping-cache keying and probes (prefill and evaluator) per completed
design."""


def read(ctx):
    s = ctx["spans"].get("mapper_cache.keys")
    if not ctx["designs"] or s is None:
        return None
    return 1e3 * s / ctx["designs"]
