"""Device busy time (union of device-op intervals in the profiler trace)
per completed design."""


def read(ctx):
    d = ctx["device"]
    if not ctx["designs"] or d is None or d["busy_s"] <= 0:
        return None
    return 1e3 * d["busy_s"] / ctx["designs"]
