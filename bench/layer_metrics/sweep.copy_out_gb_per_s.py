"""Bytes copied from the device to the host over the time of those copies."""


def read(ctx):
    n = ctx["counters"].get("mapper_batch.d2h_bytes", 0)
    s = ctx["spans"].get("mapper_batch.copy_out")
    if not n or not s:
        return None
    return n / s / 1e9
