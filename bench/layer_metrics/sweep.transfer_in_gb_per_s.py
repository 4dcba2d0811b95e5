"""Bytes of the scoring kernel's arguments copied to the device over the
time of the calls that copy them in (the launch included)."""


def read(ctx):
    n = ctx["counters"].get("mapper_batch.h2d_bytes", 0)
    s = ctx["spans"].get("mapper_batch.transfer_in")
    if not n or not s:
        return None
    return n / s / 1e9
