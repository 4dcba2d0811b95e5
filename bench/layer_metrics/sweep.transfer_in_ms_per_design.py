"""Host-to-device copy of the scoring kernel's arguments, waited for, per
completed design."""


def read(ctx):
    s = ctx["spans"].get("mapper_batch.transfer_in")
    if not ctx["designs"] or s is None:
        return None
    return 1e3 * s / ctx["designs"]
