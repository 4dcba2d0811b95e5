"""Seconds spent compiling (or reading back from the persistent cache) the
scoring kernels during set-up."""


def read(ctx):
    n = ctx["setup"].get("mapper_batch.jax_compiles", 0)
    if not n:
        return None
    return ctx["setup"].get("mapper_batch.jax_compile_s.sum", 0.0)
