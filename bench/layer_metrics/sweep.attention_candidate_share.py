"""Share of the scoring kernel's real candidate rows that score the
attention stages (``attention_qk`` and ``attention_pv``), from the
per-workload candidate counters."""

PREFIX = "mapper_batch.jax_candidates."


def read(ctx):
    c = ctx["counters"]
    total = c.get("mapper_batch.jax_candidates", 0)
    if not total or not any(k.startswith(PREFIX) for k in c):
        return None
    attn = c.get(PREFIX + "attention_qk", 0) + c.get(PREFIX + "attention_pv", 0)
    return 100.0 * attn / total
