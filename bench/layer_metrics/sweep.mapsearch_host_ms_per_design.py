"""Host mapping search per completed design: the design-batched prefill
(candidate enumeration, dispatch set-up, selection, NumPy re-scoring)
less the time inside the scoring engine's dispatch-and-copy span."""


def read(ctx):
    s = ctx["spans"]
    if not ctx["designs"] or "dse.batch_sweep.prefill" not in s:
        return None
    host = s["dse.batch_sweep.prefill"] - s.get("mapper_batch.jax_execute", 0.0)
    return 1e3 * host / ctx["designs"]
