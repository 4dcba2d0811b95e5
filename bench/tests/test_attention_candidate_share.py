"""``sweep.attention_candidate_share`` on hand-built counters: the share
of the real candidate rows that score attention, and nothing from a
program that does not count candidates per workload kind."""

import pytest

from bench.lib.harness import _reader

READ = _reader("sweep.attention_candidate_share")


def ctx(counters):
    return {"designs": 10, "window_s": 12.0, "spans": {},
            "counters": counters, "setup": {}, "device": None}


def test_share_of_attention_rows():
    c = {"mapper_batch.jax_candidates": 4000,
         "mapper_batch.jax_candidates.gemm": 3000,
         "mapper_batch.jax_candidates.attention_qk": 600,
         "mapper_batch.jax_candidates.attention_pv": 400}
    assert READ(ctx(c)) == pytest.approx(25.0, rel=1e-12)


def test_zero_where_nothing_scored_attention():
    c = {"mapper_batch.jax_candidates": 4000,
         "mapper_batch.jax_candidates.gemm": 4000}
    assert READ(ctx(c)) == 0.0


def test_silent_without_its_counters():
    assert READ(ctx({"mapper_batch.jax_candidates": 4000})) is None
    assert READ(ctx({})) is None
