"""The reader of ``sweep.late_fetch_share``: its value, and nothing where
the program defers no output."""

import pytest

from bench.lib.harness import _reader

NAME = "sweep.late_fetch_share"


def ctx(counters):
    return {"designs": 250, "window_s": 12.0, "spans": {},
            "counters": dict(counters), "setup": {}, "device": None}


def test_reader_value():
    read = _reader(NAME)
    assert read(ctx({"mapper_batch.outputs_deferred": 600,
                     "mapper_batch.outputs_fetched_late": 150})) == \
        pytest.approx(25.0, rel=1e-12)
    # deferred and never read late
    assert read(ctx({"mapper_batch.outputs_deferred": 600})) == 0.0


def test_reader_silent_without_deferred_outputs():
    read = _reader(NAME)
    assert read(ctx({"mapper_batch.outputs_fetched_late": 150})) is None
    assert read(ctx({"mapper_batch.jax_dispatches": 100,
                     "mapper_batch.d2h_bytes": 5.7e9})) is None
