"""The readers of ``sweep.enum_memo_hit_share`` and
``evolve.enum_memo_hit_share``: their value, and nothing where the program
keeps no enumeration memo."""

import pytest

from bench.lib.harness import _reader

NAMES = ["sweep.enum_memo_hit_share", "evolve.enum_memo_hit_share"]


def ctx(counters):
    return {"designs": 250, "window_s": 12.0, "spans": {},
            "counters": dict(counters), "setup": {}, "device": None}


@pytest.mark.parametrize("name", NAMES)
def test_reader_value(name):
    read = _reader(name)
    assert read(ctx({"mapper_batch.enum_memo_lookups": 400,
                     "mapper_batch.enum_memo_hits": 348})) == \
        pytest.approx(87.0, rel=1e-12)
    # every lookup built its rows afresh
    assert read(ctx({"mapper_batch.enum_memo_lookups": 400})) == 0.0


@pytest.mark.parametrize("name", NAMES)
def test_reader_silent_without_lookups(name):
    read = _reader(name)
    assert read(ctx({})) is None
    assert read(ctx({"mapper_batch.enum_memo_lookups": 0})) is None
    # a program without the memo still counts what it enumerates
    assert read(ctx({"mapper.candidates_enumerated": 5000,
                     "mapper_batch.jax_dispatches": 100})) is None
