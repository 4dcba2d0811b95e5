"""The per-layer readers of the scoring engine's dispatch phases and the
host mapping search, on a hand-built context: the value each gives, and
nothing where the program records no such span or counter."""

import pytest

from bench.lib.harness import _reader

SPANS = {"dse.evaluate": 10.0, "dse.batch_sweep.prefill": 6.0,
         "mapper_batch.enumerate": 1.0, "mapper_batch.pack": 0.25,
         "mapper_batch.jax_execute": 4.0, "mapper_batch.transfer_in": 1.5,
         "mapper_batch.device_wait": 0.5, "mapper_batch.copy_out": 1.75,
         "mapper_batch.select": 0.5, "mapper_batch.rescore": 0.75,
         "mapper_cache.keys": 0.125}
COUNTERS = {"mapper_batch.h2d_bytes": 4.5e9,
            "mapper_batch.d2h_bytes": 3.5e9,
            "mapper_batch.jax_candidates": 3000,
            "mapper_batch.jax_rows_padded": 4096}
DESIGNS = 250


def ctx(spans=SPANS, counters=COUNTERS, designs=DESIGNS):
    return {"designs": designs, "window_s": 12.0, "spans": dict(spans),
            "counters": dict(counters), "setup": {}, "device": None}


def per_design(*names):
    return 1e3 * sum(SPANS[n] for n in names) / DESIGNS


# metric -> (expected value, the spans and counters it reads)
CASES = {
    **{f"{cell}.{m}_ms_per_design": (per_design(f"mapper_batch.{m}"),
                                     [f"mapper_batch.{m}"])
       for cell in ("sweep", "evolve")
       for m in ("transfer_in", "device_wait", "copy_out", "enumerate")},
    **{f"{cell}.select_rescore_ms_per_design": (
        per_design("mapper_batch.select", "mapper_batch.rescore"),
        ["mapper_batch.select", "mapper_batch.rescore"])
       for cell in ("sweep", "evolve")},
    "sweep.cache_keys_ms_per_design": (per_design("mapper_cache.keys"),
                                       ["mapper_cache.keys"]),
    "sweep.copy_out_gb_per_s": (2.0, ["mapper_batch.d2h_bytes",
                                      "mapper_batch.copy_out"]),
    "sweep.transfer_in_gb_per_s": (3.0, ["mapper_batch.h2d_bytes",
                                         "mapper_batch.transfer_in"]),
    "sweep.row_fill_share": (100.0 * 3000 / 4096,
                             ["mapper_batch.jax_rows_padded"]),
    "evolve.evaluate_self_ms_per_design": (
        1e3 * (10.0 - 1.0 - 0.25 - 4.0 - 0.5 - 0.75 - 0.125) / DESIGNS,
        ["dse.evaluate", "mapper_batch.enumerate", "mapper_batch.pack",
         "mapper_batch.jax_execute", "mapper_batch.select",
         "mapper_batch.rescore", "mapper_cache.keys"]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_value(name):
    want, _ = CASES[name]
    assert _reader(name)(ctx()) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_silent_without_its_source(name):
    read = _reader(name)
    for key in CASES[name][1]:
        spans = {k: v for k, v in SPANS.items() if k != key}
        counters = {k: v for k, v in COUNTERS.items() if k != key}
        assert read(ctx(spans, counters)) is None, key
    if name.endswith("_per_design"):
        assert read(ctx(designs=0)) is None


def test_older_program_reads_nothing():
    """Every new reader is silent on the spans and counters a program
    without the dispatch phases and search spans records."""
    old = {k: SPANS[k] for k in ("dse.evaluate", "dse.batch_sweep.prefill",
                                 "mapper_batch.jax_execute")}
    old_counters = {"mapper_batch.jax_candidates": 3000}
    for name in CASES:
        assert _reader(name)(ctx(old, old_counters)) is None, name
