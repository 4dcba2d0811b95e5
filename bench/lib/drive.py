"""The general traffic generator: set-up and measured window of a mix.

A mix's ``mode`` picks one of two generators, both over the program's public
entry points with a fresh, in-memory mapping cache per unit of work (no
mapping cache or run ledger on disk is ever read):

``sweep``
    ``batch_sweep`` over every valid design of the mix's space, pass after
    pass; the seed permutes the design list of each pass.
``evolve``
    ``evolve_search`` under the mix's budget, one search for each of the
    mix's search seeds, in an order the run's seed draws: every run does
    the same searches, so the seed does not change the work.

The window runs whole units of work (passes, searches) until at least
``seconds`` have gone by, so every design counted was scored with all the
work a user's sweep or search spends on it.
"""

from __future__ import annotations

import time

import numpy as np


class WindowStats:
    def __init__(self):
        self.t0 = 0.0
        self.t1 = 0.0
        self.results = []      # SearchResult of every completed unit
        self.units = 0

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def evals(self) -> list:
        return [e for r in self.results for e in r.evals]


def _evaluator(zoo, traffic):
    from repro.dse.cache import MappingCache
    from repro.dse.evaluate import Evaluator

    return Evaluator(zoo=zoo, cache=MappingCache(), engine="jax",
                     objective=traffic.get("objective", "cycles"))


def _space(traffic):
    from .cell import design_space

    return design_space(traffic)


def _points(traffic):
    return list(_space(traffic).enumerate())


# -- sweep ------------------------------------------------------------------

def sweep_warmup(zoo, traffic) -> None:
    """Compile the kernel shapes a pass uses.  A pass pads each dispatch to
    the largest candidate set its workload kind has met so far, and the
    candidate sets depend on the design group (FU count, dataflow set)
    only, so a pass over one design of every group, in the pass's order,
    meets the same shapes in the same order."""
    from repro.dse.batch_sweep import batch_sweep, plan_tiles

    d_tile = traffic["d_tile"]
    groups = {}
    for tile in plan_tiles(_points(traffic), d_tile=d_tile):
        groups.setdefault((tile[0].n_fus, tile[0].dataflow_set), tile[0])
    batch_sweep(list(groups.values()), _evaluator(zoo, traffic),
                d_tile=d_tile)


def sweep_window(zoo, traffic, seed: int, seconds: float) -> WindowStats:
    from repro.dse.batch_sweep import batch_sweep

    points = _points(traffic)
    rng = np.random.default_rng([seed, 0x5EE9])
    st = WindowStats()
    st.t0 = time.perf_counter()
    while True:
        order = [points[i] for i in rng.permutation(len(points))]
        st.results.append(batch_sweep(order, _evaluator(zoo, traffic),
                                      d_tile=traffic["d_tile"]))
        st.units += 1
        st.t1 = time.perf_counter()
        if st.t1 - st.t0 >= seconds:
            return st


# -- evolve -----------------------------------------------------------------

def _evolve_kw(traffic) -> dict:
    return {"budget": traffic["budget"], "population": traffic["population"]}


def evolve_warmup(zoo, traffic) -> None:
    """Compile every per-design kernel shape the window uses by running the
    window's own searches once.  A search is deterministic per (seed,
    budget) and starts from an empty cache, so the window meets exactly
    these dispatch shapes, whatever rules the search follows inside."""
    from repro.dse.search import evolve_search

    space = _space(traffic)
    for s in traffic["search_seeds"]:
        evolve_search(space, _evaluator(zoo, traffic), seed=int(s),
                      **_evolve_kw(traffic))


def evolve_window(zoo, traffic, seed: int, seconds: float) -> WindowStats:
    from repro.dse.search import evolve_search

    space = _space(traffic)
    seeds = traffic["search_seeds"]
    rng = np.random.default_rng([seed, 0xE701])
    st = WindowStats()
    st.t0 = time.perf_counter()
    while True:
        for i in rng.permutation(len(seeds)):
            st.results.append(evolve_search(
                space, _evaluator(zoo, traffic), seed=int(seeds[i]),
                **_evolve_kw(traffic)))
            st.units += 1
        st.t1 = time.perf_counter()
        if st.t1 - st.t0 >= seconds:
            return st


MODES = {"sweep": (sweep_warmup, sweep_window),
           "evolve": (evolve_warmup, evolve_window)}
