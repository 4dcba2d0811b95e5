"""Batched, NumPy-vectorized mapping-search engine.

The scalar mapper walks (spatial choice × factorization × loop order)
candidates one Python iteration at a time; a DSE sweep multiplies that by
every (design, layer) pair and the per-candidate interpreter overhead
dominates the whole repo's hot path.  This module keeps the *same* candidate
enumeration (:func:`repro.core.mapper.enumerate_candidates`) but lowers the
candidate set — for one layer or for **all layers of a workload kind at
once** — into the struct-of-arrays row encoding of
:mod:`repro.core.perf_model` and scores the entire batch in a single
broadcasted :func:`~repro.core.perf_model.perf_kernel` pass.  Selection is a
stable lexicographic argmin per layer, so ties resolve to the first
enumerated candidate exactly like the scalar search; only the winning
:class:`~repro.core.dataflow.Dataflow` is ever materialized.

Because the scalar perf API wraps the identical kernels (batch of one), the
two engines return bit-identical ``(cycles, energy, dataflow)`` decisions —
asserted by the parity suite in ``tests/test_mapper_batch.py``.

``engine="jax"`` swaps the scoring pass for the AOT-compiled XLA kernel in
:mod:`repro.core.perf_model_jax` (one fused dispatch for the whole batch).
There is one solver for it: :func:`best_mappings_design` scores a batch
against D designs in one ``(design, candidate)`` dispatch, and
``best_mappings(engine="jax")`` is its one-design case.  Both build one
query table per solve (:func:`_query_table`) and share one selection and
re-score (:func:`_mappings`).  Selection **stays on the host**: the same
stable lexsort runs over the JAX-scored arrays, and the per-layer winners
are then re-scored through the NumPy kernel, so the reported
:class:`LayerPerf` — and everything downstream of it (mapping caches,
scorecards, Pareto frontiers) — is byte-identical across engines
(``tests/test_engine_parity.py``).
"""

from __future__ import annotations

from bisect import bisect_right
from collections.abc import Sequence
from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

from repro.obs import METRICS, span

from .mapper import (Candidate, Mapping, SpatialChoice, enumerate_candidates,
                     materialize)
from .perf_model import NO_TRUE_SIZE, HWConfig, LayerPerf, perf_kernel
from .workload import Workload

__all__ = ["CandidateBatch", "build_batch", "evaluate_batch", "best_mappings",
           "best_mappings_design"]


@dataclass
class CandidateBatch:
    """Struct-of-arrays form of every mapping candidate of a query batch.

    Row ``i`` is one candidate of layer ``layer_id[i]``; ``offsets`` slices
    rows per layer (``offsets[q] .. offsets[q+1]``).  Array semantics match
    the row encoding documented in :mod:`repro.core.perf_model`.
    """

    wl: Workload
    spatials: list[SpatialChoice]
    candidates: Sequence[Candidate]
    loop_dim: np.ndarray   # (C, L) int64, -1 = padding slot
    loop_size: np.ndarray  # (C, L) int64
    S: np.ndarray          # (C, D) int64 spatial extent per dim
    n_fus: np.ndarray      # (C,) int64
    fill: np.ndarray       # (C,) float64
    layer_id: np.ndarray   # (C,) int64
    offsets: np.ndarray    # (n_layers + 1,) int64

    @property
    def n_candidates(self) -> int:
        return len(self.candidates)


def build_batch(
    wl: Workload,
    dims_list: list[dict[str, int]],
    spatials: list[SpatialChoice],
    hw: HWConfig,
    tile_search: bool = True,
    memo: dict | None = None,
) -> CandidateBatch:
    """Enumerate + lower the candidates of every layer into one batch (span
    ``mapper_batch.enumerate``).

    ``memo`` is a dict owned by one unit of work (a
    :class:`~repro.dse.cache.MappingCache` holds one).  With it, each
    layer's rows are built once per spatial choice and FU count and the
    batch is assembled from them: enumeration dedups only within a spatial
    choice, so a menu's batch is its choices' rows in menu order, and the
    result is byte-identical to the batch built without it."""
    with span("mapper_batch.enumerate", cat="mapper", workload=wl.name):
        if memo is None:
            return _build_batch(wl, dims_list, spatials, hw, tile_search)
        return _assemble_batch(wl, dims_list, spatials, hw, tile_search, memo)


def _build_batch(wl, dims_list, spatials, hw, tile_search) -> CandidateBatch:
    D = len(wl.iter_dims)
    dim_idx = {d: i for i, d in enumerate(wl.iter_dims)}
    per_layer = [enumerate_candidates(wl, dims, spatials, hw,
                                      tile_search=tile_search)
                 for dims in dims_list]
    cands = [c for cl in per_layer for c in cl]
    C = len(cands)
    L = max((len(c.temporal) for c in cands), default=0)

    loop_dim = np.full((C, L), -1, dtype=np.int64)
    loop_size = np.ones((C, L), dtype=np.int64)
    S = np.ones((C, D), dtype=np.int64)
    n_fus = np.empty(C, dtype=np.int64)
    fill = np.empty(C, dtype=np.float64)
    layer_id = np.empty(C, dtype=np.int64)
    offsets = np.zeros(len(dims_list) + 1, dtype=np.int64)

    i = 0
    for li, cl in enumerate(per_layer):
        for c in cl:
            sp = spatials[c.spatial_idx]
            for j, (d, r) in enumerate(c.temporal):
                loop_dim[i, j] = dim_idx[d]
                loop_size[i, j] = r
            nf = 1
            for d, P in zip(sp.dims, c.facs):
                S[i, dim_idx[d]] *= P
                nf *= P
            n_fus[i] = nf
            fill[i] = float(sum(c.facs))
            layer_id[i] = li
            i += 1
        offsets[li + 1] = i
    return CandidateBatch(wl, list(spatials), cands, loop_dim, loop_size, S,
                          n_fus, fill, layer_id, offsets)


class _MenuCandidates(Sequence):
    """Candidates of an assembled batch: the memo's entries, each built on
    a one-choice menu (``spatial_idx`` 0), relabelled on access with their
    choice's index in the batch's menu."""

    def __init__(self, parts: list[tuple[list[Candidate], int]]):
        self._parts = [p for p in parts if p[0]]
        self._starts: list[int] = []
        n = 0
        for cands, _ in self._parts:
            self._starts.append(n)
            n += len(cands)
        self._n = n

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, i: int) -> Candidate:
        if not 0 <= i < self._n:
            raise IndexError("candidate index out of range")
        p = bisect_right(self._starts, i) - 1
        cands, si = self._parts[p]
        c = cands[i - self._starts[p]]
        return c if c.spatial_idx == si else Candidate(si, c.facs, c.temporal)

    def __iter__(self):
        for cands, si in self._parts:
            for c in cands:
                yield (c if c.spatial_idx == si
                       else Candidate(si, c.facs, c.temporal))


def _memo_entry(wl, dims, sp, hw, tile_search) -> CandidateBatch:
    """One layer's rows for one spatial choice, read-only."""
    e = _build_batch(wl, [dims], [sp], hw, tile_search)
    for a in (e.loop_dim, e.loop_size, e.S, e.n_fus, e.fill, e.layer_id,
              e.offsets):
        a.setflags(write=False)
    return e


def _assemble_batch(wl, dims_list, spatials, hw, tile_search,
                    memo: dict) -> CandidateBatch:
    """:func:`_build_batch` from per-(layer, spatial choice) memo entries:
    layers in query order, spatial choices in menu order."""
    parts: list[tuple[CandidateBatch, int]] = []
    per_layer = np.zeros(len(dims_list), dtype=np.int64)
    hits = 0
    for li, dims in enumerate(dims_list):
        dkey = tuple(sorted(dims.items()))
        for si, sp in enumerate(spatials):
            key = (wl.name, sp, hw.n_fus, dkey, tile_search)
            e = memo.get(key)
            if e is None:
                e = memo[key] = _memo_entry(wl, dims, sp, hw, tile_search)
            else:
                hits += 1
            parts.append((e, si))
            per_layer[li] += e.n_candidates
    METRICS.counter("mapper_batch.enum_memo_lookups").inc(len(parts))
    METRICS.counter("mapper_batch.enum_memo_hits").inc(hits)

    C = int(per_layer.sum())
    L = max((e.loop_dim.shape[1] for e, _ in parts), default=0)
    loop_dim = np.full((C, L), -1, dtype=np.int64)
    loop_size = np.ones((C, L), dtype=np.int64)
    S = np.ones((C, len(wl.iter_dims)), dtype=np.int64)
    n_fus = np.empty(C, dtype=np.int64)
    fill = np.empty(C, dtype=np.float64)
    lo = 0
    for e, _ in parts:
        hi = lo + e.n_candidates
        el = e.loop_dim.shape[1]
        loop_dim[lo:hi, :el] = e.loop_dim
        loop_size[lo:hi, :el] = e.loop_size
        S[lo:hi] = e.S
        n_fus[lo:hi] = e.n_fus
        fill[lo:hi] = e.fill
        lo = hi
    layer_id = np.repeat(np.arange(len(dims_list), dtype=np.int64), per_layer)
    offsets = np.zeros(len(dims_list) + 1, dtype=np.int64)
    np.cumsum(per_layer, out=offsets[1:])
    cands = _MenuCandidates([(e.candidates, si) for e, si in parts])
    return CandidateBatch(wl, list(spatials), cands, loop_dim, loop_size, S,
                          n_fus, fill, layer_id, offsets)


class _Queries(NamedTuple):
    """The query table of one solve: what a layer and a design add to the
    candidate rows."""

    true: np.ndarray  # (n_layers, D) int64 true sizes, NO_TRUE_SIZE if none
    ppu: np.ndarray   # (n_layers,) float64 PPU elements
    dn: np.ndarray    # (n_designs, T) int64 data-node row of each design


def _query_table(wl: Workload, dims_list: list[dict[str, int]],
                 ppu_list: list[float], hw_list: list[HWConfig],
                 dnt_list: Sequence[dict[str, int] | None]) -> _Queries:
    """Build the :class:`_Queries` of a solve, once for all its designs."""
    true = np.full((len(dims_list), len(wl.iter_dims)), NO_TRUE_SIZE,
                   dtype=np.int64)
    for li, dims in enumerate(dims_list):
        for i, d in enumerate(wl.iter_dims):
            if d in dims:
                true[li, i] = dims[d]
    # a tensor without a count reads one bank per FU: mapper candidates
    # always span exactly hw.n_fus FUs, so min(dn, n_fus) == n_fus either way
    dn = np.array([[(dnt or {}).get(t.name, hw.n_fus) for t in wl.tensors]
                   for hw, dnt in zip(hw_list, dnt_list)], dtype=np.int64)
    return _Queries(true, np.asarray(ppu_list, dtype=np.float64), dn)


def _score(kernel, batch: CandidateBatch, hw: HWConfig, q: _Queries,
           di: int = 0, rows=slice(None)) -> dict[str, np.ndarray]:
    """``kernel`` over the candidate ``rows`` of ``batch`` for ``hw``, the
    design of data-node row ``q.dn[di]``."""
    lid = batch.layer_id[rows]
    dn = q.dn[di:di + 1]
    return kernel(batch.wl, hw, batch.loop_dim[rows], batch.loop_size[rows],
                  batch.S[rows], n_fus=batch.n_fus[rows],
                  fill=batch.fill[rows], true_sizes=q.true[lid],
                  data_nodes=np.broadcast_to(dn, (len(lid), dn.shape[1])),
                  ppu_elements=q.ppu[lid])


def evaluate_batch(
    batch: CandidateBatch,
    hw: HWConfig,
    dims_list: list[dict[str, int]],
    ppu_list: list[float],
    data_nodes_per_tensor: dict[str, int] | None = None,
    engine: str = "numpy",
) -> dict[str, np.ndarray]:
    """Score every candidate row: one broadcasted perf-kernel pass.

    ``engine="numpy"`` runs the broadcasted NumPy kernels;
    ``engine="jax"`` runs the jitted XLA port — integer-derived outputs
    are bit-identical, ``energy_pj`` within
    :data:`repro.core.perf_model_jax.ENERGY_RTOL` (see that module for the
    tolerance policy), returned in a read-only mapping that copies each
    output but ``cycles`` and ``energy_pj`` from the device on first read."""
    q = _query_table(batch.wl, dims_list, ppu_list, [hw],
                     [data_nodes_per_tensor])
    return _score(_kernel(engine), batch, hw, q)


def _kernel(engine: str):
    """The scoring kernel of ``engine``; the JAX one is looked up on each
    call, so a wrapper installed on its module sees every dispatch."""
    if engine == "numpy":
        return perf_kernel
    if engine == "jax":
        from .perf_model_jax import perf_kernel_jax
        return perf_kernel_jax
    raise ValueError(f"unknown engine {engine!r} (expected 'numpy' or 'jax')")


def _argbest(cycles: np.ndarray, energy: np.ndarray, objective: str) -> int:
    """Index of the objective-minimal candidate; ties resolve to the first
    enumerated row (stable lexsort), matching the scalar strict-< search."""
    if objective == "cycles":
        return int(np.lexsort((energy, cycles))[0])
    if objective == "energy":
        return int(np.lexsort((cycles, energy))[0])
    if objective == "edp":
        return int(np.argmin(cycles * energy))
    raise ValueError(f"unknown objective {objective!r}")


def best_mappings(
    wl: Workload,
    queries: list[tuple[dict[str, int], float]],
    spatials: list[SpatialChoice],
    hw: HWConfig,
    data_nodes_per_tensor: dict[str, int] | None = None,
    objective: str = "cycles",
    tile_search: bool = True,
    engine: str = "numpy",
    memo: dict | None = None,
) -> list[Mapping]:
    """Best mapping for every ``(dims, ppu_elements)`` query of one workload.

    All queries share the spatial-dataflow menu and data-node counts (the
    DSE evaluator's per-workload-kind shape), so their candidate sets are
    concatenated and scored in a single kernel pass; argmin runs per layer
    slice.  Only winners become :class:`Dataflow`/:class:`Mapping` objects.

    ``engine="numpy"`` reports the NumPy scores of the winners directly.
    ``engine="jax"`` is the one-design case of
    :func:`best_mappings_design`: the candidate scores come from one XLA
    dispatch (:func:`~repro.core.perf_model_jax.perf_kernel_jax`), the
    stable-lexsort selection runs on the host, and the per-layer winners
    are re-scored through the NumPy kernel, so the returned
    :class:`Mapping` is byte-identical to the ``engine="numpy"`` result.
    ``memo`` is passed to :func:`build_batch`.
    """
    kernel = _kernel(engine)
    dims_list = [q[0] for q in queries]
    ppu_list = [float(q[1]) for q in queries]
    batch = build_batch(wl, dims_list, spatials, hw, tile_search=tile_search,
                        memo=memo)
    q = _query_table(wl, dims_list, ppu_list, [hw], [data_nodes_per_tensor])
    r = _score(kernel, batch, hw, q)
    METRICS.counter("mapper.batch_solves").inc()
    METRICS.counter("mapper.layers_solved").inc(len(queries))
    METRICS.counter("mapper.candidates_scored").inc(batch.n_candidates)
    return _mappings(batch, r["cycles"][None], r["energy_pj"][None], [hw], q,
                     objective, reported=r if engine == "numpy" else None)[0]


def best_mappings_design(
    wl: Workload,
    queries: list[tuple[dict[str, int], float]],
    spatials: list[SpatialChoice],
    hw_list: list[HWConfig],
    data_nodes_per_tensor_list: list[dict[str, int] | None] | None = None,
    objective: str = "cycles",
    tile_search: bool = True,
    min_c: int = 1,
    min_l: int = 4,
    min_d: int = 1,
    batch: CandidateBatch | None = None,
) -> list[list[Mapping]]:
    """Best mappings for every query against **D design points** at once.

    One candidate batch is enumerated (all designs must share ``n_fus`` —
    candidate enumeration depends on the design only through the FU count,
    asserted here) and one ``(design, candidate)`` XLA dispatch scores it
    against every design's runtime HW parameters
    (:func:`perf_kernel_jax_design`).  Selection and reporting follow the
    engine contract per design: host-side stable lexsort over the JAX
    scores, then the per-layer winners are re-scored through the NumPy
    kernel, so ``result[d]`` is byte-identical to
    ``best_mappings(..., hw_list[d], engine="jax")``, its one-design case —
    and therefore to the NumPy engine.  Returns ``result[d][q]``
    (D × len(queries) mappings).

    ``min_c``/``min_l``/``min_d`` forward bucket floors to the kernel so a
    tiled sweep can pin one compiled shape across tiles.
    """
    from .perf_model_jax import perf_kernel_jax_design

    assert hw_list, "best_mappings_design needs at least one design"
    assert len({hw.n_fus for hw in hw_list}) == 1, \
        "design batch must share n_fus (identical candidate enumeration)"
    dims_list = [q[0] for q in queries]
    ppu_list = [float(q[1]) for q in queries]
    if batch is None:
        batch = build_batch(wl, dims_list, spatials, hw_list[0],
                            tile_search=tile_search)
    q = _query_table(wl, dims_list, ppu_list, hw_list,
                     data_nodes_per_tensor_list or [None] * len(hw_list))
    lid = batch.layer_id
    r = perf_kernel_jax_design(
        wl, hw_list, batch.loop_dim, batch.loop_size, batch.S,
        n_fus=batch.n_fus, fill=batch.fill, true_sizes=q.true[lid],
        data_nodes=q.dn, ppu_elements=q.ppu[lid], min_c=min_c, min_l=min_l,
        min_d=min_d)
    METRICS.counter("mapper.design_batch_solves").inc()
    METRICS.counter("mapper.layers_solved").inc(len(hw_list) * len(queries))
    METRICS.counter("mapper.candidates_scored").inc(
        len(hw_list) * batch.n_candidates)
    return _mappings(batch, r["cycles"], r["energy_pj"], hw_list, q,
                     objective)


def _mappings(batch: CandidateBatch, cycles: np.ndarray, energy: np.ndarray,
              hw_list: list[HWConfig], q: _Queries, objective: str,
              reported: dict | None = None) -> list[list[Mapping]]:
    """Every design's per-layer winners as :class:`Mapping` lists, from the
    ``(D, C)`` scores of ``batch`` (span ``mapper_batch.select``).

    The winners' reported numbers (span ``mapper_batch.rescore``) come from
    the NumPy kernel: from ``reported``, the one design's NumPy scores of
    the whole batch, where given; else from a re-score of the winner rows,
    so float-ulp drift in device energies never leaks into caches or
    frontiers."""
    n_layers = len(q.true)
    with span("mapper_batch.select", cat="mapper"):
        winners = [_winners(batch, cycles[di], energy[di], n_layers,
                            objective) for di in range(len(hw_list))]
    sps = batch.spatials
    out: list[list[Mapping]] = []
    with span("mapper_batch.rescore", cat="mapper"):
        for di, (hw, rows) in enumerate(zip(hw_list, winners)):
            if reported is None:  # row i of the re-score is winner i
                r = _score(perf_kernel, batch, hw, q, di, rows)
                at = range(n_layers)
            else:
                r, at = reported, rows
            cands = [batch.candidates[w] for w in rows]
            out.append([Mapping(materialize(batch.wl, c, sps),
                                LayerPerf.from_kernel(r, i),
                                sps[c.spatial_idx])
                        for c, i in zip(cands, at)])
    return out


def _winners(batch: CandidateBatch, cycles: np.ndarray, energy: np.ndarray,
             n_queries: int, objective: str) -> list[int]:
    """Row of the objective-best candidate of every query's slice."""
    winners: list[int] = []
    for li in range(n_queries):
        lo, hi = int(batch.offsets[li]), int(batch.offsets[li + 1])
        assert hi > lo, "no feasible mapping"
        winners.append(lo + _argbest(cycles[lo:hi], energy[lo:hi],
                                     objective))
    return winners
