"""Design-axis batched sweep: score a whole tile of designs per dispatch.

A per-design sweep pays the full extents → footprint → traffic chain once
per (design, workload-kind) even though that math only depends on the
candidate set — and candidate enumeration depends on the design only
through its FU count.  This orchestrator exploits that structure:

1. **Group** the space by ``(n_fus, dataflow_set)``: every design in a
   group enumerates the identical candidate batch, shares its PPU count and
   √N data-node estimate, and differs only in runtime HW parameters
   (buffer, bandwidth — exactly what PR 8 made kernel *arguments*).
2. **Tile** each group along the design axis into pow2-bucketed ``(D, C)``
   blocks and *prefill* the mapping cache: one
   :func:`~repro.core.mapper_batch.best_mappings_design` dispatch per
   (tile, workload kind) solves every missing (design, layer-shape) query.
   Bucket floors are carried across tiles per workload kind, so after
   warm-up one compiled kernel serves every tile
   (``mapper_batch.jax_compiles`` stays at one per kind — the check.sh
   gate pins ≤2 across ≥3 tiles).
3. **Evaluate** each tile through the ordinary
   :class:`~repro.dse.supervisor.Supervisor` → :class:`Evaluator` path on
   the now-warm cache.  Every query hits, so the evaluator does pure
   aggregation — and because the prefilled entries are NumPy-rescored
   winners in the exact ``best_mapping_perfs`` entry format, the resulting
   ``DesignEval``s (and the Pareto frontier) are **byte-identical** to a
   per-design ``--engine numpy`` sweep.  Fusion credits, baselines,
   area/power and serving replay all reuse the unchanged evaluator code.
4. **Snapshot** the frontier into the :class:`~repro.dse.supervisor.RunLedger`
   every ``snapshot_every`` tiles, so a killed 10⁵-design run documents how
   the frontier converged and ``--resume`` (ledger-completed designs skip
   both prefill and evaluation) picks up at the last tile boundary.
"""

from __future__ import annotations

from repro.core.fusion import estimate_data_nodes
from repro.core.mapper_batch import best_mappings_design, build_batch
from repro.core.perf_model_jax import jax_available
from repro.frontend import has_attention_rows
from repro.obs import METRICS, get_logger, span

from .cache import mapping_key
from .evaluate import Evaluator
from .search import SearchResult, pareto_frontier
from .space import DesignPoint, DesignSpace
from .supervisor import Supervisor

_LOG = get_logger("dse.batch_sweep")

__all__ = ["batch_sweep", "plan_tiles"]

# default designs per tile: pow2 so the (D, C) bucket is exact; big enough
# that the design-invariant candidate math amortizes over the whole tile,
# small enough that partial groups still fill most of the padded axis
DEFAULT_TILE = 32


def plan_tiles(points: list[DesignPoint],
               d_tile: int = DEFAULT_TILE) -> list[list[DesignPoint]]:
    """Group by ``(n_fus, dataflow_set)`` (identical candidate enumeration)
    and split each group into design-axis tiles of at most ``d_tile``.

    Groups are ordered by descending FU count so the widest candidate batch
    per workload kind compiles first and the bucket floors never grow
    mid-sweep — later, narrower tiles reuse the same compiled shape.
    """
    groups: dict[tuple[int, str], list[DesignPoint]] = {}
    for p in points:
        groups.setdefault((p.n_fus, p.dataflow_set), []).append(p)
    tiles: list[list[DesignPoint]] = []
    for key in sorted(groups, key=lambda k: (-k[0], k[1])):
        g = groups[key]
        tiles.extend(g[i:i + d_tile] for i in range(0, len(g), d_tile))
    return tiles


def _prefill_queries(evaluator: Evaluator, rep: DesignPoint) -> list[tuple]:
    """The distinct mapping queries one design of ``rep``'s group issues.

    Mirrors the evaluator's scoring walk exactly — fused zoo, plus the
    unfused attention-bearing subset when the design is fusion-capable (the
    ``speedup_fused_attention`` denominator) — and dedups per workload
    kind.  Returns ``[(wl, spatials, data_nodes, [(dims, ppu), ...]), ...]``.
    """
    fused = (rep.supports("attention_qk") and rep.supports("attention_pv"))
    zoos = [evaluator._zoo_layers(fused)]
    if fused:
        zoos.append({n: ls for n, ls in evaluator._zoo_layers(False).items()
                     if has_attention_rows(evaluator.zoo[n])})
    kinds: dict[str, tuple] = {}
    seen: dict[str, set] = {}
    for zoo_layers in zoos:
        for layers in zoo_layers.values():
            for wl, dims, _, ppu in layers:
                if wl.name not in kinds:
                    dn = estimate_data_nodes(rep.n_fus,
                                             [t.name for t in wl.tensors])
                    kinds[wl.name] = (wl, rep.spatials(wl.name), dn, [])
                    seen[wl.name] = set()
                sig = (tuple(sorted(dims.items())), float(ppu))
                if sig not in seen[wl.name]:
                    seen[wl.name].add(sig)
                    kinds[wl.name][3].append((dims, float(ppu)))
    return list(kinds.values())


def _prefill_tile(evaluator: Evaluator, tile: list[DesignPoint],
                  buckets: dict[str, tuple[int, int]], d_tile: int) -> int:
    """Solve every cache-missing (design, query) pair of one tile in
    design-batched dispatches (one per workload kind with misses); returns
    the number of entries added.  ``buckets`` carries the per-kind running
    ``(min_c, min_l)`` floors that keep all tiles on one compiled shape."""
    cache = evaluator.cache
    objective = evaluator.objective
    hw_list = [p.hw_config() for p in tile]
    added = 0
    for wl, sps, dn, queries in _prefill_queries(evaluator, tile[0]):
        with span("mapper_cache.keys", cat="mapper"):
            keys = [[mapping_key(wl, dims, sps, hw, dn, ppu, objective)
                     for dims, ppu in queries] for hw in hw_list]
            need_d = [di for di in range(len(tile))
                      if any(not cache.contains(k) for k in keys[di])]
        if not need_d:
            continue
        # solve the full query set for every design that misses anything:
        # per-query subsetting would fragment the (D, C) dispatch shape
        # for no win — the batch is one compiled call either way
        min_c, min_l = buckets.get(wl.name, (1, 4))
        cand = build_batch(wl, [q[0] for q in queries], sps, hw_list[0],
                           memo=cache.enum_memo)
        mappings = best_mappings_design(
            wl, queries, sps, [hw_list[di] for di in need_d],
            data_nodes_per_tensor_list=[dn] * len(need_d),
            objective=objective, min_c=min_c, min_l=min_l, min_d=d_tile,
            batch=cand)
        for row, di in enumerate(need_d):
            for qi, m in enumerate(mappings[row]):
                if not cache.contains(keys[di][qi]):
                    cache.put(keys[di][qi],
                              {"perf": m.perf.as_dict(),
                               "spatial": m.spatial.name,
                               "dataflow": m.dataflow.name})
                    added += 1
        # remember the widest shape this kind has seen; plan_tiles orders
        # groups by descending FU count, so in practice the floor is set by
        # the first tile of a kind and never grows afterwards
        buckets[wl.name] = (max(min_c, cand.n_candidates),
                            max(min_l, cand.loop_size.shape[1]))
    return added


def batch_sweep(space: DesignSpace | list[DesignPoint],
                evaluator: Evaluator,
                workers: int = 1,
                supervisor: Supervisor | None = None,
                log=None,
                d_tile: int = DEFAULT_TILE,
                snapshot_every: int = 1) -> SearchResult:
    """Exhaustive sweep with design-axis batched mapping search.

    Drop-in replacement for :func:`~repro.dse.search.exhaustive_search`
    (same :class:`SearchResult`, byte-identical evals/frontier) that scores
    mapping candidates D designs at a time through the JAX engine.  Designs
    already completed in ``supervisor``'s ledger skip both prefill and
    evaluation; the frontier-so-far is checkpointed into the ledger every
    ``snapshot_every`` tiles.
    """
    if not jax_available():
        raise RuntimeError("batch_sweep needs the jax runtime "
                           "(engine='jax'); use exhaustive_search instead")
    points = list(space.enumerate()) if isinstance(space, DesignSpace) \
        else list(space)
    space_name = space.name if isinstance(space, DesignSpace) else "custom"
    tiles = plan_tiles(points, d_tile=d_tile)
    _LOG.info("design-batched sweep: %d points in %d tiles (d_tile=%d) "
              "over space %r", len(points), len(tiles), d_tile, space_name)
    buckets: dict[str, tuple[int, int]] = {}
    by_name = {}
    with span("dse.batch_sweep", cat="dse", space=space_name,
              n_points=len(points), n_tiles=len(tiles),
              d_tile=d_tile) as sp, \
            _supervised(evaluator, workers, supervisor) as pe:
        for ti, tile in enumerate(tiles):
            todo = [p for p in tile if p.name not in pe.completed]
            if todo:
                with span("dse.batch_sweep.prefill", cat="dse", tile=ti,
                          designs=len(todo)):
                    added = _prefill_tile(evaluator, todo, buckets, d_tile)
                METRICS.counter("dse.prefill_entries").inc(added)
            METRICS.counter("dse.tiles_swept").inc()
            for e in pe.map(tile, log=log):
                by_name[e.point.name] = e
            if pe.ledger is not None and (ti + 1) % max(1,
                                                        snapshot_every) == 0:
                pe.ledger.record_frontier(
                    pareto_frontier(list(by_name.values())))
                pe.ledger.flush()
    # report in enumeration order: evals / frontier / BENCH artifacts are
    # byte-identical to the per-design exhaustive sweep, tiling invisible
    evals = [by_name[p.name] for p in points]
    return SearchResult(space=space_name, strategy="exhaustive",
                        evals=evals, frontier=pareto_frontier(evals),
                        wall_s=sp.duration_s,
                        cache_stats=evaluator.cache.stats,
                        supervisor=dict(pe.stats))


def _supervised(evaluator: Evaluator, workers: int,
                supervisor: Supervisor | None) -> Supervisor:
    if supervisor is not None:
        return supervisor
    if workers > 1:
        # pool workers snapshot the cache at spawn time — tiles prefilled
        # after that would re-solve in-process; the XLA design axis already
        # replaces process parallelism, so run the evaluation loop inline
        _LOG.warning("batch_sweep ignores workers=%d (design-axis batching "
                     "replaces the process pool); evaluating in-process",
                     workers)
    return Supervisor(evaluator, workers=1)
