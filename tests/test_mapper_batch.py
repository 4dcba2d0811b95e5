"""Parity + determinism suite for the batched mapping engine.

The batched engine (``repro.core.mapper_batch``) must return bit-identical
``(cycles, energy, spatial, dataflow)`` decisions to the scalar reference
path — both engines share the candidate enumeration and the perf kernels, so
any drift is a real bug.  Randomized parity runs on seeded ``random`` (always
exercised) plus hypothesis property tests where available; the worker-pool
sweep must produce a frontier independent of the worker count.
"""

import random

import numpy as np
import pytest

from conftest import given, settings, st

from repro.core import workload as W
from repro.core.mapper import (SpatialChoice, best_mapping,
                               enumerate_candidates, factor_pairs)
from repro.core.mapper_batch import best_mappings, build_batch
from repro.core.perf_model import HWConfig, layer_perf

GEMM_SP = [SpatialChoice(("i", "j"), (1, 1), "ij"),
           SpatialChoice(("k", "j"), (1, 1), "jk")]
HW = HWConfig(n_fus=256)

_WLS = {w.name: w for w in (W.gemm(), W.conv2d(), W.depthwise_conv2d(),
                            W.attention_qk(), W.mttkrp())}
_SP_MENU = {
    "gemm": GEMM_SP + [SpatialChoice(("j",), (1,), "j1")],
    "conv2d": [SpatialChoice(("ow", "oh"), (0, 0), "ohow"),
               SpatialChoice(("ic", "oc"), (1, 1), "icoc")],
    "dwconv2d": [SpatialChoice(("ow", "oh"), (0, 0), "ohow")],
    "attention_qk": [SpatialChoice(("m", "n"), (1, 1), "mn"),
                     SpatialChoice(("d", "n"), (1, 1), "nd")],
    "mttkrp": [SpatialChoice(("i", "j"), (1, 1), "ij")],
}
_DIM_VALUES = (1, 3, 7, 16, 56, 130, 512, 2048)


def _random_case(rng):
    name = rng.choice(sorted(_WLS))
    wl = _WLS[name]
    dims = {d: rng.choice(_DIM_VALUES) for d in wl.iter_dims}
    hw = HWConfig(n_fus=rng.choice([64, 256, 1024]),
                  buffer_bytes=rng.choice([64, 256, 1024]) * 1024,
                  dram_gbps=rng.choice([8.0, 16.0, 64.0]))
    obj = rng.choice(["cycles", "energy", "edp"])
    dn = ({t.name: rng.choice([8, 16]) for t in wl.tensors}
          if rng.random() < 0.5 else None)
    ppu = rng.choice([0.0, 4096.0])
    return wl, dims, _SP_MENU[name], hw, dn, ppu, obj


def _assert_same_mapping(ms, mb, ctx=""):
    for f in ("cycles", "energy_pj", "macs", "utilization", "dram_bytes",
              "sram_reads", "ppu_cycles"):
        assert getattr(ms.perf, f) == getattr(mb.perf, f), (f, ctx)
    assert ms.perf.bound == mb.perf.bound, ctx
    assert ms.spatial.name == mb.spatial.name, ctx
    # dataflow construction is memoized: identical decisions share objects
    assert ms.dataflow is mb.dataflow, ctx


class TestScalarBatchParity:
    @pytest.mark.parametrize("seed", range(4))
    def test_randomized_parity(self, seed):
        """Seeded-random parity across workloads/dims/HWConfigs/objectives
        (runs everywhere, no hypothesis needed)."""
        rng = random.Random(seed)
        for _ in range(25):
            wl, dims, sps, hw, dn, ppu, obj = _random_case(rng)
            ms = best_mapping(wl, dims, sps, hw, data_nodes_per_tensor=dn,
                              ppu_elements=ppu, objective=obj,
                              engine="scalar")
            mb = best_mapping(wl, dims, sps, hw, data_nodes_per_tensor=dn,
                              ppu_elements=ppu, objective=obj,
                              engine="numpy")
            _assert_same_mapping(ms, mb, (wl.name, dims, obj))

    def test_tile_search_parity_and_no_regression(self):
        """The default (tile-widened) space: scalar/batch parity over the
        tiled candidates — the gate that let tile_search flip default-on."""
        rng = random.Random(7)
        for _ in range(10):
            wl, dims, sps, hw, dn, ppu, obj = _random_case(rng)
            ms = best_mapping(wl, dims, sps, hw, data_nodes_per_tensor=dn,
                              ppu_elements=ppu, objective="cycles",
                              engine="scalar", tile_search=True)
            mb = best_mapping(wl, dims, sps, hw, data_nodes_per_tensor=dn,
                              ppu_elements=ppu, objective="cycles",
                              engine="numpy", tile_search=True)
            _assert_same_mapping(ms, mb, (wl.name, dims, "tile"))
            base = best_mapping(wl, dims, sps, hw, data_nodes_per_tensor=dn,
                                ppu_elements=ppu, objective="cycles",
                                tile_search=False)
            # tile search only widens the space: never worse, and identical
            # when no split wins (ties keep the earlier base candidate)
            assert mb.perf.cycles <= base.perf.cycles

    def test_multi_query_matches_single(self):
        wl = W.gemm()
        queries = [(dict(i=64, j=256, k=128), 0.0),
                   (dict(i=512, j=512, k=512), 16.0),
                   (dict(i=1, j=4096, k=4096), 0.0)]
        many = best_mappings(wl, queries, GEMM_SP, HW)
        for (dims, ppu), m_many in zip(queries, many):
            m_one = best_mapping(wl, dims, GEMM_SP, HW, ppu_elements=ppu)
            _assert_same_mapping(m_one, m_many, dims)

    @settings(max_examples=40, deadline=None)
    @given(st.tuples(st.sampled_from(_DIM_VALUES),
                     st.sampled_from(_DIM_VALUES),
                     st.sampled_from(_DIM_VALUES)),
           st.sampled_from([64, 256, 1024]),
           st.sampled_from(["cycles", "energy", "edp"]))
    def test_property_gemm_parity(self, ijk, n_fus, objective):
        wl = W.gemm()
        dims = dict(zip("ijk", ijk))
        hw = HWConfig(n_fus=n_fus)
        ms = best_mapping(wl, dims, GEMM_SP, hw, objective=objective,
                          engine="scalar")
        mb = best_mapping(wl, dims, GEMM_SP, hw, objective=objective,
                          engine="numpy")
        _assert_same_mapping(ms, mb, (dims, objective))


class TestEnumeration:
    def test_single_dim_spatial_deduped(self):
        """The historical duplicate-work bug: a 1-D spatial choice collapsed
        every factor pair to the same (n_fus,) candidate."""
        wl = W.gemm()
        sps = [SpatialChoice(("j",), (1,), "j1")]
        cands = enumerate_candidates(wl, dict(i=64, j=512, k=64), sps, HW,
                                     tile_search=False)
        keys = [(c.spatial_idx, c.facs, c.temporal) for c in cands]
        assert len(keys) == len(set(keys))
        assert all(c.facs == (HW.n_fus,) for c in cands)
        # without dedup this would be ~len(factor_pairs) times larger
        assert len(cands) <= len(factor_pairs(HW.n_fus)) * 5
        # the default (tiled) space dedups the same way
        tiled = enumerate_candidates(wl, dict(i=64, j=512, k=64), sps, HW)
        tkeys = [(c.spatial_idx, c.facs, c.temporal) for c in tiled]
        assert len(tkeys) == len(set(tkeys))

    def test_batch_rows_match_candidates(self):
        wl = W.conv2d()
        dims = dict(n=1, oc=64, ic=32, oh=56, ow=56, kh=3, kw=3)
        sps = _SP_MENU["conv2d"]
        batch = build_batch(wl, [dims], sps, HW)
        assert batch.n_candidates == len(
            enumerate_candidates(wl, dims, sps, HW))
        assert batch.loop_dim.shape == batch.loop_size.shape
        assert (batch.n_fus == HW.n_fus).all()
        # padding slots are inert (size 1, dim -1)
        pad = batch.loop_dim < 0
        assert (batch.loop_size[pad] == 1).all()

    def test_tile_search_defaults_on(self):
        """Tile splits are part of the default candidate space; the opt-out
        narrower space is a strict subset with base candidates first."""
        wl = W.gemm()
        dims = dict(i=512, j=512, k=512)
        base = enumerate_candidates(wl, dims, GEMM_SP, HW, tile_search=False)
        tiled = enumerate_candidates(wl, dims, GEMM_SP, HW)
        assert len(tiled) > len(base)
        # base candidates come first within each (spatial, facs, order) group
        assert set((c.spatial_idx, c.facs, c.temporal) for c in base) <= \
            set((c.spatial_idx, c.facs, c.temporal) for c in tiled)
        # default entry points agree with the explicit tile_search=True space
        explicit = enumerate_candidates(wl, dims, GEMM_SP, HW,
                                        tile_search=True)
        assert [(c.spatial_idx, c.facs, c.temporal) for c in tiled] == \
            [(c.spatial_idx, c.facs, c.temporal) for c in explicit]


# every kind a dataflow set carries a menu for, at dims that need padding
# (primes and odd extents that no FU factor divides)
_MEMO_WLS = {w.name: w for w in (W.gemm(), W.conv2d(), W.depthwise_conv2d(),
                                 W.attention_qk(), W.attention_pv())}
_MEMO_DIMS = {
    "gemm": [dict(i=7, j=130, k=56), dict(i=512, j=64, k=3)],
    "conv2d": [dict(n=1, oc=64, ic=3, oh=7, ow=13, kh=3, kw=3)],
    "dwconv2d": [dict(n=1, c=32, oh=14, ow=13, kh=3, kw=3)],
    "attention_qk": [dict(b=2, m=1, n=130, d=64), dict(b=4, m=16, n=17, d=7)],
    "attention_pv": [dict(b=2, m=7, n=33, d=64)],
}
_MEMO_FUS = (64, 96)
_BATCH_ARRAYS = ("loop_dim", "loop_size", "S", "n_fus", "fill", "layer_id",
                 "offsets")


def _menus(dataflow_set):
    from repro.dse.space import DATAFLOW_SETS
    return {k: list(v) for k, v in DATAFLOW_SETS[dataflow_set].items()}


def _assert_same_batch(ref, got, ctx=""):
    for f in _BATCH_ARRAYS:
        a, b = getattr(ref, f), getattr(got, f)
        assert a.dtype == b.dtype and a.shape == b.shape, (f, ctx)
        assert a.tobytes() == b.tobytes(), (f, ctx)
    assert got.n_candidates == ref.n_candidates, ctx
    assert list(got.candidates) == list(ref.candidates), ctx
    assert got.spatials == ref.spatials, ctx


def _memo_counts():
    from repro.obs import METRICS
    c = METRICS.snapshot()["counters"]
    return (c.get("mapper_batch.enum_memo_lookups", 0),
            c.get("mapper_batch.enum_memo_hits", 0),
            c.get("mapper.candidates_enumerated", 0))


class TestEnumMemo:
    """``build_batch(memo=...)`` assembles a batch from per-(layer, spatial
    choice) rows; it must equal the batch built without it, byte for
    byte, in every array, dtype and candidate."""

    @pytest.mark.parametrize("tile_search", [True, False])
    @pytest.mark.parametrize("dataflow_set",
                             ["os", "ws", "switch", "attention_fused"])
    def test_byte_identical_to_memo_less(self, dataflow_set, tile_search):
        memo: dict = {}
        for n_fus in _MEMO_FUS:
            hw = HWConfig(n_fus=n_fus)
            for kind, sps in _menus(dataflow_set).items():
                wl, dims_list = _MEMO_WLS[kind], _MEMO_DIMS[kind]
                ref = build_batch(wl, dims_list, sps, hw,
                                  tile_search=tile_search)
                for _ in range(2):  # cold, then every entry a hit
                    got = build_batch(wl, dims_list, sps, hw,
                                      tile_search=tile_search, memo=memo)
                    _assert_same_batch(ref, got, (kind, n_fus))
        assert memo

    @pytest.mark.parametrize("order", [("ws", "switch"),
                                       ("attention_fused", "os")])
    def test_warm_order_across_menus(self, order):
        """Rows built under one menu serve another that shares a spatial
        choice, relabelled with the new menu's ``spatial_idx``."""
        memo: dict = {}
        for dataflow_set in order:
            for n_fus in _MEMO_FUS:
                hw = HWConfig(n_fus=n_fus)
                for kind, sps in _menus(dataflow_set).items():
                    wl, dims_list = _MEMO_WLS[kind], _MEMO_DIMS[kind]
                    _assert_same_batch(
                        build_batch(wl, dims_list, sps, hw),
                        build_batch(wl, dims_list, sps, hw, memo=memo),
                        (dataflow_set, kind, n_fus))

    @pytest.mark.parametrize("objective", ["cycles", "energy", "edp"])
    def test_winners_match_memo_less(self, objective):
        """Each winner keeps the menu's own spatial choice and dataflow."""
        memo: dict = {}
        for dataflow_set in ("ws", "switch", "attention_fused"):
            for kind, sps in _menus(dataflow_set).items():
                wl = _MEMO_WLS[kind]
                queries = [(d, 4096.0) for d in _MEMO_DIMS[kind]]
                for hw in (HWConfig(n_fus=64, buffer_bytes=64 * 1024),
                           HWConfig(n_fus=64, buffer_bytes=512 * 1024,
                                    dram_gbps=64.0)):
                    ref = best_mappings(wl, queries, sps, hw,
                                        objective=objective)
                    got = best_mappings(wl, queries, sps, hw,
                                        objective=objective, memo=memo)
                    for ms, mb in zip(ref, got):
                        _assert_same_mapping(ms, mb, (dataflow_set, kind))
                        assert mb.spatial is sps[sps.index(ms.spatial)]

    def test_entries_read_only_and_keyed_on_what_enumeration_reads(self):
        memo: dict = {}
        wl, dims = W.gemm(), dict(i=7, j=130, k=56)
        sps = _menus("switch")["gemm"]
        build_batch(wl, [dims], sps, HWConfig(n_fus=64), memo=memo)
        # buffer and bandwidth do not enter the key, the FU count does
        build_batch(wl, [dims], sps,
                    HWConfig(n_fus=64, buffer_bytes=1 << 20,
                             dram_gbps=128.0), memo=memo)
        assert len(memo) == 2
        build_batch(wl, [dims], sps, HWConfig(n_fus=128), memo=memo)
        build_batch(wl, [dims], sps, HWConfig(n_fus=64), tile_search=False,
                    memo=memo)
        assert len(memo) == 6
        for e in memo.values():
            for f in _BATCH_ARRAYS:
                with pytest.raises(ValueError):
                    getattr(e, f)[...] = 0
        # an assembled batch owns its arrays
        got = build_batch(wl, [dims], sps, HWConfig(n_fus=64), memo=memo)
        assert all(getattr(got, f).flags.writeable for f in _BATCH_ARRAYS)

    def test_counters(self):
        """One lookup per (layer, spatial choice); the enumeration counter
        counts only the enumerations actually run."""
        memo: dict = {}
        wl = W.gemm()
        dims_list = _MEMO_DIMS["gemm"]
        sps = _menus("switch")["gemm"]
        hw = HWConfig(n_fus=64)
        l0, h0, e0 = _memo_counts()
        cold = build_batch(wl, dims_list, sps, hw, memo=memo)
        l1, h1, e1 = _memo_counts()
        assert (l1 - l0, h1 - h0) == (4, 0)
        assert e1 - e0 == cold.n_candidates
        build_batch(wl, dims_list, sps, hw, memo=memo)
        # "ws" shares the "jk" rows of both layers with "switch"
        build_batch(wl, dims_list, _menus("ws")["gemm"], hw, memo=memo)
        l2, h2, e2 = _memo_counts()
        assert (l2 - l1, h2 - h1, e2 - e1) == (6, 6, 0)
        # no memo: no lookups, every candidate enumerated
        build_batch(wl, dims_list, sps, hw)
        l3, h3, e3 = _memo_counts()
        assert (l3, h3, e3 - e2) == (l2, h2, cold.n_candidates)

    def test_mapping_caches_share_nothing(self):
        from repro.dse.cache import MappingCache

        wl, sps = W.gemm(), _menus("switch")["gemm"]
        queries = [(d, 0.0) for d in _MEMO_DIMS["gemm"]]
        a, b = MappingCache(), MappingCache()
        assert a.enum_memo is not b.enum_memo
        a.best_mapping_perfs(wl, queries, sps, HWConfig(n_fus=64))
        assert a.enum_memo and not b.enum_memo
        l0, h0, _ = _memo_counts()
        b.best_mapping_perfs(wl, queries, sps, HWConfig(n_fus=64))
        l1, h1, _ = _memo_counts()
        assert (l1 - l0, h1 - h0) == (4, 0)
        assert b.enum_memo.keys() == a.enum_memo.keys()
        assert not any(b.enum_memo[k] is a.enum_memo[k] for k in a.enum_memo)
        # memory only: nothing of it in the stats nor the saved store
        assert (a.hits, a.misses) == (0, 2)
        assert len(a.snapshot()) == 2

    def test_prefilter_then_full_evaluation_hits(self):
        """The evolve prefilter shares its parent's mapping cache, so the
        full evaluation of a child that differs in buffer alone finds its
        rows already built, and scores as it would without the memo."""
        from dataclasses import replace

        from repro.configs import get_config
        from repro.dse import DesignPoint, Evaluator, MappingCache
        from repro.dse.evaluate import lower_config

        zoo = {n: lower_config(get_config(n, reduced=True), seq=32)
               for n in ("gemma_7b", "glm4_9b")}
        pre_name = min(zoo, key=lambda n: (len(zoo[n]), n))
        p = DesignPoint(n_fus=64, buffer_kb=128, dataflow_set="switch")
        q = replace(p, buffer_kb=256)

        ev = Evaluator(zoo=zoo, cache=MappingCache())
        pre_ev = Evaluator(zoo={pre_name: zoo[pre_name]}, cache=ev.cache,
                           objective=ev.objective, engine=ev.engine)
        pre_ev.evaluate(p)
        built = len(ev.cache.enum_memo)
        l0, h0, _ = _memo_counts()
        got = ev.evaluate(q)
        l1, h1, _ = _memo_counts()
        assert built and h1 - h0 >= built and l1 - l0 > h1 - h0

        cold = MappingCache()
        cold.enum_memo = None
        ref = Evaluator(zoo=zoo, cache=cold).evaluate(q)
        assert got.as_dict() == ref.as_dict()


class TestKernelsAgainstScalar:
    def test_layer_perf_is_batch_of_one(self):
        """The scalar API wraps the batched kernels: a hand-built dataflow
        must score identically through both entry points."""
        from repro.core.dataflow import build_dataflow
        from repro.core.mapper_batch import evaluate_batch

        wl = W.gemm()
        dims = dict(i=64, j=2048, k=64)
        m = best_mapping(wl, dims, GEMM_SP, HW)
        p = layer_perf(wl, m.dataflow, HW, true_sizes=dims)
        assert p.cycles == m.perf.cycles
        assert p.energy_pj == m.perf.energy_pj

        df = build_dataflow(wl, spatial=[("i", 16), ("j", 16)],
                            temporal=[("k", 64), ("i", 4), ("j", 128)],
                            c=(1, 1), name="hand")
        p2 = layer_perf(wl, df, HW, true_sizes=dims)
        assert p2.cycles > 0 and p2.energy_pj > 0


class TestParallelSweepDeterminism:
    @pytest.mark.parametrize("strategy", ["exhaustive", "evolutionary"])
    def test_frontier_independent_of_worker_count(self, strategy):
        from repro.configs import get_config
        from repro.dse import Evaluator, MappingCache, SPACES, run_search
        from repro.dse.evaluate import lower_config

        zoo = {n: lower_config(get_config(n, reduced=True), seq=32)
               for n in ("gemma_7b",)}
        results = {}
        for workers in (1, 2):
            ev = Evaluator(zoo=zoo, cache=MappingCache())
            kw = (dict(population=4, generations=2)
                  if strategy == "evolutionary" else {})
            results[workers] = run_search(SPACES["tiny"], ev,
                                          strategy=strategy,
                                          workers=workers, **kw)
            # worker-computed entries merged back into the parent cache
            assert len(ev.cache) > 0
        a, b = results[1], results[2]
        assert [e.point.name for e in a.evals] == \
            [e.point.name for e in b.evals]
        assert [e.cycles for e in a.evals] == [e.cycles for e in b.evals]
        assert [e.point.name for e in a.frontier] == \
            [e.point.name for e in b.frontier]
        assert [(e.cycles, e.energy_pj, e.area_mm2) for e in a.frontier] == \
            [(e.cycles, e.energy_pj, e.area_mm2) for e in b.frontier]
