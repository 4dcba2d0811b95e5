"""Differential-testing harness: scalar vs NumPy vs JAX mapping engines.

The tolerance policy (``repro.core.perf_model_jax``) under test:

* integer-derived outputs (cycles, MACs, utilization, DRAM bytes, SRAM
  reads, PPU cycles, the memory-bound flag) are **bit-identical** across
  all three engines;
* raw JAX ``energy_pj`` may carry FMA-contraction noise bounded by
  :data:`~repro.core.perf_model_jax.ENERGY_RTOL`;
* everything *reported* (``LayerPerf``, mapping-cache entries, Pareto
  frontiers) is byte-identical, because selection runs on the host and the
  winners are re-scored through the NumPy kernel.

Coverage must not depend on hypothesis being installed: the seeded-random
suites below always run (>= 200 three-engine comparisons between them);
the ``@given`` property variants add fuzz on top where hypothesis exists.
A silently-drifting engine poisons every DSE objective downstream, which
is why this suite is wired into ``scripts/check.sh``.
"""

import random

import numpy as np
import pytest

from conftest import given, settings, st
from repro.core import workload as W
from repro.core.mapper import SpatialChoice, best_mapping
from repro.core.mapper_batch import best_mappings, build_batch, evaluate_batch
from repro.core.perf_model import HWConfig
from repro.core.perf_model_jax import ENERGY_RTOL, ENGINES, jax_available

needs_jax = pytest.mark.skipif(not jax_available(),
                               reason="jax runtime not importable")

_WLS = {w.name: w for w in (W.gemm(), W.conv2d(), W.depthwise_conv2d(),
                            W.attention_qk(), W.mttkrp())}
_SP_MENU = {
    "gemm": [SpatialChoice(("i", "j"), (1, 1), "ij"),
             SpatialChoice(("k", "j"), (1, 1), "jk"),
             SpatialChoice(("j",), (1,), "j1")],
    "conv2d": [SpatialChoice(("ow", "oh"), (0, 0), "ohow"),
               SpatialChoice(("ic", "oc"), (1, 1), "icoc")],
    "dwconv2d": [SpatialChoice(("ow", "oh"), (0, 0), "ohow")],
    "attention_qk": [SpatialChoice(("m", "n"), (1, 1), "mn"),
                     SpatialChoice(("d", "n"), (1, 1), "nd")],
    "mttkrp": [SpatialChoice(("i", "j"), (1, 1), "ij")],
}
# moderate menus keep the AOT compile-cache keys (workload, bucketed C/L)
# repeating across cases — the whole suite amortizes a handful of compiles
_DIM_VALUES = (1, 3, 7, 16, 56, 130, 512)
_HW_MENU = dict(n_fus=(64, 256), buffer_bytes=(64 * 1024, 512 * 1024),
                dram_gbps=(8.0, 64.0))

# integer-derived evaluate_batch outputs: exact across engines by contract
_EXACT = ("cycles", "macs", "utilization", "dram_bytes", "sram_reads",
          "ppu_cycles", "memory_bound")


def _random_case(rng):
    name = rng.choice(sorted(_WLS))
    wl = _WLS[name]
    dims = {d: rng.choice(_DIM_VALUES) for d in wl.iter_dims}
    hw = HWConfig(n_fus=rng.choice(_HW_MENU["n_fus"]),
                  buffer_bytes=rng.choice(_HW_MENU["buffer_bytes"]),
                  dram_gbps=rng.choice(_HW_MENU["dram_gbps"]))
    obj = rng.choice(["cycles", "energy", "edp"])
    dn = ({t.name: rng.choice([8, 16]) for t in wl.tensors}
          if rng.random() < 0.5 else None)
    ppu = rng.choice([0.0, 4096.0])
    return wl, dims, _SP_MENU[name], hw, dn, ppu, obj


def _assert_same_mapping(ma, mb, ctx=""):
    """Byte-identical reported mapping: the headline invariant."""
    for f in ("cycles", "energy_pj", "macs", "utilization", "dram_bytes",
              "sram_reads", "ppu_cycles"):
        assert getattr(ma.perf, f) == getattr(mb.perf, f), (f, ctx)
    assert ma.perf.bound == mb.perf.bound, ctx
    assert ma.spatial.name == mb.spatial.name, ctx
    # dataflow construction is memoized: identical decisions share objects
    assert ma.dataflow is mb.dataflow, ctx


def _assert_kernel_parity(ra, rb, ctx=""):
    """evaluate_batch result parity under the documented tolerance policy."""
    for f in _EXACT:
        assert np.array_equal(np.asarray(ra[f]), np.asarray(rb[f])), (f, ctx)
    np.testing.assert_allclose(ra["energy_pj"], rb["energy_pj"],
                               rtol=ENERGY_RTOL, err_msg=str(ctx))


@needs_jax
class TestKernelParity:
    """evaluate_batch(engine="numpy") vs engine="jax" over whole candidate
    batches — the raw score arrays, before any selection."""

    @pytest.mark.parametrize("seed", range(4))
    def test_randomized_batches(self, seed):
        rng = random.Random(100 + seed)
        for _ in range(30):
            wl, dims, sps, hw, dn, ppu, _ = _random_case(rng)
            # several layers per batch: exercises layer slicing + padding
            n_layers = rng.choice([1, 2, 3])
            dims_list = [dims] + [
                {d: rng.choice(_DIM_VALUES) for d in wl.iter_dims}
                for _ in range(n_layers - 1)]
            ppu_list = [ppu] * n_layers
            batch = build_batch(wl, dims_list, sps, hw)
            ra = evaluate_batch(batch, hw, dims_list, ppu_list,
                                data_nodes_per_tensor=dn, engine="numpy")
            rb = evaluate_batch(batch, hw, dims_list, ppu_list,
                                data_nodes_per_tensor=dn, engine="jax")
            _assert_kernel_parity(ra, rb, (wl.name, dims_list))

    @settings(max_examples=60, deadline=None)
    @given(st.data())
    def test_property_batches(self, data):
        wl = _WLS[data.draw(st.sampled_from(sorted(_WLS)))]
        dims = {d: data.draw(st.sampled_from(_DIM_VALUES))
                for d in wl.iter_dims}
        hw = HWConfig(
            n_fus=data.draw(st.sampled_from(_HW_MENU["n_fus"])),
            buffer_bytes=data.draw(
                st.sampled_from(_HW_MENU["buffer_bytes"])),
            dram_gbps=data.draw(st.sampled_from(_HW_MENU["dram_gbps"])))
        ppu = data.draw(st.sampled_from([0.0, 4096.0]))
        batch = build_batch(wl, [dims], _SP_MENU[wl.name], hw)
        ra = evaluate_batch(batch, hw, [dims], [ppu], engine="numpy")
        rb = evaluate_batch(batch, hw, [dims], [ppu], engine="jax")
        _assert_kernel_parity(ra, rb, (wl.name, dims))


@needs_jax
class TestLargeTripParity:
    """Full-width layer shapes, where loop trips multiply up to the
    ~2.8e11-cycle range of the model zoo (``BENCH_models.json``): the int64
    footprint contraction (a broadcast multiply and sum, not a ``dot``) and
    every product after it must still match NumPy bit for bit, through the
    single-design and the design-axis kernel alike."""

    _DIMS = {"i": (4096, 8192, 32768), "j": (4096, 14336, 28672),
             "k": (3584, 4096, 14336)}

    @pytest.mark.parametrize("seed", range(3))
    def test_randomized_large_batches(self, seed):
        from repro.core.perf_model import perf_kernel
        from repro.core.perf_model_jax import perf_kernel_jax_design

        rng = random.Random(300 + seed)
        wl, sps = _WLS["gemm"], _SP_MENU["gemm"]
        dims_list = [{d: rng.choice(v) for d, v in self._DIMS.items()}
                     for _ in range(3)]
        ppu_list = [rng.choice([0.0, 4096.0]) for _ in dims_list]
        hw_list = [HWConfig(n_fus=64,
                            buffer_bytes=rng.choice(_HW_MENU["buffer_bytes"]),
                            dram_gbps=rng.choice(_HW_MENU["dram_gbps"]))
                   for _ in range(3)]
        batch = build_batch(wl, dims_list, sps, hw_list[0])
        ra = evaluate_batch(batch, hw_list[0], dims_list, ppu_list,
                            engine="numpy")
        assert ra["cycles"].max() > 1e11  # the large-trip regime
        rb = evaluate_batch(batch, hw_list[0], dims_list, ppu_list,
                            engine="jax")
        _assert_kernel_parity(ra, rb, dims_list)

        true = np.array([[dims[d] for d in wl.iter_dims]
                         for dims in dims_list])[batch.layer_id]
        ppu = np.asarray(ppu_list)[batch.layer_id]
        dn = np.full((batch.n_candidates, len(wl.tensors)), 64)
        rd = perf_kernel_jax_design(
            wl, hw_list, batch.loop_dim, batch.loop_size, batch.S,
            n_fus=batch.n_fus, fill=batch.fill, true_sizes=true,
            data_nodes=dn[:len(hw_list)], ppu_elements=ppu)
        for di, hw in enumerate(hw_list):
            rn = perf_kernel(wl, hw, batch.loop_dim, batch.loop_size,
                             batch.S, n_fus=batch.n_fus, fill=batch.fill,
                             true_sizes=true, data_nodes=dn,
                             ppu_elements=ppu)
            _assert_kernel_parity(rn, {k: v[di] for k, v in rd.items()},
                                  (di, dims_list))


@needs_jax
class TestThreeEngineMappingParity:
    """scalar vs numpy vs jax through the full mapping search: the winner
    and its reported LayerPerf must be byte-identical (exact — no
    tolerance — because jax winners are re-scored through NumPy)."""

    @pytest.mark.parametrize("seed", range(4))
    def test_randomized_three_way(self, seed):
        rng = random.Random(seed)
        for _ in range(25):
            wl, dims, sps, hw, dn, ppu, obj = _random_case(rng)
            ctx = (wl.name, dims, obj)
            ms, mn, mj = (best_mapping(
                wl, dims, sps, hw, data_nodes_per_tensor=dn,
                ppu_elements=ppu, objective=obj, engine=e)
                for e in ENGINES)
            _assert_same_mapping(ms, mn, ("scalar/numpy",) + ctx)
            _assert_same_mapping(mn, mj, ("numpy/jax",) + ctx)

    @pytest.mark.parametrize("seed", range(2))
    def test_randomized_batched_queries(self, seed):
        """Multi-layer best_mappings: numpy vs jax over shared batches."""
        rng = random.Random(50 + seed)
        for _ in range(15):
            wl, dims, sps, hw, dn, ppu, obj = _random_case(rng)
            queries = [(dims, ppu)] + [
                ({d: rng.choice(_DIM_VALUES) for d in wl.iter_dims}, ppu)
                for _ in range(2)]
            a = best_mappings(wl, queries, sps, hw,
                              data_nodes_per_tensor=dn, objective=obj,
                              engine="numpy")
            b = best_mappings(wl, queries, sps, hw,
                              data_nodes_per_tensor=dn, objective=obj,
                              engine="jax")
            for qi, (ma, mb) in enumerate(zip(a, b)):
                _assert_same_mapping(ma, mb, (wl.name, qi, obj))

    @settings(max_examples=40, deadline=None)
    @given(st.data())
    def test_property_three_way(self, data):
        wl = _WLS[data.draw(st.sampled_from(sorted(_WLS)))]
        dims = {d: data.draw(st.sampled_from(_DIM_VALUES))
                for d in wl.iter_dims}
        hw = HWConfig(n_fus=data.draw(st.sampled_from(_HW_MENU["n_fus"])))
        obj = data.draw(st.sampled_from(["cycles", "energy", "edp"]))
        sps = _SP_MENU[wl.name]
        ms, mn, mj = (best_mapping(wl, dims, sps, hw, objective=obj,
                                   engine=e) for e in ENGINES)
        _assert_same_mapping(ms, mn, (wl.name, dims, obj))
        _assert_same_mapping(mn, mj, (wl.name, dims, obj))


@needs_jax
class TestDesignAxisParity:
    """best_mappings_design: one stacked (D, C) dispatch vs D independent
    single-design searches.  The design axis is a pure vmap over runtime HW
    parameters, so every per-design winner (and its NumPy-rescored
    LayerPerf) must be byte-identical to the per-design loop — per
    objective, cold or warm compile cache."""

    def _case(self, rng, n_designs=4):
        name = rng.choice(sorted(_WLS))
        wl = _WLS[name]
        queries = [({d: rng.choice(_DIM_VALUES) for d in wl.iter_dims},
                    rng.choice([0.0, 4096.0]))
                   for _ in range(rng.choice([1, 2, 3]))]
        n_fus = rng.choice(_HW_MENU["n_fus"])
        hw_list = [HWConfig(
            n_fus=n_fus,
            buffer_bytes=rng.choice(_HW_MENU["buffer_bytes"]),
            dram_gbps=rng.choice(_HW_MENU["dram_gbps"]))
            for _ in range(n_designs)]
        dn = ({t.name: rng.choice([8, 16]) for t in wl.tensors}
              if rng.random() < 0.5 else None)
        return wl, queries, _SP_MENU[name], hw_list, dn

    @pytest.mark.parametrize("objective", ["cycles", "energy", "edp"])
    def test_stacked_vs_independent(self, objective):
        from repro.core.mapper_batch import best_mappings_design
        rng = random.Random({"cycles": 7, "energy": 8, "edp": 9}[objective])
        for _ in range(6):
            wl, queries, sps, hw_list, dn = self._case(rng)
            stacked = best_mappings_design(
                wl, queries, sps, hw_list,
                data_nodes_per_tensor_list=[dn] * len(hw_list),
                objective=objective)
            assert len(stacked) == len(hw_list)
            for di, hw in enumerate(hw_list):
                for eng in ("numpy", "jax"):
                    solo = best_mappings(wl, queries, sps, hw,
                                         data_nodes_per_tensor=dn,
                                         objective=objective, engine=eng)
                    for qi, (ma, mb) in enumerate(zip(stacked[di], solo)):
                        _assert_same_mapping(
                            ma, mb, (wl.name, objective, di, qi, eng))

    def test_cold_and_warm_compile_cache_identical(self):
        from repro.core.mapper_batch import best_mappings_design
        from repro.core.perf_model_jax import clear_compile_cache
        from repro.obs import METRICS

        wl, sps = _WLS["gemm"], _SP_MENU["gemm"]
        queries = [({"i": 56, "j": 130, "k": 512}, 0.0),
                   ({"i": 16, "j": 512, "k": 130}, 4096.0)]
        hw_list = [HWConfig(n_fus=64, buffer_bytes=b, dram_gbps=g)
                   for b in (64 * 1024, 512 * 1024) for g in (8.0, 64.0)]

        def dump(rows):
            return [[(m.perf.as_dict(), m.spatial.name, m.dataflow.name)
                     for m in row] for row in rows]

        def compiles():
            return METRICS.snapshot()["counters"].get(
                "mapper_batch.jax_compiles", 0)

        clear_compile_cache()
        c0 = compiles()
        cold = dump(best_mappings_design(wl, queries, sps, hw_list))
        c1 = compiles()
        warm = dump(best_mappings_design(wl, queries, sps, hw_list))
        c2 = compiles()
        assert cold == warm
        assert c1 - c0 >= 1, "cold dispatch must have compiled"
        assert c2 == c1, "warm dispatch must not recompile"

    def test_design_group_contract(self):
        """One design group = one FU count (candidate enumeration depends
        on the design only through n_fus); mixed groups are a caller bug."""
        from repro.core.mapper_batch import best_mappings_design
        wl, sps = _WLS["gemm"], _SP_MENU["gemm"]
        q = [({"i": 16, "j": 16, "k": 16}, 0.0)]
        with pytest.raises(AssertionError):
            best_mappings_design(wl, q, sps, [HWConfig(n_fus=64),
                                              HWConfig(n_fus=256)])
        with pytest.raises(AssertionError):
            best_mappings_design(wl, q, sps, [])


@needs_jax
class TestOneScoringPath:
    """Per-design scoring is the one-design case of the design-batched
    program: the same outputs, byte for byte, and only design programs are
    ever compiled."""

    _CASES = {"gemm": [({"i": 56, "j": 16, "k": 130}, 0.0),
                       ({"i": 16, "j": 512, "k": 7}, 4096.0)],
              "attention_qk": [({"b": 2, "m": 16, "n": 56, "d": 130},
                                0.0)],
              "conv2d": [({"n": 1, "oc": 16, "ic": 3, "oh": 7, "ow": 56,
                           "kh": 3, "kw": 3}, 4096.0)]}

    @pytest.mark.parametrize("name", ["gemm", "attention_qk", "conv2d"],
                             ids=["gemm", "attn_qk", "conv"])
    def test_per_design_is_row_zero_of_the_design_kernel(self, name):
        from repro.core.mapper_batch import best_mappings_design
        from repro.core.perf_model_jax import (perf_kernel_jax,
                                               perf_kernel_jax_design)
        wl, sps, queries = _WLS[name], _SP_MENU[name], self._CASES[name]
        dn = {t.name: 16 for t in wl.tensors}
        for kb in (64, 128, 512):
            hw = HWConfig(n_fus=64, buffer_bytes=kb * 1024)
            batch = build_batch(wl, [q[0] for q in queries], sps, hw)
            true = np.array([[q[0][d] for d in wl.iter_dims]
                             for q in queries])[batch.layer_id]
            ppu = np.array([q[1] for q in queries])[batch.layer_id]
            rows = (batch.loop_dim, batch.loop_size, batch.S, batch.n_fus,
                    batch.fill, true)
            dn_rows = np.full((batch.n_candidates, len(wl.tensors)), 16)
            one = perf_kernel_jax(wl, hw, *rows, dn_rows, ppu)
            both = perf_kernel_jax_design(wl, [hw], *rows, dn_rows[:1], ppu)
            assert list(one) == list(both) and len(one) == 8
            for k in one:
                assert one[k].dtype == both[k].dtype, (name, kb, k)
                assert one[k].shape == (batch.n_candidates,), (name, kb, k)
                assert one[k].tobytes() == both[k][0].tobytes(), \
                    (name, kb, k)
            solo = best_mappings(wl, queries, sps, hw,
                                 data_nodes_per_tensor=dn, engine="jax")
            [stacked] = best_mappings_design(
                wl, queries, sps, [hw], data_nodes_per_tensor_list=[dn])
            assert [m.perf.as_dict() for m in solo] == \
                [m.perf.as_dict() for m in stacked]

    def test_per_design_solve_compiles_only_design_programs(self):
        from repro.core import perf_model_jax as pmj
        from repro.obs import METRICS

        wl, sps = _WLS["gemm"], _SP_MENU["gemm"]
        queries = self._CASES["gemm"]
        hw = HWConfig(n_fus=64)

        def compiles():
            return METRICS.snapshot()["counters"].get(
                "mapper_batch.jax_compiles", 0)

        pmj.clear_compile_cache()
        c0 = compiles()
        cold = best_mappings(wl, queries, sps, hw, engine="jax")
        c1 = compiles()
        assert c1 - c0 == len(pmj._COMPILED) == 1
        assert all(key[1] == "design" and key[2] == 1
                   for key in pmj._COMPILED)
        warm = best_mappings(wl, queries, sps, hw, engine="jax")
        assert compiles() == c1, "a repeated solve must not compile"
        for ma, mb in zip(cold, warm):
            _assert_same_mapping(ma, mb, "warm")


class TestCacheCrossEngine:
    """dse/cache.py engine invariance: keys carry no engine field, so a
    cache populated by one engine must serve every other engine."""

    def _queries(self):
        wl = _WLS["gemm"]
        qs = [({"i": i, "j": j, "k": 512}, 0.0)
              for i in (56, 130) for j in (16, 512)]
        return wl, qs, _SP_MENU["gemm"], HWConfig(n_fus=256)

    def test_mapping_key_has_no_engine_field(self):
        import inspect

        from repro.dse.cache import mapping_key
        assert "engine" not in inspect.signature(mapping_key).parameters

    @pytest.mark.parametrize("first,second",
                             [("numpy", "scalar"), ("scalar", "numpy")] +
                             ([("jax", "numpy"), ("numpy", "jax")]
                              if jax_available() else []))
    def test_cache_populated_by_one_engine_hits_the_other(
            self, first, second, tmp_path):
        from repro.dse.cache import MappingCache
        wl, qs, sps, hw = self._queries()
        path = tmp_path / "cache.json"

        c1 = MappingCache(path)
        p1 = c1.best_mapping_perfs(wl, qs, sps, hw, engine=first)
        assert c1.misses == len(qs)
        c1.save()

        c2 = MappingCache(path)
        p2 = c2.best_mapping_perfs(wl, qs, sps, hw, engine=second)
        assert c2.misses == 0 and c2.hits == len(qs), \
            f"{second} run must fully hit the {first}-populated cache"
        assert [p.as_dict() for p in p1] == [p.as_dict() for p in p2]

    @needs_jax
    def test_cross_engine_frontier_identical(self, tmp_path):
        """A tiny sweep under each engine — and under each engine warmed by
        the *other* engine's cache — must produce one identical frontier."""
        import json

        from repro.dse import Evaluator, MappingCache, load_zoo
        from repro.dse.space import SPACES

        zoo = load_zoo(["gemma_7b"], seq=64, reduced=True)
        points = list(SPACES["tiny"].enumerate())

        def frontier(engine, path):
            cache = MappingCache(path)
            ev = Evaluator(zoo=zoo, cache=cache, engine=engine)
            evals = [ev.evaluate(p).as_dict() for p in points]
            cache.save()
            return json.dumps(evals, sort_keys=True)

        f_np = frontier("numpy", tmp_path / "np.json")
        f_jx = frontier("jax", tmp_path / "jx.json")
        assert f_np == f_jx
        # engine swap over the other engine's warm cache: still identical
        assert frontier("numpy", tmp_path / "jx.json") == f_np
        assert frontier("jax", tmp_path / "np.json") == f_np


class TestEngineValidation:
    def test_unknown_engine_rejected_everywhere(self):
        from repro.dse import Evaluator
        wl, hw = _WLS["gemm"], HWConfig(n_fus=64)
        dims = {"i": 16, "j": 16, "k": 16}
        batch = build_batch(wl, [dims], _SP_MENU["gemm"], hw)
        for engine in ("fortran", "batch"):
            with pytest.raises(ValueError, match="engine"):
                best_mapping(wl, dims, _SP_MENU["gemm"], hw, engine=engine)
            with pytest.raises(ValueError, match="engine"):
                evaluate_batch(batch, hw, [dims], [0.0], engine=engine)
            with pytest.raises(ValueError, match="engine"):
                Evaluator(zoo={}, engine=engine)

    def test_scalar_engine_through_cache_front_door(self):
        from repro.dse.cache import MappingCache
        wl, hw = _WLS["gemm"], HWConfig(n_fus=64)
        qs = [({"i": 56, "j": 16, "k": 130}, 0.0),
              ({"i": 16, "j": 16, "k": 512}, 128.0)]
        p_sc = MappingCache().best_mapping_perfs(wl, qs, _SP_MENU["gemm"],
                                                 hw, engine="scalar")
        p_np = MappingCache().best_mapping_perfs(wl, qs, _SP_MENU["gemm"],
                                                 hw, engine="numpy")
        assert [p.as_dict() for p in p_sc] == [p.as_dict() for p in p_np]

    def test_jax_unavailable_raises_cleanly(self, monkeypatch):
        """Without a jax runtime, engine='jax' must fail with a clear
        RuntimeError (not an ImportError mid-kernel)."""
        import repro.core.perf_model_jax as pmj
        monkeypatch.setattr(pmj, "_jax", False)
        assert not pmj.jax_available()
        with pytest.raises(RuntimeError, match="jax"):
            pmj._require_jax()


class TestServingParity:
    """The serving simulator inherits engine invariance: a replayed trace's
    schedule is a pure function of the mapping-search winners, which are
    byte-identical across engines (extends tests/test_serve_sim.py)."""

    @needs_jax
    def test_serving_summary_engine_invariant(self):
        from repro.dse.space import DesignPoint
        from repro.serve.sim import SLO, DecodeCostModel, ServingSpec, simulate
        from repro.serve.trace import TraceSpec, generate_trace

        pt = DesignPoint(n_fus=128, buffer_kb=128, dram_gbps=64,
                         dataflow_set="attention_fused")
        ts = TraceSpec(seed=1, requests=6, rate_rps=1.0,
                       models=(("gemma_7b", 1.0),), prompt_mean=8,
                       prompt_max=32, output_mean=4, output_max=8)
        spec = ServingSpec(trace=ts, slo=SLO(), reduced=True)
        trace = generate_trace(ts)
        results = {}
        for engine in ("numpy", "jax"):
            cm = DecodeCostModel(pt, engine=engine, reduced=True)
            results[engine] = simulate(pt, trace, spec=spec, cost_model=cm,
                                       record_steps=True)
        assert results["numpy"].summary() == results["jax"].summary()
        assert results["numpy"].steps == results["jax"].steps
        assert results["numpy"].requests == results["jax"].requests
