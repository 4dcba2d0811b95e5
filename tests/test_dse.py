"""DSE subsystem tests: Pareto correctness, persistent-cache round-trip,
mapper determinism, space pruning/mutation, and an end-to-end tiny sweep."""

import json
import random

import pytest

from repro.core import workload as W
from repro.core.fusion import estimate_data_nodes, score_fused_design
from repro.core.mapper import SpatialChoice, best_mapping, factor_pairs
from repro.core.perf_model import HWConfig
from repro.dse import (MappingCache, SPACES, DesignPoint, DesignSpace,
                       Evaluator, dominates, pareto_frontier, run_search)
from repro.dse.cache import mapping_key
from repro.dse.evaluate import DesignEval, lower_config
from repro.dse.report import write_bench_json
from repro.configs import get_config

GEMM_SP = [SpatialChoice(("k", "j"), (1, 1), "jk"),
           SpatialChoice(("i", "j"), (1, 1), "ij")]
HW = HWConfig(n_fus=64, buffer_bytes=128 * 1024)


def _eval(name, cycles, energy, area):
    return DesignEval(point=DesignPoint(n_fus=64, buffer_kb=128),
                      cycles=cycles, energy_pj=energy, area_mm2=area,
                      power_mw=0.0, macs=1.0,
                      per_config={"_label": {"name": name}})


class TestPareto:
    def test_dominates(self):
        assert dominates((1, 1, 1), (2, 2, 2))
        assert dominates((1, 2, 2), (2, 2, 2))
        assert not dominates((2, 2, 2), (2, 2, 2))      # equal ≠ dominating
        assert not dominates((1, 3, 1), (2, 2, 2))      # trade-off

    def test_hand_built_scorecard(self):
        evals = [
            _eval("fast_big", cycles=10, energy=100, area=4.0),
            _eval("slow_small", cycles=100, energy=100, area=1.0),
            _eval("balanced", cycles=50, energy=50, area=2.0),
            _eval("dominated", cycles=60, energy=60, area=2.5),   # by balanced
            _eval("strictly_worse", cycles=200, energy=200, area=5.0),
        ]
        front = pareto_frontier(evals)
        names = {e.per_config["_label"]["name"] for e in front}
        assert names == {"fast_big", "slow_small", "balanced"}
        # sorted by first objective (cycles)
        assert [e.cycles for e in front] == sorted(e.cycles for e in front)

    def test_duplicate_vectors_kept_once(self):
        evals = [_eval("a", 10, 10, 1.0), _eval("b", 10, 10, 1.0)]
        front = pareto_frontier(evals)
        assert len(front) == 1

    def test_single_point_is_frontier(self):
        evals = [_eval("only", 10, 10, 1.0)]
        assert pareto_frontier(evals) == evals


class TestMappingCache:
    def _query(self):
        wl = W.gemm()
        dims = dict(i=64, j=128, k=64)
        dn = estimate_data_nodes(HW.n_fus, ["Y", "X", "W"])
        return wl, dims, dn

    def test_roundtrip_through_disk(self, tmp_path):
        path = tmp_path / "cache.json"
        wl, dims, dn = self._query()

        c1 = MappingCache(path)
        p1 = c1.best_mapping_perf(wl, dims, GEMM_SP, HW,
                                  data_nodes_per_tensor=dn)
        assert c1.misses == 1 and c1.hits == 0
        p1b = c1.best_mapping_perf(wl, dims, GEMM_SP, HW,
                                   data_nodes_per_tensor=dn)
        assert c1.hits == 1
        assert p1b.cycles == p1.cycles
        c1.save()
        assert path.exists()

        # a fresh process-equivalent: load from disk, no mapper call needed
        c2 = MappingCache(path)
        assert len(c2) == 1
        p2 = c2.best_mapping_perf(wl, dims, GEMM_SP, HW,
                                  data_nodes_per_tensor=dn)
        assert c2.hits == 1 and c2.misses == 0
        assert p2.cycles == p1.cycles
        assert p2.energy_pj == p1.energy_pj
        assert c2.lookup_spatial(wl, dims, GEMM_SP, HW,
                                 data_nodes_per_tensor=dn) in ("ij", "jk")

    def test_key_sensitivity(self):
        wl, dims, dn = self._query()
        k1 = mapping_key(wl, dims, GEMM_SP, HW, dn, 0.0, "cycles")
        assert k1 == mapping_key(wl, dict(dims), GEMM_SP, HW, dict(dn),
                                 0.0, "cycles")
        hw2 = HWConfig(n_fus=256, buffer_bytes=HW.buffer_bytes)
        assert k1 != mapping_key(wl, dims, GEMM_SP, hw2, dn, 0.0, "cycles")
        assert k1 != mapping_key(wl, {**dims, "i": 65}, GEMM_SP, HW, dn,
                                 0.0, "cycles")
        assert k1 != mapping_key(wl, dims, GEMM_SP, HW, dn, 0.0, "energy")
        assert k1 != mapping_key(wl, dims, GEMM_SP[:1], HW, dn, 0.0, "cycles")

    def test_corrupt_cache_is_cold_not_fatal(self, tmp_path):
        path = tmp_path / "cache.json"
        path.write_text("{not json")
        c = MappingCache(path)
        assert len(c) == 0


class TestMapperDeterminism:
    def test_best_mapping_repeatable(self):
        wl = W.gemm()
        dims = dict(i=96, j=512, k=256)
        results = [best_mapping(wl, dims, GEMM_SP, HW) for _ in range(3)]
        assert len({m.perf.cycles for m in results}) == 1
        assert len({m.perf.energy_pj for m in results}) == 1
        assert len({m.spatial.name for m in results}) == 1
        assert len({m.dataflow.name for m in results}) == 1

    def test_factor_pairs_memoized_and_correct(self):
        assert factor_pairs(256) is factor_pairs(256)  # lru_cache hit
        assert (16, 16) in factor_pairs(256)
        assert all(a * b == 256 for a, b in factor_pairs(256))


class TestDesignSpace:
    def test_small_space_meets_acceptance_floor(self):
        pts = list(SPACES["small"].enumerate())
        assert len(pts) >= 20
        assert len(set(p.name for p in pts)) == len(pts)

    def test_pruning(self):
        space = DesignSpace(name="t", n_fus=(1024,), buffer_kb=(16,),
                            min_buffer_bytes_per_fu=64)
        assert list(space.enumerate()) == []  # 16 KB / 1024 FUs = 16 B/FU
        space2 = DesignSpace(name="t2", n_fus=(96,))  # non-power-of-two
        assert list(space2.enumerate()) == []

    def test_mutate_stays_valid(self):
        space = SPACES["small"]
        rng = random.Random(0)
        p = space.sample(rng)
        for _ in range(32):
            q = space.mutate(p, rng)
            assert space.is_valid(q)
            p = q


class TestEvaluator:
    @pytest.fixture(scope="class")
    def tiny_result(self, tmp_path_factory):
        cfg_names = ["gemma_7b", "glm4_9b"]
        zoo = {n: lower_config(get_config(n, reduced=True), seq=64)
               for n in cfg_names}
        cache = MappingCache(tmp_path_factory.mktemp("dse") / "c.json")
        ev = Evaluator(zoo=zoo, cache=cache)
        return run_search(SPACES["tiny"], ev, strategy="exhaustive"), ev

    def test_sweep_shape(self, tiny_result):
        result, _ = tiny_result
        assert result.n_designs == len(list(SPACES["tiny"].enumerate()))
        assert 1 <= len(result.frontier) <= result.n_designs
        for e in result.evals:
            assert e.cycles > 0 and e.energy_pj > 0 and e.area_mm2 > 0
            assert set(e.per_config) == {"gemma_7b", "glm4_9b"}

    def test_frontier_is_nondominated(self, tiny_result):
        result, _ = tiny_result
        for a in result.frontier:
            for b in result.evals:
                assert not dominates(b.objectives(), a.objectives())

    def test_cached_rerun_identical_and_mapper_free(self, tiny_result):
        result, ev = tiny_result
        before = ev.cache.misses
        again = run_search(SPACES["tiny"], ev, strategy="exhaustive")
        assert ev.cache.misses == before  # no new mapper calls
        assert [e.cycles for e in again.evals] == \
            [e.cycles for e in result.evals]

    def test_bench_json(self, tiny_result, tmp_path):
        result, _ = tiny_result
        out = tmp_path / "BENCH_dse.json"
        payload = write_bench_json(out, result)
        loaded = json.loads(out.read_text())
        assert loaded["n_designs"] == result.n_designs
        assert loaded["best"]["cycles"] == result.best("cycles").point.name
        assert payload["frontier"]


class TestLowering:
    def test_all_archs_lower(self):
        from repro.configs import ARCH_IDS
        from repro.frontend import unfuse_attention_rows
        for name in ARCH_IDS:
            rows = lower_config(get_config(name, reduced=True), seq=32)
            assert rows, name
            for kind, dims, rep, nt in rows:
                assert kind in ("gemm", "conv", "dwconv",
                                "attn_qk", "attn_pv")
                assert rep >= 1
                assert all(v >= 1 for v in dims.values()), (name, dims)
            # the plain-GEMM fallback of the fused attention pair stays
            # available for non-fused designs and carries only classic kinds
            for kind, *_ in unfuse_attention_rows(rows):
                assert kind in ("gemm", "conv", "dwconv")

    def test_moe_scales_active_compute(self):
        import math
        cfg = get_config("deepseek_moe_16b", reduced=True)
        rows = lower_config(cfg, seq=32)
        macs = sum(rep * math.prod(dims.values())
                   for _, dims, rep, _ in rows)
        dense = get_config("glm4_9b", reduced=True)
        assert macs > 0 and dense is not None


class TestScoreFusedDesign:
    def test_matches_direct_mapper(self):
        wl = W.gemm()
        layers = [(wl, dict(i=64, j=256, k=128), 3, 16.0)]
        dn = estimate_data_nodes(HW.n_fus, [t.name for t in wl.tensors])
        s = score_fused_design(layers, GEMM_SP, HW,
                               data_nodes_per_tensor=dn)
        m = best_mapping(wl, dict(i=64, j=256, k=128), GEMM_SP, HW,
                         data_nodes_per_tensor=dn, ppu_elements=16.0)
        assert s.cycles == pytest.approx(3 * m.perf.cycles)
        assert s.energy_pj == pytest.approx(3 * m.perf.energy_pj)


# ---------------------------------------------------------------------------
# guided evolve search + design-axis batched sweep
# ---------------------------------------------------------------------------

from repro.core.perf_model_jax import jax_available  # noqa: E402
from repro.dse import (RunLedger, Supervisor, SupervisorConfig,  # noqa: E402
                       batch_sweep, evolve_search, load_zoo, plan_tiles)

needs_jax = pytest.mark.skipif(not jax_available(),
                               reason="jax runtime not importable")

_MINI_ZOO = None


def _mini_evaluator(cache_path, engine="numpy"):
    global _MINI_ZOO
    if _MINI_ZOO is None:
        _MINI_ZOO = load_zoo(["gemma_7b"], seq=64, reduced=True)
    return Evaluator(zoo=_MINI_ZOO, cache=MappingCache(cache_path),
                     engine=engine)


def _dump(evals):
    return json.dumps([e.as_dict() for e in evals], sort_keys=True)


class TestEvolveSearch:
    def test_deterministic_per_seed(self, tmp_path):
        a = evolve_search(SPACES["small"], _mini_evaluator(tmp_path / "a"),
                          budget=18, seed=5)
        b = evolve_search(SPACES["small"], _mini_evaluator(tmp_path / "b"),
                          budget=18, seed=5)
        assert a.extra["visited"] == b.extra["visited"]
        assert _dump(a.evals) == _dump(b.evals)
        assert [e.point.name for e in a.frontier] == \
            [e.point.name for e in b.frontier]
        # a different seed walks a different trajectory
        c = evolve_search(SPACES["small"], _mini_evaluator(tmp_path / "c"),
                          budget=18, seed=6)
        assert c.extra["visited"] != a.extra["visited"]

    def test_budget_and_extra(self, tmp_path):
        r = evolve_search(SPACES["small"], _mini_evaluator(tmp_path / "c2"),
                          budget=12, seed=0)
        assert r.strategy == "evolve"
        assert r.extra["spent"] <= 12
        assert r.n_designs == len(r.extra["visited"]) <= 12
        assert r.extra["seed"] == 0 and r.extra["budget"] == 12
        assert r.extra["prefilter_zoo"] == "gemma_7b"

    def test_skips_failure_stub_parents(self, tmp_path):
        """Quarantined designs (zeroed objectives) must neither win the
        tournament nor reach the frontier."""
        ev = _mini_evaluator(tmp_path / "f")
        real = ev.evaluate
        ev.evaluate = lambda p: ((_ for _ in ()).throw(ValueError("boom"))
                                 if p.buffer_kb >= 512 else real(p))
        sup = Supervisor(ev, cfg=SupervisorConfig(max_retries=0,
                                                  backoff_base_s=0.0))
        r = evolve_search(SPACES["small"], ev, budget=16, seed=2,
                          supervisor=sup)
        failed = [e for e in r.evals if e.failed]
        assert failed, "corner seeding must have hit a poisoned design"
        assert all(not e.failed for e in r.frontier)
        assert r.extra["spent"] <= 16

    def test_resume_replays_and_counts_ledger_hits(self, tmp_path):
        ev = _mini_evaluator(tmp_path / "r1")
        led = RunLedger(tmp_path / "led.json", run_key={"k": 1})
        a = evolve_search(SPACES["small"], ev, budget=14, seed=4,
                          supervisor=Supervisor(ev, ledger=led))

        ev2 = _mini_evaluator(tmp_path / "r2")
        led2 = RunLedger(tmp_path / "led.json", run_key={"k": 1})
        assert led2.load()
        completed = led2.completed_evals()
        assert completed
        b = evolve_search(SPACES["small"], ev2, budget=14, seed=4,
                          supervisor=Supervisor(ev2, ledger=led2,
                                                completed=completed))
        # same trajectory, adopted from the ledger; hits count as spent
        assert b.extra["visited"] == a.extra["visited"]
        assert b.extra["spent"] == a.extra["spent"]
        assert _dump(b.evals) == _dump(a.evals)

    def test_run_search_routes_big_spaces_to_evolve(self, tmp_path):
        r = run_search(SPACES["huge"], _mini_evaluator(tmp_path / "h"),
                       strategy="auto", max_exhaustive=64,
                       budget=10, seed=1)
        assert r.strategy == "evolve"
        assert r.n_designs <= 10


def _memo_less(ev):
    ev.cache.enum_memo = None  # build_batch(memo=None): today's lowering
    return ev


class TestEnumMemoFrontier:
    """The mapping cache's enumeration memo changes no answer: the same
    visit order, scorecards and frontier as without it."""

    def test_evolve_search_unchanged(self, tmp_path):
        from repro.obs import METRICS

        h0 = METRICS.snapshot()["counters"].get(
            "mapper_batch.enum_memo_hits", 0)
        a = evolve_search(SPACES["small"], _mini_evaluator(tmp_path / "m"),
                          budget=18, seed=5)
        h1 = METRICS.snapshot()["counters"].get(
            "mapper_batch.enum_memo_hits", 0)
        b = evolve_search(SPACES["small"],
                          _memo_less(_mini_evaluator(tmp_path / "n")),
                          budget=18, seed=5)
        assert h1 > h0
        assert a.extra["visited"] == b.extra["visited"]
        assert _dump(a.evals) == _dump(b.evals)
        assert _dump(a.frontier) == _dump(b.frontier)

    @needs_jax
    def test_batch_sweep_unchanged(self, tmp_path):
        ev = _mini_evaluator(tmp_path / "m")
        a = batch_sweep(SPACES["tiny"], ev, d_tile=2)
        assert ev.cache.enum_memo
        b = batch_sweep(SPACES["tiny"],
                        _memo_less(_mini_evaluator(tmp_path / "n")),
                        d_tile=2)
        assert _dump(a.evals) == _dump(b.evals)
        assert _dump(a.frontier) == _dump(b.frontier)


class TestPlanTiles:
    def test_partition_and_grouping(self):
        pts = list(SPACES["small"].enumerate())
        tiles = plan_tiles(pts, d_tile=4)
        assert all(1 <= len(t) <= 4 for t in tiles)
        assert sorted(p.name for t in tiles for p in t) == \
            sorted(p.name for p in pts)
        for t in tiles:
            assert len({(p.n_fus, p.dataflow_set) for p in t}) == 1
        fus = [t[0].n_fus for t in tiles]
        assert fus == sorted(fus, reverse=True), \
            "widest candidate batches must compile first"


@needs_jax
class TestBatchSweep:
    def test_byte_identical_to_exhaustive(self, tmp_path):
        base = run_search(SPACES["tiny"], _mini_evaluator(tmp_path / "np"),
                          strategy="exhaustive")
        ev = _mini_evaluator(tmp_path / "db")
        got = batch_sweep(SPACES["tiny"], ev, workers=3, d_tile=2)
        assert got.strategy == "exhaustive"
        assert _dump(got.evals) == _dump(base.evals)
        assert [e.point.name for e in got.frontier] == \
            [e.point.name for e in base.frontier]
        # the evaluation pass runs entirely on the prefilled cache
        assert ev.cache.misses == 0 and ev.cache.hits > 0

    def test_frontier_snapshots_checkpointed(self, tmp_path):
        ev = _mini_evaluator(tmp_path / "s")
        led = RunLedger(tmp_path / "led.json", run_key={"k": 1})
        r = batch_sweep(SPACES["tiny"], ev, d_tile=2, snapshot_every=1,
                        supervisor=Supervisor(ev, ledger=led))
        snaps = led.frontier_snapshots()
        assert snaps
        assert set(snaps[-1]["frontier"]) == \
            {e.point.name for e in r.frontier}
        counts = [s["n_evals"] for s in snaps]
        assert counts == sorted(counts)
        back = RunLedger(tmp_path / "led.json", run_key={"k": 1})
        assert back.load()
        assert back.frontier_snapshots() == snaps

    def test_resume_skips_prefill_and_eval(self, tmp_path):
        from repro.obs import METRICS
        ev = _mini_evaluator(tmp_path / "p1")
        led = RunLedger(tmp_path / "led.json", run_key={"k": 2})
        a = batch_sweep(SPACES["tiny"], ev, d_tile=2,
                        supervisor=Supervisor(ev, ledger=led))

        ev2 = _mini_evaluator(tmp_path / "p2")
        led2 = RunLedger(tmp_path / "led.json", run_key={"k": 2})
        assert led2.load()
        before = METRICS.snapshot()["counters"].get("dse.prefill_entries", 0)
        b = batch_sweep(SPACES["tiny"], ev2, d_tile=2,
                        supervisor=Supervisor(
                            ev2, ledger=led2,
                            completed=led2.completed_evals()))
        after = METRICS.snapshot()["counters"].get("dse.prefill_entries", 0)
        assert after == before, "completed designs must skip the prefill"
        assert _dump(b.evals) == _dump(a.evals)

    def test_requires_jax(self, monkeypatch):
        import repro.core.perf_model_jax as pmj
        monkeypatch.setattr(pmj, "_jax", False)
        with pytest.raises(RuntimeError, match="jax"):
            batch_sweep(SPACES["tiny"], object())


@needs_jax
class TestCompileCache:
    """``use_compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` stands when set;
    otherwise the AOT compiles land in the one fixed directory given."""

    @pytest.mark.parametrize("env_set", [True, False])
    def test_compiles_land_in_one_directory(self, env_set, tmp_path):
        import os
        import subprocess
        import sys

        default_dir, env_dir = tmp_path / "default", tmp_path / "env"
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(sys.path))
        if env_set:
            env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
        script = ("import sys, jax, jax.numpy as jnp\n"
                  "from repro.core.perf_model_jax import use_compile_cache\n"
                  "print(use_compile_cache(sys.argv[1]))\n"
                  "jax.jit(lambda x: x * 2 + 1).lower(jnp.ones(3)).compile()\n")
        out = subprocess.run([sys.executable, "-c", script, str(default_dir)],
                             env=env, capture_output=True, text=True,
                             timeout=120, check=True).stdout
        used, unused = ((env_dir, default_dir) if env_set
                        else (default_dir, env_dir))
        assert out.strip() == str(used)
        assert any(used.iterdir()), "the compile was not cached"
        assert not unused.exists()
