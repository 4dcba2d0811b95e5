"""The comparison that decides ``correct``, on a test-size cell on the CPU.

Each run skips the harness's look for a chip and drives the rest of a run:
set-up, window, capture, reference.  A sound run comes out correct; the
float32 control and every fault planted in the timed path underneath the
harness come out not correct.
"""

import functools
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench.lib.harness import run_cell

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(DATA)))
SWEEP, EVOLVE = "tiny_zoo.tiny_sweep", "tiny_zoo.tiny_evolve"
SEED = 2 ** 31 + 77


@pytest.fixture(autouse=True)
def _own_compile_cache(tmp_path_factory, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path_factory.getbasetemp() / "jax_cache"))


def run(cell=SWEEP, control=False, seconds=0.3):
    return run_cell(cell, SEED, seconds, False, time.perf_counter(),
                    control=control, require_tpu=False,
                    bench_json=os.path.join(DATA, "BENCHMARK.json"),
                    traffic_dir=DATA)


def checks(out):
    return {k: v["value"] for k, v in out["checks"].items()}


@pytest.mark.parametrize("cell", [SWEEP, EVOLVE])
def test_sound_run_is_correct(cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(out)[-1] == "checks"
    c = checks(out)
    assert c["score_gap"] < 1e-12 and c["answer_gap"] == 0.0
    assert c["window_compiles"] == 0


@pytest.mark.parametrize("cell", [SWEEP, EVOLVE])
def test_float32_control_fails(cell):
    out = run(cell, control=True)
    assert not out["correct"]
    c = checks(out)
    assert c["score_gap"] > out["checks"]["score_gap"]["limit"]
    assert c["answer_gap"] > out["checks"]["answer_gap"]["limit"]


def _wrap_design_kernel(monkeypatch, change):
    import repro.core.perf_model_jax as pmj

    orig = pmj.perf_kernel_jax_design

    @functools.wraps(orig)
    def broken(wl, hw_list, *a, **kw):
        return change(orig(wl, hw_list, *a, **kw))
    monkeypatch.setattr(pmj, "perf_kernel_jax_design", broken)


def test_float32_device_scores_fail(monkeypatch):
    _wrap_design_kernel(monkeypatch, lambda out: {
        k: (v.astype(np.float32).astype(v.dtype)
            if v.dtype == np.float64 else v) for k, v in out.items()})
    out = run()
    assert not out["correct"]
    assert checks(out)["score_gap"] > out["checks"]["score_gap"]["limit"]


def test_answer_altered_where_produced_fails(monkeypatch):
    def alter(out):
        out = {k: v.copy() for k, v in out.items()}
        out["energy_pj"][:, 0] *= 1 + 1e-6
        return out
    _wrap_design_kernel(monkeypatch, alter)
    assert not run()["correct"]


def test_half_the_designs_left_out_fails(monkeypatch):
    def half(out):
        out = {k: v.copy() for k, v in out.items()}
        n = out["cycles"].shape[0]
        for v in out.values():
            v[n // 2:] = v[0]
        return out
    _wrap_design_kernel(monkeypatch, half)
    assert not run()["correct"]


def test_perturbed_selection_fails(monkeypatch):
    import repro.core.mapper_batch as mb

    orig = mb._argbest

    def second_best(cycles, energy, objective):
        """The best candidate whose numbers differ from the winner's."""
        order = np.lexsort((energy, cycles))
        key = (cycles[order[0]], energy[order[0]])
        for i in order:
            if (cycles[i], energy[i]) != key:
                return int(i)
        return orig(cycles, energy, objective)
    monkeypatch.setattr(mb, "_argbest", second_best)
    out = run()
    assert not out["correct"]
    assert checks(out)["answer_gap"] > out["checks"]["answer_gap"]["limit"]


def test_stale_scorecard_fails(monkeypatch):
    import dataclasses

    from repro.dse.evaluate import Evaluator

    orig = Evaluator.evaluate
    last = {}

    def stale(self, point):
        e = orig(self, point)
        prev = last.get("e")
        last["e"] = e
        return dataclasses.replace(prev, point=point) if prev else e
    monkeypatch.setattr(Evaluator, "evaluate", stale)
    assert not run()["correct"]


def test_one_design_group_scored_wrong_fails(monkeypatch):
    """A fault confined to one tile: every design of one (FU count,
    dataflow set) group gets a scorecard 0.1% off."""
    import dataclasses

    from repro.dse.evaluate import Evaluator

    orig = Evaluator.evaluate

    def off(self, point):
        e = orig(self, point)
        if (point.n_fus, point.dataflow_set) == (128, "switch"):
            e = dataclasses.replace(e, cycles=e.cycles * 1.001)
        return e
    monkeypatch.setattr(Evaluator, "evaluate", off)
    out = run()
    assert not out["correct"]
    assert checks(out)["answer_gap"] > out["checks"]["answer_gap"]["limit"]


@pytest.mark.parametrize("cell", [SWEEP, EVOLVE])
def test_frontier_member_dropped_fails(monkeypatch, cell):
    import importlib

    bs = importlib.import_module("repro.dse.batch_sweep")
    search = importlib.import_module("repro.dse.search")
    orig = search.pareto_frontier

    def short(evals, *a, **kw):
        return orig(evals, *a, **kw)[:-1]
    monkeypatch.setattr(search, "pareto_frontier", short)
    monkeypatch.setattr(bs, "pareto_frontier", short)
    out = run(cell)
    assert not out["correct"]
    assert checks(out)["answer_gap"] == 1.0


def test_compile_in_window_fails(monkeypatch):
    from repro.core import perf_model_jax as pmj

    orig = pmj.perf_kernel_jax_design

    @functools.wraps(orig)
    def cold(*a, **kw):
        pmj.clear_compile_cache()
        return orig(*a, **kw)
    monkeypatch.setattr(pmj, "perf_kernel_jax_design", cold)
    out = run()
    assert not out["correct"]
    assert checks(out)["window_compiles"] > 0


def test_cli_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "zoo4.sweep_large_prefill", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_every_cell_resolves_by_name():
    from bench.lib.cell import load_cell

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        cell = load_cell(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert names == {"setup_s", cell.traffic["rate_metric"]}
        assert cell.per_layer
        for m in cell.per_layer:
            assert os.path.exists(os.path.join(
                ROOT, "bench", "layer_metrics", m["name"] + ".py"))
