"""Crash-safe sweep tests: supervised worker pool (crash / hang /
transient / quarantine), run-ledger checkpoint + resume, deterministic
fault injection, and the multi-process-safe mapping cache.

The acceptance bar throughout: a sweep under injected faults must converge
to results identical to the clean run — faults cost retries, never answers.
"""

import json
import os
import subprocess
import sys

import pytest

from repro.configs import get_config
from repro.dse import (MappingCache, SPACES, Evaluator, FaultPlan,
                       RunLedger, Supervisor, SupervisorConfig,
                       corrupt_cache_file, pareto_frontier,
                       parse_fault_spec)
from repro.dse.cache import _SCHEMA, atomic_write_json, entry_checksum
from repro.dse.evaluate import DesignEval, lower_config
from repro.dse.faults import SweepKilled, TransientFault
from repro.dse.space import DesignPoint
from repro.dse.supervisor import failure_stub
from repro.obs import METRICS

POINTS = list(SPACES["tiny"].enumerate())


@pytest.fixture(scope="module")
def zoo():
    return {"gemma_7b": lower_config(get_config("gemma_7b", reduced=True),
                                     seq=64)}


@pytest.fixture(scope="module")
def clean_evals(zoo):
    ev = Evaluator(zoo=zoo, cache=MappingCache())
    with Supervisor(ev) as sup:
        return sup.map(POINTS)


def _sig(evals):
    return [(e.point.name, e.cycles, e.energy_pj, e.area_mm2)
            for e in evals]


class TestFaultPlan:
    def test_spec_round_trip(self):
        plan = FaultPlan(seed=7, crash=1, hang=2, transient=3, corrupt=1,
                         kill_after=4, hang_s=12.5)
        assert parse_fault_spec(plan.spec()) == plan

    def test_parse_rejects_unknown_key(self):
        with pytest.raises(ValueError, match="unknown fault spec key"):
            parse_fault_spec("crash=1,bogus=2")
        with pytest.raises(ValueError, match="not a number"):
            parse_fault_spec("crash=yes")

    def test_kind_assignment_deterministic(self):
        plan = FaultPlan(seed=3, crash=2, hang=1, transient=3)
        kinds = plan.kinds()
        assert kinds == plan.kinds()  # stable across calls
        assert sorted(kinds) == ["crash", "crash", "hang", "transient",
                                 "transient", "transient"]
        assert plan.kind_for(len(kinds)) is None  # slots beyond the plan

    def test_inactive_plan_never_fires(self):
        plan = FaultPlan()
        assert not plan.active
        plan.fire(0, in_process=True)  # no-op, no exception


class TestSupervisorSequential:
    def test_transient_fault_recovers_identically(self, zoo, clean_evals):
        ev = Evaluator(zoo=zoo, cache=MappingCache())
        with Supervisor(ev, fault_plan=FaultPlan(transient=2, seed=1),
                        cfg=SupervisorConfig(backoff_base_s=0.0)) as sup:
            evals = sup.map(POINTS)
        assert _sig(evals) == _sig(clean_evals)
        assert sup.stats["retries"] == 2
        assert sup.stats["quarantined"] == 0

    def test_poison_point_quarantined_not_fatal(self, zoo, clean_evals):
        poison = POINTS[2].name

        class PoisonEvaluator(Evaluator):
            def evaluate(self, point):
                if point.name == poison:
                    raise RuntimeError("poison point")
                return super().evaluate(point)

        ev = PoisonEvaluator(zoo=zoo, cache=MappingCache())
        with Supervisor(ev, cfg=SupervisorConfig(
                max_retries=1, backoff_base_s=0.0)) as sup:
            evals = sup.map(POINTS)
        assert sup.stats["quarantined"] == 1
        stub = evals[2]
        assert stub.failed and "poison point" in stub.error
        assert stub.retries == 2  # max_retries + the final attempt
        # the other points are untouched by the neighbour's failure
        assert _sig(e for e in evals if not e.failed) == \
            _sig(e for e in clean_evals if e.point.name != poison)
        # and the frontier never contains the zeroed stub
        assert stub not in pareto_frontier(evals)

    def test_kill_after_checkpoints_and_resumes(self, zoo, clean_evals,
                                                tmp_path):
        path = tmp_path / "run.ledger"
        ev = Evaluator(zoo=zoo, cache=MappingCache())
        with Supervisor(ev, fault_plan=FaultPlan(kill_after=3),
                        ledger=RunLedger(path, run_key={"t": 1})) as sup:
            with pytest.raises(SweepKilled):
                sup.map(POINTS)
        assert path.exists()  # flushed on the interrupt exit path

        ledger = RunLedger(path, run_key={"t": 1})
        assert ledger.load() == 3
        completed = ledger.completed_evals()
        ev2 = Evaluator(zoo=zoo, cache=MappingCache())
        ev2.cache.merge(ledger.cache_entries())
        with Supervisor(ev2, ledger=ledger, completed=completed) as sup2:
            evals = sup2.map(POINTS)
        assert sup2.stats["resumed"] == 3
        assert sup2.stats["evaluated"] == len(POINTS) - 3
        assert _sig(evals) == _sig(clean_evals)


class TestSupervisorPool:
    def test_crash_hang_transient_converge(self, zoo, clean_evals):
        ev = Evaluator(zoo=zoo, cache=MappingCache())
        plan = FaultPlan(crash=1, hang=1, transient=1, seed=3, hang_s=30.0)
        with Supervisor(ev, workers=4, fault_plan=plan,
                        cfg=SupervisorConfig(task_timeout_s=5.0,
                                             backoff_base_s=0.0)) as sup:
            evals = sup.map(POINTS)
        assert _sig(evals) == _sig(clean_evals)
        assert sup.stats["retries"] == 3
        assert sup.stats["respawns"] >= 2  # the crash + the killed hang
        assert sup.stats["timeouts"] == 1
        assert sup.stats["quarantined"] == 0

    def test_respawn_budget_degrades_to_sequential(self, zoo, clean_evals):
        ev = Evaluator(zoo=zoo, cache=MappingCache())
        with Supervisor(ev, workers=2,
                        fault_plan=FaultPlan(crash=1, seed=0),
                        cfg=SupervisorConfig(max_respawns=0,
                                             backoff_base_s=0.0)) as sup:
            evals = sup.map(POINTS)
        assert sup.stats["degraded_sequential"] is True
        assert _sig(evals) == _sig(clean_evals)


class TestSupervisorEngine:
    """The engine asked for is the engine that scores: no worker pool that
    silently swaps it, no quarantine that hides a JAX compile error."""

    def test_jax_engine_refuses_worker_pool(self, zoo):
        ev = Evaluator(zoo=zoo, cache=MappingCache(), engine="jax")
        with pytest.raises(ValueError, match="one process"):
            Supervisor(ev, workers=2)

    def test_cli_jax_engine_with_workers_is_an_argparse_error(self, capsys):
        from benchmarks import dse
        with pytest.raises(SystemExit) as exc:
            dse.main(["--engine", "jax", "--workers", "2", "--dry-run"])
        assert exc.value.code == 2
        assert "--engine jax scores in one process" in capsys.readouterr().err

    def test_pool_workers_score_with_the_evaluator_engine(self, zoo):
        from repro.dse.supervisor import _WORKER, _init_worker
        ev = Evaluator(zoo=zoo, cache=MappingCache(), engine="scalar")
        try:
            _init_worker(*Supervisor(ev, workers=2)._init_args())
            assert _WORKER["ev"].engine == "scalar"
        finally:
            _WORKER.clear()

    def test_jax_compile_error_propagates_unquarantined(self, zoo):
        jax = pytest.importorskip("jax")
        refusal = ("UNIMPLEMENTED: While rewriting computation to not "
                   "contain X64 element types ...")

        class RefusedEvaluator(Evaluator):
            def evaluate(self, point):
                raise jax.errors.JaxRuntimeError(refusal)

        ev = RefusedEvaluator(zoo=zoo, cache=MappingCache())
        with Supervisor(ev, cfg=SupervisorConfig(
                max_retries=2, backoff_base_s=0.0)) as sup:
            with pytest.raises(jax.errors.JaxRuntimeError,
                               match="UNIMPLEMENTED"):
                sup.map(POINTS)
        assert sup.stats["retries"] == 0
        assert sup.stats["quarantined"] == 0


class TestRunLedger:
    def _eval(self, i):
        return DesignEval(point=POINTS[i], cycles=10.0 + i, energy_pj=1.0,
                          area_mm2=2.0, power_mw=3.0, macs=4.0)

    def test_round_trip(self, tmp_path):
        path = tmp_path / "l.json"
        led = RunLedger(path, run_key={"space": "tiny"})
        led.record(self._eval(0))
        led.record(self._eval(1))
        led.add_cache_entries({"k1": {"perf": {"cycles": 1.0}}})
        led.flush()
        led.flush()  # idempotent: nothing dirty
        assert led.flushes == 1

        back = RunLedger(path, run_key={"space": "tiny"})
        assert back.load() == 2
        assert set(back.completed_evals()) == {POINTS[0].name,
                                               POINTS[1].name}
        assert back.completed_evals()[POINTS[0].name].cycles == 10.0
        assert back.cache_entries() == {"k1": {"perf": {"cycles": 1.0}}}

    def test_run_key_mismatch_starts_fresh(self, tmp_path):
        path = tmp_path / "l.json"
        led = RunLedger(path, run_key={"space": "tiny"})
        led.record(self._eval(0))
        led.flush()
        other = RunLedger(path, run_key={"space": "large"})
        assert other.load() == 0

    def test_failure_stubs_recorded_but_not_resumed(self, tmp_path):
        path = tmp_path / "l.json"
        led = RunLedger(path)
        led.record(self._eval(0))
        led.record(failure_stub(POINTS[1], "boom", retries=3))
        led.flush()
        back = RunLedger(path)
        back.load()
        assert len(back.evals()) == 2  # partial artifact stays auditable
        assert set(back.completed_evals()) == {POINTS[0].name}  # retry boom

    def test_unreadable_ledger_is_empty(self, tmp_path):
        path = tmp_path / "l.json"
        path.write_text("{not json")
        assert RunLedger(path).load() == 0

    def test_eval_dict_round_trip(self):
        e = DesignEval(point=POINTS[0], cycles=1.0, energy_pj=2.0,
                       area_mm2=3.0, power_mw=4.0, macs=5.0,
                       per_config={"m": {"cycles": 1.0}})
        back = DesignEval.from_dict(json.loads(json.dumps(e.as_dict())))
        assert back.point == e.point
        assert _sig([back]) == _sig([e])
        stub = failure_stub(POINTS[1], "boom", retries=2)
        back = DesignEval.from_dict(stub.as_dict())
        assert back.failed and back.error == "boom" and back.retries == 2


def _fill(path, n=8):
    c = MappingCache(path)
    for i in range(n):
        c.put(f"key{i}", {"perf": {"cycles": float(i + 1)}, "spatial": "ij"})
    c.save()
    return c


class TestCacheRobustness:
    def test_corrupt_entries_quarantined_individually(self, tmp_path):
        path = str(tmp_path / "c.json")
        _fill(path, 8)
        assert corrupt_cache_file(path, 2, seed=0) == 2
        before = METRICS.counter("mapper_cache.corrupt_entries").value
        c = MappingCache(path)
        assert len(c) == 6  # exactly the corrupted entries are gone
        assert METRICS.counter(
            "mapper_cache.corrupt_entries").value == before + 2

    def test_unreadable_file_is_cold_cache(self, tmp_path):
        path = tmp_path / "c.json"
        path.write_text("{torn")
        before = METRICS.counter("mapper_cache.load_failures").value
        assert len(MappingCache(path)) == 0
        assert METRICS.counter(
            "mapper_cache.load_failures").value == before + 1

    def test_schema_mismatch_evicts_wholesale(self, tmp_path):
        path = str(tmp_path / "c.json")
        atomic_write_json(path, {"schema": _SCHEMA - 1,
                                 "entries": {"k": {"perf": {}}}})
        before = METRICS.counter("mapper_cache.schema_evictions").value
        assert len(MappingCache(path)) == 0
        assert METRICS.counter(
            "mapper_cache.schema_evictions").value == before + 1

    def test_save_merges_foreign_entries(self, tmp_path):
        path = str(tmp_path / "c.json")
        a = _fill(path, 2)
        # a second process writes disjoint entries to the same path
        b = MappingCache(path)
        b.put("other", {"perf": {"cycles": 9.0}})
        b.save()
        # a's save must not clobber b's entry: read-merge-write
        a.put("mine", {"perf": {"cycles": 8.0}})
        a.save()
        assert set(MappingCache(path).snapshot()) == \
            {"key0", "key1", "other", "mine"}

    def test_concurrent_process_saves_converge(self, tmp_path):
        path = str(tmp_path / "c.json")
        script = (
            "import sys; sys.path.insert(0, {src!r})\n"
            "from repro.dse import MappingCache\n"
            "c = MappingCache({path!r})\n"
            "for i in range(5):\n"
            "    c.put(f'{{sys.argv[1]}}-{{i}}', "
            "{{'perf': {{'cycles': float(i)}}}})\n"
            "c.save()\n").format(
                src=os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src"), path=path)
        procs = [subprocess.Popen([sys.executable, "-c", script, tag])
                 for tag in ("a", "b")]
        assert [p.wait() for p in procs] == [0, 0]
        keys = set(MappingCache(path).snapshot())
        assert keys == {f"{t}-{i}" for t in ("a", "b") for i in range(5)}

    def test_checksums_written_on_save(self, tmp_path):
        path = str(tmp_path / "c.json")
        _fill(path, 2)
        payload = json.load(open(path))
        assert set(payload["sums"]) == set(payload["entries"])
        for k, v in payload["entries"].items():
            assert payload["sums"][k] == entry_checksum(v)
