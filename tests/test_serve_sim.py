"""Property-based invariant harness for the serving simulator.

The simulator (:mod:`repro.serve.sim`) is exactly the kind of code that is
subtly wrong without adversarial tests, so every component ships behind
invariants:

* **determinism** — seeded trace generation and trace replay are
  bit-identical across runs, ``workers`` settings, and scoring engines;
* **conservation** — every request finishes exactly once, served tokens ==
  requested tokens, each preemption is matched by a resume, KV occupancy
  never exceeds capacity, p50 <= p99;
* **differential oracle** — a <=20-line brute-force reference event loop
  agrees step-for-step with the real simulator on tiny traces (the same
  oracle pattern rtlsim uses against funcsim);
* **straggler containment** — a straggling decode shard inflates p99 but
  not p50 under the monitor's default patience.

Coverage must not depend on hypothesis being installed: the seeded
concrete suites below always run; the ``@given`` property variants add
fuzz on top where hypothesis exists (via the shared ``conftest`` guard).
The invariant list is documented in ``docs/SERVING.md``.
"""

import json
import math
import os

import pytest

from conftest import HAVE_HYPOTHESIS, given, settings, st
from repro.core.perf_model_jax import jax_available
from repro.dse.evaluate import DesignEval, Evaluator, load_zoo
from repro.dse.search import SearchResult, pareto_frontier
from repro.dse.space import DesignPoint
from repro.serve.sim import (SLO, DecodeCostModel, ServingSpec,
                             StragglerEpisode, const_state_bytes,
                             kv_bytes_per_token, next_pow2, percentile,
                             simulate)
from repro.serve.trace import (Request, TraceSpec, generate_trace,
                               parse_trace_spec, trace_as_dicts,
                               trace_from_dicts)

needs_jax = pytest.mark.skipif(not jax_available(),
                               reason="jax runtime not importable")

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "tiny_trace.json")

TINY_SPEC = TraceSpec(seed=0, requests=8, rate_rps=1.0,
                      models=(("gemma_7b", 2.0), ("rwkv6_7b", 1.0)),
                      prompt_mean=16, prompt_max=64,
                      output_mean=4, output_max=16)


class FakeCostModel:
    """Deterministic arithmetic costs — isolates event-loop logic from the
    mapping search so invariant tests are exact and fast."""

    def __init__(self, decode_base=10.0, decode_per_ctx=0.01,
                 prefill_per_tok=0.5, kv_per_tok=64, const=0):
        self.a, self.b = decode_base, decode_per_ctx
        self.c, self.kv, self.const = prefill_per_tok, kv_per_tok, const

    def decode_step_ms(self, model, ctx, batch):
        return self.a + self.b * ctx + 0.001 * batch

    def prefill_ms(self, model, tokens):
        return self.c * tokens

    def kv_bytes_per_token(self, model):
        return self.kv

    def const_state_bytes(self, model):
        return self.const


class _Pt:
    name = "fake-design"


def run_sim(trace, cm=None, cap=1 << 30, max_batch=64, **kw):
    spec = ServingSpec(trace=TINY_SPEC, slo=SLO(),
                       kv_capacity_bytes=cap, max_batch=max_batch)
    return simulate(_Pt(), trace, spec=spec,
                    cost_model=cm or FakeCostModel(), **kw)


# ---------------------------------------------------------------------------
# trace generation
# ---------------------------------------------------------------------------

class TestTraceGen:
    def test_deterministic_across_runs(self):
        a, b = generate_trace(TINY_SPEC), generate_trace(TINY_SPEC)
        assert a == b

    def test_seed_changes_trace(self):
        import dataclasses
        other = dataclasses.replace(TINY_SPEC, seed=1)
        assert generate_trace(TINY_SPEC) != generate_trace(other)

    def test_bounds_and_ordering(self):
        spec = TraceSpec(seed=3, requests=200, rate_rps=2.0,
                         prompt_mean=32, prompt_max=100,
                         output_mean=8, output_max=20)
        trace = generate_trace(spec)
        assert [r.rid for r in trace] == list(range(200))
        assert all(1 <= r.prompt <= 100 for r in trace)
        assert all(1 <= r.output <= 20 for r in trace)
        arr = [r.arrival_ms for r in trace]
        assert arr == sorted(arr) and arr[0] > 0

    def test_model_mix_weights(self):
        spec = TraceSpec(seed=7, requests=600, rate_rps=1.0,
                         models=(("gemma_7b", 3.0), ("rwkv6_7b", 1.0)))
        trace = generate_trace(spec)
        frac = sum(r.model == "gemma_7b" for r in trace) / len(trace)
        assert 0.6 < frac < 0.9

    def test_golden_snapshot(self):
        with open(GOLDEN) as f:
            snap = json.load(f)
        spec = parse_trace_spec(snap["spec"])
        assert spec == TINY_SPEC
        assert trace_as_dicts(generate_trace(spec)) == snap["requests"]

    def test_json_roundtrip(self):
        trace = generate_trace(TINY_SPEC)
        assert trace_from_dicts(trace_as_dicts(trace)) == trace

    def test_spec_string_roundtrip(self):
        for spec in (TINY_SPEC, TraceSpec(),
                     TraceSpec(seed=9, requests=3, rate_rps=0.5,
                               models=(("glm4_9b", 1.5),))):
            assert parse_trace_spec(spec.spec()) == spec

    def test_parse_default_models(self):
        spec = parse_trace_spec("requests=4",
                                default_models=["gemma_7b", "rwkv6_7b"])
        assert spec.models == (("gemma_7b", 1.0), ("rwkv6_7b", 1.0))
        # an explicit models= wins over the default
        spec = parse_trace_spec("models=glm4_9b:2",
                                default_models=["gemma_7b"])
        assert spec.models == (("glm4_9b", 2.0),)

    def test_parse_errors(self):
        for bad in ("bogus=1", "rate=0", "prompt=abc", "prompt=9",
                    "requests=-1", "seed"):
            with pytest.raises(ValueError):
                parse_trace_spec(bad)

    @pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
    @given(seed=st.integers(0, 2**16), n=st.integers(0, 32))
    @settings(max_examples=25, deadline=None)
    def test_prop_trace_bounds(self, seed, n):
        spec = TraceSpec(seed=seed, requests=n, rate_rps=1.0)
        trace = generate_trace(spec)
        assert len(trace) == n
        assert all(1 <= r.prompt <= spec.prompt_max for r in trace)
        assert all(1 <= r.output <= spec.output_max for r in trace)
        assert trace == generate_trace(spec)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

class TestHelpers:
    def test_next_pow2(self):
        assert [next_pow2(n) for n in (0, 1, 2, 3, 7, 8, 9, 1000)] \
            == [1, 1, 2, 4, 8, 8, 16, 1024]

    def test_percentile_deterministic(self):
        vals = [5.0, 1.0, 9.0, 3.0]
        assert percentile(vals, 50) == 3.0
        assert percentile(vals, 99) == 9.0
        assert percentile(vals, 0) == 1.0
        assert percentile([], 50) == 0.0
        assert percentile(vals, 50) in vals  # nearest-rank, never interp

    def test_kv_bytes_per_token_attention(self):
        from repro.configs import get_config
        cfg = get_config("gemma_7b", reduced=True)
        n_attn = cfg.n_periods * sum(1 for s in cfg.layer_pattern
                                     if s.kind == "attn")
        assert kv_bytes_per_token(cfg) == n_attn * 2 * cfg.n_kv_heads * cfg.hd

    def test_kv_bytes_per_token_latent_attention(self):
        from repro.configs import get_config
        # DeepSeek-V3 caches the 512-wide kv latent and the 64-wide RoPE
        # key per layer, not 2 x 128 heads x 128
        cfg = get_config("deepseek_v3_671b")
        assert kv_bytes_per_token(cfg, data_bytes=1) == 61 * 576
        assert const_state_bytes(cfg) == 0

    def test_recurrent_state_constant(self):
        from repro.configs import get_config
        rwkv = get_config("rwkv6_7b", reduced=True)
        # pure-recurrent model: zero per-token KV growth, nonzero state
        assert kv_bytes_per_token(rwkv) == 0
        assert const_state_bytes(rwkv) > 0
        gemma = get_config("gemma_7b", reduced=True)
        assert const_state_bytes(gemma) == 0

    @pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
    @given(st.lists(st.floats(0, 1e6), max_size=40))
    @settings(max_examples=50, deadline=None)
    def test_prop_percentile_order(self, vals):
        assert percentile(vals, 50) <= percentile(vals, 99)


# ---------------------------------------------------------------------------
# simulator invariants (FakeCostModel: pure event-loop logic)
# ---------------------------------------------------------------------------

class TestSimInvariants:
    def test_bit_deterministic_replay(self):
        trace = generate_trace(TINY_SPEC)
        a = run_sim(trace).summary()
        b = run_sim(trace).summary()
        assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)

    def test_conservation_all_served(self):
        trace = generate_trace(TraceSpec(seed=2, requests=32, rate_rps=5.0))
        res = run_sim(trace)
        assert res.completed == len(trace)
        assert res.tokens_served == sum(r.output for r in trace)
        for row in res.requests:
            assert row["ttft_ms"] >= 0 and row["finish_ms"] \
                >= row["arrival_ms"]
            assert row["resumes"] == row["preemptions"]

    def test_kv_pressure_preempts_and_recovers(self):
        trace = generate_trace(TraceSpec(seed=4, requests=24, rate_rps=50.0,
                                         prompt_mean=8, prompt_max=16,
                                         output_mean=8, output_max=16))
        # capacity fits ~2 full requests -> heavy preemption, no deadlock
        cap = 64 * (16 + 16) * 2
        res = run_sim(trace, cap=cap)
        assert res.preemptions > 0
        assert res.kv_peak_bytes <= cap
        assert res.completed == len(trace)
        assert res.tokens_served == sum(r.output for r in trace)
        for row in res.requests:
            assert row["resumes"] == row["preemptions"]

    def test_request_larger_than_capacity_rejected(self):
        trace = [Request(0, 0.0, "gemma_7b", prompt=100, output=10)]
        with pytest.raises(ValueError, match="never be served"):
            run_sim(trace, cap=64 * 50)

    def test_percentile_ordering_in_result(self):
        res = run_sim(generate_trace(TINY_SPEC))
        assert res.p50_ttft_ms <= res.p99_ttft_ms
        assert res.p50_tpot_ms <= res.p99_tpot_ms

    def test_empty_trace(self):
        res = run_sim([])
        assert (res.n_steps, res.completed, res.goodput_tps) == (0, 0, 0.0)

    def test_max_batch_respected(self):
        trace = generate_trace(TraceSpec(seed=5, requests=40, rate_rps=100.0))
        res = run_sim(trace, max_batch=4, record_steps=True)
        assert res.completed == len(trace)
        assert all(sum(s["batch"].values()) + len(s["admitted"]) <= 4 + 4
                   for s in res.steps)
        assert max(sum(s["batch"].values()) for s in res.steps) <= 4

    def test_goodput_monotone_in_slo(self):
        trace = generate_trace(TraceSpec(seed=6, requests=24, rate_rps=2.0))
        spec_t = ServingSpec(trace=TINY_SPEC, slo=SLO(ttft_ms=20.0,
                                                      tpot_ms=5.0))
        spec_l = ServingSpec(trace=TINY_SPEC, slo=SLO(ttft_ms=1e9,
                                                      tpot_ms=1e9))
        tight = simulate(_Pt(), trace, spec=spec_t,
                         cost_model=FakeCostModel())
        loose = simulate(_Pt(), trace, spec=spec_l,
                         cost_model=FakeCostModel())
        assert loose.slo_attainment >= tight.slo_attainment
        assert loose.slo_attainment == 1.0
        assert loose.goodput_tps >= tight.goodput_tps

    def test_step_log_contract(self):
        trace = generate_trace(TINY_SPEC)
        res = run_sim(trace, record_steps=True)
        assert len(res.steps) == res.n_steps
        admitted = [rid for s in res.steps for rid in s["admitted"]]
        completed = [rid for s in res.steps for rid in s["completed"]]
        assert sorted(completed) == [r.rid for r in trace]
        assert set(admitted) == {r.rid for r in trace}
        t_prev = -1.0
        for s in res.steps:
            assert s["t_ms"] >= t_prev and s["step_ms"] > 0
            t_prev = s["t_ms"]

    def test_metrics_counters(self):
        from repro.obs import METRICS, set_metrics_enabled
        set_metrics_enabled(True)
        METRICS.reset()
        trace = generate_trace(TraceSpec(seed=4, requests=12, rate_rps=50.0,
                                         prompt_mean=8, prompt_max=16,
                                         output_mean=8, output_max=16))
        res = run_sim(trace, cap=64 * (16 + 16) * 2)
        snap = METRICS.snapshot()
        assert snap["counters"]["serve.steps"] == res.n_steps
        assert snap["counters"]["serve.preemptions"] == res.preemptions
        assert snap["histograms"]["serve.batch_occupancy"]["count"] \
            == res.n_steps

    @pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
    @given(seed=st.integers(0, 2**10), rate=st.floats(0.5, 100.0),
           tight=st.booleans())
    @settings(max_examples=20, deadline=None)
    def test_prop_conservation(self, seed, rate, tight):
        trace = generate_trace(TraceSpec(seed=seed, requests=16,
                                         rate_rps=rate, prompt_mean=8,
                                         prompt_max=16, output_mean=4,
                                         output_max=8))
        cap = 64 * (16 + 8) * (2 if tight else 1000)
        res = run_sim(trace, cap=cap)
        assert res.completed == len(trace)
        assert res.tokens_served == sum(r.output for r in trace)
        assert res.kv_peak_bytes <= cap
        assert res.p50_ttft_ms <= res.p99_ttft_ms


# ---------------------------------------------------------------------------
# differential oracle (brute-force reference, step-for-step)
# ---------------------------------------------------------------------------

def oracle(trace, cm):
    """<=20-line brute-force reference: no preemption path (ample KV), one
    batched decode per tenant model per step, admissions prefill+emit."""
    pending = sorted(trace, key=lambda r: (r.arrival_ms, r.rid))
    state = {r.rid: [r, 0] for r in trace}   # request -> tokens generated
    t, active, log = 0.0, [], []
    while pending or active:
        if not active and pending and pending[0].arrival_ms > t:
            t = pending[0].arrival_ms
        new = [state[r.rid] for r in pending if r.arrival_ms <= t]
        pending = [r for r in pending if r.arrival_ms > t]
        cost = sum(cm.prefill_ms(r.model, r.prompt + p) for r, p in new)
        groups = {}
        for r, p in active:
            groups.setdefault(r.model, []).append(r.prompt + p)
        cost += sum(cm.decode_step_ms(m, max(cs), len(cs))
                    for m, cs in sorted(groups.items()))
        for s in active + new:
            s[1] += 1
        t += cost
        done = sorted(s[0].rid for s in active + new if s[1] >= s[0].output)
        active = [s for s in active + new if s[1] < s[0].output]
        log.append((t, sorted(s[0].rid for s in new), done))
    return log


class TestDifferentialOracle:
    def test_step_for_step_golden_trace(self):
        trace = generate_trace(TINY_SPEC)
        cm = FakeCostModel()
        res = run_sim(trace, cm=cm, record_steps=True)
        ref = oracle(trace, cm)
        assert len(res.steps) == len(ref)
        for s, (t_end, new, done) in zip(res.steps, ref):
            assert sorted(s["admitted"]) == new
            assert sorted(s["completed"]) == done
            assert s["t_ms"] + s["step_ms"] == t_end  # identical float path

    @pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
    def test_step_for_step_seeded(self, seed):
        trace = generate_trace(TraceSpec(
            seed=seed, requests=8, rate_rps=2.0, prompt_mean=8,
            prompt_max=32, output_mean=4, output_max=12,
            models=(("gemma_7b", 1.0), ("glm4_9b", 1.0))))
        cm = FakeCostModel(decode_base=3.0, prefill_per_tok=0.25)
        res = run_sim(trace, cm=cm, record_steps=True)
        ref = oracle(trace, cm)
        assert [(s["t_ms"] + s["step_ms"], sorted(s["admitted"]),
                 sorted(s["completed"])) for s in res.steps] == ref
        assert res.completed == len(trace)


# ---------------------------------------------------------------------------
# decode cost model (real mapping search, reduced configs)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cost_model():
    pt = DesignPoint(n_fus=64, buffer_kb=128, dram_gbps=64,
                     dataflow_set="attention_fused")
    return DecodeCostModel(pt, reduced=True)


class TestDecodeCostModel:
    def test_decode_monotone_in_context(self, cost_model):
        ms = [cost_model.decode_step_ms("gemma_7b", ctx, 1)
              for ctx in (16, 64, 256)]
        assert ms[0] <= ms[1] <= ms[2] and ms[0] > 0

    def test_batch_amortizes(self, cost_model):
        one = cost_model.decode_step_ms("gemma_7b", 64, 1)
        eight = cost_model.decode_step_ms("gemma_7b", 64, 8)
        assert one < eight < 8 * one

    def test_bucketing_memoizes(self, cost_model):
        n0 = len(cost_model._memo)
        a = cost_model.decode_step_ms("gemma_7b", 100, 3)
        n1 = len(cost_model._memo)
        b = cost_model.decode_step_ms("gemma_7b", 127, 4)  # same buckets
        assert a == b and len(cost_model._memo) == n1 >= n0

    def test_prefill_exceeds_single_decode(self, cost_model):
        assert cost_model.prefill_ms("gemma_7b", 256) \
            > cost_model.decode_step_ms("gemma_7b", 256, 1)

    def test_real_cost_sim_end_to_end(self, cost_model):
        spec = ServingSpec(trace=TINY_SPEC, slo=SLO(), reduced=True)
        trace = generate_trace(TINY_SPEC)
        res = simulate(cost_model.point, trace, spec=spec,
                       cost_model=cost_model)
        res2 = simulate(cost_model.point, trace, spec=spec,
                        cost_model=cost_model)
        assert res.completed == len(trace) and res.goodput_tps >= 0
        assert json.dumps(res.summary(), sort_keys=True) \
            == json.dumps(res2.summary(), sort_keys=True)

    @needs_jax
    def test_engine_invariant_schedule(self):
        pt = DesignPoint(n_fus=64, buffer_kb=128, dram_gbps=64,
                         dataflow_set="os")
        spec = ServingSpec(trace=TINY_SPEC, slo=SLO(), reduced=True)
        trace = generate_trace(TINY_SPEC)
        outs = {}
        for engine in ("numpy", "jax"):
            cm = DecodeCostModel(pt, engine=engine, reduced=True)
            outs[engine] = simulate(pt, trace, spec=spec, cost_model=cm,
                                    record_steps=True)
        assert outs["numpy"].summary() == outs["jax"].summary()
        assert outs["numpy"].steps == outs["jax"].steps


# ---------------------------------------------------------------------------
# straggler containment (ft.straggler wired into the step loop)
# ---------------------------------------------------------------------------

class _NeverFlag:
    def record(self, times):
        pass

    def stragglers(self):
        return []


# dense arrivals + heavy per-step cost keep the system continuously busy,
# so a slowed step always lands on someone's latency (no idle absorption)
STRAGGLER_TRACE = TraceSpec(seed=11, requests=16, rate_rps=1000.0,
                            prompt_mean=8, prompt_max=16,
                            output_mean=6, output_max=10)


def busy_cm():
    return FakeCostModel(decode_base=100.0, prefill_per_tok=5.0)


class TestStraggler:
    def test_p99_inflates_p50_does_not(self):
        trace = generate_trace(STRAGGLER_TRACE)
        base = run_sim(trace, cm=busy_cm(), max_batch=2, shards=4)
        # slow shard 1 by 8x near the tail: the default-patience monitor
        # pays ~3 slow steps then evicts, so only the last-admitted
        # requests' TTFT moves — the median is already decided
        ep = StragglerEpisode(shard=1, start=base.n_steps - 12, factor=8.0)
        hit = run_sim(trace, cm=busy_cm(), max_batch=2, shards=4,
                      straggler=ep)
        assert hit.remeshes == 1
        assert hit.p50_ttft_ms == base.p50_ttft_ms
        assert hit.p99_ttft_ms > base.p99_ttft_ms

    def test_eviction_bounds_slowdown(self):
        trace = generate_trace(STRAGGLER_TRACE)
        ep = StragglerEpisode(shard=0, start=0, factor=8.0)
        evicted = run_sim(trace, cm=busy_cm(), shards=4, straggler=ep)
        stuck = run_sim(trace, cm=busy_cm(), shards=4, straggler=ep,
                        monitor=_NeverFlag())
        assert evicted.remeshes == 1 and stuck.remeshes == 0
        # the monitor caps the episode at ~patience slow steps; without it
        # every step of the run pays the 8x factor
        assert evicted.sim_ms < stuck.sim_ms

    def test_single_shard_has_no_monitor(self):
        trace = generate_trace(STRAGGLER_TRACE)
        ep = StragglerEpisode(shard=0, start=0, steps=5, factor=8.0)
        res = run_sim(trace, cm=busy_cm(), shards=1, straggler=ep)
        assert res.remeshes == 0  # nothing to re-mesh at one shard
        assert res.sim_ms > run_sim(trace, cm=busy_cm(), shards=1).sim_ms

    def test_remesh_penalty_charged(self):
        trace = generate_trace(STRAGGLER_TRACE)
        ep = StragglerEpisode(shard=1, start=0, factor=8.0)
        free = run_sim(trace, cm=busy_cm(), shards=4, straggler=ep)
        paid = run_sim(trace, cm=busy_cm(), shards=4, straggler=ep,
                       remesh_penalty_ms=500.0)
        assert paid.remeshes == free.remeshes == 1
        # all arrivals land before the first step, so the one-time penalty
        # shifts the whole schedule rigidly: exactly +500 ms end to end
        assert paid.sim_ms == free.sim_ms + 500.0


# ---------------------------------------------------------------------------
# DSE integration: Evaluator / DesignEval / Pareto / workers
# ---------------------------------------------------------------------------

SERVE_TRACE = TraceSpec(seed=0, requests=6, rate_rps=1.0,
                        models=(("gemma_7b", 1.0),), prompt_mean=8,
                        prompt_max=32, output_mean=4, output_max=8)
SERVE_SPEC = ServingSpec(trace=SERVE_TRACE, slo=SLO(), reduced=True)


@pytest.fixture(scope="module")
def served_eval():
    zoo = load_zoo(["gemma_7b"], seq=64, reduced=True)
    ev = Evaluator(zoo=zoo, serving=SERVE_SPEC)
    pt = DesignPoint(n_fus=64, buffer_kb=128, dram_gbps=64,
                     dataflow_set="os")
    return ev.evaluate(pt)


class TestDSEIntegration:
    def test_evaluator_attaches_serving(self, served_eval):
        s = served_eval.serving
        assert s is not None
        assert s["completed"] == SERVE_TRACE.requests
        assert {"goodput_tps", "slo_attainment", "p50_ttft_ms",
                "p99_ttft_ms", "p50_tpot_ms", "p99_tpot_ms"} <= set(s)

    def test_objectives_switch_to_goodput(self, served_eval):
        assert served_eval.objectives()[0] \
            == -served_eval.serving["goodput_tps"]
        static = DesignEval(point=served_eval.point, cycles=1.0,
                            energy_pj=1.0, area_mm2=1.0, power_mw=1.0,
                            macs=1.0)
        assert static.objectives()[0] == static.cycles

    def test_design_eval_ledger_roundtrip(self, served_eval):
        again = DesignEval.from_dict(
            json.loads(json.dumps(served_eval.as_dict())))
        assert again.serving == served_eval.serving
        assert again.objectives() == served_eval.objectives()

    def test_pareto_prefers_goodput(self):
        def ev(name, goodput):
            e = DesignEval(point=DesignPoint(64, 128, 16, name), cycles=9e9,
                           energy_pj=1.0, area_mm2=1.0, power_mw=1.0,
                           macs=1.0)
            e.serving = {"goodput_tps": goodput}
            return e
        lo, hi = ev("os", 1.0), ev("switch", 5.0)
        front = pareto_frontier([lo, hi])
        assert front == [hi]

    def test_report_serving_section(self, served_eval, tmp_path):
        from repro.dse.report import format_serving, write_bench_json
        result = SearchResult(space="tiny", strategy="exhaustive",
                              evals=[served_eval],
                              frontier=[served_eval], wall_s=0.0,
                              cache_stats={"hits": 0, "misses": 0},
                              supervisor={})
        payload = write_bench_json(str(tmp_path / "b.json"), result)
        assert payload["serving"]["winner"] == served_eval.point.name
        assert payload["best"]["goodput"] == served_eval.point.name
        assert served_eval.point.name in format_serving(result)

    def test_workers_invariant_sweep(self):
        from repro.dse.search import run_search
        from repro.dse.space import DesignSpace
        space = DesignSpace(name="serve-mini", n_fus=(64,),
                            buffer_kb=(128,), dram_gbps=(16.0,),
                            dataflow_sets=("os", "attention_fused"))
        summaries = {}
        for workers in (1, 2):
            zoo = load_zoo(["gemma_7b"], seq=64, reduced=True)
            ev = Evaluator(zoo=zoo, serving=SERVE_SPEC)
            res = run_search(space, ev, workers=workers)
            summaries[workers] = {e.point.name: e.serving
                                  for e in res.evals}
        assert json.dumps(summaries[1], sort_keys=True) \
            == json.dumps(summaries[2], sort_keys=True)


# ---------------------------------------------------------------------------
# heavy opt-in profiles (pytest -m slow; tier-1 runs -m "not slow")
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestHeavyProfiles:
    def test_stress_large_trace_conservation(self):
        trace = generate_trace(TraceSpec(seed=42, requests=2000,
                                         rate_rps=200.0, prompt_mean=16,
                                         prompt_max=64, output_mean=8,
                                         output_max=32))
        cap = 64 * (64 + 32) * 8  # sustained heavy preemption
        res = run_sim(trace, cap=cap)
        assert res.completed == 2000
        assert res.tokens_served == sum(r.output for r in trace)
        assert res.kv_peak_bytes <= cap and res.preemptions > 0

    @pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
    @given(seed=st.integers(0, 2**20), n=st.integers(1, 64),
           rate=st.floats(0.1, 500.0), cap_reqs=st.integers(2, 64))
    @settings(max_examples=300, deadline=None)
    def test_prop_conservation_heavy(self, seed, n, rate, cap_reqs):
        trace = generate_trace(TraceSpec(seed=seed, requests=n,
                                         rate_rps=rate, prompt_mean=8,
                                         prompt_max=16, output_mean=4,
                                         output_max=8))
        cap = 64 * (16 + 8) * cap_reqs
        res = run_sim(trace, cap=cap)
        assert res.completed == n
        assert res.tokens_served == sum(r.output for r in trace)
        assert res.kv_peak_bytes <= cap


# ---------------------------------------------------------------------------
# serve.engine unit tests (decode_state_shapes / build_serve_step)
# ---------------------------------------------------------------------------

@needs_jax
class TestServeEngine:
    @pytest.fixture(scope="class")
    def jax_bits(self):
        import jax
        from repro.configs import get_config
        from repro.serve.engine import (ServeConfig, build_serve_step,
                                        decode_state_shapes)
        return jax, get_config, ServeConfig, build_serve_step, \
            decode_state_shapes

    def test_decode_state_shapes_attention(self, jax_bits):
        jax, get_config, ServeConfig, _, decode_state_shapes = jax_bits
        cfg = get_config("gemma_7b", reduced=True)
        sc = ServeConfig(batch=2, max_len=16)
        shapes = decode_state_shapes(cfg, sc)
        assert set(shapes) == {f"pos{i}"
                               for i in range(len(cfg.layer_pattern))}
        k = shapes["pos0"]["k"]
        assert k.shape == (cfg.n_periods, 2, cfg.n_kv_heads, 16, cfg.hd)
        assert shapes["pos0"]["v"].shape == k.shape

    def test_decode_state_shapes_recurrent(self, jax_bits):
        jax, get_config, ServeConfig, _, decode_state_shapes = jax_bits
        cfg = get_config("rwkv6_7b", reduced=True)
        shapes = decode_state_shapes(cfg, ServeConfig(batch=3, max_len=8))
        leaves = jax.tree_util.tree_leaves(shapes)
        # every recurrent-state leaf is per-period and batch-indexed,
        # independent of max_len (constant state, not a KV cache)
        assert leaves and all(l.shape[0] == cfg.n_periods
                              and l.shape[1] == 3 for l in leaves)
        assert all(8 not in l.shape[2:] for l in leaves)

    def test_build_serve_step_shape_contract(self, jax_bits):
        jax, get_config, ServeConfig, build_serve_step, dss = jax_bits
        import jax.numpy as jnp
        from repro.models import transformer as TF
        cfg = get_config("gemma_7b", reduced=True)
        sc = ServeConfig(batch=2, max_len=16)
        params = jax.eval_shape(
            lambda: TF.init_params(cfg, jax.random.PRNGKey(0)))
        state = dss(cfg, sc)
        step, jit_with = build_serve_step(cfg)
        assert jit_with is None  # unsharded path returns the jitted step
        tok = jax.ShapeDtypeStruct((2,), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        logits, new_state = jax.eval_shape(step, params, state, tok, pos)
        assert logits.shape == (2, cfg.vocab_size)
        assert logits.dtype == jnp.float32
        assert jax.tree_util.tree_structure(new_state) \
            == jax.tree_util.tree_structure(state)
        assert all(a.shape == b.shape for a, b in zip(
            jax.tree_util.tree_leaves(new_state),
            jax.tree_util.tree_leaves(state)))

    def test_build_serve_step_encdec_contract(self, jax_bits):
        jax, get_config, ServeConfig, build_serve_step, dss = jax_bits
        import jax.numpy as jnp
        from repro.models import encdec as ED
        cfg = get_config("whisper_base", reduced=True)
        sc = ServeConfig(batch=2, max_len=8)
        params = jax.eval_shape(
            lambda: ED.init_params_encdec(cfg, jax.random.PRNGKey(0)))
        state = dss(cfg, sc)
        step, _ = build_serve_step(cfg)
        tok = jax.ShapeDtypeStruct((2,), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        enc = jax.ShapeDtypeStruct((2, cfg.enc_seq_len, cfg.d_model),
                                   cfg.jdtype)
        logits, new_state = jax.eval_shape(step, params, state, tok, pos,
                                           enc)
        assert logits.shape == (2, cfg.vocab_size)
        assert jax.tree_util.tree_structure(new_state) \
            == jax.tree_util.tree_structure(state)
