"""Observability tests: span/trace API, metrics registry, worker-pool trace
merge determinism, the scoring engine's dispatch phases and their profiler
annotations, rtlsim hardware introspection (utilization parity vs the
closed-form perf model, stall bookkeeping), the deterministic VCD writer
(golden snapshot) and the bench-JSON provenance/metrics schema."""

import json
import os

import numpy as np
import pytest

from repro.configs import get_config
from repro.core import workload as W
from repro.core.adg import generate_adg
from repro.core.dag import codegen
from repro.core.dataflow import build_dataflow
from repro.core.passes import run_backend
from repro.core.mapper import SpatialChoice
from repro.core.mapper_batch import best_mappings, best_mappings_design
from repro.core.perf_model import HWConfig, layer_perf
from repro.core.perf_model_jax import jax_available
from repro.core.rtlsim import simulate_rtl
from repro.dse import SPACES, DesignPoint, Evaluator, MappingCache, run_search
from repro.dse.evaluate import DesignEval, lower_config
from repro.dse.report import write_bench_json
from repro.dse.search import SearchResult
from repro.obs import (METRICS, PROVENANCE_SCHEMA, Gauge, Histogram,
                       Registry, VCDWriter, disable_tracing, drain_events,
                       enable_tracing, metrics_enabled, provenance_record,
                       save_trace, set_annotation_factory,
                       set_metrics_enabled, span, span_counts,
                       tracing_enabled)

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "tiny_wave.vcd")


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Tracing/metrics are process-global; every test starts and ends
    clean so test order never matters."""
    drain_events()
    METRICS.reset()
    disable_tracing()
    set_metrics_enabled(True)
    yield
    drain_events()
    METRICS.reset()
    disable_tracing()
    set_metrics_enabled(True)


# ---------------------------------------------------------------------------
# spans / trace events
# ---------------------------------------------------------------------------

class TestSpan:
    def test_measures_even_when_disabled(self):
        assert not tracing_enabled()
        with span("quiet") as sp:
            pass
        assert sp.duration_s >= 0.0
        assert drain_events() == []  # nothing recorded

    def test_records_complete_event_when_enabled(self):
        enable_tracing()
        with span("work", cat="test", key=7):
            pass
        (ev,) = drain_events()
        assert ev["name"] == "work" and ev["cat"] == "test"
        assert ev["ph"] == "X" and ev["dur"] >= 0.0
        assert ev["args"] == {"key": 7}
        assert ev["pid"] == os.getpid()

    def test_enabled_state_latched_at_entry(self):
        sp = span("latched")
        with sp:
            enable_tracing()  # too late for this span
        assert drain_events() == []

    def test_annotation_factory_wraps_recorded_spans(self, monkeypatch):
        from repro.obs import trace

        seen = []

        class Ann:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                seen.append(("open", self.name))

            def __exit__(self, *exc):
                seen.append(("close", self.name))

        # restored after the test: the JAX engine may have set its own
        monkeypatch.setattr(trace, "_ANNOTATION", trace._ANNOTATION)
        set_annotation_factory(Ann)
        with span("untraced"):
            pass
        enable_tracing()
        with span("outer"):
            with pytest.raises(ValueError):
                with span("inner"):
                    raise ValueError("x")
        assert seen == [("open", "outer"), ("open", "inner"),
                        ("close", "inner"), ("close", "outer")]
        set_annotation_factory(None)
        with span("bare"):
            pass
        assert len(seen) == 4
        assert span_counts(drain_events()) == {"bare": 1, "inner": 1,
                                               "outer": 1}

    def test_decorator(self):
        enable_tracing()

        @span("fn", cat="test")
        def f(x):
            return x + 1

        assert f(1) == 2 and f(2) == 3
        assert span_counts(drain_events()) == {"fn": 2}

    def test_exception_annotated_and_propagated(self):
        enable_tracing()
        with pytest.raises(ValueError):
            with span("boom"):
                raise ValueError("x")
        (ev,) = drain_events()
        assert ev["args"]["error"] == "ValueError"

    def test_save_trace_is_perfetto_loadable_json(self, tmp_path):
        enable_tracing()
        with span("a"):
            with span("b"):
                pass
        out = tmp_path / "trace.json"
        payload = save_trace(out)
        loaded = json.loads(out.read_text())
        assert loaded == json.loads(json.dumps(payload))
        names = [e["name"] for e in loaded["traceEvents"]]
        assert "process_name" in names  # track-naming metadata event
        assert span_counts(loaded["traceEvents"]) == {"a": 1, "b": 1}


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_lowering_is_spanned():
    from repro.frontend import lower_model

    enable_tracing()
    try:
        lower_model("deepseek_v3_671b", seq=64, phase="decode", reduced=True)
    finally:
        disable_tracing()
    spans = [e for e in drain_events() if e["name"] == "frontend.lower"]
    assert len(spans) == 1
    assert spans[0]["args"] == {"model": "deepseek-v3-smoke",
                                "phase": "decode", "seq": 64}


class TestMetrics:
    def test_counter_gauge_histogram(self):
        r = Registry()
        r.counter("c").inc()
        r.counter("c").inc(2)
        r.gauge("g").set(3.0)
        r.gauge("g").set(1.0)
        r.histogram("h").observe(2.0)
        r.histogram("h").observe(4.0)
        s = r.snapshot()
        assert s["counters"] == {"c": 3}
        assert s["gauges"] == {"g": {"value": 1.0, "max": 3.0}}
        assert s["histograms"]["h"] == {"count": 2, "sum": 6.0, "mean": 3.0,
                                        "min": 2.0, "max": 4.0}

    def test_disabled_registry_is_noop(self):
        set_metrics_enabled(False)
        assert not metrics_enabled()
        METRICS.counter("x").inc(5)
        METRICS.gauge("y").set(1.0)
        METRICS.histogram("z").observe(1.0)
        assert METRICS.snapshot() == {"counters": {}, "gauges": {},
                                      "histograms": {}}

    def test_merge_is_order_invariant(self):
        snaps = []
        for vals in ((1, 5.0), (2, 3.0)):
            r = Registry()
            r.counter("c").inc(vals[0])
            r.gauge("g").set(vals[1])
            r.histogram("h").observe(vals[1])
            snaps.append(r.drain())
            assert r.snapshot()["counters"] == {}  # drain resets
        for order in (snaps, snaps[::-1]):
            parent = Registry()
            for s in order:
                parent.merge(s)
            s = parent.snapshot()
            assert s["counters"] == {"c": 3}
            assert s["gauges"]["g"]["max"] == 5.0
            assert s["histograms"]["h"]["count"] == 2
            assert s["histograms"]["h"]["max"] == 5.0

    def test_gauge_and_histogram_types(self):
        assert isinstance(METRICS.gauge("a"), Gauge)
        assert isinstance(METRICS.histogram("b"), Histogram)


# ---------------------------------------------------------------------------
# worker-pool merge determinism
# ---------------------------------------------------------------------------

def _tiny_sweep(workers: int):
    zoo = {"gemma_7b": lower_config(get_config("gemma_7b", reduced=True),
                                    seq=64)}
    ev = Evaluator(zoo=zoo, cache=MappingCache())
    result = run_search(SPACES["tiny"], ev, strategy="exhaustive",
                        workers=workers)
    return result, span_counts(drain_events()), METRICS.drain()


# spans that fire once per cache-miss solve: their count follows the
# solves, which depend on the pool size like the hit/miss counters
MISS_SPANS = ("mapper_batch.enumerate", "mapper_batch.select",
              "mapper_batch.rescore")


class TestWorkerPoolMerge:
    def test_trace_and_metrics_identical_across_worker_counts(self):
        """The trace skeleton (span name → count) and the worker-count-
        invariant counters of a sweep must not depend on the pool size —
        workers drain their buffers with each result and the parent merges.
        (Cache hit/miss counters legitimately differ: each worker's private
        cache re-solves shapes a sequential run would have cached; so do
        the spans of a solve, which each run must count once per solve.)"""
        enable_tracing()
        r1, spans1, metrics1 = _tiny_sweep(workers=1)
        drain_events()
        r4, spans4, metrics4 = _tiny_sweep(workers=4)
        n = len(list(SPACES["tiny"].enumerate()))
        for spans, metrics in ((spans1, metrics1), (spans4, metrics4)):
            solves = metrics["counters"]["mapper.batch_solves"]
            assert solves > 0
            for name in MISS_SPANS:
                assert spans.pop(name) == solves, name
        assert spans1 == spans4
        assert spans1["dse.evaluate"] == n
        assert spans1["mapper_cache.keys"] >= n  # one per (design, kind)
        assert spans1["dse.exhaustive_search"] == 1
        for key in ("dse.designs_scored", "dse.designs_fused_capable",
                    "dse.designs_unfused"):
            assert metrics1["counters"].get(key) == \
                metrics4["counters"].get(key), key
        assert metrics1["counters"]["dse.designs_scored"] == n
        # and the sweep itself is worker-count deterministic
        assert [e.cycles for e in r1.evals] == [e.cycles for e in r4.evals]

    def test_wall_s_comes_from_the_span(self):
        r, _, _ = _tiny_sweep(workers=1)
        assert r.wall_s > 0.0


# ---------------------------------------------------------------------------
# scoring-engine dispatch phases and host mapping-search spans
# ---------------------------------------------------------------------------

needs_jax = pytest.mark.skipif(not jax_available(),
                               reason="jax runtime not importable")
PHASES = ("mapper_batch.transfer_in", "mapper_batch.device_wait",
          "mapper_batch.copy_out")
GEMM_MENU = [SpatialChoice(("i", "j"), (1, 1), "ij"),
             SpatialChoice(("k", "j"), (1, 1), "jk")]
GEMM_QUERIES = [({"i": 16, "j": 16, "k": 16}, 0.0),
                ({"i": 56, "j": 7, "k": 130}, 4096.0)]


def _solve_both():
    """One per-design JAX solve and one design-batched solve, each
    enumerating its own batch: two ``build_batch`` calls, two solves."""
    wl = W.gemm()
    hw = [HWConfig(n_fus=64, buffer_bytes=kb * 1024) for kb in (64, 512)]
    best_mappings(wl, GEMM_QUERIES, GEMM_MENU, hw[0], engine="jax")
    best_mappings_design(wl, GEMM_QUERIES, GEMM_MENU, hw)


def _inside(inner, outer) -> bool:
    return outer["ts"] <= inner["ts"] and \
        inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


@needs_jax
class TestScoringSpans:
    def test_dispatch_phases_nest_in_each_execute(self, monkeypatch):
        from repro.core import perf_model_jax

        put, copied = [], []
        real = perf_model_jax._execute

        def spy(jax, fn, args, real_rows, padded_rows, *a, **kw):
            put.append(sum(np.asarray(v).nbytes for v in args))
            out = real(jax, fn, args, real_rows, padded_rows, *a, **kw)
            eager = [out[k] for k in perf_model_jax.EAGER_OUTPUTS]
            copied.append(padded_rows * sum(v.dtype.itemsize for v in eager))
            if len(copied) == 2:  # read every output of the second dispatch
                late = [v for k, v in out.items()
                        if k not in perf_model_jax.EAGER_OUTPUTS]
                copied.append(padded_rows *
                              sum(v.dtype.itemsize for v in late))
            return out

        _solve_both()  # compiles outside the traced calls
        METRICS.reset()
        monkeypatch.setattr(perf_model_jax, "_execute", spy)
        enable_tracing()
        _solve_both()
        events = [e for e in drain_events() if e["ph"] == "X"]
        execs = [e for e in events if e["name"] == "mapper_batch.jax_execute"]
        assert len(execs) == 2
        for ex in execs:
            inside = [e for e in events if e["name"] in PHASES
                      and _inside(e, ex)]
            assert [e["name"] for e in inside] == list(PHASES)
            for a, b in zip(inside, inside[1:]):  # in order, disjoint
                assert a["ts"] + a["dur"] <= b["ts"]
        assert span_counts(events) == {
            "mapper_batch.enumerate": 2, "mapper_batch.pack": 2,
            "mapper_batch.jax_execute": 2, **{p: 2 for p in PHASES},
            "mapper_batch.copy_out_late": 6,
            "mapper_batch.select": 2, "mapper_batch.rescore": 2}
        # the host search, packing and late copies lie outside the dispatch
        for e in events:
            if e["name"] not in PHASES + ("mapper_batch.jax_execute",):
                assert not any(_inside(e, x) for x in execs), e["name"]
        c = METRICS.snapshot()["counters"]
        assert c["mapper_batch.h2d_bytes"] == sum(put) > 0
        # bytes actually copied: two scores a dispatch, six outputs late
        assert c["mapper_batch.d2h_bytes"] == sum(copied) > 0
        assert c["mapper_batch.outputs_deferred"] == 12
        assert c["mapper_batch.outputs_fetched_late"] == 6
        assert c["mapper_batch.jax_rows_padded"] >= \
            c["mapper_batch.jax_candidates"] > 0
        assert "mapper_batch.jax_design_points" not in c

    def test_candidates_counted_per_workload_kind(self):
        """Every scored candidate row is counted once in total and once
        under its workload kind (``mapper_batch.jax_candidates.<kind>``)."""
        _solve_both()  # compiles outside the counted calls
        METRICS.reset()
        _solve_both()
        best_mappings(W.attention_qk(), [({"b": 2, "m": 16, "n": 32,
                                           "d": 8}, 0.0)],
                      [SpatialChoice(("m", "n"), (1, 1), "mn")],
                      HWConfig(n_fus=64), engine="jax")
        c = METRICS.snapshot()["counters"]
        kinds = {k: v for k, v in c.items()
                 if k.startswith("mapper_batch.jax_candidates.")}
        assert set(kinds) == {"mapper_batch.jax_candidates.gemm",
                              "mapper_batch.jax_candidates.attention_qk"}
        assert all(v > 0 for v in kinds.values())
        assert sum(kinds.values()) == c["mapper_batch.jax_candidates"]

    def test_annotations_share_the_profiler_clock(self, tmp_path):
        """Recorded spans are annotations of a profiler session too: same
        names and nesting on the host plane, and the program's own times,
        mapped through one marker's offset as the benchmark maps them,
        agree with the annotations within 1 ms."""
        import glob
        import time

        import jax
        from jax.profiler import ProfileData

        _solve_both()
        enable_tracing()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                mark_ns = time.perf_counter_ns()
                _solve_both()
        finally:
            jax.profiler.stop_trace()
        events = [e for e in drain_events() if e["ph"] == "X"]
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        names = {e["name"] for e in events} | {"bench.window"}
        ann = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
               for plane in ProfileData.from_file(path).planes
               if plane.name == "/host:CPU"
               for line in plane.lines for e in line.events
               if e.name in names]
        (mark,) = [a for a in ann if a[0] == "bench.window"]
        ann = sorted(a for a in ann if a[0] != "bench.window")
        off = mark[1] - mark_ns
        mine = sorted((e["name"], e["ts"] * 1e3 + off,
                       (e["ts"] + e["dur"]) * 1e3 + off) for e in events)
        assert [a[0] for a in ann] == [m[0] for m in mine]
        for a, m in zip(sorted(ann, key=lambda x: x[1]),
                        sorted(mine, key=lambda x: x[1])):
            assert a[0] == m[0]
            assert abs(a[1] - m[1]) < 1e6 and abs(a[2] - m[2]) < 1e6, a[0]

        def nesting(spans):
            return sorted((inner[0], outer[0]) for inner in spans
                          for outer in spans if inner is not outer
                          and outer[1] <= inner[1] and inner[2] <= outer[2])

        assert nesting(ann) == nesting(mine)
        assert ("mapper_batch.device_wait", "mapper_batch.jax_execute") \
            in nesting(ann)


# ---------------------------------------------------------------------------
# rtlsim hardware introspection
# ---------------------------------------------------------------------------

def _gemm_rtl(true_sizes=None, vcd=None):
    wl = W.gemm()
    df = build_dataflow(wl, spatial=[("k", 4), ("j", 4)],
                        temporal=[("i", 2), ("j", 2), ("k", 2), ("i", 4)],
                        c=(1, 1), name="gemm-jk")
    adg = generate_adg([(wl, df)], name="tpu")
    dag = codegen(adg)
    run_backend(dag)
    sizes = df.sizes()
    rng = np.random.default_rng(0)
    inputs = {t.name: rng.integers(-4, 5, size=wl.tensor_shape(t, sizes))
              .astype(np.float64) for t in wl.inputs}
    res = simulate_rtl(dag, adg, df.name, inputs, true_sizes=true_sizes,
                       vcd=vcd)
    return res, wl, df


class TestHardwareIntrospection:
    def test_utilization_matches_perf_model(self):
        """Per-cycle useful-MAC accounting in the netlist simulation must
        agree with the closed-form ``true_macs / padded_macs`` utilization
        of :func:`repro.core.perf_model.layer_perf` (ISSUE acceptance: the
        unfused GEMM parity case, within 1%)."""
        ts = {"i": 5, "j": 7, "k": 8}  # padded sizes are i=8, j=8, k=8
        res, wl, df = _gemm_rtl(true_sizes=ts)
        lp = layer_perf(wl, df, HWConfig(n_fus=df.n_fus,
                                         buffer_bytes=128 * 1024),
                        true_sizes=ts)
        assert 0.0 < res.hw["utilization"] < 1.0
        assert res.hw["utilization"] == pytest.approx(lp.utilization,
                                                      rel=0.01)

    def test_full_problem_is_fully_utilized(self):
        res, _, _ = _gemm_rtl()
        assert res.hw["utilization"] == 1.0
        assert all(u == 1.0 for u in res.hw["fu_utilization"])
        assert res.hw["stalls"]["padding"] == 0

    def test_stall_attribution_accounts_every_cycle(self):
        """fill + drain cover exactly the out-of-window FU-cycles, padding
        the in-window cycles on padded iteration points, and the behavioral
        memory model never stalls."""
        ts = {"i": 5, "j": 7, "k": 8}
        res, _, _ = _gemm_rtl(true_sizes=ts)
        hw = res.hw
        n, T, W = hw["n_fus"], hw["active_cycles"], hw["total_cycles"]
        st = hw["stalls"]
        assert st["fill"] + st["drain"] == n * (W - T)
        useful = round(sum(hw["fu_utilization"]) * T)
        assert st["padding"] == n * T - useful
        assert st["memory"] == 0
        assert len(hw["fu_utilization"]) == n
        assert 0.0 < hw["occupancy"] <= 1.0

    def test_fifo_occupancy_reported(self):
        res, _, _ = _gemm_rtl()
        for rec in res.hw["fifo_occupancy"].values():
            assert 0 <= rec["high_water"] <= rec["capacity"]

    def test_rtlsim_metrics(self):
        _gemm_rtl()
        snap = METRICS.snapshot()
        assert snap["counters"]["rtlsim.runs"] == 1
        assert snap["histograms"]["rtlsim.cycles"]["count"] == 1


# ---------------------------------------------------------------------------
# VCD waveforms
# ---------------------------------------------------------------------------

def tiny_wave_text() -> str:
    """The golden tiny-netlist waveform (also the generator for
    ``tests/golden/tiny_wave.vcd`` — regenerate with
    ``PYTHONPATH=src:tests python -c
    "import test_obs; test_obs.write_golden()"``).

    Inputs are arange-derived, not RNG-drawn, so the dump is identical on
    any platform/NumPy version."""
    wl = W.gemm()
    df = build_dataflow(wl, spatial=[("k", 2), ("j", 2)],
                        temporal=[("i", 2), ("j", 2), ("k", 2)],
                        c=(1, 1), name="gemm-jk")
    adg = generate_adg([(wl, df)], name="tiny")
    dag = codegen(adg)
    run_backend(dag)
    sizes = df.sizes()
    inputs = {}
    for t in wl.inputs:
        shape = wl.tensor_shape(t, sizes)
        n_el = int(np.prod(shape))
        inputs[t.name] = (np.arange(n_el, dtype=np.float64)
                          .reshape(shape) % 5 - 2)
    writer = VCDWriter(design="tiny")
    simulate_rtl(dag, adg, df.name, inputs, vcd=writer)
    return writer.render()


def write_golden() -> None:
    with open(GOLDEN, "w") as f:
        f.write(tiny_wave_text())


class TestVCD:
    def test_change_compression_and_shared_signals(self):
        w = VCDWriter(design="d")
        w.dump_stream("sig a", [1.0, 1.0, 2.0])
        w.advance(3)
        w.dump_stream("sig a", [2.0, 3.0])  # same var across stages
        assert w.n_signals == 1
        text = w.render()
        assert "$var real 64 ! sig_a $end" in text  # sanitized identifier
        body = text.split("$enddefinitions $end\n", 1)[1]
        # t0: initial value; t1 unchanged (compressed); t2: change;
        # t3 (stage 2 start): re-dumped; t4: change; then end-of-dump time
        assert body == "#0\nr1 !\n#2\nr2 !\n#3\nr2 !\n#4\nr3 !\n#5\n"

    def test_deterministic_header(self):
        w = VCDWriter(design="d")
        w.dump_stream("x", [0.5])
        text = w.render()
        assert "$date" not in text and "$version" not in text
        assert "$timescale 1ns $end" in text

    def test_save_roundtrip(self, tmp_path):
        w = VCDWriter(path=tmp_path / "w.vcd", design="d")
        w.dump_stream("x", [1.0, 2.0])
        p = w.save()
        assert open(p).read() == w.render()

    def test_golden_tiny_netlist_snapshot(self):
        """Byte-exact golden diff: the rtlsim VCD dump of a tiny GEMM
        netlist must never change silently (schedule, node naming and
        change-compression are all load-bearing for waveform debugging)."""
        assert os.path.exists(GOLDEN), \
            "golden missing — run tests/test_obs.py:write_golden()"
        assert tiny_wave_text() == open(GOLDEN).read()

    def test_simulate_rtl_writes_path(self, tmp_path):
        out = tmp_path / "wave.vcd"
        res, _, _ = _gemm_rtl(vcd=str(out))
        text = out.read_text()
        assert text.startswith("$comment")
        assert "$enddefinitions $end" in text
        # one $var per simulated node stream
        assert text.count("$var real 64 ") > res.hw["n_fus"]


# ---------------------------------------------------------------------------
# provenance / bench-JSON schema
# ---------------------------------------------------------------------------

class TestProvenance:
    def test_record_shape(self):
        rec = provenance_record(argv=["prog", "--flag"])
        assert rec["schema"] == PROVENANCE_SCHEMA
        for key in ("timestamp_utc", "host", "platform", "python", "numpy"):
            assert key in rec, key
        assert rec["argv"] == ["prog", "--flag"]
        assert rec["timestamp_utc"].endswith("+00:00")

    def test_bench_json_carries_metrics_and_provenance(self, tmp_path):
        e = DesignEval(point=DesignPoint(n_fus=64, buffer_kb=128),
                       cycles=10.0, energy_pj=20.0, area_mm2=1.0,
                       power_mw=5.0, macs=100.0)
        result = SearchResult(space="tiny", strategy="exhaustive",
                              evals=[e], frontier=[e], wall_s=0.1)
        METRICS.counter("dse.designs_scored").inc(1)
        out = tmp_path / "BENCH_dse.json"
        payload = write_bench_json(out, result)
        loaded = json.loads(out.read_text())
        for p in (payload, loaded):
            assert p["provenance"]["schema"] == PROVENANCE_SCHEMA
            assert p["provenance"]["timestamp_utc"]
            assert p["metrics"]["counters"]["dse.designs_scored"] == 1
            assert set(p["metrics"]) == {"counters", "gauges", "histograms"}

    def test_bench_json_accepts_overrides(self, tmp_path):
        e = DesignEval(point=DesignPoint(n_fus=64, buffer_kb=128),
                       cycles=10.0, energy_pj=20.0, area_mm2=1.0,
                       power_mw=5.0, macs=100.0)
        result = SearchResult(space="tiny", strategy="exhaustive",
                              evals=[e], frontier=[e])
        payload = write_bench_json(
            tmp_path / "b.json", result,
            metrics={"counters": {"x": 1}, "gauges": {}, "histograms": {}},
            provenance={"schema": PROVENANCE_SCHEMA, "note": "frozen"})
        assert payload["metrics"]["counters"] == {"x": 1}
        assert payload["provenance"]["note"] == "frozen"
