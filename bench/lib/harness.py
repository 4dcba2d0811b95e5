"""One run of one cell: set-up, measured window, metrics, correctness."""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

from .cell import BENCH_DIR, ROOT, load_cell


class NoDevice(RuntimeError):
    """JAX found no accelerator of the kind the benchmark measures."""


def _log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _device(cell, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    rec = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if not require_tpu:
        return rec
    if rec["platform"] != "tpu":
        raise NoDevice(f"JAX finds no TPU (platform {rec['platform']!r})")
    if rec["count"] < cell.chips:
        raise NoDevice(f"the cell needs {cell.chips} chips, JAX finds "
                       f"{rec['count']}")
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if rec["kind"] not in peaks:
        raise NoDevice(f"device kind {rec['kind']!r} is not in peaks.json")
    return rec


def _compile_cache() -> str:
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def _memory_peak(n: int) -> int:
    import jax

    peak = 0
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _counters(snap: dict) -> dict:
    out = dict(snap["counters"])
    for k, h in snap["histograms"].items():
        out[k + ".sum"] = h.get("sum", 0.0)
    return out


def _delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def _span_sums(events, lo_us: float, hi_us: float) -> dict:
    out: dict[str, float] = {}
    for e in events:
        if e.get("ph") == "X" and e["ts"] >= lo_us and e["ts"] + e["dur"] <= hi_us:
            out[e["name"]] = out.get(e["name"], 0.0) + e["dur"] / 1e6
    return out


def _reader(name: str):
    path = os.path.join(BENCH_DIR, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_layer_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _limits() -> dict:
    with open(os.path.join(BENCH_DIR, "limits.json")) as f:
        return json.load(f)["limits"]


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, control: bool = False, require_tpu: bool = True,
             bench_json: str | None = None,
             traffic_dir: str | None = None) -> dict:
    """Set up, measure, check.  Returns the result object of the run's last
    line.  ``require_tpu=False`` (tests) skips the look for a chip."""
    cell = load_cell(workload, bench_json, traffic_dir)
    traffic, mode = cell.traffic, cell.traffic["mode"]
    t_attach = time.perf_counter()
    device = _device(cell, require_tpu)
    t_attached = time.perf_counter()

    import jax
    from repro.obs import (METRICS, disable_tracing, drain_events,
                           enable_tracing)

    from . import check, drive
    from .capture import Capture
    from .cell import lower

    cache_dir = _compile_cache()
    _log(f"{workload}: jax {jax.__version__} on {device['count']}x "
         f"{device['kind']}, compile cache {cache_dir}")
    warmup, window = drive.MODES[mode]
    t_jax = time.perf_counter()
    zoo = lower(cell.config, traffic)
    t_lower = time.perf_counter()
    snap0 = _counters(METRICS.snapshot())
    warmup(zoo, traffic)
    gc.collect()
    snap1 = _counters(METRICS.snapshot())
    setup_s = time.perf_counter() - t_start
    setup_compile = _delta(snap0, snap1)
    _log(f"set-up {setup_s:.2f}s: start {t_attach - t_start:.2f}s, JAX and "
         f"device {t_attached - t_attach:.2f}s, imports and compile cache "
         f"{t_jax - t_attached:.2f}s, lowering {t_lower - t_jax:.2f}s, warm-up "
         f"{t_start + setup_s - t_lower:.2f}s with "
         f"{setup_compile.get('mapper_batch.jax_compiles', 0):.0f} compiles "
         f"in {setup_compile.get('mapper_batch.jax_compile_s.sum', 0):.2f}s")

    cap = Capture(seed, traffic["check"]["dispatches_per_kind"])
    cap.install()
    prof_dir = None
    if trace:
        enable_tracing()
        drain_events()
        prof_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1      # annotations only: a small trace
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
    try:
        if trace:
            with jax.profiler.TraceAnnotation("bench.window"):
                mark_ns = time.perf_counter_ns()
                stats = window(zoo, traffic, seed, seconds)
        else:
            stats = window(zoo, traffic, seed, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
            events = drain_events()
            disable_tracing()
        cap.uninstall()
    snap2 = _counters(METRICS.snapshot())
    in_window = _delta(snap1, snap2)
    memory_peak = _memory_peak(cell.chips)
    designs = len(stats.evals)
    compiles = int(in_window.get("mapper_batch.jax_compiles", 0))
    _log(f"window {stats.window_s:.2f}s, {stats.units} {mode} units, "
         f"{designs} designs, {compiles} compiles, "
         f"{int(in_window.get('mapper_batch.jax_dispatches', 0))} dispatches")

    rate_name = traffic["rate_metric"]
    metrics: dict = {}
    breakdown = None
    if not trace:
        values = {rate_name: designs / stats.window_s, "setup_s": setup_s}
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise KeyError(f"{workload} cannot report {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        from . import trace_reduce as tr

        xp = tr.find_xplane(prof_dir)
        red = None
        if xp is not None:
            t = tr.load_xplane(xp)
            if t["mark"] is not None and t["devices"]:
                off = t["mark"][0] - mark_ns  # profiler clock - host clock
                spans = [(e["name"], e["ts"] * 1e3 + off,
                          (e["ts"] + e["dur"]) * 1e3 + off)
                         for e in events if e.get("ph") == "X"]
                win_lo = stats.t0 * 1e9 + off
                win_hi = stats.t1 * 1e9 + off
                red = tr.reduce(t, spans, win_lo, win_hi)
        shutil.rmtree(prof_dir, ignore_errors=True)
        ctx = {"designs": designs, "window_s": stats.window_s,
               "spans": _span_sums(events, stats.t0 * 1e6, stats.t1 * 1e6),
               "counters": in_window, "setup": setup_compile,
               "device": red}
        for m in cell.per_layer:
            v = _reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
    device["memory_peak_bytes"] = memory_peak

    # the program's state is gone; the reference runs after the window
    records = cap.records()
    cap = None
    gc.collect()
    t_ref = time.perf_counter()
    cmp = check.compare(records, stats, zoo, traffic, seed, control=control)
    _log(f"reference check {time.perf_counter() - t_ref:.2f}s over "
         f"{cmp['dispatches_checked']} dispatches and "
         f"{cmp['designs_checked']} designs; parts {cmp['parts']}")

    limits = _limits()
    numbers = {"score_gap": cmp["score_gap"], "answer_gap": cmp["answer_gap"],
               "window_compiles": compiles}
    limits = dict(limits, window_compiles=0)
    failed = sum(1 for e in stats.evals if e.failed)
    correct = (all(numbers[k] <= limits[k] for k in numbers)
               and cmp["dispatches_checked"] > 0 and designs > 0
               and failed == 0)
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in numbers}
    out = {"correct": correct, "attempted": designs, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
