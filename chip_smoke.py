"""Chip smoke test: the design-batched JAX sweep on one TPU, checked
against the NumPy engine.

    python chip_smoke.py [--out-dir DIR]

Runs ``benchmarks/dse.py --space large --design-batch --engine jax`` on the
default zoo (full-width configs, prefill at seq 512 and 4096) in this one
process, twice: cold, then again after dropping the in-process compiled
kernels, so that the second sweep's compiles are read back from JAX's
persistent compilation cache.  A third sweep, ``--engine numpy`` per design
on the host, is the reference: the ``frontier``, ``designs`` and ``best``
sections of both JAX artifacts must be byte-identical to it.  Every sweep
runs with ``--no-cache`` (an in-memory mapping cache), so every mapping
query is solved, and for the JAX sweeps solved on the device.

Exits 1, with no result line, when JAX finds no TPU, when a JAX sweep made
no device dispatch, when any design was retried or quarantined, or when the
artifacts differ.  On success the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Artifacts go to ``--out-dir`` (default ``chiprun_out/chip_smoke``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
SECTIONS = ("frontier", "designs", "best")


class SmokeFailure(Exception):
    pass


def _sweep(dse, name: str, engine: str, out_dir: str) -> dict:
    """One ``benchmarks/dse.py`` sweep in-process; returns its artifact."""
    from repro.obs import METRICS

    out = os.path.join(out_dir, f"BENCH_dse_{name}.json")
    # exhaustive: 'auto' would hand a per-design sweep of this space to the
    # budgeted evolve search, which scores a different set of designs
    argv = ["--space", "large", "--seq", "512,4096", "--engine", engine,
            "--strategy", "exhaustive", "--no-cache", "-q", "--out", out]
    if engine == "jax":
        argv.append("--design-batch")
    METRICS.reset()  # each artifact's metrics section covers its own sweep
    t0 = time.perf_counter()
    rc = dse.main(argv)
    wall = time.perf_counter() - t0
    if rc != 0:
        raise SmokeFailure(f"{name} sweep exited {rc}")
    with open(out) as f:
        art = json.load(f)
    sup = art["supervisor"]
    print(f"chip_smoke: {name}: wall {wall:.1f}s, {art['n_designs']} "
          f"designs, supervisor retries={sup['retries']} "
          f"quarantined={sup['quarantined']}", flush=True)
    if sup["retries"] or sup["quarantined"]:
        raise SmokeFailure(f"{name} sweep retried {sup['retries']} and "
                           f"quarantined {sup['quarantined']} designs")
    return art


def _check_device_work(name: str, art: dict, cache_hits: int) -> None:
    counters = art["metrics"]["counters"]
    hist = art["metrics"]["histograms"]
    compile_s = hist.get("mapper_batch.jax_compile_s", {}).get("sum", 0.0)
    dispatches = int(counters.get("mapper_batch.jax_dispatches", 0))
    prefilled = int(counters.get("dse.prefill_entries", 0))
    print(f"chip_smoke: {name}: jax_compiles "
          f"{int(counters.get('mapper_batch.jax_compiles', 0))} in "
          f"{compile_s:.2f}s (persistent-cache hits {cache_hits}), "
          f"dispatches {dispatches}, candidates scored "
          f"{int(counters.get('mapper_batch.jax_candidates', 0))}, "
          f"design-batched cache entries {prefilled}", flush=True)
    # prefill entries come only from the sweep's own (D, C) dispatches; the
    # engine micro-benchmark that follows a jax sweep dispatches as well
    if dispatches <= 0 or prefilled <= 0:
        raise SmokeFailure(f"{name} sweep made no device dispatch")


def _compare(name: str, art: dict, ref: dict, out_dir: str) -> None:
    bad = [k for k in SECTIONS
           if json.dumps(art[k], sort_keys=True)
           != json.dumps(ref[k], sort_keys=True)]
    if not bad:
        print(f"chip_smoke: {name}: {', '.join(SECTIONS)} byte-identical "
              f"to the numpy engine", flush=True)
        return
    differ = [(a["design"]["name"], a, b)
              for a, b in zip(art["designs"], ref["designs"]) if a != b]
    path = os.path.join(out_dir, f"diff_{name}.json")
    with open(path, "w") as f:
        json.dump({"sections": bad,
                   "designs": [{"name": n, name: a, "numpy": b}
                               for n, a, b in differ]}, f, indent=1)
    raise SmokeFailure(f"{name} differs from the numpy engine in {bad} "
                       f"({len(differ)} designs differ: "
                       f"{[n for n, _, _ in differ[:5]]}...; see {path})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out-dir", default=os.path.join(
        _ROOT, "chiprun_out", "chip_smoke"))
    args = ap.parse_args(argv)

    sys.path.insert(0, _ROOT)
    import jax
    from benchmarks import dse
    from repro.core.perf_model_jax import (clear_compile_cache, device_record,
                                           use_compile_cache)

    device = device_record()
    if device["platform"] != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform "
              f"{device['platform']!r}); nothing was run", file=sys.stderr)
        return 1

    os.makedirs(args.out_dir, exist_ok=True)
    cache_dir = use_compile_cache(dse.COMPILE_CACHE_DIR)
    hits = {"n": 0}

    def count_cache_hits(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits["n"] += 1

    jax.monitoring.register_event_listener(count_cache_hits)
    print(f"chip_smoke: jax {jax.__version__} on {device['count']}x "
          f"{device['kind']}; compile cache {cache_dir}", flush=True)

    t0 = time.perf_counter()
    try:
        arts = {}
        for name in ("jax_cold", "jax_warm"):
            clear_compile_cache()  # the warm sweep compiles from disk
            hits["n"] = 0
            arts[name] = _sweep(dse, name, "jax", args.out_dir)
            _check_device_work(name, arts[name], hits["n"])
        ref = _sweep(dse, "numpy", "numpy", args.out_dir)
        for name, art in arts.items():
            _compare(name, art, ref, args.out_dir)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
