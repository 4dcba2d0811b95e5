"""Design-space exploration CLI: sweep candidate accelerators over the model
zoo, print the Pareto frontier, dump ``BENCH_dse.json`` — or, with
``--models``, run the paper's cross-model study ("one generated architecture
for diverse modern foundation models") and dump ``BENCH_models.json`` with a
single cross-model winner design.

Run:  python benchmarks/dse.py --space small
      python benchmarks/dse.py --space large --workers 4
      python benchmarks/dse.py --models all --quick

Model configs lower through the graph frontend (:mod:`repro.frontend`):
attention (incl. GQA/MQA and sliding windows), MoE experts, SSM scans as
real depthwise convs, RWKV mixes, encoder-decoder cross-attention and
vision/audio conv stems, with ``--phases prefill,decode`` scoring both the
throughput-bound prefill pass and the latency-bound decode step.  In
``--models`` mode every zoo entry is also scored on the Gemmini baseline
and the winner maximizes the geometric-mean speedup across models.

Layer mappings are solved by the batched NumPy engine (all candidates of a
layer batch in one broadcasted perf-kernel pass) and ``--workers N`` fans
independent design evaluations across a process pool, so even a cold large
sweep (hundreds of designs × multiple sequence lengths) finishes in seconds.
``--engine jax`` swaps the scoring pass for the AOT-compiled XLA kernels
(:mod:`repro.core.perf_model_jax`); selection and all reported numbers stay
on the NumPy path, so the frontier is byte-identical across engines — the
``scripts/check.sh`` engine-parity gate holds ``--engine numpy`` and
``--engine jax`` to the same artifact.  The chosen engine (and jax version)
is stamped into the ``provenance`` section of the output JSON, and
``engine_bench`` in the meta section records a micro-benchmark of the
candidate fan-out on every available engine.
``--seq`` accepts a comma list (e.g. ``--seq 512,4096``) to score several
prefill lengths in one sweep; ``--space large`` defaults to ``512,4096``.

``--design-batch`` goes one axis further: the sweep is tiled along the
*design* axis (:mod:`repro.dse.batch_sweep`) and every mapping search is
solved for a whole tile of designs in one ``(D, C)`` XLA dispatch — same
frontier, byte for byte, an order of magnitude less mapping-solve time
(the measured speedup lands in ``meta.engine_bench.design_batch``).  For
spaces too big to enumerate at all (``--space huge``, ~10⁵ raw points),
``--strategy evolve --budget N --seed S`` runs the guided
tournament+mutation search with a cheap single-entry prefilter; the same
seed visits the same designs and reproduces the same frontier.

Re-runs hit the persistent mapping cache (``.dse_mapping_cache.json`` next to
the output file by default) and skip the mapper entirely for already-seen
(design, layer) pairs — worker-computed entries merge back on join.
``--dry-run`` validates arguments and lowers the zoo, prints the sweep plan,
and exits before any mapping search (used by ``scripts/docs_examples.py``).

Sweeps are crash-safe (see ``docs/ROBUSTNESS.md``): evaluations run under a
supervised worker pool with per-task timeouts (``--task-timeout``), bounded
retries (``--max-retries``) and poison-point quarantine, and every completed
evaluation checkpoints to a run ledger next to the output file.  A sweep
killed mid-run (Ctrl-C, SIGTERM, OOM-kill) leaves a partial artifact with
``"partial": true`` plus the ledger; ``--resume`` restarts it evaluating
only the missing points.  ``--inject-faults SPEC`` (or the ``REPRO_FAULTS``
env var) arms the deterministic fault-injection harness — e.g.
``--inject-faults crash=1,hang=1,corrupt=1`` — whose injected sweep must
produce a frontier bit-identical to the clean run (the ``scripts/check.sh``
robustness gate).
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# JAX's persistent compile cache when JAX_COMPILATION_CACHE_DIR is unset: a
# fixed path in the checkout, so a later run finds it again (.gitignore'd)
COMPILE_CACHE_DIR = os.path.join(_ROOT, ".jax_cache")
for p in (_ROOT, os.path.join(_ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from repro.configs import ARCH_IDS, resolve_ids
from repro.dse import (Evaluator, FaultPlan, MappingCache, RunLedger,
                       SPACES, Supervisor, SupervisorConfig,
                       corrupt_cache_file, format_frontier, format_models,
                       format_scorecard, format_serving, load_zoo,
                       pareto_frontier, parse_fault_spec, plan_from_env,
                       run_search, write_bench_json, write_models_json)
from repro.dse.evaluate import DEFAULT_ZOO
from repro.dse.search import SearchResult
from repro.frontend import PHASES
from repro.obs import (add_verbosity_flag, configure, enable_tracing,
                       save_trace, set_metrics_enabled)


def emit_frontier_rtl(result, out_dir: str) -> dict:
    """Emit one structural-Verilog netlist per wiring class on the frontier.

    Every frontier design belongs to one of three dataflow sets
    (``os``/``ws``/``switch``); each set is realized by a generated demo ADG
    (:data:`benchmarks.designs.SET_TO_DESIGN`), so a sweep ends in
    inspectable, simulable hardware instead of a dict of statistics."""
    from benchmarks.designs import SET_TO_DESIGN, build_design
    from repro.core.dag import codegen
    from repro.core.emit import build_netlist
    from repro.core.passes import run_backend

    os.makedirs(out_dir, exist_ok=True)
    artifacts: dict[str, str] = {}
    for ds in sorted({e.point.dataflow_set for e in result.frontier}):
        design = SET_TO_DESIGN[ds]
        t0 = time.perf_counter()
        dag = codegen(build_design(design))
        run_backend(dag)
        nl = build_netlist(dag)
        text = nl.verilog()
        path = os.path.join(out_dir, f"{design}.v")
        with open(path, "w") as f:
            f.write(text)
        st = nl.stats(text)
        artifacts[ds] = path
        print(f"  emitted {path} ({st['instances']} instances, "
              f"{st['lines']} lines) in {time.perf_counter()-t0:.1f}s")
    return artifacts


def engine_microbench(repeats: int = 5, design_axis: bool = False) -> dict:
    """Time the per-batch candidate fan-out on every available engine.

    One representative mapping batch (a transformer-ish GEMM fan-out) is
    built once, then scored through ``evaluate_batch`` per engine:
    ``numpy`` reports the median wall time, ``jax`` reports the cold
    dispatch (compile + execute) and the warm median separately — the
    compile-vs-execute split that decides when the XLA engine pays off.
    With ``design_axis`` (and jax present) a second section sweeps the
    mapping solve for every design of the ``large`` space — the current
    per-design loop versus the tiled ``(D, C)`` design-axis dispatches —
    and records the speedup ``--design-batch`` buys at the engine level.
    Recorded under ``meta["engine_bench"]`` in ``BENCH_dse.json``.
    """
    import statistics

    from repro.core import workload as W
    from repro.core.mapper import SpatialChoice
    from repro.core.mapper_batch import build_batch, evaluate_batch
    from repro.core.perf_model import HWConfig
    from repro.core.perf_model_jax import clear_compile_cache, jax_available

    wl = W.gemm()
    hw = HWConfig(n_fus=256)
    sps = [SpatialChoice(("i", "j"), (1, 1), "ij"),
           SpatialChoice(("k", "j"), (1, 1), "jk")]
    d = 2048
    dims_list = [{"i": s, "j": j, "k": d}
                 for s in (256, 512, 1024) for j in (d, 3 * d, 4 * d)]
    ppu_list = [0.0] * len(dims_list)
    batch = build_batch(wl, dims_list, sps, hw)

    def timed(engine, n):
        ts = []
        for _ in range(n):
            t = time.perf_counter()
            evaluate_batch(batch, hw, dims_list, ppu_list, engine=engine)
            ts.append(time.perf_counter() - t)
        return ts

    out = {"workload": wl.name, "layers": len(dims_list),
           "candidates": batch.n_candidates, "engines": {}}
    out["engines"]["numpy"] = {
        "warm_ms": statistics.median(timed("numpy", repeats)) * 1e3}
    if jax_available():
        clear_compile_cache()
        cold = timed("jax", 1)[0]
        out["engines"]["jax"] = {
            "cold_ms": cold * 1e3,
            "warm_ms": statistics.median(timed("jax", repeats)) * 1e3}
        if design_axis:
            out["design_batch"] = _design_axis_bench(
                wl, sps, dims_list, ppu_list, repeats)
    return out


def _design_axis_bench(wl, sps, dims_list, ppu_list,
                       repeats: int, space_name: str = "large") -> dict:
    """Mapping-solve wall clock over every design of one space: the
    per-design ``best_mappings`` loop (NumPy engine — today's default —
    and warm per-design JAX dispatches) against the tiled design-axis
    ``best_mappings_design`` path.  ``speedup_vs_numpy_loop`` is the
    acceptance number for ``--design-batch``."""
    import statistics

    from repro.core.mapper_batch import (best_mappings, best_mappings_design,
                                         build_batch)
    from repro.core.perf_model_jax import clear_compile_cache
    from repro.dse.batch_sweep import DEFAULT_TILE, plan_tiles
    from repro.dse.space import SPACES

    points = list(SPACES[space_name].enumerate())
    queries = [(dims, ppu) for dims, ppu in zip(dims_list, ppu_list)]
    tiles = plan_tiles(points, d_tile=DEFAULT_TILE)
    # one candidate batch per FU count (enumeration only depends on the
    # design through n_fus); pad every tile to the widest (C, L) so a
    # single compiled kernel serves the whole sweep
    batches = {}
    for tile in tiles:
        if tile[0].n_fus not in batches:
            batches[tile[0].n_fus] = build_batch(
                wl, dims_list, sps, tile[0].hw_config())
    min_c = max(b.n_candidates for b in batches.values())
    min_l = max(b.loop_size.shape[1] for b in batches.values())

    def loop(engine):
        t = time.perf_counter()
        for p in points:
            best_mappings(wl, queries, sps, p.hw_config(), engine=engine)
        return time.perf_counter() - t

    def batched():
        t = time.perf_counter()
        for tile in tiles:
            best_mappings_design(
                wl, queries, sps, [p.hw_config() for p in tile],
                min_c=min_c, min_l=min_l, min_d=DEFAULT_TILE,
                batch=batches[tile[0].n_fus])
        return time.perf_counter() - t

    loop_numpy_s = loop("numpy")
    loop("jax")                      # warm the per-design kernel shapes
    loop_jax_s = loop("jax")
    clear_compile_cache()
    cold_s = batched()
    warm_s = statistics.median(batched() for _ in range(max(1, repeats - 2)))
    return {"space": space_name, "designs": len(points),
            "tiles": len(tiles), "d_tile": DEFAULT_TILE,
            "layers": len(dims_list),
            "loop_numpy_ms": loop_numpy_s * 1e3,
            "loop_jax_warm_ms": loop_jax_s * 1e3,
            "batched_cold_ms": cold_s * 1e3,
            "batched_warm_ms": warm_s * 1e3,
            "speedup_vs_numpy_loop": loop_numpy_s / warm_s,
            "speedup_vs_jax_loop": loop_jax_s / warm_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--space", default=None, choices=sorted(SPACES),
                    help="design space (default: small; tiny with --quick)")
    ap.add_argument("--configs", default=",".join(DEFAULT_ZOO),
                    help="comma-separated repro.configs ids")
    ap.add_argument("--models", default=None, metavar="IDS",
                    help="cross-model mode: 'all' or a comma list of "
                         "repro.configs ids — scores a Gemmini baseline per "
                         "model and writes BENCH_models.json with the "
                         "one-architecture winner (overrides --configs)")
    ap.add_argument("--phases", default=None,
                    help="execution phases to lower, comma list of "
                         "prefill/decode (default: prefill; --models "
                         "defaults to prefill,decode unless --quick)")
    ap.add_argument("--nets", default="",
                    help="also score benchmarks.nn_workloads networks "
                         "(comma-separated, e.g. MobileNetV2,ResNet50) — "
                         "conv workloads make fused dataflow sets earn "
                         "their mux area")
    ap.add_argument("--seq", default=None,
                    help="prefill sequence length(s) to score, comma list "
                         "(default: 512; 512,4096 for --space large; 256 "
                         "with --quick)")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--reduced", action="store_true",
                    help="use smoke() configs instead of full()")
    ap.add_argument("--quick", action="store_true",
                    help="sub-minute smoke sweep: tiny space, seq 256, "
                         "prefill only (the check.sh cross-model budget)")
    ap.add_argument("--dry-run", action="store_true",
                    help="validate args + lower the zoo, print the sweep "
                         "plan, exit before searching")
    ap.add_argument("--strategy", default="auto",
                    choices=["auto", "exhaustive", "evolutionary", "evolve"],
                    help="search strategy: 'exhaustive' enumerates, "
                         "'evolve' is the guided tournament+mutation loop "
                         "for big spaces (--budget/--seed), 'evolutionary' "
                         "is the legacy generational GA; 'auto' picks "
                         "exhaustive up to --max-exhaustive raw points, "
                         "evolve beyond")
    ap.add_argument("--budget", type=int, default=64,
                    help="evolve: full-evaluation budget — total designs "
                         "scored, ledger-resumed points included "
                         "(default 64)")
    ap.add_argument("--seed", type=int, default=0,
                    help="evolve/evolutionary RNG seed; the same seed "
                         "visits the same designs and yields the same "
                         "frontier (default 0)")
    ap.add_argument("--design-batch", action="store_true",
                    help="exhaustive sweeps only: solve mapping searches a "
                         "design-tile at a time through the AOT JAX "
                         "kernels — one (D, C) dispatch per tile instead "
                         "of a per-design loop (needs the jax runtime; "
                         "frontier stays byte-identical to a per-design "
                         "--engine numpy sweep)")
    ap.add_argument("--d-tile", type=int, default=32, metavar="D",
                    help="--design-batch: designs per tile, pow2-bucketed "
                         "into the compiled (D, C) dispatch shape "
                         "(default 32)")
    ap.add_argument("--snapshot-every", type=int, default=1, metavar="N",
                    help="--design-batch: checkpoint the frontier-so-far "
                         "into the run ledger every N tiles (default 1)")
    ap.add_argument("--workers", type=int, default=1,
                    help="process-pool fan-out for design evaluations")
    ap.add_argument("--resume", action="store_true",
                    help="resume an interrupted sweep from its run ledger: "
                         "already-completed points are adopted, only the "
                         "missing ones evaluate")
    ap.add_argument("--ledger", default=None, metavar="PATH",
                    help="run-ledger checkpoint file "
                         "(default: <out>.ledger)")
    ap.add_argument("--task-timeout", type=float, default=120.0,
                    metavar="S",
                    help="per-evaluation timeout with workers>1: a worker "
                         "past it is killed and the point retried "
                         "(0 disables; default 120)")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="failures per design point before it is "
                         "quarantined as a failure stub (default 2)")
    ap.add_argument("--inject-faults", default=None, metavar="SPEC",
                    help="deterministic fault injection, e.g. "
                         "'crash=1,hang=1,transient=1,corrupt=1,seed=3' "
                         "(also: kill_after=N, hang_s=S); falls back to "
                         "the REPRO_FAULTS env var; see docs/ROBUSTNESS.md")
    ap.add_argument("--max-exhaustive", type=int, default=512,
                    help="auto strategy: exhaustive up to this many raw "
                         "points, evolutionary beyond")
    ap.add_argument("--objective", default="cycles",
                    choices=["cycles", "energy", "edp", "serving"],
                    help="per-layer mapping-search objective; 'serving' "
                         "replays a synthetic traffic trace against every "
                         "design (repro.serve.sim) and ranks the frontier "
                         "by goodput-under-SLO instead of static cycles")
    ap.add_argument("--trace-spec", default=None, metavar="SPEC",
                    help="serving traffic mix, e.g. 'seed=0,requests=64,"
                         "rate=0.25,models=gemma_7b:2;rwkv6_7b:1,"
                         "prompt=64:256,output=16:64' (see docs/SERVING.md; "
                         "models default to the swept configs, requests "
                         "default to 16 with --quick else 64)")
    ap.add_argument("--slo-ms", default="30000:1500", metavar="TTFT:TPOT",
                    help="serving SLO bounds in ms — time-to-first-token : "
                         "time-per-output-token (default 30000:1500)")
    ap.add_argument("--kv-gb", type=float, default=4.0, metavar="GB",
                    help="KV-cache capacity modeled by the serving "
                         "simulator (default 4.0 GiB)")
    ap.add_argument("--engine", default="numpy",
                    choices=["numpy", "jax", "scalar"],
                    help="mapping-search scoring engine (results are "
                         "byte-identical across engines; 'jax' needs the "
                         "jax runtime, 'scalar' is the slow reference)")
    ap.add_argument("--engine-bench", action="store_true",
                    help="micro-benchmark the candidate fan-out on every "
                         "available engine and record it in the output "
                         "meta (implied by --engine jax)")
    ap.add_argument("--emit-dir", default=None, metavar="DIR",
                    help="emit the frontier designs' wiring classes as "
                         "structural Verilog into DIR; BENCH_dse.json "
                         "frontier entries gain an 'rtl' artifact path")
    ap.add_argument("--out", default=None,
                    help="output JSON (default: BENCH_dse.json, or "
                         "BENCH_models.json with --models)")
    ap.add_argument("--cache-path", default=None,
                    help="mapping-cache JSON (default: next to --out)")
    ap.add_argument("--no-cache", action="store_true",
                    help="disable the persistent mapping cache")
    ap.add_argument("--top", type=int, default=12,
                    help="scorecard rows to print")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write a Chrome trace-event JSON of the sweep "
                         "(load in https://ui.perfetto.dev or "
                         "chrome://tracing); covers process-pool workers")
    ap.add_argument("--no-metrics", action="store_true",
                    help="disable the hot-path metrics registry (the bench "
                         "JSON 'metrics' section comes out empty)")
    ap.add_argument("-q", "--quiet", action="store_true")
    add_verbosity_flag(ap)
    args = ap.parse_args(argv)
    configure(args.verbose)
    set_metrics_enabled(not args.no_metrics)
    if args.trace:
        enable_tracing()

    t0 = time.perf_counter()
    if args.engine == "jax" and args.workers > 1:
        ap.error("--engine jax scores in one process (worker processes "
                 "cannot share the parent's device); drop --workers")
    # provenance stamp: which engine scored this sweep, under which jax, on
    # which device — so perf trajectories across PRs/artifacts stay
    # attributable.  jax is only probed when actually requested: plain
    # NumPy sweeps (and their worker processes) must stay jax-free.
    jax_version = device = None
    if args.engine == "jax" or args.engine_bench or args.design_batch:
        from repro.core.perf_model_jax import (device_record, jax_available,
                                               use_compile_cache)
        if jax_available():
            import jax as _jax_mod
            jax_version = _jax_mod.__version__
            cache_dir = use_compile_cache(COMPILE_CACHE_DIR)
            device = device_record()
            print(f"  jax {jax_version} on {device['count']}x "
                  f"{device['platform']} ({device['kind']}); compile cache "
                  f"{cache_dir}")
        elif args.engine == "jax":
            ap.error("--engine jax: the jax runtime is not importable in "
                     "this environment; use --engine numpy")
        elif args.design_batch:
            ap.error("--design-batch needs the jax runtime (the design "
                     "axis is an XLA vmap); drop the flag for a "
                     "per-design sweep")
    if args.design_batch and args.strategy not in ("auto", "exhaustive"):
        ap.error("--design-batch is an exhaustive-sweep orchestrator; "
                 "use --strategy auto or exhaustive (guided search wants "
                 "--strategy evolve instead)")
    if args.d_tile < 1:
        ap.error(f"--d-tile expects a positive tile size, got "
                 f"{args.d_tile}")
    if args.budget < 1:
        ap.error(f"--budget expects a positive evaluation count, got "
                 f"{args.budget}")
    space = SPACES[args.space or ("tiny" if args.quick else "small")]
    if args.models:
        try:
            configs = resolve_ids(args.models)
        except KeyError as e:
            ap.error(str(e.args[0]))
    else:
        configs = [c for c in args.configs.split(",") if c]
    if args.phases is None:
        args.phases = ("prefill,decode" if args.models and not args.quick
                       else "prefill")
    phases = tuple(dict.fromkeys(p for p in args.phases.split(",") if p))
    if not phases or any(p not in PHASES for p in phases):
        ap.error(f"--phases expects a comma list of {'/'.join(PHASES)}, "
                 f"got {args.phases!r}")
    if args.seq is None:
        args.seq = ("256" if args.quick
                    else "512,4096" if space.name == "large" else "512")
    try:
        seqs = list(dict.fromkeys(int(s) for s in args.seq.split(",") if s))
    except ValueError:
        ap.error(f"--seq expects a comma list of ints, got {args.seq!r}")
    if not seqs or any(s <= 0 for s in seqs):
        ap.error(f"--seq expects positive lengths, got {args.seq!r}")
    # --objective serving: the mapping search still optimizes cycles per
    # layer; the *design ranking* comes from the traffic-trace replay
    serving_spec = None
    map_objective = args.objective
    if args.objective == "serving":
        from repro.serve import SLO, ServingSpec, parse_trace_spec
        map_objective = "cycles"
        text = (args.trace_spec if args.trace_spec is not None
                else f"requests={16 if args.quick else 64}")
        try:
            trace_spec = parse_trace_spec(text, default_models=configs)
        except ValueError as e:
            ap.error(f"--trace-spec: {e}")
        bad = [m for m, _ in trace_spec.models if m not in ARCH_IDS]
        if bad:
            ap.error(f"--trace-spec names unknown configs {bad}; "
                     f"known ids: {', '.join(ARCH_IDS)}")
        parts = args.slo_ms.split(":")
        try:
            ttft, tpot = ((float(parts[0]), float(parts[1]))
                          if len(parts) == 2 else (None, None))
        except ValueError:
            ttft = tpot = None
        if ttft is None or ttft <= 0 or tpot <= 0:
            ap.error(f"--slo-ms expects 'TTFT:TPOT' in positive ms, got "
                     f"{args.slo_ms!r}")
        serving_spec = ServingSpec(
            trace=trace_spec, slo=SLO(ttft_ms=ttft, tpot_ms=tpot),
            kv_capacity_bytes=int(args.kv_gb * (1 << 30)),
            reduced=args.reduced)
    elif args.trace_spec is not None:
        ap.error("--trace-spec requires --objective serving")
    out = args.out or os.path.join(
        _ROOT, "BENCH_models.json" if args.models else "BENCH_dse.json")
    log = (lambda m: None) if args.quiet else (
        lambda m: print(f"  {m}", flush=True))

    mode = "cross-model study" if args.models else "DSE sweep"
    print(f"== {mode}: space={space.name} ({space.raw_size} raw points), "
          f"zoo={configs}, seq={seqs}, phases={list(phases)} ==")
    zoo = {}
    for seq in seqs:
        try:
            part = load_zoo(configs, seq=seq, batch=args.batch,
                            reduced=args.reduced, phases=phases)
        except ModuleNotFoundError as e:
            ap.error(f"unknown config in --configs ({e.name}); "
                     f"known ids: {', '.join(ARCH_IDS)}")
        for k, v in part.items():
            zoo[k if len(seqs) == 1 else f"{k}@s{seq}"] = v
    if args.nets:
        from benchmarks.nn_workloads import NETWORKS
        for net in args.nets.split(","):
            if net not in NETWORKS:
                ap.error(f"unknown net {net!r}; known: "
                         f"{', '.join(sorted(NETWORKS))}")
            zoo[net] = NETWORKS[net]()
    n_layers = sum(len(v) for v in zoo.values())
    print(f"  lowered {len(zoo)} configs -> {n_layers} unique layer shapes")

    if args.dry_run:
        print(f"  dry run: would sweep {space.raw_size} raw design points "
              f"(strategy={args.strategy}, workers={args.workers}) and "
              f"write {out}")
        return 0

    try:
        plan = (parse_fault_spec(args.inject_faults) if args.inject_faults
                else plan_from_env() or FaultPlan())
    except ValueError as e:
        ap.error(str(e))
    if plan.active:
        print(f"  fault injection armed: {plan.spec()}")

    cache_path = None
    if not args.no_cache:
        cache_path = args.cache_path or os.path.join(
            os.path.dirname(os.path.abspath(out)),
            ".dse_mapping_cache.json")
    if plan.corrupt and cache_path and os.path.exists(cache_path):
        hit = corrupt_cache_file(cache_path, plan.corrupt, plan.seed)
        print(f"  fault injection: corrupted {hit} mapping-cache "
              f"entries in {cache_path}")
    cache = MappingCache(cache_path)
    if len(cache):
        print(f"  mapping cache: {len(cache)} entries from {cache_path}")

    # run ledger: checkpoint of completed evaluations, keyed to this exact
    # sweep so --resume can never splice two different configurations
    run_key = {"space": space.name, "configs": configs, "seqs": seqs,
               "batch": args.batch, "phases": list(phases),
               "objective": args.objective, "nets": args.nets,
               "models": bool(args.models),
               "strategy": args.strategy, "budget": args.budget,
               "seed": args.seed,
               "serving": (serving_spec.as_dict() if serving_spec
                           else None)}
    ledger = RunLedger(args.ledger or out + ".ledger", run_key=run_key)
    completed = {}
    if args.resume:
        loaded = ledger.load()
        completed = ledger.completed_evals()
        cache.merge(ledger.cache_entries())
        print(f"  resume: adopted {len(completed)} completed evaluations "
              f"from {ledger.path}" if loaded else
              f"  resume: no usable ledger at {ledger.path} — full sweep")

    evaluator = Evaluator(zoo=zoo, cache=cache, objective=map_objective,
                          baseline="gemmini" if args.models else None,
                          engine=args.engine, serving=serving_spec)
    if serving_spec is not None:
        print(f"  serving: trace '{serving_spec.trace.spec()}', SLO "
              f"ttft<={serving_spec.slo.ttft_ms:g}ms "
              f"tpot<={serving_spec.slo.tpot_ms:g}ms, "
              f"KV {args.kv_gb:g} GiB")
    if args.models:
        # baselines depend only on the zoo — score them once in the parent
        # (workers recompute lazily from the same zoo, deterministically)
        evaluator.baselines

    sup = Supervisor(
        evaluator, workers=args.workers,
        cfg=SupervisorConfig(
            task_timeout_s=args.task_timeout if args.task_timeout > 0
            else None,
            max_retries=args.max_retries),
        fault_plan=plan if plan.active else None,
        ledger=ledger, completed=completed)
    meta = {"configs": configs, "seqs": seqs, "batch": args.batch,
            "phases": list(phases), "objective": args.objective,
            "serving": serving_spec.as_dict() if serving_spec else None,
            "engine": args.engine,
            "design_batch": bool(args.design_batch),
            "budget": args.budget, "seed": args.seed,
            "workers": args.workers, "ledger": ledger.path,
            "resume": bool(args.resume),
            "faults": plan.spec() if plan.active else None}
    from repro.obs import provenance_record
    provenance = provenance_record(
        extra={"engine": args.engine, "jax": jax_version, "device": device,
               "strategy": args.strategy, "seed": args.seed,
               "budget": args.budget,
               "design_batch": bool(args.design_batch)})

    # a SIGTERM (e.g. an OOM-killer sibling or batch-system preemption)
    # takes the same checkpoint path as Ctrl-C
    signal.signal(signal.SIGTERM,
                  lambda s, f: (_ for _ in ()).throw(KeyboardInterrupt()))
    try:
        if args.design_batch:
            from repro.dse.batch_sweep import batch_sweep
            result = batch_sweep(space, evaluator, workers=args.workers,
                                 supervisor=sup, log=log,
                                 d_tile=args.d_tile,
                                 snapshot_every=args.snapshot_every)
        else:
            # seed/budget only reach the strategies that take them; 'auto'
            # may resolve to evolve, where run_search forwards them
            kw = ({"budget": args.budget, "seed": args.seed}
                  if args.strategy in ("auto", "evolve")
                  else {"seed": args.seed}
                  if args.strategy == "evolutionary" else {})
            result = run_search(space, evaluator, strategy=args.strategy,
                                log=log, workers=args.workers,
                                supervisor=sup,
                                max_exhaustive=args.max_exhaustive, **kw)
    except KeyboardInterrupt:
        # the supervisor already flushed the ledger on its way out; leave a
        # partial artifact instead of dying with nothing
        evals = ledger.evals()
        partial = SearchResult(
            space=space.name, strategy=args.strategy, evals=evals,
            frontier=pareto_frontier(evals),
            wall_s=time.perf_counter() - t0, cache_stats=cache.stats,
            supervisor=dict(sup.stats))
        meta["partial"] = True
        meta["total_wall_s"] = time.perf_counter() - t0
        write_bench_json(out, partial, meta=meta, partial=True,
                         provenance=provenance)
        cache.save()
        if args.trace:
            save_trace(args.trace)
        print(f"\ninterrupted after {len(evals)} evaluations — partial "
              f"artifact {out} + ledger {ledger.path}; rerun with "
              f"--resume to finish", flush=True)
        return 130
    cache.save()

    print()
    print(format_scorecard(result.evals, limit=args.top))
    print()
    print(format_frontier(result))
    if args.models:
        print()
        print(format_models(result))
    if serving_spec is not None:
        print()
        print(format_serving(result))

    artifacts = None
    if args.emit_dir:
        artifacts = emit_frontier_rtl(result, args.emit_dir)

    wall = time.perf_counter() - t0
    meta.update({"strategy": result.strategy, "total_wall_s": wall,
                 "supervisor": dict(sup.stats)})
    if args.engine == "jax" or args.engine_bench or args.design_batch:
        # the design-axis section re-sweeps the large space at the engine
        # level (~10s) — keep it out of the --quick gate budget
        meta["engine_bench"] = engine_microbench(
            design_axis=args.design_batch and not args.quick)
        if not args.quiet:
            for name, row in meta["engine_bench"]["engines"].items():
                print(f"  engine_bench {name}: "
                      + ", ".join(f"{k}={v:.3f}" for k, v in row.items()))
            db = meta["engine_bench"].get("design_batch")
            if db:
                print(f"  engine_bench design_batch: {db['designs']} "
                      f"designs/{db['tiles']} tiles — numpy loop "
                      f"{db['loop_numpy_ms']:.0f}ms, jax loop "
                      f"{db['loop_jax_warm_ms']:.0f}ms, batched warm "
                      f"{db['batched_warm_ms']:.0f}ms "
                      f"({db['speedup_vs_numpy_loop']:.1f}x vs numpy "
                      f"loop)")
    if args.models:
        write_models_json(out, result, model_ids=configs,
                          baselines=evaluator.baselines, meta=meta,
                          artifacts=artifacts, provenance=provenance)
    else:
        write_bench_json(out, result, meta=meta, artifacts=artifacts,
                         provenance=provenance)
    if args.trace:
        payload = save_trace(args.trace)
        print(f"  trace: {len(payload['traceEvents'])} events -> "
              f"{args.trace}")
    cs = result.cache_stats
    ss = result.supervisor
    extra = "".join(
        f"; {k}={ss[k]}" for k in ("resumed", "retries", "respawns",
                                   "quarantined", "timeouts") if ss.get(k))
    print(f"\nswept {result.n_designs} designs x {len(zoo)} configs in "
          f"{wall:.1f}s (workers={args.workers}; mapper cache: "
          f"{cs['hits']} hits / {cs['misses']} misses{extra}); wrote {out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
