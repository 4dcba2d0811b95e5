"""JAX-jitted port of the perf-model kernels (``engine="jax"``).

The NumPy kernels in :mod:`repro.core.perf_model` score one candidate batch
per Python call; at the ROADMAP's 10⁵–10⁶-design sweep scale the remaining
cost is the per-batch NumPy interpreter overhead and the lost opportunity to
fuse the whole extents → footprint → traffic → perf chain into one compiled
dispatch.  This module re-expresses the same math as a **per-candidate JAX
function vmapped over the candidate axis and then over the design axis**
and AOT-compiles it with ``jax.jit``, so an entire design×mapping×layer
tensor scores in a single XLA dispatch — the
affine-representation-is-just-arrays property the LEGO front end is built
on.

There is one program and one dispatch path: :func:`perf_kernel_jax_design`
scores ``D`` designs × ``C`` candidates, and :func:`perf_kernel_jax`
(one design, ``(C,)`` results) is its ``D = 1`` case, so the sweeps and
the per-design searches run the same compiled ``(design, candidate)``
kernel.

Contract with the NumPy engine (the differential-testing harness in
``tests/test_engine_parity.py`` pins all of this):

* every integer-derived quantity (cycles, MACs, utilization, DRAM bytes,
  SRAM reads, PPU cycles, the memory-bound flag) is **bit-identical** —
  all reductions (``prod``/``cumprod``/``sum``) run in int64 exactly
  like NumPy, and the float steps are elementwise IEEE ops;
* ``energy_pj`` may differ by float-associativity noise (XLA is free to
  contract multiply-adds into FMAs), bounded by :data:`ENERGY_RTOL`;
* selection therefore never trusts JAX floats for the *reported* numbers:
  :mod:`repro.core.mapper_batch` uses the JAX scores only to order
  candidates (host-side stable lexsort, identical code path) and re-scores
  the per-layer winners through the NumPy kernel, so mapping caches,
  scorecards and Pareto frontiers are byte-identical across engines.

JAX is imported lazily and only on first use: DSE worker processes stay
NumPy-only unless ``engine="jax"`` is actually requested, and environments
without jax degrade to a clear error (guard with :func:`jax_available`).
float64 semantics come from the ``jax.enable_x64(True)`` scoped override,
not the global flag, so co-resident float32 Pallas kernels keep
their dtypes.
"""

from __future__ import annotations

import os
import time
from collections.abc import Mapping

import numpy as np

from repro.obs import METRICS, set_annotation_factory, span

from .cost import DRAM_PJ_PER_BYTE, sram_read_pj_per_byte
from .perf_model import HWConfig
from .workload import Workload

__all__ = ["jax_available", "perf_kernel_jax", "perf_kernel_jax_design",
           "design_kernel_program", "ENERGY_RTOL", "clear_compile_cache",
           "use_compile_cache", "device_record", "ENGINES"]

# the engines a mapping query can be solved with ("numpy" is the batched
# default; "scalar" is the reference candidate-at-a-time oracle)
ENGINES = ("numpy", "jax", "scalar")

# tolerance policy for float energies (everything else is exact): XLA may
# contract a*b+c chains into FMAs, so the energy sum can differ from NumPy
# in the last ulps.  1e-9 relative is ~6 orders of magnitude looser than
# observed drift and ~6 tighter than any mapping-relevant energy gap.
ENERGY_RTOL = 1e-9

_jax = None          # module cache: None = not tried, False = unavailable
_COMPILED: dict[tuple, object] = {}


def jax_available() -> bool:
    """True iff the jax runtime can be imported (lazily probed once)."""
    return _import_jax() is not None


def _import_jax():
    global _jax
    if _jax is None:
        try:
            import jax  # deferred: keep NumPy-only processes jax-free
            _jax = jax
            # recorded spans also land in any profiler session's own trace
            set_annotation_factory(jax.profiler.TraceAnnotation)
        except Exception:  # pragma: no cover - environment without jax
            _jax = False
    return _jax or None


def _require_jax():
    jax = _import_jax()
    if jax is None:
        raise RuntimeError(
            "engine='jax' requested but the jax runtime is not importable; "
            "install jax or use engine='numpy'")
    return jax


def use_compile_cache(default_dir: str) -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    stands; otherwise the cache goes to ``default_dir``, a fixed path (the
    path is part of what a later run must find again).  Every compile is
    cached, however short, so the AOT kernel compiles of a repeated sweep
    are read back instead of rebuilt.  Returns the directory in use.
    Called from entry points only, never on import of a library module.
    """
    jax = _require_jax()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", default_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def device_record() -> dict:
    """The device the JAX engine dispatches to, as JAX reports it."""
    jax = _require_jax()
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def clear_compile_cache() -> None:
    """Drop all AOT-compiled kernels (tests / memory pressure)."""
    _COMPILED.clear()


def _bucket_c(c: int) -> int:
    """Pad the candidate axis to the next power of two so the compile cache
    stays O(log batch-size) instead of one entry per candidate count."""
    n = 1
    while n < c:
        n *= 2
    return n


def _bucket_l(length: int) -> int:
    """Pad the temporal-loop axis to a multiple of 4 (padding slots are
    inert by the row encoding: dim -1, size 1)."""
    return max(4, -(-length // 4) * 4)


def _candidate_kernel(jax, Mpos_list, b_list, dep_list, out_mask, L, D):
    """Per-candidate scoring function over the static workload structure.

    Mirrors ``extents_kernel → footprint_kernel → traffic_kernel →
    perf_kernel`` from :mod:`repro.core.perf_model` for one candidate row;
    every reduction stays in int64 so the integer-derived outputs are
    bit-identical to the NumPy engine.  No reduction is a ``dot``: the
    footprint contraction is an elementwise product and a sum, because the
    TPU compiler refuses 64-bit ``dot_general``.
    """
    jnp = jax.numpy
    T = len(Mpos_list)

    def kernel(loop_dim, loop_size, S, n_fus, fill, true_sizes, data_nodes,
               ppu_elements, budget, db, bytes_per_cycle, n_ppus_f,
               e_mac_pj, e_reg_pj_per_byte, e_ppu_pj, static_pj_per_cycle,
               sram_pj_per_byte, data_bytes_f):
        # extents: per-dim iteration extent at every temporal depth (L+1, D)
        onehot = loop_dim[:, None] == jnp.arange(D, dtype=jnp.int64)
        G = jnp.where(onehot, loop_size[:, None], jnp.int64(1))
        suffix = jnp.cumprod(G[::-1, :], axis=0)[::-1, :]
        E = S[None, :] * jnp.concatenate(
            [suffix, jnp.ones((1, D), dtype=jnp.int64)], axis=0)

        sizes_full = E[0, :]
        padded_macs = jnp.prod(sizes_full).astype(jnp.float64)
        true_macs = jnp.prod(
            jnp.minimum(true_sizes, sizes_full)).astype(jnp.float64)
        util = true_macs / padded_macs

        compute_cycles = jnp.prod(loop_size).astype(jnp.float64) + fill

        # traffic per tensor: smallest resident level, replay outside it
        real = loop_dim >= 0
        pre = jnp.concatenate(
            [jnp.ones((1,), dtype=jnp.int64),
             jnp.cumprod(loop_size)]).astype(jnp.float64)
        lvl_of = jnp.arange(L)
        dram_bytes = jnp.float64(0.0)
        sram_reads = jnp.float64(0.0)
        for k in range(T):
            Mpos = jnp.asarray(Mpos_list[k])
            bvec = jnp.asarray(b_list[k])
            # broadcast multiply + integer sum, not an int64 dot_general:
            # the TPU compiler cannot rewrite an s64 dot into 32-bit ops
            mx = (Mpos[None, :, :] * (E - 1)[:, None, :]).sum(axis=2) + bvec
            fp = jnp.prod(mx + 1, axis=1).astype(jnp.float64) * db[k]
            fits = fp <= budget[k]
            lvl = jnp.where(fits.any(), jnp.argmax(fits), L)
            traffic = fp[lvl] * pre[lvl]
            if out_mask[k]:
                dep = jnp.asarray(dep_list[k])
                nondep = real & ~dep[jnp.clip(loop_dim, 0, None)]
                spills = (nondep & (lvl_of < lvl)).any()
                traffic = traffic * jnp.where(spills, 2.0, 1.0)
            dram_bytes = dram_bytes + traffic
            sram_reads = sram_reads + \
                compute_cycles * jnp.minimum(data_nodes[k], n_fus) * db[k]
        mem_cycles = dram_bytes / bytes_per_cycle

        ppu_cycles = ppu_elements / n_ppus_f
        cycles = jnp.maximum(compute_cycles, mem_cycles) + ppu_cycles
        memory_bound = mem_cycles > compute_cycles

        sram_pj = sram_pj_per_byte * sram_reads
        link_pj = e_reg_pj_per_byte * compute_cycles * n_fus * data_bytes_f
        energy = (true_macs * e_mac_pj
                  + sram_pj + link_pj
                  + dram_bytes * DRAM_PJ_PER_BYTE
                  + ppu_elements * e_ppu_pj
                  + static_pj_per_cycle * cycles)
        return {"cycles": cycles, "macs": true_macs, "utilization": util,
                "dram_bytes": dram_bytes, "sram_reads": sram_reads,
                "energy_pj": energy, "memory_bound": memory_bound,
                "ppu_cycles": ppu_cycles}

    return kernel


def _workload_kernel(jax, wl: Workload, L: int):
    """:func:`_candidate_kernel` over ``wl``'s static tensor structure."""
    return _candidate_kernel(
        jax,
        [np.clip(t.fmap.M, 0, None).astype(np.int64) for t in wl.tensors],
        [np.asarray(t.fmap.b, dtype=np.int64) for t in wl.tensors],
        [t.fmap.M.any(axis=0) for t in wl.tensors],
        [t.role == "output" for t in wl.tensors],
        L, len(wl.iter_dims))


def _pad_loops(loop_dim: np.ndarray, loop_size: np.ndarray, Cp: int,
               Lp: int) -> tuple[np.ndarray, np.ndarray]:
    """The ``(Cp, Lp)`` loop rows: padding slots are inert (dim -1, size 1)
    and padded candidate rows replay row 0 (scored, sliced away, never
    win)."""
    C, L = loop_size.shape
    ld = np.full((Cp, Lp), -1, dtype=np.int64)
    ld[:C, :L] = loop_dim
    ls = np.ones((Cp, Lp), dtype=np.int64)
    ls[:C, :L] = loop_size
    if Cp > C:
        ld[C:] = ld[0]
        ls[C:] = ls[0]
    return ld, ls


# the outputs host selection reads: every objective ranks by these two
EAGER_OUTPUTS = ("cycles", "energy_pj")


class KernelOutputs(Mapping):
    """A dispatch's outputs, each sliced to the real rows: the
    :data:`EAGER_OUTPUTS` as host arrays copied by the dispatch, every
    other output still on the device until its first read, which copies it
    (span ``mapper_batch.copy_out_late``) and keeps the host array.  The
    device buffers go with the mapping."""

    def __init__(self, out: dict, rows: tuple):
        self._out = out    # name -> host array (sliced) or device array
        self._rows = rows  # the index of the real rows

    def __getitem__(self, key: str) -> np.ndarray:
        v = self._out[key]
        if not isinstance(v, np.ndarray):
            with span("mapper_batch.copy_out_late", cat="mapper",
                      output=key):
                v = np.asarray(v)
            METRICS.counter("mapper_batch.outputs_fetched_late").inc()
            METRICS.counter("mapper_batch.d2h_bytes").inc(v.nbytes)
            v = self._out[key] = v[self._rows]
        return v

    def __iter__(self):
        return iter(self._out)

    def __len__(self) -> int:
        return len(self._out)


def _execute(jax, fn, args: tuple, real_rows: int, padded_rows: int,
             rows: tuple, workload: str, **span_args) -> KernelOutputs:
    """One warm dispatch of a compiled kernel; returns its outputs indexed
    by ``rows`` (the real rows of the padded outputs).

    ``mapper_batch.jax_execute`` spans the whole of it, in three phases:
    ``transfer_in`` is the compiled call until it returns, that is the
    call's own copy of the host arguments to the device and the launch;
    ``device_wait`` waits for the outputs, so it holds the device work and
    any copy-in still in flight; ``copy_out`` copies the
    :data:`EAGER_OUTPUTS` to the host, both copies issued before either is
    waited for, and leaves the other outputs on the device
    (:class:`KernelOutputs`).  The wait adds nothing to the dispatch, since
    the first copy out would wait as long, and it runs whether or not
    tracing is on, so a traced run executes the same program.
    ``real_rows`` of the ``padded_rows`` scored (design × candidate) rows
    are real; they are counted in total and per ``workload`` kind.
    """
    t0 = time.perf_counter()
    with span("mapper_batch.jax_execute", cat="mapper", workload=workload,
              **span_args), \
            jax.enable_x64(True):  # inside: int64 rows are not narrowed
        with span("mapper_batch.transfer_in", cat="mapper"):
            out = fn(*args)
        with span("mapper_batch.device_wait", cat="mapper"):
            jax.block_until_ready(out)
        with span("mapper_batch.copy_out", cat="mapper"):
            host = jax.device_get({k: out[k] for k in EAGER_OUTPUTS})
    METRICS.counter("mapper_batch.jax_dispatches").inc()
    METRICS.counter("mapper_batch.jax_candidates").inc(real_rows)
    METRICS.counter(f"mapper_batch.jax_candidates.{workload}").inc(real_rows)
    METRICS.counter("mapper_batch.jax_rows_padded").inc(padded_rows)
    METRICS.counter("mapper_batch.h2d_bytes").inc(
        sum(a.nbytes for a in args))
    METRICS.counter("mapper_batch.d2h_bytes").inc(
        sum(v.nbytes for v in host.values()))
    METRICS.counter("mapper_batch.outputs_deferred").inc(
        len(out) - len(host))
    METRICS.histogram("mapper_batch.jax_execute_s").observe(
        time.perf_counter() - t0)
    return KernelOutputs({k: host[k][rows] if k in host else v
                          for k, v in out.items()}, rows)


def _pad_rows(a: np.ndarray, C: int) -> np.ndarray:
    """Pad the candidate axis by repeating row 0 — padded rows are scored
    and discarded, never selected."""
    if a.shape[0] < C:
        a = np.concatenate(
            [a, np.broadcast_to(a[:1], (C - a.shape[0],) + a.shape[1:])],
            axis=0)
    return np.ascontiguousarray(a)


# ---------------------------------------------------------------------------
# one program: a dispatch scores D design points × C candidates
# ---------------------------------------------------------------------------

def design_kernel_program(jax, wl: Workload, Dp: int, C: int, L: int,
                          sharding=None):
    """The jitted ``(design, candidate)`` kernel and its argument shapes.

    Returns ``(jitted, shapes)``: ``jitted.lower(*shapes).compile()`` under
    ``jax.enable_x64(True)`` is the program :func:`_compiled_design_kernel`
    dispatches.  ``sharding`` is placed on every ``ShapeDtypeStruct`` (None:
    the default device), so the same program can be compiled for a
    described, unattached device.

    The outer vmap runs over the design axis with ``in_axes=None`` for every
    candidate array, so the design-invariant chain — extents, footprints,
    compute cycles, true MACs — is traced **once** at ``(C, …)`` shape and
    shared by all D designs; only the footprint-vs-budget selection and the
    energy arithmetic batch to ``(D, C)``.  That work sharing (not
    parallelism) is where the design-batched sweep speedup comes from.
    """
    D = len(wl.iter_dims)
    T = len(wl.tensors)
    kernel = _workload_kernel(jax, wl, L)
    # inner vmap over the candidate axis; HW scalars/vectors broadcast (None)
    per_design = jax.vmap(kernel,
                          in_axes=(0, 0, 0, 0, 0, 0, None, 0,
                                   None, None, None, None, None, None, None,
                                   None, None, None))
    # outer vmap: candidate arrays broadcast (None) so the design-invariant
    # math hoists out of the design axis; only per-design HW rows batch
    batched = jax.vmap(per_design,
                       in_axes=(None, None, None, None, None, None, 0, None,
                                0, 0, 0, 0, 0, 0, 0, 0, 0, 0))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=sharding)

    i64, f64 = np.int64, np.float64
    shapes = (
        sds((C, L), i64), sds((C, L), i64), sds((C, D), i64),
        sds((C,), i64), sds((C,), f64), sds((C, D), i64),
        sds((Dp, T), i64), sds((C,), f64),
        sds((Dp, T), f64), sds((Dp, T), f64), sds((Dp,), f64),
        sds((Dp,), f64), sds((Dp,), f64), sds((Dp,), f64), sds((Dp,), f64),
        sds((Dp,), f64), sds((Dp,), f64), sds((Dp,), f64),
    )
    return jax.jit(batched), shapes


def _compiled_design_kernel(jax, wl: Workload, Dp: int, C: int, L: int):
    """AOT-compiled :func:`design_kernel_program` for the default device.

    HW parameters are runtime arguments, so one compile serves every design
    point that lands on the same bucketed shape; the cache key is
    ``(workload, "design", Dp, Cp, Lp)``.  The compile-vs-execute split is
    observable: ``mapper_batch.jax_compiles`` + the
    ``mapper_batch.jax_compile`` span cover compilation,
    ``mapper_batch.jax_dispatches`` the warm dispatches.
    """
    key = (wl.name, "design", Dp, C, L)
    fn = _COMPILED.get(key)
    if fn is not None:
        return fn
    jitted, shapes = design_kernel_program(jax, wl, Dp, C, L)
    t0 = time.perf_counter()
    with span("mapper_batch.jax_compile", cat="mapper", workload=wl.name,
              designs=Dp, candidates=C, loops=L):
        with jax.enable_x64(True):
            fn = jitted.lower(*shapes).compile()
    METRICS.counter("mapper_batch.jax_compiles").inc()
    METRICS.histogram("mapper_batch.jax_compile_s").observe(
        time.perf_counter() - t0)
    _COMPILED[key] = fn
    return fn


def _hw_rows(hw_list: list[HWConfig], tensors) -> tuple[np.ndarray, ...]:
    """Stack the per-design runtime HW arguments into ``(D, …)`` rows, in
    the exact argument order of :func:`_candidate_kernel`'s HW tail."""
    T = len(tensors)
    budget = np.array([[hw.buffer_bytes / T] * T for hw in hw_list],
                      dtype=np.float64)
    db = np.array([[hw.acc_bytes if t.role == "output" else hw.data_bytes
                    for t in tensors] for hw in hw_list], dtype=np.float64)
    return (
        budget, db,
        np.array([hw.bytes_per_cycle for hw in hw_list], dtype=np.float64),
        np.array([max(1, hw.n_ppus) for hw in hw_list], dtype=np.float64),
        np.array([hw.e_mac_pj for hw in hw_list], dtype=np.float64),
        np.array([hw.e_reg_pj_per_byte for hw in hw_list], dtype=np.float64),
        np.array([hw.e_ppu_pj for hw in hw_list], dtype=np.float64),
        np.array([hw.static_mw / hw.freq_ghz * 1e-3 for hw in hw_list],
                 dtype=np.float64),  # mW·ns = pJ
        np.array([sram_read_pj_per_byte(hw.buffer_bytes) for hw in hw_list],
                 dtype=np.float64),
        np.array([float(hw.data_bytes) for hw in hw_list], dtype=np.float64),
    )


def _dispatch(wl: Workload, hw_list: list[HWConfig], loop_dim, loop_size, S,
              n_fus, fill, true_sizes, data_nodes, ppu_elements, rows: tuple,
              min_c: int, min_l: int, min_d: int) -> Mapping[str, np.ndarray]:
    """Score the candidate rows against every design of ``hw_list`` in one
    dispatch of the ``(design, candidate)`` program: bucket the shapes,
    compile or fetch the program, pack the rows, run it; the outputs are
    indexed by ``rows`` (the real rows of the padded ``(Dp, Cp)``
    outputs)."""
    jax = _require_jax()
    C, L = loop_size.shape
    Dn = len(hw_list)
    if C == 0:  # nothing to score: the NumPy kernel's empty outputs
        from .perf_model import perf_kernel
        empty = perf_kernel(wl, hw_list[0], loop_dim, loop_size, S, n_fus,
                            fill, true_sizes, data_nodes[:0], ppu_elements)
        return {k: np.empty((Dn, 0), dtype=v.dtype)[rows]
                for k, v in empty.items()}
    Cp = _bucket_c(max(C, min_c))
    Lp = _bucket_l(max(L, min_l))
    Dp = _bucket_c(max(Dn, min_d))
    fn = _compiled_design_kernel(jax, wl, Dp, Cp, Lp)

    with span("mapper_batch.pack", cat="mapper"):
        # pad the design axis by repeating design 0 (scored, sliced away)
        hw_rows = tuple(_pad_rows(a, Dp)
                        for a in _hw_rows(hw_list, list(wl.tensors)))
        args = (
            *_pad_loops(loop_dim, loop_size, Cp, Lp),
            _pad_rows(S, Cp), _pad_rows(n_fus, Cp),
            _pad_rows(fill.astype(np.float64), Cp),
            _pad_rows(true_sizes, Cp),
            _pad_rows(np.asarray(data_nodes, dtype=np.int64), Dp),
            _pad_rows(np.asarray(ppu_elements, dtype=np.float64), Cp),
            *hw_rows,
        )
    return _execute(jax, fn, args, Dn * C, Dp * Cp, rows, workload=wl.name,
                    designs=Dn, candidates=C)


def perf_kernel_jax_design(
    wl: Workload,
    hw_list: list[HWConfig],
    loop_dim: np.ndarray,
    loop_size: np.ndarray,
    S: np.ndarray,
    n_fus: np.ndarray,
    fill: np.ndarray,
    true_sizes: np.ndarray,
    data_nodes: np.ndarray,
    ppu_elements: np.ndarray,
    min_c: int = 1,
    min_l: int = 4,
    min_d: int = 1,
) -> Mapping[str, np.ndarray]:
    """Score one candidate batch against **D designs** in one XLA dispatch.

    Candidate arrays are the shared ``(C, …)`` row encoding of
    :func:`repro.core.perf_model.perf_kernel` (all designs must enumerate
    the identical candidate set — callers group designs by ``n_fus``);
    ``data_nodes`` is one ``(D, T)`` row per design.  Returns
    ``(D, C)``-shaped host arrays, in a read-only mapping that copies each
    output but :data:`EAGER_OUTPUTS` from the device on first read
    (:class:`KernelOutputs`).

    ``min_c`` / ``min_l`` / ``min_d`` are bucket floors: a sweep
    orchestrator passes its running per-workload maxima so every tile lands
    on the same padded shape and the first compile serves all tiles.
    """
    C = loop_size.shape[0]
    Dn = len(hw_list)
    assert Dn >= 1 and data_nodes.shape[0] == Dn
    return _dispatch(wl, hw_list, loop_dim, loop_size, S, n_fus, fill,
                     true_sizes, data_nodes, ppu_elements,
                     (slice(Dn), slice(C)), min_c, min_l, min_d)


def perf_kernel_jax(
    wl: Workload,
    hw: HWConfig,
    loop_dim: np.ndarray,
    loop_size: np.ndarray,
    S: np.ndarray,
    n_fus: np.ndarray,
    fill: np.ndarray,
    true_sizes: np.ndarray,
    data_nodes: np.ndarray,
    ppu_elements: np.ndarray,
) -> Mapping[str, np.ndarray]:
    """Drop-in JAX replacement for :func:`repro.core.perf_model.perf_kernel`:
    the one-design case of :func:`perf_kernel_jax_design`.

    Same candidate row encoding, same result keys, ``(C,)`` arrays.
    ``data_nodes`` rows must be identical across the batch (the
    mapper-batch invariant: one data-node vector per query set) — asserted,
    because the program takes one ``(T,)`` row per design.
    """
    assert (data_nodes == data_nodes[:1]).all(), \
        "engine='jax' expects one shared data-node row per batch"
    return _dispatch(wl, [hw], loop_dim, loop_size, S, n_fus, fill,
                     true_sizes, data_nodes[:1], ppu_elements,
                     (0, slice(loop_size.shape[0])), min_c=1, min_l=4,
                     min_d=1)
