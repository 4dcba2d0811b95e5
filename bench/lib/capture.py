"""Keep a seeded sample of the window's own device dispatches.

The scoring kernel's raw outputs never leave the mapper: winners are
re-scored on the host before anything is reported.  To compare what the
device computed, the harness wraps the two entry points that dispatch
(``best_mappings_design`` for a design tile, ``best_mappings`` for one
design) and the kernels under them, and keeps, per workload kind, a
reservoir sample of dispatches: their candidate rows, the designs they
scored, the raw outputs and the mappings the program chose.  A dispatch
that is not sampled costs one random draw.
"""

from __future__ import annotations

import importlib
import inspect

import numpy as np


def _hw(hw) -> dict:
    return {"n_fus": int(hw.n_fus), "buffer_bytes": int(hw.buffer_bytes),
            "dram_gbps": float(hw.dram_gbps)}


class Capture:
    """Reservoir of ``per_kind`` dispatches for every workload kind."""

    def __init__(self, seed: int, per_kind: int):
        self.rng = np.random.default_rng([seed, 0xCA97])
        self.per_kind = per_kind
        self.seen: dict[str, int] = {}
        self.kept: dict[str, list] = {}
        self._slot = None
        self._saved: list = []

    # -- sampling -----------------------------------------------------------
    def _draw(self, kind: str):
        """A fresh record if this dispatch enters the reservoir, else None."""
        n = self.seen.get(kind, 0) + 1
        self.seen[kind] = n
        kept = self.kept.setdefault(kind, [])
        rec = {"kind": kind}
        if len(kept) < self.per_kind:
            kept.append(rec)
            return rec
        j = int(self.rng.integers(n))
        if j < self.per_kind:
            kept[j] = rec
            return rec
        return None

    def records(self) -> list[dict]:
        return [r for kind in sorted(self.kept) for r in self.kept[kind]
                if "out" in r and "mappings" in r]

    # -- wrappers -----------------------------------------------------------
    def _solver(self, orig, design_axis: bool):
        def solve(wl, queries, spatials, hw, *args, **kwargs):
            rec = self._draw(wl.name)
            self._slot = rec
            try:
                out = orig(wl, queries, spatials, hw, *args, **kwargs)
            finally:
                self._slot = None
            if rec is not None:
                bound = inspect.signature(orig).bind(
                    wl, queries, spatials, hw, *args, **kwargs)
                bound.apply_defaults()
                a = bound.arguments
                rec["queries"] = [(dict(d), float(p)) for d, p in queries]
                rec["menu"] = [tuple(s.dims) for s in spatials]
                rec["objective"] = a["objective"]
                hws = hw if design_axis else [hw]
                rec["designs"] = [_hw(h) for h in hws]
                rows = out if design_axis else [out]
                rec["mappings"] = [[m.perf.as_dict() for m in ms]
                                   for ms in rows]
            return out
        return solve

    def _kernel(self, orig, design_axis: bool):
        sig = inspect.signature(orig)

        def kernel(*args, **kwargs):
            out = orig(*args, **kwargs)
            rec = self._slot
            if rec is not None and "out" not in rec:
                a = sig.bind(*args, **kwargs).arguments
                rec["rows"] = {k: np.array(a[k]) for k in (
                    "loop_dim", "loop_size", "S", "n_fus", "fill",
                    "true_sizes", "ppu_elements")}
                rec["out"] = {k: (v if design_axis else v[None, :])
                              for k, v in out.items()}
            return out
        return kernel

    def install(self) -> None:
        # by module path: ``repro.dse`` re-exports functions under the
        # names of its submodules
        pmj = importlib.import_module("repro.core.perf_model_jax")
        bs = importlib.import_module("repro.dse.batch_sweep")
        cache = importlib.import_module("repro.dse.cache")

        for mod, name, wrap in (
                (bs, "best_mappings_design", self._solver),
                (cache, "best_mappings", self._solver),
                (pmj, "perf_kernel_jax_design", self._kernel),
                (pmj, "perf_kernel_jax", self._kernel)):
            orig = getattr(mod, name)
            self._saved.append((mod, name, orig))
            setattr(mod, name, wrap(orig, name.endswith("design")))

    def uninstall(self) -> None:
        while self._saved:
            mod, name, orig = self._saved.pop()
            setattr(mod, name, orig)
