"""Plain reference of the design-space scorer the benchmark checks against.

It imports nothing of the program under test.  It restates, in straight
NumPy, what a design sweep computes for one design point:

1. the hardware parameters that follow from the design axes (FU count,
   buffer, DRAM bandwidth, dataflow set);
2. every mapping candidate of a layer (spatial factorization x loop order
   x two-level tile split), in the program's documented enumeration order;
3. the analytic latency/energy model of each candidate;
4. the best candidate per layer (least cycles, then least energy, first
   enumerated on a tie);
5. the design's scorecard over a lowered model zoo: per-model sums, the
   score-stationary attention credit, the unfused comparison, area and
   power;
6. the Pareto frontier over (cycles, energy, area).

``dtype`` selects the float precision of steps 3 and 5.  float64 is the
precision the configuration states; float32 is the control, the precision
a faster path would be tempted to use.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import numpy as np

NO_TRUE_SIZE = np.int64(2 ** 62)

# -- workloads: iteration dims and one access matrix per tensor -----------
# (tensor name, role, rows) where each row lists (iteration dim, coeff)


def _tensor(name, role, rows, dims):
    M = np.zeros((len(rows), len(dims)), dtype=np.int64)
    for r, terms in enumerate(rows):
        for d, coeff in terms:
            M[r, dims.index(d)] += coeff
    return name, role, M


def _workload(dims, tensors):
    return {"dims": dims,
            "tensors": [_tensor(n, r, rows, dims) for n, r, rows in tensors]}


def _one(*names):
    return [[(n, 1)] for n in names]


WORKLOADS = {
    "gemm": _workload(("i", "j", "k"), [
        ("Y", "output", _one("i", "j")),
        ("X", "input", _one("i", "k")),
        ("W", "input", _one("k", "j"))]),
    "conv2d": _workload(("n", "oc", "ic", "oh", "ow", "kh", "kw"), [
        ("Y", "output", _one("n", "oc", "oh", "ow")),
        ("X", "input", _one("n", "ic") + [[("oh", 1), ("kh", 1)],
                                          [("ow", 1), ("kw", 1)]]),
        ("W", "input", _one("oc", "ic", "kh", "kw"))]),
    "dwconv2d": _workload(("n", "c", "oh", "ow", "kh", "kw"), [
        ("Y", "output", _one("n", "c", "oh", "ow")),
        ("X", "input", _one("n", "c") + [[("oh", 1), ("kh", 1)],
                                         [("ow", 1), ("kw", 1)]]),
        ("W", "input", _one("c", "kh", "kw"))]),
    "attention_qk": _workload(("b", "m", "n", "d"), [
        ("S", "output", _one("b", "m", "n")),
        ("Q", "input", _one("b", "m", "d")),
        ("K", "input", _one("b", "n", "d"))]),
    "attention_pv": _workload(("b", "m", "n", "d"), [
        ("O", "output", _one("b", "m", "d")),
        ("P", "input", _one("b", "m", "n")),
        ("V", "input", _one("b", "n", "d"))]),
}

# lowered row kind -> workload
ROW_WORKLOAD = {"gemm": "gemm", "conv": "conv2d", "dwconv": "dwconv2d",
                "attn_qk": "attention_qk", "attn_pv": "attention_pv"}

# spatial menus per dataflow set: workload -> [parallel dims per choice]
_GEMM2 = [("i", "j"), ("k", "j")]
_CONV2 = [("ow", "oh"), ("ic", "oc")]
_ATTN = [("m", "n"), ("b", "n")]
SPATIAL_MENUS = {
    "os": {"gemm": [("i", "j")], "conv2d": [("ow", "oh")],
           "dwconv2d": [("ow", "oh")]},
    "ws": {"gemm": [("k", "j")], "conv2d": [("ic", "oc")],
           "dwconv2d": [("ow", "oh")]},
    "switch": {"gemm": _GEMM2, "conv2d": _CONV2, "dwconv2d": [("ow", "oh")]},
    "attention_fused": {"gemm": _GEMM2, "conv2d": _CONV2,
                        "dwconv2d": [("ow", "oh")],
                        "attention_qk": _ATTN, "attention_pv": _ATTN},
}

# -- hardware ---------------------------------------------------------------
DRAM_PJ_PER_BYTE = 31.2


@dataclass(frozen=True)
class Hardware:
    n_fus: int
    buffer_bytes: int
    dram_gbps: float
    n_ppus: int
    freq_ghz: float = 1.0
    data_bytes: int = 1
    acc_bytes: int = 4
    e_mac_pj: float = 0.28
    e_reg_pj_per_byte: float = 0.024
    e_ppu_pj: float = 1.1
    static_mw: float = 25.0

    @property
    def bytes_per_cycle(self) -> float:
        return self.dram_gbps / self.freq_ghz


@dataclass(frozen=True)
class Design:
    n_fus: int
    buffer_kb: int
    dram_gbps: float
    dataflow_set: str

    @property
    def hw(self) -> Hardware:
        return Hardware(n_fus=self.n_fus, buffer_bytes=self.buffer_kb * 1024,
                        dram_gbps=float(self.dram_gbps),
                        n_ppus=max(8, self.n_fus // 32))

    @property
    def n_dataflows(self) -> int:
        return max(len(v) for v in SPATIAL_MENUS[self.dataflow_set].values())

    def menu(self, workload: str) -> list[tuple[str, ...]]:
        return SPATIAL_MENUS[self.dataflow_set][workload]

    @property
    def fused_attention(self) -> bool:
        m = SPATIAL_MENUS[self.dataflow_set]
        return "attention_qk" in m and "attention_pv" in m


def sram_read_pj_per_byte(capacity_bytes: int) -> float:
    return 0.125 * float(np.sqrt(max(0.5, capacity_bytes / 1024)))


def data_nodes(n_fus: int, n_tensors: int) -> list[int]:
    """Bank readers per tensor: one edge of an O(sqrt N) array."""
    return [max(1, int(np.sqrt(n_fus)))] * n_tensors


def area_mm2(d: Design) -> float:
    bits = d.buffer_kb * 1024 * 8
    n_ep = max(8, int(np.sqrt(d.n_fus)))
    parts = [d.n_fus * (1150.0 + 280.0 * max(0, d.n_dataflows - 1)),
             bits * 0.62 * (1.0 + 0.06 * np.sqrt(16)),
             n_ep * 128 * 9.0,
             d.hw.n_ppus * 4400.0]
    return sum(parts) / 1e6


def power_mw(d: Design) -> float:
    n_ep = max(8, int(np.sqrt(d.n_fus)))
    parts = [d.n_fus * (0.78 + 0.07 * max(0, d.n_dataflows - 1)),
             sram_read_pj_per_byte(d.buffer_kb * 1024)
             * (4.0 * np.sqrt(d.n_fus)) * 1.0,
             n_ep * 128 * 0.0028 * 0.5 * 1.0,
             d.hw.n_ppus * 1.8 * 0.6]
    return sum(parts)


# -- candidate enumeration ----------------------------------------------------

@functools.lru_cache(maxsize=None)
def factor_pairs(n: int) -> tuple[tuple[int, int], ...]:
    out = []
    for a in range(1, int(np.sqrt(n)) + 1):
        if n % a == 0:
            b = n // a
            if max(a, b) / min(a, b) <= 16:
                out.append((a, b))
                if a != b:
                    out.append((b, a))
    return tuple(out) or ((1, n), (n, 1))


@functools.lru_cache(maxsize=None)
def loop_orders(workload: str) -> tuple[tuple[str, ...], ...]:
    wl = WORKLOADS[workload]
    dims = wl["dims"]
    out_M = next(M for _, role, M in wl["tensors"] if role == "output")
    out_dims = {dims[i] for i in np.nonzero(out_M.any(axis=0))[0]}
    red = [d for d in dims if d not in out_dims]
    keep = [d for d in dims if d in out_dims]
    orders = [keep + red, red + keep]
    if len(keep) > 1:
        orders.append(keep[::-1] + red)
    if len(red) > 1:
        orders.append(keep + red[::-1])
    if red and keep:
        orders.append([keep[0]] + red + keep[1:])
    uniq = []
    for o in orders:
        if o not in uniq:
            uniq.append(o)
    return tuple(tuple(o) for o in uniq[:8])


def _tile_splits(temporal):
    for p, (d, T) in enumerate(temporal):
        for t in sorted({1, T} | {t for t in (2, 4, 8, 16, 32, 64) if t < T}):
            if t <= 1 or t >= T or T % t:
                continue
            yield temporal[:p] + ((d, T // t),) + temporal[p + 1:] + ((d, t),)


def candidates(workload: str, dims: dict, menu, n_fus: int) -> list[tuple]:
    """``(spatial dims, factors, temporal nest)`` of every distinct mapping
    of one layer, first occurrence first."""
    out, seen = [], set()

    def add(c):
        if c in seen:
            return False
        seen.add(c)
        out.append(c)
        return True

    for si, sp in enumerate(menu):
        for facs in factor_pairs(n_fus):
            if len(sp) != len(facs):
                if len(sp) != 1:
                    continue
                facs = (n_fus,)
            if any(d not in dims for d in sp):
                continue
            trips = dict(dims)
            for d, P in zip(sp, facs):
                trips[d] = -(-trips[d] // P)
            for order in loop_orders(workload):
                temporal = tuple((d, trips[d]) for d in order if trips[d] > 1)
                if add((si, sp, facs, temporal)):
                    for split in _tile_splits(temporal):
                        add((si, sp, facs, split))
    return out


def candidate_rows(workload: str, cands: list[tuple]) -> dict:
    """The row encoding of a candidate list (loop dims, trips, spatial
    extents, FU count, fill term)."""
    dims = WORKLOADS[workload]["dims"]
    C = len(cands)
    L = max((len(c[3]) for c in cands), default=0)
    loop_dim = np.full((C, L), -1, dtype=np.int64)
    loop_size = np.ones((C, L), dtype=np.int64)
    S = np.ones((C, len(dims)), dtype=np.int64)
    n_fus = np.empty(C, dtype=np.int64)
    fill = np.empty(C, dtype=np.float64)
    for i, (_, sp, facs, temporal) in enumerate(cands):
        for j, (d, r) in enumerate(temporal):
            loop_dim[i, j] = dims.index(d)
            loop_size[i, j] = r
        for d, P in zip(sp, facs):
            S[i, dims.index(d)] *= P
        n_fus[i] = math.prod(facs)
        fill[i] = float(sum(facs))
    return {"loop_dim": loop_dim, "loop_size": loop_size, "S": S,
            "n_fus": n_fus, "fill": fill}


def true_sizes(workload: str, dims: dict) -> np.ndarray:
    return np.array([dims.get(d, NO_TRUE_SIZE)
                     for d in WORKLOADS[workload]["dims"]], dtype=np.int64)


# -- latency / energy of candidate rows --------------------------------------

def score(workload: str, hw: Hardware, loop_dim, loop_size, S, n_fus, fill,
          true_sz, dn, ppu, dtype=np.float64) -> dict:
    """Cycles, MACs, utilization, DRAM bytes, SRAM reads, energy, PPU
    cycles and the memory-bound flag of every row.  ``true_sz (C, D)``,
    ``dn (T,)`` data nodes per tensor, ``ppu (C,)``."""
    F = np.dtype(dtype).type
    tensors = WORKLOADS[workload]["tensors"]
    C, L = loop_size.shape
    D = S.shape[1]
    if L == 0:
        E = S[:, None, :].copy()
    else:
        onehot = loop_dim[:, :, None] == np.arange(D, dtype=np.int64)
        G = np.where(onehot, loop_size[:, :, None], np.int64(1))
        suffix = np.cumprod(G[:, ::-1, :], axis=1)[:, ::-1, :]
        E = S[:, None, :] * np.concatenate(
            [suffix, np.ones((C, 1, D), dtype=np.int64)], axis=1)
    full = E[:, 0, :]
    padded_macs = np.prod(full, axis=1).astype(F)
    macs = np.prod(np.minimum(true_sz, full), axis=1).astype(F)
    util = macs / padded_macs
    compute = np.prod(loop_size, axis=1).astype(F) + np.asarray(fill, F)

    budget = F(hw.buffer_bytes / len(tensors))
    real = loop_dim >= 0
    replay = np.concatenate(
        [np.ones((C, 1), dtype=np.int64), np.cumprod(loop_size, axis=1)],
        axis=1).astype(F)
    rows = np.arange(C)
    depth = np.arange(L)[None, :]
    dram = np.zeros(C, dtype=F)
    for _, role, M in tensors:
        nbytes = hw.acc_bytes if role == "output" else hw.data_bytes
        hi = np.einsum("rd,cld->clr", np.clip(M, 0, None), E - 1)
        fp = np.prod(hi + 1, axis=2).astype(F) * F(nbytes)
        fits = fp <= budget
        lvl = np.where(fits.any(axis=1), fits.argmax(axis=1), L)
        t = fp[rows, lvl] * replay[rows, lvl]
        if role == "output":
            dep = M.any(axis=0)
            nondep = real & ~dep[np.clip(loop_dim, 0, None)]
            spills = (nondep & (depth < lvl[:, None])).any(axis=1)
            t = t * np.where(spills, F(2.0), F(1.0))
        dram = dram + t
    mem = dram / F(hw.bytes_per_cycle)
    ppu = np.asarray(ppu, dtype=F)
    ppu_cycles = ppu / F(max(1, hw.n_ppus))
    cycles = np.maximum(compute, mem) + ppu_cycles
    sram = np.zeros(C, dtype=F)
    for k, (_, role, _) in enumerate(tensors):
        nbytes = hw.acc_bytes if role == "output" else hw.data_bytes
        sram = sram + compute * np.minimum(dn[k], n_fus).astype(F) * F(nbytes)
    energy = (macs * F(hw.e_mac_pj)
              + F(sram_read_pj_per_byte(hw.buffer_bytes)) * sram
              + F(hw.e_reg_pj_per_byte) * compute * n_fus.astype(F)
              * F(hw.data_bytes)
              + dram * F(DRAM_PJ_PER_BYTE)
              + ppu * F(hw.e_ppu_pj)
              + F(hw.static_mw) * cycles / F(hw.freq_ghz) * F(1e-3))
    return {"cycles": cycles, "macs": macs, "utilization": util,
            "dram_bytes": dram, "sram_reads": sram, "energy_pj": energy,
            "memory_bound": mem > compute, "ppu_cycles": ppu_cycles}


def best_index(cycles, energy, objective: str) -> int:
    if objective == "cycles":
        return int(np.lexsort((energy, cycles))[0])
    if objective == "energy":
        return int(np.lexsort((cycles, energy))[0])
    if objective == "edp":
        return int(np.argmin(cycles * energy))
    raise ValueError(f"unknown objective {objective!r}")


def row_perf(r: dict, i: int) -> dict:
    return {"cycles": float(r["cycles"][i]), "macs": float(r["macs"][i]),
            "utilization": float(r["utilization"][i]),
            "dram_bytes": float(r["dram_bytes"][i]),
            "sram_reads": float(r["sram_reads"][i]),
            "energy_pj": float(r["energy_pj"][i]),
            "memory_bound": bool(r["memory_bound"][i]),
            "ppu_cycles": float(r["ppu_cycles"][i])}


@functools.lru_cache(maxsize=128)
def layer_rows(workload: str, dataflow_set: str, n_fus: int,
               dims: tuple) -> dict:
    """Read-only candidate rows of one layer (``dims`` as sorted items),
    shared by every design of one FU count and dataflow set."""
    rows = candidate_rows(workload, candidates(
        workload, dict(dims), SPATIAL_MENUS[dataflow_set][workload], n_fus))
    for v in rows.values():
        v.flags.writeable = False
    return rows


def best_mapping(workload: str, dims: dict, ppu: float, design: Design,
                 objective: str = "cycles", dtype=np.float64) -> dict:
    """Best candidate's numbers for one layer on one design."""
    hw = design.hw
    rows = layer_rows(workload, design.dataflow_set, hw.n_fus,
                      tuple(sorted(dims.items())))
    C = len(rows["n_fus"])
    T = len(WORKLOADS[workload]["tensors"])
    r = score(workload, hw, rows["loop_dim"], rows["loop_size"], rows["S"],
              rows["n_fus"], rows["fill"],
              np.broadcast_to(true_sizes(workload, dims), (C, rows["S"].shape[1])),
              data_nodes(hw.n_fus, T), np.full(C, float(ppu)), dtype=dtype)
    return row_perf(r, best_index(r["cycles"], r["energy_pj"], objective))


# -- a design's scorecard over a lowered zoo ----------------------------------

def unfuse(rows: list) -> list:
    """Attention pair rows -> one GEMM per head, repeats merged."""
    out = []
    for kind, dims, rep, nt in rows:
        if kind == "attn_qk":
            b = dims["b"]
            out.append(("gemm", {"i": dims["m"], "j": dims["n"],
                                 "k": dims["d"]}, rep * b, nt / b))
        elif kind == "attn_pv":
            b = dims["b"]
            out.append(("gemm", {"i": dims["m"], "j": dims["d"],
                                 "k": dims["n"]}, rep * b, nt / b))
        else:
            out.append((kind, dims, rep, nt))
    merged: dict = {}
    for kind, dims, rep, nt in out:
        key = (kind, tuple(sorted(dims.items())), nt)
        if key in merged:
            merged[key][2] += rep
        else:
            merged[key] = [kind, dict(dims), rep, nt]
    return [tuple(v) for v in merged.values()]


def _credit(p: dict, credit_bytes: float, hw: Hardware) -> dict:
    """``p`` with ``credit_bytes`` of DRAM traffic elided."""
    credit = min(float(credit_bytes), p["dram_bytes"])
    if credit <= 0.0:
        return p
    dram = p["dram_bytes"] - credit
    core = p["cycles"] - p["ppu_cycles"]
    bound = p["memory_bound"]
    if bound:
        compute = p["macs"] / max(p["utilization"], 1e-12) / hw.n_fus
        mem = dram / hw.bytes_per_cycle
        core = min(core, max(compute, mem))
        bound = mem >= compute
    cycles = core + p["ppu_cycles"]
    saved = hw.static_mw * (p["cycles"] - cycles) / hw.freq_ghz * 1e-3
    return dict(p, dram_bytes=dram, cycles=cycles, memory_bound=bound,
                energy_pj=max(0.0, p["energy_pj"] - credit * DRAM_PJ_PER_BYTE
                              - saved))


def model_score(rows: list, design: Design, objective: str, memo: dict,
                dtype=np.float64) -> dict:
    """Sum of one lowered model's rows on ``design``, attention pairs
    credited with the resident score tensor."""
    hw = design.hw
    perfs = []
    for kind, dims, rep, nt in rows:
        wl = ROW_WORKLOAD[kind]
        key = (wl, tuple(sorted(dims.items())), float(nt))
        if key not in memo:
            memo[key] = best_mapping(wl, dims, nt, design, objective, dtype)
        perfs.append(memo[key])
    pending: dict = {}
    for idx, (kind, dims, rep, _) in enumerate(rows):
        key = (tuple(sorted(dims.items())), rep)
        if kind == "attn_qk":
            pending.setdefault(key, []).append(idx)
        elif kind == "attn_pv" and pending.get(key):
            qi = pending[key].pop(0)
            if dims["m"] * dims["n"] * hw.data_bytes > hw.buffer_bytes:
                continue
            n_el = dims["b"] * dims["m"] * dims["n"]
            perfs[qi] = _credit(perfs[qi], n_el * hw.acc_bytes, hw)
            perfs[idx] = _credit(perfs[idx], n_el * hw.data_bytes, hw)
    F = np.dtype(dtype).type
    tot = {"cycles": F(0.0), "energy_pj": F(0.0), "macs": F(0.0)}
    for (_, _, rep, _), p in zip(rows, perfs):
        for k in tot:
            tot[k] = F(tot[k] + F(rep * p[k]))
    return {k: float(v) for k, v in tot.items()}


def evaluate(design: Design, zoo: dict, objective: str = "cycles",
             dtype=np.float64) -> dict:
    """The design's scorecard: totals, area, power, and per model cycles,
    energy, MACs and (attention-capable designs) the fused-attention
    speedup."""
    memo: dict = {}
    fused = design.fused_attention
    F = np.dtype(dtype).type
    per, tot = {}, {"cycles": F(0.0), "energy_pj": F(0.0), "macs": F(0.0)}
    for name, rows in zoo.items():
        s = model_score(rows if fused else unfuse(rows), design, objective,
                        memo, dtype)
        rec = dict(s)
        if fused and any(k in ("attn_qk", "attn_pv") for k, *_ in rows):
            u = model_score(unfuse(rows), design, objective, memo, dtype)
            rec["speedup_fused_attention"] = u["cycles"] / max(1.0,
                                                               s["cycles"])
        per[name] = rec
        for k in tot:
            tot[k] = F(tot[k] + F(s[k]))
    out = {k: float(v) for k, v in tot.items()}
    out.update(area_mm2=float(F(area_mm2(design))),
               power_mw=float(F(power_mw(design))), per_config=per)
    return out


def pareto(points: list[tuple[str, tuple]]) -> list[str]:
    """Names of the non-dominated ``(name, objectives)`` entries, minimizing
    every objective; of identical vectors the first is kept."""
    out = []
    for i, (name, v) in enumerate(points):
        dominated = False
        for j, (_, w) in enumerate(points):
            if j == i:
                continue
            if (all(a <= b for a, b in zip(w, v))
                    and any(a < b for a, b in zip(w, v))) or \
                    (w == v and j < i):
                dominated = True
                break
        if not dominated:
            out.append((v, name))
    return [n for _, n in sorted(out, key=lambda t: t[0])]
