"""The comparison that decides ``correct``.

Two numbers, each against the plain reference in ``bench/ref``:

``score_gap``
    the widest relative gap between the raw outputs of the sampled device
    dispatches and the reference's numbers for the same candidate rows on
    the same designs (every output: cycles, MACs, utilization, DRAM bytes,
    SRAM reads, energy, PPU cycles; a flipped memory-bound flag counts 1).
``answer_gap``
    the widest relative gap between what the program answered and what the
    reference answers from scratch: the cycles and energy of the chosen
    mapping of every layer of the sampled dispatches (the reference
    enumerates its own candidates), the scorecard of every frontier
    member and of one seeded design of each design group of every
    completed pass or search, and each frontier against the Pareto set of
    the reference's scorecards over those designs and against the Pareto
    set of the program's own scorecards over all of them (a frontier that
    differs counts 1).

``control=True`` puts the reference computed in float32 in the program's
place, for the control runs that show the limits fail on it.
"""

from __future__ import annotations

import numpy as np

from bench.ref import model as ref

FIELDS = ("cycles", "macs", "utilization", "dram_bytes", "sram_reads",
          "energy_pj", "ppu_cycles")


def rel_gap(a, b) -> float:
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    if a.shape != b.shape:
        return 1.0
    d = np.abs(a - b)
    with np.errstate(divide="ignore", invalid="ignore"):
        g = np.where(b != 0, d / np.abs(b), np.where(d > 0, 1.0, 0.0))
    return float(np.max(g, initial=0.0))


def choice_gap(p: dict, q: dict) -> float:
    """Gap between two chosen mappings in what the choice ranks by: cycles
    and energy.  Candidates tied on both may differ elsewhere; the program
    and the reference may break such a tie differently."""
    return max(rel_gap(p[k], q[k]) for k in ("cycles", "energy_pj"))


def _menu_design(h: dict, kind: str, menu) -> ref.Design:
    """A reference design with the dispatch's hardware whose spatial menu
    for ``kind`` is ``menu``."""
    for ds, menus in ref.SPATIAL_MENUS.items():
        if [tuple(m) for m in menus.get(kind, [])] == [tuple(m) for m in menu]:
            return ref.Design(n_fus=h["n_fus"],
                              buffer_kb=h["buffer_bytes"] // 1024,
                              dram_gbps=h["dram_gbps"], dataflow_set=ds)
    raise ValueError(f"no dataflow set has the {kind} menu {menu}")


def _score_rows(kind, d: ref.Design, rows, dtype):
    T = len(ref.WORKLOADS[kind]["tensors"])
    return ref.score(kind, d.hw, rows["loop_dim"], rows["loop_size"],
                     rows["S"], rows["n_fus"], rows["fill"],
                     rows["true_sizes"], ref.data_nodes(d.hw.n_fus, T),
                     rows["ppu_elements"], dtype=dtype)


def check_dispatch(rec: dict, rng, designs_per_dispatch: int,
                   control: bool) -> tuple[float, float]:
    """(score gap, selection gap) of one sampled dispatch, over a seeded
    subset of its designs (the first always among them)."""
    kind = rec["kind"]
    n = len(rec["designs"])
    pick = sorted({0} | set(rng.choice(n, size=min(n, designs_per_dispatch),
                                       replace=False).tolist()))
    rows = rec["rows"]
    cands = [ref.candidates(kind, dims, rec["menu"], rec["designs"][0]["n_fus"])
             for dims, _ in rec["queries"]]
    own = ref.candidate_rows(kind, [c for cs in cands for c in cs])
    offsets = np.cumsum([0] + [len(c) for c in cands])
    own["true_sizes"] = np.concatenate([
        np.broadcast_to(ref.true_sizes(kind, dims),
                        (len(c), len(ref.WORKLOADS[kind]["dims"])))
        for (dims, _), c in zip(rec["queries"], cands)])
    own["ppu_elements"] = np.concatenate([
        np.full(len(c), p) for (_, p), c in zip(rec["queries"], cands)])
    same_rows = all(np.array_equal(own[k], rows[k]) for k in own)
    score_gap = sel_gap = 0.0
    for di in pick:
        d = _menu_design(rec["designs"][di], kind, rec["menu"])
        r = _score_rows(kind, d, rows, np.float64)
        dev = {k: np.asarray(v[di]) for k, v in rec["out"].items()}
        if control:
            dev = _score_rows(kind, d, rows, np.float32)
        score_gap = max(score_gap, max(rel_gap(dev[k], r[k]) for k in FIELDS),
                        float(np.any(dev["memory_bound"] != r["memory_bound"])))
        mine = r if same_rows else _score_rows(kind, d, own, np.float64)
        for qi, (dims, ppu) in enumerate(rec["queries"]):
            lo, hi = offsets[qi], offsets[qi + 1]
            best = ref.row_perf(mine, lo + ref.best_index(
                mine["cycles"][lo:hi], mine["energy_pj"][lo:hi],
                rec["objective"]))
            got = rec["mappings"][di][qi]
            if control:
                got = ref.best_mapping(kind, dims, ppu, d, rec["objective"],
                                       np.float32)
            sel_gap = max(sel_gap, choice_gap(got, best))
    return score_gap, sel_gap


OBJECTIVES = ("cycles", "energy_pj", "area_mm2")


def eval_gap(e, want: dict, got: dict | None = None) -> float:
    """Gap between a scorecard and the reference's ``want``: the program's
    ``DesignEval`` ``e``, or ``got`` where the control stands in for it."""
    if got is None:
        got = {"cycles": e.cycles, "energy_pj": e.energy_pj, "macs": e.macs,
               "area_mm2": e.area_mm2, "power_mw": e.power_mw,
               "per_config": e.per_config}
    if e.failed or set(got["per_config"]) != set(want["per_config"]):
        return 1.0
    g = max(rel_gap(got[k], want[k])
            for k in ("cycles", "energy_pj", "macs", "area_mm2", "power_mw"))
    for name, w in want["per_config"].items():
        h = got["per_config"][name]
        for k in ("cycles", "energy_pj", "macs", "speedup_fused_attention"):
            if (k in w) != (k in h):
                return 1.0
            if k in w:
                g = max(g, rel_gap(h[k], w[k]))
    return g


def checked_designs(result, rng) -> list:
    """The designs of one pass or search that the reference scores: every
    member of the program's frontier and one seeded design of each (FU
    count, dataflow set) group, in the order the program evaluated them.
    A sweep tile never spans two groups, so every tile has a design here."""
    groups: dict = {}
    for e in result.evals:
        groups.setdefault((e.point.n_fus, e.point.dataflow_set), []).append(e)
    pick = {e.point.name for e in result.frontier}
    for members in groups.values():
        pick.add(members[int(rng.integers(len(members)))].point.name)
    return [e for e in result.evals if e.point.name in pick]


def own_card(e) -> dict:
    """The program's own scorecard of ``e``."""
    return {k: getattr(e, k) for k in OBJECTIVES}


def pareto_names(checked: list, card) -> list[str]:
    """The names of the Pareto set of ``card``'s scorecards over
    ``checked``, sorted.  Every design off a frontier is dominated by a
    frontier member, so over a set that holds the program's whole frontier
    this is that frontier when the program is right: a member that does not
    belong shows here.  One left out shows in the set over every design."""
    return sorted(ref.pareto([(e.point.name,
                               tuple(card(e)[k] for k in OBJECTIVES))
                              for e in checked if not e.failed]))


def compare(records: list[dict], stats, zoo: dict, traffic: dict, seed: int,
            control: bool = False) -> dict:
    """The numbers compared, each with how many answers it covers."""
    chk = traffic["check"]
    rng = np.random.default_rng([seed, 0xC0C0])
    objective = traffic.get("objective", "cycles")
    score = sel = 0.0
    for rec in records:
        s, q = check_dispatch(rec, rng, chk["designs_per_dispatch"], control)
        score, sel = max(score, s), max(sel, q)

    units = [(r, checked_designs(r, rng)) for r in stats.results]
    # score each design once, one (FU count, dataflow set) group after
    # another, so the reference's candidate rows of a group are made once
    todo = {e.point.name: e.point for _, checked in units for e in checked}
    dtypes = (np.float64, np.float32) if control else (np.float64,)
    cards: dict = {}
    for p in sorted(todo.values(),
                    key=lambda p: (p.n_fus, p.dataflow_set, p.name)):
        d = ref.Design(p.n_fus, p.buffer_kb, p.dram_gbps, p.dataflow_set)
        for dt in dtypes:
            cards[p.name, np.dtype(dt).name] = ref.evaluate(d, zoo, objective,
                                                            dt)
    ref.layer_rows.cache_clear()

    def card(e):
        return cards[e.point.name, "float64"]

    def control_card(e):
        return cards[e.point.name, "float32"]

    ev = fr = 0.0
    for r, checked in units:
        for e in checked:
            ev = max(ev, eval_gap(e, card(e),
                                  control_card(e) if control else None))
        got = (pareto_names(checked, control_card) if control
               else sorted(e.point.name for e in r.frontier))
        fr = max(fr, float(got != pareto_names(checked, card)))
        if not control:  # no design left out: the filter over them all
            fr = max(fr, float(got != pareto_names(r.evals, own_card)))
    return {"score_gap": score, "answer_gap": max(sel, ev, fr),
            "dispatches_checked": len(records), "designs_checked": len(todo),
            "parts": {"selection": sel, "scorecard": ev, "frontier": fr}}
