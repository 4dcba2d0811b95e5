"""Find a cell's files by name and turn them into the program's inputs.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: it names a
configuration (``configs[].file``) and a traffic mix
(``bench/traffic/<traffic>.json``).  Nothing here is specific to one cell:
a new cell is a new entry and new data files.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's content
    traffic: dict         # the traffic file's content
    end_to_end: list      # BENCHMARK.json end_to_end entries of this cell
    per_layer: list       # BENCHMARK.json per_layer entries of this cell


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, bench_json: str | None = None,
              traffic_dir: str | None = None) -> Cell:
    """The cell ``name`` of ``bench_json`` (default: the repo's
    ``BENCHMARK.json``), its mix read from ``traffic_dir`` (default
    ``bench/traffic``)."""
    bench_json = bench_json or os.path.join(ROOT, "BENCHMARK.json")
    traffic_dir = traffic_dir or os.path.join(BENCH_DIR, "traffic")
    with open(bench_json) as f:
        spec = json.load(f)
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in {bench_json}; known: "
                       f"{', '.join(w['name'] for w in spec['workloads'])}")
    cfg = next(c for c in spec["configs"] if c["name"] == wl["config"])
    with open(os.path.join(os.path.dirname(bench_json), cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(traffic_dir, f"{wl['traffic']}.json")) as f:
        traffic = json.load(f)
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _reports(m, name, names)]
    return Cell(name=name, chips=int(wl["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def model_config(fields: dict):
    """A ``repro`` model configuration from the sizes in a config file."""
    from repro.models.common import BlockSpec, ModelConfig

    fields = dict(fields)
    pattern = tuple(BlockSpec(**b) for b in fields.pop("layer_pattern"))
    return ModelConfig(layer_pattern=pattern, **fields)


def lower(config: dict, traffic: dict) -> dict[str, list]:
    """The zoo the evaluator scores: every model of the configuration,
    lowered at each of the traffic's sequence lengths and phases.  Keys
    follow the sweep CLI: ``<model>[@<phase>][@s<seq>]``."""
    from repro.frontend import lower_model

    seqs, phases = traffic["seqs"], traffic["phases"]
    zoo = {}
    for seq in seqs:
        for mid, m in config["models"].items():
            cfg = model_config(m["model_config"])
            for phase in phases:
                key = mid if len(phases) == 1 else f"{mid}@{phase}"
                if len(seqs) > 1:
                    key = f"{key}@s{seq}"
                zoo[key] = lower_model(cfg, seq=seq,
                                       batch=traffic.get("batch", 1),
                                       phase=phase)
    return zoo


def design_space(traffic: dict):
    """The program's ``DesignSpace`` over the traffic's own axes."""
    from repro.dse.space import DesignSpace

    s = traffic["space"]
    return DesignSpace(
        name=traffic["name"], n_fus=tuple(s["n_fus"]),
        buffer_kb=tuple(s["buffer_kb"]),
        dram_gbps=tuple(float(g) for g in s["dram_gbps"]),
        dataflow_sets=tuple(s["dataflow_sets"]),
        min_buffer_bytes_per_fu=s["min_buffer_bytes_per_fu"],
        max_buffer_bytes_per_fu=s["max_buffer_bytes_per_fu"],
        max_area_mm2=s.get("max_area_mm2"))
