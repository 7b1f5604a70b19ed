"""What one cell is: its entry in BENCHMARK.json, its configuration file,
its traffic file and the table of peaks.  Everything is found by name, so
a later cell, configuration or metric is a new file and a new entry."""
from __future__ import annotations

import dataclasses
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """The cell cannot be run as specified."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file, as run
    traffic: dict         # the traffic file
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _reported_in(metric: dict, cell: str, e2e: set[str]) -> bool:
    """A metric with ``workloads`` is reported in those cells; an
    end-to-end one without, in every cell; a per-layer one without, in
    every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                        f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _reported_in(m, name, set())]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _reported_in(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


def peaks_for(device_kind: str) -> dict:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]
