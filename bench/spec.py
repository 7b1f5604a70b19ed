"""What one cell is: its entry in BENCHMARK.json, its configuration file,
its traffic file, the reference module its configuration names, and the
table of peaks.  Everything is found by name, so a later cell,
configuration or metric is a new file and a new entry.

A configuration file may name its reference module, ``"reference":
"<name>"``: ``bench/<name>.py``, dots for subdirectories.  With no such
key it is ``model``, the dense stack.  The harness reaches the model only
through that module (``reference``), or through the module that made the
``Dims`` it holds (``reference_of``), and never names one.  A reference
module provides:

  Dims.of(model, override)   the sizes from the file's ``model`` block
                             (``override``: the ``--reduced`` ones);
                             frozen and hashable, with at least
                             ``layers``, ``d``, ``vocab``, ``vocab_rows``
  make_weights(dm, key)      every weight, in the program's parameter
                             tree, in one jitted call
  forward(w, tokens, *, dm, quant=None)
                             float32 logits of every position;
                             ``quant="fp8"`` is the control
  program_fields(dm)         the program's ``ModelConfig`` fields the file
                             fixes (checked before serving)
  set_fields(dm, reduced)    the fields set on the program's config: those
                             the file states over it, and at ``--reduced``
                             the rehearsal's sizes
  param_count, weight_bytes, kv_bytes_per_position, decode_token_flops,
  prefill_flops, decode_step_bytes
                             the work counts ``work.py`` answers with

So a new architecture joins the benchmark as new files: its configuration
file, its reference module where no existing one covers its layers, a
traffic file where no mix fits, and its entries in BENCHMARK.json.  The
room is for the first stack that is not dense, DeepSeek-V2-Lite: latent
attention, 64 routed experts (6 a token) with 2 shared, and one leading
dense layer.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

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


def reference(config: dict):
    """The reference module the configuration file names, imported once
    under that name (``model`` where it names none)."""
    name = config.get("reference", "model")
    if name not in sys.modules:
        path = os.path.join(BENCH_DIR, *name.split(".")) + ".py"
        if not os.path.isfile(path):
            raise SpecError(f"no reference module {name!r} ({path})")
        loader = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(loader)
        sys.modules[name] = module
        try:
            loader.loader.exec_module(module)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def reference_of(dims):
    """The reference module that made ``dims``."""
    return sys.modules[type(dims).__module__]
