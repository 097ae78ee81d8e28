"""What a cell is made of, found by name from ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, so that a new one is a new file:

* a configuration is the JSON file that the cell's ``config`` entry names;
* a traffic mix is ``bench/traffic/<traffic>.json``;
* a per-layer metric is ``bench/metrics/<name>.py``, whose ``read(ctx)``
  returns a number, or ``None`` where it found nothing to read.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, NamedTuple

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list   # the BENCHMARK.json entries this cell reports
    per_layer: list


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration and traffic mix read."""
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(name, int(w["chips"]), config, traffic,
                [m for m in spec["end_to_end"] if _applies(m, name)],
                [m for m in spec["per_layer"] if _applies(m, name)])


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    """``read`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


class Context(NamedTuple):
    """What a per-layer metric's ``read`` gets in a ``--trace 1`` run."""

    trace: object        # bench.trace.Trace of the traced slice, or None
    spans: list          # repro.obs span records of the timed call
    config: dict
    traffic: dict
    peak: object         # bench.peaks.Peak of the chip
