"""Finds a cell's pieces by name: ``BENCHMARK.json`` at the root, the
configuration file it names, ``bench/mixes/<traffic>.json`` and
``bench/metrics/<name>.py``.  Adding a cell, a configuration, a mix or a
per-layer metric means adding files and entries; nothing here changes."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

from bench.reference import order

#: The checkout root: the directory that holds ``BENCHMARK.json``.
ROOT = Path(__file__).resolve().parent.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict         # the configuration file, as it is run
    mix: dict            # the traffic mix file
    end_to_end: list     # the BENCHMARK.json entries this cell reports
    per_layer: list
    root: Path


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration and mix read from disk."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = json.loads(
        (root / "bench" / "mixes" / f"{w['traffic']}.json").read_text())
    if config.get("guarantee") != "serializable":
        raise ValueError(f"{w['config']}: every configuration states "
                         "guarantee 'serializable'")
    if config.get("priority", order.DEFAULT) not in order.RULES:
        raise ValueError(f"{w['config']}: priority "
                         f"{config['priority']!r} is none of {order.RULES}")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        root=root)


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
