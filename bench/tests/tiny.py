"""Throwaway benchmark roots at a size the CPU runs in seconds: a
``BENCHMARK.json`` and the data files of one cell, laid out as in the
repository, so the harness finds them by name."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
COST = {"opt_overlap": 1.0, "phase_overlap": 1.0}

CONFIGS = {
    "occ": dict(driver="engine", chips=1, generator="ycsb", records=2000,
                columns=10, ops_per_txn=16, zipf_theta=0.9, lanes=32,
                cc="occ", granularity="fine", backend="jnp",
                guarantee="serializable", cost_model=COST,
                waves_per_chunk=4),
    "mvocc": dict(driver="engine", chips=1, generator="ycsb", records=2000,
                  columns=10, ops_per_txn=16, zipf_theta=0.9, lanes=32,
                  cc="mvocc", mv_depth=4, granularity="fine",
                  backend="jnp", guarantee="serializable", cost_model=COST,
                  waves_per_chunk=4),
    "tpcc": dict(driver="engine", chips=1, generator="tpcc", warehouses=2,
                 scale=0.01, records=8742, slots=64, lanes=16, cc="occ",
                 granularity="fine", backend="pallas",
                 guarantee="serializable", cost_model=COST,
                 waves_per_chunk=4),
    "sharded": dict(driver="sharded", chips=4, generator="ycsb",
                    records=4000, columns=10, ops_per_txn=16,
                    zipf_theta=0.9, lanes_per_chip=16, cc="occ",
                    granularity="fine", backend="jnp",
                    guarantee="serializable", waves_per_chunk=4),
}
TRAFFIC = {"occ": "ycsb-a", "mvocc": "ycsb-a", "tpcc": "tpcc-nps",
           "sharded": "ycsb-a"}


def make_root(root: Path, config: dict, traffic: str, *,
              mixes: dict | None = None, metrics: dict | None = None,
              per_layer: list | None = None) -> str:
    """Write a root with one cell ``t.<traffic>`` of configuration ``t``;
    ``mixes``/``metrics`` add files (name -> dict / source) beside the
    repository's own.  Returns the cell's name."""
    (root / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    shutil.copytree(REPO / "bench" / "mixes", root / "bench" / "mixes",
                    dirs_exist_ok=True)
    shutil.copytree(REPO / "bench" / "metrics", root / "bench" / "metrics",
                    dirs_exist_ok=True)
    for name, mix in (mixes or {}).items():
        (root / "bench" / "mixes" / f"{name}.json").write_text(
            json.dumps(mix))
    for name, src in (metrics or {}).items():
        (root / "bench" / "metrics" / f"{name}.py").write_text(src)
    (root / "bench" / "configs" / "t.json").write_text(
        json.dumps(dict(config, name="t")))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = f"t.{traffic}"
    bench["configs"] = [{"name": "t", "source": "test",
                         "file": "bench/configs/t.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": cell, "config": "t", "traffic": traffic,
                           "chips": config["chips"], "why": "test"}]
    bench["per_layer"] += per_layer or []
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell
