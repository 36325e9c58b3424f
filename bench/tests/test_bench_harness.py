"""The harness end to end on the CPU at a tiny size: each driver runs a
cell through the same code as on the chip, the result has the contract's
shape, cells are found by name, and ``run.py`` refuses to measure
without a TPU."""
import json
import subprocess
import sys

import jax
import pytest

from bench import run, spec, trace_reduce
from bench.tests import tiny
from bench.tests.test_bench_units import PLAIN_TRACE

SEED = 2**31 + 12345        # larger than 32 signed bits hold


def _run(root, name, seconds=1.0, **kw):
    cell = spec.load_cell(name, root)
    return cell, run.run_cell(cell, SEED, seconds, False,
                              jax.devices()[:cell.chips], **kw)


def check_schema(out: dict, names: set):
    assert list(out)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(out)
    assert isinstance(out["correct"], bool)
    assert out["attempted"] > 0 and 0 <= out["failed"] <= out["attempted"]
    assert set(out["metrics"]) == names
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    d = out["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(d)
    for c in out["checks"].values():
        assert {"value", "limit"} <= set(c)
    json.loads(json.dumps(out))


@pytest.mark.parametrize("which", ["occ", "mvocc", "tpcc"])
def test_engine_cell_end_to_end(tmp_path, which):
    name = tiny.make_root(tmp_path, tiny.CONFIGS[which], tiny.TRAFFIC[which])
    _, out = _run(tmp_path, name)
    check_schema(out, {"commits_per_s", "commit_p95_ms", "setup_s"})
    assert out["correct"], out["checks"]
    assert out["checks"]["waves_checked"]["value"] > 0
    assert out["checks"]["compiles_in_window"]["value"] == 0


def test_new_config_mix_and_metric_need_no_harness_edit(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as files
    and entries alone run through the harness as they are."""
    config = dict(tiny.CONFIGS["occ"], records=3000, lanes=16)
    mix = {"name": "ycsb-c-ish", "generator": "ycsb", "write_frac": 0.2,
           "ro_frac": 0.3}
    metric = ("def read(ctx):\n"
              "    c = ctx['counters']\n"
              "    return 100.0 * c['commits'] / c['attempts']\n")
    entry = {"name": "commit_share_pct", "unit": "%", "better": "higher",
             "source": "program_counter", "layer": "concurrency control",
             "moves": "commits_per_s"}
    name = tiny.make_root(tmp_path, config, "ycsb-c-ish",
                          mixes={"ycsb-c-ish": mix},
                          metrics={"commit_share_pct": metric},
                          per_layer=[entry])
    cell, out = _run(tmp_path, name)
    assert out["correct"], out["checks"]
    ctx = {"cell": cell, "trace": trace_reduce.reduce(PLAIN_TRACE),
           "counters": {"commits": 3, "attempts": 4, "aborts": 1},
           "hbm_peak": 819e9, "validate_bytes": None}
    got = run.per_layer_metrics(cell, ctx)
    assert got["commit_share_pct"] == {"value": 75.0, "unit": "%"}
    # Readers that find nothing to read leave their metric out.
    assert "validate_roofline_pct" not in got
    assert got["exchange_exposed_pct"]["value"] == pytest.approx(2.5)
    assert got["validate_share_pct"]["value"] > 0


def test_run_refuses_without_a_tpu(tmp_path):
    """On the CPU run.py exits non-zero and prints no result."""
    proc = subprocess.run(
        [sys.executable, str(tiny.REPO / "bench" / "run.py"), "--workload",
         "tpcc-w8-occ-fine.nps", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=120,
        cwd=tmp_path, env={**__import__("os").environ,
                           "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr
