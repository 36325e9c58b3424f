"""Chip smoke test: the transaction engine's main path on a TPU.

    python chip_smoke.py               # one chip: three phases x two backends
    python chip_smoke.py --four-chips  # the sharded engine on a 4-chip mesh

One chip.  Each phase runs the normal entry point
(``repro.launch.txn_bench.run_grid`` -> ``core/engine.py``) on the ``jnp``
backend and on the ``pallas`` kernels, at the sizes the paper runs:

  a. TPC-C, 8 warehouses at scale 1.0: occ-fine, occ-coarse, tictoc-coarse
     at 128 lanes (the paper's headline comparison);
  b. YCSB at 10M keys (16 ops, 50% writes, Zipf 0.9, 2 column groups):
     occ-fine and mvocc-fine (ring depth 4) at 1024 lanes;
  c. the YCSB scan mix (30% scans of 8 records) at 10M keys: occ-fine at
     128 lanes — iterate_validate's phantom checks.

Each grid runs twice: the first call compiles, the second hits the compiled
program and is timed to the end of its device work.  A phase passes when
the two backends agree exactly in commits, aborts and per-cause aborts,
commits are positive, and the causes sum to the aborts.

Four chips.  ``core/distributed.py`` with occ and mvcc over a 4-shard mesh
of ``jax.devices()``: 256 global lanes x 16 slots over 10M range-sharded
records, at pipeline depth 1 and 2, on both backends.  Every pair — depth
1 vs 2, jnp vs pallas — must agree bit for bit in commits, tables and
stats, and every device must hold a quarter of each table.

Progress goes to stdout as JSON lines; the last line is
``{"ok": true, "device": {...}}``.  Any failure raises, and the script
exits non-zero.  It exits with status 2, printing no result, when JAX finds
no TPU or the ``repro`` package is not beside it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

YCSB_KEYS = 10_000_000
WAVES = 50
#: (name, run_grid arguments, [(ccs, granularities), ...])
PHASES = (
    ("tpcc", dict(workload="tpcc", lanes=[128], scale=1.0),
     [(["occ"], (1, 0)), (["tictoc"], (0,))]),
    ("ycsb", dict(workload="ycsb", lanes=[1024], n_keys=YCSB_KEYS,
                  mv_depth=4),
     [(["occ"], (1,)), (["mvocc"], (1,))]),
    ("scan", dict(workload="ycsb", lanes=[128], n_keys=YCSB_KEYS,
                  scan_frac=0.3, scan_len=8),
     [(["occ"], (1,))]),
)
BACKENDS = ("jnp", "pallas")


def emit(**kw):
    print(json.dumps(kw), flush=True)


def check(cond: bool, what: str):
    if not cond:
        raise AssertionError(what)


def bytes_in_use(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("bytes_in_use", -1))


def run_phase(name: str, args: dict, grids, waves: int, dev) -> dict:
    """Both backends of one phase; returns {backend: [rows]}."""
    from repro.launch.txn_bench import run_grid
    out = {}
    for backend in BACKENDS:
        rows = []
        for ccs, grans in grids:
            kw = dict(args)
            workload, lanes = kw.pop("workload"), kw.pop("lanes")
            t0 = time.perf_counter()
            run_grid(workload, ccs, grans, lanes, waves, backend=backend,
                     **kw)
            first = time.perf_counter() - t0
            t0 = time.perf_counter()
            got = run_grid(workload, ccs, grans, lanes, waves,
                           backend=backend, **kw)
            warm = time.perf_counter() - t0
            emit(phase=name, backend=backend, ccs=ccs, grans=list(grans),
                 compile_s=first - warm, first_s=first, warm_s=warm,
                 device_kind=dev.device_kind,
                 bytes_in_use=bytes_in_use(dev),
                 commits=[r["commits"] for r in got],
                 aborts=[r["aborts"] for r in got])
            rows += got
        out[backend] = rows
    for a, b in zip(out["jnp"], out["pallas"]):
        key = (name, a["cc"], a["granularity"])
        check((a["cc"], a["granularity"]) == (b["cc"], b["granularity"]),
              f"{key}: backends ran different grids")
        check((a["commits"], a["aborts"], a["abort_causes"])
              == (b["commits"], b["aborts"], b["abort_causes"]),
              f"{key}: jnp and pallas disagree: {a['commits']}/"
              f"{a['aborts']}/{a['abort_causes']} vs {b['commits']}/"
              f"{b['aborts']}/{b['abort_causes']}")
        check(a["commits"] > 0, f"{key}: no commits")
        check(sum(a["abort_causes"].values()) == a["aborts"],
              f"{key}: abort causes do not sum to aborts")
    return out


def one_chip(dev):
    for name, args, grids in PHASES:
        out = run_phase(name, args, grids, WAVES, dev)
        emit(phase=name, passed=True,
             rows=[{k: r[k] for k in ("cc", "granularity", "lanes",
                                      "commits", "aborts")}
                   for r in out["pallas"]])


def sharded_inputs(n_records: int, lanes: int, slots: int, waves: int):
    """The txn_scaling inputs: uniform keys over the record space, half
    reads and half writes, a fresh lane-priority permutation per wave."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import types as t
    rng = np.random.default_rng(0)
    keys = rng.integers(0, n_records, (lanes, slots), dtype=np.int32)
    groups = rng.integers(0, 2, (lanes, slots), dtype=np.int32)
    kinds = rng.choice([t.READ, t.WRITE], (lanes, slots)).astype(np.int32)
    prio = np.stack([np.random.default_rng(w).permutation(lanes)
                     for w in range(waves)]).astype(np.uint32)
    stack = lambda x: jnp.asarray(np.broadcast_to(x, (waves,) + x.shape))
    return stack(keys), stack(groups), stack(kinds), jnp.asarray(prio)


def quarters(tables, what: str):
    """Every table is split in four row ranges, one per device."""
    for tbl in tables:
        shards = tbl.addressable_shards
        check(len({s.device for s in shards}) == 4
              and all(s.data.shape[0] * 4 == tbl.shape[0] for s in shards),
              f"{what}: a table is not split in quarters over 4 devices")


def four_chips(n_records=YCSB_KEYS, lanes=256, slots=16, waves=20):
    import jax
    import numpy as np

    from repro.core import distributed as D
    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    mesh = jax.sharding.Mesh(np.array(devs[:4]), ("data",))
    inputs = sharded_inputs(n_records, lanes, slots, waves)
    for cc in ("occ", "mvcc"):
        outs = {}
        for backend in BACKENDS:
            for depth in (1, 2):
                cfg = D.DistConfig(n_records=n_records, n_groups=2,
                                   lanes_per_shard=lanes // 4, slots=slots,
                                   backend=backend, cc=cc,
                                   mv_depth=4 if cc == "mvcc" else 0,
                                   pipeline_depth=depth)
                tables = D.init_tables(cfg, mesh)
                quarters(tables, f"{cc}/{backend}/depth {depth} init")
                run = jax.jit(D.make_run_fn(cfg, mesh, waves))
                t0 = time.perf_counter()
                res = jax.block_until_ready(
                    run(*inputs, tables, np.uint32(0)))
                first = time.perf_counter() - t0
                t0 = time.perf_counter()
                res = jax.block_until_ready(
                    run(*inputs, D.init_tables(cfg, mesh), np.uint32(0)))
                warm = time.perf_counter() - t0
                quarters(res[1], f"{cc}/{backend}/depth {depth} final")
                outs[(backend, depth)] = jax.tree.map(np.asarray, res)
                emit(phase="sharded", cc=cc, backend=backend, depth=depth,
                     compile_s=first - warm, first_s=first, warm_s=warm,
                     commits=int(outs[(backend, depth)][0].sum()),
                     bytes_in_use=[bytes_in_use(d) for d in devs[:4]])
        base = outs[("jnp", 1)]
        check(int(base[0].sum()) > 0, f"{cc}: no commits")
        for key, other in outs.items():
            for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(other)):
                check(np.array_equal(a, b),
                      f"{cc}: {key} differs from jnp depth 1")
        emit(phase="sharded", cc=cc, passed=True,
             commits=int(base[0].sum()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded engine on a 4-chip mesh")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print("chip_smoke: no repro package beside this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devs[0].platform!r})",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    emit(compile_cache=enable_compile_cache(), devices=len(devs),
         device_kind=devs[0].device_kind)
    if args.four_chips:
        four_chips()
    else:
        one_chip(devs[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
