"""Shared sweep machinery for the paper-figure benchmarks."""
from __future__ import annotations

import json
import os
import time

CCS = ["occ", "tictoc", "2pl", "swisstm", "adaptive", "mvcc", "mvocc"]
LANES = [8, 16, 32, 64, 96, 128]


def warm_then_time(fn, *args, **kw):
    """The warm-then-time pattern of benchmarks/txn_scaling.py, shared:
    call ``fn`` once to compile and fill every cache (blocking until the
    result is ready), then time a second, fully-warm call.  Returns
    ``(result, seconds)``; the seconds never include compile time — for
    grid sweeps the second call re-executes the compiled-sweep memo
    (core/engine.py _SWEEP_PROGRAMS) instead of re-tracing."""
    import jax
    jax.block_until_ready(fn(*args, **kw))
    t0 = time.time()
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    return out, time.time() - t0


def sweep(workload: str, *, ccs=None, lanes=None, grans=(0, 1), waves=300,
          scale=1.0, n_keys=1_000_000, seed=1, quiet=False, backend="jnp",
          warm=False, **wl_kw):
    """One jitted sweep over the whole grid (core/engine.py sweep).
    Extra keywords (write_frac, ro_frac, theta, mv_depth) pass through to
    ``run_grid``.  ``warm=True`` runs the grid twice through
    ``warm_then_time`` and rewrites each row's ``wall_s`` from the warm
    second pass, so no emitted row includes compile time."""
    from repro.launch.txn_bench import run_grid
    grid_args = (workload, list(ccs or CCS), tuple(grans),
                 list(lanes or LANES), waves)
    grid_kw = dict(scale=scale, n_keys=n_keys, seed=seed, backend=backend,
                   **wl_kw)
    if warm:
        rows, dt = warm_then_time(run_grid, *grid_args, **grid_kw)
        wall = round(dt / max(len(rows), 1), 4)
        for r in rows:
            r["wall_s"] = wall
    else:
        rows = run_grid(*grid_args, **grid_kw)
    if not quiet:
        for r in rows:
            line = (f"  {workload} {r['cc']:9s} "
                    f"{'fine' if r['granularity'] else 'coarse'} "
                    f"T={r['lanes']:4d}  "
                    f"thpt={r['throughput']:8.3f}  "
                    f"abort={100*r['abort_rate']:6.2f}%")
            if r.get("open_loop"):
                line += (f"  goodput={r['goodput']:8.3f}  "
                         f"p99ttc={max(r['p99_ttc_waves']):g}w")
            print(line)
    return rows


def save_rows(rows, path):
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"[saved] {path}")


def by(rows, **kv):
    out = [r for r in rows
           if all(r.get(k) == v for k, v in kv.items())]
    return out


def one(rows, **kv):
    m = by(rows, **kv)
    assert len(m) == 1, (kv, len(m))
    return m[0]
