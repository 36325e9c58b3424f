#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's configuration and traffic from the seed,
compiles (or loads from the compile cache) the cell's one chunk program
and runs two chunks.  The window then dispatches chunks back to back for
``--seconds`` and ends with its last whole chunk; nothing compiles inside
it.  With ``--trace 1`` the first seconds of the window are traced and
the cell's per-layer metrics are reported in place of the end-to-end
ones.  After the window the reference re-runs sampled chunks of it and
decides ``correct``.  The last line of standard output is the result;
the numbers compared, each with its limit, end standard error.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits with status 3.  ``--control`` runs the program's own
non-serializable cost model, which the check must find incorrect.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

#: Seconds of the window that a ``--trace 1`` run traces.
TRACE_SECONDS = 2.0
#: Host-side events that mean something was traced or compiled.
_COMPILE_EVENTS = ("/jax/core/compile/", "/jax/compilation_cache/")


class NoChip(RuntimeError):
    pass


def process_start() -> float:
    """Wall-clock time at which this process started."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def make_driver(cell, seed: int, devices, control: bool):
    kind = cell.config["driver"]
    if kind == "engine":
        from bench.drivers.engine import EngineDriver
        return EngineDriver(cell.config, cell.mix, seed, control=control)
    if kind == "sharded":
        from bench.drivers.sharded import ShardedDriver
        return ShardedDriver(cell.config, cell.mix, seed, devices,
                             control=control)
    raise ValueError(f"unknown driver {kind!r}")


class _CompileCounter:
    def __init__(self):
        self.n = 0

    def __call__(self, event: str, *args, **kwargs):
        if event.startswith(_COMPILE_EVENTS):
            self.n += 1


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(cell, seed: int, seconds: float, trace: bool, devices, *,
             control: bool = False, started: float | None = None) -> dict:
    """One run of ``cell``; returns the result line's object."""
    import jax
    import numpy as np

    from bench import check, latency, roofline, trace_reduce
    from repro.launch.compile_cache import enable_compile_cache

    started = time.time() if started is None else started
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    drv = make_driver(cell, seed, devices, control)
    for _ in range(2):
        c0 = time.perf_counter()
        drv.collect(drv.dispatch())
        chunk_est = time.perf_counter() - c0
    setup_s = time.time() - started

    # Chunks whose waves the reference re-runs: one drawn from the seed
    # (among the traced ones in a traced run) and the window's last.
    span = min(TRACE_SECONDS, seconds) if trace else seconds
    n_est = max(int(span / max(chunk_est, 1e-3)), 1)
    pick = int(np.random.default_rng(seed).integers(0, n_est))

    counter = _CompileCounter()
    jax.monitoring.register_event_listener(counter)
    jax.monitoring.register_event_duration_secs_listener(counter)
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    traced, win = 0, None
    try:
        if trace:
            jax.profiler.start_trace(tdir)
            win = jax.profiler.TraceAnnotation("bench:window")
            win.__enter__()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:dispatch"):
            inflight = collections.deque([drv.dispatch(), drv.dispatch()])
        done, ends, kept = [], [], {}
        while True:
            c = inflight.popleft()
            with jax.profiler.TraceAnnotation("bench:read"):
                done.append(drv.collect(c))
            t = time.perf_counter()
            ends.append(t)
            if len(done) - 1 == pick:
                kept["pick"] = c
            kept["last"] = c
            if win is not None:
                traced += 1
                if t - t0 >= min(TRACE_SECONDS, seconds):
                    win.__exit__(None, None, None)
                    win = None
                    jax.profiler.stop_trace()
            if t - t0 >= seconds:
                break
            with jax.profiler.TraceAnnotation("bench:dispatch"):
                inflight.append(drv.dispatch())
        for c in inflight:
            drv.collect(c)
    finally:
        if win is not None:
            win.__exit__(None, None, None)
            jax.profiler.stop_trace()
        jax.monitoring.unregister_event_listener(counter)
        jax.monitoring.unregister_event_duration_listener(counter)
    compiles = counter.n
    window_s = ends[-1] - t0
    chunk_s = np.diff(np.r_[t0, ends])
    peak = _peak_bytes(devices)

    # Check data to the host, then free the program's state.
    chunks = [kept["last"]] + ([kept["pick"]] if "pick" in kept
                               and kept["pick"] is not kept["last"] else [])
    inputs = [drv.check_inputs(c) for c in chunks]
    hlo = drv.compiled_text() if trace else ""
    geo = getattr(drv, "geo", None)
    waves = drv.waves
    n_groups = getattr(getattr(drv, "wl", None), "n_groups", 2)
    del chunks, kept, c, inflight
    del drv

    commits = sum(r["commits"] for r in done)
    attempts = sum(r["attempts"] for r in done)
    cfg = cell.config
    moved = []          # bytes the reference's waves had to move
    if cfg["driver"] == "engine":
        def on_wave(key, group, kind, commit):
            moved.append(roofline.validate_bytes(
                cfg["cc"], key, group, kind, commit, n_groups=n_groups,
                fine=cfg["granularity"] == "fine",
                mv_depth=cfg.get("mv_depth", 0)))

        counts = check.merge([
            check.check_engine_chunk(cfg, n_groups, inp,
                                     on_wave if trace else None)
            for inp in inputs])
        failed = 0
    else:
        counts = check.merge([check.check_sharded_chunk(cfg, geo, inp)
                              for inp in inputs])
        failed = attempts - commits
    waves_checked = sum(inp["rec"]["commit"].shape[0] for inp in inputs)
    checks = {k: {"value": v, "limit": check.LIMITS[k]}
              for k, v in counts.items()}
    checks["compiles_in_window"] = {"value": compiles, "limit": 0}
    correct = waves_checked > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    checks["waves_checked"] = {"value": waves_checked, "limit": 1,
                               "at_least": True}

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": attempts,
           "failed": failed}
    if not trace:
        e2e = {"setup_s": (setup_s, "s"),
               "commits_per_s": (commits / window_s, "txn/s")}
        if cfg["driver"] == "engine":
            commit = np.concatenate([r["commit"] for r in done])
            age = np.concatenate([r["age"] for r in done])
            e2e["commit_p95_ms"] = (latency.p95_ms(
                latency.latencies(commit, age, window_s)), "ms")
        metrics = {m["name"]: {"value": e2e[m["name"]][0],
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
    else:
        plain = trace_reduce.load_xplane(_xplane(tdir), hlo, "bench:window")
        red = trace_reduce.reduce(plain)
        ctx = {"cell": cell, "trace": red,
               "counters": {"commits": commits, "attempts": attempts,
                            "aborts": attempts - commits},
               "hbm_peak": roofline.hbm_peak(dev.device_kind),
               "validate_bytes": (np.mean(moved) * traced * waves
                                  if moved else None)}
        metrics = per_layer_metrics(cell, ctx)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["breakdown"] = trace_reduce.breakdown(plain)
    if tdir:
        shutil.rmtree(tdir, ignore_errors=True)
    out.update(metrics=metrics, device=device,
               window={"seconds": window_s, "chunks": len(chunk_s),
                       "waves_per_chunk": waves,
                       "chunk_ms_min": float(chunk_s.min() * 1e3),
                       "chunk_ms_median": float(np.median(chunk_s) * 1e3),
                       "chunk_ms_max": float(chunk_s.max() * 1e3)},
               checks=checks)
    return out


def per_layer_metrics(cell, ctx: dict) -> dict:
    """The cell's per-layer metrics, each from its own reader; a reader
    that finds nothing to read leaves its metric out."""
    from bench import spec
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"], cell.root)(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def _xplane(tdir: str) -> str:
    found = sorted(Path(tdir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"the profiler wrote no trace under {tdir}")
    return str(found[-1])


def main(argv=None) -> int:
    started = process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the program's non-serializable cost model")
    args = ap.parse_args(argv)
    sys.path.insert(1, str(ROOT / "src"))
    from bench import spec
    cell = spec.load_cell(args.workload)
    try:
        devices = require_chips(cell.chips)
    except NoChip as e:
        print(f"bench/run.py: {e}; refusing to measure", file=sys.stderr)
        return 3
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   control=args.control, started=started)
    for name, c in out["checks"].items():
        rel = ">=" if c.get("at_least") else "<="
        print(f"check {name} {c['value']} {rel} {c['limit']}",
              file=sys.stderr)
    print(f"correct {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
