"""Beyond-paper: open-loop traffic — goodput and time-to-commit latency
vs offered load (DESIGN.md section 11).

The paper's experiments are closed-loop (every thread always has a
transaction; aborts retry in place).  This benchmark drives the same
engine open-loop: Poisson arrivals queue for admission
(core/admission.py) and aborts re-enqueue with a bounded incarnation
counter, so the figure reads as a classic load-latency curve — goodput
(unique committed txns per simulated us) saturates at the closed-loop
capacity while p50/p99 time-to-commit (waves from first admission to
commit) blows up past the knee.  Fine-granularity timestamps move the
knee right for both occ and mvcc: higher sustainable load at the same
latency, the open-loop restatement of the paper's throughput claim.

    PYTHONPATH=src python -m benchmarks.open_loop [--json out.json]
"""
from __future__ import annotations

import argparse

from benchmarks.common import save_rows, sweep


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--waves", type=int, default=200)
    ap.add_argument("--lanes", type=int, default=64)
    ap.add_argument("--rates", type=float, nargs="+", default=None,
                    help="offered loads (expected arrivals/wave); default "
                         "0.25/0.5/0.75/1.0x the lane width")
    ap.add_argument("--n-keys", type=int, default=1_000_000)
    ap.add_argument("--max-incarnations", type=int, default=8)
    ap.add_argument("--backend", choices=("jnp", "pallas"), default="jnp")
    ap.add_argument("--json", default="reports/open_loop.json")
    ap.add_argument("--trace", nargs="?", const="reports/open_loop_trace"
                    ".json", default=None, metavar="PATH",
                    help="export ONE combined Chrome-trace timeline across "
                         "all offered loads (analysis/trace.py; rows are "
                         "labeled cc/gran/rate) — REPRO_TRACE=1 also "
                         "enables it")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    import os
    trace_path = args.trace
    if trace_path is None:
        env = os.environ.get("REPRO_TRACE", "")
        if env and env != "0":
            trace_path = (env if env not in ("1", "true")
                          else "reports/open_loop_trace.json")

    T = args.lanes
    rates = args.rates or [0.25 * T, 0.5 * T, 0.75 * T, 1.0 * T]
    rows = []
    traced = []
    for rate in rates:
        # One jitted sweep per offered load (the arrival rate is part of
        # the compiled scan); occ + mvcc at both granularities per sweep.
        got = sweep("ycsb", ccs=["occ", "mvcc"], lanes=[T],
                    waves=args.waves, n_keys=args.n_keys,
                    backend=args.backend, quiet=True, warm=True,
                    arrival_rate=rate, queue_cap=4 * T,
                    max_incarnations=args.max_incarnations,
                    per_wave=bool(trace_path),
                    return_points=bool(trace_path))
        if trace_path:
            got, points = got
            traced += [(rate, p) for p in points]
        for r in got:
            r["arrival_rate"] = rate
        rows += got
        for r in got:
            print(f"  rate={rate:6.1f} {r['cc']:5s} "
                  f"{'fine' if r['granularity'] else 'coarse'}: "
                  f"goodput={r['goodput']:7.3f} txn/us  "
                  f"p50={max(r['p50_ttc_waves']):3g} "
                  f"p99={max(r['p99_ttc_waves']):3g} waves  "
                  f"dropped={r['inc_drops']}")
    save_rows(rows, args.json)
    if trace_path:
        # One combined timeline: every (offered load x cc x granularity)
        # grid point is its own process row on the simulated-time axis.
        from repro.core import types as t
        from repro.analysis.trace import point_events, validate_chrome_trace
        import json as _json
        events, pid = [], 0
        for rate, p in traced:
            pid += 1
            label = (f"{t.CC_NAMES.get(p.cc, p.cc)}/"
                     f"{'fine' if p.granularity else 'coarse'}/"
                     f"rate{rate:g}")
            events += point_events(label, pid, p.per_wave_commits,
                                   p.per_wave_aborts, p.per_wave_us,
                                   p.per_wave_causes)
        trace = {"traceEvents": events, "displayTimeUnit": "ms",
                 "otherData": {"source": "repro open-loop wave trace",
                               "time_axis": "simulated microseconds"}}
        errs = validate_chrome_trace(trace)
        assert not errs, errs
        os.makedirs(os.path.dirname(trace_path) or ".", exist_ok=True)
        with open(trace_path, "w") as f:
            _json.dump(trace, f)
        print(f"[saved] {trace_path} ({pid} trace rows)")

    # The headline ordering: at the highest offered load, fine granularity
    # sustains more goodput than coarse for both mechanisms.
    from benchmarks.common import one
    hi = rates[-1]
    picked = [r for r in rows if r["arrival_rate"] == hi]
    for cc in ("occ", "mvcc"):
        g0 = one(picked, cc=cc, granularity=0)["goodput"]
        g1 = one(picked, cc=cc, granularity=1)["goodput"]
        print(f"at rate={hi:g}: {cc} fine/coarse goodput = {g1/g0:.2f}x "
              "(expected > 1 under contention)")
    return rows


if __name__ == "__main__":
    main()
