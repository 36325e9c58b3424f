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
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    T = args.lanes
    rates = args.rates or [0.25 * T, 0.5 * T, 0.75 * T, 1.0 * T]
    rows = []
    for rate in rates:
        # One jitted sweep per offered load (the arrival rate is part of
        # the compiled scan); occ + mvcc at both granularities per sweep.
        got = sweep("ycsb", ccs=["occ", "mvcc"], lanes=[T],
                    waves=args.waves, n_keys=args.n_keys,
                    backend=args.backend, quiet=True, warm=True,
                    arrival_rate=rate, queue_cap=4 * T,
                    max_incarnations=args.max_incarnations)
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
