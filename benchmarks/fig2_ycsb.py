"""Paper Figure 2: YCSB-like workload, high contention (Zipf theta=0.9,
50% writes), coarse (2a) vs fine (2b) timestamps, throughput vs threads.

    PYTHONPATH=src python -m benchmarks.fig2_ycsb [--full] [--json out.json]

Validated orderings (paper section 4.2):
  2a: TicToc starts above OCC at low threads, falls below OCC at high
      threads (rts-extension CAS contention); SwissTM/Adaptive/2PL
      uniformly below OCC.
  2b: all mechanisms improve; OCC and SwissTM gain the most.
"""
from __future__ import annotations

import argparse

from benchmarks.common import LANES, save_rows, sweep


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale: 10M keys (slower)")
    ap.add_argument("--waves", type=int, default=300)
    ap.add_argument("--backend", choices=("jnp", "pallas"), default="jnp")
    ap.add_argument("--json", default="reports/fig2_ycsb.json")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    n_keys = 10_000_000 if args.full else 1_000_000
    print(f"# Fig 2a (coarse) + 2b (fine), {n_keys} keys "
          f"[{args.backend} backend, one jitted grid]")
    rows = sweep("ycsb", waves=args.waves, n_keys=n_keys,
                 backend=args.backend, warm=True)
    save_rows(rows, args.json)

    # ordering checks
    from benchmarks.common import one
    hiT = max(LANES)
    occ_hi = one(rows, cc="occ", granularity=0, lanes=hiT)["throughput"]
    tic_hi = one(rows, cc="tictoc", granularity=0, lanes=hiT)["throughput"]
    occ_lo = one(rows, cc="occ", granularity=0, lanes=LANES[0])["throughput"]
    tic_lo = one(rows, cc="tictoc", granularity=0,
                 lanes=LANES[0])["throughput"]
    print(f"2a: TicToc/OCC at T={LANES[0]}: {tic_lo/occ_lo:.2f}x  "
          f"at T={hiT}: {tic_hi/occ_hi:.2f}x "
          f"(paper: >1 at low T, <1 at high T)")
    for cc in ("2pl", "swisstm", "adaptive"):
        r = one(rows, cc=cc, granularity=0, lanes=hiT)["throughput"]
        print(f"2a: {cc}/OCC at T={hiT}: {r/occ_hi:.2f}x (paper: <1)")
    for cc in ("occ", "swisstm", "tictoc", "2pl", "adaptive", "mvcc",
               "mvocc"):
        c = one(rows, cc=cc, granularity=0, lanes=hiT)["throughput"]
        f = one(rows, cc=cc, granularity=1, lanes=hiT)["throughput"]
        print(f"2b: {cc} fine/coarse at T={hiT}: {f/c:.2f}x (paper: >1)")
    # Beyond-paper: granularity still matters when readers never block —
    # YCSB's random columns put write-write pairs in different groups, so
    # the MV mechanisms' per-group first-committer-wins keeps the fine
    # advantage.  (Read-only abort rates live in benchmarks/abort_rates.py,
    # which runs the mix that actually has read-only clients.)
    mvc = one(rows, cc="mvcc", granularity=0, lanes=hiT)["throughput"]
    mvf = one(rows, cc="mvcc", granularity=1, lanes=hiT)["throughput"]
    print(f"mv: mvcc fine/coarse at T={hiT}: {mvf/mvc:.2f}x "
          "(write-write resolution stays per-group)")
    return rows


if __name__ == "__main__":
    main()
