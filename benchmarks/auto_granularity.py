"""Beyond-paper: auto-granularity OCC (the paper's section-5 sketch).

Starts coarse everywhere; promotes records with false-conflict evidence to
fine-grained timestamps.  Success = recovers manual-fine OCC throughput on
TPC-C without annotations.
"""
from __future__ import annotations

import argparse

from benchmarks.common import one, save_rows, sweep


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--waves", type=int, default=400)
    ap.add_argument("--lanes", type=int, nargs="+", default=[64, 128])
    ap.add_argument("--json", default="reports/auto_granularity.json")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    rows = []
    rows += sweep("tpcc", ccs=["occ"], lanes=args.lanes, grans=(0, 1),
                  waves=args.waves, scale=1.0)
    rows += sweep("tpcc", ccs=["autogran"], lanes=args.lanes, grans=(0,),
                  waves=args.waves, scale=1.0)
    save_rows(rows, args.json)

    for T in args.lanes:
        coarse = one(rows, cc="occ", granularity=0, lanes=T)["throughput"]
        fine = one(rows, cc="occ", granularity=1, lanes=T)["throughput"]
        auto = one(rows, cc="autogran", granularity=0,
                   lanes=T)["throughput"]
        rec = (auto - coarse) / max(fine - coarse, 1e-9)
        print(f"T={T:4d}: coarse {coarse:.3f}  auto {auto:.3f}  "
              f"fine {fine:.3f}  -> auto recovers {100*rec:.0f}% of the "
              f"fine-granularity gain")
    return rows


if __name__ == "__main__":
    main()
