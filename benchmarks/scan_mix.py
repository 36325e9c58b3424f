"""Interval read-sets: fine vs coarse abort rates and goodput as the
scan mix grows (ISSUE 10 — the phantom-protection cost of timestamp
granularity).

    PYTHONPATH=src python -m benchmarks.scan_mix [--json out.json]

Fig2-style rows over a YCSB-E-like mix: ``--scan-frac`` of the
transactions carry one interval READ of ``scan_len`` consecutive keys,
validated at commit by the ``iterate_validate`` pass (phantom
protection; DESIGN.md section 13).  Two sweeps:

  (a) scan FRACTION at a fixed length — how fast each granularity's
      phantom-abort bill grows as scans enter the mix;
  (b) scan LENGTH at a fixed fraction — coarse bucket-interval claims
      pay for the whole bucket expansion of the interval, fine
      per-gap timestamps only for the keys actually read.

Validated orderings printed per point:
  - coarse phantom aborts >= fine phantom aborts (bucket claims
    over-approximate the interval; the paper's granularity gap, now on
    the scan axis);
  - fine goodput >= coarse goodput on every scan mix;
  - mvcc aborts ZERO phantoms (snapshot scans read a consistent cut —
    SI admits phantoms by design) while mvocc, which re-validates, pays.

Rows carry ``scan_frac``/``scan_len`` next to the standard bench fields
(abort_causes["phantom"], goodput, max_extent), so the dashboard can
slice the scan axis like any other grid dimension.
"""
from __future__ import annotations

import argparse

from benchmarks.common import one, save_rows, sweep

CCS = ["occ", "tictoc", "mvcc", "mvocc"]
LANES = [64]
SCAN_FRACS = (0.1, 0.3, 0.5)
SCAN_LENS = (4, 16, 64)


def _scan_rows(waves, n_keys, backend, *, scan_frac, scan_len, lanes,
               open_loop):
    kw = {}
    if open_loop:
        # Offered load at 3/4 of the lane width keeps the admission queue
        # busy without saturating it — goodput then reflects abort-driven
        # retries, not queue overflow.
        kw["arrival_rate"] = 0.75 * max(lanes)
    rows = sweep("ycsb", ccs=CCS, lanes=lanes, waves=waves, n_keys=n_keys,
                 backend=backend, warm=True, quiet=True,
                 scan_frac=scan_frac, scan_len=scan_len, **kw)
    for r in rows:
        r["scan_frac"] = scan_frac
        r["scan_len"] = scan_len
    return rows


def _report(rows, axis, value, lanes):
    for cc in CCS:
        c = one(rows, cc=cc, granularity=0, lanes=lanes)
        f = one(rows, cc=cc, granularity=1, lanes=lanes)
        cp, fp = (r["abort_causes"]["phantom"] for r in (c, f))
        line = (f"  {axis}={value:<5g} {cc:7s} phantoms "
                f"coarse={cp:6d} fine={fp:6d}  "
                f"abort {100 * c['abort_rate']:6.2f}% -> "
                f"{100 * f['abort_rate']:6.2f}%")
        if "goodput" in c:
            line += (f"  goodput {c['goodput']:7.3f} -> "
                     f"{f['goodput']:7.3f} txn/us")
        else:
            line += (f"  thpt {c['throughput']:7.3f} -> "
                     f"{f['throughput']:7.3f} txn/us")
        print(line)
        if cc == "mvcc":
            assert cp == fp == 0, "snapshot scans admit phantoms (SI)"
        else:
            assert cp >= fp, (cc, "coarse bucket claims over-approximate")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--waves", type=int, default=200)
    ap.add_argument("--n-keys", type=int, default=100_000)
    ap.add_argument("--backend", choices=("jnp", "pallas"), default="jnp")
    ap.add_argument("--closed-loop", action="store_true",
                    help="skip the open-loop front-end (rows then carry "
                         "throughput instead of goodput)")
    ap.add_argument("--json", default="reports/scan_mix.json")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    open_loop = not args.closed_loop

    rows = []
    print(f"# scan-fraction sweep (scan_len=16, T={LANES[0]}, "
          f"{args.backend} backend)")
    for sf in SCAN_FRACS:
        r = _scan_rows(args.waves, args.n_keys, args.backend,
                       scan_frac=sf, scan_len=16, lanes=LANES,
                       open_loop=open_loop)
        _report(r, "frac", sf, LANES[0])
        rows += r
    print(f"# scan-length sweep (scan_frac=0.25, T={LANES[0]})")
    for sl in SCAN_LENS:
        r = _scan_rows(args.waves, args.n_keys, args.backend,
                       scan_frac=0.25, scan_len=sl, lanes=LANES,
                       open_loop=open_loop)
        _report(r, "len", sl, LANES[0])
        rows += r
    save_rows(rows, args.json)
    return rows


if __name__ == "__main__":
    main()
