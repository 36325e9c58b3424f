"""Transaction-engine benchmark CLI (the paper's experiments).

    PYTHONPATH=src python -m repro.launch.txn_bench --workload tpcc \
        --cc occ tictoc --granularity both --lanes 16 64 128 --waves 300

The whole cc x granularity x lanes grid compiles to ONE XLA program
(core/engine.py sweep, vmapped in lane buckets); ``--backend pallas`` routes
every CC shared-state op (the wave_commit megakernel, validate/gather,
commit/timestamp scatters) through the TPU-native kernels via the
``backend.N_OPS``-op backend surface of core/backend.py (interpret mode on CPU — see
DESIGN.md section 5).  Each JSON row records the resolved backend and
per-op kernel coverage (CC_OPS), which benchmarks/perf_dashboard.py
aggregates into reports/perf_dashboard.md.
"""
from __future__ import annotations

import argparse
import functools
import json
import time


@functools.lru_cache(maxsize=32)
def _make_workload(workload: str, *, scale: float = 1.0,
                   n_keys: int = 1_000_000, write_frac: float = 0.5,
                   ro_frac: float = 0.0, theta: float = 0.9,
                   scan_frac: float = 0.0, scan_len: int = 0):
    """Workloads are deterministic in their parameters and read-only once
    built, so identical grid points share ONE object — which also keys the
    compiled-sweep memo (core/engine.py), letting a re-run of the same
    grid (benchmarks/common.py warm_then_time) skip tracing entirely."""
    from repro.workloads import TPCCWorkload, YCSBWorkload
    if workload == "tpcc":
        return TPCCWorkload.make(n_warehouses=8, scale=scale,
                                 scan_len=scan_len)
    return YCSBWorkload.make(n_keys=n_keys, write_frac=write_frac,
                             ro_frac=ro_frac, theta=theta,
                             scan_frac=scan_frac, scan_len=scan_len or 8)


def _device_fields() -> dict:
    """The device every row ran on, as JAX reports it."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _model_kind() -> str:
    """Device kind the analytic roofline columns are stated for: the TPU
    the run is on (a kind missing from analysis/peaks.py raises), or a
    v5e for runs on the CPU, where no device is measured."""
    import jax
    from repro.analysis import peaks
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        peaks.peaks_for(dev.device_kind)
        return dev.device_kind
    return peaks.V5E


def _cost_fields(cc_name: str, lanes: int, granularity: int, slots: int,
                 n_groups: int, mv_depth: int, max_extent: int = 1,
                 bucket_size: int = 8) -> dict:
    """Per-op roofline cost-model columns (analysis/txn_cost.py): analytic
    bytes/flops per transaction attempt and the mechanism's fraction of
    the roofline of ``_model_kind()``.  Closed-form in the wave shape, so the
    fields are backend-INDEPENDENT (CI's jnp-vs-pallas CLI parity diff
    relies on that)."""
    from repro.analysis import txn_cost as tc
    shape = tc.WaveShape(lanes=lanes, slots=slots, n_groups=n_groups,
                         granularity=granularity, mv_depth=mv_depth,
                         max_extent=max_extent, bucket_size=bucket_size)
    cost = tc.txn_cost(cc_name, shape, chip=_model_kind())
    fields = {
        "bytes_per_txn": round(cost["bytes_per_txn"], 1),
        "flops_per_txn": round(cost["flops_per_txn"], 1),
        "roofline_frac": round(cost["roofline_frac"], 6),
        "roofline_bound": cost["bound"],
        "roofline_chip": cost["chip"],
    }
    if cc_name in tc.PROBE_CHAIN_LAUNCHES:
        # ISSUE 9 fused-wave accounting: launches and touched-row DMA
        # visits of the probe chain per wave, fused (the shipped default)
        # next to the unfused baseline — the dashboard's row-traffic-cut
        # columns.
        chain = tc.probe_chain(cc_name, shape, fused=True)
        unfused = tc.probe_chain(cc_name, shape, fused=False)
        fields.update({
            "launches_per_wave": chain["launches_per_wave"],
            "dma_rows_per_wave": chain["dma_rows_per_wave"],
            "dma_rows_per_wave_unfused": unfused["dma_rows_per_wave"],
        })
    return fields


def _row(workload: str, cc_name: str, p, wall_s: float,
         backend: str, *, slots: int = 0, n_groups: int = 2,
         mv_depth: int = 0, max_extent: int = 1,
         bucket_size: int = 8) -> dict:
    from repro.core import types as t
    from repro.core.backend import kernel_coverage
    row = {
        "workload": workload, "cc": cc_name, "granularity": p.granularity,
        "lanes": p.lanes, "waves": p.waves,
        "commits": p.commits, "aborts": p.aborts,
        "abort_rate": round(p.abort_rate, 4),
        "ro_commits": p.ro_commits, "ro_aborts": p.ro_aborts,
        "ro_abort_rate": round(p.ro_abort_rate, 4),
        "throughput": round(p.throughput, 4),
        "ext_events": p.ext_events,
        "wall_s": round(wall_s, 2),
        "backend": backend,
        **_device_fields(),
        # Which backend-surface ops this mechanism actually routed through
        # Pallas kernels vs XLA — makes BENCH_*.json trajectories
        # attributable to an execution engine (DESIGN.md section 5).
        "kernel_ops": kernel_coverage(backend, t.CC_IDS[cc_name]),
        # Interval-read shape of the run; extent-1 rows are pure point
        # workloads (perf_dashboard.py defaults missing values to 1 for
        # pre-scan JSON rows).
        "max_extent": max_extent,
    }
    if getattr(p, "abort_causes", None) is not None:
        # Per-cause abort breakdown (types.CAUSE_*), name-keyed in code
        # order; the values sum to `aborts` exactly (the conservation
        # invariant tests/test_abort_causes.py asserts).
        row["abort_causes"] = {t.CAUSE_NAMES[i]: int(n)
                               for i, n in enumerate(p.abort_causes)}
    if slots:
        row.update(_cost_fields(cc_name, p.lanes, p.granularity, slots,
                                n_groups, mv_depth, max_extent,
                                bucket_size))
    if getattr(p, "open_loop", False):
        # Goodput (unique committed txns per simulated us) and the
        # per-txn-class time-to-commit percentiles (waves) the dashboard's
        # latency section reads (DESIGN.md section 11).
        row.update({
            "open_loop": True,
            "goodput": round(p.goodput, 4),
            "offered": p.offered, "admitted": p.admitted,
            "arrival_drops": p.arrival_drops, "inc_drops": p.inc_drops,
            "queued_final": p.queued_final,
            "p50_ttc_waves": p.p50_ttc, "p99_ttc_waves": p.p99_ttc,
        })
    return row


def run_grid(workload: str, ccs: list, grans, lanes: list, waves: int, *,
             scale: float = 1.0, n_keys: int = 1_000_000, seed: int = 0,
             backend: str = "jnp", mv_depth: int = 4, snapshot_age: int = 0,
             write_frac: float = 0.5, ro_frac: float = 0.0,
             theta: float = 0.9, scan_frac: float = 0.0, scan_len: int = 0,
             arrival_rate: float = 0.0,
             queue_cap: int = 0, max_incarnations: int = 0):
    """Run the whole benchmark grid in one jitted sweep; returns row dicts.

    ``wall_s`` in each row is the grid's wall time amortized over its rows
    (the grid runs as one XLA program, so per-point timing does not exist).
    The multi-version ring (``mv_depth``) is only allocated when the grid
    contains an MV mechanism; ``snapshot_age`` (aged reader snapshots —
    mvstore.snapshot_ts) requires an all-MV grid, since only snapshot
    readers have a snapshot to age.  ``arrival_rate > 0`` switches every
    grid point to the open-loop front-end (core/admission.py) — rows then
    carry goodput, the admission counters, and the per-class
    time-to-commit percentiles; queue_cap defaults to 4x the widest lane
    count and max_incarnations to 8 when left at 0.
    """
    from repro.core import types as t
    from repro.core.engine import sweep

    wl = _make_workload(workload, scale=scale, n_keys=n_keys,
                        write_frac=write_frac, ro_frac=ro_frac, theta=theta,
                        scan_frac=scan_frac, scan_len=scan_len)
    need_mv = any(t.CC_IDS[c] in t.MV_CCS for c in ccs)
    if snapshot_age and not all(t.CC_IDS[c] in t.MV_CCS for c in ccs):
        raise ValueError("snapshot_age > 0 needs an all-MV cc grid "
                         "(mvcc/mvocc): single-version mechanisms have no "
                         "snapshots to age")
    if arrival_rate > 0:
        queue_cap = queue_cap or 4 * max(lanes)
        max_incarnations = max_incarnations or 8
    # The base cfg must itself validate: an aged-snapshot grid is all-MV,
    # so anchor it on the first requested mechanism instead of CC_OCC.
    cfg = t.EngineConfig(
        cc=t.CC_IDS[ccs[0]] if snapshot_age else t.CC_OCC,
        lanes=max(lanes), slots=wl.slots,
        n_records=wl.n_records, n_groups=wl.n_groups, n_cols=wl.n_cols,
        n_txn_types=wl.n_txn_types, n_rings=wl.n_rings, backend=backend,
        mv_depth=mv_depth if need_mv else 0, snapshot_age=snapshot_age,
        max_extent=wl.max_extent,
        arrival_rate=arrival_rate, queue_cap=queue_cap,
        max_incarnations=max_incarnations)
    t0 = time.time()
    points = sweep(cfg, wl, waves, ccs=[t.CC_IDS[c] for c in ccs],
                   grans=tuple(grans), lane_counts=tuple(lanes),
                   seeds=(seed,))
    wall = (time.time() - t0) / max(len(points), 1)
    rows = [_row(workload, t.CC_NAMES[p.cc], p, wall, backend,
                 slots=wl.slots, n_groups=wl.n_groups,
                 mv_depth=cfg.mv_depth, max_extent=cfg.max_extent,
                 bucket_size=cfg.bucket_size)
            for p in points]
    return rows


def run_one(workload: str, cc_name: str, gran: int, lanes: int, waves: int,
            *, scale: float = 1.0, n_keys: int = 1_000_000, seed: int = 0,
            backend: str = "jnp", mv_depth: int = 4, snapshot_age: int = 0,
            scan_frac: float = 0.0, scan_len: int = 0,
            arrival_rate: float = 0.0, queue_cap: int = 0,
            max_incarnations: int = 0):
    """Single grid point (one compiled run; prefer run_grid for grids)."""
    from repro.core import types as t
    from repro.core.engine import run

    wl = _make_workload(workload, scale=scale, n_keys=n_keys,
                        scan_frac=scan_frac, scan_len=scan_len)
    if arrival_rate > 0:
        queue_cap = queue_cap or 4 * lanes
        max_incarnations = max_incarnations or 8
    cfg = t.EngineConfig(
        cc=t.CC_IDS[cc_name], lanes=lanes, slots=wl.slots,
        n_records=wl.n_records, n_groups=wl.n_groups, n_cols=wl.n_cols,
        n_txn_types=wl.n_txn_types, granularity=gran, n_rings=wl.n_rings,
        backend=backend,
        mv_depth=mv_depth if t.CC_IDS[cc_name] in t.MV_CCS else 0,
        snapshot_age=snapshot_age, max_extent=wl.max_extent,
        arrival_rate=arrival_rate,
        queue_cap=queue_cap, max_incarnations=max_incarnations)
    from repro.core.backend import kernel_coverage
    t0 = time.time()
    res = run(cfg, wl, n_waves=waves, seed=seed)
    wall = time.time() - t0
    row = {
        "workload": workload, "cc": cc_name, "granularity": gran,
        "lanes": lanes, "waves": waves,
        "commits": res.commits, "aborts": res.aborts,
        "abort_rate": round(res.abort_rate, 4),
        "ro_commits": res.ro_commits, "ro_aborts": res.ro_aborts,
        "ro_abort_rate": round(res.ro_abort_rate, 4),
        "throughput": round(res.throughput, 4),
        "ext_events": res.ext_events,
        "wall_s": round(wall, 2),
        "backend": backend,
        **_device_fields(),
        "kernel_ops": kernel_coverage(backend, t.CC_IDS[cc_name]),
        "max_extent": cfg.max_extent,
    }
    if res.abort_causes is not None:
        row["abort_causes"] = {t.CAUSE_NAMES[i]: int(n)
                               for i, n in enumerate(res.abort_causes)}
    row.update(_cost_fields(cc_name, lanes, gran, wl.slots, wl.n_groups,
                            cfg.mv_depth, cfg.max_extent, cfg.bucket_size))
    if res.open_loop:
        row.update({
            "open_loop": True, "goodput": round(res.goodput, 4),
            "offered": res.offered, "admitted": res.admitted,
            "arrival_drops": res.arrival_drops,
            "inc_drops": res.inc_drops,
            "queued_final": res.queued_final,
            "p50_ttc_waves": res.p50_ttc, "p99_ttc_waves": res.p99_ttc,
        })
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=("tpcc", "ycsb"), default="tpcc")
    ap.add_argument("--cc", nargs="+",
                    default=["occ", "tictoc", "2pl", "swisstm", "adaptive",
                             "mvcc", "mvocc"])
    ap.add_argument("--granularity", choices=("coarse", "fine", "both"),
                    default="both")
    ap.add_argument("--lanes", type=int, nargs="+", default=[16, 64, 128])
    ap.add_argument("--waves", type=int, default=300)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--n-keys", type=int, default=1_000_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", choices=("jnp", "pallas"), default="jnp",
                    help="probe/commit substrate (pallas = TPU kernels, "
                         "interpret mode on CPU)")
    ap.add_argument("--mv-depth", type=int, default=4,
                    help="version-ring depth for mvcc/mvocc grids "
                         "(core/mvstore.py; ignored without an MV cc)")
    ap.add_argument("--snapshot-age", type=int, default=0,
                    help="pin MV reader snapshots this many waves in the "
                         "past (aged readers; ring reclamation aborts fire "
                         "once writers outrun the ring — requires an "
                         "all-mvcc/mvocc --cc list)")
    # None sentinels so the guards below detect flag *presence*, not just
    # non-default values (the --snapshot-age validation pattern).
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="open-loop traffic: expected Poisson arrivals per "
                         "wave (capped at the lane width); switches every "
                         "grid point from the closed-loop retry buffer to "
                         "the admission queue (DESIGN.md section 11)")
    ap.add_argument("--queue-cap", type=int, default=None,
                    help="admission-queue ring capacity (open loop only; "
                         "default 4x the widest --lanes)")
    ap.add_argument("--max-incarnations", type=int, default=None,
                    help="re-executions allowed per transaction before it "
                         "is dropped and counted (open loop only; "
                         "default 8)")
    ap.add_argument("--write-frac", type=float, default=None,
                    help="YCSB per-op write probability (default 0.5)")
    ap.add_argument("--ro-frac", type=float, default=None,
                    help="YCSB fraction of read-only transactions "
                         "(default 0)")
    ap.add_argument("--theta", type=float, default=None,
                    help="YCSB Zipf skew (default 0.9)")
    ap.add_argument("--scan-frac", type=float, default=None,
                    help="YCSB fraction of short-range-scan transactions "
                         "(YCSB-E style; adds the interval-read txn class "
                         "and switches the engine to extent-carrying ops)")
    ap.add_argument("--scan-len", type=int, default=None,
                    help="interval width of a scan op in records: the YCSB "
                         "scan class's range (default 8; needs "
                         "--scan-frac > 0) or, for TPC-C, switches on the "
                         "Order-status/Stock-level scan classes at this "
                         "stock window")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    ycsb_flags = (args.write_frac, args.ro_frac, args.theta)
    if args.workload == "tpcc" and any(v is not None for v in ycsb_flags):
        ap.error("--write-frac/--ro-frac/--theta shape the ycsb workload "
                 "only; TPC-C's mix is fixed by the standard")
    # Presence validation: each scan flag must name a scan class the
    # chosen workload actually has.  YCSB's class is switched by
    # --scan-frac (with --scan-len as its width); TPC-C's mix is fixed by
    # the standard, so only --scan-len (the Stock-level window) applies.
    if args.scan_frac is not None:
        if args.workload == "tpcc":
            ap.error("--scan-frac shapes the ycsb scan class only; TPC-C's "
                     "mix is fixed by the standard (--scan-len switches on "
                     "its Order-status/Stock-level scans)")
        if not 0 < args.scan_frac <= 1:
            ap.error(f"--scan-frac must be in (0, 1], got {args.scan_frac}")
    if args.scan_len is not None:
        if args.scan_len < 1:
            ap.error(f"--scan-len must be >= 1, got {args.scan_len}")
        if args.workload == "ycsb" and args.scan_frac is None:
            ap.error("--scan-len sizes the ycsb scan class: set "
                     "--scan-frac > 0 to add scan transactions to the mix")
    if args.snapshot_age:
        from repro.core import types as t
        if not all(t.CC_IDS[c] in t.MV_CCS for c in args.cc):
            ap.error("--snapshot-age only ages multi-version snapshots: "
                     "use it with an all-mvcc/mvocc --cc list")
    if args.arrival_rate is None:
        if args.queue_cap is not None or args.max_incarnations is not None:
            ap.error("--queue-cap/--max-incarnations shape the open-loop "
                     "admission queue only: set --arrival-rate > 0 (the "
                     "open-loop switch) to use them")
    elif args.arrival_rate <= 0:
        ap.error(f"--arrival-rate must be > 0 (got {args.arrival_rate}); "
                 "omit the flag for the closed-loop retry buffer")
    grans = {"coarse": (0,), "fine": (1,), "both": (0, 1)}[args.granularity]
    rows = run_grid(
        args.workload, args.cc, grans, args.lanes, args.waves,
        scale=args.scale, n_keys=args.n_keys, seed=args.seed,
        backend=args.backend, mv_depth=args.mv_depth,
        snapshot_age=args.snapshot_age,
        write_frac=(0.5 if args.write_frac is None
                    else args.write_frac),
        ro_frac=0.0 if args.ro_frac is None else args.ro_frac,
        theta=0.9 if args.theta is None else args.theta,
        scan_frac=args.scan_frac or 0.0,
        scan_len=args.scan_len or 0,
        arrival_rate=args.arrival_rate or 0.0,
        queue_cap=args.queue_cap or 0,
        max_incarnations=args.max_incarnations or 0)
    for r in rows:
        line = (f"{r['workload']} {r['cc']:9s} "
                f"{'fine' if r['granularity'] else 'coarse'} "
                f"T={r['lanes']:4d}: "
                f"thpt={r['throughput']:8.3f} txn/us  "
                f"abort={100*r['abort_rate']:6.2f}%")
        if r.get("open_loop"):
            line += (f"  goodput={r['goodput']:8.3f} txn/us  "
                     f"p50/p99 ttc={max(r['p50_ttc_waves']):g}/"
                     f"{max(r['p99_ttc_waves']):g} waves")
        print(line)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
