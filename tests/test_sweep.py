"""The vmapped sweep runner: one jitted XLA program per benchmark grid
(core/engine.py sweep), padded-lane masking, and the txn_bench row schema."""
import dataclasses

import numpy as np
import pytest

from repro.core import types as t
from repro.core.engine import lane_buckets, run, sweep
from repro.workloads import YCSBWorkload

WL = YCSBWorkload.make(n_keys=512)


def base_cfg(backend="jnp"):
    return t.EngineConfig(cc=t.CC_OCC, lanes=8, slots=WL.slots,
                          n_records=WL.n_records, n_groups=WL.n_groups,
                          n_cols=WL.n_cols, n_txn_types=WL.n_txn_types,
                          n_rings=WL.n_rings, backend=backend)


def test_sweep_full_grid_shape_and_attempts():
    """granularity x {occ, tictoc} x 3 lane counts in a single jitted call
    (ISSUE acceptance criterion)."""
    lanes = (4, 8, 16)
    pts = sweep(base_cfg(), WL, 5, ccs=[t.CC_OCC, t.CC_TICTOC],
                grans=(0, 1), lane_counts=lanes, seeds=(0,))
    assert len(pts) == 2 * 2 * 3
    for p in pts:
        # Inactive padding lanes are masked out of all accounting.
        assert p.commits + p.aborts == p.lanes * 5
    coords = {(p.cc, p.granularity, p.lanes) for p in pts}
    assert len(coords) == 12


def test_sweep_matches_run_at_max_lanes():
    """A grid point at T == max(lane_counts) is bit-identical to run()."""
    T = 16
    pts = sweep(base_cfg(), WL, 8, ccs=[t.CC_OCC, t.CC_TICTOC],
                grans=(0, 1), lane_counts=(4, T), seeds=(3,))
    for p in pts:
        if p.lanes != T:
            continue
        cfg = dataclasses.replace(base_cfg(), cc=p.cc,
                                  granularity=p.granularity, lanes=T)
        r = run(cfg, WL, n_waves=8, seed=3)
        assert (r.commits, r.aborts) == (p.commits, p.aborts), \
            (p.cc, p.granularity)
        assert r.throughput == pytest.approx(p.throughput)
        assert r.ext_events == p.ext_events


def test_lane_buckets():
    """Greedy grouping bounds padding waste to the ratio; None = one bucket
    (legacy pad-to-global-max)."""
    assert lane_buckets((16, 64, 128), 2.0) == [[16], [64, 128]]
    assert lane_buckets((8, 16, 32, 64, 96, 128), 2.0) == \
        [[8, 16], [32, 64], [96, 128]]
    assert lane_buckets((16, 128), 8.0) == [[16, 128]]
    assert lane_buckets((16, 64, 128), None) == [[16, 64, 128]]
    assert lane_buckets((128, 16, 64), 2.0) == [[16], [64, 128]]  # sorted


def test_sweep_matches_run_at_every_bucket_max():
    """Bucketed padding strengthens the bit-identity guarantee: EVERY point
    sitting at its bucket's max lane count equals a standalone run()."""
    lanes = (4, 16)   # ratio 2 puts these in separate buckets
    assert lane_buckets(lanes, 2.0) == [[4], [16]]
    pts = sweep(base_cfg(), WL, 6, ccs=[t.CC_OCC], grans=(1,),
                lane_counts=lanes, seeds=(2,))
    for p in pts:
        cfg = dataclasses.replace(base_cfg(), cc=p.cc,
                                  granularity=p.granularity, lanes=p.lanes)
        r = run(cfg, WL, n_waves=6, seed=2)
        assert (r.commits, r.aborts) == (p.commits, p.aborts), p.lanes


def test_sweep_bucketing_preserves_grid_order():
    """Bucketed execution must not permute the returned point grid."""
    pts = sweep(base_cfg(), WL, 3, ccs=[t.CC_OCC, t.CC_TICTOC], grans=(0, 1),
                lane_counts=(4, 8, 16), seeds=(0, 1))
    coords = [(p.cc, p.granularity, p.lanes, p.seed) for p in pts]
    want = [(cc, g, T, sd)
            for g in (0, 1) for cc in (t.CC_OCC, t.CC_TICTOC)
            for T in (4, 8, 16) for sd in (0, 1)]
    assert coords == want


def test_sweep_seeds_axis():
    pts = sweep(base_cfg(), WL, 5, ccs=[t.CC_OCC], grans=(1,),
                lane_counts=(8,), seeds=(0, 1, 2))
    assert len(pts) == 3
    assert {p.seed for p in pts} == {0, 1, 2}
    # different seeds draw different workloads
    assert len({p.commits for p in pts}) > 1 or len(
        {p.throughput for p in pts}) > 1


def test_sweep_pallas_backend_parity():
    a = sweep(base_cfg("jnp"), WL, 5, ccs=[t.CC_OCC], grans=(0, 1),
              lane_counts=(8,), seeds=(0,))
    b = sweep(base_cfg("pallas"), WL, 5, ccs=[t.CC_OCC], grans=(0, 1),
              lane_counts=(8,), seeds=(0,))
    for pa, pb in zip(a, b):
        assert (pa.commits, pa.aborts) == (pb.commits, pb.aborts)


def test_txn_bench_grid_schema():
    """txn_bench --json schema: the seed keys plus backend attribution and
    the observability fields (per-cause aborts + analytic cost model)."""
    from repro.launch.txn_bench import run_grid
    rows = run_grid("ycsb", ["occ", "tictoc"], (0, 1), [4, 8], 4,
                    n_keys=512, backend="jnp")
    assert len(rows) == 2 * 2 * 2
    want = {"workload", "cc", "granularity", "lanes", "waves", "commits",
            "aborts", "abort_rate", "ro_commits", "ro_aborts",
            "ro_abort_rate", "throughput", "ext_events", "wall_s",
            "backend", "kernel_ops", "abort_causes", "bytes_per_txn",
            "flops_per_txn", "roofline_frac", "roofline_bound",
            "roofline_chip", "launches_per_wave", "dma_rows_per_wave",
            "dma_rows_per_wave_unfused", "max_extent", "platform",
            "device_kind", "device_count"}
    for r in rows:
        assert set(r) == want
        assert r["backend"] == "jnp"
        assert r["commits"] + r["aborts"] == r["lanes"] * r["waves"]
        assert sum(r["abort_causes"].values()) == r["aborts"]
        assert all(v == "xla" for v in r["kernel_ops"].values())


def test_txn_bench_kernel_ops_attribution():
    """Pallas rows must name the ops that actually ran as kernels, per
    mechanism: the probe family (OCC, TicToc, 2PL, SwissTM, Adaptive) runs
    the FUSED wave_commit megakernel — claim install, probe, verdicts, and
    version bumps in one launch (ISSUE 9) — while AutoGran keeps
    validate_dual and the multi-version pair keeps its claim channels +
    mv ring ops."""
    from repro.core.backend import dist_kernel_coverage, kernel_coverage
    occ_ops = kernel_coverage("pallas", t.CC_OCC)
    tic_ops = kernel_coverage("pallas", t.CC_TICTOC)
    ag_ops = kernel_coverage("pallas", t.CC_AUTOGRAN)
    mv_ops = kernel_coverage("pallas", t.CC_MVCC)
    # every mechanism's wave also counts same-row contention through
    # segment_count (the engine cost model) — no XLA sort on the pallas
    # path; every scan-validating mechanism (all but mvcc) also runs the
    # iterate_validate interval pass (ISSUE 10)
    assert occ_ops == {"wave_commit": "pallas",
                       "iterate_validate": "pallas",
                       "commit_install": "pallas",
                       "segment_count": "pallas"}
    assert tic_ops == {"wave_commit": "pallas",
                       "iterate_validate": "pallas",
                       "ts_gather": "pallas",
                       "ts_install_max": "pallas", "segment_count": "pallas"}
    assert ag_ops == {"validate_dual": "pallas",
                      "iterate_validate": "pallas",
                      "claim_scatter": "pallas",
                      "commit_install": "pallas", "segment_count": "pallas"}
    assert mv_ops == {"validate": "pallas", "claim_scatter": "pallas",
                      "mv_gather": "pallas", "mv_install": "pallas",
                      "segment_count": "pallas"}
    assert kernel_coverage("pallas", t.CC_MVOCC) == dict(
        mv_ops, iterate_validate="pallas")
    for cc in (t.CC_2PL, t.CC_SWISS, t.CC_ADAPTIVE):
        assert kernel_coverage("pallas", cc) == occ_ops
    # the distributed wave's shard-local coverage (benchmarks/txn_scaling):
    # occ bumps versions on the return trip, the MV pair gathers snapshots
    # and publishes into the sharded ring instead; both ship verdicts and
    # commit bits bit-packed through the verdict_pack/verdict_unpack pair
    assert dist_kernel_coverage("pallas") == {
        "route_pack": "pallas", "verdict_pack": "pallas",
        "verdict_unpack": "pallas", "wave_commit": "pallas",
        "iterate_validate": "pallas", "commit_install": "pallas"}
    dist_mv = {"route_pack": "pallas", "verdict_pack": "pallas",
               "verdict_unpack": "pallas", "claim_probe": "pallas",
               "mv_gather": "pallas", "mv_install": "pallas"}
    # mvcc never validates intervals (snapshot cut); mvocc adds the
    # owner-side interval pass
    assert dist_kernel_coverage("pallas", "mvcc") == dist_mv
    assert dist_kernel_coverage("pallas", "mvocc") == dict(
        dist_mv, iterate_validate="pallas")
    assert set(dist_kernel_coverage("jnp").values()) == {"xla"}
    assert set(dist_kernel_coverage("jnp", "mvcc").values()) == {"xla"}
