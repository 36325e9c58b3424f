"""analysis/txn_cost.py: the per-op roofline cost model — WAVE_OPS pinned
against the backend attribution tables (and the tictoc source), the
granularity switch visible as a byte difference, and the memory-bound
verdict on every chip in the shared peaks table."""
import pytest
import re

import repro.analysis.peaks as peaks
import repro.analysis.roofline as roofline
from repro.analysis.txn_cost import (DIST_WAVE_OPS, WAVE_OPS, WaveShape,
                                     op_costs, txn_cost, wave_cost)
from repro.core import backend as kb
from repro.core import types as t

SHAPE = WaveShape(lanes=64, slots=16, n_groups=2, granularity=1, mv_depth=4)


# ---------------------------------------------------- op-count pinning
def test_wave_ops_pin_backend_attribution():
    """WAVE_OPS mirrors each mechanism's backend call set.  CC_OPS
    (core/backend.py) is the attribution table benchmark rows record, and
    it additionally lists segment_count for every mechanism (the ENGINE's
    per-wave install-contention counter, not a mechanism op) — so the op
    SETS must agree modulo that one op.  A new backend call added to a
    cc/*.py wave lands in CC_OPS and fails here until the cost model
    learns its traffic."""
    assert set(WAVE_OPS) == set(t.CC_IDS), "one entry per mechanism"
    for name, ops in WAVE_OPS.items():
        want = set(kb.CC_OPS[t.CC_IDS[name]])
        assert set(ops) | {"segment_count"} == want | {"segment_count"}, \
            (name, sorted(ops), sorted(want))
        assert all(k >= 1 for k in ops.values()), name


def test_dist_wave_ops_pin_backend_attribution():
    assert set(DIST_WAVE_OPS["occ"]) == set(kb.DIST_OPS)
    assert set(DIST_WAVE_OPS["mvcc"]) == set(kb.DIST_MV_OPS)
    assert set(DIST_WAVE_OPS["mvocc"]) == set(kb.DIST_MVOCC_OPS)


def test_tictoc_counts_pin_source():
    """The docstring's example claim — tictoc's 2 ts_gather + 2
    segment_count + 3 ts_install_max — counted in cc/tictoc.py itself
    (those calls are all local to the module)."""
    src = open("src/repro/core/cc/tictoc.py").read()
    for op in ("ts_gather", "segment_count", "ts_install_max"):
        calls = len(re.findall(rf"be\.{op}\(", src))
        assert calls == WAVE_OPS["tictoc"][op], (op, calls)


def test_every_counted_op_has_a_descriptor():
    costs = op_costs(SHAPE)
    for table in (WAVE_OPS, DIST_WAVE_OPS):
        for name, ops in table.items():
            for op in ops:
                assert op in costs, (name, op)


# ---------------------------------------------------- cost-model shape
def test_granularity_is_a_byte_difference():
    """The paper's switch, in traffic terms: fine timestamps probe ONE
    group word where coarse probes the whole row — strictly fewer bytes
    per txn for every mechanism once n_groups > 1."""
    for cc in WAVE_OPS:
        fine = txn_cost(cc, SHAPE)
        coarse = txn_cost(cc, WaveShape(lanes=64, slots=16, n_groups=2,
                                        granularity=0, mv_depth=4))
        assert fine["bytes_per_txn"] < coarse["bytes_per_txn"], cc


def test_memory_bound_at_small_waves_on_every_chip():
    """Gather/scatter over uint32 words with a few compares per cell: at
    SMALL waves (where the all-pairs wave term is noise) intensity sits
    far below every ridge in the shared peaks table.  Large waves are the
    quad-dominance test below — the probe family's O(n^2) in-wave-min
    term changes the regime there."""
    small = WaveShape(lanes=8, slots=4, n_groups=2, granularity=1,
                      mv_depth=4)
    for chip in peaks.HW_PEAKS:
        for cc in WAVE_OPS:
            c = txn_cost(cc, small, chip=chip)
            assert c["bound"] == "memory", (chip, cc)
            assert 0.0 < c["roofline_frac"] < 0.05, (chip, cc, c)
        for cc in DIST_WAVE_OPS:
            c = txn_cost(cc, WaveShape(lanes=16, slots=8, n_shards=8,
                                       route_cap=64, mv_depth=4),
                         distributed=True, chip=chip)
            assert c["bound"] == "memory", (chip, cc)


def test_quadratic_wave_term_pinned():
    """ISSUE 9 satellite: the in-wave min of segment_count / claim_probe /
    wave_commit is an all-pairs same-cell compare — 2*n^2 flops on top of
    the linear per-cell work, pinned termwise here."""
    n, c = SHAPE.ops, SHAPE.cells
    costs = op_costs(SHAPE)
    assert costs["segment_count"].flops_per_call == 2.0 * n + 2.0 * n * n
    assert costs["claim_probe"].flops_per_call == 3.0 * n * c + 2.0 * n * n
    assert costs["wave_commit"].flops_per_call == 4.0 * n * c + 2.0 * n * n
    # The quadratic term is per-CALL, not per-cell: granularity must not
    # change it (only the linear table-word traffic narrows at fine).
    coarse = op_costs(WaveShape(lanes=64, slots=16, n_groups=2,
                                granularity=0))
    assert coarse["wave_commit"].flops_per_call == \
        4.0 * n * 2 + 2.0 * n * n


def test_quad_term_dominates_at_large_waves():
    """When it dominates (DESIGN.md section 5): large waves.  At n = T*K
    = 1024 the 2*n^2 all-pairs compares are >90% of the probe family's
    flops and intensity is a sizable fraction of the ridge — orders of
    magnitude above the small-wave regime, though the bytes still win on
    the chips in the peaks table."""
    n = SHAPE.ops
    wc = op_costs(SHAPE)["wave_commit"]
    assert 2.0 * n * n / wc.flops_per_call > 0.9
    big = txn_cost("occ", SHAPE)
    small = txn_cost("occ", WaveShape(lanes=8, slots=4, n_groups=2,
                                      granularity=1))
    assert big["roofline_frac"] > 0.25
    assert big["intensity"] > 20 * small["intensity"]


def test_probe_chain_launch_and_row_accounting():
    """ISSUE 9 acceptance: fused probe chain = ONE launch and ONE row
    visit per wave; the unfused chain's modeled DMA-row traffic is >= 2x
    for every probe-family mechanism."""
    from repro.analysis.txn_cost import PROBE_CHAIN_LAUNCHES, probe_chain
    for cc, launches in PROBE_CHAIN_LAUNCHES.items():
        fused = probe_chain(cc, SHAPE, fused=True)
        unfused = probe_chain(cc, SHAPE, fused=False)
        assert fused["launches_per_wave"] == 1, cc
        assert unfused["launches_per_wave"] == launches, cc
        assert fused["dma_rows_per_wave"] == SHAPE.ops, cc
        assert unfused["dma_rows_per_wave"] >= 2 * fused["dma_rows_per_wave"], cc
    try:
        probe_chain("mvcc", SHAPE)
    except KeyError as e:
        assert "mvcc" in str(e)
    else:
        raise AssertionError("mvcc is not probe-family")


def test_bytes_per_txn_lane_invariant():
    """All ops are per-(lane x slot) linear except the distributed route
    buffers, so LOCAL bytes-per-txn is lane-count invariant."""
    a = txn_cost("occ", WaveShape(lanes=8, slots=16))
    b = txn_cost("occ", WaveShape(lanes=256, slots=16))
    assert a["bytes_per_txn"] == b["bytes_per_txn"]


def test_mv_depth_raises_mv_gather_cost():
    shallow = wave_cost("mvcc", WaveShape(lanes=64, slots=16, mv_depth=1))
    deep = wave_cost("mvcc", WaveShape(lanes=64, slots=16, mv_depth=8))
    assert deep["bytes_per_wave"] > shallow["bytes_per_wave"]


def test_distributed_adds_route_and_verdict_traffic():
    s = WaveShape(lanes=64, slots=16, n_shards=8, route_cap=128)
    local = wave_cost("occ", s)
    dist = wave_cost("occ", s, distributed=True)
    assert dist["bytes_per_wave"] > local["bytes_per_wave"]
    assert "route_pack" in dist["ops"] and "verdict_pack" in dist["ops"]


def test_unknown_mechanism_raises():
    try:
        wave_cost("nope", SHAPE)
    except KeyError as e:
        assert "nope" in str(e)
    else:
        raise AssertionError("expected KeyError")


# ---------------------------------------------------- shared peaks table
def test_roofline_reexports_shared_peaks():
    """ISSUE 8 satellite: the hardware peaks moved to analysis/peaks.py;
    analysis/roofline.py must consume the SAME constants (single source of
    truth for both the collective model and the txn cost model)."""
    assert roofline.PEAK_FLOPS is peaks.PEAK_FLOPS
    assert roofline.HBM_BW is peaks.HBM_BW
    assert roofline.LINK_BW is peaks.LINK_BW
    d = peaks.HW_PEAKS[peaks.V5E]
    assert peaks.PEAK_FLOPS == d["peak_flops"]
    assert peaks.ridge(peaks.V5E) == (d["peak_flops"] / d["hbm_bw"])
    with pytest.raises(ValueError, match="no peak table entry"):
        peaks.ridge("cpu")
