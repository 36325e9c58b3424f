"""The benchmark's arithmetic on hand-made inputs: the trace reduction,
commit latency, the roofline bytes and peak, the copied generator; and
what ``load_cell`` refuses."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import latency, roofline, spec, trace_reduce
from bench.tests import tiny

_RAW = json.loads((Path(__file__).parent / "data" /
                   "trace_small.json").read_text())
PLAIN_TRACE = {"window": _RAW["window"], "devices": _RAW["devices"],
               "host": _RAW["host"],
               "scopes": trace_reduce.hlo_scopes(_RAW["hlo"])}


def test_hlo_scopes_read_op_metadata():
    s = PLAIN_TRACE["scopes"]
    assert s["custom-call.2"].endswith("repro:wave_commit/pallas_call")
    assert "repro:cost" in s["fusion.3"] and "all-to-all.4" in s


def test_trace_reduction_busy_scopes_and_exposed_collective():
    r = trace_reduce.reduce(PLAIN_TRACE)
    assert r["window_s"] == pytest.approx(10e-6)
    # device 0: [1000, 7000) and [8000, 10000); device 1: [2000, 6000)
    # and the part of [500, 1500) inside the window.
    assert r["busy_s"] == pytest.approx((8000 + 4500) / 2 * 1e-9)
    assert r["scope_s"]["repro:validate"] == pytest.approx(
        (5000 + 4000) / 2 * 1e-9)
    assert r["scope_s"]["repro:wave_commit"] == pytest.approx(1500e-9)
    assert r["scope_s"]["repro:cost"] == pytest.approx((1000 + 500) / 2
                                                      * 1e-9)
    # the all-to-all runs alone on device 0 for [8000, 8500) only
    assert r["exposed_collective_s"] == pytest.approx(250e-9)
    assert r["has_collective"]


def test_breakdown_names_ops_and_idle_gaps_by_host_span():
    b = trace_reduce.breakdown(PLAIN_TRACE)
    ops = dict(b["device_ops"])
    assert ops["fusion.1"] == pytest.approx(3000e-9)
    assert b["device_ops"][0][0] == "fusion.1"
    gaps = sorted(b["idle_gaps"], key=lambda g: g[0])
    assert gaps == [["bench:read", pytest.approx(1000e-9)],
                    ["bench:window", pytest.approx(1000e-9)]]


def test_interval_subtract():
    a = [[0, 10], [20, 30]]
    b = [[2, 4], [8, 22], [25, 26]]
    assert trace_reduce.subtract(a, b) == (2 + 4) + (3 + 4)


def test_commit_latency_counts_retries_and_pending():
    # 4 waves of 2 lanes in a 0.8 s window: 0.2 s a wave.
    commit = np.array([[1, 0], [1, 1], [0, 1], [0, 1]], bool)
    age = np.array([[0, 1], [0, 2], [0, 0], [1, 0]])
    lat = latency.latencies(commit, age, 0.8)
    # lane 1's first transaction began two waves before its commit in
    # wave 1, one of them before the window; lane 0's last one is pending
    # after two attempts.
    want = [0.2, 0.2, 0.6, 0.2, 0.2, 0.4]
    assert sorted(lat) == pytest.approx(sorted(want))
    assert latency.p95_ms(lat) == pytest.approx(
        np.percentile(want, 95) * 1e3)


def _touched_words(cc, key, group, kind, commit, G, D):
    """Every (table, index) word a wave's operations must read or write,
    enumerated one operation at a time."""
    reads, writes = set(), set()
    T, K = key.shape
    upd = [any(kind[i, k] in (2, 3) and key[i, k] >= 0 for k in range(K))
           for i in range(T)]
    for i in range(T):
        for k in range(K):
            r, g, op = int(key[i, k]), int(group[i, k]), int(kind[i, k])
            if r < 0 or op == 0:
                continue
            cell = (r, g)
            if cc == "occ":
                reads.add(("claim_w", cell))
                if op in (2, 3):
                    writes.add(("claim_w", cell))
                    if commit[i]:
                        reads.add(("wts", cell))
                        writes.add(("wts", cell))
            else:
                if op in (2, 3):
                    reads.add(("claim_w", cell))
                    writes.add(("claim_w", cell))
                    reads.add(("claim_r", cell))
                    if op == 2:
                        writes.add(("claim_r", cell))
                    if commit[i]:
                        reads.add(("head", r))
                        writes.add(("head", r))
                        for gg in range(G):
                            writes.add(("row", r, gg))
                else:
                    for d in range(D):
                        reads.add(("ring", cell, d))
                    if upd[i]:
                        reads.add(("claim_w", cell))
    return 4 * (len(reads) + len(writes))


@pytest.mark.parametrize("cc", ["occ", "mvocc"])
def test_roofline_bytes_never_exceed_what_the_ops_must_touch(cc):
    rng = np.random.default_rng(3)
    for _ in range(20):
        key = rng.integers(-1, 12, (6, 5))
        group = rng.integers(0, 2, (6, 5))
        kind = rng.integers(0, 4, (6, 5))
        commit = rng.random(6) < 0.5
        b = roofline.validate_bytes(cc, key, group, kind, commit,
                                    n_groups=2, fine=True, mv_depth=4)
        assert b == _touched_words(cc, key, group, kind, commit, 2, 4)


def test_hbm_peak_is_for_v5e_only():
    assert roofline.hbm_peak("TPU v5 lite") == 819e9
    with pytest.raises(ValueError):
        roofline.hbm_peak("TPU v4")


def test_generator_copy_draws_the_programs_keys():
    import jax
    from bench import gen_ycsb
    from repro.workloads.zipf import ZipfSampler
    rng = jax.random.PRNGKey(7)
    ours = gen_ycsb.Zipf.make(100_000, 0.9).sample(rng, (64, 16))
    theirs = ZipfSampler.make(100_000, 0.9).sample(rng, (64, 16))
    np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))


def test_tpu_event_names_and_control_flow_ops():
    name = trace_reduce.op_name(
        "%fusion.3 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop")
    assert name == "fusion.3"
    assert trace_reduce._CONTAINER.match("while.129")
    assert not trace_reduce._CONTAINER.match("while_body_fusion.2")
    assert not trace_reduce._CONTAINER.match("wave_commit.4")
    # the collective and the ops jax.lax.all_to_all lowers to around it
    assert trace_reduce._is_exchange("all-to-all.3")
    assert trace_reduce._is_exchange("all_to_all.37")
    assert not trace_reduce._is_exchange("fusion.137")


@pytest.mark.parametrize("priority,ok", [(None, True), ("random", True),
                                         ("age", True), ("oldest", False)])
def test_load_cell_checks_the_priority_rule(tmp_path, priority, ok):
    config = dict(tiny.CONFIGS["mvocc"])
    if priority is not None:
        config["priority"] = priority
    name = tiny.make_root(tmp_path, config, "ycsb-a")
    if ok:
        assert spec.load_cell(name, tmp_path).config.get(
            "priority", "random") == (priority or "random")
    else:
        with pytest.raises(ValueError, match="priority"):
            spec.load_cell(name, tmp_path)
