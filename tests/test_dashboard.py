"""benchmarks/perf_dashboard.py: JSON-row aggregation into the markdown
perf dashboard (peak-point selection, kernel-op attribution cells, the
distributed txn_scaling section, and malformed-row resilience)."""
import json

from benchmarks.perf_dashboard import (_causes_cell, _ops_cell, load_rows,
                                       main, render_markdown)

MECH_ROWS = [
    {"workload": "ycsb", "cc": "occ", "granularity": 1, "lanes": 16,
     "throughput": 10.0, "abort_rate": 0.10, "backend": "pallas",
     "kernel_ops": {"claim_probe": "pallas", "commit_install": "pallas",
                    "segment_count": "pallas"}},
    {"workload": "ycsb", "cc": "occ", "granularity": 1, "lanes": 64,
     "throughput": 25.5, "abort_rate": 0.20, "backend": "pallas",
     "kernel_ops": {"claim_probe": "pallas", "commit_install": "pallas",
                    "segment_count": "pallas"}},
    {"workload": "ycsb", "cc": "tictoc", "granularity": 0, "lanes": 64,
     "throughput": 18.0, "abort_rate": 0.30, "backend": "jnp",
     "kernel_ops": {"claim_probe": "xla", "ts_gather": "xla",
                    "ts_install_max": "xla", "segment_count": "xla"}},
]
DIST_ROWS = [
    {"shards": 0, "commits": 900, "waves_per_s": 50.0,
     "coll_bytes_per_wave": 0, "backend": "jnp", "kernel_ops": {}},
    {"shards": 8, "cc": "mvcc", "commits": 850, "waves_per_s": 12.5,
     "ro_commits": 120, "ro_aborts": 3,
     "coll_bytes_per_wave": 65536, "backend": "pallas",
     "kernel_ops": {"route_pack": "pallas", "claim_probe": "pallas",
                    "mv_gather": "pallas", "mv_install": "pallas"}},
]


def test_ops_cell_attribution():
    assert _ops_cell({}) == "—"
    assert _ops_cell({"a": "pallas", "b": "pallas"}) == "2/2 pallas"
    assert _ops_cell({"a": "xla", "b": "xla"}) == "xla"
    # a mixed map means a partial fallback — rendered loudly, per op
    assert _ops_cell({"a": "pallas", "b": "xla"}) == "a:pallas, b:xla"


def test_render_picks_peak_point_per_group():
    rows = [dict(r, _src="BENCH_a.json") for r in MECH_ROWS]
    md = render_markdown(rows, [])
    # rows predating the cost model / cause taxonomy / megakernel / scan
    # era render '—' in the abort-causes, scan, B/txn, flop/txn,
    # roofline, launches/wave, and DMA-rows/wave columns
    assert "| ycsb | occ | fine | pallas | 25.500 | 64 | 20.00% " \
           "| — | — | — | — | — | — | — | 3/3 pallas | BENCH_a.json |" in md
    assert "10.000" not in md                     # dominated point dropped
    assert "| ycsb | tictoc | coarse | jnp | 18.000 | 64 | 30.00% " \
           "| — | — | — | — | — | — | — | xla | BENCH_a.json |" in md


def test_render_distributed_section():
    rows = [dict(r, _src="txn_scaling.json") for r in DIST_ROWS]
    md = render_markdown([], rows)
    # rows without the cc / read-only / pipeline-wire fields (pre-MV,
    # pre-pipeline txn_scaling files) default to occ and render unknown
    # splits as '?' and unknown depth/wire columns as '—'
    assert "| 0 | occ | — | 50.0 | 900 | ? | ? | 0.0 | — | — | — | jnp " \
           "| — | txn_scaling.json |" in md
    assert "| 8 | mvcc | — | 12.5 | 850 | 120 | 3 | 64.0 | — | — | — " \
           "| pallas | 4/4 pallas | txn_scaling.json |" in md


def test_render_distributed_depth_and_wire_columns():
    """Pipelined txn_scaling rows carry pipeline_depth + the modeled wire
    split; the dashboard renders depth, wire KiB/wave, and the packed vs
    legacy verdict bytes side by side, and orders depth-1 before depth-2
    within one (source, cc, shards) group."""
    base = {"shards": 8, "cc": "occ", "commits": 800, "waves_per_s": 100.0,
            "ro_commits": 0, "ro_aborts": 0, "coll_bytes_per_wave": 16384,
            "backend": "jnp", "kernel_ops": {}, "_src": "txn_scaling.json",
            "wire_bytes_per_wave": 18432, "route_bytes_per_wave": 16384,
            "verdict_bytes_per_wave": 1024, "commit_bytes_per_wave": 1024,
            "verdict_bytes_per_wave_legacy": 4096}
    rows = [dict(base, pipeline_depth=2, waves_per_s=150.0),
            dict(base, pipeline_depth=1)]
    md = render_markdown([], rows)
    assert "| 8 | occ | 1 | 100.0 | 800 | 0 | 0 | 16.0 | 18.0 " \
           "| 1024 / 4096 | — | jnp | — | txn_scaling.json |" in md
    assert "| 8 | occ | 2 | 150.0 | 800 | 0 | 0 | 16.0 | 18.0 " \
           "| 1024 / 4096 | — | jnp | — | txn_scaling.json |" in md
    assert md.index("| 8 | occ | 1 |") < md.index("| 8 | occ | 2 |")
    # the legend explains the columns
    assert "verdict B/wave" in md and "depth" in md


def test_causes_cell_shapes():
    assert _causes_cell(None) == "—"
    assert _causes_cell("bogus") == "—"
    assert _causes_cell({"read_val": 56, "ww": 0}) == "read_val:56"
    # txn_scaling rows store the code-ordered 6-list
    assert _causes_cell([0, 3, 0, 0, 9, 2]) == "capacity:3 ww:9 read_val:2"
    assert _causes_cell({"read_val": 0}) == "none"
    assert _causes_cell({"read_val": "junk"}) == "—"


def test_render_mech_cost_and_cause_columns():
    """Rows carrying the ISSUE 8 observability fields render the per-cause
    breakdown, the analytic B/txn + flop/txn, and the roofline fraction."""
    r = dict(MECH_ROWS[1], _src="BENCH_a.json",
             abort_causes={"inc_cap": 0, "capacity": 0, "stale_snapshot": 0,
                           "lock_wound": 0, "ww": 0, "read_val": 56},
             bytes_per_txn=512.0, flops_per_txn=128.0,
             roofline_frac=0.00104, roofline_bound="memory",
             roofline_chip="TPU v5 lite")
    md = render_markdown([r], [])
    assert "| ycsb | occ | fine | pallas | 25.500 | 64 | 20.00% " \
           "| read_val:56 | — | 512 | 128 | 0.10% (memory) | — | — " \
           "| 3/3 pallas | BENCH_a.json |" in md


def test_render_mech_fusion_columns():
    """Probe-family rows carrying the ISSUE 9 megakernel fields render
    launches/wave and DMA rows/wave with the modeled cut vs unfused."""
    r = dict(MECH_ROWS[1], _src="BENCH_a.json",
             launches_per_wave=1, dma_rows_per_wave=1024,
             dma_rows_per_wave_unfused=3072)
    md = render_markdown([r], [])
    assert "| 20.00% | — | — | — | — | — | 1 | 1024 (/3 vs unfused) " \
           "| 3/3 pallas | BENCH_a.json |" in md
    assert "launches/wave" in md and "DMA rows/wave" in md


def test_render_distributed_dedupes_repeat_runs():
    """Regression (ISSUE 8 satellite): txn_scaling appends a row per run,
    so three runs of one config stacked three near-identical rows in the
    report.  The dashboard keys by (cc, shards, depth, backend) and keeps
    only the latest (last-in-file) row; distinct depths/backends all
    survive."""
    base = {"shards": 1, "cc": "mvcc", "pipeline_depth": 1, "commits": 800,
            "ro_commits": 0, "ro_aborts": 0, "coll_bytes_per_wave": 0,
            "backend": "jnp", "kernel_ops": {}, "_src": "txn_scaling.json"}
    rows = [dict(base, waves_per_s=10.0), dict(base, waves_per_s=20.0),
            dict(base, waves_per_s=30.0),             # latest run wins
            dict(base, pipeline_depth=2, waves_per_s=44.0),
            dict(base, backend="pallas", waves_per_s=55.0)]
    md = render_markdown([], rows)
    dup = [ln for ln in md.splitlines()
           if ln.startswith("| 1 | mvcc | 1 |") and "| jnp |" in ln]
    assert len(dup) == 1, md
    assert "| 30.0 |" in dup[0]
    assert "| 10.0 |" not in md and "| 20.0 |" not in md
    assert "| 44.0 |" in md and "| 55.0 |" in md     # other configs kept
    assert "latest run wins" in md                   # legend explains it


def test_render_distributed_open_loop_rows_disambiguated():
    """The open-loop row family shares (cc, shards, depth) with the
    closed-loop rows; mode + granularity join the dedupe key and the cc
    cell so the three rows of one config no longer render as an
    identical-looking stack."""
    base = {"shards": 1, "cc": "mvcc", "pipeline_depth": 1, "commits": 800,
            "waves_per_s": 73.8, "ro_commits": 0, "ro_aborts": 0,
            "coll_bytes_per_wave": 0, "backend": "jnp", "kernel_ops": {},
            "_src": "txn_scaling.json"}
    rows = [base,
            dict(base, mode="open_loop", granularity=0, waves_per_s=1.4),
            dict(base, mode="open_loop", granularity=1, waves_per_s=1.6)]
    md = render_markdown([], rows)
    assert "| 1 | mvcc | 1 | 73.8 |" in md
    assert "| 1 | mvcc open/coarse | 1 | 1.4 |" in md
    assert "| 1 | mvcc open/fine | 1 | 1.6 |" in md


def test_render_distributed_causes_column():
    r = dict(DIST_ROWS[1], _src="txn_scaling.json",
             abort_causes=[0, 60, 0, 0, 159, 0])
    md = render_markdown([], [r])
    assert "| capacity:60 ww:159 | pallas |" in md


def test_string_throughput_compares_numerically():
    """Regression (ISSUE 6 satellite): CSV-converted/hand-edited bench
    files store throughput as STRINGS — "0.9" vs "12.3" must compare
    numerically (12.3 wins), not lexically ("0.9" > "12.3")."""
    rows = [
        {"workload": "ycsb", "cc": "occ", "granularity": 1, "lanes": 8,
         "throughput": "0.9", "abort_rate": 0.1, "backend": "jnp",
         "kernel_ops": {}, "_src": "BENCH_csv.json"},
        {"workload": "ycsb", "cc": "occ", "granularity": 1, "lanes": 64,
         "throughput": "12.3", "abort_rate": 0.2, "backend": "jnp",
         "kernel_ops": {}, "_src": "BENCH_csv.json"},
    ]
    md = render_markdown(rows, [])
    assert "| 12.300 | 64 |" in md          # the numeric peak
    assert "| 0.900 | 8 |" not in md        # lexical "winner" dropped
    assert "## Skipped rows" not in md      # numeric strings aren't skipped


def test_string_throughput_mixed_with_numeric():
    """A numeric 5.0 row and a string "12.3" row rank on one scale."""
    rows = [dict(MECH_ROWS[0], throughput=5.0, _src="a.json"),
            dict(MECH_ROWS[0], lanes=32, throughput="12.3", _src="a.json")]
    md = render_markdown(rows, [])
    assert "| 12.300 | 32 |" in md
    assert "| 5.000 |" not in md


OPEN_ROWS = [
    {"workload": "ycsb", "cc": "occ", "granularity": 1, "lanes": 64,
     "throughput": 9.0, "abort_rate": 0.2, "backend": "jnp",
     "kernel_ops": {}, "open_loop": True, "goodput": 7.25,
     "p50_ttc_waves": [1.0], "p99_ttc_waves": [4.0, 6.0],
     "inc_drops": 12, "arrival_drops": 3, "arrival_rate": 48.0},
    {"workload": "ycsb", "cc": "occ", "granularity": 1, "lanes": 8,
     "throughput": 2.0, "abort_rate": 0.1, "backend": "jnp",
     "kernel_ops": {}, "open_loop": True, "goodput": "1.5",
     "p50_ttc_waves": [1.0], "p99_ttc_waves": [2.0],
     "inc_drops": 0, "arrival_drops": 0, "arrival_rate": 6.0},
]


def test_render_open_loop_latency_section():
    """Open-loop rows get their own latency section: peak-GOODPUT point
    per group (string goodputs coerced too), per-class ttc cells."""
    rows = [dict(r, _src="open_loop.json") for r in OPEN_ROWS]
    md = render_markdown(rows, [])
    assert "## Open-loop latency" in md
    assert "| ycsb | occ | fine | jnp | 7.250 | 1 | 4/6 | 12 | 3 " \
           "| open_loop.json |" in md
    assert "1.500" not in md               # dominated (and string) goodput
    # closed-loop section still renders these rows by throughput
    assert "| 9.000 | 64 |" in md


def test_no_open_loop_rows_no_section():
    md = render_markdown([dict(r, _src="a.json") for r in MECH_ROWS], [])
    assert "## Open-loop latency" not in md


# ------------------------------------------------ malformed-row resilience
def test_truncated_mech_row_is_skipped_with_warning():
    """Regression (ISSUE 5 satellite): a partial row — e.g. the tail of a
    killed bench run — must not abort the whole dashboard; it is skipped
    and called out in the report."""
    rows = [dict(r, _src="BENCH_a.json") for r in MECH_ROWS]
    rows.append({"workload": "ycsb", "cc": "occ", "_src": "BENCH_cut.json"})
    rows.append({"cc": "occ", "throughput": "fast?",
                 "_src": "BENCH_bad.json"})
    md = render_markdown(rows, [])
    assert "25.500" in md                          # good rows still render
    assert "## Skipped rows (2)" in md
    assert "`BENCH_cut.json`: mechanism row: missing/non-numeric " \
           "'throughput'" in md
    assert "`BENCH_bad.json`" in md


def test_truncated_dist_row_is_skipped_with_warning():
    rows = [dict(r, _src="txn_scaling.json") for r in DIST_ROWS]
    rows.append({"shards": None, "commits": 7, "_src": "txn_cut.json"})
    md = render_markdown([], rows)
    assert "| 8 | mvcc |" in md                    # good rows still render
    assert "## Skipped rows (1)" in md
    assert "`txn_cut.json`: distributed row: missing/non-numeric " \
           "'shards'" in md


def test_only_bad_rows_still_renders_warnings():
    md = render_markdown([{"cc": "x", "_src": "a.json"}], [])
    assert "## Skipped rows (1)" in md
    assert "No benchmark rows found" not in md


def test_main_end_to_end(tmp_path):
    """Glob -> split -> render -> write: the CLI path, on a synthetic
    BENCH file mixing both row shapes plus an unreadable file and a
    truncated row."""
    bench = tmp_path / "BENCH_mix.json"
    bench.write_text(json.dumps(
        MECH_ROWS + DIST_ROWS
        + [{"cc": "occ", "workload": "ycsb"}]))       # truncated row
    (tmp_path / "BENCH_broken.json").write_text("{not json")
    out = tmp_path / "reports" / "perf_dashboard.md"
    assert main([str(tmp_path / "BENCH_*.json"), "--out", str(out)]) == 0
    md = out.read_text()
    assert "## Mechanisms" in md and "## Distributed engine" in md
    assert "25.500" in md and "route_pack" not in md  # ops compressed
    assert "## Skipped rows (1)" in md
    mech, dist = load_rows((str(tmp_path / "BENCH_*.json"),))
    assert len(mech) == 4 and len(dist) == 2          # truncated row loads…
    md2 = render_markdown(mech, dist)                 # …and only warns
    assert "## Skipped rows (1)" in md2


def test_main_no_rows(tmp_path):
    out = tmp_path / "dash.md"
    assert main([str(tmp_path / "nothing_*.json"), "--out", str(out)]) == 0
    assert "No benchmark rows found" in out.read_text()


def test_pre_scan_rows_render_unchanged():
    """Regression (ISSUE 10 satellite): JSON rows written before the
    interval era — no max_extent / scan_frac / scan_len, a 6-cause
    abort_causes dict without 'phantom' — must render with a '—' scan
    cell and NO skipped-row warning."""
    r = dict(MECH_ROWS[1], _src="BENCH_pr9.json",
             abort_causes={"inc_cap": 0, "capacity": 0,
                           "stale_snapshot": 0, "lock_wound": 0,
                           "ww": 2, "read_val": 56})
    md = render_markdown([r], [])
    assert "## Skipped rows" not in md
    assert "| ww:2 read_val:56 | — |" in md
    # the code-ordered 6-list (pre-phantom txn_scaling files) also parses
    assert _causes_cell([0, 0, 0, 0, 2, 56]) == "ww:2 read_val:56"


def test_scan_rows_render_and_keep_own_peak_group():
    """A scan-mix row shares (workload, cc, gran, backend) with a faster
    point row; max_extent joins the peak-group key so BOTH render — the
    scan row with its 'ext=L (frac x len)' cell."""
    point = dict(MECH_ROWS[1], _src="BENCH_a.json", throughput=25.5)
    scan = dict(MECH_ROWS[1], _src="scan_mix.json", throughput=9.25,
                max_extent=16, scan_frac=0.5, scan_len=16,
                abort_causes={"read_val": 3, "phantom": 41})
    md = render_markdown([point, scan], [])
    assert "| 25.500 | 64 | " in md                 # point peak survives
    assert "| 9.250 | 64 | " in md                  # scan row not dominated
    assert "| ext=16 (0.5×16) |" in md
    assert "phantom:41" in md
