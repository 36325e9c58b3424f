"""Beyond-paper: distributed txn-engine scaling (the paper's section 5:
"perform similar evaluations on distributed CC mechanisms").

Runs the shard_map wave on 1/2/4/8 devices (same *global* lane and
record counts), measuring committed txns per second of wall time and the
per-wave collective bytes — the weak-scaling story of the routed engine —
for BOTH the single-version mechanism (occ) and the sharded multi-version
ring (mvcc: snapshot reads + first-committer-wins over the distributed
version ring of core/mvstore.py).  A ``shards=0`` anchor row first runs
the single-device engine through the vmapped ``sweep()`` grid runner at
the same global lane count, so the table reads "local engine vs N-shard
routed engine".  ``REPRO_TXN_BACKEND`` ("jnp" | "pallas") selects the
kernel-backend surface for BOTH engines — the distributed wave routes its
shard-local route/claim/probe/gather/install through core/backend.py like
the local one — and every row records the resolved backend, the per-op
kernel attribution, the read-only commit/abort split, and the per-cause
abort breakdown the distributed stats vector carries (core/distributed.py
STATS_LEN layout; the six cause slots sum exactly to total aborts).

Every multi-shard grid point runs at TWO pipeline depths through the
scanned ``make_run_fn`` runner (one XLA program per run, so waves/s
measures the wave, not host dispatch): depth 1 — the synchronous
three-exchange wave — and the software-pipelined depth (default 2, ONE
fused all_to_all per steady-state wave; ``--pipeline-depth``).  Rows
carry both the HLO-parsed collective bytes per wave and the modeled wire
split (``route_bytes_per_wave`` / ``verdict_bytes_per_wave`` / the
retired 1-byte-per-op ``verdict_bytes_per_wave_legacy`` baseline the
bit-packed wire beats >= 4x) from ``distributed.wire_bytes_per_wave``.

    PYTHONPATH=src python -m benchmarks.txn_scaling \\
        [--waves N] [--pipeline-depth D] [--shards 1 8] [--json out.json]

The shard counts stop at the devices present: up to 8 forced host
devices when JAX runs on the CPU, up to ``jax.device_count()`` chips on a
TPU (the parent process never imports JAX, so the child owns the chips).
``--shards`` (or ``REPRO_TXN_SHARDS=1,8``) subsets the shard sweep — the
CI pallas-interpret smoke runs the 1/8 endpoints only, since every grid
point pays an interpret-mode compile there.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

PROG = textwrap.dedent("""
    import os, sys, time, json
    # Host-device override: it shapes only the CPU platform (8 devices for
    # a run without an accelerator); a TPU machine shards over its chips.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    sys.path.insert(0, "src")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.core import distributed as D, types as t
    from repro.analysis.roofline import collective_bytes_from_hlo
    MAX_SHARDS = jax.device_count()
    print(f"devices: {MAX_SHARDS} x {jax.devices()[0].device_kind}")

    K, N = 16, 1_000_000
    # Global lane count (kept at the default for real sweeps; the CI
    # pallas-interpret smoke shrinks it — interpret mode validates the
    # kernel semantics inside the pipelined wave, not speed, and its
    # route_pack cost grows superlinearly in the wave size).
    GLOBAL_LANES = int(os.environ.get("REPRO_TXN_LANES", "256"))
    WAVES = int(os.environ.get("REPRO_TXN_WAVES", "30"))
    DEPTH = int(os.environ.get("REPRO_TXN_DEPTH", "2"))
    BACKEND = os.environ.get("REPRO_TXN_BACKEND", "jnp")
    # Shard-count subset (e.g. "1,8" for the CI interpret-mode smoke,
    # where each grid point pays a pallas interpret compile).
    SHARDS = tuple(int(s) for s in os.environ.get(
        "REPRO_TXN_SHARDS", "1,2,4,8").split(",") if int(s) <= MAX_SHARDS)
    rows = []

    # shards=0 anchor: the local (single-device) engine at the same global
    # lane count, via the one-XLA-program sweep() grid runner.
    from repro.core import types as t
    from repro.core.backend import kernel_coverage
    from repro.core.engine import sweep as engine_sweep
    from repro.workloads import YCSBWorkload
    wl = YCSBWorkload.make(n_keys=N)
    cfg = t.EngineConfig(cc=t.CC_OCC, lanes=GLOBAL_LANES, slots=wl.slots,
                         n_records=wl.n_records, n_groups=wl.n_groups,
                         n_cols=wl.n_cols, n_txn_types=wl.n_txn_types,
                         n_rings=wl.n_rings, backend=BACKEND)
    # Warm call first: the timed call then hits the XLA executable cache and
    # measures (re-trace +) waves rather than a full compile.
    engine_sweep(cfg, wl, WAVES, ccs=[t.CC_OCC], grans=(1,),
                 lane_counts=(GLOBAL_LANES,))
    t0 = time.time()
    (pt,) = engine_sweep(cfg, wl, WAVES, ccs=[t.CC_OCC], grans=(1,),
                         lane_counts=(GLOBAL_LANES,))
    rows.append({"shards": 0, "cc": "occ", "commits": pt.commits,
                 "waves_per_s": WAVES / (time.time() - t0),
                 "coll_bytes_per_wave": 0,
                 # The local engine's read-only split (SweepPoint) rides
                 # the row like the distributed stats split does.
                 "ro_commits": pt.ro_commits, "ro_aborts": pt.ro_aborts,
                 "abort_causes": pt.abort_causes,
                 # Attribution: which engine the anchor actually ran on.
                 "backend": BACKEND,
                 "kernel_ops": kernel_coverage(BACKEND, t.CC_OCC)})
    print(f"local  : {rows[0]['waves_per_s']:6.1f} waves/s  "
          f"{pt.commits} commits  (sweep() anchor, no collectives)")

    from repro.core.backend import dist_kernel_coverage
    for cc in ("occ", "mvcc"):
        for ns in SHARDS:
            mesh = jax.make_mesh((ns,), ("data",))
            # Effective depths at this shard count, deduplicated (1-shard
            # meshes auto-fall back to depth 1 — one row, not two).
            depths = sorted({D.DistConfig(
                n_records=N, lanes_per_shard=GLOBAL_LANES // ns, slots=K,
                cc=cc, mv_depth=4 if cc != "occ" else 0,
                pipeline_depth=d).depth(ns) for d in (1, DEPTH)})
            rng = np.random.default_rng(0)
            keys = jnp.asarray(rng.integers(0, N, (GLOBAL_LANES, K),
                                            dtype=np.int32))
            groups = jnp.asarray(rng.integers(0, 2, (GLOBAL_LANES, K),
                                              dtype=np.int32))
            kinds = jnp.asarray(rng.choice(
                [t.READ, t.WRITE],
                (GLOBAL_LANES, K)).astype(np.int32))
            Ks = jnp.broadcast_to(keys, (WAVES,) + keys.shape)
            Gs = jnp.broadcast_to(groups, (WAVES,) + groups.shape)
            Is = jnp.broadcast_to(kinds, (WAVES,) + kinds.shape)
            Ps = jnp.asarray(np.stack(
                [np.random.default_rng(w).permutation(GLOBAL_LANES)
                 for w in range(WAVES)]).astype(np.uint32))
            for depth in depths:
                cfg = D.DistConfig(n_records=N, n_groups=2,
                                   lanes_per_shard=GLOBAL_LANES // ns,
                                   slots=K, backend=BACKEND, cc=cc,
                                   mv_depth=4 if cc != "occ" else 0,
                                   pipeline_depth=depth)
                tables = D.init_tables(cfg, mesh)
                # ONE compile per grid point: the scanned runner is one
                # XLA program for all WAVES waves; the executable answers
                # the HLO collective-bytes parse (trip-count aware, so
                # dividing by WAVES yields per-wave bytes) AND runs the
                # timed call — waves/s never includes compile time.
                run = jax.jit(D.make_run_fn(cfg, mesh, WAVES)).lower(
                    Ks, Gs, Is, Ps, tables, jnp.uint32(0)).compile()
                # Per-wave = per-scan-step: the pipelined scan runs three
                # extra drain steps beyond WAVES (each with the same one
                # fused exchange), so divide by the real trip count.
                steps = WAVES + (3 if depth >= 2 else 0)
                coll = collective_bytes_from_hlo(run.as_text()) / steps
                c, tb, stats = run(Ks, Gs, Is, Ps, tables, jnp.uint32(0))
                jax.block_until_ready(tb)          # warm (cached) call
                t0 = time.time()
                c, tb, stats = run(Ks, Gs, Is, Ps, tables, jnp.uint32(0))
                jax.block_until_ready(tb)
                dt = time.time() - t0
                commits = int(np.asarray(c).sum())
                s = np.asarray(stats).reshape(WAVES, ns, D.STATS_LEN)
                ro_c = int(s[:, :, D.STAT_RO_COMMITS].sum())
                ro_a = int(s[:, :, D.STAT_RO_ABORTS].sum())
                # Per-cause abort breakdown summed over waves x shards;
                # conserves exactly: sum == total aborts at every depth.
                causes = [int(x) for x
                          in s[:, :, D.STAT_CAUSES].sum(axis=(0, 1))]
                wire = D.wire_bytes_per_wave(cfg, mesh)
                rows.append({"shards": ns, "cc": cc, "commits": commits,
                             "waves_per_s": WAVES / dt,
                             "pipeline_depth": depth,
                             "coll_bytes_per_wave": coll,
                             "ro_commits": ro_c, "ro_aborts": ro_a,
                             "abort_causes": causes,
                             # The routed engine claims/probes/gathers/
                             # installs through the same backend surface
                             # as the local one; only the exchange itself
                             # stays shard_map + XLA collectives.
                             "backend": BACKEND,
                             "kernel_ops": dist_kernel_coverage(BACKEND,
                                                                cc),
                             **wire})
                print(f"{cc:4s} shards={ns} depth={depth}: "
                      f"{WAVES/dt:6.1f} waves/s  {commits} commits  "
                      f"ro={ro_c}/{ro_a}  coll/wave={coll/1024:.1f} KiB  "
                      f"wire/wave={wire['wire_bytes_per_wave']/1024:.1f} "
                      f"KiB")

    # Open-loop row family (DESIGN.md section 11): the same routed wave
    # behind per-shard admission queues — Poisson arrivals, bounded retry
    # incarnations, goodput (unique committed txns/s of wall time) and
    # p50/p99 time-to-commit in waves from the summed shard histograms.
    # Multi-shard points run at the pipelined depth (run_open_loop scans
    # ONE fused-exchange program); retries land two waves later there, the
    # conservation identities stay exact at every depth.
    from repro.core.admission import ttc_percentiles
    from repro.workloads.arrivals import PoissonArrivals

    def gen_fn_for(seed_base, n_total):
        def gen(w):
            rng = np.random.default_rng(seed_base + w)
            keys = jnp.asarray(rng.integers(0, N, (n_total, K),
                                            dtype=np.int32))
            groups = jnp.asarray(rng.integers(0, 2, (n_total, K),
                                              dtype=np.int32))
            kinds = jnp.asarray(rng.choice(
                [t.READ, t.WRITE], (n_total, K)).astype(np.int32))
            prio = jnp.asarray(rng.permutation(n_total).astype(np.uint32))
            return keys, groups, kinds, prio
        return gen

    for cc in ("occ", "mvcc"):
        for gran in (0, 1):
            for ns in sorted({1, max(SHARDS)} & set(SHARDS)):
                mesh = jax.make_mesh((ns,), ("data",))
                T_loc = GLOBAL_LANES // ns
                cfg = D.DistConfig(n_records=N, n_groups=2,
                                   lanes_per_shard=T_loc, slots=K,
                                   granularity=gran, backend=BACKEND,
                                   cc=cc,
                                   mv_depth=4 if cc != "occ" else 0,
                                   pipeline_depth=DEPTH,
                                   queue_cap=4 * T_loc,
                                   max_incarnations=8, lat_bins=32)
                arr = PoissonArrivals(
                    rate=0.75 * GLOBAL_LANES,
                    seed=7).shard_counts(WAVES, ns, T_loc)
                t0 = time.time()
                s = D.run_open_loop(cfg, mesh, arr, gen_fn_for(5000, GLOBAL_LANES),
                                    WAVES)
                dt = time.time() - t0
                (p50,), (p99,) = ttc_percentiles(
                    s["lat_hist"].sum(axis=0)[None, :])
                rows.append({
                    "shards": ns, "cc": cc, "mode": "open_loop",
                    "granularity": gran,
                    "pipeline_depth": cfg.depth(ns),
                    "commits": s["commits"],
                    "waves_per_s": WAVES / dt,
                    "coll_bytes_per_wave": 0,
                    "goodput_txn_per_s": s["commits"] / dt,
                    "p50_ttc_waves": p50, "p99_ttc_waves": p99,
                    "offered": s["offered"], "admitted": s["admitted"],
                    "arrival_drops": s["arrival_drops"],
                    "inc_drops": s["inc_drops"],
                    "queued_final": s["queued_final"],
                    "ro_commits": s["ro_commits"],
                    "ro_aborts": s["ro_aborts"],
                    "abort_causes": s["abort_causes"],
                    "backend": BACKEND,
                    "kernel_ops": dist_kernel_coverage(BACKEND, cc),
                    **D.wire_bytes_per_wave(cfg, mesh)})
                print(f"open {cc:4s} g={gran} shards={ns} "
                      f"depth={cfg.depth(ns)}: "
                      f"goodput={s['commits']/dt:8.1f} txn/s  "
                      f"p50/p99 ttc={p50:g}/{p99:g} waves  "
                      f"dropped={s['inc_drops']}")
    print("JSON:" + json.dumps(rows))
""")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--waves", type=int, default=None,
                    help="waves per grid point (default 30)")
    ap.add_argument("--pipeline-depth", type=int, default=None,
                    help="software-pipeline depth of the second depth "
                         "sweep (default 2; 1 collapses the sweep to the "
                         "synchronous wave only)")
    ap.add_argument("--shards", type=int, nargs="+", default=None,
                    help="shard counts to sweep (default 1 2 4 8; the "
                         "open-loop family keeps its 1/8 endpoints "
                         "intersected with this set)")
    ap.add_argument("--json", default="reports/txn_scaling.json")
    args = ap.parse_args(argv)
    # Presence-validated: the flags are optional, but a PROVIDED value
    # must be sane (argparse type=int already rejects non-integers).
    if args.waves is not None and args.waves < 1:
        ap.error(f"--waves must be >= 1, got {args.waves}")
    if args.pipeline_depth is not None and args.pipeline_depth < 1:
        ap.error(f"--pipeline-depth must be >= 1 (1 = synchronous wave), "
                 f"got {args.pipeline_depth}")
    if args.shards is not None and any(
            s < 1 or s > 8 or s & (s - 1) for s in args.shards):
        ap.error(f"--shards must be powers of two in [1, 8] (counts "
                 f"above the devices present are skipped), got "
                 f"{args.shards}")
    env = dict(os.environ)
    if args.waves is not None:
        env["REPRO_TXN_WAVES"] = str(args.waves)
    if args.pipeline_depth is not None:
        env["REPRO_TXN_DEPTH"] = str(args.pipeline_depth)
    if args.shards is not None:
        env["REPRO_TXN_SHARDS"] = ",".join(str(s) for s in args.shards)
    r = subprocess.run([sys.executable, "-c", PROG], capture_output=True,
                       text=True, cwd=".", timeout=2400, env=env)
    print(r.stdout)
    if r.returncode:
        print(r.stderr[-2000:], file=sys.stderr)
        return 1
    for line in r.stdout.splitlines():
        if line.startswith("JSON:"):
            rows = json.loads(line[5:])
            out_dir = os.path.dirname(args.json)
            if out_dir:
                os.makedirs(out_dir, exist_ok=True)
            with open(args.json, "w") as f:
                json.dump(rows, f, indent=1)
            print(f"[saved] {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
