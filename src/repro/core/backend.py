"""The kernel-backend layer: one dispatch surface for every CC mechanism.

Every concurrency-control mechanism in ``core/cc/`` — and the distributed
engine's shard-local wave (``core/distributed.py``) — touches shared state
through exactly ``N_OPS`` ops (``SURFACE_OPS`` below — doc strings
elsewhere cite the constant, pinned by tests/test_backend_surface.py), the
full surface a wave needs (DESIGN.md sections 5, 9 and 10):

    validate        read-set verdicts vs the writer-claim table (OCC rule;
                    mvcc/mvocc's first-committer-wins channels)
    validate_dual   fine AND coarse verdicts from one row fetch (AutoGran)
    probe           raw strongest-claimant prio16 (NO_PRIO if unclaimed)
    claim_probe     FUSED claim_scatter + probe: one pass installs the
                    wave's claim words and answers every op's post-install
                    strongest-claimant probe (the probe family — OCC,
                    TicToc, 2PL, SwissTM, Adaptive — and the distributed
                    owner step; half the launches and claim-row DMAs)
    wave_commit     the probe-family MEGAKERNEL (kernels/wave_commit.py):
                    one launch with aliased claim/version tables installs
                    the wave's write claims, answers every op's
                    strongest-claimant probe, reduces per-op conflicts to
                    lane verdicts in VMEM, and bumps versions for
                    committed writes — each touched row rides ONE DMA per
                    wave where the unfused claim_probe -> verdict ->
                    commit_install chain re-fetched it 2-3 times
                    (EngineConfig.fuse_wave routes the probe family here)
    iterate_validate  interval (scan) validation — phantom protection for
                    extent-carrying ops: conflict when any record of the
                    op's validated interval carries a live same-wave claim
                    stronger than the lane; fine = the exact interval at
                    the op's group column (per-gap timestamps), coarse =
                    the bucket-expanded interval with the whole-row
                    compare (bucket-interval claims, one word per
                    EngineConfig.bucket_size records) — DESIGN.md
                    section 13
    ts_gather       per-op (wts | rts) observation; coarse = row max (TicToc)
    claim_scatter   pack + scatter-min claim words (install-only callers:
                    AutoGran's verdict path, the MV claim channels)
    commit_install  +1 version bumps for committed writes (OCC-family +
                    the distributed install return-trip)
    ts_install_max  monotone scatter-max timestamp install (TicToc)
    segment_count   same-cell op counts within the wave (TicToc's extension
                    chains + the engine's install-contention cost model —
                    ops that are not simple row gathers)
    route_pack      sort-free per-destination exchange-buffer pack (the
                    distributed wave's send side; counting/offset scan in
                    place of the old argsort routing pass)
    mv_gather       snapshot version select on the multi-version ring
                    (mvcc/mvocc reads; core/mvstore.py)
    mv_install      ring-slot claim + version publish (mvcc/mvocc commits)
    verdict_pack    bit-pack per-op verdict bytes for the wire — 2 bits/op
                    (conflict + read-validation), 16 ops per int32 word, a
                    4x byte cut on the distributed verdict/commit return
                    channels (kernels/verdict_pack.py)
    verdict_unpack  the inverse: wire words back to per-op verdict bytes

``resolve(cfg)`` maps ``EngineConfig.backend`` (or ``DistConfig.backend`` —
any config with a ``backend`` field) to one of two stateless singleton
implementations:

- ``jnp``    — XLA gather/scatter (the oracles in ``kernels/ref.py`` and the
  helpers in ``core/claims.py`` are the same computations);
- ``pallas`` — the TPU-native kernels behind ``kernels/ops.py`` (interpret
  mode on the CPU), every table op a block row-DMA gather or an
  aliased-output block read-modify-write over the packed table layout of
  ``kernels/rows.py``.

Both decode the one claim-word layout in ``core/claimword.py`` and are
bit-identical (tests/test_backend_parity.py, tests/test_kernels.py).  CC
mechanisms hold no ``cfg.backend`` branches: they call ``resolve(cfg)`` once
per wave and use only this surface, so a new mechanism gets TPU execution for
free and a new backend only has to implement these ``N_OPS`` ops.

``resolve`` honors ``cfg.lane_block`` on the pallas backend: the row-DMA
kernels tile the wave into LB-lane blocks (kernels/rows.py
``pick_lane_block``; 0 = the least block) and the override threads
through every lane-block kernel call.
"""
from __future__ import annotations

from repro.core import claims
from repro.core import types as t
from repro.core.claimword import inv_wave

#: The canonical kernel-backend surface: every op both backends implement
#: as a method, in DESIGN.md section 5 table order.  ``N_OPS`` is THE
#: op count — README.md, DESIGN.md, core/engine.py and launch/txn_bench.py
#: cite it instead of a hard-coded number word, and
#: tests/test_backend_surface.py pins the docs, the backends' method
#: surfaces and the CC_OPS/DIST_OPS subsets to this tuple.
SURFACE_OPS = ("validate", "validate_dual", "probe", "claim_probe",
               "wave_commit", "iterate_validate", "ts_gather",
               "claim_scatter", "commit_install", "ts_install_max",
               "segment_count", "route_pack", "mv_gather", "mv_install",
               "verdict_pack", "verdict_unpack")

#: Op count of the backend surface (sixteen as of the iterate_validate PR).
N_OPS = len(SURFACE_OPS)


class JnpBackend:
    """XLA gather/scatter implementation (the reference substrate)."""
    name = "jnp"
    use_pallas = False

    def validate(self, claim_w, keys, groups, myprio, check, wave,
                 fine: bool):
        """Conflict bool[T, K]: live read cells claimed by a strictly
        stronger lane this wave."""
        wprio = (claims.probe(claim_w, keys, groups, wave) if fine
                 else claims.probe_any_group(claim_w, keys, wave))
        return check & (wprio < myprio)

    def validate_dual(self, claim_w, keys, groups, myprio, check, wave):
        """(fine, coarse) conflict bool[T, K] from one logical row fetch."""
        from repro.kernels import ref
        return ref.occ_validate_dual(claim_w, keys, groups, myprio, check,
                                     inv_wave(wave))

    def probe(self, table, keys, groups, wave, fine: bool):
        """Strongest live claimant prio16 per op (NO_PRIO if unclaimed)."""
        return (claims.probe(table, keys, groups, wave) if fine
                else claims.probe_any_group(table, keys, wave))

    def claim_probe(self, table, keys, groups, prio, wave, mask,
                    fine: bool):
        """Fused claim_scatter + probe: min-install claim words for masked
        ops, return every op's post-install strongest-claimant prio16."""
        from repro.kernels import ref
        return ref.claim_probe_fused(table, keys, groups, prio, mask, wave,
                                     fine)

    def wave_commit(self, claim_w, claim_r, wts, keys, groups, prio, do_w,
                    do_r, check_w, check_w2, check_r, extra, wave,
                    fine: bool, dual: bool, bump: bool):
        """The fused probe-family wave: claim install + probe + lane
        verdicts + version bumps in one pass.  Returns (claim_w', claim_r',
        wts', conflict bool[T, K], commit bool[T]); claim_r/wts ride only
        when dual/bump."""
        from repro.kernels import ref
        return ref.wave_commit(claim_w, claim_r, wts, keys, groups, prio,
                               do_w, do_r, check_w, check_w2, check_r,
                               extra, wave, fine, dual, bump)

    def iterate_validate(self, table, keys, extents, groups, myprio, check,
                         wave, fine: bool, bucket_size: int, ext_cap: int):
        """Interval (scan) validation: conflict bool[T, K] where any record
        of ``[key, key + extent)`` (bucket-expanded when coarse) carries a
        live same-wave claim stronger than the lane — the phantom check."""
        from repro.kernels import ref
        return ref.iterate_validate(table, keys, extents, groups, myprio,
                                    check, inv_wave(wave), fine,
                                    bucket_size, ext_cap)

    def route_pack(self, owner, vals, n_dest: int, cap: int, fills):
        """Sort-free per-destination fixed-capacity buffer pack."""
        from repro.kernels import ref
        return ref.route_pack(owner, vals, n_dest, cap, fills)

    def ts_gather(self, table, keys, groups, fine: bool):
        """Per-op timestamp observation; coarse reads the row max."""
        from repro.kernels import ref
        return ref.ts_gather(table, keys, groups, fine)

    def claim_scatter(self, table, keys, groups, prio, wave, mask):
        """Scatter-min packed claim words into table[record, group]."""
        from repro.kernels import ref
        return ref.claim_scatter(table, keys, groups, prio, mask, wave)

    def commit_install(self, wts, keys, groups, do):
        """+1 per committed write op (monotone version bump)."""
        from repro.kernels import ref
        return ref.occ_commit(wts, keys, groups, do)

    def ts_install_max(self, table, keys, groups, vals, mask,
                       whole_row: bool = False):
        """Monotone scatter-max timestamp install."""
        from repro.kernels import ref
        return ref.ts_install_max(table, keys, groups, vals, mask, whole_row)

    def segment_count(self, keys, groups, G: int, mask):
        """#same-(record, group) ops in the wave, per op (0 where masked)."""
        from repro.kernels import ref
        return ref.segment_count(keys, groups, G, mask)

    def mv_gather(self, begin, keys, groups, ts, fine: bool):
        """(slot, ok) of the newest ring version visible at snapshot ts."""
        from repro.kernels import ref
        return ref.mv_gather(begin, keys, groups, ts, fine)

    def mv_install(self, begin, head, keys, groups, do, ts):
        """Claim one ring slot per written record; publish begin stamps."""
        from repro.kernels import ref
        return ref.mv_install(begin, head, keys, groups, do, ts)

    def verdict_pack(self, v):
        """Bit-pack verdict bytes: 2 bits/op, 16 ops per int32 wire word."""
        from repro.kernels import ref
        return ref.verdict_pack(v)

    def verdict_unpack(self, words, n: int):
        """Inverse of verdict_pack: wire words -> int8[..., n] verdicts."""
        from repro.kernels import ref
        return ref.verdict_unpack(words, n)


class PallasBackend:
    """TPU-native kernels (compiled on a TPU, interpret mode on the CPU).

    ``lane_block`` threads the lane-block tiling override (LB lanes per
    grid step; 0 = auto) into every row-DMA kernel — see
    kernels/rows.pick_lane_block and ``resolve``."""
    name = "pallas"
    use_pallas = True

    def __init__(self, lane_block: int = 0):
        self.lane_block = lane_block

    def validate(self, claim_w, keys, groups, myprio, check, wave,
                 fine: bool):
        from repro.kernels import ops
        return ops.occ_validate(claim_w, keys, groups, myprio, check,
                                inv_wave(wave), fine,
                                lane_block=self.lane_block, use_pallas=True)

    def validate_dual(self, claim_w, keys, groups, myprio, check, wave):
        from repro.kernels import ops
        return ops.occ_validate_dual(claim_w, keys, groups, myprio, check,
                                     inv_wave(wave),
                                     lane_block=self.lane_block,
                                     use_pallas=True)

    def probe(self, table, keys, groups, wave, fine: bool):
        from repro.kernels import ops
        return ops.claim_probe(table, keys, groups, inv_wave(wave), fine,
                               lane_block=self.lane_block, use_pallas=True)

    def claim_probe(self, table, keys, groups, prio, wave, mask,
                    fine: bool):
        from repro.kernels import ops
        return ops.claim_probe_fused(table, keys, groups, prio, mask, wave,
                                     fine, lane_block=self.lane_block,
                                     use_pallas=True)

    def wave_commit(self, claim_w, claim_r, wts, keys, groups, prio, do_w,
                    do_r, check_w, check_w2, check_r, extra, wave,
                    fine: bool, dual: bool, bump: bool):
        from repro.kernels import ops
        return ops.wave_commit(claim_w, claim_r, wts, keys, groups, prio,
                               do_w, do_r, check_w, check_w2, check_r,
                               extra, wave, fine, dual, bump,
                               lane_block=self.lane_block, use_pallas=True)

    def iterate_validate(self, table, keys, extents, groups, myprio, check,
                         wave, fine: bool, bucket_size: int, ext_cap: int):
        from repro.kernels import ops
        return ops.iterate_validate(table, keys, extents, groups, myprio,
                                    check, inv_wave(wave), fine,
                                    bucket_size, ext_cap,
                                    lane_block=self.lane_block,
                                    use_pallas=True)

    def route_pack(self, owner, vals, n_dest: int, cap: int, fills):
        from repro.kernels import ops
        return ops.route_pack(owner, vals, n_dest, cap, fills,
                              use_pallas=True)

    def ts_gather(self, table, keys, groups, fine: bool):
        from repro.kernels import ops
        return ops.ts_gather(table, keys, groups, fine, use_pallas=True)

    def claim_scatter(self, table, keys, groups, prio, wave, mask):
        from repro.kernels import ops
        return ops.claim_scatter(table, keys, groups, prio, mask, wave,
                                 use_pallas=True)

    def commit_install(self, wts, keys, groups, do):
        from repro.kernels import ops
        return ops.occ_commit(wts, keys, groups, do, use_pallas=True)

    def ts_install_max(self, table, keys, groups, vals, mask,
                       whole_row: bool = False):
        from repro.kernels import ops
        return ops.ts_install_max(table, keys, groups, vals, mask, whole_row,
                                  use_pallas=True)

    def segment_count(self, keys, groups, G: int, mask):
        from repro.kernels import ops
        return ops.segment_count(keys, groups, G, mask, use_pallas=True)

    def mv_gather(self, begin, keys, groups, ts, fine: bool):
        from repro.kernels import ops
        return ops.mv_gather(begin, keys, groups, ts, fine,
                             lane_block=self.lane_block, use_pallas=True)

    def mv_install(self, begin, head, keys, groups, do, ts):
        from repro.kernels import ops
        return ops.mv_install(begin, head, keys, groups, do, ts,
                              use_pallas=True)

    def verdict_pack(self, v):
        from repro.kernels import ops
        return ops.verdict_pack(v, use_pallas=True)

    def verdict_unpack(self, words, n: int):
        from repro.kernels import ops
        return ops.verdict_unpack(words, n, use_pallas=True)


_BACKENDS = {"jnp": JnpBackend(), "pallas": PallasBackend()}

#: The surface ops each mechanism's wave routes through the backend —
#: consumed by benchmark JSON rows so BENCH_* trajectories record which ops
#: actually ran as Pallas kernels (see launch/txn_bench.py).  Every
#: mechanism includes ``segment_count``: the engine's install-contention
#: cost model counts same-row committers/readers through it each wave
#: (core/engine.py make_wave_step), on top of TicToc's extension chains.
#: The probe family (OCC's read validation included) runs on the fused
#: ``wave_commit`` megakernel — the claim_probe -> verdict ->
#: commit_install chain in ONE launch (EngineConfig.fuse_wave; the
#: unfused chain remains behind fuse_wave=False).  ``commit_install``
#: stays listed for the bumping mechanisms: its version-bump traffic
#: rides the fused launch but is still attributed to the op (the cost
#: model splits it out — analysis/txn_cost.py).  ``claim_scatter``
#: remains listed only where a mechanism still installs claims it never
#: probes as priorities (AutoGran's verdict path, the MV
#: first-committer-wins channels).  ``iterate_validate`` is listed for
#: every mechanism that phantom-protects scans (extent > 1 ops): the
#: probe family and AutoGran validate intervals against the post-install
#: write-claim table, mvocc against its wave claim channel; mvcc alone
#: omits it — snapshot-isolation scans read a stable snapshot and are
#: never re-validated (DESIGN.md section 13).
CC_OPS = {
    t.CC_OCC: ("wave_commit", "iterate_validate", "commit_install",
               "segment_count"),
    t.CC_TICTOC: ("wave_commit", "iterate_validate", "ts_gather",
                  "ts_install_max", "segment_count"),
    t.CC_2PL: ("wave_commit", "iterate_validate", "commit_install",
               "segment_count"),
    t.CC_SWISS: ("wave_commit", "iterate_validate", "commit_install",
                 "segment_count"),
    t.CC_ADAPTIVE: ("wave_commit", "iterate_validate", "commit_install",
                    "segment_count"),
    t.CC_AUTOGRAN: ("validate_dual", "iterate_validate", "claim_scatter",
                    "commit_install", "segment_count"),
    t.CC_MVCC: ("validate", "claim_scatter", "mv_gather", "mv_install",
                "segment_count"),
    t.CC_MVOCC: ("validate", "iterate_validate", "claim_scatter",
                 "mv_gather", "mv_install", "segment_count"),
}

#: The surface ops one shard-local distributed wave routes through the
#: backend (core/distributed.py), per mechanism: the sort-free exchange
#: pack, the verdict bit-pack/unpack pair riding every verdict and commit
#: return channel, and the owner-side claim step — occ's runs as the
#: fused ``wave_commit`` (DistConfig.fuse_wave; claim install + probe +
#: verdicts in one table pass), the multi-version pair keeps the
#: ``claim_probe`` primitive (two claim channels + the ring gather can't
#: share one launch) — plus the install return-trip: ``commit_install``
#: version bumps for occ, ``mv_gather`` snapshot reads + ``mv_install``
#: ring publishes for the multi-version pair.  Scan fragments validate on
#: their owner shard through ``iterate_validate`` (intervals split at
#: range-shard boundaries; verdicts AND-reduce back on the sender —
#: DESIGN.md section 13).  Recorded by benchmarks/txn_scaling.py rows.
DIST_OPS = ("route_pack", "verdict_pack", "verdict_unpack", "wave_commit",
            "iterate_validate", "commit_install")
DIST_MV_OPS = ("route_pack", "verdict_pack", "verdict_unpack",
               "claim_probe", "mv_gather", "mv_install")
#: mvocc adds the interval pass; mvcc does NOT — its scans read the
#: snapshot's consistent cut and never re-validate (cc/mvcc.py).
DIST_MVOCC_OPS = DIST_MV_OPS + ("iterate_validate",)


def resolve(cfg) -> JnpBackend | PallasBackend:
    """Config (EngineConfig / DistConfig — anything with a validated
    ``backend`` field) -> the backend singleton.  A nonzero
    ``cfg.lane_block`` override on the pallas backend gets a dedicated
    instance threading the tiling into the lane-block kernels (the
    backends are stateless otherwise — DESIGN.md section 5)."""
    if cfg.backend == "pallas":
        lb = getattr(cfg, "lane_block", 0)
        if lb:
            return PallasBackend(lane_block=lb)
    return _BACKENDS[cfg.backend]


def kernel_coverage(backend_name: str, cc: int) -> dict:
    """{op: "pallas" | "xla"} for the ops mechanism ``cc`` routes through
    backend ``backend_name`` — the attribution record for benchmark JSON."""
    engine = "pallas" if backend_name == "pallas" else "xla"
    return {op: engine for op in CC_OPS[cc]}


def dist_kernel_coverage(backend_name: str, cc: str = "occ") -> dict:
    """Kernel attribution for the distributed wave's shard-local ops
    (``cc`` is the DistConfig mechanism string: occ / mvcc / mvocc)."""
    engine = "pallas" if backend_name == "pallas" else "xla"
    ops = {"mvcc": DIST_MV_OPS, "mvocc": DIST_MVOCC_OPS}.get(cc, DIST_OPS)
    return {op: engine for op in ops}
