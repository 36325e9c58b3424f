"""Per-op roofline cost model of the transaction engine's backend surface.

Every mechanism's wave is a fixed pipeline drawn from the
``backend.N_OPS``-op kernel surface (core/backend.py); each op's traffic
is analytic in the wave shape — T lanes x K op slots against uint32
claim/version tables of ``cells`` words per op probe (``n_groups`` at
coarse granularity, 1 at fine; the paper's switch is literally the probe
width, which is why coarse and fine have different bytes-per-txn here).
Interval reads (``max_extent > 1``) add the ``iterate_validate`` pass,
whose traffic scales with the per-op scan span — ``max_extent`` rows at
fine granularity, the bucket-expanded span at coarse (the same
``scan_span`` law as kernels/ref.py).  From the per-op descriptors we roll
up bytes/flops per wave per mechanism, divide by the lane count for the
dashboard's **bytes-per-txn / flops-per-txn** columns (per *attempt* — an
aborted incarnation pays the same traffic), and place each mechanism on
the roofline of ``analysis/peaks.py`` (the shared hardware peak table):

    intensity       = flops_per_wave / bytes_per_wave        [FLOP/B]
    frac_of_roofline= min(1, intensity / ridge(chip))
    bound           = memory below the ridge, compute above

The engine's ops are gather/scatter over uint32 words with a handful of
compares per cell — PLUS, for the in-wave-minimum family (segment_count,
claim_probe, wave_commit), the all-pairs same-cell wave term: every op
compares its (key, group) against every other op's, O((T*K)^2) compares
per call.  At small waves that term is noise and the engine is
**memory-bound everywhere**; at large waves (T*K in the thousands) the
quadratic flops dominate the linear table bytes and the probe family
climbs toward — and past — the ridge.  Both regimes are pinned in
tests/test_txn_cost.py.

``probe_chain`` models the fused-wave launch accounting (ISSUE 9): the
unfused probe chain (claim/probe RMW, XLA verdict reduction, version
bump — per claim table) is 2–4 launches per wave, each re-visiting the
wave's touched-row working set; the fused ``wave_commit`` megakernel is
ONE launch and ONE row visit.  ``launches_per_wave`` and
``dma_rows_per_wave`` (visits x ops) are the dashboard columns showing
the >= 2x modeled row-traffic cut per mechanism.

The op-call counts per wave (``WAVE_OPS``) mirror the mechanism sources
one-to-one — e.g. tictoc's 1 claim_probe + 2 ts_gather + 2 segment_count
+ 3 ts_install_max is exactly cc/tictoc.py's backend call sequence —
and tests/test_txn_cost.py pins them against the source so they cannot
drift silently.  ``DIST_WAVE_OPS`` does the same for the routed
distributed wave (core/distributed.py), whose exchange payload is already
accounted honestly by ``distributed.wire_bytes_per_wave``.

Nothing here imports jax — the model is closed-form, cheap enough to run
inside the bench row builder (launch/txn_bench.py) for every grid point.
"""
from __future__ import annotations

import dataclasses

from repro.analysis import peaks

#: Claim / version tables are packed uint32 words (core/claims.py).
WORD = 4


@dataclasses.dataclass(frozen=True)
class OpCost:
    """Analytic traffic of ONE backend-op call at a given wave shape."""
    bytes_per_call: float
    flops_per_call: float


@dataclasses.dataclass(frozen=True)
class WaveShape:
    """The shape terms the per-op descriptors depend on."""
    lanes: int                 # T
    slots: int                 # K ops per txn
    n_groups: int = 2          # G column groups per record
    granularity: int = 1       # 0 coarse / 1 fine — the paper's switch
    mv_depth: int = 0          # version-ring depth D (mv mechanisms)
    n_shards: int = 1          # distributed: mesh size
    route_cap: int = 0         # distributed: per-destination buffer cap
    max_extent: int = 1        # interval reads: static scan-length bound
    bucket_size: int = 8       # coarse bucket-claim width B (records)

    @property
    def ops(self) -> int:
        return self.lanes * self.slots

    @property
    def cells(self) -> int:
        """Claim words touched per op probe: the whole row at coarse
        granularity, one group word at fine — the byte-level face of the
        paper's timestamp-granularity switch."""
        fine = self.granularity == 1 and self.n_groups > 1
        return 1 if fine else self.n_groups

    @property
    def scan_span(self) -> int:
        """Rows an ``iterate_validate`` probe walks per scan op — the
        same law as kernels/ref.py ``scan_span``: the raw extent bound at
        fine granularity, the worst-case bucket expansion
        ``(1 + ceil((ext-1)/B)) * B`` at coarse (an interval can straddle
        one more bucket than its length suggests)."""
        if self.max_extent <= 1 or self.granularity == 1:
            return self.max_extent
        b = self.bucket_size
        return (1 + -(-(self.max_extent - 1) // b)) * b


def op_costs(s: WaveShape) -> dict:
    """OpCost per backend-surface op name at shape ``s``.

    Reads and read-modify-writes count actual table words (WORD bytes
    each; RMW = read + write).  Flops are the compare/select ALU work per
    cell — deliberately generous, and still orders of magnitude below any
    ridge point.
    """
    n, c, D = s.ops, s.cells, max(s.mv_depth, 1)
    ns, cap = s.n_shards, max(s.route_cap, 1)
    return {
        # one claim-word read + priority compare per cell
        "validate": OpCost(WORD * n * c, 2.0 * n * c),
        # both widths in one pass (autogran's dual verdict)
        "validate_dual": OpCost(WORD * n * (1 + s.n_groups),
                                2.0 * n * (1 + s.n_groups)),
        "probe": OpCost(WORD * n * c, 1.0 * n * c),
        # interval (phantom) validation: each op walks its scan span —
        # ``max_extent`` rows at fine, the bucket-expanded span at coarse
        # — reading ``cells`` claim words per row with a decode + strict
        # priority compare.  At max_extent == 1 this degenerates exactly
        # to ``validate`` (the extent-1 bit-identity guard, in traffic
        # terms).
        "iterate_validate": OpCost(WORD * n * s.scan_span * c,
                                   2.0 * n * s.scan_span * c),
        # fused min-install + probe: one RMW pass answers both; the
        # in-wave min is the all-pairs same-cell term — O(n^2) compares
        "claim_probe": OpCost(2 * WORD * n * c, 3.0 * n * c + 2.0 * n * n),
        # scatter-min RMW
        "claim_scatter": OpCost(2 * WORD * n * c, 1.0 * n * c),
        "ts_gather": OpCost(WORD * n * c, 1.0 * n),
        # scatter-add RMW (version bumps / conflict-hit histogram)
        "commit_install": OpCost(2 * WORD * n * c, 1.0 * n * c),
        # scatter-max RMW
        "ts_install_max": OpCost(2 * WORD * n * c, 1.0 * n * c),
        # sort-free per-cell counts: key read + counter scatter-add; the
        # per-cell count is an all-pairs key-equality reduction — O(n^2)
        "segment_count": OpCost(2 * WORD * n, 2.0 * n + 2.0 * n * n),
        # ISSUE 9 megakernel: claim-row RMW (install + probe, like
        # claim_probe) + the all-pairs wave term + the in-VMEM verdict
        # reduction, all in one launch.  Dual-table mechanisms count the
        # op twice (one per claim table); the version bump rides the same
        # launch but is still listed as commit_install (its version-row
        # traffic is unchanged by fusion).
        "wave_commit": OpCost(2 * WORD * n * c,
                              4.0 * n * c + 2.0 * n * n),
        # 3 int32 channels in, 3 [ns, cap] buffers out + offset scan
        "route_pack": OpCost(WORD * 3 * (n + ns * cap), 4.0 * n),
        # ring scan: D slots x cells begin-words + head read per op
        "mv_gather": OpCost(WORD * n * (D * c + 1), 2.0 * n * D * c),
        # slot claim + begin publish (RMW) + head bump
        "mv_install": OpCost(2 * WORD * n * (c + 1), 2.0 * n * c),
        # 16 2-bit verdicts per int32 word + the int8 source/dest
        "verdict_pack": OpCost(n + WORD * -(-n // 16), 1.0 * n),
        "verdict_unpack": OpCost(n + WORD * -(-n // 16), 1.0 * n),
    }


#: Backend-op calls per wave per LOCAL mechanism — a one-to-one mirror of
#: each cc/*.py source (claim_probe_commit -> wave_commit, once per claim
#: table; write_claims / plain_write_claims -> claim_scatter;
#: bump_versions -> commit_install, which the probe family's fused launch
#: absorbs without changing its version-row traffic).
#: Every mechanism that validates scans makes ONE phantom pass per wave
#: (base.phantom_validate, inside claim_probe_commit or appended after
#: the point verdicts) — iterate_validate: 1 across the board.  mvcc is
#: the deliberate absence: snapshot scans read a consistent cut and SI
#: admits phantoms by design (cc/mvcc.py).
WAVE_OPS = {
    "occ": {"wave_commit": 1, "commit_install": 1, "iterate_validate": 1},
    "tictoc": {"wave_commit": 1, "ts_gather": 2, "segment_count": 2,
               "ts_install_max": 3, "iterate_validate": 1},
    "2pl": {"wave_commit": 2, "commit_install": 1, "iterate_validate": 1},
    "swisstm": {"wave_commit": 1, "commit_install": 1,
                "iterate_validate": 1},
    "adaptive": {"wave_commit": 2, "commit_install": 1,
                 "iterate_validate": 1},
    "autogran": {"claim_scatter": 1, "validate_dual": 1,
                 "commit_install": 1, "iterate_validate": 1},
    "mvcc": {"claim_scatter": 2, "validate": 2, "mv_gather": 1,
             "mv_install": 1},
    "mvocc": {"claim_scatter": 2, "validate": 3, "mv_gather": 1,
              "mv_install": 1, "iterate_validate": 1},
}

#: Shard-local op calls per wave of the routed DISTRIBUTED wave
#: (core/distributed.py _make_phases; wire bytes live in
#: distributed.wire_bytes_per_wave, not here).
DIST_WAVE_OPS = {
    "occ": {"route_pack": 1, "wave_commit": 1, "verdict_pack": 2,
            "verdict_unpack": 2, "commit_install": 1,
            "iterate_validate": 1},
    "mvcc": {"route_pack": 1, "claim_probe": 2, "mv_gather": 1,
             "verdict_pack": 2, "verdict_unpack": 2, "mv_install": 1},
    "mvocc": {"route_pack": 1, "claim_probe": 2, "mv_gather": 1,
              "verdict_pack": 2, "verdict_unpack": 2, "mv_install": 1,
              "iterate_validate": 1},
}

#: Launches in the UNFUSED probe chain per wave — the claim/probe RMW
#: pass(es), the XLA verdict reduction, and the version bump that
#: ``wave_commit`` collapses into ONE launch (base.claim_probe_commit's
#: fuse_wave=False path).  occ/swisstm: claim_probe + verdict + bump = 3;
#: tictoc: claim_probe + verdict = 2 (no bump — ts_install_max owns the
#: timestamp writes); 2pl/adaptive: two claim tables + verdict + bump = 4.
PROBE_CHAIN_LAUNCHES = {
    "occ": 3,
    "tictoc": 2,
    "2pl": 4,
    "swisstm": 3,
    "adaptive": 4,
}


def probe_chain(cc: str, s: WaveShape, fused: bool = True) -> dict:
    """Launch/row-traffic accounting of mechanism ``cc``'s probe chain at
    shape ``s`` — the ISSUE 9 dashboard columns.

    Each launch in the unfused chain re-visits the wave's touched-row
    working set (the claim RMW fetches it, the verdict pass re-reads the
    probe outputs derived from it, the bump re-fetches the version rows):
    ``dma_rows_per_wave`` = visits x (T*K) row slots.  Fused, the whole
    chain is ONE launch and each touched row rides ONE DMA round-trip —
    the >= 2x modeled row-traffic cut per mechanism.
    """
    if cc not in PROBE_CHAIN_LAUNCHES:
        raise KeyError(f"{cc!r} is not a probe-family mechanism (expected "
                       f"one of {sorted(PROBE_CHAIN_LAUNCHES)})")
    visits = 1 if fused else PROBE_CHAIN_LAUNCHES[cc]
    return {
        "launches_per_wave": visits,
        "dma_rows_per_wave": visits * s.ops,
    }


def wave_cost(cc: str, s: WaveShape, distributed: bool = False) -> dict:
    """Roll up mechanism ``cc``'s per-wave traffic at shape ``s``:
    {bytes_per_wave, flops_per_wave, ops: {name: count}}."""
    table = DIST_WAVE_OPS if distributed else WAVE_OPS
    if cc not in table:
        raise KeyError(f"unknown mechanism {cc!r} (expected one of "
                       f"{sorted(table)})")
    costs = op_costs(s)
    counts = table[cc]
    b = sum(costs[op].bytes_per_call * k for op, k in counts.items())
    f = sum(costs[op].flops_per_call * k for op, k in counts.items())
    return {"bytes_per_wave": b, "flops_per_wave": f, "ops": dict(counts)}


def txn_cost(cc: str, s: WaveShape, distributed: bool = False,
             chip: str = peaks.V5E) -> dict:
    """The dashboard row fields: per-ATTEMPT per-transaction traffic and
    the mechanism's place on ``chip``'s roofline.

    bytes_per_txn / flops_per_txn divide the wave rollup by the lane
    count — each incarnation of an aborted transaction pays this again,
    so goodput-per-byte divides further by the commit rate (the dashboard
    already carries commit rates; this model stays traffic-only).
    """
    w = wave_cost(cc, s, distributed)
    lanes = max(s.lanes, 1)
    intensity = w["flops_per_wave"] / max(w["bytes_per_wave"], 1.0)
    r = peaks.ridge(chip)
    return {
        "bytes_per_txn": w["bytes_per_wave"] / lanes,
        "flops_per_txn": w["flops_per_wave"] / lanes,
        "intensity": intensity,
        "roofline_frac": min(1.0, intensity / r),
        "bound": "memory" if intensity < r else "compute",
        "chip": chip,
    }
