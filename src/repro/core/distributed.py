"""Distributed CC: the paper's section-5 future work ("evaluate in a
distributed setting"), mapped onto a TPU mesh with shard_map + all_to_all.

Layout
------
The record space is range-sharded over every mesh axis combined (an
``n_shards``-way partition); each device owns its slice of the version /
claim / multi-version tables.  Lanes (transactions) are sharded the same
way.  One wave is:

  1. route    every op is routed to its key's owner shard.  Per-destination
              fixed-capacity buffers [n_shards, cap, words] are built by the
              backend's ``route_pack`` op — a counting/offset scan (the
              placement a stable argsort by owner would give, WITHOUT the
              sort; kernels/route_pack.py) — and exchanged through the one
              ``_make_exchange`` collective.  Ops beyond a pair's capacity
              abort their lane (counted; capacity is sized for the
              workload).
  2. claim    owners run the backend's fused ``claim_probe`` op on their
              claim-table shard(s): ONE pass min-installs the routed write
              claims and answers every routed op's strongest-claimant
              probe — the same reset-free wave-tag tables as the local
              engine (core/claims.py).  The MV mechanisms claim TWO
              channels (all writes in claim_w, plain WRITEs in claim_r —
              the ADD-commutes rule of cc/base.plain_write_claims) and
              additionally run ``mv_gather`` on their shard of the version
              ring: the snapshot-visibility read that replaces read
              validation, honoring ``snapshot_age`` (aged snapshots that
              outlive the ring report reclamation and abort — never read a
              recycled slot).
  3. verdict  per-op conflict flags return through the inverse exchange,
              BIT-PACKED 16 ops per int32 word by the backend's
              ``verdict_pack`` op (2 bits per op — a 4x wire cut vs the
              old 1-int8-per-op scheme); the sender unpacks and *gathers*
              its verdicts back by each op's (owner, pos) routing
              coordinates from route_pack — no return scatter.  A lane
              commits iff none of its routed ops conflicted and none were
              capacity-dropped.  The verdict carries two bits:
              unconditional conflicts (FCW write-write + snapshot
              reclamation; single-version OCC uses only this bit) and the
              read-validation bit, which only mvocc applies — and only to
              lanes that also write, a fact the *sender* knows (read-only
              lanes serialize at their snapshot; cc/mvocc.py), so it never
              travels.
  4. install  committed write ops publish through the backend on the same
              return trip (the commit bits ride the inverse exchange
              packed like the verdicts, so installation reuses the routed
              buffer — no extra payload): ``commit_install`` bumps
              (record, group) versions for occ; ``mv_install`` claims one
              ring slot per written record and publishes begin timestamps
              for mvcc/mvocc (concurrent group writers of a record merge
              into the slot, exactly the local mv_commit).

Software pipeline (``pipeline_depth >= 2``; DESIGN.md section 10)
-----------------------------------------------------------------
The synchronous wave serializes three exchanges against shard-local
compute.  The scanned runners (``make_run_fn`` / the pipelined open loop
behind ``run_open_loop``) overlap them: ``route_pack`` never reads the CC
tables, so wave N's routing runs while owners claim/probe/gather wave
N-1, and the verdict + commit return words are FUSED with the next wave's
outbound buffers into ONE ``all_to_all`` per steady-state wave.  Step s
of the scan (wave w = wave0 + s):

    1. owner-install  wave w-3  (commit bits arrived last step)
    2. owner-claim    wave w-1  (routed buffers arrived last step) -> V
    3. sender-commit  wave w-2  (verdict words arrived last step)  -> C
    4. route          wave w                                       -> O
    5. one fused exchange of [O_key | O_meta | V | C]

In-flight wave buffers thread through the scan carry (three owner-side
routed-buffer slots, two sender-side coordinate slots); warmup steps run
on NO_OP-filled buffers (masked everywhere, so they are table no-ops) and
three trailing NOP-padded waves drain the pipe (wave w's verdicts land at
step w + 2, its installs at w + 3).  Depth 1 keeps today's
synchronous schedule bit-identically; depth >= 2 is bit-identical to it
for occ always and for mvcc/mvocc at ``snapshot_age == 0`` (the claim
scatter-min commutes across waves, probes only see current-wave claims,
occ's wts is write-only inside the wave, and a wave-fresh MV snapshot
never depends on the one install the pipelined gather has not seen yet);
aged snapshots are validation-rejected at depth >= 2 because that missing
install's reclamation CAN flip an aged reader's verdict.

Every shard-local table touch goes through ``backend.resolve(cfg)``
(core/backend.py): ``DistConfig.backend`` selects XLA gather/scatter or the
Pallas kernels exactly like the local engine, bit-identically — the
sharded wave is the local wave's op pipeline behind one exchange
(DESIGN.md section 10).

Granularity (the paper's mechanism) is carried per op exactly as in the
local engine: coarse probes the whole row (and the MV visibility check
reduces each ring slot over the row), fine probes the op's group.

Interval (scan) ops — ``max_extent > 1`` (DESIGN.md section 13)
---------------------------------------------------------------
The caller packs each op's extent into the kind channel's high bits
(``kind = kinds & 3``, ``extent = max(kinds >> 2, 1)``), so every wave
signature, the admission ring, and the pipeline carries are untouched —
a re-enqueued incarnation automatically retries the identical interval.
``route`` splits an interval at its range-shard boundary into at most two
fragments (``max_extent <= rec_per`` is enforced), each riding the wire
with its width in meta bits 19..30 (0 for point ops — pure-point waves
stay byte-identical).  Owners validate scan fragments with the
``iterate_validate`` op against the post-install claim shard (fine:
per-row probes at the op's group; coarse: bucket-expanded row-min,
``rec_per % bucket_size == 0`` keeps expansion inside the shard); the
verdict rides the existing bits and the SENDER — who kept the packed
kinds — classifies scan conflicts as ``CAUSE_PHANTOM`` and AND-reduces
fragment verdicts per lane.  Aged snapshots are rejected with
``max_extent > 1`` exactly like the local engine (and independently at
``pipeline_depth >= 2``, the pre-existing rule).

In-wave conflict semantics match the local engine (DESIGN.md sections 2
and 9): a single-version read aborts iff a *higher-priority* lane claimed
its cell this wave, regardless of that lane's own fate — STO's non-waiting
prevention — which is what makes one round trip sufficient; an MV read
never aborts on writers, only on reclamation (plus mvocc's update-lane
read validation).

State threading: ``make_wave_fn`` takes and returns one ``tables`` tuple
whose layout depends on the mechanism — ``(wts, claim_w)`` for occ,
``(claim_w, claim_r, mv_begin, mv_head)`` for mvcc/mvocc (the version ring
of core/mvstore.py, range-sharded like every other table).  Values are not
tracked on the distributed path (``mv_vals`` stays local-engine-only, as in
the throughput benchmarks — the wire carries no value channel).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


from repro.core import admission
from repro.core import backend as kb
from repro.core import mvstore
from repro.core import types as t

# Python ints (not jnp scalars): route_pack bakes the buffer fills into the
# Pallas kernel body, which may not capture traced constants.
NO_OP = 0x7FFFFFFF       # empty buffer cell in the key channel
META_FILL = 0x7FFF8      # empty meta: group 0, kind NOP, prio16 NO_PRIO
LANE_FILL = -1           # empty cell in the local slot -> lane map

#: Mechanisms the routed wave implements (string-keyed like
#: DistConfig.backend; the local engine's int ids stay in core/types.py).
DIST_CCS = ("occ", "mvcc", "mvocc")
DIST_MV_CCS = ("mvcc", "mvocc")

#: Exchange factorings of the routed wave (DistConfig.topology).
TOPOLOGIES = ("flat", "axiswise")

#: stats vector layout per shard (int32[STATS_LEN]; ro = read-only lanes,
#: the multi-version headline split SimResult/dashboard rows expect).
#: Slots 6..9 are the open-loop front-end counters (make_open_wave_fn);
#: the closed wave reports zeros there.  ADMITTED / ARRIVAL_DROPS /
#: INC_DROPS are per-wave deltas the driver accumulates; QUEUED is the
#: post-wave queue-occupancy snapshot (NOT a delta).  Slots 10 onward are
#: the N_ABORT_CAUSES per-cause abort counts, indexed by types.CAUSE_*
#: code; they sum to the ABORTS slot exactly, at every shard count and
#: pipeline depth (the conservation invariant
#: tests/test_abort_causes.py asserts).
STATS_LEN = 10 + t.N_ABORT_CAUSES
STAT_COMMITS, STAT_ABORTS, STAT_DROPPED_LANES, STAT_DROPPED_OPS, \
    STAT_RO_COMMITS, STAT_RO_ABORTS, STAT_ADMITTED, STAT_ARRIVAL_DROPS, \
    STAT_INC_DROPS, STAT_QUEUED = range(10)
STAT_CAUSE0 = 10
STAT_CAUSES = slice(STAT_CAUSE0, STAT_CAUSE0 + t.N_ABORT_CAUSES)


def verdict_words(cap: int) -> int:
    """int32 wire words per ``cap``-op verdict row: 2 bits per op, 16 ops
    per word (kernels/verdict_pack.py)."""
    return -(-cap // 16)


@dataclasses.dataclass(frozen=True)
class DistConfig:
    n_records: int
    n_groups: int = 2
    lanes_per_shard: int = 64      # T_loc
    slots: int = 16                # K ops per txn
    route_cap: int = 0             # 0 = auto: 4x fair share, 8-aligned
    granularity: int = 1           # 0 coarse / 1 fine (probe width)
    backend: str = "jnp"           # kernel-backend surface substrate for
                                   # every shard-local table touch
                                   # (core/backend.py): "jnp" XLA, "pallas"
                                   # TPU kernels (interpret mode off-TPU)
    cc: str = "occ"                # routed mechanism: "occ" (single-version
                                   # timestamps) or "mvcc"/"mvocc" (the
                                   # multi-version ring of core/mvstore.py,
                                   # sharded with the claim tables)
    mv_depth: int = 0              # version-ring depth D (mvcc/mvocc only;
                                   # required >= 1 there, must stay 0 for
                                   # occ — it has no ring)
    snapshot_age: int = 0          # MV readers pin snapshots this many
                                   # waves back (mvstore.snapshot_ts); > 0
                                   # makes ring reclamation fire under load
    pipeline_depth: int = 1        # software-pipeline depth of the scanned
                                   # runners: 1 = the synchronous wave
                                   # (bit-identical to make_wave_fn), >= 2
                                   # overlaps wave N's route/exchange with
                                   # wave N-1's owner compute behind ONE
                                   # fused all_to_all per wave (module
                                   # docstring; 1-shard meshes auto-fall
                                   # back to 1 — see ``depth()``)
    topology: str = "flat"         # exchange factoring: "flat" = one
                                   # n_shards-way all_to_all over the
                                   # combined mesh axes, "axiswise" = one
                                   # smaller exchange per mesh axis on
                                   # >= 2-D meshes (falls back to flat on
                                   # 1-axis meshes)
    # ---- open-loop front-end (make_open_wave_fn; DESIGN.md section 11).
    # queue_cap >= 1 turns on the per-shard admission ring; arrival counts
    # are driver-supplied per wave (workloads/arrivals.PoissonArrivals
    # .shard_counts), so there is no arrival_rate knob here.
    queue_cap: int = 0             # per-SHARD admission-ring capacity
                                   # (0 = closed loop)
    max_incarnations: int = 0      # max re-executions after first attempt;
                                   # past it a txn drops (counted)
    lat_bins: int = 32             # per-shard time-to-commit histogram
                                   # width in waves (last bin = overflow)
    max_extent: int = 1            # widest op interval [key, key+extent):
                                   # 1 = point ops only (the wire and the
                                   # compiled wave are byte-identical to
                                   # the pre-scan engine); > 1 enables
                                   # interval (scan) ops — routed by
                                   # splitting each interval at its range-
                                   # shard boundary (route), validated
                                   # owner-side by iterate_validate, abort
                                   # cause CAUSE_PHANTOM (DESIGN.md
                                   # section 13)
    bucket_size: int = 8           # coarse interval-claim bucket width B
                                   # (records per claim word on the scan
                                   # path; rec_per must divide by it so
                                   # bucket expansion never crosses a
                                   # shard boundary)
    fuse_wave: bool = True         # owner claim step runs as the fused
                                   # wave_commit op (one table pass answers
                                   # the probe AND installs the claims);
                                   # False = claim_probe + XLA verdict
                                   # compare.  Bit-identical either way.
    lane_block: int = 0            # lanes per pallas grid step, 0 = auto
                                   # (EngineConfig.lane_block semantics)

    def __post_init__(self):
        if self.backend not in ("jnp", "pallas"):
            raise ValueError(f"unknown backend {self.backend!r} "
                             "(expected 'jnp' or 'pallas')")
        if self.cc not in DIST_CCS:
            raise ValueError(f"unknown distributed cc {self.cc!r} "
                             f"(expected one of {DIST_CCS})")
        if self.lane_block < 0:
            raise ValueError(
                f"lane_block must be >= 0 (0 = auto), got {self.lane_block}")
        if self.cc in DIST_MV_CCS and self.mv_depth < 1:
            raise ValueError(
                f"cc={self.cc!r} needs the multi-version ring: set "
                "DistConfig.mv_depth >= 1 (the local benchmarks use 4)")
        if self.cc not in DIST_MV_CCS and self.mv_depth:
            raise ValueError(
                f"mv_depth={self.mv_depth} is set but cc={self.cc!r} has "
                "no version ring — use cc='mvcc' or 'mvocc'")
        if self.snapshot_age < 0:
            raise ValueError(
                f"snapshot_age must be >= 0, got {self.snapshot_age}")
        if self.snapshot_age > 0 and self.cc not in DIST_MV_CCS:
            raise ValueError(
                f"snapshot_age={self.snapshot_age} needs a multi-version "
                f"cc (mvcc/mvocc): {self.cc!r} has no snapshots to age")
        if self.pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth={self.pipeline_depth} must be >= 1 "
                "(1 = the synchronous wave; >= 2 = the software pipeline "
                "of the scanned runners)")
        if self.pipeline_depth > 1 and self.snapshot_age > 0:
            raise ValueError(
                f"pipeline_depth={self.pipeline_depth} with snapshot_age="
                f"{self.snapshot_age}: the pipelined wave's mv_gather runs "
                "one wave before the previous wave's mv_install lands, so "
                "an AGED snapshot could read a ring slot the synchronous "
                "engine had already reclaimed — wave-fresh snapshots "
                "(age 0) are provably unaffected (module docstring), aged "
                "readers must run at pipeline_depth=1")
        if self.topology not in TOPOLOGIES:
            raise ValueError(
                f"unknown topology {self.topology!r} (expected one of "
                f"{TOPOLOGIES}; 'axiswise' falls back to 'flat' on 1-axis "
                "meshes)")
        if self.route_cap < 0:
            raise ValueError(
                f"route_cap={self.route_cap} is negative (0 = auto, "
                "positive = explicit per-destination capacity)")
        if 0 < self.route_cap < self.slots:
            raise ValueError(
                f"route_cap={self.route_cap} < slots={self.slots}: one "
                "lane sending its whole transaction to a single shard "
                "could never fit, so every wave would drop it — set "
                "route_cap >= slots (or 0 for auto)")
        if self.route_cap % 8:
            raise ValueError(
                f"route_cap={self.route_cap} must be a multiple of 8: "
                "exchange buffers are the Pallas kernels' lane dimension "
                "and must never be ragged (auto capacity rounds itself)")
        if not 1 <= self.n_groups <= 2:
            raise ValueError(
                f"n_groups={self.n_groups}: the wire meta word packs the "
                "group id into one bit (group | kind << 1 | prio16 << 3)")
        if self.queue_cap < 0:
            raise ValueError(
                f"queue_cap={self.queue_cap} is negative (0 = closed "
                "loop, >= 1 = per-shard admission-ring capacity)")
        if self.max_incarnations < 0:
            raise ValueError(f"max_incarnations must be >= 0, got "
                             f"{self.max_incarnations}")
        if self.queue_cap and self.lat_bins < 2:
            raise ValueError(
                f"lat_bins={self.lat_bins}: the time-to-commit histogram "
                "needs >= 2 bins (the last bin is the overflow bin)")
        if self.max_incarnations and not self.queue_cap:
            raise ValueError(
                f"max_incarnations={self.max_incarnations} shapes the "
                "open-loop admission queue only — set queue_cap >= 1 "
                "(the open-loop switch) to use it")
        if self.max_extent < 1:
            raise ValueError(
                f"max_extent must be >= 1 (1 = point ops), got "
                f"{self.max_extent}")
        if self.max_extent > 0xFFF:
            raise ValueError(
                f"max_extent={self.max_extent} does not fit the wire: the "
                "meta word carries a fragment's scan width in bits 19..30 "
                "(group | kind << 1 | prio16 << 3 | width << 19), so "
                "intervals cap at 4095 records")
        if self.bucket_size < 1:
            raise ValueError(
                f"bucket_size must be >= 1, got {self.bucket_size}")
        if self.max_extent > 1 and self.snapshot_age > 0:
            raise ValueError(
                f"max_extent={self.max_extent} with snapshot_age="
                f"{self.snapshot_age}: interval validation runs against "
                "the CURRENT wave's claim tables, but an aged snapshot "
                "serializes in the past — a scan validated today cannot "
                "protect a cut taken waves ago (the local engine rejects "
                "this identically; EngineConfig)")

    @property
    def open_loop(self) -> bool:
        return self.queue_cap >= 1

    @property
    def is_mv(self) -> bool:
        return self.cc in DIST_MV_CCS

    def cap(self, n_shards: int) -> int:
        """Per-destination buffer capacity: explicit, or 4x the fair share
        — but never below ``slots``, so one lane routing its whole
        transaction to a single shard always fits (the invariant the
        explicit-cap validation enforces).  Always a multiple of 8 (auto
        rounds up, explicit is validated) so Pallas lane tiling never sees
        ragged exchange buffers.  Interval configs (max_extent > 1) double
        the fair share: every op routes up to TWO fragments (one per side
        of a range-shard boundary)."""
        if self.route_cap:
            return self.route_cap
        nfrag = 2 if self.max_extent > 1 else 1
        fair = nfrag * self.lanes_per_shard * self.slots / max(n_shards, 1)
        return -(-max(8, int(4 * fair), self.slots) // 8) * 8

    def depth(self, n_shards: int) -> int:
        """Effective pipeline depth on an ``n_shards`` mesh: 1-shard
        meshes auto-fall back to the synchronous wave (the exchange is a
        local copy there — nothing to overlap), larger meshes run the
        configured ``pipeline_depth``."""
        return 1 if n_shards <= 1 else self.pipeline_depth


def _axes(mesh) -> tuple:
    return tuple(mesh.axis_names)


def _auto(mesh):
    """``mesh`` with plain auto axes: whatever axis types the caller's mesh
    carries (``jax.make_mesh`` makes explicit ones), the engine's sharded
    state stays out of the arrays' types, so host code may index it."""
    return jax.sharding.Mesh(mesh.devices, mesh.axis_names)


def n_shards(mesh) -> int:
    return math.prod(mesh.shape[a] for a in mesh.axis_names)


def wire_bytes_per_wave(cfg: DistConfig, mesh) -> dict:
    """Modeled steady-state exchange payload per shard per wave, in bytes
    — the honest-wire columns of the perf dashboard (this CPU container
    cannot time real interconnects, so the speed story reports what the
    fused collective actually carries):

    - ``route_bytes_per_wave``:   key + meta int32 channels,
      ``n_shards * cap * 8``;
    - ``verdict_bytes_per_wave``: the bit-packed verdict return,
      ``n_shards * verdict_words(cap) * 4``;
    - ``commit_bytes_per_wave``:  the packed commit-bit return, same words;
    - ``verdict_bytes_per_wave_legacy``: the retired 1-int8-per-op scheme
      (``n_shards * cap``), the >= 4x-reduction baseline for 16-aligned
      caps;
    - ``wire_bytes_per_wave``: route + verdict + commit.

    The axiswise topology re-sends the payload once per mesh axis (each
    exchange only crosses one axis), so its bytes count ``len(axes)``
    times on >= 2-D meshes.
    """
    ns = n_shards(mesh)
    cap = cfg.cap(ns)
    W = verdict_words(cap)
    ax = _axes(mesh)
    hops = len(ax) if (cfg.topology == "axiswise" and len(ax) > 1) else 1
    route = ns * cap * 2 * 4
    verdict = ns * W * 4
    commit = ns * W * 4
    return {"route_bytes_per_wave": route * hops,
            "verdict_bytes_per_wave": verdict * hops,
            "commit_bytes_per_wave": commit * hops,
            "verdict_bytes_per_wave_legacy": ns * cap * hops,
            "wire_bytes_per_wave": (route + verdict + commit) * hops}


def _make_exchange(cfg: DistConfig, mesh):
    """The ONE exchange collective of the routed wave.

    Returns ``exchange(buf [n_shards, B]) -> [n_shards, B]`` (arrived row
    i = what shard i sent us), for use inside shard_map over ``mesh``.
    ``topology="flat"`` runs a single n_shards-way ``all_to_all`` over the
    combined mesh axes; ``"axiswise"`` factors it on >= 2-D meshes into
    one exchange per mesh axis (reshape [n_shards, B] to mesh.shape + [B]
    and exchange dim i over axis i — the row-major composition equals the
    flat exchange exactly, with a smaller peer fan-out per collective at
    len(axes)x the wire bytes), falling back to flat on 1-axis meshes.

    Every wave body routes its exchanges through this closure — the AST
    guard in tests/test_pipeline.py pins ``all_to_all`` to this function
    and counts one ``exchange(`` call in the pipelined step bodies.
    """
    ax = _axes(mesh)
    dims = tuple(mesh.shape[a] for a in ax)
    if cfg.topology == "axiswise" and len(ax) > 1:
        steps = [(dims, i, ax[i]) for i in range(len(ax))]
    else:
        steps = [((math.prod(dims),), 0, ax if len(ax) > 1 else ax[0])]

    def exchange(buf):
        with jax.named_scope("repro:exchange"):
            x = buf.reshape(steps[0][0] + buf.shape[1:])
            for _, i, name in steps:
                x = jax.lax.all_to_all(x, axis_name=name, split_axis=i,
                                       concat_axis=i, tiled=True)
            return x.reshape(buf.shape)

    return exchange


def _make_phases(cfg: DistConfig, mesh):
    """The four shard-local phases of the routed wave, factored so the
    synchronous body (``_make_shard_body``) and the software-pipelined
    steps (``_make_pipeline_step`` / ``_make_open_pipeline_step``) share
    one implementation:

    - ``route(keys, groups, kinds, prio) -> (out [ns, 2*cap], send)`` —
      sender side; ``out`` is the concatenated key|meta wire buffer and
      ``send`` the sender's coordinate state ``(owner, pos, took, b_lane,
      lane_dropped, has_write, dropped_op, kinds_flat)`` (the kind channel
      never travels — the sender keeps it to classify abort causes);
    - ``owner_claim(tables, r_buf, wave) -> (tables', v_words [ns, W])`` —
      owner side: fused claim install + probe (and MV snapshot gather),
      verdicts bit-packed for the wire;
    - ``sender_commit(send, v_words) -> (commit [T], c_words [ns, W],
      cause [T])`` — sender side: unpack + gather verdicts by routing
      coordinates, pack the commit bits for the return trip, and classify
      each aborted lane's ABORT_CAUSE code (types.CAUSE_*: min over the
      lane's per-op codes, CAUSE_NONE for committing lanes);
    - ``owner_install(tables, r_buf, c_words, wave) -> tables'`` — owner
      side: version bumps (occ) or ring publishes (mvcc/mvocc) for
      committed writes.

    route never touches the CC tables — the fact that makes the pipeline
    overlap semantics-free (module docstring).
    """
    ns = n_shards(mesh)
    cap = cfg.cap(ns)
    rec_per = -(-cfg.n_records // ns)
    T, K, G = cfg.lanes_per_shard, cfg.slots, cfg.n_groups
    fine = cfg.granularity == 1 and G > 1
    be = kb.resolve(cfg)
    mv = cfg.is_mv
    # Interval (scan) support: the caller's kind channel packs each op's
    # extent in bits 2+ (kind = kinds & 3, extent = max(kinds >> 2, 1) —
    # point workloads leave the high bits zero, so nothing changes for
    # them).  An interval splits into at most TWO fragments at its
    # range-shard boundary, doubling the flat-op axis.
    scans = cfg.max_extent > 1
    nfrag = 2 if scans else 1
    if scans and cfg.max_extent > rec_per:
        raise ValueError(
            f"max_extent={cfg.max_extent} > rec_per={rec_per}: an "
            "interval may cross at most ONE range-shard boundary (two "
            "fragments) — shrink the interval or the shard count")
    if scans and not fine and rec_per % cfg.bucket_size:
        raise ValueError(
            f"bucket_size={cfg.bucket_size} does not divide rec_per="
            f"{rec_per}: coarse interval validation expands fragments to "
            "bucket boundaries, which must never cross a shard boundary")

    def route(keys, groups, kinds, prio):
        # keys/groups/kinds: [T, K] local lanes; prio: [T]
        kind = (kinds & 3) if scans else kinds
        live = (kind != t.NOP) & (keys >= 0)
        owner = jnp.where(live, keys // rec_per, ns)         # dest shard
        lkey = jnp.where(live, keys % rec_per, NO_OP)
        # Pack (group | kind | prio16) into ONE int32 rider word — 2 words
        # per op on the wire; the lane id never travels (the sender keeps
        # the slot->lane map).  Scan fragments add their width in bits
        # 19..30 (0 = point op, keeping pure-point waves byte-identical).
        meta = (groups | (kind << 1)
                | (jnp.broadcast_to(prio[:, None], (T, K)).astype(jnp.int32)
                   << 3))
        lane = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None],
                                (T, K))
        kflat = kinds.reshape(-1)
        if scans:
            # Split each interval [key, key + ext) at its range-shard
            # boundary: fragment 1 stays with the start key's owner,
            # fragment 2 (the remainder, possibly empty) routes to the
            # NEXT shard and starts at its row 0.  Verdicts AND-reduce
            # back on the sender like any other op of the lane.
            ext = jnp.maximum(kinds >> 2, 1)
            is_sc = (kinds >> 2) > 1
            bound = (keys // rec_per + 1) * rec_per
            w1 = jnp.minimum(keys + ext, bound) - keys
            w2 = keys + ext - jnp.minimum(keys + ext, bound)
            meta = meta | (jnp.where(live & is_sc, w1, 0) << 19)
            live2 = live & is_sc & (w2 > 0)
            owner2 = jnp.where(live2, owner + 1, ns)
            lkey2 = jnp.where(live2, 0, NO_OP)
            meta2 = jnp.where(live2, (meta & ((1 << 19) - 1)) | (w2 << 19),
                              META_FILL)
            owner_f = jnp.concatenate([owner.reshape(-1),
                                       owner2.reshape(-1)])
            vals = jnp.stack([
                jnp.concatenate([lkey.reshape(-1), lkey2.reshape(-1)]),
                jnp.concatenate([meta.reshape(-1), meta2.reshape(-1)]),
                jnp.concatenate([lane.reshape(-1), lane.reshape(-1)])])
            kflat = jnp.concatenate(
                [kflat, jnp.where(live2, kinds, t.NOP).reshape(-1)])
        else:
            owner_f = owner.reshape(-1)
            vals = jnp.stack([lkey.reshape(-1), meta.reshape(-1),
                              lane.reshape(-1)])
        buf, pos, took = be.route_pack(owner_f, vals, ns, cap,
                                       (NO_OP, META_FILL, LANE_FILL))
        b_key, b_meta, b_lane = buf[0], buf[1], buf[2]
        # capacity-dropped ops abort their lane (no scatter: took is
        # flat-op aligned, so a reshape + any does the lane reduce)
        dropped_op = ~took & (owner_f < ns)
        if scans:
            lane_dropped = dropped_op.reshape(2, T, K).any(axis=(0, 2))
        else:
            lane_dropped = dropped_op.reshape(T, K).any(axis=1)
        has_write = (live & ((kind == t.WRITE)
                             | (kind == t.ADD))).any(axis=1)
        out = jnp.concatenate([b_key, b_meta], axis=-1)      # [ns, 2*cap]
        send = (jnp.clip(owner_f, 0, ns - 1),
                jnp.clip(pos, 0, cap - 1), took, b_lane,
                lane_dropped, has_write, dropped_op, kflat)
        return out, send

    def _decode(r_buf):
        """Arrived [ns, 2*cap] wire buffer -> owner-side op arrays."""
        r_key, r_meta = r_buf[:, :cap], r_buf[:, cap:]
        r_live = r_key != NO_OP
        rk = jnp.where(r_live, r_key, -1)    # masked-op convention of the
        r_grp = r_meta & 1                   # backend surface: key -1
        r_kind = (r_meta >> 1) & 3
        r_prio = ((r_meta >> 3) & 0xFFFF).astype(jnp.uint32)
        return rk, r_grp, r_kind, r_prio, r_live

    def owner_claim(tables, r_buf, wave_idx):
        rk, r_grp, r_kind, r_prio, r_live = _decode(r_buf)
        is_w = r_live & ((r_kind == t.WRITE) | (r_kind == t.ADD))
        is_r = r_live & (r_kind == t.READ)
        if scans:
            # Scan fragments (meta width bits > 0) leave the point verdict
            # channel and validate their whole local interval against the
            # POST-install claim shard instead — op sixteen,
            # iterate_validate (DESIGN.md section 13).  The owner never
            # learns lane composition, so the phantom verdict rides the
            # existing bits and the SENDER classifies CAUSE_PHANTOM by
            # the op's packed kind.
            r_w = (r_buf[:, cap:] >> 19) & 0xFFF
            is_sc = r_live & (r_w > 0)
            is_rp = is_r & ~is_sc
        else:
            is_rp = is_r
        if not mv:
            # Single-version OCC: ONE table pass; verdict bit 0 = read
            # claimed by a stronger lane.  Fused (default): the
            # wave_commit megakernel answers the verdicts directly from
            # its in-VMEM reduction; unfused: claim_probe + XLA compare.
            # Bit-identical — the kernel evaluates the same mask algebra.
            wts, claim_w = tables
            if cfg.fuse_wave:
                claim_w, _, _, conflict, _ = be.wave_commit(
                    claim_w, None, None, rk, r_grp, r_prio, is_w, None,
                    is_rp, None, None, None, wave_idx, fine, False, False)
                v = conflict.astype(jnp.int8)
            else:
                claim_w, wprio = be.claim_probe(claim_w, rk, r_grp, r_prio,
                                                wave_idx, is_w, fine)
                v = (is_rp & (wprio < r_prio)).astype(jnp.int8)
            if scans:
                ph = be.iterate_validate(
                    claim_w, rk, jnp.maximum(r_w, 1), r_grp, r_prio,
                    is_sc, wave_idx, fine, cfg.bucket_size, cfg.max_extent)
                v = v | ph.astype(jnp.int8)
            tables = (wts, claim_w)
        else:
            # The local fcw_conflicts + mv snapshot check (cc/mvcc.py),
            # per shard: claim_w carries ALL writes, claim_r only plain
            # WRITEs (so ADD-ADD pairs commute); reads consult the ring.
            claim_w, claim_r, mv_begin, mv_head = tables
            is_pw = r_live & (r_kind == t.WRITE)
            is_ad = r_live & (r_kind == t.ADD)
            claim_w, wprio_w = be.claim_probe(claim_w, rk, r_grp, r_prio,
                                              wave_idx, is_w, fine)
            claim_r, wprio_r = be.claim_probe(claim_r, rk, r_grp, r_prio,
                                              wave_idx, is_pw, fine)
            _, ok = be.mv_gather(
                mv_begin, rk, r_grp,
                mvstore.snapshot_ts(wave_idx, cfg.snapshot_age), fine)
            # bit 0: unconditional — FCW write-write (a plain WRITE loses
            # to any stronger writer, an ADD only to a stronger plain
            # WRITE) and snapshot reclamation (the aged-reader abort).
            uncond = ((is_pw & (wprio_w < r_prio))
                      | (is_ad & (wprio_r < r_prio))
                      | (is_r & ~ok))
            # bit 1: read-validation — only mvocc applies it, and only to
            # update lanes; the sender owns that mask (lane composition
            # never travels).  Scan fragments re-route through the
            # interval pass (mvocc only — mvcc scans read a consistent
            # snapshot cut and never re-validate; cc/mvcc.py).
            rdval = is_rp & (wprio_w < r_prio)
            if scans and cfg.cc == "mvocc":
                ph = be.iterate_validate(
                    claim_w, rk, jnp.maximum(r_w, 1), r_grp, r_prio,
                    is_sc, wave_idx, fine, cfg.bucket_size, cfg.max_extent)
                rdval = rdval | ph
            v = uncond.astype(jnp.int8) | (rdval.astype(jnp.int8) << 1)
            tables = (claim_w, claim_r, mv_begin, mv_head)
        return tables, be.verdict_pack(v)

    def sender_commit(send, v_words):
        # Gathered back by each op's routing coordinates — sort-free and
        # scatter-free, the inverse of route_pack's placement.
        (owner_c, pos_c, took, b_lane, lane_dropped, has_write, dropped_op,
         kind_f) = send
        if scans:
            # The kind channel packs extents (route); a conflict on a scan
            # fragment IS a phantom — no extra wire bit needed.
            is_sc_f = (kind_f >> 2) > 1
            kind_f = kind_f & 3
        vv = be.verdict_unpack(v_words, cap)[owner_c, pos_c]
        bit0 = ((vv & 1) > 0) & took
        op_conf = bit0
        # Per-op ABORT_CAUSE codes mirror the verdict channels exactly
        # (the owner's bit semantics in owner_claim): the sender holds the
        # op-kind channel, so no cause ever travels on the wire.
        if not mv:
            cause = jnp.where(bit0, jnp.int32(t.CAUSE_READ_VAL),
                              jnp.int32(t.CAUSE_NONE))
            if scans:
                cause = jnp.where(bit0 & is_sc_f,
                                  jnp.int32(t.CAUSE_PHANTOM), cause)
        else:
            cause = jnp.full_like(kind_f, t.CAUSE_NONE)
            if cfg.cc == "mvocc":
                hw_op = jnp.broadcast_to(has_write[:, None],
                                         (T, K)).reshape(-1)
                if scans:
                    hw_op = jnp.concatenate([hw_op, hw_op])
                rdval = ((vv & 2) > 0) & hw_op & took
                op_conf = op_conf | rdval
                cause = jnp.where(rdval, jnp.int32(t.CAUSE_READ_VAL),
                                  cause)
                if scans:
                    cause = jnp.where(rdval & is_sc_f,
                                      jnp.int32(t.CAUSE_PHANTOM), cause)
            # bit 0 on a write op is a first-committer-wins w-w loss; on a
            # read op it is snapshot reclamation (cc/mvcc.py's disjoint
            # channels) — reclamation outranks the mvocc read validation.
            is_wr = (kind_f == t.WRITE) | (kind_f == t.ADD)
            cause = jnp.where(bit0 & is_wr, jnp.int32(t.CAUSE_WW), cause)
            cause = jnp.where(bit0 & ~is_wr,
                              jnp.int32(t.CAUSE_STALE_SNAPSHOT), cause)
        cause = jnp.where(dropped_op, jnp.int32(t.CAUSE_CAPACITY), cause)
        if scans:
            # Fragment verdicts AND-reduce per lane (both fragments of an
            # interval must survive); causes min-reduce like any op.
            commit = (~op_conf.reshape(2, T, K).any(axis=(0, 2))
                      & ~lane_dropped)
            lane_cause = cause.reshape(2, T, K).min(axis=(0, 2))
        else:
            commit = ~op_conf.reshape(T, K).any(axis=1) & ~lane_dropped
            lane_cause = cause.reshape(T, K).min(axis=1)
        b_commit = jnp.where(
            b_lane >= 0,
            commit[jnp.clip(b_lane, 0, T - 1)].astype(jnp.int8),
            jnp.int8(0))
        return commit, be.verdict_pack(b_commit), lane_cause

    def owner_install(tables, r_buf, c_words, wave_idx):
        rk, r_grp, r_kind, _, r_live = _decode(r_buf)
        is_w = r_live & ((r_kind == t.WRITE) | (r_kind == t.ADD))
        bump = is_w & (be.verdict_unpack(c_words, cap) > 0)
        if not mv:
            wts, claim_w = tables
            wts = be.commit_install(wts, rk, r_grp, bump)
            return (wts, claim_w)
        claim_w, claim_r, mv_begin, mv_head = tables
        mv_begin, mv_head = be.mv_install(
            mv_begin, mv_head, rk, r_grp, bump,
            mvstore.install_ts(wave_idx))
        return (claim_w, claim_r, mv_begin, mv_head)

    # Profiler-visible phase attribution (jax.profiler / Perfetto): each
    # phase's ops group under one named scope in the trace viewer.
    route = jax.named_scope("repro:route")(route)
    owner_claim = jax.named_scope("repro:claim")(owner_claim)
    sender_commit = jax.named_scope("repro:commit")(sender_commit)
    owner_install = jax.named_scope("repro:install")(owner_install)
    return route, owner_claim, sender_commit, owner_install


def _make_shard_body(cfg: DistConfig, mesh):
    """The SYNCHRONOUS (pipeline_depth 1) shard-local routed wave: route ->
    claim -> verdict -> install within one call (module docstring), three
    ``exchange`` round trips.  Returns ``body(keys, groups, kinds, prio,
    tables, wave_idx) -> (commit, tables', lane_dropped, has_write,
    dropped_op)`` — the op pipeline shared by the closed-loop wave
    (make_wave_fn) and the open-loop wave (make_open_wave_fn); only the
    traffic model around it differs.  Must be called inside shard_map over
    ``mesh``'s axes (the exchange closure names them).
    """
    route, owner_claim, sender_commit, owner_install = _make_phases(cfg,
                                                                    mesh)
    exchange = _make_exchange(cfg, mesh)

    def body(keys, groups, kinds, prio, tables, wave_idx):
        out, send = route(keys, groups, kinds, prio)
        r_buf = exchange(out)
        tables, v_words = owner_claim(tables, r_buf, wave_idx)
        commit, c_words, cause = sender_commit(send, exchange(v_words))
        tables = owner_install(tables, r_buf, exchange(c_words), wave_idx)
        _, _, _, _, lane_dropped, has_write, dropped_op, _ = send
        return commit, tables, lane_dropped, has_write, dropped_op, cause

    return body


@jax.named_scope("repro:account")
def _closed_stats(commit, lane_dropped, has_write, dropped_op, cause):
    ro = ~has_write
    z = jnp.int32(0)
    head = jnp.stack([commit.sum(), (~commit).sum(), lane_dropped.sum(),
                      dropped_op.sum(), (commit & ro).sum(),
                      (~commit & ro).sum(), z, z, z, z]).astype(jnp.int32)
    return jnp.concatenate([head, t.cause_counts(cause, ~commit)])


def _pipe_carry_init(cfg: DistConfig, ns: int, tables):
    """Zero pipeline state: NO_OP-filled routed buffers and empty sender
    coordinates, so the warmup steps' owner/sender phases are fully masked
    table no-ops (every op dead, every commit bit 0)."""
    cap = cfg.cap(ns)
    T, K = cfg.lanes_per_shard, cfg.slots
    W = verdict_words(cap)
    # Interval configs route up to two fragments per op (_make_phases), so
    # the flat-op coordinate axis doubles.
    M = T * K * (2 if cfg.max_extent > 1 else 1)
    rb = jnp.concatenate([jnp.full((ns, cap), NO_OP, jnp.int32),
                          jnp.full((ns, cap), META_FILL, jnp.int32)],
                         axis=-1)
    vz = jnp.zeros((ns, W), jnp.int32)
    st = (jnp.zeros((M,), jnp.int32),                  # owner (clipped)
          jnp.zeros((M,), jnp.int32),                  # pos (clipped)
          jnp.zeros((M,), jnp.bool_),                  # took
          jnp.full((ns, cap), LANE_FILL, jnp.int32),   # b_lane
          jnp.zeros((T,), jnp.bool_),                  # lane_dropped
          jnp.zeros((T,), jnp.bool_),                  # has_write
          jnp.zeros((M,), jnp.bool_),                  # dropped_op
          jnp.full((M,), t.NOP, jnp.int32))            # kinds_flat
    return (tables, rb, rb, rb, vz, vz, st, st)


def _make_pipeline_step(cfg: DistConfig, mesh):
    """One steady-state step of the software-pipelined CLOSED-LOOP wave
    (module docstring schedule): install wave w-3, claim wave w-1, commit
    wave w-2, route wave w, then ONE fused exchange of
    ``[O_key | O_meta | V_{w-1} | C_{w-2}]``.  Emits wave w-2's (commit,
    stats); the scanned runner drops the two warmup rows and appends three
    NOP drain waves (the third flushes the final wave's installs)."""
    route, owner_claim, sender_commit, owner_install = _make_phases(cfg,
                                                                    mesh)
    exchange = _make_exchange(cfg, mesh)
    ns = n_shards(mesh)
    cap = cfg.cap(ns)
    W = verdict_words(cap)

    def step(carry, x):
        tables, rb1, rb2, rb3, v_in, c_in, st1, st2 = carry
        keys, groups, kinds, prio, wave = x
        with jax.named_scope("repro:schedule"):
            w_install, w_claim = wave - jnp.uint32(3), wave - jnp.uint32(1)
        tables = owner_install(tables, rb3, c_in, w_install)
        tables, v_words = owner_claim(tables, rb1, w_claim)
        commit, c_words, cause = sender_commit(st2, v_in)
        out, st0 = route(keys, groups, kinds, prio)
        with jax.named_scope("repro:exchange"):
            arrived = exchange(jnp.concatenate([out, v_words, c_words],
                                               axis=-1))
            r_out = arrived[:, :2 * cap]
            v_nxt = arrived[:, 2 * cap:2 * cap + W]
            c_nxt = arrived[:, 2 * cap + W:]
        stats = _closed_stats(commit, st2[4], st2[5], st2[6], cause)
        carry = (tables, r_out, rb1, rb2, v_nxt, c_nxt, st0, st1)
        return carry, (commit, stats)

    return step


def _spec_ops(mesh):
    ax = _axes(mesh)
    return P(ax if len(ax) > 1 else ax[0])


def _spec_stack(mesh):
    """Sharding for wave-stacked arrays ([n_waves, ...]: wave axis
    replicated, lane/shard axis split)."""
    ax = _axes(mesh)
    return P(None, ax if len(ax) > 1 else ax[0])


def make_wave_fn(cfg: DistConfig, mesh):
    """Returns wave(keys, groups, kinds, prio, tables, wave_idx) ->
    (commit [T], tables', stats) — all arguments globally shaped, sharded
    over the combined mesh axes.  ``tables`` is the mechanism's state tuple
    (see module docstring / ``init_tables``); ``stats`` is
    int32[STATS_LEN] per shard: [commits, aborts, capacity-dropped lanes,
    dropped ops, read-only commits, read-only aborts, zeros in the
    open-loop slots (this is the closed-loop wave), then the six per-cause
    abort counts (slots STAT_CAUSES, summing exactly to aborts)].

    This is the one-wave-per-call SYNCHRONOUS driver: it cannot overlap
    waves, so configs whose effective depth exceeds 1 are rejected — use
    ``make_run_fn`` for the pipelined scanned runner (on a 1-shard mesh
    ``pipeline_depth`` auto-falls back to 1 and this driver still works).

    The resolved backend (``cfg.backend``) is threaded into the
    shard-local wave; route/claim/probe/gather/install all run through its
    surface ops on the shard's table slices.
    """
    ns = n_shards(mesh)
    if cfg.depth(ns) > 1:
        raise ValueError(
            f"make_wave_fn is the one-wave-per-call synchronous driver: "
            f"pipeline_depth={cfg.pipeline_depth} on a {ns}-shard mesh "
            "needs the scanned runner — use make_run_fn(cfg, mesh, "
            "n_waves) (1-shard meshes auto-fall back to depth 1)")
    body = _make_shard_body(cfg, mesh)
    mv = cfg.is_mv

    def local_wave(keys, groups, kinds, prio, tables, wave_idx):
        commit, tables, lane_dropped, has_write, dropped_op, cause = body(
            keys, groups, kinds, prio, tables, wave_idx)
        stats = _closed_stats(commit, lane_dropped, has_write, dropped_op,
                              cause)
        return commit, tables, stats

    spec_ops = _spec_ops(mesh)
    tab_spec = (spec_ops,) * (4 if mv else 2)
    wave = jax.shard_map(
        local_wave, mesh=_auto(mesh),
        in_specs=(spec_ops, spec_ops, spec_ops, spec_ops, tab_spec, P()),
        out_specs=(spec_ops, tab_spec, spec_ops),
        check_vma=False)
    return wave


def make_run_fn(cfg: DistConfig, mesh, n_waves: int):
    """The scanned CLOSED-LOOP runner: returns ``run(keys [n_waves, ns*T,
    K], groups, kinds, prio [n_waves, ns*T], tables, wave0) -> (commit
    [n_waves, ns*T], tables', stats [n_waves, ns*STATS_LEN])`` — the whole
    run is ONE XLA program (lax.scan inside shard_map), so waves/s
    measures the wave, not host dispatch.

    ``cfg.depth(n_shards)`` selects the schedule: depth 1 scans the
    synchronous body (three exchanges per wave — bit-identical to a
    make_wave_fn loop), depth >= 2 scans the software-pipelined step (ONE
    fused exchange per wave; the scan runs ``n_waves + 3`` steps, the
    three NOP-padded drain waves flushing the in-flight buffers, and the
    two warmup output rows are dropped) — bit-identical to depth 1 for occ
    always and mvcc/mvocc at snapshot_age 0 (module docstring)."""
    ns = n_shards(mesh)
    depth = cfg.depth(ns)
    mv = cfg.is_mv
    T, K = cfg.lanes_per_shard, cfg.slots

    if depth == 1:
        body = _make_shard_body(cfg, mesh)

        def local_run(keys, groups, kinds, prio, tables, wave0):
            def step(tables, x):
                k, g, i, p, w = x
                (commit, tables, lane_dropped, has_write, dropped_op,
                 cause) = body(k, g, i, p, tables, w)
                stats = _closed_stats(commit, lane_dropped, has_write,
                                      dropped_op, cause)
                return tables, (commit, stats)

            waves = wave0 + jnp.arange(n_waves, dtype=jnp.uint32)
            tables, (commit, stats) = jax.lax.scan(
                step, tables, (keys, groups, kinds, prio, waves))
            return commit, tables, stats
    else:
        pstep = _make_pipeline_step(cfg, mesh)

        def local_run(keys, groups, kinds, prio, tables, wave0):
            keys = jnp.concatenate(
                [keys, jnp.full((3, T, K), -1, jnp.int32)])
            groups = jnp.concatenate(
                [groups, jnp.zeros((3, T, K), jnp.int32)])
            kinds = jnp.concatenate(
                [kinds, jnp.full((3, T, K), t.NOP, jnp.int32)])
            prio = jnp.concatenate([prio, jnp.zeros((3, T), jnp.uint32)])
            waves = wave0 + jnp.arange(n_waves + 3, dtype=jnp.uint32)
            carry = _pipe_carry_init(cfg, ns, tables)
            carry, (commit, stats) = jax.lax.scan(
                pstep, carry, (keys, groups, kinds, prio, waves))
            return (commit[2:2 + n_waves], carry[0],
                    stats[2:2 + n_waves])

    spec = _spec_stack(mesh)
    tab_spec = (_spec_ops(mesh),) * (4 if mv else 2)
    run = jax.shard_map(
        local_run, mesh=_auto(mesh),
        in_specs=(spec, spec, spec, spec, tab_spec, P()),
        out_specs=(spec, tab_spec, spec),
        check_vma=False)
    return run


def make_open_wave_fn(cfg: DistConfig, mesh):
    """The OPEN-LOOP routed wave (DESIGN.md section 11): each shard runs a
    fixed-capacity admission ring in front of the shared shard body
    (_make_shard_body), mirroring the local engine's core/admission.py.
    Like ``make_wave_fn`` this is the one-wave-per-call synchronous driver
    — pipelined open-loop runs go through ``run_open_loop`` (which scans
    ``_make_open_pipeline_step``).

    Returns ``open_wave(keys, groups, kinds, prio, n_arrive, tables,
    qstate, wave_idx) -> (commit, tables', qstate', stats)``:

    - keys/groups/kinds [ns*T, K]: the wave's FRESH arrival candidates
      (the front-end materializes at most T per shard per wave); the first
      ``n_arrive[shard]`` lanes of each shard's slice actually arrive —
      the driver draws the counts host-side
      (workloads/arrivals.PoissonArrivals.shard_counts).
    - prio [ns*T]: per-lane wave priorities for the DEQUEUED lanes.
    - qstate: the sharded queue tuple from ``init_open_queue``.
    - stats int32[ns, STATS_LEN] flattened: slots 6..9 carry
      admitted/arrival_drops/inc_drops (per-wave deltas) and the post-wave
      queue occupancy snapshot; slots 10..15 are the per-cause abort
      counts (terminal aborts reclassify as CAUSE_INC_CAP, so
      causes[CAUSE_INC_CAP] == inc_drops here at depth 1).

    Ring discipline per shard and wave — enqueue arrivals, dequeue up to T
    lanes FIFO, run the routed wave, re-enqueue aborted lanes with
    incarnation + 1 (drop + count past ``cfg.max_incarnations``), record
    committed lanes' time-to-commit (waves) in the shard's histogram.
    Arrivals land before the dequeue frees lanes, so the re-enqueue can
    never overflow (the core/admission.py invariant; the conservation
    oracle in tests/test_open_loop.py reconciles the counters exactly).
    """
    if not cfg.open_loop:
        raise ValueError("make_open_wave_fn needs queue_cap >= 1 "
                         "(the open-loop switch); use make_wave_fn for "
                         "closed-loop waves")
    ns = n_shards(mesh)
    if cfg.depth(ns) > 1:
        raise ValueError(
            f"make_open_wave_fn is the one-wave-per-call synchronous "
            f"driver: pipeline_depth={cfg.pipeline_depth} on a {ns}-shard "
            "mesh needs the scanned runner — use run_open_loop (1-shard "
            "meshes auto-fall back to depth 1)")
    body = _make_shard_body(cfg, mesh)
    mv = cfg.is_mv
    T, K = cfg.lanes_per_shard, cfg.slots
    C = cfg.queue_cap

    def local_wave(keys, groups, kinds, prio, n_arrive, tables, qstate,
                   wave_idx):
        (qk, qg, qi, qa, qc, qd, head, size, next_id, lat_hist) = qstate
        head, size, nid = head[0], size[0], next_id[0]
        w = wave_idx.astype(jnp.int32)

        def enq(head, size, mask, ek, eg, ei, ea, ec, ed):
            """Append masked lanes into the ring (ascending lane order);
            the cumsum-rank placement of admission.ring_enqueue."""
            tabs, size, n_acc, n_ovf = admission.ring_enqueue(
                C, head, size, mask, (qk, qg, qi, qa, qc, qd),
                (ek, eg, ei, ea, ec, ed))
            return tabs + (size, n_acc, n_ovf)

        with jax.named_scope("repro:schedule"):
            # --- arrivals: first n_arrive fresh lanes enter the ring -----
            n_arr = jnp.minimum(n_arrive[0], T)
            arr = jnp.arange(T, dtype=jnp.int32) < n_arr
            ids = nid + jnp.arange(T, dtype=jnp.int32)
            qk, qg, qi, qa, qc, qd, size, n_adm, n_ovf = enq(
                head, size, arr, keys, groups, kinds,
                jnp.full((T,), w, jnp.int32), jnp.zeros((T,), jnp.int32),
                ids)

            # --- admit: fill the shard's T lanes FIFO --------------------
            take = jnp.minimum(size, T)
            i = jnp.arange(T, dtype=jnp.int32)
            got = i < take
            pos = (head + i) % C
            dk = jnp.where(got[:, None], qk[pos, :], -1)
            dg = jnp.where(got[:, None], qg[pos, :], 0)
            di = jnp.where(got[:, None], qi[pos, :], t.NOP)
            admit_w = jnp.where(got, qa[pos], 0)
            incarn = jnp.where(got, qc[pos], 0)
            head, size = (head + take) % C, size - take

        # --- the routed wave on the admitted lanes ----------------------
        commit, tables, lane_dropped, has_write, dropped_op, cause = body(
            dk, dg, di, prio, tables, wave_idx)

        # --- retry incarnations ------------------------------------------
        with jax.named_scope("repro:schedule"):
            commit = commit & got
            aborted = got & ~commit
            retry = aborted & (incarn < cfg.max_incarnations)
            inc_drop = aborted & ~retry
            # Arrivals enqueued before the dequeue freed these slots, so
            # this can never overflow (n_re_ovf stays 0; the oracle
            # asserts it via the exact counter reconciliation).
            qk, qg, qi, qa, qc, qd, size, _, n_re_ovf = enq(
                head, size, retry, dk, dg, di, admit_w, incarn + 1,
                jnp.where(got, qd[pos], -1))

        # --- latency and stats -------------------------------------------
        with jax.named_scope("repro:account"):
            # A terminal abort leaves the system as an incarnation drop —
            # that outcome outranks whatever validation verdict killed the
            # attempt (CAUSE_INC_CAP is the lowest code), mirroring the
            # local engine.
            cause = jnp.where(inc_drop, jnp.int32(t.CAUSE_INC_CAP), cause)
            lat_hist = admission.record_ttc(lat_hist, w - admit_w + 1,
                                            commit)
            ro = ~has_write
            head_stats = jnp.stack([
                commit.sum(), aborted.sum(), lane_dropped.sum(),
                dropped_op.sum(),
                (commit & ro).sum(), (aborted & ro).sum(),
                n_adm, n_ovf + n_re_ovf,
                inc_drop.sum(), size]).astype(jnp.int32)
            stats = jnp.concatenate([head_stats,
                                     t.cause_counts(cause, aborted)])
        qstate = (qk, qg, qi, qa, qc, qd, head[None], size[None],
                  (nid + n_arr)[None], lat_hist)
        return commit, tables, qstate, stats

    spec = _spec_ops(mesh)
    tab_spec = (spec,) * (4 if mv else 2)
    q_spec = (spec,) * 10
    wave = jax.shard_map(
        local_wave, mesh=_auto(mesh),
        in_specs=(spec, spec, spec, spec, spec, tab_spec, q_spec, P()),
        out_specs=(spec, tab_spec, q_spec, spec),
        check_vma=False)
    return wave


def _make_open_pipeline_step(cfg: DistConfig, mesh):
    """One steady-state step of the software-pipelined OPEN-LOOP wave: the
    closed pipeline schedule (_make_pipeline_step) with the per-shard
    admission ring threaded through the carry.  Wave w-2's verdicts land
    this step, so its aborted lanes re-enqueue TWO waves after they ran —
    the retry latency the pipeline buys its overlap with.  Retries
    re-enter the ring before this step's fresh arrivals (oldest first);
    with two waves in flight the depth-1 "re-enqueue can never overflow"
    invariant no longer holds, so a retry the full ring rejects leaves the
    system as an incarnation drop (counted — the conservation identity
    ``admitted == commits + queued_final + inc_drops`` stays exact)."""
    route, owner_claim, sender_commit, owner_install = _make_phases(cfg,
                                                                    mesh)
    exchange = _make_exchange(cfg, mesh)
    ns = n_shards(mesh)
    cap = cfg.cap(ns)
    W = verdict_words(cap)
    T, K = cfg.lanes_per_shard, cfg.slots
    C = cfg.queue_cap

    def step(carry, x):
        (tables, rb1, rb2, rb3, v_in, c_in, st1, st2, os1, os2,
         qk, qg, qi, qa, qc, qd, head, size, nid, lat_hist) = carry
        keys, groups, kinds, prio, n_arrive, wave, live_w = x

        # --- owner phases: install wave w-3, claim wave w-1 -------------
        with jax.named_scope("repro:schedule"):
            w_install, w_claim = wave - jnp.uint32(3), wave - jnp.uint32(1)
        tables = owner_install(tables, rb3, c_in, w_install)
        tables, v_words = owner_claim(tables, rb1, w_claim)

        # --- sender: commit wave w-2, ring bookkeeping -------------------
        commit, c_words, cause = sender_commit(st2, v_in)
        dk2, dg2, di2, admit2, inc2, got2, qid2, n_adm2, n_ovf2 = os2
        with jax.named_scope("repro:schedule"):
            commit = commit & got2
            aborted = got2 & ~commit
            retry = aborted & (inc2 < cfg.max_incarnations)
            (qk, qg, qi, qa, qc, qd), size, _, n_re_ovf = \
                admission.ring_enqueue(
                    C, head, size, retry, (qk, qg, qi, qa, qc, qd),
                    (dk2, dg2, di2, admit2, inc2 + 1, qid2))

            # --- arrivals for wave w -------------------------------------
            n_arr = jnp.where(live_w, jnp.minimum(n_arrive, T), 0)
            arr = jnp.arange(T, dtype=jnp.int32) < n_arr
            ids = nid + jnp.arange(T, dtype=jnp.int32)
            (qk, qg, qi, qa, qc, qd), size, n_adm, n_ovf = \
                admission.ring_enqueue(
                    C, head, size, arr, (qk, qg, qi, qa, qc, qd),
                    (keys, groups, kinds,
                     jnp.full((T,), wave.astype(jnp.int32), jnp.int32),
                     jnp.zeros((T,), jnp.int32), ids))
            nid = nid + n_arr

            # --- dequeue wave w's lanes (never on drain steps) -----------
            take = jnp.where(live_w, jnp.minimum(size, T), 0)
            i = jnp.arange(T, dtype=jnp.int32)
            got = i < take
            pos = (head + i) % C
            dk = jnp.where(got[:, None], qk[pos, :], -1)
            dg = jnp.where(got[:, None], qg[pos, :], 0)
            di = jnp.where(got[:, None], qi[pos, :], t.NOP)
            admit_w = jnp.where(got, qa[pos], 0)
            incarn = jnp.where(got, qc[pos], 0)
            qid = jnp.where(got, qd[pos], -1)
            head, size = (head + take) % C, size - take

        # --- route wave w, ONE fused exchange ----------------------------
        out, st0 = route(dk, dg, di, prio)
        with jax.named_scope("repro:exchange"):
            arrived = exchange(jnp.concatenate([out, v_words, c_words],
                                               axis=-1))
            r_out = arrived[:, :2 * cap]
            v_nxt = arrived[:, 2 * cap:2 * cap + W]
            c_nxt = arrived[:, 2 * cap + W:]

        with jax.named_scope("repro:account"):
            # Terminal aborts reclassify as CAUSE_INC_CAP like the
            # synchronous wave; a retry the full ring rejects (n_re_ovf)
            # KEEPS its validation cause — ring_enqueue exposes no
            # per-lane overflow mask — so causes[CAUSE_INC_CAP] <=
            # inc_drops at depth >= 2 while the per-cause sum still equals
            # aborts exactly.
            cause = jnp.where(aborted & ~retry, jnp.int32(t.CAUSE_INC_CAP),
                              cause)
            inc_drop = (aborted & ~retry).sum() + n_re_ovf
            w2 = (wave.astype(jnp.int32) - 2)
            lat_hist = admission.record_ttc(lat_hist, w2 - admit2 + 1,
                                            commit)
            # Every counter in the emitted row belongs to wave w-2 (the
            # wave whose fate resolved this step): its admission counters
            # rode the os carry from the step that enqueued it, so the
            # runner's [2 : 2+n_waves] slice conserves exactly.  QUEUED
            # stays a current occupancy snapshot (informational; the
            # driver's queued_final reads the final qstate, not this
            # column).
            ro = ~st2[5]
            head_stats = jnp.stack([
                commit.sum(), aborted.sum(), st2[4].sum(), st2[6].sum(),
                (commit & ro).sum(), (aborted & ro).sum(),
                n_adm2, n_ovf2, inc_drop, size]).astype(jnp.int32)
            stats = jnp.concatenate([head_stats, t.cause_counts(cause,
                                                                aborted)])
        os0 = (dk, dg, di, admit_w, incarn, got, qid, n_adm, n_ovf)
        carry = (tables, r_out, rb1, rb2, v_nxt, c_nxt, st0, st1, os0, os1,
                 qk, qg, qi, qa, qc, qd, head, size, nid, lat_hist)
        return carry, (commit, stats)

    return step


def make_open_run_fn(cfg: DistConfig, mesh, n_waves: int):
    """The scanned PIPELINED open-loop runner (cfg.depth(n_shards) >= 2):
    returns ``run(keys [n_waves, ns*T, K], groups, kinds, prio [n_waves,
    ns*T], n_arrive [n_waves, ns], tables, qstate, wave0) -> (commit
    [n_waves, ns*T], tables', qstate', stats [n_waves, ns*STATS_LEN])``.
    The scan runs ``n_waves + 3`` steps — the three drain steps admit no
    arrivals and dequeue no lanes, they only flush the in-flight waves —
    and drops the two warmup output rows, so row w is wave w's commit."""
    if not cfg.open_loop:
        raise ValueError("make_open_run_fn needs queue_cap >= 1 "
                         "(the open-loop switch)")
    ns = n_shards(mesh)
    if cfg.depth(ns) < 2:
        raise ValueError(
            "make_open_run_fn is the pipelined scanned runner: "
            f"effective depth {cfg.depth(ns)} on this mesh runs the "
            "synchronous make_open_wave_fn instead (run_open_loop picks)")
    pstep = _make_open_pipeline_step(cfg, mesh)
    mv = cfg.is_mv
    T, K = cfg.lanes_per_shard, cfg.slots

    def local_run(keys, groups, kinds, prio, n_arrive, tables, qstate,
                  wave0):
        (qk, qg, qi, qa, qc, qd, head, size, next_id, lat_hist) = qstate
        keys = jnp.concatenate([keys, jnp.full((3, T, K), -1, jnp.int32)])
        groups = jnp.concatenate([groups, jnp.zeros((3, T, K), jnp.int32)])
        kinds = jnp.concatenate(
            [kinds, jnp.full((3, T, K), t.NOP, jnp.int32)])
        prio = jnp.concatenate([prio, jnp.zeros((3, T), jnp.uint32)])
        n_arr = jnp.concatenate([n_arrive[:, 0],
                                 jnp.zeros((3,), n_arrive.dtype)])
        n_steps = n_waves + 3
        waves = wave0 + jnp.arange(n_steps, dtype=jnp.uint32)
        live = jnp.arange(n_steps) < n_waves
        open_slot = (jnp.full((T, K), -1, jnp.int32),
                     jnp.zeros((T, K), jnp.int32),
                     jnp.full((T, K), t.NOP, jnp.int32),
                     jnp.zeros((T,), jnp.int32),
                     jnp.zeros((T,), jnp.int32),
                     jnp.zeros((T,), jnp.bool_),
                     jnp.full((T,), -1, jnp.int32),
                     jnp.int32(0), jnp.int32(0))
        carry = _pipe_carry_init(cfg, ns, tables) + (
            open_slot, open_slot,
            qk, qg, qi, qa, qc, qd, head[0], size[0], next_id[0], lat_hist)
        carry, (commit, stats) = jax.lax.scan(
            pstep, carry, (keys, groups, kinds, prio, n_arr, waves, live))
        (tables, _, _, _, _, _, _, _, _, _,
         qk, qg, qi, qa, qc, qd, head, size, nid, lat_hist) = carry
        qstate = (qk, qg, qi, qa, qc, qd, head[None], size[None],
                  nid[None], lat_hist)
        return (commit[2:2 + n_waves], tables, qstate,
                stats[2:2 + n_waves])

    spec = _spec_stack(mesh)
    spec1 = _spec_ops(mesh)
    tab_spec = (spec1,) * (4 if mv else 2)
    q_spec = (spec1,) * 10
    run = jax.shard_map(
        local_run, mesh=_auto(mesh),
        in_specs=(spec, spec, spec, spec, spec, tab_spec, q_spec, P()),
        out_specs=(spec, tab_spec, q_spec, spec),
        check_vma=False)
    return run


def init_open_queue(cfg: DistConfig, mesh):
    """Fresh sharded open-loop queue state for ``make_open_wave_fn``:
    ``(q_key, q_grp, q_kind, q_admit, q_inc, q_id, head, size, next_id,
    lat_hist)`` — per-shard ring buffers (globally [ns*cap, ...]), ring
    cursors ([ns], local scalars inside shard_map), and the per-shard
    time-to-commit histogram ([ns*lat_bins]).  ``next_id`` starts at
    ``shard * 2^20`` so admission serials are globally unique without any
    cross-shard coordination (up to 2^20 admissions per shard)."""
    if not cfg.open_loop:
        raise ValueError("init_open_queue needs queue_cap >= 1")
    ns = n_shards(mesh)
    C, K, L = cfg.queue_cap, cfg.slots, cfg.lat_bins
    zi1 = jnp.zeros((ns * C,), jnp.int32)
    return (jnp.full((ns * C, K), -1, jnp.int32),          # q_key
            jnp.zeros((ns * C, K), jnp.int32),             # q_grp
            jnp.full((ns * C, K), t.NOP, jnp.int32),       # q_kind
            zi1,                                           # q_admit
            zi1,                                           # q_inc
            zi1,                                           # q_id
            jnp.zeros((ns,), jnp.int32),                   # head
            jnp.zeros((ns,), jnp.int32),                   # size
            jnp.arange(ns, dtype=jnp.int32) * (1 << 20),   # next_id
            jnp.zeros((ns * L,), jnp.int32))               # lat_hist


def run_open_loop(cfg: DistConfig, mesh, arrive_counts, gen_fn,
                  n_waves: int):
    """Host-side open-loop driver: run ``n_waves`` open waves and
    reconcile the per-shard stats into one summary dict.  The effective
    pipeline depth picks the engine — a host loop of jitted synchronous
    waves at depth 1, the one-XLA-program pipelined scan
    (``make_open_run_fn``) at depth >= 2.

    ``arrive_counts`` is int[n_waves, n_shards] (PoissonArrivals
    .shard_counts); ``gen_fn(wave) -> (keys, groups, kinds, prio)``
    supplies the wave's globally-shaped fresh-arrival candidates and lane
    priorities (seeded host-side, so reruns and backends see identical
    traffic).  The summary carries the conservation identities the oracle
    test asserts: admitted == commits + queued_final + inc_drops and
    offered == admitted + arrival_drops, both exact — at EVERY pipeline
    depth (a pipelined retry re-enqueues two waves later and may find the
    ring full, in which case it drops into inc_drops).
    """
    import numpy as np
    ns = n_shards(mesh)
    acc = np.zeros((ns, STATS_LEN), np.int64)
    tables = init_tables(cfg, mesh)
    qstate = init_open_queue(cfg, mesh)
    offered = 0
    if cfg.depth(ns) >= 2:
        run = jax.jit(make_open_run_fn(cfg, mesh, n_waves))
        per_wave = [gen_fn(w) for w in range(n_waves)]
        keys, groups, kinds, prio = (jnp.stack(col)
                                     for col in zip(*per_wave))
        n_arr = jnp.asarray(arrive_counts, jnp.int32)
        offered = int(jnp.minimum(n_arr, cfg.lanes_per_shard).sum())
        commit, tables, qstate, stats = run(
            keys, groups, kinds, prio, n_arr, tables, qstate,
            jnp.uint32(0))
        acc += np.asarray(stats).reshape(n_waves, ns, STATS_LEN)\
            .sum(axis=0)
    else:
        wave = jax.jit(make_open_wave_fn(cfg, mesh))
        for w in range(n_waves):
            keys, groups, kinds, prio = gen_fn(w)
            n_arr = jnp.asarray(arrive_counts[w], jnp.int32)
            offered += int(jnp.minimum(n_arr, cfg.lanes_per_shard).sum())
            commit, tables, qstate, stats = wave(
                keys, groups, kinds, prio, n_arr, tables, qstate,
                jnp.uint32(w))
            acc += np.asarray(stats).reshape(ns, STATS_LEN)
    lat_hist = np.asarray(qstate[-1]).reshape(ns, cfg.lat_bins)
    queued = int(np.asarray(qstate[7]).sum())
    return {
        "commits": int(acc[:, STAT_COMMITS].sum()),
        "aborts": int(acc[:, STAT_ABORTS].sum()),
        "ro_commits": int(acc[:, STAT_RO_COMMITS].sum()),
        "ro_aborts": int(acc[:, STAT_RO_ABORTS].sum()),
        "offered": offered,
        "admitted": int(acc[:, STAT_ADMITTED].sum()),
        "arrival_drops": int(acc[:, STAT_ARRIVAL_DROPS].sum()),
        "inc_drops": int(acc[:, STAT_INC_DROPS].sum()),
        "queued_final": queued,
        "abort_causes": [int(x) for x in acc[:, STAT_CAUSES].sum(axis=0)],
        "lat_hist": lat_hist,
        "per_shard_stats": acc,
    }


def init_tables(cfg: DistConfig, mesh):
    """Fresh sharded state for ``cfg.cc``:

    - occ:         ``(wts, claim_w)``
    - mvcc/mvocc:  ``(claim_w, claim_r, mv_begin, mv_head)`` — the version
      ring of core/mvstore.py (slot 0 live at begin 0, head 0) plus the two
      claim channels, all range-sharded over the padded record space.
    """
    from jax.sharding import NamedSharding
    ns = n_shards(mesh)
    rec_per = -(-cfg.n_records // ns)
    N, G = ns * rec_per, cfg.n_groups
    ax = _axes(mesh)

    def make():
        claim_w = jnp.full((N, G), t.NO_CLAIM, jnp.uint32)
        if cfg.is_mv:
            mv_begin, mv_head, _ = mvstore.mv_init(N, cfg.mv_depth, G)
            claim_r = jnp.full((N, G), t.NO_CLAIM, jnp.uint32)
            return (claim_w, claim_r, mv_begin, mv_head)
        return (jnp.zeros((N, G), jnp.uint32), claim_w)

    # Built in place on the mesh: each device holds its own range shard.
    return jax.jit(make, out_shardings=NamedSharding(
        _auto(mesh), P(ax if len(ax) > 1 else ax[0])))()


def abstract_args(cfg: DistConfig, mesh):
    """ShapeDtypeStructs (with shardings) for the dry-run cell."""
    from jax.sharding import NamedSharding
    ax = _axes(mesh)
    ns = n_shards(mesh)
    rec_per = -(-cfg.n_records // ns)
    T, K, G = cfg.lanes_per_shard, cfg.slots, cfg.n_groups
    sh2 = NamedSharding(mesh, P(ax if len(ax) > 1 else ax[0]))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh2)

    N = ns * rec_per
    if cfg.is_mv:
        tables = (sds((N, G), jnp.uint32),              # claim_w
                  sds((N, G), jnp.uint32),              # claim_r
                  sds((N, cfg.mv_depth, G), jnp.uint32),  # mv_begin
                  sds((N,), jnp.int32))                 # mv_head
    else:
        tables = (sds((N, G), jnp.uint32),              # wts
                  sds((N, G), jnp.uint32))              # claim_w
    return (sds((ns * T, K), jnp.int32),    # keys
            sds((ns * T, K), jnp.int32),    # groups
            sds((ns * T, K), jnp.int32),    # kinds
            sds((ns * T,), jnp.uint32),     # prio
            tables,
            jax.ShapeDtypeStruct((), jnp.uint32))
