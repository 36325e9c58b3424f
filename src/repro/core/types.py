"""Core pytree types for the wave-based transaction engine.

The engine executes transactions in *waves*: a wave is a batch of T lanes, each
lane running one transaction in lockstep (the TPU analogue of T hardware
threads).  Everything is a fixed-shape array so the whole simulator jits and
scans.

Operation encoding
------------------
Each transaction is a fixed-length list of K operation slots:

  op_key   int32[T, K]   flat record id (see workloads), -1 or masked = unused
  op_group int32[T, K]   conflict-unit (timestamp) group within the record.
                         THIS is where timestamp granularity enters: coarse
                         granularity maps every column to group 0, fine
                         granularity maps disjoint column sets to distinct
                         groups (the paper's contribution).
  op_col   int32[T, K]   column index (only used when values are tracked)
  op_kind  int32[T, K]   NOP / READ / WRITE / ADD (ADD = blind commutative
                         increment; in the write set for versioning purposes
                         but never aborts against other ADDs)
  op_val   f32[T, K]     value or delta for WRITE/ADD
  op_extent int32[T, K]  interval width: the op covers records
                         [op_key, op_key + op_extent).  extent 1 is a point
                         op (every pre-scan call site); extent > 1 is a
                         range SCAN, validated at commit through the
                         iterate_validate backend op so concurrently
                         claimed rows inside the interval abort the scan
                         with CAUSE_PHANTOM (DESIGN.md section 13)

Priorities
----------
`prio` is a uint32 per lane; *lower wins*.  The in-wave serialization order is
ascending priority.  Contention managers (SwissTM) encode age in high bits so
starved transactions win claims.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

# Operation kinds.
NOP: int = 0
READ: int = 1
WRITE: int = 2
ADD: int = 3  # blind commutative increment (STO-style commutative update)

# Concurrency-control mechanism ids (used by lax.switch in the engine).
CC_OCC: int = 0
CC_TICTOC: int = 1
CC_2PL: int = 2
CC_SWISS: int = 3
CC_ADAPTIVE: int = 4
CC_AUTOGRAN: int = 5
CC_MVCC: int = 6     # multi-version snapshot reads + first-committer-wins
CC_MVOCC: int = 7    # multi-version OCC: read-set validation on the chain

#: Mechanisms that need the multi-version ring (EngineConfig.mv_depth >= 1).
MV_CCS = (CC_MVCC, CC_MVOCC)

CC_NAMES = {
    CC_OCC: "occ",
    CC_TICTOC: "tictoc",
    CC_2PL: "2pl",
    CC_SWISS: "swisstm",
    CC_ADAPTIVE: "adaptive",
    CC_AUTOGRAN: "autogran",
    CC_MVCC: "mvcc",
    CC_MVOCC: "mvocc",
}
CC_IDS = {v: k for k, v in CC_NAMES.items()}

# Abort-cause taxonomy (DESIGN.md "Observability").  Every abort is
# attributed to exactly ONE cause so per-cause counts sum to total aborts
# at every layer (local sweep, distributed stats, open loop).  Codes are
# ordered by precedence: when a lane carries several conflicting ops the
# lane's cause is the MINIMUM over its per-op cause codes, so the most
# structural cause (capacity drop) dominates the most incidental one
# (read validation).  CAUSE_NONE is the min-identity for clean ops and
# sits one past the histogram so scatter-adds of clean lanes drop.
CAUSE_INC_CAP: int = 0         # open loop: terminal abort at the
                               #   incarnation cap (txn leaves the system)
CAUSE_CAPACITY: int = 1        # distributed: route-buffer capacity drop
CAUSE_STALE_SNAPSHOT: int = 2  # MV ring reclamation: the reader's aged
                               #   snapshot outlived the version ring
CAUSE_LOCK_WOUND: int = 3      # eager lock conflict (2PL, SwissTM w-w,
                               #   Adaptive's pessimistic path)
CAUSE_WW: int = 4              # claim / write-write conflict
                               #   (first-committer-wins)
CAUSE_READ_VAL: int = 5        # commit-time read-validation failure
                               #   (the paper's false-conflict channel)
CAUSE_PHANTOM: int = 6         # interval (scan) validation failure: a
                               #   concurrent writer claimed a record inside
                               #   a committed scan's [key, key+extent)
                               #   interval (iterate_validate; DESIGN.md
                               #   section 13)
N_ABORT_CAUSES: int = 7
CAUSE_NONE: int = N_ABORT_CAUSES  # sentinel: op not conflicting

CAUSE_NAMES = {
    CAUSE_INC_CAP: "inc_cap",
    CAUSE_CAPACITY: "capacity",
    CAUSE_STALE_SNAPSHOT: "stale_snapshot",
    CAUSE_LOCK_WOUND: "lock_wound",
    CAUSE_WW: "ww",
    CAUSE_READ_VAL: "read_val",
    CAUSE_PHANTOM: "phantom",
}


def cause_counts(lane_cause: jax.Array, aborted: jax.Array) -> jax.Array:
    """Histogram lane cause codes over aborted lanes -> int32[N_ABORT_CAUSES].

    Non-aborted lanes are steered to CAUSE_NONE, which is out of bounds
    for the histogram and drops on scatter — the counts therefore sum to
    exactly ``aborted.sum()`` as long as every aborted lane carries a
    real cause (< CAUSE_NONE), which each validator guarantees by
    construction (cause codes are set under the same final conflict
    masks that decide the abort)."""
    idx = jnp.where(aborted, lane_cause, N_ABORT_CAUSES)
    return jnp.zeros((N_ABORT_CAUSES,), jnp.int32).at[idx].add(
        1, mode="drop")

# Priority layout: (inverse-age << AGE_SHIFT) | lane-permutation rank.
# Lower priority value = earlier in the wave serialization order.
PRIO_LANE_BITS = 10  # up to 1024 lanes
PRIO_LANE_MASK = (1 << PRIO_LANE_BITS) - 1
NO_CLAIM = jnp.uint32(0xFFFFFFFF)

# Masked-op scatter sentinel.  JAX wraps *negative* indices Python-style even
# under mode="drop"/"fill" (verified in this container: x.at[-1].add(1,
# mode="drop") hits x[-1]).  A large positive out-of-bounds index is the only
# value that actually drops on scatter and fills on gather, so every scatter
# site masks keys to OOB_KEY, never to -1.  (-1 remains the *marker* for an
# unused op slot in op_key; TxnBatch.live() screens it out of semantics.)
OOB_KEY: int = 0x7F000000


def field(**kw):
    return dataclasses.field(**kw)


@partial(jax.tree_util.register_dataclass,
         data_fields=["op_key", "op_group", "op_col", "op_kind", "op_val",
                      "txn_type", "n_ops", "op_extent"],
         meta_fields=[])
@dataclasses.dataclass
class TxnBatch:
    """A wave's worth of transactions (T lanes x K op slots)."""
    op_key: jax.Array    # int32[T, K]
    op_group: jax.Array  # int32[T, K]
    op_col: jax.Array    # int32[T, K]
    op_kind: jax.Array   # int32[T, K]
    op_val: jax.Array    # f32[T, K]
    txn_type: jax.Array  # int32[T]      workload-defined transaction type
    n_ops: jax.Array     # int32[T]      number of live ops (for the cost model)
    op_extent: jax.Array = None  # int32[T, K]  interval width
                          #   [key, key+extent); 1 = point op.  Defaults
                          #   to all-ones (every op a point op) so
                          #   pre-extent construction sites stay valid.

    def __post_init__(self):
        if self.op_extent is None:
            self.op_extent = jnp.ones_like(self.op_key)

    @property
    def lanes(self) -> int:
        return self.op_key.shape[0]

    @property
    def slots(self) -> int:
        return self.op_key.shape[1]

    def is_read(self) -> jax.Array:
        return self.op_kind == READ

    def is_write(self) -> jax.Array:
        """Version-bumping accesses (WRITE and ADD)."""
        return (self.op_kind == WRITE) | (self.op_kind == ADD)

    def is_plain_write(self) -> jax.Array:
        return self.op_kind == WRITE

    def is_add(self) -> jax.Array:
        return self.op_kind == ADD

    def live(self) -> jax.Array:
        return (self.op_kind != NOP) & (self.op_key >= 0)

    def is_scan(self) -> jax.Array:
        """Interval ops (extent > 1) — validated via iterate_validate."""
        return self.op_extent > 1

    def extent(self) -> jax.Array:
        """Effective interval width, clamped to >= 1 so legacy callers
        that fill op_extent with zeros still mean point ops."""
        return jnp.maximum(self.op_extent, 1)


@partial(jax.tree_util.register_dataclass,
         data_fields=["values", "wts", "rts", "claim_w", "claim_r",
                      "pess_mode", "abort_heat", "fine_mode", "false_heat",
                      "heat_wave", "ring_tails", "mv_begin", "mv_head",
                      "mv_vals"],
         meta_fields=[])
@dataclasses.dataclass
class StoreState:
    """The database: values + version metadata + CC bookkeeping tables.

    All tables are flat over a unified record space (workloads lay out their
    tables at offsets inside [0, n_records)).

    wts/rts are the paper's version timestamps, shape [n_records, G] where G is
    the max number of timestamp groups per record (1 = coarse, 2 = the paper's
    fine granularity).  `claim_*` are wave-scoped claim tables (see claims.py)
    that never need resetting thanks to a monotone wave tag.
    """
    values: jax.Array      # f32[n_records, n_cols] (may be zero-width when untracked)
    wts: jax.Array         # uint32[n_records, G]   write timestamps
    rts: jax.Array         # uint32[n_records, G]   read timestamps (TicToc only)
    claim_w: jax.Array     # uint32[n_records, G]   writer claim table
    claim_r: jax.Array     # uint32[n_records, G]   reader claim table (2PL/Swiss)
    pess_mode: jax.Array   # bool[n_records]        Adaptive: pessimistic mode
    abort_heat: jax.Array  # f32[n_records]         Adaptive: abort EWMA
    fine_mode: jax.Array   # bool[n_records]        AutoGran: fine granularity on
    false_heat: jax.Array  # f32[n_records]         AutoGran: false-conflict EWMA
    heat_wave: jax.Array   # int32[n_records]       last wave a heat was touched
                           #   (lazy exponential decay: full-table decay per wave
                           #    would be O(n_records) memory traffic; instead decay
                           #    decay**(wave - heat_wave) is applied at touch time)
    ring_tails: jax.Array  # int32[n_rings]         append-ring cursors (inserts)
    mv_begin: jax.Array    # uint32[n_records, D, G] multi-version ring begin
                           #   timestamps (core/mvstore.py; [1,1,1] when the
                           #   MV store is disabled, mv_depth=0)
    mv_head: jax.Array     # int32[n_records]       newest ring slot per record
    mv_vals: jax.Array     # f32[n_records, D, n_cols] version values
                           #   (track_values only; [1,1,1] otherwise)

    @property
    def n_records(self) -> int:
        return self.wts.shape[0]

    @property
    def n_groups(self) -> int:
        return self.wts.shape[1]

    @property
    def mv_depth(self) -> int:
        """Ring depth D of the multi-version store (1 when disabled —
        the placeholder's single slot)."""
        return self.mv_begin.shape[1]


@partial(jax.tree_util.register_dataclass,
         data_fields=["rng", "wave", "store", "pending", "pending_live",
                      "age", "lane_time", "commits", "aborts",
                      "commits_by_type", "wasted_time", "ext_events",
                      "ro_commits", "ro_aborts", "abort_causes",
                      "conflict_hits", "conflict_peak", "ol"],
         meta_fields=[])
@dataclasses.dataclass
class EngineState:
    """Carried state of the wave scan."""
    rng: jax.Array          # PRNG key
    wave: jax.Array         # uint32 scalar, current wave index
    store: StoreState
    pending: TxnBatch       # retry buffer: aborted txns re-run next wave
    pending_live: jax.Array  # bool[T] lane has a pending (aborted) txn
    age: jax.Array          # int32[T] retry count of the lane's current txn
    lane_time: jax.Array    # f32[T]   simulated microseconds consumed per lane
    commits: jax.Array      # int64 scalar
    aborts: jax.Array       # int64 scalar
    commits_by_type: jax.Array  # int64[n_txn_types]
    wasted_time: jax.Array  # f32 scalar, simulated time lost to aborts
    ext_events: jax.Array   # int64 scalar, TicToc rts-extension CAS events
    ro_commits: jax.Array   # int scalar: commits of read-only transactions
    ro_aborts: jax.Array    # int scalar: aborts of read-only transactions
                            #   (the MV headline metric: snapshot readers
                            #   never abort — DESIGN.md section 9)
    abort_causes: jax.Array = None  # int32[N_ABORT_CAUSES] per-cause abort
                            #   counts; sums to `aborts` exactly (the
                            #   conservation invariant)
    conflict_hits: jax.Array = None  # uint32[n_records, G] total conflicting
                            #   ops per cell (track_conflicts only;
                            #   [1, 1] placeholder otherwise)
    conflict_peak: jax.Array = None  # uint32[n_records, G] max same-cell
                            #   conflicting ops in any single wave
                            #   (segment_count fed through ts_install_max)
    ol: Any = None          # core/admission.OpenLoopState: the open-loop
                            #   front-end (admission queue + goodput
                            #   counters + time-to-commit histograms);
                            #   a minimal placeholder on closed-loop runs
                            #   (DESIGN.md section 11)


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Simulated-time constants (microseconds).  See DESIGN.md section 4.

    The paper measures wall-clock throughput of a C++ STM on a 192-core Xeon;
    we reproduce the *structure* of those curves with a calibrated per-op cost
    model.  All constants live here so the calibration is auditable.
    """
    c_op: float = 0.12          # base cost of one record access
    c_txn: float = 0.80         # per-transaction fixed overhead (setup/commit)
    c_validate: float = 0.03    # OCC per-read-op validation pass cost
    kappa_occ: float = 1.0
    kappa_tictoc: float = 1.12  # TicToc read-timestamp maintenance: the
                                # paper runs the 128-bit (uncompressed)
                                # variant (their section 3.2) — a two-word
                                # atomic per tracked read
    kappa_2pl: float = 1.38     # rw-lock acquire/release writes shared cachelines
    kappa_swiss: float = 1.18   # eager w-locks + CM table updates
    kappa_adaptive_opt: float = 1.12   # mode check on the optimistic path
    kappa_adaptive_pess: float = 1.42  # rw-lock path
    kappa_mvcc: float = 1.30    # multi-version overhead: version-chain
                                # traversal on every read, allocate+publish
                                # on every write, GC bookkeeping (Larson et
                                # al.'s measured penalty vs single-version)
    kappa_mvocc: float = 1.24   # same chain costs minus the SI visibility
                                # check writes (read validation is charged
                                # through c_validate like the OCC family)
    c_ext: float = 0.04        # uncontended rts-extension CAS (+fence); the
                                # 128-bit two-word variant the paper runs
    lam_ext: float = 1.35       # TicToc rts-extension contention: extra cost per
                                # concurrent extender of the same (record, group)
    lam_w: float = 0.55         # install contention: committed writers of the
                                # same (record, group) serialize on its
                                # cacheline (all mechanisms; the universal
                                # optimistic degradation at high core counts)
    opt_overlap: float = 0.60    # an optimistic read is vulnerable between
                                 # first read and commit-time validation; a
                                 # concurrent writer's install lands in that
                                 # window with this probability (lockstep
                                 # waves over-align the windows)
    phase_overlap: float = 0.55  # eager-lock conflicts require temporal
                                 # overlap of hold windows; the lockstep wave
                                 # over-aligns them — conflicts are thinned
                                 # to this probability (2PL/Swiss/Adaptive-
                                 # pessimistic only; see DESIGN.md section 4)
    c_abort: float = 0.35       # abort bookkeeping + backoff
    backoff: float = 0.25       # inter-retry backoff


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static configuration of a simulation run."""
    cc: int                     # CC_* mechanism id
    lanes: int                  # T: number of simulated threads
    slots: int                  # K: op slots per transaction
    n_records: int
    n_groups: int               # G: timestamp groups per record (physical width)
    n_cols: int                 # value columns (0 = untracked)
    n_txn_types: int
    granularity: int = 1        # 0 = coarse (one timestamp per row),
                                # 1 = fine (the paper's mechanism).
                                # Claims are always scattered at fine group
                                # resolution; granularity selects the probe/
                                # observe width of the backend surface ops
                                # (core/backend.py validate/probe/ts_gather).
    n_rings: int = 1
    track_values: bool = False
    mv_depth: int = 0           # D: version-ring depth of the multi-version
                                # store (core/mvstore.py).  0 disables the MV
                                # tables entirely (placeholder arrays); the
                                # MV mechanisms (mvcc/mvocc) require >= 1 and
                                # benchmarks default to 4.  Depth bounds how
                                # far behind a snapshot may trail before its
                                # version is reclaimed and the reader aborts.
    snapshot_age: int = 0       # MV readers pin their snapshot this many
                                # waves in the past (0 = wave-fresh, the
                                # classic path).  Age > 0 models long-lived
                                # reader snapshots: once writers have pushed
                                # a record's ring past the aged snapshot,
                                # mv_gather reports reclamation and the
                                # reader aborts cleanly (ok=False) — the
                                # knob that makes epoch reclamation actually
                                # fire under load (mvstore.snapshot_ts).
    # Open-loop traffic front-end (core/admission.py; DESIGN.md section
    # 11).  arrival_rate > 0 switches the engine from the closed-loop
    # one-txn-per-lane retry model to open-loop admission: transactions
    # arrive ~ Poisson(arrival_rate) per wave (capped at the lane width),
    # queue in a fixed-capacity ring, and an abort re-enqueues the SAME
    # transaction with an incremented incarnation counter.
    arrival_rate: float = 0.0   # expected arrivals per wave (0 = closed)
    queue_cap: int = 0          # admission-queue ring capacity (>= 1 when
                                # open-loop; overflow arrivals are dropped
                                # and counted)
    max_incarnations: int = 0   # max re-executions after the first attempt;
                                # an abort at this incarnation drops the
                                # transaction (counted, never silent)
    lat_bins: int = 64          # time-to-commit histogram width in waves,
                                # per txn class (last bin = overflow)
    track_conflicts: bool = False  # maintain the hot-record conflict
                                # histogram: per-cell total conflicting-op
                                # hits plus the per-wave same-cell peak
                                # (segment_count), surfaced as
                                # SimResult.hot_records top-k
    cost: CostModel = dataclasses.field(default_factory=CostModel)
    # Adaptive CC state machine:
    adapt_up: float = 0.20      # abort-heat threshold -> pessimistic
    adapt_down: float = 0.02    # decay floor -> back to optimistic
    adapt_decay: float = 0.95
    # Auto-granularity (beyond-paper, paper section 5 future work):
    autogran_up: float = 0.10
    autogran_decay: float = 0.97
    backend: str = "jnp"        # Substrate for the kernel-backend surface
                                # (core/backend.py) every CC mechanism calls:
                                # "jnp": XLA gather/scatter; "pallas": the
                                # TPU-native kernels (interpret mode off-TPU).
                                # Both read the same claim words
                                # (core/claimword.py) and are bit-identical —
                                # see DESIGN.md section 5.
    fuse_wave: bool = True      # Probe family (occ/tictoc/2pl/swisstm/
                                # adaptive) runs its whole claim -> verdict ->
                                # install chain as the ONE fused wave_commit
                                # op (kernels/wave_commit.py): each touched
                                # row rides one DMA per wave.  False = the
                                # unfused claim_probe + commit_install chain;
                                # bit-identical either way (DESIGN.md
                                # section 5, tests/test_wave_commit.py).
    lane_block: int = 0         # Lanes per pallas grid step (LB): the
                                # kernels walk the wave in blocks of LB*K
                                # ops, their row DMAs in flight together.
                                # 0 = the least LB with LB*K a multiple of
                                # 128 (kernels/rows.pick_lane_block);
                                # explicit values round up to the next such
                                # LB.  jnp backend ignores it.
    max_extent: int = 1         # Widest op interval the workload emits
                                # ([key, key+extent) — TxnBatch.op_extent).
                                # 1 = point ops only: the scan validation
                                # pass is compiled OUT and the wave is
                                # bit-identical to the pre-extent engine.
                                # > 1 compiles the iterate_validate pass
                                # (static loop bound; DESIGN.md section 13).
    bucket_size: int = 8        # Coarse-granularity interval claims: one
                                # claim word stands for `bucket_size`
                                # consecutive records, so a coarse scan
                                # validates the bucket-expanded interval
                                # [floor(key/B)*B, ceil((key+extent)/B)*B)
                                # — fewer probes, more false phantoms (the
                                # granularity trade-off, now for intervals).
                                # Fine granularity probes every gap row and
                                # ignores this knob.

    def __post_init__(self):
        if self.backend not in ("jnp", "pallas"):
            raise ValueError(f"unknown backend {self.backend!r} "
                             "(expected 'jnp' or 'pallas')")
        if self.lane_block < 0:
            raise ValueError(
                f"lane_block must be >= 0 (0 = auto), got {self.lane_block}")
        if self.mv_depth < 0:
            raise ValueError(f"mv_depth must be >= 0, got {self.mv_depth}")
        if self.cc in MV_CCS and self.mv_depth < 1:
            raise ValueError(
                f"{CC_NAMES[self.cc]} needs the multi-version store: "
                "set EngineConfig.mv_depth >= 1 (benchmarks use 4)")
        if self.snapshot_age < 0:
            raise ValueError(
                f"snapshot_age must be >= 0, got {self.snapshot_age}")
        if self.snapshot_age > 0 and self.cc not in MV_CCS:
            raise ValueError(
                f"snapshot_age={self.snapshot_age} needs a multi-version "
                f"mechanism (mvcc/mvocc): {CC_NAMES[self.cc]} has no "
                "snapshots to age")
        if self.arrival_rate < 0:
            raise ValueError(
                f"arrival_rate must be >= 0, got {self.arrival_rate}")
        if self.open_loop:
            if self.queue_cap < 1:
                raise ValueError(
                    f"open-loop runs (arrival_rate={self.arrival_rate}) "
                    "need an admission queue: set queue_cap >= 1")
            if self.max_incarnations < 0:
                raise ValueError(f"max_incarnations must be >= 0, got "
                                 f"{self.max_incarnations}")
            if self.lat_bins < 2:
                raise ValueError(
                    f"lat_bins={self.lat_bins}: the time-to-commit "
                    "histogram needs >= 2 bins (last bin = overflow)")
        elif self.queue_cap or self.max_incarnations:
            raise ValueError(
                f"queue_cap={self.queue_cap} / max_incarnations="
                f"{self.max_incarnations} shape the open-loop admission "
                "queue only: set arrival_rate > 0 (closed-loop lanes "
                "retry in place and never queue)")
        if self.max_extent < 1:
            raise ValueError(
                f"max_extent must be >= 1 (1 = point ops), got "
                f"{self.max_extent}")
        if self.max_extent > self.n_records:
            raise ValueError(
                f"max_extent={self.max_extent} exceeds n_records="
                f"{self.n_records}: no interval can be wider than the "
                "record space")
        if self.bucket_size < 1:
            raise ValueError(
                f"bucket_size must be >= 1, got {self.bucket_size}")
        if self.max_extent > 1 and self.snapshot_age > 0:
            raise ValueError(
                f"max_extent={self.max_extent} with snapshot_age="
                f"{self.snapshot_age}: scans validate intervals against "
                "the CURRENT wave's claim tables, which aged snapshots "
                "have already drifted past — scan workloads need "
                "wave-fresh snapshots (the pipeline_depth >= 2 analogue "
                "of this rule lives in DistConfig)")

    @property
    def open_loop(self) -> bool:
        """Open-loop traffic front-end active (DESIGN.md section 11)."""
        return self.arrival_rate > 0


def txn_batch_zeros(lanes: int, slots: int) -> TxnBatch:
    zi = jnp.zeros((lanes, slots), jnp.int32)
    return TxnBatch(
        op_key=jnp.full((lanes, slots), -1, jnp.int32),
        op_group=zi, op_col=zi, op_kind=zi,
        op_val=jnp.zeros((lanes, slots), jnp.float32),
        op_extent=jnp.ones((lanes, slots), jnp.int32),
        txn_type=jnp.zeros((lanes,), jnp.int32),
        n_ops=jnp.zeros((lanes,), jnp.int32),
    )


def store_init(n_records: int, n_groups: int, n_cols: int,
               n_rings: int = 1, values: Optional[jax.Array] = None,
               need_rts: bool = True, mv_depth: int = 0) -> StoreState:
    from repro.core import mvstore
    G = n_groups
    if values is None:
        values = jnp.zeros((n_records, max(n_cols, 1)), jnp.float32)
    if mv_depth > 0:
        mv_begin, mv_head, mv_vals = mvstore.mv_init(
            n_records, mv_depth, G, n_cols,
            values if n_cols > 0 else None)
    else:
        mv_begin, mv_head, mv_vals = mvstore.mv_placeholder()
    return StoreState(
        values=values,
        wts=jnp.zeros((n_records, G), jnp.uint32),
        rts=(jnp.zeros((n_records, G), jnp.uint32) if need_rts
             else jnp.zeros((1, 1), jnp.uint32)),
        claim_w=jnp.full((n_records, G), NO_CLAIM, jnp.uint32),
        claim_r=jnp.full((n_records, G), NO_CLAIM, jnp.uint32),
        pess_mode=jnp.zeros((n_records,), jnp.bool_),
        abort_heat=jnp.zeros((n_records,), jnp.float32),
        fine_mode=jnp.zeros((n_records,), jnp.bool_),
        false_heat=jnp.zeros((n_records,), jnp.float32),
        heat_wave=jnp.zeros((n_records,), jnp.int32),
        ring_tails=jnp.zeros((n_rings,), jnp.int32),
        mv_begin=mv_begin,
        mv_head=mv_head,
        mv_vals=mv_vals,
    )


def engine_state_init(cfg: EngineConfig, rng: jax.Array,
                      store: StoreState) -> EngineState:
    from repro.core import admission
    T = cfg.lanes
    ol = (admission.open_loop_init(cfg.queue_cap, cfg.slots,
                                   cfg.n_txn_types, cfg.lat_bins)
          if cfg.open_loop else admission.open_loop_placeholder())
    return EngineState(
        rng=rng,
        wave=jnp.uint32(0),
        store=store,
        pending=txn_batch_zeros(T, cfg.slots),
        pending_live=jnp.zeros((T,), jnp.bool_),
        age=jnp.zeros((T,), jnp.int32),
        lane_time=jnp.zeros((T,), jnp.float32),
        commits=jnp.int64(0) if jax.config.jax_enable_x64 else jnp.int32(0),
        aborts=jnp.int64(0) if jax.config.jax_enable_x64 else jnp.int32(0),
        commits_by_type=jnp.zeros((cfg.n_txn_types,),
                                  jnp.int64 if jax.config.jax_enable_x64 else jnp.int32),
        wasted_time=jnp.float32(0),
        ext_events=jnp.int32(0),
        ro_commits=jnp.int64(0) if jax.config.jax_enable_x64 else jnp.int32(0),
        ro_aborts=jnp.int64(0) if jax.config.jax_enable_x64 else jnp.int32(0),
        abort_causes=jnp.zeros((N_ABORT_CAUSES,), jnp.int32),
        conflict_hits=jnp.zeros(
            (cfg.n_records, cfg.n_groups) if cfg.track_conflicts else (1, 1),
            jnp.uint32),
        conflict_peak=jnp.zeros(
            (cfg.n_records, cfg.n_groups) if cfg.track_conflicts else (1, 1),
            jnp.uint32),
        ol=ol,
    )
