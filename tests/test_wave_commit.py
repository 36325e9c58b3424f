"""Op fifteen — ``wave_commit``, the lane-block megakernel (ISSUE 9).

Covers: Pallas-vs-oracle bit-identity (duplicate cells, masked ops, both
granularities, dual tables, version bumps, explicit lane blocks), the
monotone-wave-tag precondition (eager check, ``REPRO_PRECONDITION_CHECKS=0``
opt-out), fuse_wave on/off bit-identity for every probe-family mechanism at
run() and sweep() level on both backends, the distributed fused owner step,
lane-block selection, and the single-launch jaxpr guard (the fused
probe-family wave emits exactly ONE transaction ``pallas_call`` per wave
on the pallas backend).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distributed as D
from repro.core import types as t
from repro.core.claimword import EMPTY_WORD, NO_PRIO
from repro.core.engine import run, sweep
from repro.core.types import EngineConfig, TxnBatch, store_init
from repro.kernels import ops, ref
from repro.kernels.rows import pick_lane_block
from repro.kernels.wave_commit import wave_row_copies
from repro.workloads import YCSBWorkload

RNG = np.random.default_rng(7)

PROBE_CCS = {"occ": t.CC_OCC, "tictoc": t.CC_TICTOC, "2pl": t.CC_2PL,
             "swisstm": t.CC_SWISS, "adaptive": t.CC_ADAPTIVE}

WL = YCSBWorkload.make(n_keys=256)


# ------------------------------------------------------- oracle parity
def _op_inputs(T, K, N, G, wave, dup=True, masked=True, keys=None):
    """Random op tensors with duplicate cells and masked (key < 0) ops
    baked in (or the given ``keys``), plus claim tables seeded with BOTH
    dead older-wave claims and live same-wave claims — the fetched-row
    probe term and the all-pairs wave term must both fire."""
    if keys is None:
        keys = RNG.integers(0, max(2, N // 8) if dup else N, (T, K),
                            dtype=np.int32)
        if masked:
            keys[RNG.random((T, K)) < 0.3] = -1
    groups = RNG.integers(0, G, (T, K), dtype=np.int32)
    prio = RNG.integers(0, 0xFFFF, (T, K), dtype=np.uint32)

    def table():
        tbl = np.full((N, G), EMPTY_WORD, np.uint32)
        dead = RNG.random((N, G)) < 0.4
        old_ivw = (0xFFFF - max(wave - 1, 0)) & 0xFFFF
        tbl[dead] = ((np.uint32(old_ivw) << 16)
                     | RNG.integers(0, 0xFFFF, dead.sum(), dtype=np.uint32))
        live = RNG.random((N, G)) < 0.3
        cur_ivw = (0xFFFF - wave) & 0xFFFF
        tbl[live] = ((np.uint32(cur_ivw) << 16)
                     | RNG.integers(0, 0xFFFF, live.sum(), dtype=np.uint32))
        return jnp.asarray(tbl)

    wts = jnp.asarray(RNG.integers(0, 50, (N, G), dtype=np.uint32))
    masks = tuple(jnp.asarray(RNG.random((T, K)) < p)
                  for p in (0.5, 0.5, 0.5, 0.3, 0.4, 0.1))
    return (jnp.asarray(keys), jnp.asarray(groups), jnp.asarray(prio),
            table(), table(), wts, masks)


@pytest.mark.parametrize("lane_block", [0, 1, 2])
@pytest.mark.parametrize("fine", [False, True])
@pytest.mark.parametrize("dual,bump", [(False, False), (False, True),
                                       (True, True)])
def test_wave_commit_pallas_matches_oracle(fine, dual, bump, lane_block):
    """The megakernel is bit-identical to ref.wave_commit on all five
    outputs — claim tables, version table, conflict mask, commit mask —
    with duplicate cells, masked ops, live and dead table claims, and
    every lane-block width (0 = auto)."""
    T, K, N, G, wave = 8, 4, 64, 3, 5
    _assert_parity(_op_inputs(T, K, N, G, wave), wave, fine, dual, bump,
                   lane_block)


def _assert_parity(inputs, wave, fine, dual, bump, lane_block):
    keys, groups, prio, cw, cr, wts, masks = inputs
    do_w, do_r, check_w, check_w2, check_r, extra = masks
    args = (cw, cr if dual else None, wts if bump else None, keys, groups,
            prio, do_w, do_r if dual else None, check_w, check_w2,
            check_r if dual else None, extra, jnp.uint32(wave), fine,
            dual, bump)
    a = ref.wave_commit(*args)
    b = ops.wave_commit(*args, lane_block=lane_block, use_pallas=True)
    for name, x, y in zip(("claim_w", "claim_r", "wts", "conflict",
                           "commit"), a, b):
        if x is None:
            assert y is None, name
            continue
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), name)


def _layout_keys(case):
    """(keys int32[T, K], lane_block) of a hand-built wave over 1024
    records (8 packed block rows of 128 records) that stresses the row
    leaders: which ops fetch, which copy installs, which writes back."""
    rng = np.random.default_rng(sum(map(ord, case)))

    def rand(shape, lo=0, hi=1024):
        return rng.integers(lo, hi, shape, dtype=np.int32)

    if case == "masked_block":       # blocks of 8 lanes; block 0 masked
        keys = rand((12, 16))        # lanes 12-15 pad block 1
        keys[:8] = -1
        return keys, 0
    if case == "one_row_block":      # block 0 on packed row 3, duplicates
        keys = rand((16, 16))
        keys[:8] = rand((8, 16), 384, 384 + 24)
        return keys, 0
    if case == "row_across_blocks":  # row 5 in each of three blocks
        keys = rand((24, 16))
        keys[:, ::3] = rand((24, 6), 640, 768)
        keys[rng.random(keys.shape) < 0.3] = -1
        return keys, 0
    if case == "rw_one_cell":        # writes and reads of one cell
        keys = rand((16, 16))
        keys[:8, :12] = 300
        return keys, 0
    if case == "tpcc_tiling":        # K = 64: 2 lanes a block, 61% masked
        keys = rand((6, 64), 0, 300)
        keys[rng.random(keys.shape) < 0.61] = -1
        return keys, 0
    if case == "owner_tiling":       # wide K: 1 lane a block, half-empty
        keys = rand((4, 256))
        keys[:, 128:] = -1
        keys[3] = -1
        return keys, 0
    raise ValueError(case)


@pytest.mark.parametrize("case", ["masked_block", "one_row_block",
                                  "row_across_blocks", "rw_one_cell",
                                  "tpcc_tiling", "owner_tiling"])
@pytest.mark.parametrize("fine", [False, True])
@pytest.mark.parametrize("dual,bump", [(False, False), (False, True),
                                       (True, True)])
def test_wave_commit_pallas_matches_oracle_row_leaders(case, fine, dual,
                                                       bump):
    """The same bit-identity on hand-built waves that exercise the row
    leaders: an all-masked block, a block on one packed row, a row in
    several blocks, writes and reads of one cell in one block, and the
    TPC-C (2 lanes x 64 slots) and sharded-owner (1 lane x a wide
    route buffer) tilings."""
    keys, lane_block = _layout_keys(case)
    T, K = keys.shape
    wave = 5
    _assert_parity(_op_inputs(T, K, 1024, 2, wave, keys=keys), wave, fine,
                   dual, bump, lane_block)


def test_wave_row_copies():
    """The copy counter on a hand-built wave of two blocks (2 lanes x 64
    slots each; lane 3 pads): six live ops on three packed rows in block
    0, one live op in block 1.  A live op fetches, a row leader writes
    back, G rows per table; before, every op of the padded wave did
    both.  An all-live wave on distinct rows issues what it did before."""
    keys = np.full((3, 64), -1, np.int32)
    keys[0, :3] = [0, 1, 130]
    keys[1, :3] = [5, 260, 131]
    keys[2, 0] = 7
    new, old = wave_row_copies(jnp.asarray(keys), n_tables=2, G=2)
    assert (int(new), int(old)) == (2 * 2 * (7 + 4), 2 * 2 * 2 * 256)
    # one block of 4 lanes: lane 2's op shares packed row 0 with lane 0
    new, old = wave_row_copies(jnp.asarray(keys), n_tables=1, G=3,
                               lane_block=4)
    assert (int(new), int(old)) == (3 * (7 + 3), 2 * 3 * 256)
    distinct = jnp.arange(128, dtype=jnp.int32).reshape(2, 64) * 128
    new, old = wave_row_copies(distinct, n_tables=1, G=2)
    assert int(new) == int(old) == 2 * 2 * 128


def test_wave_commit_oracle_semantics():
    """Hand-checked case: two lanes contending one cell — the weaker
    (larger prio16) lane conflicts via the all-pairs wave term, the
    stronger commits, and exactly its write bumps the version."""
    N, G = 16, 2
    cw = jnp.full((N, G), EMPTY_WORD, jnp.uint32)
    wts = jnp.zeros((N, G), jnp.uint32)
    keys = jnp.asarray([[5], [5]], jnp.int32)
    groups = jnp.zeros((2, 1), jnp.int32)
    prio = jnp.asarray([[1], [2]], jnp.uint32)
    on = jnp.ones((2, 1), bool)
    cw2, _, wts2, conflict, commit = ref.wave_commit(
        cw, None, wts, keys, groups, prio, on, None, on, None, None, None,
        jnp.uint32(3), True, False, True)
    assert conflict.tolist() == [[False], [True]]
    assert commit.tolist() == [True, False]
    assert int(wts2[5, 0]) == 1 and int(wts2.sum()) == 1
    # the winning claim word is installed: inv-wave tag | strongest prio16
    assert int(cw2[5, 0]) == (((0xFFFF - 3) << 16) | 1)
    # a masked op (key < 0) neither probes, installs, nor bumps
    _, _, wts3, conflict3, _ = ref.wave_commit(
        cw, None, wts, -jnp.ones_like(keys), groups, prio, on, None, on,
        None, None, None, jnp.uint32(3), True, False, True)
    assert not bool(conflict3.any()) and int(wts3.sum()) == 0


def test_wave_commit_monotone_tag_precondition(monkeypatch):
    """A claim table already tagged with a FUTURE wave (inv_wave below the
    current wave's) means the wave counter ran backwards — the eager
    pallas path must raise on either table, and
    REPRO_PRECONDITION_CHECKS=0 must bypass the check."""
    T, K, N, G, wave = 2, 2, 16, 2, 5
    keys = jnp.zeros((T, K), jnp.int32).at[0, 0].set(3)
    groups = jnp.zeros((T, K), jnp.int32)
    prio = jnp.ones((T, K), jnp.uint32)
    on = jnp.ones((T, K), bool)
    good = jnp.full((N, G), EMPTY_WORD, jnp.uint32)
    # inv_wave(9) < inv_wave(5): row 3 claims to be from a future wave
    bad = good.at[3, 0].set(jnp.uint32(((0xFFFF - 9) << 16) | 1))
    wts = jnp.zeros((N, G), jnp.uint32)

    def call(cw, cr):
        return ops.wave_commit(cw, cr, wts, keys, groups, prio, on, on,
                               on, None, on, None, jnp.uint32(wave), True,
                               True, True, use_pallas=True)

    with pytest.raises(ValueError, match="precondition"):
        call(bad, good)
    with pytest.raises(ValueError, match="precondition"):
        call(good, bad)
    monkeypatch.setenv("REPRO_PRECONDITION_CHECKS", "0")
    call(bad, good)


def test_pick_lane_block():
    """Lane-block selection: a block of LB * K ops fills whole 128-lane
    rows (the chip's tile), the auto width is the least such LB, and an
    override rounds UP to the next such multiple (the wave is padded to
    whole blocks with masked lanes)."""
    assert pick_lane_block(16) == 8                       # 8 x 16 = 128
    assert pick_lane_block(64) == 2
    assert pick_lane_block(4, override=3) == 32           # 32 x 4 = 128
    assert pick_lane_block(16, override=9) == 16          # next multiple
    assert pick_lane_block(3) == 128                      # lcm(3, 128) / 3
    for K in (1, 3, 4, 6, 16, 64, 96):
        for override in (0, 1, 2, 7, 64):
            lb = pick_lane_block(K, override)
            assert (lb * K) % 128 == 0 and lb >= override
    with pytest.raises(ValueError):
        EngineConfig(cc=t.CC_OCC, lanes=8, slots=4, n_records=64,
                     n_groups=2, n_cols=0, n_txn_types=1, lane_block=-1)
    with pytest.raises(ValueError):
        D.DistConfig(n_records=64, n_groups=2, lanes_per_shard=8, slots=4,
                     lane_block=-1)


# --------------------------------------- fused vs unfused engine identity
def _engine_cfg(cc_name, gran, backend, fuse):
    return EngineConfig(
        cc=PROBE_CCS[cc_name], lanes=8, slots=WL.slots,
        n_records=WL.n_records, n_groups=WL.n_groups, n_cols=WL.n_cols,
        n_txn_types=WL.n_txn_types, granularity=gran, n_rings=WL.n_rings,
        backend=backend, fuse_wave=fuse)


def _assert_runs_identical(a, b):
    assert (a.commits, a.aborts) == (b.commits, b.aborts)
    assert (a.ro_commits, a.ro_aborts) == (b.ro_commits, b.ro_aborts)
    np.testing.assert_array_equal(np.asarray(a.abort_causes),
                                  np.asarray(b.abort_causes))
    for name in ("wts", "rts", "claim_w", "claim_r"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.final_state.store, name)),
            np.asarray(getattr(b.final_state.store, name)), name)


@pytest.mark.parametrize("cc", sorted(PROBE_CCS))
@pytest.mark.parametrize("gran", [0, 1])
def test_fuse_wave_run_bit_identity_jnp(cc, gran):
    """ISSUE 9 acceptance: fuse_wave=True is bit-identical to the unfused
    probe chain — commits, aborts, per-cause breakdown, and ALL final
    store tables — for every probe-family mechanism x granularity."""
    a = run(_engine_cfg(cc, gran, "jnp", True), WL, n_waves=4, seed=0,
            keep_state=True)
    b = run(_engine_cfg(cc, gran, "jnp", False), WL, n_waves=4, seed=0,
            keep_state=True)
    _assert_runs_identical(a, b)


@pytest.mark.parametrize("cc,gran", [("2pl", 1), ("adaptive", 0),
                                     ("tictoc", 1)])
def test_fuse_wave_run_bit_identity_pallas(cc, gran):
    """The same identity with both paths on the interpret-mode kernels
    (dual-table, coarse, and no-bump representatives; the full matrix
    runs on jnp above and via the sweep test below)."""
    a = run(_engine_cfg(cc, gran, "pallas", True), WL, n_waves=3, seed=0,
            keep_state=True)
    b = run(_engine_cfg(cc, gran, "pallas", False), WL, n_waves=3, seed=0,
            keep_state=True)
    _assert_runs_identical(a, b)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_fuse_wave_sweep_bit_identity(backend):
    """sweep()-level identity: the whole probe family x both granularities
    in ONE compiled grid per fuse setting, on each backend."""
    cfg = _engine_cfg("occ", 1, backend, True)
    pts_f = sweep(cfg, WL, 3, ccs=sorted(PROBE_CCS.values()), grans=(0, 1),
                  lane_counts=(8,), seeds=(0,))
    pts_u = sweep(dataclasses.replace(cfg, fuse_wave=False), WL, 3,
                  ccs=sorted(PROBE_CCS.values()), grans=(0, 1),
                  lane_counts=(8,), seeds=(0,))
    assert len(pts_f) == len(pts_u) == 10
    for pa, pb in zip(pts_f, pts_u):
        assert (pa.cc, pa.granularity) == (pb.cc, pb.granularity)
        assert (pa.commits, pa.aborts) == (pb.commits, pb.aborts)
        assert (pa.ro_commits, pa.ro_aborts) == (pb.ro_commits, pb.ro_aborts)
        assert pa.abort_causes == pb.abort_causes


# --------------------------------------------------- distributed owner step
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("gran", [0, 1])
def test_distributed_fuse_wave_bit_identity(gran, backend):
    """The routed occ wave's owner step through the fused op vs the
    claim_probe chain: identical commit mask, tables, and stats over
    every available host device (8 under the CI XLA_FLAGS)."""
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    ns = len(jax.devices())
    N, Tl, K = 256, 8, 4
    keys = jnp.asarray(RNG.integers(0, N, (ns * Tl, K), dtype=np.int32))
    groups = jnp.asarray(RNG.integers(0, 2, (ns * Tl, K), dtype=np.int32))
    kinds = jnp.asarray(RNG.choice([t.READ, t.WRITE],
                                   (ns * Tl, K)).astype(np.int32))
    prio = jnp.asarray(RNG.permutation(ns * Tl).astype(np.uint32))
    outs = {}
    for fuse in (True, False):
        cfg = D.DistConfig(n_records=N, n_groups=2, lanes_per_shard=Tl,
                           slots=K, granularity=gran, backend=backend,
                           fuse_wave=fuse)
        wave_fn = jax.jit(D.make_wave_fn(cfg, mesh))
        tables = D.init_tables(cfg, mesh)
        outs[fuse] = wave_fn(keys, groups, kinds, prio, tables,
                             jnp.uint32(0))
    for a, b in zip(jax.tree.leaves(outs[True]),
                    jax.tree.leaves(outs[False])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    commit = outs[True][0]
    assert int(commit.sum()) > 0


# ------------------------------------------------------ single-launch guard
def _pallas_launches(fn, *args):
    """Names of every pallas_call in fn's jaxpr, sub-jaxprs included."""
    jaxpr = jax.make_jaxpr(fn)(*args)

    def walk(jx, out):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(str(eqn.params.get("name")))
            for v in eqn.params.values():
                for x in (v if isinstance(v, (list, tuple)) else (v,)):
                    if hasattr(x, "jaxpr"):
                        walk(x.jaxpr, out)
        return out
    return walk(jaxpr.jaxpr, [])


@pytest.mark.parametrize("cc", sorted(PROBE_CCS))
def test_fused_wave_single_launch_guard(cc):
    """ISSUE 9 guard: on the pallas backend the fused probe-family wave
    emits exactly ONE transaction pallas_call — the wave_commit
    megakernel — and none of the unfused chain's claim_probe / occ_commit
    launches.  Unfused occ, for contrast, launches the chain."""
    from repro.core.cc import adaptive, occ, swisstm, tictoc, two_pl
    mod = {"occ": occ, "tictoc": tictoc, "2pl": two_pl,
           "swisstm": swisstm, "adaptive": adaptive}[cc]
    T, K = 4, 3
    cfg = _engine_cfg(cc, 1, "pallas", True)
    cfg = dataclasses.replace(cfg, lanes=T)
    store = store_init(cfg.n_records, cfg.n_groups, 0)
    batch = TxnBatch(op_key=jnp.zeros((T, K), jnp.int32),
                     op_group=jnp.zeros((T, K), jnp.int32),
                     op_col=jnp.zeros((T, K), jnp.int32),
                     op_kind=jnp.full((T, K), t.WRITE, jnp.int32),
                     op_val=jnp.zeros((T, K), jnp.float32),
                     txn_type=jnp.zeros((T,), jnp.int32),
                     n_ops=jnp.full((T,), K, jnp.int32))
    prio = jnp.arange(T, dtype=jnp.uint32)

    def fused(s, b, p):
        return mod.wave_validate(s, b, p, jnp.uint32(1), cfg)

    names = _pallas_launches(fused, store, batch, prio)
    wc = [n for n in names if n == "wave_commit"]
    assert len(wc) == 1, names
    assert not any("claim_probe" in n or "occ_commit" in n
                   for n in names), names

    ucfg = dataclasses.replace(cfg, fuse_wave=False)

    def unfused(s, b, p):
        return mod.wave_validate(s, b, p, jnp.uint32(1), ucfg)

    unames = _pallas_launches(unfused, store, batch, prio)
    assert "wave_commit" not in unames, unames
    assert any("claim_probe" in n for n in unames), unames


def test_wave_commit_in_backend_surface():
    """The op is part of the ``backend.N_OPS``-op surface: both backends expose it,
    CC_OPS attributes it to every probe-family mechanism, and the
    distributed occ op list routes through it."""
    from repro.core import backend as kb
    assert hasattr(kb.JnpBackend, "wave_commit")
    assert hasattr(kb.PallasBackend, "wave_commit")
    for cc in PROBE_CCS.values():
        assert "wave_commit" in kb.CC_OPS[cc], cc
        assert "claim_probe" not in kb.CC_OPS[cc], cc
    assert "wave_commit" in kb.DIST_OPS
    # the MV routed wave keeps the two-channel claim_probe (no fused path)
    assert "claim_probe" in kb.DIST_MV_OPS
