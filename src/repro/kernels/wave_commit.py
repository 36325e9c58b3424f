"""Op fifteen: the lane-block megakernel for the probe-family wave.

One ``pallas_call`` replaces the whole claim -> verdict -> install chain
(``claim_probe`` launch, XLA verdict compare, ``commit_install`` launch)
that the probe family ran per wave: in a single launch with the claim and
version tables aliased in/out, the kernel installs the wave's write
claims, answers every op's strongest-claimant probe, reduces the per-op
conflicts to lane verdicts in VMEM, and bumps versions for the committed
writes — each touched row rides ONE DMA per wave instead of 2-3
(DESIGN.md section 5).  ``claim_probe_fused_pallas`` is the same kernel
with one table and the raw priorities as output.

Tiling.  Tables are packed 128-lane rows (kernels/rows.py) in ANY memory
space.  The grid walks blocks of ``LBK = LB * K`` ops (``LB`` whole
lanes, LBK a multiple of 128): a step issues the packed-row fetches of
all its live ops back-to-back (the whole read stream in flight at once),
waits once, runs the block's probe / verdict / install math in VMEM, and
streams the writebacks out.  Its scalar loops walk lists of the block's
live ops and of its row leaders (``op_order``), never its masked ops.

Row leaders.  The launcher computes, per op, the in-block index of the
first live op of its block on the same packed row (``row_leaders``; -1
for a masked op).  A block's row fetches all happen before any of its
writebacks, so every live op reads the same pre-block copy of its row
(the probe reads each op's own copy); the *final* row is built in the
leader's copy alone, and only leaders write back — one writeback per
distinct packed row per block:

  - claim install: each installing op min-combines its claim word into
    one word of its leader's copy (a scalar loop over the block's live
    ops, one (1, 128) row each);
  - version bump: fetched row + the block's committed-write count per
    cell — a 0/1 matrix product (same packed row x committed) @ (op lane
    one-hot) on the MXU, exact for counts below 2^24; every copy gets
    its row's full count, the leader's included.  Lane verdicts are
    block-local by construction (a block holds whole lanes).

Cross-block installs are ordered by the sequential grid (a step's
writebacks are waited before it ends).  A probe must also see the claims
of LATER blocks: the whole wave's claim cells and priorities ride along
as lane-dense rows, and an all-pairs same-cell min over them (128 lanes
at a time, folded elementwise, one lane reduce at the end) completes the
row probe — sound under the monotone-wave-tag precondition checked by
``ref.check_claim_tag_monotone``.  Masked ops (key < 0, and the
padding lanes) move no row: their scratch rows keep stale words, which
the probe masks out and no writeback reads.  ``wave_row_copies`` counts
the copies a wave issues.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.claimword import NO_PRIO, PRIO16_MASK, WAVE_SHIFT
from repro.kernels import rows as rw

# flag bits of the per-op ``flags`` row
_LIVE, _CW, _CW2, _CR, _EX, _DOW = (1 << b for b in range(6))


def _bit(flags, bit):
    return (flags & bit) != 0


def probe_col(words, ivw, ocell_c, grp_c, live_c, wcell_ref, wp16_ref,
              fine, TKp):
    """Strongest live claimant prio16 (int32 column, NO_PRIO if none or
    masked) of each block op: min over its fetched cell (fine) or record
    (coarse) and over every same-wave claim on that cell or record."""
    if fine:
        pr = rw.live_prio(rw.group_word(words, grp_c), ivw)
    else:
        pr = rw.live_prio(words[0], ivw)
        for w in words[1:]:
            pr = jnp.minimum(pr, rw.live_prio(w, ivw))

    def chunk(c, acc):
        off = pl.multiple_of(c * rw.LANES, rw.LANES)
        wc = wcell_ref[:, pl.ds(off, rw.LANES)]
        wp = wp16_ref[:, pl.ds(off, rw.LANES)]
        return jnp.minimum(acc, jnp.where(ocell_c == wc, wp, NO_PRIO))

    acc = jax.lax.fori_loop(0, TKp // rw.LANES, chunk,
                            jnp.full((ocell_c.shape[0], rw.LANES), NO_PRIO,
                                     jnp.int32))
    wave = acc.min(axis=1, keepdims=True)
    return jnp.where(live_c, jnp.minimum(pr, wave), NO_PRIO)


def install_claims(buf, inst_s, lead_s, ivw, base, LBK, live):
    """Fold every installing block op's claim word (``inst`` = ``g << 23
    | lane << 16 | prio16``, -1 when not installing) into its row
    leader's copy of group row ``g``: one (1, 128) row per op.  Walks the
    block's live ops, ``live`` = (op list, count)."""
    tag = ivw << WAVE_SHIFT
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, rw.LANES), 1)

    def body(i, _):
        j = live[0][base + i]
        v = inst_s[base + j]

        @pl.when(v >= 0)
        def _():
            word = tag | (v & PRIO16_MASK)
            at = pl.ds((v >> 23) * LBK + lead_s[base + j], 1)
            cur = buf[at, :]
            buf[at, :] = jnp.where(lanes == ((v >> 16) & 127),
                                   rw.umin(cur, word), cur)
        return 0

    jax.lax.fori_loop(0, live[1], body, 0)


def _wave_kernel(fine, G, LBK, TKp, dual, bump, verdict, *refs):
    it = iter(refs)
    prow_s, lead_s, order_s, nlive_s, nlead_s, ivw_s, instw_s = (
        next(it) for _ in range(7))
    instr_s = next(it) if dual else None
    (prow_b, lane_b, grp_b, ocell_b, prio_b, flags_b,
     lid_b) = (next(it) for _ in range(7))
    wcw, wp16 = next(it), next(it)
    wcr = next(it) if dual else None
    n_tbl = 1 + int(dual) + int(bump)
    for _ in range(n_tbl):
        next(it)                     # tables in: RMW through the outputs
    outs = [next(it) for _ in range(2 if verdict else 1)]
    tables = [next(it) for _ in range(n_tbl)]
    scratch = [(next(it), next(it), next(it)) for _ in tables]
    offs = list(range(G))

    blk = pl.program_id(0)
    base = blk * LBK
    ivw = ivw_s[0]
    live = (order_s, nlive_s[blk])       # leaders first, then other live
    leaders = (order_s, nlead_s[blk])
    dmas = functools.partial(rw.row_dmas, prow_ref=prow_s, base=base,
                             n=LBK, offs=offs)
    for tbl, (buf, sem, _) in zip(tables, scratch):
        dmas(True, tbl_ref=tbl, buf_ref=buf, sem=sem, ops=live)
    for tbl, (buf, sem, _) in zip(tables, scratch):
        dmas(False, tbl_ref=tbl, buf_ref=buf, sem=sem, ops=live)

    ident = rw.eye(LBK)
    lanes = rw.lane_iota(LBK)
    lane_c = rw.to_col(lane_b[...], ident)
    grp_c = rw.to_col(grp_b[...], ident)
    flags_c = rw.to_col(flags_b[...], ident)
    at = lanes == lane_c

    def probe(buf, wcell_ref):
        words = [rw.pick(buf[pl.ds(r * LBK, LBK), :], at) for r in offs]
        return probe_col(words, ivw, rw.to_col(ocell_b[...], ident), grp_c,
                         _bit(flags_c, _LIVE), wcell_ref, wp16, fine, TKp)

    wprio = probe(scratch[0][0], wcw)
    if verdict:
        prio_c = rw.to_col(prio_b[...], ident)
        conf = _bit(flags_c, _CW) & rw.ult(wprio, prio_c)
        conf |= (_bit(flags_c, _CW2) & (wprio != NO_PRIO)
                 & (wprio != prio_c))
        if dual:
            conf |= _bit(flags_c, _CR) & rw.ult(probe(scratch[1][0], wcr),
                                                prio_c)
        conf_c = (conf | _bit(flags_c, _EX)).astype(jnp.int32)
        # lane verdicts: a lane commits iff none of its block ops conflict
        same_lane = rw.to_col(lid_b[...], ident) == lid_b[...]
        commit_r = 1 - jnp.where(same_lane, conf_c, 0).max(axis=0,
                                                           keepdims=True)
        outs[0][...] = rw.to_row(conf_c, ident)
        outs[1][...] = commit_r
    else:
        outs[0][...] = rw.to_row(wprio, ident)

    install_claims(scratch[0][0], instw_s, lead_s, ivw, base, LBK, live)
    if dual:
        install_claims(scratch[1][0], instr_s, lead_s, ivw, base, LBK, live)
    if bump:
        buf = scratch[-1][0]
        prow_c = rw.to_col(prow_b[...], ident)
        committed = ((flags_b[...] & _DOW) != 0) & (commit_r != 0)
        b = jnp.where(at, 1.0, 0.0).astype(jnp.float32)
        for r in offs:
            same_row = (prow_c == prow_b[...]) & committed & (grp_b[...] == r)
            a = jnp.where(same_row, 1.0, 0.0).astype(jnp.float32)
            cnt = jnp.dot(a, b, preferred_element_type=jnp.float32)
            sl = pl.ds(r * LBK, LBK)
            buf[sl, :] = buf[sl, :] + cnt.astype(jnp.int32)

    for tbl, (buf, _, sem) in zip(tables, scratch):
        dmas(True, tbl_ref=tbl, buf_ref=buf, sem=sem, to_table=True,
             ops=leaders)
    # Writebacks must land before the next block fetches (sequential grid).
    for tbl, (buf, _, sem) in zip(tables, scratch):
        dmas(False, tbl_ref=tbl, buf_ref=buf, sem=sem, to_table=True,
             ops=leaders)


def row_leaders(krow: jax.Array, LBK: int) -> jax.Array:
    """Row-leader map of a padded wave: for each op of the lane-dense key
    row ``krow`` ``[1, TKp]`` (padding -1), the in-block index of the
    first live op of its block of ``LBK`` on the same packed row, or -1
    for a masked op.  An all-pairs compare per block, in XLA."""
    k = krow.reshape(-1, LBK)
    live = k >= 0
    blk = k >> 7
    idx = jnp.arange(LBK, dtype=jnp.int32)
    same = (blk[:, :, None] == blk[:, None, :]) & live[:, None, :]
    first = jnp.min(jnp.where(same, idx, LBK), axis=2)
    return jnp.where(live, first, -1).reshape(1, -1)


def op_order(lead: jax.Array, LBK: int):
    """The kernel's op lists from the leader map ``[TKp]``: each block's
    in-block op indices with its row leaders first, then its other live
    ops, then its masked ops; and per block the count of live ops and of
    leaders.  The kernel's loops run over those prefixes alone."""
    ld = lead.reshape(-1, LBK)
    is_lead = ld == jnp.arange(LBK, dtype=jnp.int32)
    rank = jnp.where(is_lead, 0, jnp.where(ld >= 0, 1, 2))
    order = jnp.argsort(rank, axis=1).astype(jnp.int32)
    return (order.reshape(-1), (ld >= 0).sum(axis=1, dtype=jnp.int32),
            is_lead.sum(axis=1, dtype=jnp.int32))


def wave_row_copies(keys: jax.Array, n_tables: int, G: int,
                    lane_block: int = 0) -> tuple[jax.Array, jax.Array]:
    """(row copies the kernel issues for a ``[T, K]`` wave on ``n_tables``
    tables of ``G`` group rows, copies of the scheme that moved every op's
    rows): a live op fetches its G rows per table, a row leader writes
    them back; before, every op of the padded wave did both."""
    LBK, Tp = rw.blocking(keys, lane_block)
    lead = row_leaders(rw.op_rows(keys, Tp, fill=-1), LBK)[0]
    _, n_live, n_lead = op_order(lead, LBK)
    moved = n_live.sum() + n_lead.sum()
    return n_tables * G * moved, 2 * n_tables * G * lead.shape[0]


def _wave_call(tables, keys, groups, prio, do_w, do_r, flags, inv_wave,
               fine, dual, bump, verdict, lane_block, interpret, name):
    """Shared launcher: returns ([T, K] int32 outputs, updated tables)."""
    T, K = keys.shape
    G = tables[0].shape[1]
    LBK, Tp = rw.blocking(keys, lane_block)
    TKp = Tp * K
    row = functools.partial(rw.op_rows, Tp=Tp)

    live = keys >= 0
    kcl = jnp.maximum(keys, 0)
    prow, lane = rw.record_pos(keys, G)
    ocell = kcl * G + groups if fine else kcl
    p16 = (prio.astype(jnp.uint32) & PRIO16_MASK).astype(jnp.int32)
    inst = (groups << 23) | (lane << 16) | p16
    do_w = do_w & live
    lid = jnp.broadcast_to(jnp.arange(Tp, dtype=jnp.int32)[:, None], (Tp, K))

    lead = row_leaders(row(keys, fill=-1), LBK)[0]
    prefetch = [row(prow)[0], lead, *op_order(lead, LBK),
                jnp.reshape(rw.i32(inv_wave.astype(jnp.uint32)), (1,)),
                row(jnp.where(do_w, inst, -1), fill=-1)[0]]
    wave_rows = [row(jnp.where(do_w, ocell, rw.SENT), fill=rw.SENT), row(p16)]
    if dual:
        do_r = do_r & live
        prefetch.append(row(jnp.where(do_r, inst, -1), fill=-1)[0])
        wave_rows.append(row(jnp.where(do_r, ocell, rw.SENT), fill=rw.SENT))
    op_ins = [row(prow), row(lane), row(groups), row(ocell), row(prio),
              row(flags), lid.reshape(1, TKp)]

    blk = rw.blk_spec(LBK)
    full = rw.full_spec((1, TKp))
    n_tbl = len(tables)
    n_out = 2 if verdict else 1
    packed = [rw.pack(tb) for tb in tables]
    first_tbl = len(prefetch) + len(op_ins) + len(wave_rows)
    scratch = [pltpu.VMEM((G * LBK, rw.LANES), jnp.int32),
               pltpu.SemaphoreType.DMA(()),
               pltpu.SemaphoreType.DMA(())] * n_tbl
    outs = pl.pallas_call(
        functools.partial(_wave_kernel, fine, G, LBK, TKp, dual, bump,
                          verdict),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(TKp // LBK,),
            in_specs=[blk] * len(op_ins) + [full] * len(wave_rows)
            + [rw.any_spec()] * n_tbl,
            out_specs=[blk] * n_out + [rw.any_spec()] * n_tbl,
            scratch_shapes=scratch),
        out_shape=[jax.ShapeDtypeStruct((1, TKp), jnp.int32)] * n_out
        + [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in packed],
        input_output_aliases={first_tbl + i: n_out + i
                              for i in range(n_tbl)},
        interpret=interpret,
        name=name,
    )(*prefetch, *op_ins, *wave_rows, *packed)
    res = [rw.from_rows(o, T, K) for o in outs[:n_out]]
    return res, [rw.unpack(o, tb) for o, tb in zip(outs[n_out:], tables)]


def wave_commit_pallas(claim_w: jax.Array, claim_r, wts, keys: jax.Array,
                       groups: jax.Array, prio: jax.Array, do_w: jax.Array,
                       do_r, check_w: jax.Array, check_w2, check_r, extra,
                       inv_wave: jax.Array, fine: bool, dual: bool,
                       bump: bool, lane_block: int = 0,
                       interpret: bool = False):
    """(claim_w', claim_r', wts', conflict bool[T,K], commit bool[T]) —
    see ref.wave_commit (None passed through for absent tables)."""
    zeros = jnp.zeros(keys.shape, jnp.bool_)

    def bit(x, b):
        return jnp.where(zeros if x is None else x, b, 0)

    do_r = do_r if dual else zeros
    flags = (bit(keys >= 0, _LIVE) | bit(check_w, _CW) | bit(check_w2, _CW2)
             | bit(check_r, _CR) | bit(extra, _EX)
             | bit(do_w & (keys >= 0), _DOW))
    tables = [claim_w] + ([claim_r] if dual else []) + ([wts] if bump else [])
    (conf, commit), tbl = _wave_call(
        tables, keys, groups, prio, do_w, do_r, flags, inv_wave, fine, dual,
        bump, True, lane_block, interpret, "wave_commit")
    claim_w = tbl[0]
    claim_r = tbl[1] if dual else None
    wts = tbl[-1] if bump else None
    return claim_w, claim_r, wts, conf != 0, commit[:, 0] != 0


def claim_probe_fused_pallas(table: jax.Array, keys: jax.Array,
                             groups: jax.Array, prio: jax.Array,
                             do: jax.Array, inv_wave: jax.Array, fine: bool,
                             lane_block: int = 0, interpret: bool = False
                             ) -> tuple[jax.Array, jax.Array]:
    """(table', wprio uint32[T, K]) — see ref.claim_probe_fused."""
    flags = jnp.where(keys >= 0, _LIVE, 0)
    (wprio,), (table,) = _wave_call(
        [table], keys, groups, prio, do, None, flags, inv_wave, fine, False,
        False, False, lane_block, interpret, "claim_probe")
    return table, wprio.astype(jnp.uint32)
