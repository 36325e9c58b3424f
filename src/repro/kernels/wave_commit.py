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
all its ops back-to-back (the whole read stream in flight at once), waits
once, runs the block's probe / verdict / install math in VMEM, and
streams the writebacks out.

Correctness under the block tiling.  A block's row fetches all happen
before any of its writebacks, so ops that share a packed row read the
same pre-block row — the kernel therefore writes back *final* rows, the
same bytes for every op on that row:

  - claim install: fetched row, min-combined with the claim word of
    every block write op on that packed row (a scalar loop over the
    block's ops, each a masked vector min);
  - version bump: fetched row + the block's committed-write count per
    cell — a 0/1 matrix product (same packed row x committed) @ (op lane
    one-hot) on the MXU, exact for counts below 2^24.  Lane verdicts are
    block-local by construction (a block holds whole lanes).

Cross-block installs are ordered by the sequential grid (a step's
writebacks are waited before it ends).  A probe must also see the claims
of LATER blocks: the whole wave's claim cells and priorities ride along
as lane-dense rows, and an all-pairs same-cell min over them (128 lanes
at a time, folded elementwise, one lane reduce at the end) completes the
row probe — sound under the monotone-wave-tag precondition checked by
``ref.check_claim_tag_monotone``.  Masked ops fetch packed row 0 and
write back the same final row 0 as any real op on it.
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


def install_claims(buf, inst_s, prow_s, prow_c, lanes, ivw, base, LBK, G):
    """Fold every installing block op's claim word (``inst`` = ``g << 23
    | lane << 16 | prio16``, -1 when not installing) into every fetched
    copy of its packed row: each op on a row ends with the same final
    row."""
    tag = ivw << WAVE_SHIFT

    def body(j, _):
        v = inst_s[base + j]

        @pl.when(v >= 0)
        def _():
            word = tag | (v & PRIO16_MASK)
            m = (prow_c == prow_s[base + j]) & (lanes == ((v >> 16) & 127))
            for r in range(G):
                @pl.when((v >> 23) == r)
                def _():
                    sl = pl.ds(r * LBK, LBK)
                    cur = buf[sl, :]
                    buf[sl, :] = jnp.where(m, rw.umin(cur, word), cur)
        return 0

    jax.lax.fori_loop(0, LBK, body, 0)


def _wave_kernel(fine, G, LBK, TKp, dual, bump, verdict, *refs):
    it = iter(refs)
    prow_s, ivw_s, instw_s = next(it), next(it), next(it)
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

    base = pl.program_id(0) * LBK
    ivw = ivw_s[0]
    for tbl, (buf, sem, _) in zip(tables, scratch):
        rw.row_dmas(True, prow_s, tbl, buf, sem, base, LBK, offs)
    for tbl, (buf, sem, _) in zip(tables, scratch):
        rw.row_dmas(False, prow_s, tbl, buf, sem, base, LBK, offs)

    ident = rw.eye(LBK)
    lanes = rw.lane_iota(LBK)
    prow_c = rw.to_col(prow_b[...], ident)
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

    install_claims(scratch[0][0], instw_s, prow_s, prow_c, lanes, ivw, base,
                   LBK, G)
    if dual:
        install_claims(scratch[1][0], instr_s, prow_s, prow_c, lanes, ivw,
                       base, LBK, G)
    if bump:
        buf = scratch[-1][0]
        committed = ((flags_b[...] & _DOW) != 0) & (commit_r != 0)
        b = jnp.where(at, 1.0, 0.0).astype(jnp.float32)
        for r in offs:
            same_row = (prow_c == prow_b[...]) & committed & (grp_b[...] == r)
            a = jnp.where(same_row, 1.0, 0.0).astype(jnp.float32)
            cnt = jnp.dot(a, b, preferred_element_type=jnp.float32)
            sl = pl.ds(r * LBK, LBK)
            buf[sl, :] = buf[sl, :] + cnt.astype(jnp.int32)

    for tbl, (buf, _, sem) in zip(tables, scratch):
        rw.row_dmas(True, prow_s, tbl, buf, sem, base, LBK, offs,
                    to_table=True)
    # Writebacks must land before the next block fetches (sequential grid).
    for tbl, (buf, _, sem) in zip(tables, scratch):
        rw.row_dmas(False, prow_s, tbl, buf, sem, base, LBK, offs,
                    to_table=True)


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

    prefetch = [row(prow)[0],
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
