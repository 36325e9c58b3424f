"""Op sixteen: interval (scan) validation — phantom protection.

Hekaton-style iterator validation for extent-carrying ops: every scan op
covers ``[key, key + extent)`` and must abort if any record of its
validated interval carries a live same-wave claim stronger than the
scanning lane (DESIGN.md section 13).  Run against the POST-install claim
table, the monotone wave tags make this exactly the phantom check — the
only claims visible are this wave's writers, i.e. precisely the installs
the scan's wave-start snapshot could have missed.

The grid reuses the block row-DMA idiom of occ_validate.py on the packed
table (kernels/rows.py): consecutive records are consecutive lanes, so an
interval of ``span`` records (the STATIC per-op bound from
``ref.scan_span``) touches at most ``NB = ceil(span / 128) + 1`` record
blocks, G rows each.  Each step issues those ``NB * G`` row fetches per op
back-to-back before one wait and a vectorized compare in which every lane
knows its record.  Granularity is
the interval-claim layout, not just the compare width:

- fine (per-gap timestamps): records ``key .. key+extent-1`` probed at
  the op's own group — only a writer of the scanned column group inside
  the exact interval kills the scan;
- coarse (bucket-interval claims, one claim word per ``bucket_size``
  records): the bucket-EXPANDED interval is probed with the whole-record
  compare; a bucket's claim word is the min over its member rows, so
  writers anywhere in a touched bucket abort the scan (false phantoms at
  the bucket edges).

Masked ops (check False or key < 0) validate an empty interval, and
records past the table edge never conflict.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import rows as rw
from repro.kernels.ref import scan_span


def _kernel(fine, G, N, Nb, NB, LBK, kb_s, ivw_s, start_b, width_b,
            grp_b, prio_b, tbl, out_b, buf, sem):
    base = pl.program_id(0) * LBK

    def dmas(start):
        for b in range(NB):
            for g in range(G):
                def body(j, _, b=b, g=g):
                    p = jnp.minimum(kb_s[base + j] + b, Nb - 1) * G + g
                    copy = pltpu.make_async_copy(
                        tbl.at[pl.ds(p, 1)],
                        buf.at[pl.ds((b * G + g) * LBK + j, 1)], sem)
                    if start:
                        copy.start()
                    else:
                        copy.wait()
                    return 0

                jax.lax.fori_loop(0, LBK, body, 0)

    dmas(True)
    dmas(False)
    ident = rw.eye(LBK)
    start_c = rw.to_col(start_b[...], ident)
    width_c = rw.to_col(width_b[...], ident)
    grp_c = rw.to_col(grp_b[...], ident)
    prio_c = rw.to_col(prio_b[...], ident)
    lanes = rw.lane_iota(LBK)
    hit = jnp.zeros((LBK, rw.LANES), jnp.int32)
    for b in range(NB):
        rec = jnp.minimum((start_c >> 7) + b, Nb - 1) * rw.LANES + lanes
        act = (rec >= start_c) & (rec < start_c + width_c) & (rec < N)
        for g in range(G):
            rows = buf[pl.ds((b * G + g) * LBK, LBK), :]
            cell = act & (grp_c == g) if fine else act
            hit |= jnp.where(
                cell & rw.ult(rw.live_prio(rows, ivw_s[0]), prio_c), 1, 0)
    out_b[...] = rw.to_row(hit.max(axis=1, keepdims=True), ident)


def iterate_validate_pallas(table: jax.Array, keys: jax.Array,
                            extents: jax.Array, groups: jax.Array,
                            myprio: jax.Array, check: jax.Array,
                            inv_wave: jax.Array, fine: bool,
                            bucket_size: int, ext_cap: int,
                            lane_block: int = 0,
                            interpret: bool = False) -> jax.Array:
    """conflict bool[T, K] — see ref.iterate_validate for the oracle."""
    T, K = keys.shape
    N, G = table.shape
    B = bucket_size
    span = scan_span(ext_cap, fine, B)
    LBK, Tp = rw.blocking(keys, lane_block)
    TKp = Tp * K
    Nb = -(-N // rw.LANES)
    NB = min(-(-span // rw.LANES) + 1, Nb)
    ext = jnp.maximum(extents, 1)
    if fine:
        start, width = keys, ext
    else:
        start = (keys // B) * B
        width = ((keys + ext + B - 1) // B) * B - start
    active = check & (keys >= 0)
    start = jnp.where(active, start, 0)
    width = jnp.where(active, jnp.minimum(width, span), 0)
    row = functools.partial(rw.op_rows, Tp=Tp)
    blk = rw.blk_spec(LBK)
    out = pl.pallas_call(
        functools.partial(_kernel, fine, G, N, Nb, NB, LBK),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(TKp // LBK,),
            in_specs=[blk] * 4 + [rw.any_spec()],
            out_specs=blk,
            scratch_shapes=rw.row_scratch(LBK, NB * G)),
        out_shape=jax.ShapeDtypeStruct((1, TKp), jnp.int32),
        interpret=interpret,
        name="iterate_validate",
    )(row(start >> 7)[0],
      jnp.reshape(rw.i32(inv_wave.astype(jnp.uint32)), (1,)),
      row(start), row(width), row(groups), row(myprio), rw.pack(table))
    return rw.from_rows(out, T, K) != 0
