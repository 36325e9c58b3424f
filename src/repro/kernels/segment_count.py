"""Same-cell segment-count kernel (TicToc's extension-pass contention).

TicToc's cost model needs, per op, how many ops of the SAME WAVE hit the
same (record, group) cell — the rts-extension CAS chain length and the
commit-ts install chain (cc/tictoc.py).  The jnp path counts segments with
an XLA sort + two searchsorted passes; this kernel does a direct all-pairs
compare instead: the wave's cell ids sit in VMEM as one lane-dense row, and
each grid step compares a block of ops (one per sublane) against the whole
wave 128 lanes at a time, summing matches — no sort, no O(n_records)
table, and the count is an order-free sum, bit-identical to the sorted
formulation.

Masked ops take a sentinel cell id and report 0, matching ref.segment_count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import rows as rw


def _kernel(LBK, TKp, cell_b, wave, out_b):
    ident = rw.eye(LBK)
    cell_c = rw.to_col(cell_b[...], ident)

    def chunk(c, acc):
        off = pl.multiple_of(c * rw.LANES, rw.LANES)
        return acc + (cell_c == wave[:, pl.ds(off, rw.LANES)]).astype(
            jnp.int32)

    acc = jax.lax.fori_loop(0, TKp // rw.LANES, chunk,
                            jnp.zeros((LBK, rw.LANES), jnp.int32))
    cnt = jnp.where(cell_c != rw.SENT, acc.sum(axis=1, keepdims=True), 0)
    out_b[...] = rw.to_row(cnt, ident)


def segment_count_pallas(keys: jax.Array, groups: jax.Array, G: int,
                         mask: jax.Array, lane_block: int = 0,
                         interpret: bool = False) -> jax.Array:
    """float32[T, K] same-cell op counts — see ref.segment_count."""
    T, K = keys.shape
    LBK, Tp = rw.blocking(keys, lane_block)
    TKp = Tp * K
    cell = rw.op_rows(jnp.where(mask, keys * G + groups, rw.SENT), Tp,
                      fill=rw.SENT)
    out = pl.pallas_call(
        functools.partial(_kernel, LBK, TKp),
        grid=(TKp // LBK,),
        in_specs=[rw.blk_spec(LBK), rw.full_spec((1, TKp))],
        out_specs=rw.blk_spec(LBK),
        out_shape=jax.ShapeDtypeStruct((1, TKp), jnp.int32),
        interpret=interpret,
        name="segment_count",
    )(cell, cell)
    return rw.from_rows(out, T, K).astype(jnp.float32)
