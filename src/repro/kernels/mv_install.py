"""Ring-slot claim + version-publish kernel (the MV store's commit path).

A read-modify-write on *two* aliased packed tables (kernels/rows.py): the
begin-timestamp ring [N, D, G] and the head cursor [N] are both input and
output (input_output_aliases).  One grid step walks the wave's committed
write ops in order; for each it DMAs the record's packed ring row and
cursor row into VMEM, edits them, and writes both back before the next op.

Unlike the min/+1/max scatters, a version install is NOT a per-cell
commutative combine — a record must claim exactly ONE new slot per wave no
matter how many committed ops hit it (concurrent group writers and
duplicate in-transaction writes merge into that slot).  The op order makes
this well-defined: the FIRST op to visit a record advances the head,
copies the old newest slot's begin row into the new slot (carry-forward of
unwritten groups) and stamps its group; LATER visits detect the same-wave
install — some begin in the record already equals this wave's install
timestamp, which no earlier wave can have written because install
timestamps advance monotonically (core/mvstore.install_ts) — and only stamp
their group.  Under that monotonicity precondition the result is
order-independent across a wave, and bit-identical to the jnp oracle
(ref.mv_install), which resolves every op against the pre-wave head instead.

Masked ops touch nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import rows as rw


def _kernel(D, G, offs, n, kb_s, info_s, ts_s, b_in, h_in, b_out,
            h_out, ring, hrow, sem):
    del b_in, h_in                   # RMW through the aliased outputs
    ts = ts_s[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (D * G, rw.LANES), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (D * G, rw.LANES), 1)

    def copies(j, back):
        pairs = [(b_out.at[pl.ds(kb_s[j] * G + off, 1)],
                  ring.at[pl.ds(r, 1)]) for r, off in enumerate(offs)]
        pairs.append((h_out.at[pl.ds(kb_s[j], 1)], hrow))
        cs = [pltpu.make_async_copy(*(p[::-1] if back else p), sem)
              for p in pairs]
        for c in cs:
            c.start()
        for c in cs:
            c.wait()

    def body(j, _):
        v = info_s[j]

        @pl.when(v >= 0)
        def _():
            copies(j, False)
            lane, g = v >> 8, v & 0xFF
            at = lanes == lane
            cur, hr = ring[...], hrow[...]
            h = jnp.where(at[:1], hr, 0).sum(axis=1, keepdims=True)
            already = jnp.where(at & (cur == ts), 1, 0).max(
                axis=(0, 1), keepdims=True) != 0
            h_eff = jnp.where(already, h, jnp.where(h + 1 == D, 0, h + 1))
            new = cur
            for gg in range(G):       # carry the old newest slot forward
                old = jnp.where(at & (rows == h * G + gg), cur, 0).sum(
                    axis=(0, 1), keepdims=True)
                new = jnp.where(~already & at & (rows == h_eff * G + gg),
                                old, new)
            ring[...] = jnp.where(at & (rows == h_eff * G + g), ts, new)
            hrow[...] = jnp.where(at[:1], h_eff, hr)
            copies(j, True)
        return 0

    jax.lax.fori_loop(0, n, body, 0)


def mv_install_pallas(begin: jax.Array, head: jax.Array, keys: jax.Array,
                      groups: jax.Array, do: jax.Array, ts: jax.Array,
                      interpret: bool = False
                      ) -> tuple[jax.Array, jax.Array]:
    """(begin', head') with one new ring slot per masked record — see
    ref.mv_install (incl. the begin < ts monotonicity precondition)."""
    D, G = begin.shape[1], begin.shape[2]
    blk_row, lane = rw.record_pos(keys, G)
    info = jnp.where(do & (keys >= 0), (lane << 8) | groups, -1)
    bp, hp = rw.pack(begin), rw.pack(head)
    b2, h2 = pl.pallas_call(
        functools.partial(_kernel, D, G, rw.row_offsets(begin.shape),
                          keys.size),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[rw.any_spec(), rw.any_spec()],
            out_specs=(rw.any_spec(), rw.any_spec()),
            scratch_shapes=[pltpu.VMEM((D * G, rw.LANES), jnp.int32),
                            pltpu.VMEM((1, rw.LANES), jnp.int32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=(jax.ShapeDtypeStruct(bp.shape, bp.dtype),
                   jax.ShapeDtypeStruct(hp.shape, hp.dtype)),
        # begin is operand 3 and head operand 4, counting the prefetches.
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
        name="mv_install",
    )((blk_row // G).reshape(-1), info.reshape(-1),
      jnp.reshape(rw.i32(ts.astype(jnp.uint32)), (1,)), bp, hp)
    return rw.unpack(b2, begin), rw.unpack(h2, head)
