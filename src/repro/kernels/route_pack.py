"""Routing-pack kernel (the distributed wave's send side).

The sharded engine builds per-destination fixed-capacity exchange buffers.
The ranks are a counting scan (op ``i`` lands at slot ``pos[i]`` = the
number of earlier ops bound for the same destination — the placement a
*stable* argsort by owner would produce, without the sort); the kernel
materializes the buffers: every output cell compares its flat slot id
against the wave's slot row (128 cells x 128 ops at a time) and selects
the one op, if any, that lands there, for every payload channel at once.

Ops whose rank reaches the capacity are dropped (``took`` False — their
lane aborts, counted by the caller); masked ops carry an out-of-range owner
and match no destination.  Bit-identical to ``ref.route_pack``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import rows as rw


def _kernel(W, Mp, fills, slot_ref, vals_ref, buf_ref):
    L = rw.LANES
    cells = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    wcol = jax.lax.broadcasted_iota(jnp.int32, (L, W), 1)

    def cell_chunk(c, _):
        c0 = pl.multiple_of(c * L, L)

        def op_chunk(o, acc):
            o0 = pl.multiple_of(o * L, L)
            sel = cells + c0 == slot_ref[:, pl.ds(o0, L)]
            have = acc[0] | jnp.where(sel, 1, 0)
            return (have,) + tuple(
                a + jnp.where(sel, vals_ref[w:w + 1, pl.ds(o0, L)], 0)
                for w, a in enumerate(acc[1:]))

        zeros = jnp.zeros((L, L), jnp.int32)
        acc = jax.lax.fori_loop(0, Mp // L, op_chunk,
                                (zeros,) * (W + 1))
        have = acc[0].max(axis=1, keepdims=True) != 0
        tile = jnp.zeros((L, W), jnp.int32)
        for w in range(W):
            v = jnp.where(have, acc[1 + w].sum(axis=1, keepdims=True),
                          fills[w])
            tile = jnp.where(wcol == w, v, tile)
        buf_ref[pl.ds(c0, L), :] = tile
        return 0

    jax.lax.fori_loop(0, buf_ref.shape[0] // L, cell_chunk, 0)


def route_pack_pallas(owner: jax.Array, vals: jax.Array, n_dest: int,
                      cap: int, fills, interpret: bool = False
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(buf [W, n_dest, cap], pos [M], took [M]) — see ref.route_pack."""
    W, M = vals.shape
    d = jnp.arange(n_dest, dtype=jnp.int32)[:, None]
    match = owner[None, :] == d                        # [n_dest, M]
    prefix = jnp.cumsum(match, axis=1) - match         # rank within dest
    pos = jnp.where(match, prefix, 0).sum(axis=0).astype(jnp.int32)
    took = (match & (prefix < cap)).any(axis=0)
    slot = jnp.where(took, owner * cap + pos, -1)
    L = rw.LANES
    Mp = -(-M // L) * L
    C = n_dest * cap
    Cp = -(-C // L) * L
    buf = pl.pallas_call(
        functools.partial(_kernel, W, Mp, tuple(int(f) for f in fills)),
        out_shape=jax.ShapeDtypeStruct((Cp, W), jnp.int32),
        interpret=interpret,
        name="route_pack",
    )(jnp.pad(slot, (0, Mp - M), constant_values=-1).reshape(1, Mp),
      jnp.pad(vals.astype(jnp.int32), ((0, 0), (0, Mp - M))))
    return buf[:C].T.reshape(W, n_dest, cap), pos, took
