"""Timestamp row-gather kernel (TicToc's (wts, rts) observation).

TicToc reads two timestamps per op — the cell's write timestamp and read
timestamp — before computing its commit_ts.  On the paper's CPU platform this
is the same pointer chase as OCC validation; the TPU-native formulation is the
same block row-DMA gather as kernels/occ_validate.py (``rows.gather_call``),
and the VPU selects the observation width.

Granularity is the observation width (DESIGN.md sections 2 and 5): fine reads
the op's own group word, coarse reads the record's *max* — one timestamp per
record means any group's modification constrains the whole row.  The row is
already in VMEM either way, so the coarse reduce is free: the DMA cost is
identical for both granularities.

Masked ops (key < 0) are forced to 0 in the output — the same fill value the
jnp gather path uses.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import rows as rw


def ts_gather_pallas(table: jax.Array, keys: jax.Array, groups: jax.Array,
                     fine: bool, lane_block: int = 0,
                     interpret: bool = False) -> jax.Array:
    """Per-op timestamp observation uint32[T, K] — see ref.ts_gather."""

    def compute(words, scalars, cols):
        key_c, grp_c = cols[1], cols[2]
        if fine:
            ts = rw.group_word(words, grp_c)
        else:
            ts = words[0]
            for w in words[1:]:
                ts = rw.umax(ts, w)
        return [jnp.where(key_c >= 0, ts, 0)]

    (ts,) = rw.gather_call(compute, table, keys, [groups], [], 1,
                           lane_block, interpret, "ts_gather")
    return jax.lax.bitcast_convert_type(ts, jnp.uint32)
