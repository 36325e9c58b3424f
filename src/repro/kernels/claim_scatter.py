"""Fused claim-scatter kernel: pack + scatter-min claim words on-chip.

The jnp backend claims in two steps — pack ``(inv_wave << 16) | prio16``
words (core/claimword.py), then an XLA scatter-min into the claim table
(claims.scatter_claims).  This kernel installs the packed words with the
block read-modify-write of ``rows.scatter_call`` into the aliased packed
claim table.

Why min: claim words are arranged so *lower = stronger* — the current wave's
tag is numerically below every stale wave's and in-wave priority breaks ties
— so min over duplicate cells picks the strongest claimant, the vectorized
replacement for the paper's CAS races (core/claims.py).  Min is commutative
and idempotent, so the visit order cannot be observed: bit-identical to the
XLA scatter-min.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.claimword import PRIO16_MASK, WAVE_SHIFT
from repro.kernels import rows as rw


def claim_scatter_pallas(table: jax.Array, keys: jax.Array,
                         groups: jax.Array, prio: jax.Array, do: jax.Array,
                         inv_wave: jax.Array, lane_block: int = 0,
                         interpret: bool = False) -> jax.Array:
    """table' with the wave's claim words min-installed — see
    ref.claim_scatter."""
    words = ((inv_wave.astype(jnp.uint32) << WAVE_SHIFT)
             | (prio.astype(jnp.uint32) & PRIO16_MASK))
    return rw.scatter_call(rw.umin, table, keys, groups, words, do, False,
                           lane_block, interpret, "claim_scatter")
