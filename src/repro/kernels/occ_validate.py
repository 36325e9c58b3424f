"""OCC read-set validation kernels.

The hot loop of optimistic commit: for every read op, fetch the claimed-writer
word of its (record, group) cell and compare priorities.  On the paper's CPU
platform this is a pointer chase per read; the TPU-native formulation is a
scalar-prefetch-driven DMA: each op's packed table row index is prefetched
into SMEM and rows move HBM->VMEM by explicit ``make_async_copy`` DMAs, a
whole block of ops in flight at once (``rows.gather_call``), then the VPU
does the tag/priority compare for the block.

Granularity is the compare width (DESIGN.md section 2): fine compares the
op's own group word, coarse reduces over the record's G words (the row is in
VMEM either way, so the coarse reduce is free; the DMA is the cost, and it is
identical for both granularities, matching the paper's "fine-grained
timestamps have no measurable overhead").

Three kernels share the one gather grid:

- ``occ_validate_pallas`` — conflict bool at one granularity (OCC's hot loop);
- ``occ_validate_dual_pallas`` — fine AND coarse verdicts from the same row
  fetch, so AutoGran's double probe costs one DMA per op, not two;
- ``claim_probe_pallas`` — the raw strongest-claimant prio16 (NO_PRIO when
  the cell is unclaimed this wave or the op is masked).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.claimword import NO_PRIO
from repro.kernels import rows as rw


def _table_prio(words, ivw, cols, fine):
    """Strongest live claimant per block op from its record's words."""
    key_c, grp_c = cols[1], cols[2]
    if fine:
        pr = rw.live_prio(rw.group_word(words, grp_c), ivw)
    else:
        pr = rw.live_prio(words[0], ivw)
        for w in words[1:]:
            pr = jnp.minimum(pr, rw.live_prio(w, ivw))
    return jnp.where(key_c >= 0, pr, NO_PRIO)


def occ_validate_pallas(claim_w: jax.Array, keys: jax.Array,
                        groups: jax.Array, myprio: jax.Array,
                        check: jax.Array, inv_wave: jax.Array, fine: bool,
                        lane_block: int = 0,
                        interpret: bool = False) -> jax.Array:
    """conflict bool[T, K] — see ref.occ_validate for the oracle."""

    def compute(words, scalars, cols):
        wprio = _table_prio(words, scalars[0], cols, fine)
        return [(cols[4] != 0) & rw.ult(wprio, cols[3])]

    (conf,) = rw.gather_call(compute, claim_w, keys,
                             [groups, myprio, check], [inv_wave], 1,
                             lane_block, interpret, "occ_validate")
    return conf != 0


def occ_validate_dual_pallas(claim_w: jax.Array, keys: jax.Array,
                             groups: jax.Array, myprio: jax.Array,
                             check: jax.Array, inv_wave: jax.Array,
                             lane_block: int = 0, interpret: bool = False
                             ) -> tuple[jax.Array, jax.Array]:
    """(fine, coarse) conflict bool[T, K] from ONE row DMA per op — the
    AutoGran double probe without the double fetch."""

    def compute(words, scalars, cols):
        chk = cols[4] != 0
        return [chk & rw.ult(_table_prio(words, scalars[0], cols, f),
                             cols[3])
                for f in (True, False)]

    fine, coarse = rw.gather_call(compute, claim_w, keys,
                                  [groups, myprio, check], [inv_wave], 2,
                                  lane_block, interpret, "occ_validate_dual")
    return fine != 0, coarse != 0


def claim_probe_pallas(table: jax.Array, keys: jax.Array, groups: jax.Array,
                       inv_wave: jax.Array, fine: bool, lane_block: int = 0,
                       interpret: bool = False) -> jax.Array:
    """Strongest live claimant prio16 per op (uint32[T, K]; NO_PRIO when the
    cell is unclaimed this wave or the op is masked) — see ref.claim_probe."""

    def compute(words, scalars, cols):
        return [_table_prio(words, scalars[0], cols, fine)]

    (wprio,) = rw.gather_call(compute, table, keys, [groups], [inv_wave], 1,
                              lane_block, interpret, "claim_probe_raw")
    return wprio.astype(jnp.uint32)
