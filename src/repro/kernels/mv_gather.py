"""Snapshot version-select kernel (the MV store's read path).

A multi-version read walks the record's version chain for the newest version
visible at its snapshot timestamp.  On the paper's CPU platform that is a
pointer chase per read; here the chain is a fixed-depth ring
(core/mvstore.py), so the TPU-native formulation is the block row-DMA gather
of kernels/occ_validate.py (``rows.gather_call``): each op's whole begin ring
[D, G] (D*G packed words) rides in together, and the VPU does the
visibility scan over all D slots of all block ops at once instead of a
serial chain walk.

Granularity is the visibility width (DESIGN.md section 9): fine checks the
op's own group's begin timestamp per slot, coarse reduces each slot over the
whole row (one timestamp per record: max over groups, so a group-1-only
update hides the slot from coarse group-0 readers — the false-conflict
structure of the paper's section 3.4 at the version-chain level).  Empty
slots carry MV_EMPTY begins and are never visible.  When NO retained slot is
visible the snapshot has been reclaimed by the ring's epoch advance: ok is
False and the caller aborts the reader — it can never read a recycled slot.

Masked ops (key < 0) are forced to (slot 0, ok False), matching the jnp
gather's fill path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import rows as rw


def mv_gather_pallas(begin: jax.Array, keys: jax.Array, groups: jax.Array,
                     ts: jax.Array, fine: bool, lane_block: int = 0,
                     interpret: bool = False
                     ) -> tuple[jax.Array, jax.Array]:
    """(slot int32[T, K], ok bool[T, K]) — see ref.mv_gather."""
    D, G = begin.shape[1], begin.shape[2]

    def compute(words, scalars, cols):
        (ts_,) = scalars
        key_c, grp_c = cols[1], cols[2]
        scores = []
        for d in range(D):
            slot_words = words[d * G:(d + 1) * G]
            if fine:
                eff = rw.group_word(slot_words, grp_c)
            else:
                eff = slot_words[0]
                for w in slot_words[1:]:
                    eff = rw.umax(eff, w)
            # visible (eff <= ts, unsigned) -> eff + 1, else 0
            scores.append(jnp.where(rw.ult(ts_, eff), 0, eff + 1))
        best = scores[0]
        for s in scores[1:]:
            best = rw.umax(best, s)
        slot = jnp.full(best.shape, D, jnp.int32)
        for d in reversed(range(D)):
            slot = jnp.where(scores[d] == best, d, slot)
        live = key_c >= 0
        return [jnp.where(live, slot, 0), live & (best != 0)]

    slot, ok = rw.gather_call(compute, begin, keys, [groups],
                              [ts.astype(jnp.uint32)], 2, lane_block,
                              interpret, "mv_gather")
    return slot, ok != 0
