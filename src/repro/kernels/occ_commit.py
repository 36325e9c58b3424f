"""OCC commit kernel: install version bumps for committed write ops.

A block read-modify-write (``rows.scatter_call``): the packed version table
is both input and output (input_output_aliases), each block of ops fetches
its rows, adds one per committed write to the op's cell (duplicates within
a block accumulate), and writes the final rows back; the sequential grid
orders the blocks, so duplicate bumps across blocks accumulate too.
Validated against ref.occ_commit, including duplicate-row cases.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import rows as rw


def _add(cell, val):
    return cell + val


def occ_commit_pallas(wts: jax.Array, keys: jax.Array, groups: jax.Array,
                      do: jax.Array, lane_block: int = 0,
                      interpret: bool = False) -> jax.Array:
    """wts' with +1 at each (key[t,k], group[t,k]) where do[t,k]."""
    return rw.scatter_call(_add, wts, keys, groups,
                           jnp.ones(keys.shape, jnp.int32), do, False,
                           lane_block, interpret, "occ_commit")
