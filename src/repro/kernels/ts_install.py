"""Scatter-max timestamp-install kernel (TicToc's wts/rts advance).

TicToc installs commit timestamps monotonically: wts/rts of each written
(record, group) cell only ever move up (``table.at[...].max`` on the jnp
backend).  This kernel is the block read-modify-write of
``rows.scatter_call``: the packed timestamp table is both input and output
(input_output_aliases), each block fetches its ops' rows, folds every
op's candidate in with an unsigned max, and writes the final rows back.
Because max is commutative and idempotent, duplicate (record, group)
cells in one wave land on the same result in any order — which is what
makes the kernel bit-identical to the XLA scatter-max.

``whole_row=True`` installs the value across *every* group of the record —
coarse-granularity rts extension raises the whole row's read horizon (one
timestamp per record; see cc/tictoc.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import rows as rw


def ts_install_max_pallas(table: jax.Array, keys: jax.Array,
                          groups: jax.Array, vals: jax.Array, do: jax.Array,
                          whole_row: bool = False, lane_block: int = 0,
                          interpret: bool = False) -> jax.Array:
    """table' with table[k, g] = max(table[k, g], vals) per masked op — see
    ref.ts_install_max."""
    return rw.scatter_call(rw.umax, table, keys, groups,
                           vals.astype(jnp.uint32), do, whole_row,
                           lane_block, interpret, "ts_install_max")
