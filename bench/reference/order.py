"""A wave's serial order, from the lanes' retry ages and a permutation.

A closed loop retries an aborted transaction in the next wave.  A lane's
retry age is the number of earlier attempts of the transaction it runs:
0 for a fresh one, one more after each abort, 0 again once it commits.

The priority word of a lane (lower goes first in the wave) is six bits
of inverted age over ten bits of the lane's place in a permutation:

- ``"random"``: ``(63 << 10) | perm``, the permutation alone;
- ``"age"``: ``((63 - min(age, 63)) << 10) | perm``: older goes first,
  ages from 63 on tie, and ties fall back on the permutation.
"""
from __future__ import annotations

import numpy as np

RULES = ("random", "age")
#: The rule of a configuration file that names none.
DEFAULT = "random"
MAX_AGE = 63
LANE_BITS = 10


def priority(rule: str, age, perm) -> np.ndarray:
    """int64[T]: the lanes' priority words under ``rule``."""
    perm = np.asarray(perm).astype(np.int64) & ((1 << LANE_BITS) - 1)
    if rule == "random":
        inv = np.full(perm.shape, MAX_AGE, np.int64)
    elif rule == "age":
        inv = MAX_AGE - np.minimum(np.asarray(age, np.int64), MAX_AGE)
    else:
        raise ValueError(f"no serial order rule {rule!r} (known: {RULES})")
    return (inv << LANE_BITS) | perm


def next_age(age, commit) -> np.ndarray:
    """Ages after a wave: 0 where the lane committed, one more where its
    transaction aborted and will be retried."""
    return np.where(commit, 0, np.asarray(age, np.int64) + 1)
