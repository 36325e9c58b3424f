"""The plain reference: one wave of serializable optimistic concurrency
control, in NumPy, written from the semantics and not from the program.

A wave is T transactions of K operation slots, run together.  Its serial
order is the lanes' priorities, lower first.  An operation names a
record, a timestamp group of the record and a kind; a slot whose kind is
NOP or whose record is negative is unused.

- OCC (the paper's mechanism): a transaction aborts iff one of its reads
  touches a (record, group) cell that a transaction earlier in the serial
  order writes in the same wave.  Writes never abort (commit-time locks
  serialize them).  Every abort is a read-validation failure.
- MV-OCC: first-committer-wins on writes (a plain write loses to any
  earlier writer of its cell, a commutative add only to an earlier plain
  write), and an update transaction's reads validate as in OCC; read-only
  transactions read their snapshot and never validate.  A lane's cause
  is write-write where any of its writes lost, else read validation.

Fine granularity makes the cell the (record, group) pair; coarse makes it
the record.  Every check counts: a serializable engine thins none.

The routed wave of four chips adds capacity: each source chip sends each
owner chip at most ``cap`` operations per wave, in slot order, and a
transaction with an operation beyond that aborts (cause capacity); such
an operation claims nothing.
"""
from __future__ import annotations

import numpy as np

# The engine's operation kinds and abort causes, as its inputs and
# counters encode them.
NOP, READ, WRITE, ADD = 0, 1, 2, 3
CAPACITY, WW, READ_VAL = 1, 4, 5
N_CAUSES = 7
NO_PRIO = 0xFFFF


def _cells(key, group, n_groups: int, fine: bool):
    k = key.astype(np.int64)
    return k * n_groups + group if fine else k


def earliest(cell: np.ndarray, prio: np.ndarray, mask: np.ndarray
             ) -> np.ndarray:
    """Per operation: the lowest priority among the masked operations of
    its cell in this wave (NO_PRIO where none)."""
    c, p = cell[mask], prio[mask]
    out = np.full(cell.shape, NO_PRIO, np.int64)
    if c.size == 0:
        return out
    order = np.lexsort((p, c))
    cs, ps = c[order], p[order]
    first = np.r_[True, cs[1:] != cs[:-1]]
    uniq, lowest = cs[first], ps[first]
    pos = np.clip(np.searchsorted(uniq, cell), 0, uniq.size - 1)
    hit = uniq[pos] == cell
    out[hit] = lowest[pos[hit]]
    return out


def wave(cc: str, key, group, kind, prio, *, n_groups: int,
         fine: bool = True, dropped=None) -> dict:
    """One wave's verdicts: ``commit`` bool[T], ``causes`` int[N_CAUSES],
    and the operations it installs: ``claims`` (live writes that reached
    their owner, every lane), ``plain_claims`` (their plain writes) and
    ``installs`` (the committed ones)."""
    T, K = key.shape
    live = (kind != NOP) & (key >= 0)
    if dropped is None:
        dropped = np.zeros((T, K), bool)
    arrived = live & ~dropped
    rd = arrived & (kind == READ)
    wr = arrived & ((kind == WRITE) | (kind == ADD))
    pw = arrived & (kind == WRITE)
    ad = arrived & (kind == ADD)
    myp = np.broadcast_to(prio.astype(np.int64)[:, None], (T, K))
    cell = _cells(key, group, n_groups, fine)
    first_w = earliest(cell, myp, wr)
    if cc == "occ":
        conflict = rd & (first_w < myp)
        cause = np.full((T, K), READ_VAL)
    elif cc == "mvocc":
        first_pw = earliest(cell, myp, pw)
        update = (live & ((kind == WRITE) | (kind == ADD))).any(axis=1)
        conflict = ((pw & (first_w < myp)) | (ad & (first_pw < myp))
                    | (rd & (first_w < myp) & update[:, None]))
        cause = np.where(wr, WW, READ_VAL)
    else:
        raise ValueError(f"no reference for mechanism {cc!r}")
    cause = np.where(dropped & live, CAPACITY, cause)
    conflict = conflict | (dropped & live)
    commit = ~conflict.any(axis=1)
    lane_cause = np.where(conflict, cause, N_CAUSES).min(axis=1)
    causes = np.bincount(lane_cause[~commit], minlength=N_CAUSES)
    return {"commit": commit, "causes": causes[:N_CAUSES],
            "claims": wr, "plain_claims": pw,
            "installs": wr & commit[:, None]}


def routed_drops(key, kind, lanes_per_shard: int, n_shards: int,
                 rec_per: int, cap: int) -> np.ndarray:
    """bool[T, K]: operations beyond their (source, owner) pair's capacity,
    counted in lane-major slot order."""
    T, K = key.shape
    live = (kind != NOP) & (key >= 0)
    owner = np.where(live, key // rec_per, n_shards)
    src = np.broadcast_to((np.arange(T) // lanes_per_shard)[:, None],
                          (T, K))
    pair = (src * (n_shards + 1) + owner).reshape(-1)
    order = np.argsort(pair, kind="stable")
    ps = pair[order]
    start = np.r_[0, np.flatnonzero(ps[1:] != ps[:-1]) + 1]
    rank = np.arange(ps.size) - np.repeat(start, np.diff(np.r_[start,
                                                               ps.size]))
    pos = np.empty_like(rank)
    pos[order] = rank
    return live & (pos.reshape(T, K) >= cap)
