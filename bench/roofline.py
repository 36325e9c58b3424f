"""Bytes a wave's backend operations must move, and the chip's peak.

The words follow the program's analytic model (``analysis/txn_cost.py``
``op_costs`` and ``WAVE_OPS``): a probe reads one claim word per cell, a
claim or version install reads and writes it, the ring gather reads the D
begin words of a cell, and the ring install writes a new row and reads
and writes the record's head.  Where that model counts every operation
slot of every call, this counts each distinct word of each table once per
wave, read once and written once, and only for live operations: no
implementation can move less, so the share of the roofline it gives is a
lower bound that cannot pass 100%.

The peak is copied from ``analysis/peaks.py`` (Google Cloud
documentation, "TPU v5e": 819 GB/s of HBM per chip); any other device
kind is an error.
"""
from __future__ import annotations

import numpy as np

WORD = 4
NOP, READ, WRITE, ADD = 0, 1, 2, 3
HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}


def hbm_peak(device_kind: str) -> float:
    try:
        return HBM_BYTES_PER_S[device_kind]
    except KeyError:
        raise ValueError(f"no HBM peak for device kind {device_kind!r} "
                         f"(known: {sorted(HBM_BYTES_PER_S)})") from None


def _n(key, group, mask, fine: bool, n_groups: int) -> int:
    """Distinct table words the masked operations name."""
    if not mask.any():
        return 0
    k = key[mask].astype(np.int64)
    if fine:
        return np.unique(k * n_groups + group[mask]).size
    return np.unique(k).size * n_groups


def validate_bytes(cc: str, key, group, kind, commit, *, n_groups: int,
                   fine: bool, mv_depth: int = 0) -> int:
    """Bytes one wave's concurrency-control operations must move:
    ``key``/``group``/``kind`` [T, K] and ``commit`` [T]."""
    live = (kind != NOP) & (key >= 0)
    rd = live & (kind == READ)
    wr = live & ((kind == WRITE) | (kind == ADD))
    pw = live & (kind == WRITE)
    ad = live & (kind == ADD)
    inst = wr & commit[:, None]
    n = lambda m: _n(key, group, m, fine, n_groups)  # noqa: E731
    if cc == "occ":
        # wave_commit: read every probed or claimed word, write the claimed
        # ones; its fused bump reads and writes each committed version word
        return WORD * (n(rd | wr) + n(wr) + 2 * n(inst))
    if cc == "mvocc":
        update = wr.any(axis=1)[:, None]        # read-only lanes validate
        claim_w = n(wr | (rd & update)) + n(wr)  # claims read, then written
        claim_r = n(pw | ad) + n(pw)
        gather = mv_depth * n(rd)               # every slot of a read cell
        recs = np.unique(key[inst]).size if inst.any() else 0
        install = recs * (n_groups + 2)         # new row, head read+write
        return WORD * (claim_w + claim_r + gather + install)
    raise ValueError(f"no byte count for mechanism {cc!r}")
