"""Decides ``correct``: the waves the window ran, against the reference.

For each checked chunk the reference runs the chunk's waves from the
traffic the window drove (the generator's transactions and, on one chip,
lane permutations, made again from the seed; on four chips the serial
orders) and the comparison counts where the program differs:

- ``lanes_wrong``: lanes whose commit verdict differs;
- ``ages_wrong`` (one chip): lanes and waves whose retry age, which the
  commit latency reads, is not the one the reference counts;
- ``causes_wrong``: the sum over waves of |program - reference| per abort
  cause;
- ``versions_wrong``: version-word cells whose change over the chunk is not
  the reference's count of committed writes;
- ``claims_wrong``: claim-word cells that do not hold the word of their
  last claiming wave's earliest claimant, or changed without a claim;
- ``ring_wrong`` (multi-version): records whose ring head or begin row is
  not the reference's installs applied to the chunk's starting ring.

On one chip the reference counts the lanes' retry ages itself, from the
ages the chunk started with, and builds each wave's serial order from
them and the permutation under the configuration's ``priority`` rule
(``bench/reference/order.py``; "random" where the file names none).

Every limit is 0: each is an exact comparison.  The tables are encoded as
the engine stores them: a claim word is ``(0xFFFF - (wave & 0xFFFF)) << 16
| priority``; the ring's begin row of an install in wave w is ``w + 1``.
"""
from __future__ import annotations

import numpy as np

from bench.reference import order
from bench.reference import wave as ref

LIMITS = {"lanes_wrong": 0, "ages_wrong": 0, "causes_wrong": 0,
          "versions_wrong": 0, "claims_wrong": 0, "ring_wrong": 0}


def claim_word(wave_no: int, prio):
    return ((0xFFFF - (wave_no & 0xFFFF)) << 16) | (prio & 0xFFFF)


class TableModel:
    """The reference's installs, accumulated wave by wave over a chunk."""

    def __init__(self, n_cells_shape, n_groups: int, mv: bool):
        self.G = n_groups
        self.bumps = np.zeros(n_cells_shape, np.int64)
        self.claims = {"claim_w": [], "claim_r": []}
        self.mv = mv
        self.ring_waves = []      # (wave, records, groups) per install op

    def add(self, w: int, key, group, prio, out: dict):
        T, K = key.shape
        p = np.broadcast_to(prio[:, None], (T, K))
        inst = out["installs"]
        if not self.mv:
            np.add.at(self.bumps, (key[inst], group[inst]), 1)
        else:
            self.ring_waves.append((w, key[inst], group[inst]))
        chans = [("claim_w", out["claims"])]
        if self.mv:
            chans.append(("claim_r", out["plain_claims"]))
        for name, m in chans:
            self.claims[name].append(
                (key[m].astype(np.int64) * self.G + group[m],
                 claim_word(w, p[m].astype(np.int64))))

    def compare(self, before: dict, after: dict) -> dict:
        out = {}
        delta = (after["wts"].astype(np.int64)
                 - before["wts"].astype(np.int64)) % 2**32
        out["versions_wrong"] = int(np.count_nonzero(delta != self.bumps))
        wrong = 0
        for name, parts in self.claims.items():
            if name not in after:
                continue
            exp = before[name].astype(np.int64).reshape(-1).copy()
            if parts:
                cells = np.concatenate([c for c, _ in parts])
                words = np.concatenate([wd for _, wd in parts])
                # Later waves carry smaller words, so a cell's lowest
                # word in the chunk is its last wave's earliest claimant.
                order = np.lexsort((words, cells))
                cs, ws = cells[order], words[order]
                first = np.r_[True, cs[1:] != cs[:-1]] if cs.size else cs
                exp[cs[first]] = ws[first]
            wrong += int(np.count_nonzero(
                after[name].astype(np.int64).reshape(-1) != exp))
        out["claims_wrong"] = wrong
        if self.mv:
            out["ring_wrong"] = self._ring(before, after)
        return out

    def _ring(self, before: dict, after: dict) -> int:
        begin = before["mv_begin"].copy()
        head = before["mv_head"].copy()
        D = begin.shape[1]
        for w, recs, groups in self.ring_waves:
            r = np.unique(recs)
            h_old = head[r]
            h_new = (h_old + 1) % D
            begin[r, h_new, :] = begin[r, h_old, :]
            pos = np.searchsorted(r, recs)
            begin[recs, h_new[pos], groups] = np.uint32(w + 1)
            head[r] = h_new
        bad = (after["mv_head"] != head) | (
            after["mv_begin"] != begin).reshape(len(head), -1).any(axis=1)
        return int(np.count_nonzero(bad))


def check_engine_chunk(config: dict, n_groups: int, inp: dict,
                       on_wave=None) -> dict:
    """Counts for one chunk of the one-chip engine.  The reference runs
    the chunk's waves itself: each lane runs a fresh transaction at age
    0, or retries the one the reference aborted in the wave before, one
    age older; the wave's serial order follows the configuration's
    ``priority`` rule.  ``on_wave(key, group, kind, commit)`` sees each
    wave it ran."""
    tr, rec, pend = inp["traffic"], inp["rec"], inp["pending"]
    mv = config["cc"] == "mvocc"
    rule = config.get("priority", order.DEFAULT)
    model = TableModel(inp["before"]["wts"].shape, n_groups, mv)
    key, group, kind = pend["key"], pend["group"], pend["kind"]
    retry = pend["live"].astype(bool)
    age = pend["age"].astype(np.int64)
    lanes_wrong = ages_wrong = causes_wrong = 0
    for i in range(tr["key"].shape[0]):
        w = int(tr["wave"][i])
        sel = retry[:, None]
        key = np.where(sel, key, tr["key"][i])
        group = np.where(sel, group, tr["group"][i]).astype(np.int64)
        kind = np.where(sel, kind, tr["kind"][i]).astype(np.int64)
        prio = order.priority(rule, age, tr["perm"][i])
        out = ref.wave(config["cc"], key, group, kind, prio,
                       n_groups=n_groups,
                       fine=config["granularity"] == "fine")
        lanes_wrong += int(np.count_nonzero(out["commit"]
                                            != rec["commit"][i]))
        ages_wrong += int(np.count_nonzero(age != rec["age"][i]))
        causes_wrong += int(np.abs(out["causes"]
                                   - rec["causes"][i]).sum())
        model.add(w, key, group, prio, out)
        if on_wave is not None:
            on_wave(key, group, kind, out["commit"])
        retry = ~out["commit"]
        age = order.next_age(age, out["commit"])
    res = {"lanes_wrong": lanes_wrong, "ages_wrong": ages_wrong,
           "causes_wrong": causes_wrong}
    res.update(model.compare(inp["before"], inp["after"]))
    return res


def check_sharded_chunk(config: dict, geo: dict, inp: dict) -> dict:
    """Counts for one chunk of the routed four-chip wave.  Its runner
    never retries, so every lane runs at age 0 in each wave and both
    ``priority`` rules give the permutation's order: the harness's
    traffic (``bench/gen_ycsb.py``) hands the program and the reference
    the same finished words."""
    tr, rec = inp["traffic"], inp["rec"]
    model = TableModel(inp["before"]["wts"].shape, geo["n_groups"], False)
    lanes_wrong = causes_wrong = 0
    for i in range(tr["key"].shape[0]):
        w = int(tr["wave"][i])
        key, kind = tr["key"][i], tr["kind"][i].astype(np.int64)
        group = tr["group"][i].astype(np.int64)
        drops = ref.routed_drops(key, kind, geo["lanes_per_shard"],
                                 geo["n_shards"], geo["rec_per"], geo["cap"])
        out = ref.wave(config["cc"], key, group, kind, tr["prio"][i],
                       n_groups=geo["n_groups"],
                       fine=config["granularity"] == "fine",
                       dropped=drops)
        lanes_wrong += int(np.count_nonzero(out["commit"]
                                            != rec["commit"][i]))
        causes_wrong += int(np.abs(out["causes"]
                                   - rec["causes"][i]).sum())
        model.add(w, key, group, tr["prio"][i].astype(np.int64), out)
    res = {"lanes_wrong": lanes_wrong, "causes_wrong": causes_wrong}
    res.update(model.compare(inp["before"], inp["after"]))
    return res


def merge(counts: list) -> dict:
    """Sum the counts of several checked chunks."""
    out: dict = {}
    for c in counts:
        for k, v in c.items():
            out[k] = out.get(k, 0) + v
    return out
