"""``correct`` has to come out false where the timed path is wrong: for
the control (the program's own non-serializable cost model) and for each
fault planted under the harness, at a size the CPU runs in seconds.  The
four-chip cases run in a child process with four host devices."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from bench import run, spec
from bench.reference import wave as ref
from bench.tests import tiny

SEED = 987654321


def _correct(root, name, **kw):
    cell = spec.load_cell(name, root)
    out = run.run_cell(cell, SEED, 1.0, False, jax.devices()[:1], **kw)
    return out["correct"], out["checks"]


@pytest.mark.parametrize("which", ["occ", "mvocc"])
def test_control_is_not_correct(tmp_path, which):
    name = tiny.make_root(tmp_path, tiny.CONFIGS[which], "ycsb-a")
    ok, checks = _correct(tmp_path, name, control=True)
    assert not ok
    assert checks["lanes_wrong"]["value"] > 0


def _state_unchanged(step):
    def broken(state, x):
        _, ys = step(state, x)
        return state, ys
    return broken


def _answer_altered(step):
    def broken(state, x):
        new, ys = step(state, x)
        flip = new.pending_live.at[0].set(~new.pending_live[0])
        return dataclasses.replace(new, pending_live=flip), ys
    return broken


def _age_altered(step):
    def broken(state, x):
        new, ys = step(state, x)
        return dataclasses.replace(new, age=new.age + 1), ys
    return broken


FAULTS = {
    "state_unchanged": lambda make: lambda cfg, wl, active=None:
        _state_unchanged(make(cfg, wl, active)),
    "half_batch_left_out": lambda make: lambda cfg, wl, active=None:
        make(cfg, wl, jax.numpy.arange(cfg.lanes) < cfg.lanes // 2),
    "answer_altered": lambda make: lambda cfg, wl, active=None:
        _answer_altered(make(cfg, wl, active)),
    # Every verdict stands; only the retry ages that the commit latency
    # reads are off, from the second wave of a chunk on.
    "age_altered": lambda make: lambda cfg, wl, active=None:
        _age_altered(make(cfg, wl, active)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("which", ["occ", "mvocc"])
def test_fault_under_the_harness_is_not_correct(tmp_path, monkeypatch,
                                                which, fault):
    from repro.core import engine
    monkeypatch.setattr(engine, "make_wave_step",
                        FAULTS[fault](engine.make_wave_step))
    name = tiny.make_root(tmp_path, tiny.CONFIGS[which], "ycsb-a")
    ok, checks = _correct(tmp_path, name)
    assert not ok, checks
    if fault == "age_altered":
        assert checks["ages_wrong"]["value"] > 0, checks


_SHARDED = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [{root!r}, {src!r}]
    from pathlib import Path
    import jax
    from bench import run, spec
    from bench.tests import tiny
    from repro.core import distributed as D
    out = {{}}
    root = Path({tmp!r})
    name = tiny.make_root(root, tiny.CONFIGS["sharded"], "ycsb-a")
    cell = spec.load_cell(name, root)
    def go(**kw):
        r = run.run_cell(cell, 2**32 + 5, 1.0, False, jax.devices()[:4], **kw)
        return r["correct"], r["checks"]["lanes_wrong"]["value"]
    out["sound"] = go()
    out["control"] = go(control=True)
    D._make_exchange = lambda cfg, mesh: (lambda buf: buf)
    out["exchange_left_out"] = go()
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def sharded(tmp_path_factory):
    prog = _SHARDED.format(root=str(tiny.REPO), src=str(tiny.REPO / "src"),
                           tmp=str(tmp_path_factory.mktemp("sharded")))
    proc = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                          text=True, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case,expect", [("sound", True), ("control", False),
                                         ("exchange_left_out", False)])
def test_sharded_cell(sharded, case, expect):
    ok, wrong = sharded[case]
    assert ok is expect
    assert (wrong == 0) is expect


def test_reference_occ_hand_wave():
    # lane 1 (prio 0) writes (5, 1); lane 0 (prio 1) reads it and aborts;
    # lane 2 reads (5, 0), another group of the same record: fine
    # granularity lets it commit, coarse does not.
    key = np.array([[5, 7], [5, -1], [5, 9]])
    group = np.array([[1, 0], [1, 0], [0, 0]])
    kind = np.array([[1, 2], [2, 0], [1, 1]])
    prio = np.array([1, 0, 2])
    fine = ref.wave("occ", key, group, kind, prio, n_groups=2)
    assert fine["commit"].tolist() == [False, True, True]
    assert fine["causes"][ref.READ_VAL] == 1
    coarse = ref.wave("occ", key, group, kind, prio, n_groups=2,
                      fine=False)
    assert coarse["commit"].tolist() == [False, True, False]


def test_reference_mvocc_hand_wave():
    # two writers of (4, 0): the later one loses (write-write); a read-only
    # lane reading it commits; an update lane reading it aborts.
    key = np.array([[4, -1], [4, -1], [4, -1], [4, 8]])
    group = np.zeros((4, 2), int)
    kind = np.array([[2, 0], [2, 0], [1, 0], [1, 2]])
    prio = np.array([0, 1, 2, 3])
    out = ref.wave("mvocc", key, group, kind, prio, n_groups=2)
    assert out["commit"].tolist() == [True, False, True, False]
    assert out["causes"][ref.WW] == 1 and out["causes"][ref.READ_VAL] == 1


def test_routed_drops_beyond_capacity():
    key = np.array([[0, 1, 2], [3, 100, 101]])
    kind = np.ones((2, 3), int)
    d = ref.routed_drops(key, kind, lanes_per_shard=2, n_shards=2,
                         rec_per=100, cap=2)
    assert d.tolist() == [[False, False, True], [True, False, False]]
