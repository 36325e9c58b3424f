"""The one-chip check builds each wave's serial order itself, from the
retry ages it counts and the configuration's ``priority`` rule: a program
whose order follows the rule is correct, one whose order does not is
not.  "Program ages" patches the program's priority word to put the
lane's retry age first (``claims.prio16(..., use_age=True)``)."""
import jax
import numpy as np
import pytest

from bench import run, spec
from bench.reference import order
from bench.tests import tiny

SEED = 3141592653


@pytest.mark.parametrize("cc,rule,program_ages,expect", [
    ("mvocc", "age", True, True),
    ("mvocc", "age", False, False),
    ("mvocc", "random", True, False),
    ("occ", "age", False, False),
])
def test_check_follows_the_configured_order(tmp_path, monkeypatch, cc, rule,
                                            program_ages, expect):
    from repro.core import claims
    if program_ages:
        prio16 = claims.prio16
        monkeypatch.setattr(
            claims, "prio16",
            lambda age, rank, use_age=False: prio16(age, rank, use_age=True))
    name = tiny.make_root(tmp_path, dict(tiny.CONFIGS[cc], priority=rule),
                          "ycsb-a")
    cell = spec.load_cell(name, tmp_path)
    out = run.run_cell(cell, SEED, 1.0, False, jax.devices()[:1])
    checks = out["checks"]
    assert out["correct"] is expect, checks
    assert (checks["lanes_wrong"]["value"] == 0) is expect, checks


def test_priority_words_by_hand():
    perm = np.array([7, 3, 1])
    # lane 1 (age 5) goes before lane 0 (age 0) though its rank is lower
    # in the permutation; an age of 70 clips to 63, as 63 itself.
    words = order.priority("age", np.array([0, 5, 70]), perm)
    assert words.tolist() == [(63 << 10) | 7, (58 << 10) | 3, 1]
    assert words[1] < words[0]
    assert order.priority("age", np.array([63]), np.array([1])).tolist() \
        == [1]
    rand = order.priority("random", np.array([0, 5, 70]), perm)
    assert rand.tolist() == [(63 << 10) | p for p in perm.tolist()]
    assert order.next_age(np.array([0, 5, 70]),
                          np.array([True, False, False])).tolist() \
        == [0, 6, 71]
    with pytest.raises(ValueError):
        order.priority("oldest", np.array([0]), np.array([0]))
