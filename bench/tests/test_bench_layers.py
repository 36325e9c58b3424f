"""The per-layer readers of the program's layer scopes on hand-made
traces: ``relayout_share_pct``, ``gen_share_pct`` and
``unscoped_share_pct``, each exact where its input is there and absent
(``None``) where it is not."""
import types

import pytest

from bench import spec, trace_reduce

_V = "jit(chunk)/while/body/closed_call/"

#: Two devices over a 1000 ns window.  Device 0 runs the one-chip wave's
#: phases back to back, a relayout nested in validate, and one op of the
#: harness's own (no scope); device 1 runs less of each.
ENGINE_TRACE = {
    "window": [0, 1000],
    "devices": {
        "0": [["gen.1", 0, 100], ["sched.2", 100, 50], ["pad.3", 150, 100],
              ["wave_commit.4", 250, 350], ["cost.5", 600, 100],
              ["harness.6", 700, 20], ["acct.7", 720, 40]],
        "1": [["gen.1", 0, 80], ["pad.3", 100, 60],
              ["wave_commit.4", 160, 300], ["harness.6", 500, 10],
              ["acct.7", 510, 30]],
    },
    "host": [["bench:window", 0, 1000]],
    "scopes": {
        "gen.1": _V + "repro:gen/jit(_randint)/add",
        "sched.2": _V + "repro:schedule/sort",
        "pad.3": _V + "repro:validate/repro:wave_commit/repro:relayout/pad",
        "wave_commit.4": _V + "repro:validate/repro:wave_commit/"
                              "pallas_call",
        "cost.5": _V + "repro:cost/mul",
        "harness.6": "jit(chunk)/while/body/not",
        "acct.7": _V + "repro:account/scatter-add",
    },
}
#: Busy ns per device, and what falls under no scope.
_BUSY = {"0": 760, "1": 480}
_UNSCOPED = {"0": 20, "1": 10}

#: The routed wave: an exchange between route and claim, and the
#: harness's traffic generator before it.
SHARDED_TRACE = {
    "window": [0, 400],
    "devices": {"0": [["gen_ycsb.1", 0, 40], ["route_pack.2", 40, 60],
                      ["all-to-all.3", 100, 20], ["pad.4", 120, 30],
                      ["wave_commit.5", 150, 100], ["commit.6", 250, 20],
                      ["occ_commit.7", 270, 50], ["stats.8", 320, 10]]},
    "host": [["bench:window", 0, 400]],
    "scopes": {
        "gen_ycsb.1": "jit(chunk)/jit(ops)/add",
        "route_pack.2": "jit(chunk)/shard_map/while/body/closed_call/"
                        "repro:route/route_pack",
        "all-to-all.3": "jit(chunk)/shard_map/while/body/closed_call/"
                        "repro:exchange/all_to_all",
        "pad.4": "jit(chunk)/shard_map/while/body/closed_call/repro:claim/"
                 "repro:relayout/pad",
        "wave_commit.5": "jit(chunk)/shard_map/while/body/closed_call/"
                         "repro:claim/pallas_call",
        "commit.6": "jit(chunk)/shard_map/while/body/closed_call/"
                    "repro:commit/gather",
        "occ_commit.7": "jit(chunk)/shard_map/while/body/closed_call/"
                        "repro:install/pallas_call",
        "stats.8": "jit(chunk)/shard_map/while/body/closed_call/"
                   "repro:account/reduce_sum",
    },
}


def _ctx(trace, driver):
    cell = types.SimpleNamespace(config={"driver": driver})
    return {"cell": cell, "trace": trace_reduce.reduce(trace)}


def _read(name, ctx):
    return spec.metric_reader(name)(ctx)


@pytest.mark.parametrize("trace,driver,busy_ns,unscoped_ns", [
    (ENGINE_TRACE, "engine", sum(_BUSY.values()), sum(_UNSCOPED.values())),
    (SHARDED_TRACE, "sharded", 330, 40),
], ids=["engine", "sharded"])
def test_unscoped_time_is_exact(trace, driver, busy_ns, unscoped_ns):
    """Busy time less the outermost scopes' times is exactly the time of
    the ops under no scope (the harness's), averaged over the chips."""
    ctx = _ctx(trace, driver)
    n = len(trace["devices"])
    assert ctx["trace"]["busy_s"] == pytest.approx(busy_ns / n * 1e-9)
    assert _read("unscoped_share_pct", ctx) == pytest.approx(
        100.0 * unscoped_ns / busy_ns)


_PRESENT = {
    # (pad.3 on both devices) over busy, averaged over the two devices.
    "relayout_share_pct": 100.0 * (100 + 60) / (760 + 480),
    "gen_share_pct": 100.0 * (100 + 80) / (760 + 480),
    "unscoped_share_pct": 100.0 * (20 + 10) / (760 + 480),
}


@pytest.mark.parametrize("name", sorted(_PRESENT))
def test_layer_readers_read_their_scope(name):
    assert _read(name, _ctx(ENGINE_TRACE, "engine")) == pytest.approx(
        _PRESENT[name])


def _without_scopes(trace):
    return dict(trace, scopes={})


@pytest.mark.parametrize("name", sorted(_PRESENT))
def test_layer_readers_return_none_without_input(name):
    """No trace, or a trace with no busy time, reads nothing; a trace
    whose program has no such scope (the parent program has no
    ``repro:relayout`` or ``repro:gen``) reads nothing for those."""
    assert _read(name, {"cell": None}) is None
    idle = dict(ENGINE_TRACE, devices={"0": []})
    assert _read(name, _ctx(idle, "engine")) is None
    bare = _read(name, _ctx(_without_scopes(ENGINE_TRACE), "engine"))
    if name == "unscoped_share_pct":
        assert bare == pytest.approx(100.0)
    else:
        assert bare is None
