"""Layer scopes on the compiled wave: every op that the scanned wave step
emits into the loop body of a compiled chunk carries a ``repro:<layer>``
named scope, its outermost scope is one that the benchmark's
``unscoped_share_pct`` reader sums, and the row conversions at the kernel
boundary carry ``repro:relayout``.  The benchmark reads device time per
layer from these scopes (bench/trace_reduce.py), so an op outside every
scope is device time that no layer metric can name.

The routed chunk runs in a subprocess with two host devices (the main
test process has one); the same subprocess shows that a small route
capacity aborts lanes under ``CAUSE_CAPACITY``."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from bench.metrics import unscoped_share_pct
from repro.core import engine
from repro.core import types as t
from repro.kernels import rows
from repro.workloads import TPCCWorkload

_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([a-z][a-z0-9\-]*)\(")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_SCOPE = re.compile(r"repro:[\w\-]+")


def _computations(hlo: str) -> dict:
    """Computation name -> its instruction lines, from HLO text."""
    out, cur = {}, None
    for line in hlo.splitlines():
        m = _HEADER.match(line)
        if m and not line.startswith(" "):
            cur = out.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and line.strip():
            cur.append(line)
    return out


def _op_name(line: str) -> str:
    m = re.search(r'op_name="([^"]*)"', line)
    return m.group(1) if m else ""


def step_ops(hlo: str) -> list:
    """(instruction, op_name) of every instruction in the body of the
    compiled program's outermost loop that JAX emitted from inside the
    scanned step: its op_name runs at least two levels below
    ``while/body``.  The loop's own counter, the stacking of its outputs
    and XLA's copies sit at most one level below, or carry none."""
    comps = _computations(hlo)
    bodies = []
    for lines in comps.values():
        for line in lines:
            m = _INSTR.match(line)
            if m and m.group(2) == "while" and \
                    "/while/body/" not in _op_name(line):
                bodies.append(re.search(r"body=%?([\w.\-]+)", line).group(1))
    assert len(bodies) == 1, bodies
    out = []
    for line in comps[bodies[0]]:
        name, on = _INSTR.match(line).group(1), _op_name(line)
        tail = on.split("/while/body/", 1)[1] if "/while/body/" in on else ""
        if "/" in tail:
            out.append((name, on))
    return out


def outermost(op_name: str) -> str:
    found = _SCOPE.findall(op_name)
    return found[0] if found else ""


def _check(ops, outer, must):
    assert len(ops) > 20, ops
    unscoped = [(n, on) for n, on in ops if not outermost(on)]
    assert not unscoped, unscoped
    seen = {outermost(on) for _, on in ops}
    assert seen <= set(outer), seen - set(outer)
    assert set(must) <= seen, set(must) - seen


# ------------------------------------------------------------ one chip
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("cc,gran", [("occ", 1), ("occ", 0), ("tictoc", 1)])
def test_wave_chunk_ops_carry_a_layer_scope(backend, cc, gran):
    wl = TPCCWorkload.make(n_warehouses=2, scale=0.01)
    cfg = t.EngineConfig(
        cc=t.CC_IDS[cc], lanes=16, slots=wl.slots, n_records=wl.n_records,
        n_groups=wl.n_groups, n_cols=wl.n_cols, n_txn_types=wl.n_txn_types,
        n_rings=wl.n_rings, granularity=gran, backend=backend,
        cost=t.CostModel(opt_overlap=1.0, phase_overlap=1.0))
    step = engine.make_wave_step(cfg, wl)
    state = t.engine_state_init(cfg, jax.random.PRNGKey(0),
                                engine._init_store(wl, cfg))

    def chunk(s):
        return jax.lax.scan(step, s, None, length=2)

    ops = step_ops(jax.jit(chunk).lower(state).compile().as_text())
    _check(ops, unscoped_share_pct.OUTER["engine"],
           ["repro:gen", "repro:schedule", "repro:validate", "repro:cost",
            "repro:account"])
    if backend == "pallas":
        # The kernels' row conversions nest inside the wave's phases.
        nested = {outermost(on) for _, on in ops if "repro:relayout" in on}
        assert "repro:validate" in nested, nested


# ------------------------------------------------------- row relayouts
_TABLE = jax.ShapeDtypeStruct((3000, 2), jnp.uint32)
_OPS = jax.ShapeDtypeStruct((5, 16), jnp.int32)
_CONVERSIONS = {
    "pack": (rows.pack, _TABLE),
    "unpack": (lambda x: rows.unpack(x, _TABLE),
               jax.eval_shape(rows.pack, _TABLE)),
    "op_rows": (lambda x: rows.op_rows(x, 8), _OPS),
    "from_rows": (lambda y: rows.from_rows(y, 5, 16),
                  jax.ShapeDtypeStruct((1, 8 * 16), jnp.int32)),
}


@pytest.mark.parametrize("name", sorted(_CONVERSIONS))
def test_row_conversions_carry_the_relayout_scope(name):
    fn, arg = _CONVERSIONS[name]
    hlo = jax.jit(fn).lower(arg).compile().as_text()
    entry = hlo.split("\nENTRY ", 1)[1].split("\n}", 1)[0]
    ops = [(m.group(2), _op_name(line)) for line in entry.splitlines()
           if (m := _INSTR.match(line)) and m.group(2) != "parameter"]
    assert ops
    assert all("repro:relayout" in on for _, on in ops), ops


# ---------------------------------------------------------- routed chunk
_ROUTED = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [{src!r}, {root!r}]
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.core import distributed as D
    from repro.core import types as t
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    W, T, K, N = 3, 16, 16, 4000
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.integers(0, N, (W, 2 * T, K), dtype=np.int32))
    groups = jnp.asarray(rng.integers(0, 2, (W, 2 * T, K), dtype=np.int32))
    kinds = jnp.asarray(rng.integers(1, 3, (W, 2 * T, K), dtype=np.int32))
    prio = jnp.asarray(np.stack([rng.permutation(2 * T)
                                 for _ in range(W)]).astype(np.uint32))
    out = {{}}
    for backend, depth, cap in {cases!r}:
        cfg = D.DistConfig(n_records=N, n_groups=2, lanes_per_shard=T,
                           slots=K, backend=backend, cc="occ",
                           granularity=1, pipeline_depth=depth,
                           route_cap=cap)
        tables = D.init_tables(cfg, mesh)
        args = (keys, groups, kinds, prio, tables, np.uint32(0))
        comp = jax.jit(D.make_run_fn(cfg, mesh, W)).lower(*args).compile()
        stats = np.asarray(comp(*args)[2]).reshape(W, 2, -1)
        out[f"{{backend}}-{{depth}}-{{cap}}"] = {{
            "hlo": comp.as_text(),
            "capacity": int(stats[:, :, D.STAT_CAUSES][..., t.CAUSE_CAPACITY]
                            .sum()),
            "dropped_lanes": int(stats[:, :, 2].sum())}}
    print(json.dumps(out))
""")
_CASES = [("jnp", 1, 0), ("pallas", 1, 0), ("jnp", 2, 0), ("pallas", 2, 0),
          ("jnp", 1, 16)]


@pytest.fixture(scope="module")
def routed():
    root = os.path.join(os.path.dirname(__file__), "..")
    prog = _ROUTED.format(src=os.path.join(root, "src"), root=root,
                          cases=_CASES)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("backend,depth", [("jnp", 1), ("pallas", 1),
                                           ("jnp", 2), ("pallas", 2)])
def test_routed_chunk_ops_carry_a_layer_scope(routed, backend, depth):
    ops = step_ops(routed[f"{backend}-{depth}-0"]["hlo"])
    _check(ops, unscoped_share_pct.OUTER["sharded"],
           ["repro:route", "repro:exchange", "repro:claim", "repro:commit",
            "repro:install", "repro:account"])
    # Every collective of the wave falls under the exchange scope.
    coll = [on for n, on in ops if "all-to-all" in n.replace("_", "-")]
    assert coll and all("repro:exchange" in on for on in coll), coll


def test_small_route_capacity_aborts_lanes_for_capacity(routed):
    """The counter a capacity metric reads: with 16 slots per (source,
    owner) pair and wave, ops overflow and their lanes abort under
    ``CAUSE_CAPACITY``, one count per dropped lane; at the default
    capacity none do."""
    small, default = routed["jnp-1-16"], routed["jnp-1-0"]
    assert small["capacity"] > 0
    assert small["capacity"] == small["dropped_lanes"]
    assert default["capacity"] == 0
