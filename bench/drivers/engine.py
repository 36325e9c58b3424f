"""One chip: the program's closed-loop wave step, scanned in chunks.

Set-up builds the engine's state in one jitted call from the seed and a
jitted ``lax.scan`` of W waves of ``core.engine.make_wave_step``.  Each
chunk carries ``EngineState`` into the next, so tables, ring and retry
buffers persist across the window; aborted transactions retry in place,
as the engine does.  Besides the state, a chunk returns per wave the
lanes' verdicts and retry ages and the engine's per-cause abort counts.
The check makes a chunk's traffic again after the window, from the key
the chunk started with.
"""
from __future__ import annotations

import dataclasses

import numpy as np

#: TPC-C's renormalized New-order / Payment / Order-status weights in the
#: order the program's generator draws them (workloads/tpcc.py MIX).
_TPCC_TYPES = ("new_order", "payment", "order_status")


def make_workload(config: dict, mix: dict):
    """The program's workload object for a configuration and a mix."""
    from repro.workloads import TPCCWorkload, YCSBWorkload
    from repro.workloads import tpcc
    gen = config["generator"]
    if mix["generator"] != gen:
        raise ValueError(f"mix {mix['name']!r} is for {mix['generator']!r}, "
                         f"the configuration runs {gen!r}")
    if gen == "tpcc":
        weights = np.array([mix["weights"][k] for k in _TPCC_TYPES], float)
        if not np.allclose(weights / weights.sum(), tpcc.MIX):
            raise ValueError("the program's TPC-C generator draws "
                             f"{dict(zip(_TPCC_TYPES, tpcc.MIX))}; mix "
                             f"{mix['name']!r} asks for {mix['weights']}")
        wl = TPCCWorkload.make(n_warehouses=config["warehouses"],
                               scale=config["scale"],
                               scan_len=mix.get("scan_len", 0))
    elif gen == "ycsb":
        wl = YCSBWorkload.make(n_keys=config["records"],
                               theta=config["zipf_theta"],
                               ops_per_txn=config["ops_per_txn"],
                               write_frac=mix["write_frac"],
                               ro_frac=mix.get("ro_frac", 0.0),
                               scan_frac=mix.get("scan_frac", 0.0),
                               scan_len=mix.get("scan_len", 8))
    else:
        raise ValueError(f"unknown generator {gen!r}")
    if wl.n_records != config["records"]:
        raise ValueError(f"{config['name']}: the generator lays out "
                         f"{wl.n_records} records, the file states "
                         f"{config['records']}")
    return wl


def engine_config(config: dict, wl, control: bool = False):
    """The EngineConfig the configuration states.  ``control`` runs the
    program's own thinned cost model (its defaults), which is not
    serializable: the reference must then find the run incorrect."""
    from repro.core import types as t
    cost = t.CostModel() if control else t.CostModel(**config["cost_model"])
    return t.EngineConfig(
        cc=t.CC_IDS[config["cc"]], lanes=config["lanes"], slots=wl.slots,
        n_records=wl.n_records, n_groups=wl.n_groups, n_cols=wl.n_cols,
        n_txn_types=wl.n_txn_types, n_rings=wl.n_rings,
        granularity={"coarse": 0, "fine": 1}[config["granularity"]],
        mv_depth=config.get("mv_depth", 0), max_extent=wl.max_extent,
        backend=config["backend"], cost=cost)


@dataclasses.dataclass
class Chunk:
    """One dispatched chunk: its input and output state and its records."""
    state_in: object
    state_out: object
    rec: dict


class EngineDriver:
    kind = "engine"

    def __init__(self, config: dict, mix: dict, seed: int,
                 control: bool = False):
        import jax
        import jax.numpy as jnp

        from repro.core import engine
        from repro.core import types as t

        self.config = config
        self.wl = make_workload(config, mix)
        self.cfg = engine_config(config, self.wl, control)
        self.waves = int(config["waves_per_chunk"])
        self.lanes = self.cfg.lanes
        cfg, wl = self.cfg, self.wl

        def init(key):
            return t.engine_state_init(cfg, key, engine._init_store(wl, cfg))

        step = engine.make_wave_step(cfg, wl)

        def body(state, _):
            new, ys = step(state, None)
            rec = dict(commit=~new.pending_live,
                       age=jnp.where(state.pending_live, state.age, 0),
                       causes=ys[2])
            return new, rec

        def chunk(state):
            return jax.lax.scan(body, state, None, length=self.waves)

        def traffic(rng, wave, tails):
            """The chunk's fresh transactions and lane permutations, made
            again from the key the chunk started with, as the wave step
            makes them: split the key in three, the second draws the
            transactions, the third permutes the lanes.  The serial
            order is the check's to build, from the permutation and the
            retry ages it counts itself."""
            def one(carry, _):
                rng, wave, tails = carry
                nxt, r_gen, r_perm = jax.random.split(rng, 3)
                fresh, tails = wl.gen(r_gen, wave, self.lanes, tails)
                perm = jax.random.permutation(r_perm, self.lanes)
                out = dict(key=fresh.op_key, group=fresh.op_group,
                           kind=fresh.op_kind, perm=perm, wave=wave)
                return (nxt, wave + 1, tails), out
            return jax.lax.scan(one, (rng, wave, tails), None,
                                length=self.waves)[1]

        self.state = jax.jit(init)(seed_key(seed))
        self._chunk = jax.jit(chunk).lower(self.state).compile()
        self._traffic = jax.jit(traffic)

    def compiled_text(self) -> str:
        return self._chunk.as_text()

    def dispatch(self) -> Chunk:
        state_in = self.state
        self.state, rec = self._chunk(state_in)
        return Chunk(state_in, self.state, rec)

    def collect(self, c: Chunk) -> dict:
        """Blocks until the chunk is done; the host's per-wave counters."""
        commit = np.asarray(c.rec["commit"])
        return {"commit": commit, "age": np.asarray(c.rec["age"]),
                "commits": int(commit.sum()),
                "attempts": int(commit.size)}

    def tables(self, state) -> dict:
        """The store's tables that the check compares."""
        s = state.store
        out = {"wts": s.wts, "claim_w": s.claim_w}
        if self.config["cc"] == "mvocc":
            out.update(claim_r=s.claim_r, mv_begin=s.mv_begin,
                       mv_head=s.mv_head)
        return out

    def check_inputs(self, c: Chunk) -> dict:
        """What the reference and the comparison need of one chunk, on
        the host: the retry buffer the chunk started from with its
        lanes' retry ages (0 where no lane retries), the fresh
        transactions and lane permutations of its waves, the program's
        verdicts, ages and causes, and its tables before and after."""
        import jax
        import jax.numpy as jnp
        s = c.state_in
        p = s.pending
        return jax.device_get({
            "traffic": self._traffic(s.rng, s.wave, s.store.ring_tails),
            "pending": {"key": p.op_key, "group": p.op_group,
                        "kind": p.op_kind, "live": s.pending_live,
                        "age": jnp.where(s.pending_live, s.age, 0)},
            "rec": c.rec,
            "before": self.tables(c.state_in),
            "after": self.tables(c.state_out)})


def seed_key(seed: int):
    """A PRNG key for any whole number up to 64 bits."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32),
                              (seed // 2**32) % 2**31)
