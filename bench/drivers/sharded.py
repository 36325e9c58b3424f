"""Four chips: the program's routed wave (``core.distributed``) over a
mesh of every chip, scanned in chunks by ``make_run_fn``.

The tables are built in place from ``init_tables`` and carried from chunk
to chunk.  Each chunk makes its own W waves of operations on the devices
from the seed (``bench/gen_ycsb.py``) and runs them; the check makes them
again after the window.  The routed runner does not retry, so an aborted
transaction is final.  ``control`` hides
40% of the reads from the program while the check still holds it to
them, which breaks serializability: the check must find it incorrect.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import gen_ycsb
from bench.drivers.engine import seed_key


@dataclasses.dataclass
class Chunk:
    tables_in: object
    tables_out: object
    rec: dict


class ShardedDriver:
    kind = "sharded"

    def __init__(self, config: dict, mix: dict, seed: int, devices,
                 control: bool = False):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from repro.core import distributed as D

        if config["generator"] != "ycsb" or mix["generator"] != "ycsb":
            raise ValueError("the routed wave runs YCSB point operations")
        if mix.get("ro_frac", 0) or mix.get("scan_frac", 0):
            raise ValueError("the routed cell's generator makes point "
                             "read/write transactions only")
        self.config = config
        self.mesh = Mesh(np.array(devices), ("data",))
        ns = len(devices)
        self.cfg = D.DistConfig(
            n_records=config["records"], n_groups=2,
            lanes_per_shard=config["lanes_per_chip"],
            slots=config["ops_per_txn"], backend=config["backend"],
            cc=config["cc"], route_cap=config.get("route_cap", 0),
            granularity={"coarse": 0, "fine": 1}[config["granularity"]])
        self.waves = int(config["waves_per_chunk"])
        lanes = ns * self.cfg.lanes_per_shard
        self.geo = {"n_shards": ns, "n_groups": 2,
                    "lanes_per_shard": self.cfg.lanes_per_shard,
                    "rec_per": -(-config["records"] // ns),
                    "cap": self.cfg.cap(ns)}
        zipf = gen_ycsb.Zipf.make(config["records"], config["zipf_theta"])
        run = D.make_run_fn(self.cfg, self.mesh, self.waves)
        stack = NamedSharding(self.mesh, P(None, "data"))
        W, K = self.waves, self.cfg.slots
        write_frac = mix["write_frac"]

        def traffic(key, index):
            keys, groups, kinds, prio = gen_ycsb.ops(
                jax.random.fold_in(key, index), zipf, W, lanes, K,
                config["columns"], write_frac)
            return {"key": keys, "group": groups, "kind": kinds,
                    "prio": prio,
                    "wave": index.astype(jnp.uint32) * jnp.uint32(W)
                    + jnp.arange(W, dtype=jnp.uint32)}

        def chunk(tables, key, index):
            tr = traffic(key, index)
            keys, groups, kinds, prio = (
                jax.lax.with_sharding_constraint(tr[k], stack)
                for k in ("key", "group", "kind", "prio"))
            if control:
                # The control: 40% of reads skip validation, as the local
                # engine's default cost model thins them; not serializable.
                u = jax.random.uniform(jax.random.fold_in(key, index + W),
                                       kinds.shape)
                kinds = jnp.where((kinds == gen_ycsb.READ) & (u < 0.4),
                                  0, kinds)
            commit, tables, stats = run(keys, groups, kinds, prio, tables,
                                        tr["wave"][0])
            causes = stats.reshape(W, ns, -1)[:, :, D.STAT_CAUSES]
            return tables, {"commit": commit, "causes": causes.sum(axis=1)}

        self.key = seed_key(seed)
        self.index = 0
        self.tables = D.init_tables(self.cfg, self.mesh)
        self._chunk = jax.jit(chunk).lower(
            self.tables, self.key, np.uint32(0)).compile()
        self._traffic = jax.jit(traffic)

    def compiled_text(self) -> str:
        return self._chunk.as_text()

    def dispatch(self) -> Chunk:
        tables_in = self.tables
        self.tables, rec = self._chunk(tables_in, self.key,
                                       np.uint32(self.index))
        rec["index"] = self.index
        self.index += 1
        return Chunk(tables_in, self.tables, rec)

    def collect(self, c: Chunk) -> dict:
        commit = np.asarray(c.rec["commit"])
        return {"commit": commit, "commits": int(commit.sum()),
                "attempts": int(commit.size)}

    def check_inputs(self, c: Chunk) -> dict:
        import jax
        names = ("wts", "claim_w")
        return jax.device_get({
            "traffic": self._traffic(self.key, np.uint32(c.rec["index"])),
            "rec": {k: c.rec[k] for k in ("commit", "causes")},
            "before": dict(zip(names, c.tables_in)),
            "after": dict(zip(names, c.tables_out))})
