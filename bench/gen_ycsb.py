"""The benchmark's own YCSB operation generator, for the routed wave.

Copied from the program's ``workloads/zipf.py`` (``ZipfSampler`` and
``scramble``: Gray et al.'s inverse-CDF Zipfian as YCSB's
ScrambledZipfianGenerator uses it, ranks hash-scrambled over the key
space) and the point-operation part of ``workloads/ycsb.py`` (16 ops per
transaction, one uniform column of ten, timestamp group = column % 2,
a write with probability ``write_frac``).  The routed runner takes its
operations as arrays, so the benchmark makes them itself, on the devices,
from the seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np

READ, WRITE = 1, 2


@dataclasses.dataclass(frozen=True)
class Zipf:
    n: int
    theta: float
    zetan: float
    eta: float
    alpha: float

    @staticmethod
    def make(n: int, theta: float) -> "Zipf":
        i = np.arange(1, n + 1, dtype=np.float64)
        zetan = float(np.sum(1.0 / i ** theta))
        zeta2 = 1.0 + 0.5 ** theta
        eta = (1.0 - (2.0 / n) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
        return Zipf(n=n, theta=theta, zetan=zetan, eta=eta,
                    alpha=1.0 / (1.0 - theta))

    def sample(self, rng, shape):
        """Scrambled-Zipfian keys in [0, n)."""
        import jax
        import jax.numpy as jnp
        u = jax.random.uniform(rng, shape, jnp.float32, 1e-7, 1.0)
        uz = u * self.zetan
        tail = (self.n * jnp.power(self.eta * u - self.eta + 1.0,
                                   self.alpha)).astype(jnp.int32)
        r = jnp.where(uz < 1.0, 0,
                      jnp.where(uz < 1.0 + 0.5 ** self.theta, 1, tail))
        h = jnp.clip(r, 0, self.n - 1).astype(jnp.uint32)
        h = h ^ (h >> 16)
        h = h * jnp.uint32(0x85EBCA6B)
        h = h ^ (h >> 13)
        h = h * jnp.uint32(0xC2B2AE35)
        h = h ^ (h >> 16)
        return (h % jnp.uint32(self.n)).astype(jnp.int32)


def ops(rng, zipf: Zipf, waves: int, lanes: int, slots: int, cols: int,
        write_frac: float):
    """``waves`` waves of ``lanes`` YCSB transactions: keys, groups and
    kinds int32[waves, lanes, slots], and each wave's lane priorities
    uint32[waves, lanes] (a fresh permutation, lower first)."""
    import jax
    import jax.numpy as jnp
    rk, rc, rw, rp = jax.random.split(rng, 4)
    shape = (waves, lanes, slots)
    keys = zipf.sample(rk, shape)
    col = jax.random.randint(rc, shape, 0, cols)
    kinds = jnp.where(jax.random.uniform(rw, shape) < write_frac,
                      WRITE, READ).astype(jnp.int32)
    perm = jax.vmap(lambda r: jax.random.permutation(r, lanes))(
        jax.random.split(rp, waves)).astype(jnp.uint32)
    prio = (jnp.uint32(63) << 10) | (perm & jnp.uint32(1023))
    return keys, (col % 2).astype(jnp.int32), kinds, prio
