"""Verdict bit-packing kernels (the distributed wave's wire shrink).

The routed wave used to return one int8 per op on the verdict and commit
exchanges.  Only 2 bits of that byte ever carry information (bit 0 =
unconditional conflict, bit 1 = read-validation — DESIGN.md section 10),
so these kernels interleave 16 ops per int32 wire word: op j's fields land
at bits ``2*(j % 16)`` and ``2*(j % 16) + 1`` of word ``j // 16`` — a 4x
byte reduction for the 16-aligned exchange caps the benchmarks run.

Both directions are 0/1-weighted matrix products on the MXU, exact because
every operand is a small integer (a 2-bit field, a byte, or a power of two
up to 2^14) and every sum stays below 2^16:

- pack: ``[D, M] fields @ [M, W]`` weights ``4^(j % 16)`` where
  ``j // 16 == w``, as two half-words (fields 0-7 and 8-15) so no sum
  exceeds 16 bits;
- unpack: each byte of the words times the ``[W, n]`` one-hot
  ``j // 16 == w`` gathers op j's word, then a per-op shift extracts its
  field.

Bit-identical to the ``ref.verdict_pack``/``ref.verdict_unpack`` oracles
(tests/test_pipeline.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import rows as rw


def _dot(a, b):
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   preferred_element_type=jnp.float32).astype(jnp.int32)


def _pack_kernel(v_ref, out_ref):
    M, W = v_ref.shape[1], out_ref.shape[1]
    j = jax.lax.broadcasted_iota(jnp.int32, (M, W), 0)
    w = jax.lax.broadcasted_iota(jnp.int32, (M, W), 1)
    e = j & 15
    own = (j >> 4) == w
    v = v_ref[...] & 3
    lo = _dot(v, jnp.where(own & (e < 8), jnp.left_shift(1, 2 * e), 0))
    hi = _dot(v, jnp.where(own & (e >= 8), jnp.left_shift(1, 2 * e - 16),
                           0))
    out_ref[...] = lo | (hi << 16)


def _unpack_kernel(words_ref, out_ref):
    W, n = words_ref.shape[1], out_ref.shape[1]
    gather = ((jax.lax.broadcasted_iota(jnp.int32, (W, n), 1) >> 4)
              == jax.lax.broadcasted_iota(jnp.int32, (W, n), 0))
    words = words_ref[...]
    word = jnp.zeros(out_ref.shape, jnp.int32)
    for b in range(4):
        byte = jax.lax.shift_right_logical(words, 8 * b) & 0xFF
        word |= _dot(byte, gather) << (8 * b)
    j = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] = jax.lax.shift_right_logical(word, 2 * (j & 15)) & 3


def _pad2(x, rows, cols):
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, cols - x.shape[1])))


def _up(n, m):
    return -(-n // m) * m


def verdict_pack_pallas(v: jax.Array, interpret: bool = False) -> jax.Array:
    """int8[D, M] verdict bytes -> int32[D, ceil(M/16)] wire words (see
    ref.verdict_pack)."""
    D, M = v.shape
    W = -(-M // 16)
    Dp = _up(D, 8)
    out = pl.pallas_call(
        _pack_kernel,
        out_shape=jax.ShapeDtypeStruct((Dp, _up(W, rw.LANES)), jnp.int32),
        interpret=interpret,
        name="verdict_pack",
    )(_pad2(v.astype(jnp.int32), Dp, _up(M, rw.LANES)))
    return out[:D, :W]


def verdict_unpack_pallas(words: jax.Array, n: int,
                          interpret: bool = False) -> jax.Array:
    """int32[D, ceil(n/16)] wire words -> int8[D, n] verdict bytes (see
    ref.verdict_unpack)."""
    D, W = words.shape
    Dp = _up(D, 8)
    out = pl.pallas_call(
        _unpack_kernel,
        out_shape=jax.ShapeDtypeStruct((Dp, _up(n, rw.LANES)), jnp.int32),
        interpret=interpret,
        name="verdict_unpack",
    )(_pad2(words, Dp, _up(W, rw.LANES)))
    return out[:D, :n].astype(jnp.int8)
