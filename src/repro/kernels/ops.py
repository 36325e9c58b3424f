"""Public kernel entry points.

Each op picks its execution path:
  - the Pallas kernel, compiled on a TPU and run in interpret mode on the
    CPU (the tests; tests/test_kernels.py validates kernel == reference
    across shape sweeps) — any other platform raises (``_interp``);
  - or the jnp reference in ``kernels/ref.py``.

The transaction backend (core/backend.py) always asks for the kernel
(``use_pallas=True``).  ``use_pallas=None`` callers (the language-model
scaffolding) get the kernel on a TPU and the reference elsewhere, unless
``REPRO_KERNELS`` ("pallas" | "ref") says otherwise — read per call, so
tests can toggle it after this module loads.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.core.claimword import inv_wave as _inv_wave
from repro.kernels import ref
from repro.kernels.wave_commit import claim_probe_fused_pallas
from repro.kernels.claim_scatter import claim_scatter_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.iterate_validate import iterate_validate_pallas
from repro.kernels.occ_commit import occ_commit_pallas
from repro.kernels.mv_gather import mv_gather_pallas
from repro.kernels.mv_install import mv_install_pallas
from repro.kernels.occ_validate import (claim_probe_pallas,
                                        occ_validate_dual_pallas,
                                        occ_validate_pallas)
from repro.kernels.rglru_scan import rglru_pallas
from repro.kernels.route_pack import route_pack_pallas
from repro.kernels.rwkv6_scan import rwkv6_pallas
from repro.kernels.segment_count import segment_count_pallas
from repro.kernels.ts_gather import ts_gather_pallas
from repro.kernels.ts_install import ts_install_max_pallas
from repro.kernels.verdict_pack import (verdict_pack_pallas,
                                        verdict_unpack_pallas)
from repro.kernels.wave_commit import wave_commit_pallas


def _force() -> str:
    return os.environ.get("REPRO_KERNELS", "")  # "", "pallas", "ref"


def _use_pallas(use_pallas) -> bool:
    if use_pallas is not None:
        return use_pallas
    force = _force()
    if force == "pallas":
        return True
    if force == "ref":
        return False
    return jax.default_backend() == "tpu"


def _interp() -> bool:
    """Compiled on a TPU; interpret mode only on the CPU (the tests).  Any
    other platform is an error, never a silent interpreter run."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(f"Pallas TPU kernels cannot run on {platform!r}: "
                       "only a TPU (compiled) or the CPU (interpret mode)")


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ------------------------------------------------------------------ OCC
def occ_validate(claim_w, keys, groups, myprio, check, inv_wave, fine: bool,
                 lane_block: int = 0, use_pallas=None):
    if _use_pallas(use_pallas):
        return occ_validate_pallas(claim_w, keys, groups,
                                   myprio.astype(jnp.uint32), check,
                                   inv_wave, fine, lane_block=lane_block,
                                   interpret=_interp())
    return ref.occ_validate(claim_w, keys, groups, myprio, check,
                            inv_wave, fine)


def occ_validate_dual(claim_w, keys, groups, myprio, check, inv_wave,
                      lane_block: int = 0, use_pallas=None):
    if _use_pallas(use_pallas):
        return occ_validate_dual_pallas(claim_w, keys, groups,
                                        myprio.astype(jnp.uint32), check,
                                        inv_wave, lane_block=lane_block,
                                        interpret=_interp())
    return ref.occ_validate_dual(claim_w, keys, groups, myprio, check,
                                 inv_wave)


def claim_probe(table, keys, groups, inv_wave, fine: bool,
                lane_block: int = 0, use_pallas=None):
    if _use_pallas(use_pallas):
        return claim_probe_pallas(table, keys, groups, inv_wave, fine,
                                  lane_block=lane_block,
                                  interpret=_interp())
    return ref.claim_probe(table, keys, groups, inv_wave, fine)


def occ_commit(wts, keys, groups, do, use_pallas=None):
    if _use_pallas(use_pallas):
        return occ_commit_pallas(wts, keys, groups, do, interpret=_interp())
    return ref.occ_commit(wts, keys, groups, do)


# --------------------------------------------------------- TicToc timestamps
def ts_gather(table, keys, groups, fine: bool, use_pallas=None):
    if _use_pallas(use_pallas):
        return ts_gather_pallas(table, keys, groups, fine,
                                interpret=_interp())
    return ref.ts_gather(table, keys, groups, fine)


def ts_install_max(table, keys, groups, vals, do, whole_row: bool = False,
                   use_pallas=None):
    if _use_pallas(use_pallas):
        return ts_install_max_pallas(table, keys, groups, vals, do,
                                     whole_row, interpret=_interp())
    return ref.ts_install_max(table, keys, groups, vals, do, whole_row)


# -------------------------------------------------------------- claim tables
def claim_scatter(table, keys, groups, prio, do, wave, use_pallas=None):
    if _use_pallas(use_pallas):
        return claim_scatter_pallas(table, keys, groups, prio, do,
                                    _inv_wave(wave), interpret=_interp())
    return ref.claim_scatter(table, keys, groups, prio, do, wave)


def claim_probe_fused(table, keys, groups, prio, do, wave, fine: bool,
                      lane_block: int = 0, use_pallas=None):
    if _use_pallas(use_pallas):
        # Same debug-mode precondition check as the jnp oracle path (eager
        # calls only; free under jit — see ref.check_claim_tag_monotone).
        ref.check_claim_tag_monotone(table, keys, wave)
        return claim_probe_fused_pallas(table, keys, groups, prio, do,
                                        _inv_wave(wave), fine,
                                        lane_block=lane_block,
                                        interpret=_interp())
    return ref.claim_probe_fused(table, keys, groups, prio, do, wave, fine)


def wave_commit(claim_w, claim_r, wts, keys, groups, prio, do_w, do_r,
                check_w, check_w2, check_r, extra, wave, fine: bool,
                dual: bool, bump: bool, lane_block: int = 0,
                use_pallas=None):
    """Op fifteen: the fused probe-family wave (claim install + probe +
    lane verdicts + version bumps, one launch) — see ref.wave_commit."""
    if _use_pallas(use_pallas):
        ref.check_claim_tag_monotone(claim_w, keys, wave)
        if dual:
            ref.check_claim_tag_monotone(claim_r, keys, wave)
        return wave_commit_pallas(claim_w, claim_r, wts, keys, groups,
                                  prio.astype(jnp.uint32), do_w, do_r,
                                  check_w, check_w2, check_r, extra,
                                  _inv_wave(wave), fine, dual, bump,
                                  lane_block=lane_block,
                                  interpret=_interp())
    return ref.wave_commit(claim_w, claim_r, wts, keys, groups, prio, do_w,
                           do_r, check_w, check_w2, check_r, extra, wave,
                           fine, dual, bump)


def iterate_validate(table, keys, extents, groups, myprio, check, inv_wave,
                     fine: bool, bucket_size: int, ext_cap: int,
                     lane_block: int = 0, use_pallas=None):
    """Op sixteen: interval (scan) validation — conflict bool[T, K] for
    every masked op whose ``[key, key + extent)`` interval carries a live
    same-wave claim stronger than the lane.  See ref.iterate_validate."""
    if _use_pallas(use_pallas):
        return iterate_validate_pallas(table, keys, extents, groups,
                                       myprio.astype(jnp.uint32), check,
                                       inv_wave, fine, bucket_size, ext_cap,
                                       lane_block=lane_block,
                                       interpret=_interp())
    return ref.iterate_validate(table, keys, extents, groups, myprio, check,
                                inv_wave, fine, bucket_size, ext_cap)


def route_pack(owner, vals, n_dest: int, cap: int, fills, use_pallas=None):
    if _use_pallas(use_pallas):
        return route_pack_pallas(owner, vals, n_dest, cap, fills,
                                 interpret=_interp())
    return ref.route_pack(owner, vals, n_dest, cap, fills)


def verdict_pack(v, use_pallas=None):
    if _use_pallas(use_pallas):
        return verdict_pack_pallas(v, interpret=_interp())
    return ref.verdict_pack(v)


def verdict_unpack(words, n: int, use_pallas=None):
    if _use_pallas(use_pallas):
        return verdict_unpack_pallas(words, n, interpret=_interp())
    return ref.verdict_unpack(words, n)


def segment_count(keys, groups, G: int, mask, use_pallas=None):
    if _use_pallas(use_pallas):
        return segment_count_pallas(keys, groups, G, mask,
                                    interpret=_interp())
    return ref.segment_count(keys, groups, G, mask)


# ------------------------------------------------------- multi-version store
def mv_gather(begin, keys, groups, ts, fine: bool, lane_block: int = 0,
              use_pallas=None):
    if _use_pallas(use_pallas):
        return mv_gather_pallas(begin, keys, groups, ts, fine,
                                lane_block=lane_block,
                                interpret=_interp())
    return ref.mv_gather(begin, keys, groups, ts, fine)


def mv_install(begin, head, keys, groups, do, ts, use_pallas=None):
    if _use_pallas(use_pallas):
        ref.check_mv_begin_monotone(begin, keys, do, ts)
        return mv_install_pallas(begin, head, keys, groups, do, ts,
                                 interpret=_interp())
    return ref.mv_install(begin, head, keys, groups, do, ts)


# ------------------------------------------------------- flash attention
def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128, use_pallas=None):
    """q: [B, Hq, Sq, D]; k, v: [B, Hkv, Sk, D].  See ref.attention."""
    if not _use_pallas(use_pallas):
        return ref.attention(q, k, v, causal=causal, window=window,
                             scale=scale)
    B, Hq, Sq, D = q.shape
    Sk = k.shape[2]
    bq, bk = min(block_q, max(Sq, 8)), min(block_k, max(Sk, 8))
    qp = _pad_to(q, 2, bq)
    kp = _pad_to(k, 2, bk)
    vp = _pad_to(v, 2, bk)
    out = flash_attention_pallas(qp, kp, vp, causal=causal, window=window,
                                 scale=scale, sq_valid=Sq, sk_valid=Sk,
                                 block_q=bq, block_k=bk,
                                 interpret=_interp())
    return out[:, :, :Sq, :]


# ------------------------------------------------------------- RG-LRU
def rglru(log_a, x, h0=None, chunk: int = 2048, use_pallas=None):
    """See ref.rglru.  Chunks long sequences, carrying h between chunks."""
    B, S, D = x.shape
    if h0 is None:
        h0 = jnp.zeros((B, D), jnp.float32)
    if not _use_pallas(use_pallas):
        return ref.rglru(log_a, x, h0)
    if S <= chunk:
        return rglru_pallas(log_a, x, h0, interpret=_interp())
    n = -(-S // chunk)
    la = _pad_to(log_a, 1, chunk).reshape(B, n, chunk, D)
    xx = _pad_to(x, 1, chunk).reshape(B, n, chunk, D)

    def step(h, inp):
        la_c, x_c = inp
        hs, h = rglru_pallas(la_c, x_c, h, interpret=_interp())
        return h, hs

    h_last, hs = jax.lax.scan(
        step, h0, (jnp.moveaxis(la, 1, 0), jnp.moveaxis(xx, 1, 0)))
    hs = jnp.moveaxis(hs, 0, 1).reshape(B, n * chunk, D)[:, :S]
    return hs, h_last


# ------------------------------------------------------------- RWKV-6
def rwkv6(r, k, v, w, u, s0=None, chunk: int = 2048, use_pallas=None):
    """See ref.rwkv6.  Chunks long sequences, carrying the state."""
    B, H, S, Dk = r.shape
    Dv = v.shape[-1]
    if s0 is None:
        s0 = jnp.zeros((B, H, Dk, Dv), jnp.float32)
    if not _use_pallas(use_pallas):
        return ref.rwkv6(r, k, v, w, u, s0)
    if S <= chunk:
        return rwkv6_pallas(r, k, v, w, u, s0, interpret=_interp())
    n = -(-S // chunk)

    def pad(x, const=0.0):
        p = (-S) % chunk
        if p:
            widths = [(0, 0)] * x.ndim
            widths[2] = (0, p)
            x = jnp.pad(x, widths, constant_values=const)
        return x.reshape(B, H, n, chunk, x.shape[-1])

    # Padded steps must be identity on the state: w=1 (keep), k=0 (no add).
    rr, kk, vv, ww = pad(r), pad(k), pad(v), pad(w, const=1.0)

    def step(s, inp):
        r_c, k_c, v_c, w_c = inp
        out, s = rwkv6_pallas(r_c, k_c, v_c, w_c, u, s, interpret=_interp())
        return s, out

    s_last, outs = jax.lax.scan(
        step, s0, tuple(jnp.moveaxis(t, 2, 0) for t in (rr, kk, vv, ww)))
    outs = jnp.moveaxis(outs, 0, 2).reshape(B, H, n * chunk, Dv)[:, :, :S]
    return outs, s_last
