"""Pallas TPU kernels for the system's compute hot spots.

Layout per the repo convention: one ``<name>.py`` per kernel containing the
``pl.pallas_call`` + BlockSpec tiling, ``rows.py`` with the packed-row table
layout and block helpers they share, ``ops.py`` with the public wrappers,
and ``ref.py`` with the pure-jnp oracles every kernel is validated against
(interpret mode on CPU, shape sweeps in tests/test_kernels.py; compiled for
a described TPU v5e in tests/test_tpu_compile.py).

Kernels (the CC set implements the backend surface of core/backend.py —
DESIGN.md section 5):
  wave_commit     the probe family's fused wave: claim install + probe +
                  lane verdicts + version bumps in one launch; also the
                  fused claim install + post-install probe (claim_probe)
  occ_validate    read-set validation: block row-DMA gather + compare;
                  also the dual-granularity variant (one DMA, fine+coarse
                  verdicts) and the raw strongest-claimant probe
  iterate_validate interval (scan) validation — phantom protection
  occ_commit      version-bump block read-modify-write (aliased output)
  ts_gather       TicToc (wts, rts) gather; coarse = record max
  ts_install      monotone max timestamp install (whole-row option)
  claim_scatter   pack + min-install of claim words
  segment_count   same-cell op counts in a wave (all-pairs compare — TicToc
                  extension chains without the XLA sort)
  route_pack      per-destination exchange-buffer pack for the distributed
                  wave (counting ranks, one-hot cell select)
  verdict_pack    2-bit verdict wire pack / unpack (MXU 0/1 products)
  mv_gather       multi-version snapshot select over a record's whole ring
  mv_install      ring-slot claim + version publish: aliased-output RMW over
                  the begin ring AND head cursor (DESIGN.md section 9)
  flash_attention blocked causal attention (GQA, optional sliding window)
  rglru_scan      RG-LRU linear recurrence (recurrentgemma)
  rwkv6_scan      RWKV-6 wkv state recurrence (data-dependent decay)
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
