"""Every transaction kernel compiles for a TPU v5e at real widths.

No chip is needed: the TPU compiler is installed, and it compiles for a
described ``v5e:2x2`` topology.  Interpret mode cannot see what only the
chip's compiler refuses — slices not aligned to the (8, 128) tiling, too
many DMA semaphores, tables padded past device memory — so each kernel of
the engine's main path is compiled here, one device of the described
topology, at 1024 lanes x 16 slots (128 x 64 for TPC-C's slot width)
against a 10M-record table, and the program must hold a Mosaic kernel
(``tpu_custom_call``).  The sharded OCC owner's ``wave_commit`` is
compiled at its own shape too: 4 source chips x a 2048-op route buffer
against one 2.5M-record shard of the claim table.  The topology is
described inside a fixture, never at import, so every test worker
collects the same tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.claim_scatter import claim_scatter_pallas
from repro.kernels.iterate_validate import iterate_validate_pallas
from repro.kernels.mv_gather import mv_gather_pallas
from repro.kernels.mv_install import mv_install_pallas
from repro.kernels.occ_commit import occ_commit_pallas
from repro.kernels.occ_validate import (occ_validate_dual_pallas,
                                        occ_validate_pallas)
from repro.kernels.route_pack import route_pack_pallas
from repro.kernels.segment_count import segment_count_pallas
from repro.kernels.ts_gather import ts_gather_pallas
from repro.kernels.ts_install import ts_install_max_pallas
from repro.kernels.verdict_pack import (verdict_pack_pallas,
                                        verdict_unpack_pallas)
from repro.kernels.wave_commit import (claim_probe_fused_pallas,
                                       wave_commit_pallas)

N, G, D = 10_000_000, 2, 4
U32, I32, B = jnp.uint32, jnp.int32, jnp.bool_


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _wave(fine, dual):
    def f(cw, cr, wts, keys, groups, prio, dow, dor, cw_m, cw2, cr_m, ex,
          ivw):
        return wave_commit_pallas(cw, cr if dual else None, wts, keys,
                                  groups, prio, dow, dor if dual else None,
                                  cw_m, cw2, cr_m if dual else None, ex,
                                  ivw, fine=fine, dual=dual, bump=True)
    return f


def _cases(T, K):
    """name -> (kernel, argument shapes) at T lanes x K slots."""
    tbl, ring = ((N, G), U32), ((N, D, G), U32)
    ops = lambda dt: ((T, K), dt)
    sc = ((), U32)
    wave_args = [tbl, tbl, tbl, ops(I32), ops(I32), ops(U32), ops(B),
                 ops(B), ops(B), ops(B), ops(B), ops(B), sc]
    M = T * K
    return {
        "wave_commit_fine": (_wave(True, False), wave_args),
        "wave_commit_coarse_dual": (_wave(False, True), wave_args),
        "claim_probe": (
            lambda *a: claim_probe_fused_pallas(*a, fine=True),
            [tbl, ops(I32), ops(I32), ops(U32), ops(B), sc]),
        "validate": (
            lambda *a: occ_validate_pallas(*a, fine=False),
            [tbl, ops(I32), ops(I32), ops(U32), ops(B), sc]),
        "validate_dual": (
            occ_validate_dual_pallas,
            [tbl, ops(I32), ops(I32), ops(U32), ops(B), sc]),
        "iterate_validate_fine": (
            lambda *a: iterate_validate_pallas(*a, fine=True,
                                               bucket_size=8, ext_cap=8),
            [tbl, ops(I32), ops(I32), ops(I32), ops(U32), ops(B), sc]),
        "iterate_validate_coarse": (
            lambda *a: iterate_validate_pallas(*a, fine=False,
                                               bucket_size=8, ext_cap=8),
            [tbl, ops(I32), ops(I32), ops(I32), ops(U32), ops(B), sc]),
        "segment_count": (
            lambda k, g, m: segment_count_pallas(k, g, G, m),
            [ops(I32), ops(I32), ops(B)]),
        "ts_gather": (
            lambda *a: ts_gather_pallas(*a, fine=False),
            [tbl, ops(I32), ops(I32)]),
        "ts_install_max": (
            lambda *a: ts_install_max_pallas(*a, whole_row=True),
            [tbl, ops(I32), ops(I32), ops(U32), ops(B)]),
        "claim_scatter": (
            claim_scatter_pallas,
            [tbl, ops(I32), ops(I32), ops(U32), ops(B), sc]),
        "commit_install": (occ_commit_pallas,
                           [tbl, ops(I32), ops(I32), ops(B)]),
        "mv_gather": (
            lambda *a: mv_gather_pallas(*a, fine=True),
            [ring, ops(I32), ops(I32), sc]),
        "mv_install": (
            mv_install_pallas,
            [ring, ((N,), I32), ops(I32), ops(I32), ops(B), sc]),
        "route_pack": (
            lambda o, v: route_pack_pallas(o, v, 4, M // 4,
                                           (0, -1, 0, 0, 0)),
            [((M,), I32), ((5, M), I32)]),
        "verdict_pack": (verdict_pack_pallas, [((4, M // 4), jnp.int8)]),
        "verdict_unpack": (
            lambda w: verdict_unpack_pallas(w, M // 4),
            [((4, M // 64), I32)]),
    }


WIDE = _cases(1024, 16)
TPCC = ("wave_commit_fine", "wave_commit_coarse_dual", "ts_gather",
        "ts_install_max", "segment_count", "iterate_validate_fine")


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # The table relayouts stay compact: nothing near a padded copy.
    assert mem.temp_size_in_bytes < 2 << 30
    return compiled


@pytest.mark.parametrize("name", sorted(WIDE))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = WIDE[name]
    _compile(fn, shapes, one_chip)


@pytest.mark.parametrize("name", TPCC)
def test_kernel_compiles_for_v5e_tpcc_slots(name, one_chip,
                                           no_persistent_cache):
    fn, shapes = _cases(128, 64)[name]
    _compile(fn, shapes, one_chip)


def test_wave_commit_compiles_for_v5e_owner(one_chip, no_persistent_cache):
    """The owner's call: T = 4 sources, K = 2048 route slots (one block
    of 2048 ops per source), one fine claim table of G = 2, no bump —
    the shape whose scoped VMEM overflowed at a route capacity of 4096."""
    T, K = 4, 2048
    ops = lambda dt: ((T, K), dt)

    def f(cw, keys, groups, prio, dow, cw_m):
        return wave_commit_pallas(cw, None, None, keys, groups, prio, dow,
                                  None, cw_m, None, None, None,
                                  jnp.uint32(1), fine=True, dual=False,
                                  bump=False)
    _compile(f, [((N // 4, G), U32), ops(I32), ops(I32), ops(U32), ops(B),
                 ops(B)], one_chip)
