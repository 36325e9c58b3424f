"""Roofline analysis: HLO collective parser against known programs, and the
analytic model's structural properties."""
import subprocess
import sys
import textwrap

import pytest

from repro.analysis.roofline import (_shape_bytes, _split_computations,
                                     analytic_cell, collective_bytes_from_hlo)
from repro.configs.base import SHAPES


def test_shape_bytes():
    assert _shape_bytes("bf16[8,128]{1,0}") == 8 * 128 * 2
    assert _shape_bytes("f32[16]") == 64
    assert _shape_bytes("(f32[2,2], s32[])") == 16 + 4
    assert _shape_bytes("pred[]") == 1        # scalar


def test_collective_parser_counts_loop_trips():
    """Compile a scan whose body does a per-iteration psum on 8 host devices
    (subprocess: device count must be set before jax init).  The result
    is asked for column-sharded, the layout the partitioner keeps the scan
    carry in: a replicated result would add one real all-gather after the
    loop, which the parser (rightly) counts too."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np, sys
        sys.path.insert(0, "src")
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.analysis.roofline import collective_bytes_from_hlo

        mesh = Mesh(np.array(jax.devices()), ("model",))
        def f(x, w):
            def body(c, _):
                def mm(cc, ww):
                    return jax.lax.psum(cc @ ww, "model")
                y = jax.shard_map(mm, mesh=mesh,
                                  in_specs=(P(None, "model"),
                                            P("model", None)),
                                  out_specs=P(), check_vma=False)(c, w)
                return y, None
            return jax.lax.scan(body, x, None, length=5)[0]
        x = jax.ShapeDtypeStruct((128, 512), jnp.float32,
                                 sharding=NamedSharding(mesh, P()))
        w = jax.ShapeDtypeStruct((512, 512), jnp.float32,
                                 sharding=NamedSharding(mesh, P("model",
                                                                None)))
        out = NamedSharding(mesh, P(None, "model"))
        hlo = jax.jit(f, out_shardings=out).lower(x, w).compile().as_text()
        b = collective_bytes_from_hlo(hlo)
        assert b == 5 * 128 * 512 * 4, b
        print("PARSER_OK", b)
    """)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, cwd=".", timeout=600)
    assert "PARSER_OK" in r.stdout, r.stdout + r.stderr


def test_analytic_terms_structure():
    from repro import configs
    qwen = configs.get("qwen3-32b")
    train = analytic_cell(qwen, SHAPES["train_4k"], 256, tp=16,
                          coll_bytes=1e9)
    assert train.compute_s > 0 and train.memory_s > 0
    assert train.bottleneck in ("compute", "memory", "collective")
    assert 0 < train.usefulness <= 1.0
    # train on a dense arch at 4k seq: compute must dominate memory
    assert train.compute_s > train.memory_s

    dec = analytic_cell(qwen, SHAPES["decode_32k"], 256, tp=16)
    # single-token decode: memory-bound (weights + KV cache stream)
    assert dec.bottleneck == "memory"
    assert dec.memory_s > dec.compute_s


def test_moe_capacity_inflation_shows_in_usefulness():
    import dataclasses
    from repro import configs
    l4 = configs.get("llama4-maverick-400b-a17b")
    base = analytic_cell(l4, SHAPES["train_4k"], 512, tp=16)
    wide = analytic_cell(l4, SHAPES["train_4k"], 512, tp=16,
                         overrides={"cap_factor": 2.5})
    assert wide.flops > base.flops
    assert wide.usefulness < base.usefulness


def test_remat_override_moves_compute_term():
    from repro import configs
    q = configs.get("qwen2-7b")
    a = analytic_cell(q, SHAPES["train_4k"], 256, tp=16)
    b = analytic_cell(q, SHAPES["train_4k"], 256, tp=16,
                      overrides={"remat": False})
    assert a.compute_s > b.compute_s          # remat re-runs the forward
