"""Per-kernel validation: Pallas (interpret mode on CPU) vs the pure-jnp
oracle in kernels/ref.py, across shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


# ------------------------------------------------------------- OCC kernels
@pytest.mark.parametrize("T,K,N,G", [(4, 8, 64, 2), (8, 16, 512, 2),
                                     (3, 5, 33, 1)])
@pytest.mark.parametrize("fine", [True, False])
def test_occ_validate(T, K, N, G, fine):
    claim = jnp.asarray(RNG.integers(0, 2 ** 32, (N, G), dtype=np.uint32))
    keys = jnp.asarray(RNG.integers(-1, N, (T, K), dtype=np.int32))
    groups = jnp.asarray(RNG.integers(0, G, (T, K), dtype=np.int32))
    prio = jnp.asarray(RNG.integers(0, 2 ** 16, (T, K), dtype=np.uint32))
    check = jnp.asarray(RNG.random((T, K)) < 0.7) & (keys >= 0)
    ivw = jnp.uint32(0xFF00)
    a = ops.occ_validate(claim, keys, groups, prio, check, ivw, fine,
                         use_pallas=True)
    b = ref.occ_validate(claim, keys, groups, prio, check, ivw, fine)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("T,K,N,G", [(4, 8, 64, 2), (6, 3, 17, 1)])
def test_occ_commit_with_duplicates(T, K, N, G):
    wts = jnp.asarray(RNG.integers(0, 9, (N, G), dtype=np.uint32))
    keys = jnp.asarray(RNG.integers(-1, N // 2, (T, K), dtype=np.int32))
    groups = jnp.asarray(RNG.integers(0, G, (T, K), dtype=np.int32))
    do = jnp.asarray(RNG.random((T, K)) < 0.6)
    a = ops.occ_commit(wts, keys, groups, do, use_pallas=True)
    b = ref.occ_commit(wts, keys, groups, do)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------- backend-surface kernels (new)
@pytest.mark.parametrize("T,K,N,G", [(4, 8, 64, 2), (3, 5, 33, 1)])
def test_occ_validate_dual(T, K, N, G):
    """One row DMA, two verdicts: the dual kernel must equal BOTH
    single-granularity oracles."""
    claim = jnp.asarray(RNG.integers(0, 2 ** 32, (N, G), dtype=np.uint32))
    keys = jnp.asarray(RNG.integers(-1, N, (T, K), dtype=np.int32))
    groups = jnp.asarray(RNG.integers(0, G, (T, K), dtype=np.int32))
    prio = jnp.asarray(RNG.integers(0, 2 ** 16, (T, K), dtype=np.uint32))
    check = jnp.asarray(RNG.random((T, K)) < 0.7) & (keys >= 0)
    ivw = jnp.uint32(0xFF00)
    af, ac = ops.occ_validate_dual(claim, keys, groups, prio, check, ivw,
                                   use_pallas=True)
    np.testing.assert_array_equal(
        np.asarray(af),
        np.asarray(ref.occ_validate(claim, keys, groups, prio, check, ivw,
                                    fine=True)))
    np.testing.assert_array_equal(
        np.asarray(ac),
        np.asarray(ref.occ_validate(claim, keys, groups, prio, check, ivw,
                                    fine=False)))


@pytest.mark.parametrize("T,K,N,G", [(4, 8, 64, 2), (3, 5, 17, 1)])
@pytest.mark.parametrize("fine", [True, False])
def test_claim_probe(T, K, N, G, fine):
    table = jnp.asarray(RNG.integers(0, 2 ** 32, (N, G), dtype=np.uint32))
    keys = jnp.asarray(RNG.integers(-1, N, (T, K), dtype=np.int32))
    groups = jnp.asarray(RNG.integers(0, G, (T, K), dtype=np.int32))
    ivw = jnp.uint32(0xFFF0)
    a = ops.claim_probe(table, keys, groups, ivw, fine, use_pallas=True)
    b = ref.claim_probe(table, keys, groups, ivw, fine)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("T,K,N,G", [(4, 8, 64, 2), (6, 3, 17, 1)])
@pytest.mark.parametrize("fine", [True, False])
def test_ts_gather(T, K, N, G, fine):
    """TicToc (wts, rts) observation: fine = own cell, coarse = row max."""
    table = jnp.asarray(RNG.integers(0, 1000, (N, G), dtype=np.uint32))
    keys = jnp.asarray(RNG.integers(-1, N, (T, K), dtype=np.int32))
    groups = jnp.asarray(RNG.integers(0, G, (T, K), dtype=np.int32))
    a = ops.ts_gather(table, keys, groups, fine, use_pallas=True)
    b = ref.ts_gather(table, keys, groups, fine)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("T,K,N,G", [(4, 8, 64, 2), (6, 3, 17, 1)])
@pytest.mark.parametrize("whole_row", [False, True])
def test_ts_install_max_with_duplicates(T, K, N, G, whole_row):
    """Scatter-max install; keys drawn from N//2 records force duplicate
    (record, group) cells within the wave."""
    table = jnp.asarray(RNG.integers(0, 500, (N, G), dtype=np.uint32))
    keys = jnp.asarray(RNG.integers(-1, N // 2, (T, K), dtype=np.int32))
    groups = jnp.asarray(RNG.integers(0, G, (T, K), dtype=np.int32))
    vals = jnp.asarray(RNG.integers(0, 1000, (T, K), dtype=np.uint32))
    do = jnp.asarray(RNG.random((T, K)) < 0.6)
    a = ops.ts_install_max(table, keys, groups, vals, do, whole_row,
                           use_pallas=True)
    b = ref.ts_install_max(table, keys, groups, vals, do, whole_row)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("T,K,N,G", [(4, 8, 64, 2), (6, 3, 17, 1)])
def test_claim_scatter_with_duplicates(T, K, N, G):
    """Fused pack+scatter-min; duplicate cells must resolve to the strongest
    claimant exactly like the XLA scatter-min."""
    table = jnp.asarray(RNG.integers(0, 2 ** 32, (N, G), dtype=np.uint32))
    keys = jnp.asarray(RNG.integers(-1, N // 2, (T, K), dtype=np.int32))
    groups = jnp.asarray(RNG.integers(0, G, (T, K), dtype=np.int32))
    prio = jnp.asarray(RNG.integers(0, 2 ** 16, (T, K), dtype=np.uint32))
    do = jnp.asarray(RNG.random((T, K)) < 0.6)
    wave = jnp.uint32(5)
    a = ops.claim_scatter(table, keys, groups, prio, do, wave,
                          use_pallas=True)
    b = ref.claim_scatter(table, keys, groups, prio, do, wave)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("T,K,N,G", [(4, 8, 64, 2), (6, 3, 17, 1),
                                     (8, 16, 16, 2)])
@pytest.mark.parametrize("fine", [True, False])
def test_claim_probe_fused_with_duplicates(T, K, N, G, fine):
    """Fused install + probe vs the two-phase oracle: the returned table
    must equal claim_scatter's and the returned probe must equal a probe of
    that POST-install table — duplicate cells (keys drawn from N//2), reads
    probing cells written this wave, and masked ops included.  Table words
    respect the monotone-wave-tag precondition (ref.claim_probe_fused)."""
    from repro.core.claimword import EMPTY_WORD, inv_wave
    wave = jnp.uint32(5)
    ivw = int(inv_wave(wave))
    # plausible table: claims from waves <= current (tag >= ivw) + empties
    tag = RNG.integers(ivw, 0x10000, (N, G))
    words = (tag << 16 | RNG.integers(0, 2 ** 16, (N, G))).astype(np.uint32)
    words[RNG.random((N, G)) < 0.3] = EMPTY_WORD
    table = jnp.asarray(words)
    keys = jnp.asarray(RNG.integers(-1, max(N // 2, 1), (T, K),
                                    dtype=np.int32))
    groups = jnp.asarray(RNG.integers(0, G, (T, K), dtype=np.int32))
    prio = jnp.asarray(RNG.integers(0, 2 ** 16, (T, K), dtype=np.uint32))
    do = jnp.asarray(RNG.random((T, K)) < 0.6)
    a_t, a_p = ops.claim_probe_fused(table, keys, groups, prio, do, wave,
                                     fine, use_pallas=True)
    b_t, b_p = ref.claim_probe_fused(table, keys, groups, prio, do, wave,
                                     fine)
    np.testing.assert_array_equal(np.asarray(a_t), np.asarray(b_t))
    np.testing.assert_array_equal(np.asarray(a_p), np.asarray(b_p))
    # the fused op IS the claim_scatter + post-install probe pair
    np.testing.assert_array_equal(
        np.asarray(b_t),
        np.asarray(ref.claim_scatter(table, keys, groups, prio, do, wave)))
    np.testing.assert_array_equal(
        np.asarray(b_p),
        np.asarray(ref.claim_probe(b_t, keys, groups, inv_wave(wave),
                                   fine)))


@pytest.mark.parametrize("M,ns,cap", [(48, 4, 8), (64, 8, 8), (33, 3, 16),
                                      (16, 1, 8)])
def test_route_pack(M, ns, cap):
    """Sort-free pack vs the counting oracle: duplicate destinations force
    in-destination ranking, M > ns*cap forces capacity drops, owner == ns
    exercises masked ops.  Placement must equal a stable argsort by owner."""
    owner = jnp.asarray(RNG.integers(0, ns + 1, M).astype(np.int32))
    vals = jnp.asarray(RNG.integers(-4, 1000, (3, M)).astype(np.int32))
    fills = (0x7FFFFFFF, 0x7FF8, -1)
    a_buf, a_pos, a_took = ops.route_pack(owner, vals, ns, cap, fills,
                                          use_pallas=True)
    b_buf, b_pos, b_took = ref.route_pack(owner, vals, ns, cap, fills)
    np.testing.assert_array_equal(np.asarray(a_buf), np.asarray(b_buf))
    np.testing.assert_array_equal(np.asarray(a_pos), np.asarray(b_pos))
    np.testing.assert_array_equal(np.asarray(a_took), np.asarray(b_took))
    # independent oracle: stable argsort placement
    own = np.asarray(owner)
    vs = np.asarray(vals)
    want = np.stack([np.full((ns, cap), f, np.int32) for f in fills])
    for i in np.argsort(own, kind="stable"):
        d = own[i]
        if d >= ns:
            assert not np.asarray(b_took)[i]
            continue
        p = int(np.asarray(b_pos)[i])
        assert p == (own[:i] == d).sum()
        if p < cap:
            assert np.asarray(b_took)[i]
            want[:, d, p] = vs[:, i]
        else:
            assert not np.asarray(b_took)[i]
    np.testing.assert_array_equal(np.asarray(b_buf), want)


@pytest.mark.parametrize("T,K,N,G", [(4, 8, 64, 2), (6, 3, 17, 1),
                                     (8, 16, 16, 2)])
def test_segment_count_with_duplicates(T, K, N, G):
    """All-pairs same-cell counts vs the sort-based oracle; keys drawn from
    N//2 force duplicate cells, sparse masks force sentinel handling."""
    keys = jnp.asarray(RNG.integers(-1, max(N // 2, 1), (T, K),
                                    dtype=np.int32))
    groups = jnp.asarray(RNG.integers(0, G, (T, K), dtype=np.int32))
    mask = jnp.asarray(RNG.random((T, K)) < 0.5)
    a = ops.segment_count(keys, groups, G, mask, use_pallas=True)
    b = ref.segment_count(keys, groups, G, mask)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # spot-check semantics: each masked op counts its cell's wave population
    cells = np.where(np.asarray(mask), np.asarray(keys) * G
                     + np.asarray(groups), -123)
    for t_ in range(T):
        for k_ in range(K):
            want = (cells == cells[t_, k_]).sum() if cells[t_, k_] != -123 \
                else 0
            assert np.asarray(b)[t_, k_] == want


# ------------------------------------------------------- multi-version ring
def _mv_begin_table(N, D, G, lo=0, hi=50):
    """A plausible ring: slot 0 always live, later slots a mix of installed
    and MV_EMPTY begins."""
    from repro.core.mvstore import MV_EMPTY
    b = RNG.integers(lo, hi, (N, D, G)).astype(np.uint32)
    empty = RNG.random((N, D)) < 0.3
    empty[:, 0] = False
    b[empty] = MV_EMPTY
    return jnp.asarray(b)


@pytest.mark.parametrize("T,K,N,D,G", [(4, 8, 64, 3, 2), (6, 3, 17, 2, 1),
                                       (3, 5, 9, 4, 2)])
@pytest.mark.parametrize("fine", [True, False])
def test_mv_gather(T, K, N, D, G, fine):
    """Snapshot version select: newest visible slot per op, reclaimed flag
    when every retained begin postdates the snapshot."""
    begin = _mv_begin_table(N, D, G)
    keys = jnp.asarray(RNG.integers(-1, N, (T, K), dtype=np.int32))
    groups = jnp.asarray(RNG.integers(0, G, (T, K), dtype=np.int32))
    for ts in (0, 7, 49):
        a_s, a_ok = ops.mv_gather(begin, keys, groups, jnp.uint32(ts), fine,
                                  use_pallas=True)
        b_s, b_ok = ref.mv_gather(begin, keys, groups, jnp.uint32(ts), fine)
        np.testing.assert_array_equal(np.asarray(a_s), np.asarray(b_s))
        np.testing.assert_array_equal(np.asarray(a_ok), np.asarray(b_ok))
    # masked ops never report a visible version
    assert not np.asarray(b_ok)[np.asarray(keys) < 0].any()


@pytest.mark.parametrize("T,K,N,D,G", [(4, 8, 64, 3, 2), (6, 3, 17, 2, 1),
                                       (5, 4, 8, 4, 2)])
def test_mv_install_with_duplicates(T, K, N, D, G):
    """Ring-slot claim + publish; keys drawn from N//2 force several
    committed ops onto one record in a wave (they must merge into ONE new
    slot).  Begin values respect the < ts monotonicity precondition."""
    from repro.core import mvstore
    begin, head, _ = mvstore.mv_init(N, D, G)
    # age the ring a little with real installs so heads differ
    for wave in range(3):
        ks = jnp.asarray(RNG.integers(-1, max(N // 2, 2), (T, K),
                                      dtype=np.int32))
        gs = jnp.asarray(RNG.integers(0, G, (T, K), dtype=np.int32))
        do = jnp.asarray(RNG.random((T, K)) < 0.4)
        ts = jnp.uint32(wave + 1)
        a_b, a_h = ops.mv_install(begin, head, ks, gs, do, ts,
                                  use_pallas=True)
        b_b, b_h = ref.mv_install(begin, head, ks, gs, do, ts)
        np.testing.assert_array_equal(np.asarray(a_b), np.asarray(b_b))
        np.testing.assert_array_equal(np.asarray(a_h), np.asarray(b_h))
        begin, head = b_b, b_h
    # every touched record claimed exactly one slot per wave: heads stay
    # within [0, D) and begins never exceed the last install ts
    from repro.core.mvstore import MV_EMPTY
    b = np.asarray(begin)
    assert ((b <= 3) | (b == MV_EMPTY)).all()
    assert (np.asarray(head) >= 0).all() and (np.asarray(head) < D).all()


# ------------------------------------------- precondition validation (new)
def _future_tagged_table():
    """A claim table holding a wave-7 claim — newer than the wave-3 calls
    below, violating the monotone-wave-tag precondition."""
    from repro.core.claimword import claim_word
    from repro.core.types import NO_CLAIM
    table = jnp.full((8, 2), NO_CLAIM, jnp.uint32)
    return table.at[2, 0].set(claim_word(jnp.uint32(7), jnp.uint32(5)))


def test_claim_probe_fused_rejects_future_wave_tags():
    """The documented monotone-wave-tag precondition of claim_probe is now
    CHECKED on eager calls (both backends): a table cell claimed by a wave
    newer than the current one raises instead of silently answering wrong
    (ISSUE 5 satellite).  Untouched violating cells don't fire — the check
    is per touched row, so it stays cheap."""
    table = _future_tagged_table()
    keys = jnp.asarray([[2]], jnp.int32)
    groups = jnp.zeros((1, 1), jnp.int32)
    prio = jnp.asarray([[1]], jnp.uint32)
    do = jnp.asarray([[True]])
    wave = jnp.uint32(3)
    with pytest.raises(ValueError, match="precondition"):
        ref.claim_probe_fused(table, keys, groups, prio, do, wave, True)
    with pytest.raises(ValueError, match="precondition"):
        ops.claim_probe_fused(table, keys, groups, prio, do, wave, True,
                              use_pallas=True)
    # the same wave's own tag is NOT a violation (claims land per wave)...
    ref.claim_probe_fused(table, keys, groups, prio, do, jnp.uint32(7),
                          True)
    # ...and ops that don't touch the poisoned row never see it
    ref.claim_probe_fused(table, jnp.asarray([[4]], jnp.int32), groups,
                          prio, do, wave, True)


def test_mv_install_rejects_non_monotone_begin():
    """Same for mv_install: an installed-into ring row already holding a
    begin >= the install ts (a wave driven backwards / a reused ts) raises
    on eager calls instead of silently merging distinct waves."""
    from repro.core import mvstore
    begin, head, _ = mvstore.mv_init(8, 3, 2)
    begin = begin.at[2, 0, 0].set(jnp.uint32(9))
    keys = jnp.asarray([[2]], jnp.int32)
    groups = jnp.zeros((1, 1), jnp.int32)
    do = jnp.asarray([[True]])
    with pytest.raises(ValueError, match="precondition"):
        ref.mv_install(begin, head, keys, groups, do, jnp.uint32(5))
    with pytest.raises(ValueError, match="precondition"):
        ops.mv_install(begin, head, keys, groups, do, jnp.uint32(5),
                       use_pallas=True)
    # strictly newer ts passes; so does a masked (do=False) touch of the row
    ref.mv_install(begin, head, keys, groups, do, jnp.uint32(10))
    ref.mv_install(begin, head, keys, groups, jnp.asarray([[False]]),
                   jnp.uint32(5))


def test_precondition_checks_jit_free_and_env_gated(monkeypatch):
    """Under jit the inputs are tracers and the check compiles to nothing;
    REPRO_PRECONDITION_CHECKS=0 disables it eagerly too."""
    table = _future_tagged_table()
    keys = jnp.asarray([[2]], jnp.int32)
    groups = jnp.zeros((1, 1), jnp.int32)
    prio = jnp.asarray([[1]], jnp.uint32)
    do = jnp.asarray([[True]])
    jax.jit(lambda t_: ref.claim_probe_fused(t_, keys, groups, prio, do,
                                             jnp.uint32(3), True))(table)
    monkeypatch.setenv("REPRO_PRECONDITION_CHECKS", "0")
    ref.claim_probe_fused(table, keys, groups, prio, do, jnp.uint32(3),
                          True)


@pytest.mark.parametrize("shape,dtype", [((33, 1), np.uint32),
                                         ((1500, 2), np.uint32),
                                         ((2048, 4, 2), np.uint32),
                                         ((700,), np.int32)])
def test_packed_rows_roundtrip(shape, dtype):
    """rows.pack lays cell (k, o, g) at lane k & 127 of row
    o * Nb * G + (k >> 7) * G + g, and unpack inverts it exactly."""
    from repro.kernels import rows
    x = np.random.default_rng(1).integers(0, 2 ** 31, shape).astype(dtype)
    p = np.asarray(rows.pack(jnp.asarray(x)))
    assert p.shape[1] == 128 and p.shape[0] % 8 == 0
    flat = x.reshape(shape[0], -1)
    G = shape[2] if len(shape) == 3 else (shape[1] if len(shape) == 2
                                            else 1)
    Nb = p.shape[0] // (flat.shape[1])
    offs = rows.row_offsets(shape)
    for k in (0, shape[0] // 2, shape[0] - 1):
        for r, off in enumerate(offs):
            assert p[(k >> 7) * G + off, k & 127].view(dtype) == flat[k, r]
    assert Nb * 128 >= shape[0]
    back = rows.unpack(jnp.asarray(p), jnp.asarray(x))
    assert back.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(back), x)


def test_interpret_mode_only_on_cpu(monkeypatch):
    """Kernels compile on a TPU, interpret on the CPU, and refuse any other
    platform instead of quietly interpreting there."""
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops._interp() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interp() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="cannot run on 'gpu'"):
        ops._interp()


def test_repro_kernels_env_resolved_per_call(monkeypatch):
    """REPRO_KERNELS must be read per call, not frozen at import time."""
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    assert ops._use_pallas(None) is True
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    assert ops._use_pallas(None) is False
    monkeypatch.delenv("REPRO_KERNELS")
    import jax
    assert ops._use_pallas(None) == (jax.default_backend() == "tpu")


# --------------------------------------------------------- flash attention
@pytest.mark.parametrize("B,Hq,Hkv,Sq,Sk,D", [
    (2, 4, 2, 64, 64, 32),       # GQA
    (1, 2, 2, 128, 128, 16),     # MHA
    (1, 4, 1, 32, 32, 8),        # MQA
])
@pytest.mark.parametrize("window", [None, 16])
def test_flash_attention(B, Hq, Hkv, Sq, Sk, D, window):
    q = jnp.asarray(RNG.standard_normal((B, Hq, Sq, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, Hkv, Sk, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, Hkv, Sk, D)), jnp.float32)
    a = ops.flash_attention(q, k, v, causal=True, window=window,
                            block_q=32, block_k=32, use_pallas=True)
    b = ref.attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.standard_normal((1, 2, 64, 16)), jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((1, 2, 64, 16)), jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((1, 2, 64, 16)), jnp.bfloat16)
    a = ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                            use_pallas=True)
    b = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=3e-2)


# ------------------------------------------------------------ jnp-flash
@pytest.mark.parametrize("S,window", [(1024, None), (2048, None),
                                      (2048, 256)])
def test_jnp_flash_matches_dense(S, window):
    """models/attention.py blocked path vs its own dense fallback."""
    from repro.models.attention import _dense, _flash
    B, G, R, D = 1, 2, 2, 16
    q = jnp.asarray(RNG.standard_normal((B, G, R, S, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, G, S, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, G, S, D)), jnp.float32)
    blocked = _flash(q, k, v, causal=True, window=window,
                     block_q=512, block_k=512)
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    dense = _dense(q * D ** -0.5, k, v, mask)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------------ RG-LRU
@pytest.mark.parametrize("B,S,D", [(2, 32, 128), (1, 64, 256)])
def test_rglru(B, S, D):
    la = -jnp.abs(jnp.asarray(RNG.standard_normal((B, S, D)), jnp.float32))
    x = jnp.asarray(RNG.standard_normal((B, S, D)), jnp.float32)
    h0 = jnp.asarray(RNG.standard_normal((B, D)), jnp.float32)
    a, al = ops.rglru(la, x, h0=h0, use_pallas=True)
    b, bl = ref.rglru(la, x, h0=h0)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    np.testing.assert_allclose(np.asarray(al), np.asarray(bl), atol=1e-5)


def test_rglru_chunked_carries_state():
    B, S, D = 1, 96, 128
    la = -jnp.abs(jnp.asarray(RNG.standard_normal((B, S, D)), jnp.float32))
    x = jnp.asarray(RNG.standard_normal((B, S, D)), jnp.float32)
    a, al = ops.rglru(la, x, chunk=32, use_pallas=True)
    b, bl = ref.rglru(la, x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    np.testing.assert_allclose(np.asarray(al), np.asarray(bl), atol=1e-5)


# ------------------------------------------------------------------ RWKV-6
@pytest.mark.parametrize("B,H,S,Dk,Dv", [(2, 2, 16, 8, 8), (1, 4, 32, 16, 16)])
def test_rwkv6(B, H, S, Dk, Dv):
    r = jnp.asarray(RNG.standard_normal((B, H, S, Dk)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, H, S, Dk)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, H, S, Dv)), jnp.float32)
    w = jnp.asarray(RNG.random((B, H, S, Dk)) * 0.9 + 0.05, jnp.float32)
    u = jnp.asarray(RNG.standard_normal((H, Dk)), jnp.float32)
    a, asl = ops.rwkv6(r, k, v, w, u, use_pallas=True)
    b, bsl = ref.rwkv6(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(np.asarray(asl), np.asarray(bsl), atol=2e-5,
                               rtol=2e-5)


def test_rwkv6_chunked_carries_state():
    B, H, S, D = 1, 2, 48, 8
    r = jnp.asarray(RNG.standard_normal((B, H, S, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, H, S, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, H, S, D)), jnp.float32)
    w = jnp.asarray(RNG.random((B, H, S, D)) * 0.9 + 0.05, jnp.float32)
    u = jnp.asarray(RNG.standard_normal((H, D)), jnp.float32)
    a, asl = ops.rwkv6(r, k, v, w, u, chunk=16, use_pallas=True)
    b, bsl = ref.rwkv6(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(np.asarray(asl), np.asarray(bsl), atol=2e-5,
                               rtol=2e-5)
