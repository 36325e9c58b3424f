"""Packed-row layout and lane-block helpers shared by the table kernels.

TPU memory is tiled in 128-lane rows, so a record table laid out as
``[N, G]`` with a narrow G (2 timestamp groups) cannot be moved one record
at a time: Mosaic refuses a DMA slice narrower than a lane tile, and a
row-major ``[N, 2]`` operand would be padded to 128 lanes (64x the bytes).
XLA itself stores such a table column-major in (G, 128) tiles.  Every
table kernel therefore sees its table PACKED in that same order: records
in blocks of 128 (one per lane), each block contributing one 128-lane row
per column, so ``[N, *outer, G]`` becomes ``[O * Nb * G, 128]`` rows
(``O`` = prod(outer), ``Nb`` = ceil(N / 128)) and cell ``(k, o, g)`` lives
at lane ``k & 127`` of row ``o * Nb * G + (k >> 7) * G + g``.  A record's
``RW = O * G`` words are one lane of RW rows, so kernels move RW single-row
DMAs per record and see every op's word of row ``r`` at the op's lane.
``pack``/``unpack`` convert at the kernel boundary, so engine state keeps
its ``[N, G]`` shape and both backends read the same tables; they and the
per-op row conversions run under the named scope ``repro:relayout``.

Ops are flattened lane-major (op ``t * K + k``) and walked in blocks of
``LBK = LB * K`` ops, where ``LB`` lanes per grid step makes LBK a
multiple of 128 (``pick_lane_block``).  The wave is padded to whole blocks
with masked ops (key -1), which touch nothing.  Per-op vectors reach a
kernel as lane-dense rows ``[1, TKp]`` (one block per step); the block
math that needs one op per sublane turns a row into a column with
``to_col`` (a select-sum against the identity, which Mosaic lowers for any
32-bit type) and back with ``to_row``.  Copies of one stream share one DMA
semaphore (every copy is one row, so each wait retires one copy).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SENT = 0x7FFFFFFF       # cell id of masked ops in all-pairs compares
SIGN = -0x80000000      # xor that maps uint32 order onto int32 order
#: Named scope of every layout conversion at a kernel boundary; the
#: benchmark reads its device time as ``relayout_share_pct``.
RELAYOUT = "repro:relayout"


# ------------------------------------------------------------------ layout
def _dims(shape):
    """(Nb record blocks, O, G).  Nb is rounded up to a multiple of 8 so the
    packed rows fill whole (8, 128) tiles: XLA then relayouts a table in
    one plain copy (an unaligned row count compiles for tens of seconds at
    10M records)."""
    inner = tuple(shape[1:])
    G = inner[-1] if inner else 1
    return -(-shape[0] // (8 * LANES)) * 8, math.prod(inner[:-1]), G


def pack(table: jax.Array) -> jax.Array:
    """``[N, *outer, G]`` 32-bit table -> ``[O * Nb * G, 128]`` int32 rows.

    Written from XLA's own storage order (records minor: ``[O, G, N]``)
    so that every intermediate keeps a long minor dimension — a row-major
    ``[N, G]`` intermediate would be padded to 128 lanes, and other
    orders compile for minutes at 10M records."""
    Nb, O, G = _dims(table.shape)
    n = table.shape[0]
    with jax.named_scope(RELAYOUT):
        x = i32(table).reshape(n, O, G).transpose(1, 2, 0)
        if Nb * LANES != n:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, Nb * LANES - n)))
        x = x.reshape(O, G, Nb, LANES).transpose(0, 2, 1, 3)
        return x.reshape(O * Nb * G, LANES)


def unpack(packed: jax.Array, like: jax.Array) -> jax.Array:
    """Inverse of ``pack``: back to the shape and dtype of ``like``."""
    Nb, O, G = _dims(like.shape)
    with jax.named_scope(RELAYOUT):
        x = packed.reshape(O, Nb, G, LANES).transpose(0, 2, 1, 3)
        x = x.reshape(O, G, Nb * LANES)[:, :, :like.shape[0]]
        x = x.transpose(2, 0, 1).reshape(like.shape)
        return jax.lax.bitcast_convert_type(x, like.dtype)


def row_offsets(shape) -> list[int]:
    """Row offset of each of a record's RW words from its block row
    ``(k >> 7) * G`` (word order r = o * G + g)."""
    Nb, O, G = _dims(shape)
    return [o * Nb * G + g for o in range(O) for g in range(G)]


def pick_lane_block(K: int, override: int = 0) -> int:
    """Lanes per grid step: the least multiple of ``128 / gcd(K, 128)``
    (so a block of ``LB * K`` ops fills whole 128-lane rows) that is at
    least ``override`` (EngineConfig.lane_block; 0 = the least)."""
    unit = LANES // math.gcd(K, LANES)
    return max(-(-int(override) // unit), 1) * unit


def blocking(keys: jax.Array, lane_block: int = 0):
    """(LBK ops per block, Tp padded lanes) for a ``[T, K]`` wave."""
    T, K = keys.shape
    LB = pick_lane_block(K, lane_block)
    return LB * K, -(-T // LB) * LB


def record_pos(keys: jax.Array, G: int):
    """(block row ``(k >> 7) * G``, lane ``k & 127``) of each op's record;
    masked ops -> block 0."""
    kcl = jnp.maximum(keys, 0)
    return (kcl >> 7) * G, kcl & (LANES - 1)


def op_rows(x: jax.Array, Tp: int, fill: int = 0) -> jax.Array:
    """``[T, K]`` per-op values -> lane-dense ``[1, Tp * K]`` int32 row,
    padded with ``fill`` for the masked lanes ``T .. Tp - 1``."""
    T, K = x.shape
    with jax.named_scope(RELAYOUT):
        x = i32(x)
        if Tp != T:
            x = jnp.pad(x, ((0, Tp - T), (0, 0)), constant_values=fill)
        return x.reshape(1, Tp * K)


def from_rows(y: jax.Array, T: int, K: int) -> jax.Array:
    """``[1, Tp * K]`` kernel output row -> ``[T, K]``."""
    with jax.named_scope(RELAYOUT):
        return y.reshape(-1, K)[:T]


# --------------------------------------------------------- in-kernel math
def i32(x: jax.Array) -> jax.Array:
    """Bits of a 32-bit array as int32 (kernels compute on int32 only:
    Mosaic has no unsigned reductions)."""
    if x.dtype == jnp.bool_:
        return x.astype(jnp.int32)
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def ult(a, b):
    """Unsigned ``a < b`` on int32 bit patterns."""
    return (a ^ SIGN) < (b ^ SIGN)


def umin(a, b):
    """Unsigned min on int32 bit patterns."""
    return jnp.minimum(a ^ SIGN, b ^ SIGN) ^ SIGN


def umax(a, b):
    """Unsigned max on int32 bit patterns."""
    return jnp.maximum(a ^ SIGN, b ^ SIGN) ^ SIGN


def live_prio(words, ivw):
    """``claimword.live_prio`` on int32 claim words (int32 result)."""
    live = jax.lax.shift_right_logical(words, 16) == ivw
    return jnp.where(live, words & 0xFFFF, 0xFFFF)


def eye(n: int) -> jax.Array:
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def to_col(row: jax.Array, ident: jax.Array) -> jax.Array:
    """int32 ``[1, n]`` -> ``[n, 1]`` (exact: one nonzero term per sum)."""
    return jnp.where(ident, row, 0).sum(axis=1, keepdims=True)


def to_row(col: jax.Array, ident: jax.Array) -> jax.Array:
    """int32 ``[n, 1]`` -> ``[1, n]``."""
    return jnp.where(ident, col, 0).sum(axis=0, keepdims=True)


def lane_iota(n: int) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, (n, LANES), 1)


def pick(rows: jax.Array, at: jax.Array) -> jax.Array:
    """The word of each op (one per sublane) at its lane: ``[n, 1]``."""
    return jnp.where(at, rows, 0).sum(axis=1, keepdims=True)


def row_dmas(start: bool, prow_ref, tbl_ref, buf_ref, sem, base, n, offs,
             to_table: bool = False, ops=None):
    """Issue (``start``) or wait the row copies of a block: for word r
    (offset ``offs[r]``) of op j, packed row ``prow_ref[base + j] +
    offs[r]`` <-> scratch row ``r * n + j``.  A stream's copies are all in
    flight together and share the semaphore ``sem``.

    ``ops`` = (``list_ref``, ``count``) limits the copies to the block's
    ops ``j = list_ref[base + i]``, ``i < count``; start and wait take
    the same list, so each wait retires one started copy."""
    def body(i, _):
        j = i if ops is None else ops[0][base + i]
        for r, off in enumerate(offs):
            src = tbl_ref.at[pl.ds(prow_ref[base + j] + off, 1)]
            dst = buf_ref.at[pl.ds(r * n + j, 1)]
            if to_table:
                src, dst = dst, src
            copy = pltpu.make_async_copy(src, dst, sem)
            if start:
                copy.start()
            else:
                copy.wait()
        return 0

    jax.lax.fori_loop(0, n if ops is None else ops[1], body, 0)


def any_spec():
    return pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)


def blk_spec(LBK: int):
    """One block of a lane-dense per-op row (index maps take the grid
    index plus however many scalar-prefetch refs the call has)."""
    return pl.BlockSpec((1, LBK), lambda i, *_: (0, i))


def full_spec(shape):
    return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))


def row_scratch(LBK: int, rw: int = 1):
    """VMEM rows for ``rw`` words of every block op, and their semaphore."""
    return [pltpu.VMEM((rw * LBK, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA(())]


# ------------------------------------------------------------ gather family
def _gather_kernel(compute, offs, n_pre, n_in, n_out, LBK, *refs):
    pre = refs[:n_pre]
    ins = refs[n_pre:n_pre + n_in]
    tbl = refs[n_pre + n_in]
    outs = refs[n_pre + n_in + 1:n_pre + n_in + 1 + n_out]
    buf, sem = refs[-2:]
    base = pl.program_id(0) * LBK
    row_dmas(True, pre[0], tbl, buf, sem, base, LBK, offs)
    row_dmas(False, pre[0], tbl, buf, sem, base, LBK, offs)
    ident = eye(LBK)
    cols = [to_col(r[...], ident) for r in ins]
    at = lane_iota(LBK) == cols[0]
    words = [pick(buf[pl.ds(r * LBK, LBK), :], at) for r in range(len(offs))]
    for o, c in zip(outs, compute(words, [p[0] for p in pre[1:]], cols)):
        o[...] = to_row(c.astype(jnp.int32), ident)


def gather_call(compute, table, keys, op_vals, scalars, n_out: int,
                lane_block: int, interpret: bool, name: str):
    """Record-gather launcher.  The RW words of every op's record are
    DMA'd into VMEM; ``compute(words, scalars, cols)`` maps the per-word
    ``[LBK, 1]`` columns, the scalar values and the per-op columns
    ``[lane, key, *op_vals]`` to ``n_out`` int32 result columns.  Returns
    the results as ``[T, K]`` int32 arrays."""
    T, K = keys.shape
    LBK, Tp = blocking(keys, lane_block)
    TKp = Tp * K
    offs = row_offsets(table.shape)
    prow, lane = record_pos(keys, _dims(table.shape)[2])
    pre = [op_rows(prow, Tp)[0]] + [jnp.reshape(i32(s), (1,))
                                    for s in scalars]
    ins = [op_rows(lane, Tp), op_rows(keys, Tp, fill=-1)] + [
        op_rows(v, Tp) for v in op_vals]
    outs = pl.pallas_call(
        functools.partial(_gather_kernel, compute, offs, len(pre), len(ins),
                          n_out, LBK),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(pre),
            grid=(TKp // LBK,),
            in_specs=[blk_spec(LBK)] * len(ins) + [any_spec()],
            out_specs=[blk_spec(LBK)] * n_out,
            scratch_shapes=row_scratch(LBK, len(offs))),
        out_shape=[jax.ShapeDtypeStruct((1, TKp), jnp.int32)] * n_out,
        interpret=interpret,
        name=name,
    )(*pre, *ins, pack(table))
    return [from_rows(o, T, K) for o in outs]


def group_word(words, grp_c):
    """The op's own group word (fine): exact select over the G words."""
    out = words[0]
    for g, w in enumerate(words[1:], 1):
        out = jnp.where(grp_c == g, w, out)
    return out


# ----------------------------------------------------------- scatter family
def _scatter_kernel(combine, offs, LBK, prow_s, inst_s, val_s, id_b, tbl_in,
                    tbl, buf, sem_r, sem_w):
    del tbl_in                       # RMW through the aliased output
    base = pl.program_id(0) * LBK
    row_dmas(True, prow_s, tbl, buf, sem_r, base, LBK, offs)
    row_dmas(False, prow_s, tbl, buf, sem_r, base, LBK, offs)
    id_c = to_col(id_b[...], eye(LBK))
    lanes = lane_iota(LBK)

    def body(j, _):
        lane = inst_s[base + j]

        @pl.when(lane >= 0)
        def _():
            m = (id_c == prow_s[base + j]) & (lanes == lane)
            for r in range(len(offs)):
                sl = pl.ds(r * LBK, LBK)
                now = buf[sl, :]
                buf[sl, :] = jnp.where(m, combine(now, val_s[base + j]), now)
        return 0

    jax.lax.fori_loop(0, LBK, body, 0)
    row_dmas(True, prow_s, tbl, buf, sem_w, base, LBK, offs, to_table=True)
    row_dmas(False, prow_s, tbl, buf, sem_w, base, LBK, offs, to_table=True)


def scatter_call(combine, table, keys, groups, vals, do, whole_row: bool,
                 lane_block: int, interpret: bool, name: str):
    """Read-modify-write launcher: for every op with ``do``, its cell (or
    with ``whole_row`` every group of its record) becomes
    ``combine(cell, val)`` on int32 bit patterns.  A block fetches its
    ops' rows, folds its ops in one by one (so duplicates accumulate; every
    op on a row computes the same final row), and writes the final rows
    back; the sequential grid orders the blocks."""
    T, K = keys.shape
    LBK, Tp = blocking(keys, lane_block)
    TKp = Tp * K
    G = table.shape[1]
    blk_row, lane = record_pos(keys, G)
    prow = blk_row if whole_row else blk_row + groups
    offs = list(range(G)) if whole_row else [0]
    inst = jnp.where(do & (keys >= 0), lane, -1)
    packed = pack(table)
    out = pl.pallas_call(
        functools.partial(_scatter_kernel, combine, offs, LBK),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(TKp // LBK,),
            in_specs=[blk_spec(LBK), any_spec()],
            out_specs=any_spec(),
            scratch_shapes=[pltpu.VMEM((len(offs) * LBK, LANES), jnp.int32),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(packed.shape, packed.dtype),
        input_output_aliases={4: 0},
        interpret=interpret,
        name=name,
    )(op_rows(prow, Tp)[0], op_rows(inst, Tp, fill=-1)[0],
      op_rows(vals, Tp)[0], op_rows(prow, Tp), packed)
    return unpack(out, table)
