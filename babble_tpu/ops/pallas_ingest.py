"""Pallas TPU kernel: one-pass last-ancestor fill ("the walk").

The XLA batch path fills ``la`` with a level scan — one kernel launch per
topological level (~2,600 sequential [B, N] steps on the 64x65k gossip
DAG), each gathering parent rows from HBM.  The absorb alternative is a
log-depth fixpoint but its frontier gathers scalarize (~950 ms measured).

This kernel exploits the other structural fact: *slot order is
topological*.  With the whole coordinate table resident in VMEM, one
sequential walk computes

    la[x] = max(la[sp(x)], la[op(x)]) ; la[x, creator(x)] = seq(x)

in O(E) tiny row-max steps — no HBM traffic per event, no per-level
launch overhead.  The table is packed two events per 128-lane row in
int16 (event 2r in lanes [0,64), event 2r+1 in [64,128)), which is what
makes 65k x 64 fit the ~14 MB usable VMEM: an unpacked [E, 64] int16
table pads its lane dimension to 128 and lands at 16.7 MB.

Applicability gates (callers fall back to the level scan otherwise):
- n <= 64 creators (half-lane packing),
- seqs < 32767 (int16 coordinates),
- packed table + index arrays within the VMEM budget and the index
  arrays within SMEM (87,039 events).

Reference semantics: InitEventCoordinates (hashgraph.go:399-463), one
event at a time over the Store — the same recurrence, minus the store
round-trips.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .state import I32

_HALF = 64
_VMEM_BUDGET = 13 * 1024 * 1024
_SMEM_BYTES = 1 << 20                             # v5e scalar memory
_SMEM_PAGE = 4096


def walk_supported(n: int, e_cap: int, s_cap: int) -> bool:
    table = (e_cap + 2) // 2 * 128 * 2            # packed int16 bytes
    index = 4 * (e_cap + 1) * 4                   # sp/op/creator/seq i32
    # sp/op/meta ride in SMEM, each padded to whole 4 KiB pages, with a
    # page left for the trip count: the described-v5e compiler admits
    # e_cap 87,039 and refuses 87,040 (tests/test_tpu_compile.py)
    pages = -(-(e_cap + 1) * 4 // _SMEM_PAGE)
    smem = (3 * pages + 1) * _SMEM_PAGE
    return (n <= _HALF and s_cap < 32767 and table + index < _VMEM_BUDGET
            and smem <= _SMEM_BYTES)


def _roll64(row: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    """Swap the two 64-lane halves (128-lane rotation by 64)."""
    if interpret:
        return jnp.roll(row, _HALF, axis=1)
    return pltpu.roll(row, jnp.int32(_HALF), 1)  # i32 shift (x64 mode)


def _walk_kernel(ne_ref, sp_ref, op_ref, meta_ref, la_ref, *,
                 interpret: bool):
    # int16 VMEM is tiled (16, 128) and Mosaic cannot load a single row at
    # a dynamic sublane index of a packed dtype — so every access moves the
    # row's aligned [16, 128] tile and selects/merges via sublane masks.
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (16, 128), 0)
    low = lane < _HALF

    def tile_of(r):
        base = pl.multiple_of((r >> 4) << 4, 16)
        return la_ref[pl.ds(base, 16), :], sub == (r & 15)

    def select_row(tile, is_row):
        # int16 reductions are unimplemented in Mosaic: select+max in i32
        t32 = jnp.where(is_row, tile, jnp.int16(-32768)).astype(jnp.int32)
        return jnp.max(t32, axis=0, keepdims=True)          # i32 [1, 128]

    def gather(slot):
        """Aligned [1,128] i32 row of `slot` (lanes [0,64); upper = -1)."""
        r = jnp.maximum(slot, 0) >> 1
        tile, is_row = tile_of(r)
        row = select_row(tile, is_row)
        aligned = jnp.where((slot & 1) == 1, _roll64(row, interpret), row)
        # literals pinned to i32: weak int64 constants send Mosaic's
        # convert lowering into infinite recursion under x64
        return jnp.where(low & (slot >= 0), aligned, jnp.int32(-1))

    def body(i, _):
        sps = sp_ref[i]
        ops = op_ref[i]
        meta = meta_ref[i]           # creator << 16 | seq (SMEM budget)
        row = jnp.maximum(gather(sps), gather(ops))          # i32 [1, 128]
        own = lane == (meta >> 16)
        row = jnp.where(own, meta & jnp.int32(0xFFFF), row)

        # merge into packed row i>>1: even events own the low half, odd
        # events the high half (tile read-modify-write keeps the sibling
        # half and the other 15 rows)
        r = i >> 1
        tile, is_row = tile_of(r)
        cur = select_row(tile, is_row)
        hi = _roll64(row, interpret)           # data in upper lanes, -1 low
        odd = (i & 1) == 1
        merged = jnp.where(
            odd,
            jnp.where(low, cur, hi),
            jnp.where(low, row, cur),
        ).astype(jnp.int16)
        base = pl.multiple_of((r >> 4) << 4, 16)
        la_ref[pl.ds(base, 16), :] = jnp.where(is_row, merged, tile)
        return jnp.int32(0)

    # i32 bounds keep the counter (and everything derived from it) out of
    # the x64 promotion path — i64 vectors don't exist on TPU
    jax.lax.fori_loop(jnp.int32(0), ne_ref[0], body, jnp.int32(0))


@functools.partial(jax.jit, static_argnums=(0, 1, 7))
def la_walk(e_cap: int, n: int, sp, op, creator, seq, n_events,
            interpret: bool = False):
    """Fill la[: n_events] for the whole (topologically slot-ordered) DAG.

    Takes the state's [E+1] index arrays (sentinel row included, ignored);
    returns the packed int16 table — ``unpack_la`` restores [E+1, N] i32.
    The trip count is a runtime scalar (no recompile per batch size); the
    index arrays ride in SMEM so the walk's scalar reads never touch the
    vector path."""
    rows = -(-((e_cap + 2) // 2) // 16) * 16   # tile-aligned row count
    ne = jnp.asarray(n_events, I32)[None]
    meta = (
        (creator.astype(I32) << 16) | (jnp.maximum(seq, 0).astype(I32))
    )
    packed = pl.pallas_call(
        functools.partial(_walk_kernel, interpret=interpret),
        out_shape=jax.ShapeDtypeStruct((rows, 128), jnp.int16),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )(ne, sp.astype(I32), op.astype(I32), meta)
    return packed


def unpack_la(e_cap: int, n: int, packed, n_events) -> jnp.ndarray:
    """Packed int16 [rows, 128] -> la i32 [E+1, N] with -1 beyond."""
    e1 = e_cap + 1
    rows = packed.shape[0]
    flat = packed.reshape(rows * 2, _HALF)[:e1, :n].astype(I32)
    live = (jnp.arange(e1) < n_events)[:, None]
    return jnp.where(live, flat, -1)
