"""Pallas TPU kernel: row-wise AdaGrad applied in place to a table's
touched rows — the training hot spot the paper calls out ("not optimized
for gradient aggregation", section VII).

The caller has already aggregated the batch's gradients per UNIQUE row
(`ref.bag_grad_sums` over a `SparsePlan`, or `ref.dedup_grads_ref` over
per-lookup grads) into `gsum`, and gathered each row's accumulator. This
kernel then walks the unique rows in ascending order and, per row,

    acc' = acc + mean(g^2);  w' = w - lr * g * rsqrt(acc' + eps)

writing the table row in place (io aliasing) and emitting acc' for the
caller to scatter back.

Layout: the "rows on lanes" view of row_move.py, (d, H) = `table.T`. A
touched (d, 128) tile is DMA'd into VMEM once, every unique row inside it
is updated under a lane mask, and the tile is written back when the walk
leaves it; rows arrive sorted, so each touched tile moves once per grid
step. Per-row values (gsum, acc) come in lane-dense blocks, and a
`pltpu.roll` lines row j's column up with lane r % 128. Row ids stream
through SMEM one `ROW_BLOCK` block per grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.tpu import MemorySpace, SemaphoreType

from repro.kernels.row_move import LANE, ROW_BLOCK, pad_axis



def _apply_kernel(rows_ref, lr_ref, g_ref, acc_ref, table_in, table_out,
                  acc_out, tile, cur, sem, *, eps: float):
    """One grid step: ROW_BLOCK unique rows, ascending (-1 = pad).

    rows_ref: (ROW_BLOCK,) SMEM; lr_ref: (1,) SMEM; g_ref: (ROW_BLOCK/128,
    d, 128) VMEM, row j's gradient sum in lane j % 128 of g_ref[j // 128];
    acc_ref/acc_out: (ROW_BLOCK/128, 128) VMEM, same lane placement;
    table_in/table_out: (d, H) HBM, aliased; tile: (d, 128) VMEM, the
    resident table tile; cur: (1,) SMEM, its index (-1 = none)."""
    del table_in                       # aliased: read through table_out
    d = tile.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)

    def tile_dma(t, to_hbm):
        """Move table tile t between HBM and the VMEM tile (blocking)."""
        hbm = table_out.at[:, pl.ds(pl.multiple_of(t * LANE, LANE), LANE)]
        src, dst = (tile, hbm) if to_hbm else (hbm, tile)
        cp = pltpu.make_async_copy(src, dst, sem.at[0])
        cp.start()
        cp.wait()

    cur[0] = -1
    acc_out[...] = acc_ref[...]

    def body(j, carry):
        r = rows_ref[j]

        @pl.when(r >= 0)
        def _():
            t = r // LANE

            @pl.when(t != cur[0])
            def _():
                @pl.when(cur[0] >= 0)
                def _():
                    tile_dma(cur[0], True)
                tile_dma(t, False)
                cur[0] = t

            a = j // LANE
            lane_r = r % LANE
            shift = (lane_r - j % LANE + LANE) % LANE
            mask = lane == lane_r
            g = jnp.where(mask, pltpu.roll(g_ref[a], shift, 1), 0.0)
            acc = pltpu.roll(acc_ref[pl.ds(a, 1), :], shift, 1)
            acc_new = acc + jnp.sum(g * g, axis=0, keepdims=True) / d
            upd = lr_ref[0] * g * jax.lax.rsqrt(acc_new + eps)
            w = tile[...].astype(jnp.float32)
            tile[...] = jnp.where(mask, w - upd, w).astype(tile.dtype)
            back = pltpu.roll(acc_new, (LANE - shift) % LANE, 1)
            acc_out[pl.ds(a, 1), :] = jnp.where(
                lane == j % LANE, back, acc_out[pl.ds(a, 1), :])

        return carry

    jax.lax.fori_loop(0, ROW_BLOCK, body, 0)

    @pl.when(cur[0] >= 0)
    def _():
        tile_dma(cur[0], True)


def rowwise_adagrad_apply_kernel(table_t: jax.Array, rows: jax.Array,
                                 gsum_t: jax.Array, acc: jax.Array,
                                 lr: jax.Array, eps: float = 1e-8,
                                 interpret: bool = False):
    """table_t: (d, H) with H % 128 == 0 (the rows-on-lanes view); rows:
    (U,) int32 ascending (-1 pads), U % ROW_BLOCK == 0; gsum_t: (U/128, d,
    128) fp32, row j's gradient sum at [j // 128, :, j % 128]; acc:
    (U/128, 128) fp32 per-row accumulators, same placement; lr: (1,) fp32.
    Returns (table_t', acc') — table_t' updated in place."""
    d, h = table_t.shape
    u = rows.shape[0]
    blk = ROW_BLOCK // LANE
    return pl.pallas_call(
        functools.partial(_apply_kernel, eps=eps),
        grid=(u // ROW_BLOCK,),
        in_specs=[
            pl.BlockSpec((ROW_BLOCK,), lambda i: (i,),
                         memory_space=MemorySpace.SMEM),        # rows
            pl.BlockSpec(memory_space=MemorySpace.SMEM),         # lr
            pl.BlockSpec((blk, d, LANE), lambda i: (i, 0, 0)),   # gsum
            pl.BlockSpec((blk, LANE), lambda i: (i, 0)),         # acc
            pl.BlockSpec(memory_space=MemorySpace.ANY),          # table
        ],
        out_specs=[
            pl.BlockSpec(memory_space=MemorySpace.ANY),
            pl.BlockSpec((blk, LANE), lambda i: (i, 0)),
        ],
        scratch_shapes=[
            MemorySpace.VMEM((d, LANE), table_t.dtype),
            MemorySpace.SMEM((1,), jnp.int32),
            SemaphoreType.DMA((1,)),
        ],
        out_shape=[jax.ShapeDtypeStruct((d, h), table_t.dtype),
                   jax.ShapeDtypeStruct((u // LANE, LANE), jnp.float32)],
        input_output_aliases={4: 0},
        interpret=interpret,
        name="rowwise_adagrad_apply",
    )(rows, lr, gsum_t, acc, table_t)


def rowwise_adagrad_apply(table: jax.Array, accum: jax.Array,
                          rows: jax.Array, gsum: jax.Array, lr,
                          eps: float = 1e-8, interpret: bool = False
                          ) -> tuple[jax.Array, jax.Array]:
    """Apply row-wise AdaGrad to `rows` of `table` through the kernel.

    table: (H, d); accum: (H,) fp32; rows: (U,) int32 sorted ascending
    among its valid (>= 0) entries, each valid row at most once; gsum:
    (U, d) fp32 aggregated gradients aligned with `rows`. Returns (table',
    accum'). The table moves through the kernel as its (d, H) view, so a
    height that is a multiple of 128 (every placement plan's,
    core/placement.py) is updated in place with no copy; other heights are
    padded for the call."""
    h, d = table.shape
    valid = rows >= 0
    safe = jnp.where(valid, rows, 0)
    acc_u = jnp.where(valid, accum[safe], 0.0)
    rows_p = pad_axis(rows.astype(jnp.int32), ROW_BLOCK, 0, value=-1)
    u = rows_p.shape[0]
    g = pad_axis(gsum.astype(jnp.float32), ROW_BLOCK, 0)
    gsum_t = g.reshape(u // LANE, LANE, d).transpose(0, 2, 1)
    acc_p = pad_axis(acc_u, ROW_BLOCK, 0).reshape(u // LANE, LANE)
    table_t = pad_axis(table.T, LANE, 1)
    new_t, acc_new = rowwise_adagrad_apply_kernel(
        table_t, rows_p, gsum_t, acc_p,
        jnp.asarray(lr, jnp.float32).reshape(1), eps=eps,
        interpret=interpret)
    drop = jnp.where(valid, rows, h)                  # h = dropped
    new_accum = accum.at[drop].set(acc_new.reshape(u)[:rows.shape[0]],
                                   mode="drop")
    return new_t[:, :h].T, new_accum
