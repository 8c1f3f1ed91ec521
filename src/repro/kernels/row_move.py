"""Pallas TPU kernel: copy rows between two tables in place, and the
"rows on lanes" layout every row kernel of this package shares.

Layout. Production tables are (rows, d) with d=64. TPU tiles are (8, 128),
and XLA keeps such an fp32 array with the row axis minor, so a (1, 64) row
slice cannot be DMA'd, and padding the table to 128 lanes would copy it on
every call. Row kernels therefore work on the free transposed view
(d, rows): row r is lane r % 128 of the (d, 128) tile r // 128. Heights
that are multiples of 128 (every placement plan's, core/placement.py) need
no copy. Row ids ride in SMEM one `ROW_BLOCK` block per grid step, never
whole: a production batch's row list (B*F*L = 1.7M) is far larger than
SMEM.

`move_rows` copies row src_rows[i] of one table into row dst_rows[i] of
another. Per entry the source tile is DMA'd into VMEM (skipped when it is
already resident), the column is rotated onto the destination lane, and
the destination tile is merged under a lane mask and written back when the
walk leaves it, so contiguous rows share one tile DMA. The cached tier's
exchange, fetch and commit (cache_ops.py) and the TPU embedding gather
(`gather_rows`, core/embedding.py) are passes of this one kernel; the
row-wise AdaGrad apply (sparse_update.py) walks tiles the same way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.tpu import MemorySpace, SemaphoreType

LANE = 128
#: row ids per grid step; a 1-D int32 SMEM block must be 1024 long to match
#: XLA's T(1024) layout for the row-id array
ROW_BLOCK = 1024


def pad_axis(x: jax.Array, mult: int, axis: int, value=0) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _move_kernel(src_rows_ref, dst_rows_ref, src_ref, dst_in, dst_out,
                 src_tile, dst_tile, cur, sems):
    """One grid step: ROW_BLOCK entries; entry j copies src column
    src_rows_ref[j] into dst column dst_rows_ref[j] (either -1 = skip).

    src_rows/dst_rows: (ROW_BLOCK,) SMEM; src_ref: (d, R) HBM read-only;
    dst_in/dst_out: (d, C) HBM aliased; src_tile/dst_tile: (d, 128) VMEM;
    cur: (2,) SMEM, the resident source / destination tile (-1 = none)."""
    del dst_in                         # aliased: read through dst_out
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)

    def tile_dma(hbm_ref, tile, t, to_hbm, sem):
        """Move tile t between HBM and a VMEM tile (blocking)."""
        hbm = hbm_ref.at[:, pl.ds(pl.multiple_of(t * LANE, LANE), LANE)]
        src, dst = (tile, hbm) if to_hbm else (hbm, tile)
        cp = pltpu.make_async_copy(src, dst, sem)
        cp.start()
        cp.wait()

    cur[0] = -1
    cur[1] = -1

    def body(j, carry):
        s = src_rows_ref[j]
        t = dst_rows_ref[j]

        @pl.when((s >= 0) & (t >= 0))
        def _():
            @pl.when(s // LANE != cur[0])
            def _():
                tile_dma(src_ref, src_tile, s // LANE, False, sems.at[0])
                cur[0] = s // LANE

            @pl.when(t // LANE != cur[1])
            def _():
                @pl.when(cur[1] >= 0)
                def _():
                    tile_dma(dst_out, dst_tile, cur[1], True, sems.at[1])
                tile_dma(dst_out, dst_tile, t // LANE, False, sems.at[1])
                cur[1] = t // LANE

            shift = (t % LANE - s % LANE + LANE) % LANE
            col = pltpu.roll(src_tile[...], shift, 1)
            dst_tile[...] = jnp.where(lane == t % LANE, col, dst_tile[...])

        return carry

    jax.lax.fori_loop(0, ROW_BLOCK, body, 0)

    @pl.when(cur[1] >= 0)
    def _():
        tile_dma(dst_out, dst_tile, cur[1], True, sems.at[1])


def move_rows_kernel(src_t: jax.Array, dst_t: jax.Array,
                     src_rows: jax.Array, dst_rows: jax.Array,
                     interpret: bool = False) -> jax.Array:
    """src_t: (d, R), dst_t: (d, C) rows-on-lanes views with R, C % 128 ==
    0 and one dtype; src_rows/dst_rows: (N,) int32 with N % ROW_BLOCK == 0
    (-1 = skip). Destination rows must be distinct. Returns dst_t with
    dst_t[:, dst_rows[i]] = src_t[:, src_rows[i]], updated in place."""
    d, c = dst_t.shape
    n = src_rows.shape[0]
    return pl.pallas_call(
        _move_kernel,
        grid=(n // ROW_BLOCK,),
        in_specs=[
            pl.BlockSpec((ROW_BLOCK,), lambda i: (i,),
                         memory_space=MemorySpace.SMEM),
            pl.BlockSpec((ROW_BLOCK,), lambda i: (i,),
                         memory_space=MemorySpace.SMEM),
            pl.BlockSpec(memory_space=MemorySpace.ANY),
            pl.BlockSpec(memory_space=MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=MemorySpace.ANY),
        scratch_shapes=[
            MemorySpace.VMEM((d, LANE), src_t.dtype),
            MemorySpace.VMEM((d, LANE), dst_t.dtype),
            MemorySpace.SMEM((2,), jnp.int32),
            SemaphoreType.DMA((2,)),
        ],
        out_shape=jax.ShapeDtypeStruct((d, c), dst_t.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
        name="move_rows",
    )(src_rows, dst_rows, src_t, dst_t)


def move_rows(src: jax.Array, dst: jax.Array, src_rows: jax.Array,
              dst_rows: jax.Array, interpret: bool = False) -> jax.Array:
    """dst with dst[dst_rows[i]] = src[src_rows[i]] for every entry where
    both are >= 0, through `move_rows_kernel` on the (d, rows) views.
    src: (R, d); dst: (C, d); src_rows/dst_rows: (N,) int32."""
    c = dst.shape[0]
    src_rows = pad_axis(src_rows.astype(jnp.int32), ROW_BLOCK, 0, -1)
    dst_rows = pad_axis(dst_rows.astype(jnp.int32), ROW_BLOCK, 0, -1)
    out = move_rows_kernel(pad_axis(src.T, LANE, 1),
                           pad_axis(dst.T, LANE, 1), src_rows, dst_rows,
                           interpret=interpret)
    return out[:, :c].T


def gather_rows(table: jax.Array, rows: jax.Array,
                interpret: bool = False) -> jax.Array:
    """(N, d) rows `table[rows]`, zeros where rows < 0, read in place by
    the row-move kernel; an XLA gather would first relayout the whole
    narrow table into a padded row-major copy (2x its bytes at d=64)."""
    rows = rows.astype(jnp.int32)
    n = rows.shape[0]
    return move_rows(table, jnp.zeros((n, table.shape[1]), table.dtype),
                     rows, jnp.arange(n, dtype=jnp.int32), interpret)
