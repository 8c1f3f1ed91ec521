"""Pallas TPU kernels: fused multi-hot embedding gather + pooling.

Two forward designs (docs/embedding_forward.md):

* `embedding_bag_kernel` — the legacy one-bag-per-grid-step layout: bag
  indices are scalar-prefetched into SMEM so they can drive row DMAs; each
  grid step owns one bag and double-buffers row copies HBM->VMEM (fetch row
  l+1 while accumulating row l), pooling in fp32 VREGs. Every valid lookup
  slot costs one irregular HBM row read — the paper's "irregular vector
  access" bottleneck (section III-A.2) — so a Zipf-skewed batch re-reads
  its hot rows many times per step.

* `dedup_embedding_bag_kernel` — the plan-driven dedup'd layout: the
  batch's CSR bucketing plan (kernels/sparse_plan.py) is scalar-prefetched;
  each grid step owns a TILE of unique rows and streams them HBM->VMEM
  through an `nbuf`-deep DMA slot rotation (deeper than the legacy 2-slot
  pipeline), then expands each row into every bag that references it via
  the plan's CSR slice. Accumulation happens in the VMEM-resident
  (n_bags, D) output block — revisited by every grid step — so each unique
  row is read from HBM exactly ONCE per batch no matter how many bags
  reference it: forward row traffic drops by the batch duplication factor
  (`launch.analysis.embedding_forward_traffic`).

The embedding dim D is padded to the 128-lane width by the ops.py wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.experimental.pallas.tpu import MemorySpace, SemaphoreType

# the dedup kernel keeps the whole pooled output resident in VMEM across
# the grid; beyond this it must fall back to the legacy kernel (bag-tiled
# output is the tracked follow-on, docs/embedding_forward.md)
_DEDUP_OUT_VMEM_BYTES = 8 * 2**20


def _bag_kernel(idx_ref, table_ref, out_ref, rows_vmem, sems, *,
                max_len: int, mode: str):
    """One grid step = one bag. idx_ref: (B, L) SMEM; table_ref: (H, D) HBM;
    out_ref: (1, D) VMEM block; rows_vmem: (2, 1, D) scratch; sems: 2 DMAs."""
    b = pl.program_id(0)
    d = out_ref.shape[-1]

    def row_copy(slot, j):
        """DMA descriptor for bag row j into double-buffer slot."""
        # ONE descriptor builder serves both start() and wait(): a DMA must
        # be awaited with the descriptor it was started with (any slice of
        # equal shape happens to work, but a mismatched source is latent
        # fragility the moment the shapes stop agreeing)
        ix = jnp.maximum(idx_ref[b, j], 0)
        return pltpu.make_async_copy(table_ref.at[pl.ds(ix, 1)],
                                     rows_vmem.at[slot], sems.at[slot])

    row_copy(0, 0).start()

    def body(j, carry):
        """Pool one bag member; prefetches the next behind it."""
        acc, cnt = carry
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < max_len)
        def _():
            row_copy(jax.lax.rem(j + 1, 2), j + 1).start()

        row_copy(slot, j).wait()
        valid = idx_ref[b, j] >= 0
        acc = acc + jnp.where(valid,
                              rows_vmem[slot].astype(jnp.float32), 0.0)
        cnt = cnt + jnp.where(valid, 1.0, 0.0)
        return acc, cnt

    acc, cnt = jax.lax.fori_loop(
        0, max_len, body,
        (jnp.zeros((1, d), jnp.float32), jnp.zeros((), jnp.float32)))
    if mode == "mean":
        acc = acc / jnp.maximum(cnt, 1.0)
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("mode", "interpret"))
def embedding_bag_kernel(table: jax.Array, indices: jax.Array,
                         mode: str = "sum",
                         interpret: bool = False) -> jax.Array:
    """table: (H, D) with D a multiple of 128 (pad in ops.py);
    indices: (B, L) int32 (-1 pads). Returns (B, D) pooled rows."""
    b, max_len = indices.shape
    _, d = table.shape
    kernel = functools.partial(_bag_kernel, max_len=max_len, mode=mode)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[pl.BlockSpec(memory_space=MemorySpace.ANY)],
            out_specs=pl.BlockSpec((1, d), lambda i, idx_ref: (i, 0)),
            scratch_shapes=[
                MemorySpace.VMEM((2, 1, d), table.dtype),
                SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, d), table.dtype),
        interpret=interpret,
    )(indices, table)


# ---------------------------------------------------------------------------
# dedup'd plan-driven forward
# ---------------------------------------------------------------------------


def _dedup_bag_kernel(uniq_ref, off_ref, bag_ref, table_ref, out_ref,
                      rows_vmem, sems, *, tile: int, nbuf: int):
    """Grid step t gathers-and-expands unique rows [t*tile, (t+1)*tile).

    uniq_ref: (U,), off_ref: (U+1,), bag_ref: (N,) SMEM (scalar prefetch;
    U is padded to a tile multiple by the wrapper, pads are -1);
    table_ref: (H, D) HBM; out_ref: (n_bags, D) fp32 VMEM block whose index
    map is CONSTANT — the accumulator stays resident across the whole grid
    and spills to HBM once at the end; rows_vmem: (nbuf, 1, D) DMA slot
    rotation; sems: (nbuf,) DMA semaphores.

    Valid unique rows form a prefix (the planner sorts, -1 pads trail), so
    a skipped row never precedes a live one — the pipeline never stalls on
    phantom fetches.
    """
    t = pl.program_id(0)
    base = t * tile

    @pl.when(t == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    def row_copy(r):
        """DMA descriptor for unique row r into its ring slot."""
        # same-descriptor start/wait discipline as _bag_kernel
        ix = jnp.maximum(uniq_ref[base + r], 0)
        slot = jax.lax.rem(r, nbuf)
        return pltpu.make_async_copy(table_ref.at[pl.ds(ix, 1)],
                                     rows_vmem.at[slot], sems.at[slot])

    def start(r):
        """Kick off row r's fetch (live rows only)."""
        @pl.when(uniq_ref[base + r] >= 0)
        def _():
            row_copy(r).start()

    for r in range(min(nbuf, tile)):      # static warmup: fill the pipeline
        start(r)

    def body(r, carry):
        """Await row r, expand its CSR runs, refill the drained slot."""
        valid = uniq_ref[base + r] >= 0

        @pl.when(valid)
        def _():
            row_copy(r).wait()

        # load to VREGs, then immediately refill the drained slot so the
        # next fetch overlaps this row's CSR expansion
        row = rows_vmem[jax.lax.rem(r, nbuf)].astype(jnp.float32)

        @pl.when(r + nbuf < tile)
        def _():
            start(r + nbuf)

        @pl.when(valid)
        def _():
            def expand(j, c):
                """Accumulate the row into bag j's output slot."""
                bag = bag_ref[j]
                out_ref[pl.ds(bag, 1)] = out_ref[pl.ds(bag, 1)] + row
                return c

            jax.lax.fori_loop(off_ref[base + r], off_ref[base + r + 1],
                              expand, 0)

        return carry

    jax.lax.fori_loop(0, tile, body, 0)


@functools.partial(jax.jit, static_argnames=("n_bags", "tile", "nbuf",
                                             "interpret"))
def dedup_embedding_bag_kernel(table: jax.Array, unique_rows: jax.Array,
                               bag_offsets: jax.Array, bag_ids: jax.Array,
                               n_bags: int, tile: int = 8, nbuf: int = 4,
                               interpret: bool = False) -> jax.Array:
    """table: (H, D) with D a multiple of 128 (pad in ops.py); plan arrays
    from kernels/sparse_plan.py (int32, possibly capacity-trimmed); n_bags
    static (= B*F). Returns (n_bags, D) fp32 SUM-pooled bags (mean and the
    output cast are applied by the ops.py wrapper).

    Per-bag accumulation arrives in sorted-row (CSR) order, not flat slot
    order — tested allclose against the oracle like every kernel body; the
    jnp fallback (`ref.dedup_embedding_bag_ref`) is the bit-exact contract.
    """
    _, d = table.shape
    u = unique_rows.shape[0]
    up = max(tile, -(-u // tile) * tile)   # >= one step: step 0 zeroes out
    if up != u:                            # pad U to a tile multiple
        unique_rows = jnp.pad(unique_rows, (0, up - u), constant_values=-1)
        bag_offsets = jnp.pad(bag_offsets, (0, up - u), mode="edge")
    nb = -(-n_bags // 8) * 8               # sublane-align the out block
    if nb * d * 4 > _DEDUP_OUT_VMEM_BYTES:
        raise ValueError(
            f"dedup forward out block {nb}x{d} fp32 exceeds the "
            f"{_DEDUP_OUT_VMEM_BYTES >> 20}MiB VMEM budget — use the "
            "legacy kernel (bag-tiled dedup output is the tracked "
            "follow-on, docs/embedding_forward.md)")
    kernel = functools.partial(_dedup_bag_kernel, tile=tile, nbuf=nbuf)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(up // tile,),
            in_specs=[pl.BlockSpec(memory_space=MemorySpace.ANY)],  # table
            out_specs=pl.BlockSpec((nb, d), lambda t, u_, o_, b_: (0, 0)),
            scratch_shapes=[
                MemorySpace.VMEM((nbuf, 1, d), table.dtype),
                SemaphoreType.DMA((nbuf,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((nb, d), jnp.float32),
        interpret=interpret,
    )(unique_rows, bag_offsets, bag_ids, table)
    return out[:n_bags]
