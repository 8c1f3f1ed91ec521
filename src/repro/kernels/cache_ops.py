"""Pallas TPU kernel: batched capacity<->cache row moves for the cached
embedding tier.

The cached embedding tier (core/cache.py) keeps the mega table in a slow
"capacity" array and a fixed-size hot-row cache on device. Each step the
manager emits a per-slot WORKLIST: slot i may first write its dirty victim
row back to capacity (eviction-writeback) and then be refilled from a
missed capacity row (fetch-on-miss). Every operation here — the fused
`cache_exchange`, the split async `cache_fetch` / `cache_fetch_chunked` /
`cache_commit` — is one or two passes of ONE kernel, `move_rows`: copy
row src_rows[i] of one table into row dst_rows[i] of another, in place.
The small per-row side arrays (row-wise AdaGrad accumulators, LFU scores)
move with XLA gathers/scatters on their 1-D arrays.

The kernel and its (d, rows) layout live in row_move.py; heights
that are multiples of 128 (every placement plan's) move in place with no
copy. The worklist rides in SMEM one `ROW_BLOCK` block per grid step, never
whole. The wrappers dispatch: the kernel on TPU (or `interpret=True` for
tests), the pure-jnp oracle (kernels/ref.py) otherwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.ops import use_pallas
from repro.kernels.row_move import ROW_BLOCK, gather_rows, move_rows


def _valid_or_skip(mask: jax.Array, rows: jax.Array) -> jax.Array:
    return jnp.where(mask, rows, -1)


def _bucket(*rows: jax.Array) -> list[jax.Array]:
    """Pad worklist arrays with -1 (skip) to a power-of-two length of at
    least ROW_BLOCK, so the per-batch miss counts of a serving or training
    run compile a handful of kernel variants, not one per count."""
    n = rows[0].shape[0]
    target = max(ROW_BLOCK, 1 << max(n - 1, 0).bit_length())
    return [jnp.pad(r, (0, target - n), constant_values=-1) for r in rows]


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0, 1, 2, 3, 4))
def _exchange_kernel_jit(capacity, cache, cap_accum, cache_accum, freq,
                         slots, evict_rows, fetch_rows, counts, interpret):
    """Writeback pass then fetch pass (entry order is immaterial: slots are
    distinct and evicted rows never coincide with fetched ones), the
    accumulators and LFU seeds moved exactly as `ref.cache_exchange_ref`
    moves them."""
    r, c = capacity.shape[0], cache.shape[0]
    live = slots >= 0
    wb = live & (evict_rows >= 0)
    ft = live & (fetch_rows >= 0)
    safe_slot = jnp.where(live, slots, 0)
    capacity = move_rows(cache, capacity, _valid_or_skip(wb, slots),
                         evict_rows, interpret)
    cache = move_rows(capacity, cache, fetch_rows,
                      _valid_or_skip(ft, slots), interpret)
    cap_accum = cap_accum.at[jnp.where(wb, evict_rows, r)].set(
        cache_accum[safe_slot], mode="drop")
    dst = jnp.where(ft, slots, c)                             # c drops
    cache_accum = cache_accum.at[dst].set(
        cap_accum[jnp.where(ft, fetch_rows, 0)], mode="drop")
    freq = freq.at[dst].set(counts.astype(freq.dtype), mode="drop")
    return capacity, cache, cap_accum, cache_accum, freq


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fetch_kernel_jit(capacity, cap_accum, fetch_rows, interpret):
    """Gather into a fresh zero slab: -1 entries leave zero rows."""
    shadow = gather_rows(capacity, fetch_rows, interpret)
    valid = fetch_rows >= 0
    return shadow, jnp.where(valid, cap_accum[jnp.where(valid, fetch_rows,
                                                        0)], 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0, 1, 2, 3))
def _commit_kernel_jit(capacity, cache, cap_accum, cache_accum, shadow,
                       shadow_accum, slots, evict_rows, fetch_rows, src_pos,
                       interpret):
    """Writeback pass then install pass, side arrays as
    `ref.cache_commit_ref` moves them."""
    r, c = capacity.shape[0], cache.shape[0]
    live = slots >= 0
    wb = live & (evict_rows >= 0)
    ft = live & (fetch_rows >= 0)
    safe_slot = jnp.where(live, slots, 0)
    capacity = move_rows(cache, capacity, _valid_or_skip(wb, slots),
                         evict_rows, interpret)
    cache = move_rows(shadow.astype(cache.dtype), cache,
                      _valid_or_skip(ft, src_pos),
                      _valid_or_skip(ft, slots), interpret)
    cap_accum = cap_accum.at[jnp.where(wb, evict_rows, r)].set(
        cache_accum[safe_slot], mode="drop")
    cache_accum = cache_accum.at[jnp.where(ft, slots, c)].set(
        shadow_accum[src_pos], mode="drop")
    return capacity, cache, cap_accum, cache_accum


# ---------------------------------------------------------------------------
# public wrappers (kernel on TPU / interpret, jnp oracle on CPU)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def _exchange_ref_jit(capacity, cache, cap_accum, cache_accum, freq,
                      slots, evict_rows, fetch_rows, counts):
    return ref.cache_exchange_ref(capacity, cache, cap_accum, cache_accum,
                                  freq, slots, evict_rows, fetch_rows, counts)


def cache_exchange(capacity: jax.Array, cache: jax.Array,
                   cap_accum: jax.Array, cache_accum: jax.Array,
                   freq: jax.Array, slots: jax.Array, evict_rows: jax.Array,
                   fetch_rows: jax.Array, counts: jax.Array,
                   use_kernel: bool | None = None,
                   interpret: bool = False
                   ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                              jax.Array]:
    """Batched eviction-writeback + fetch-on-miss between the capacity tier
    and the device cache. See ref.cache_exchange_ref for the worklist
    contract. Returns (capacity', cache', cap_accum',
    cache_accum', freq').

    ALL FIVE ARRAYS ARE DONATED: the swap must update a few rows in place,
    not move the whole capacity tier through memory — callers (core/cache.py
    owns its buffers, see init_state) must use the returned arrays."""
    slots = slots.astype(jnp.int32)
    evict_rows = evict_rows.astype(jnp.int32)
    fetch_rows = fetch_rows.astype(jnp.int32)
    counts = counts.astype(jnp.float32)
    if use_pallas(use_kernel) or interpret:
        slots, evict_rows, fetch_rows = _bucket(slots, evict_rows,
                                                fetch_rows)
        counts = jnp.pad(counts, (0, slots.shape[0] - counts.shape[0]))
        return _exchange_kernel_jit(capacity, cache, cap_accum, cache_accum,
                                    freq, slots, evict_rows, fetch_rows,
                                    counts, interpret)
    return _exchange_ref_jit(capacity, cache, cap_accum, cache_accum,
                             freq, slots, evict_rows, fetch_rows, counts)


@functools.partial(jax.jit)
def _fetch_ref_jit(capacity, cap_accum, fetch_rows):
    return ref.cache_fetch_ref(capacity, cap_accum, fetch_rows)


def cache_fetch(capacity: jax.Array, cap_accum: jax.Array,
                fetch_rows: jax.Array, use_kernel: bool | None = None,
                interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """FETCH half of the split async exchange: gather `fetch_rows` (+ their
    accumulators) from the capacity tier into a fresh shadow slab. Read-only
    on the tiers (nothing donated) so it overlaps the in-flight batch's
    compute. Returns (shadow (N, D), shadow_accum (N,))."""
    fetch_rows = fetch_rows.astype(jnp.int32)
    if use_pallas(use_kernel) or interpret:
        n = fetch_rows.shape[0]
        shadow, shadow_acc = _fetch_kernel_jit(
            capacity, cap_accum, _bucket(fetch_rows)[0], interpret)
        return shadow[:n], shadow_acc[:n]
    return _fetch_ref_jit(capacity, cap_accum, fetch_rows)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _fetch_chunked_ref_jit(capacity, cap_accum, chunk_starts, chunk):
    return ref.cache_fetch_chunked_ref(capacity, cap_accum, chunk_starts,
                                       chunk)


def cache_fetch_chunked(capacity: jax.Array, cap_accum: jax.Array,
                        chunk_starts: jax.Array, chunk: int,
                        use_kernel: bool | None = None,
                        interpret: bool = False
                        ) -> tuple[jax.Array, jax.Array]:
    """CHUNK-granular fetch: gather K contiguous `chunk`-row capacity blocks
    (+ accumulators) into one (K*chunk, D) shadow slab — one tile DMA per
    block rather than one per row. `chunk_starts` comes from kernels/sparse_plan.coalesce_rows
    (starts clamped so start+chunk <= R; -1 = pad, zero block). Read-only on
    the tiers, same overlap contract as `cache_fetch`. Pair with
    `cache_commit(..., src_pos=pos)` to install individual rows out of the
    block slab. Returns (shadow (K*chunk, D), shadow_accum (K*chunk,))."""
    chunk_starts = chunk_starts.astype(jnp.int32)
    if use_pallas(use_kernel) or interpret:
        # the block's rows share (d, 128) tiles, so the row-move kernel
        # reads each tile once: one DMA per block, as the ref slab layout
        rows = chunk_starts[:, None] + jnp.arange(chunk, dtype=jnp.int32)
        rows = jnp.where(chunk_starts[:, None] >= 0, rows, -1).reshape(-1)
        return cache_fetch(capacity, cap_accum, rows, interpret=interpret,
                           use_kernel=use_kernel)
    return _fetch_chunked_ref_jit(capacity, cap_accum, chunk_starts, chunk)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _commit_ref_jit(capacity, cache, cap_accum, cache_accum, shadow,
                    shadow_accum, slots, evict_rows, fetch_rows, src_pos):
    return ref.cache_commit_ref(capacity, cache, cap_accum, cache_accum,
                                shadow, shadow_accum, slots, evict_rows,
                                fetch_rows, src_pos)


def cache_commit(capacity: jax.Array, cache: jax.Array, cap_accum: jax.Array,
                 cache_accum: jax.Array, shadow: jax.Array,
                 shadow_accum: jax.Array, slots: jax.Array,
                 evict_rows: jax.Array, fetch_rows: jax.Array,
                 use_kernel: bool | None = None,
                 interpret: bool = False,
                 src_pos: jax.Array | None = None
                 ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """COMMIT half of the split async exchange: dirty-victim writeback
    (cache slot -> capacity row, reading the post-update cache) + shadow row
    -> cache slot install, at a step boundary. `fetch_rows` is the worklist
    the shadow slab was fetched with; -1 entries gate the install off
    (pure writeback). `src_pos` maps worklist entry i to its shadow row
    (default arange(n), the one-row-per-entry slab; pass the coalescer's
    `pos` for a chunk-granular slab). The four tier arrays are DONATED
    (in-place row swap, same contract as cache_exchange) — callers must use
    the returned arrays. Returns (capacity', cache', cap_accum',
    cache_accum')."""
    slots = slots.astype(jnp.int32)
    evict_rows = evict_rows.astype(jnp.int32)
    fetch_rows = fetch_rows.astype(jnp.int32)
    n = slots.shape[0]
    if src_pos is None:
        src_pos = jnp.arange(n, dtype=jnp.int32)
    else:
        src_pos = src_pos.astype(jnp.int32)
    if use_pallas(use_kernel) or interpret:
        slots, evict_rows, fetch_rows, src_pos = _bucket(
            slots, evict_rows, fetch_rows, src_pos)
        return _commit_kernel_jit(capacity, cache, cap_accum, cache_accum,
                                  shadow, shadow_accum, slots, evict_rows,
                                  fetch_rows, src_pos, interpret)
    return _commit_ref_jit(capacity, cache, cap_accum, cache_accum,
                           shadow, shadow_accum, slots, evict_rows,
                           fetch_rows, src_pos)


@functools.partial(jax.jit, static_argnames=("decay",))
def lfu_touch(freq: jax.Array, slots: jax.Array, counts: jax.Array,
              decay: float = 0.8) -> jax.Array:
    """LFU-with-decay hit accounting: freq' = decay * freq then
    freq'[slots] += counts. Dense decay + sparse scatter-add lower to
    efficient XLA on every backend, so there is one path (ref)."""
    return ref.lfu_touch_ref(freq, slots.astype(jnp.int32),
                             counts.astype(jnp.float32), decay)
