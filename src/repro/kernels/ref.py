"""Pure-jnp oracles for every kernel in this package.

These are the correctness references (tests assert_allclose kernels against
them) AND the CPU fallback path used when running the full system without a
TPU. They are written for clarity, not speed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def embedding_bag_ref(table: jax.Array, indices: jax.Array,
                      mode: str = "sum") -> jax.Array:
    """Multi-hot embedding lookup + pooling.

    table: (H, D); indices: (B, L) int32, -1 = padding slot.
    Returns (B, D) pooled embeddings (sum or mean over valid slots).
    """
    valid = indices >= 0
    rows = table[jnp.maximum(indices, 0)]                    # (B, L, D)
    rows = jnp.where(valid[..., None], rows.astype(jnp.float32), 0.0)
    out = rows.sum(axis=1)
    if mode == "mean":
        cnt = jnp.maximum(valid.sum(axis=1, keepdims=True), 1)
        out = out / cnt
    return out.astype(table.dtype)


def dedup_embedding_bag_ref(table: jax.Array, indices: jax.Array,
                            unique_rows: jax.Array,
                            mode: str = "sum") -> jax.Array:
    """Plan-shared dedup'd forward (docs/embedding_forward.md), pure jnp —
    BIT-EXACT vs `embedding_bag_ref` on the same (table, indices) whenever
    `unique_rows` covers every valid index (the planner contract): the
    (H, D) table is gathered ONCE per plan entry (U rows, not B*L), each
    lookup slot then reads its row from that compact buffer through an
    index-only searchsorted remap, and the masked pooling that follows is
    the SAME expression as the legacy oracle — identical float values
    through an identical reduction (asserted in tests/test_dedup_forward.py).

    table: (H, D); indices: (B, L) int32, -1 = padding; unique_rows: (U,)
    the plan's unique rows, live prefix strictly ascending, -1 past the
    unique count. Returns (B, D).
    """
    sent = jnp.where(unique_rows >= 0, unique_rows,
                     jnp.iinfo(jnp.int32).max)        # -1 tail sorts last
    compact = table[jnp.maximum(unique_rows, 0)]      # the ONLY table gather
    valid = indices >= 0
    pos = jnp.searchsorted(sent, jnp.maximum(indices, 0).reshape(-1))
    rows = compact[pos].reshape(*indices.shape, -1)   # (B, L, D)
    rows = jnp.where(valid[..., None], rows.astype(jnp.float32), 0.0)
    out = rows.sum(axis=1)
    if mode == "mean":
        cnt = jnp.maximum(valid.sum(axis=1, keepdims=True), 1)
        out = out / cnt
    return out.astype(table.dtype)


def dot_interaction_ref(z: jax.Array) -> jax.Array:
    """Pairwise dot-product feature interaction (paper section III-A.3).

    z: (B, F, D) stacked feature vectors (dense projection + pooled EMBs).
    Returns (B, F*(F-1)//2): strictly-lower-triangle of z @ z^T per example.
    """
    f = z.shape[1]
    s = jnp.einsum("bfd,bgd->bfg", z.astype(jnp.float32),
                   z.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    rows, cols = np.tril_indices(f, -1)
    return s[:, rows, cols].astype(z.dtype)


def rowwise_adagrad_ref(table: jax.Array, accum: jax.Array,
                        indices: jax.Array, grads: jax.Array,
                        lr: float, eps: float = 1e-8):
    """Deduplicating sparse row-wise AdaGrad (the paper's 'gradient
    aggregation' step).

    table: (H, D); accum: (H,) row-wise second-moment; indices: (N,) int32
    (-1 = padding); grads: (N, D) per-lookup gradients.

    Duplicate rows are aggregated FIRST, then a single update is applied —
    matching a synchronous dedup (not HogWild's racy per-duplicate applies).
    Returns (new_table, new_accum).
    """
    h, d = table.shape
    valid = indices >= 0
    idx = jnp.where(valid, indices, h)                       # h = sentinel
    gsum = jnp.zeros((h + 1, d), jnp.float32).at[idx].add(
        jnp.where(valid[:, None], grads.astype(jnp.float32), 0.0))[:h]
    touched = jnp.zeros((h + 1,), bool).at[idx].set(valid)[:h]
    g2 = jnp.mean(jnp.square(gsum), axis=-1)                 # (H,)
    new_accum = accum + jnp.where(touched, g2, 0.0)
    upd = lr * gsum * jax.lax.rsqrt(new_accum[:, None] + eps)
    new_table = table - jnp.where(touched[:, None], upd, 0.0
                                  ).astype(table.dtype)
    return new_table.astype(table.dtype), new_accum


def dedup_grads_ref(indices: jax.Array, grads: jax.Array, num_rows: int):
    """Aggregate per-lookup grads into unique-row grads — O(n log n) in the
    number of LOOKUPS (sort + run-length segment sum), independent of the
    table height (the paper's flat CPU hash-size curve, Fig. 12, depends on
    exactly this property).

    Returns (unique_idx (N,), summed_grads (N, D)): each unique row appears
    once (at its run head in sorted order); all other slots are -1 / zeros —
    the layout the rowwise_adagrad kernel consumes (it skips -1).
    """
    n, d = grads.shape
    valid = indices >= 0
    safe = jnp.where(valid, indices, num_rows)               # pads sort last
    order = jnp.argsort(safe)
    s_idx = safe[order]
    s_g = jnp.where(valid[order][:, None], grads[order].astype(jnp.float32),
                    0.0)
    is_head = jnp.concatenate(
        [jnp.ones((1,), bool), s_idx[1:] != s_idx[:-1]])
    seg = jnp.cumsum(is_head) - 1                            # run id per slot
    gsum_by_run = jax.ops.segment_sum(s_g, seg, num_segments=n)
    s_valid = s_idx < num_rows
    uniq = jnp.where(is_head & s_valid, s_idx, -1).astype(jnp.int32)
    gsum = jnp.where((is_head & s_valid)[:, None], gsum_by_run[seg], 0.0)
    return uniq, gsum


def bag_grad_sums(unique_rows: jax.Array, bag_offsets: jax.Array,
                  bag_ids: jax.Array, pooled: jax.Array) -> jax.Array:
    """Aggregate POOLED bag gradients into per-unique-row sums through a
    `SparsePlan` (kernels/sparse_plan.py) — the index-only replacement for
    broadcast-then-dedup: nothing `(B*F*L, D)`-shaped is built before this
    gather, and XLA fuses the gather into the segment sum.

    unique_rows: (U,); bag_offsets: (U+1,); bag_ids: (N,) — U may be
    smaller than N for a capacity-trimmed plan; pooled: (B*F, D) fp32.
    Returns (U, D) fp32 `gsum` aligned with `unique_rows` (zeros past the
    unique count). Slots within a run arrive in flat-batch order (the
    planner's stable sort), so each row's accumulation order — and hence
    its bits — matches the legacy per-lookup scatter-add.
    """
    n = bag_ids.shape[0]
    u = bag_offsets.shape[0] - 1                    # plan's unique capacity
    n_valid = bag_offsets[u]                        # planner fills tail
    pos = jnp.arange(n)
    # run id per sorted slot, O(n): count the run starts at or before each
    # position (phantom runs all "start" at n_valid, inflating only the
    # dead tail, which is routed to the dropped segment below)
    marks = jnp.zeros((n + 1,), jnp.int32).at[bag_offsets[1:]].add(1)
    seg = jnp.cumsum(marks[:n])
    seg = jnp.where(pos < n_valid, seg, u)          # u = dropped
    contrib = pooled[bag_ids].astype(jnp.float32)   # dead slots drop via seg
    return jax.ops.segment_sum(contrib, seg, num_segments=u + 1)[:u]


def fused_bag_backward_adagrad_ref(table: jax.Array, accum: jax.Array,
                                   unique_rows: jax.Array,
                                   bag_offsets: jax.Array,
                                   bag_ids: jax.Array, pooled: jax.Array,
                                   lr, eps: float = 1e-8):
    """Oracle for the fused sparse backward (kernels/sparse_update.py):
    gather + aggregate pooled bag grads per unique row, then the row-wise
    AdaGrad apply — one pass, no per-lookup gradient tensor.

    table: (H, D); accum: (H,) fp32; plan arrays as in `SparsePlan`;
    pooled: (B*F, D). Bit-identical to `rowwise_adagrad_ref` fed the legacy
    broadcast per-lookup layout (asserted in tests/test_sparse_fused.py).
    Returns (new_table, new_accum).
    """
    h, _ = table.shape
    gsum = bag_grad_sums(unique_rows, bag_offsets, bag_ids, pooled)
    valid = unique_rows >= 0
    safe = jnp.where(valid, unique_rows, 0)
    drop = jnp.where(valid, unique_rows, h)          # h = dropped
    g2 = jnp.mean(jnp.square(gsum), axis=-1)
    acc_rows = accum[safe] + g2
    upd = lr * gsum * jax.lax.rsqrt(acc_rows[:, None] + eps)
    # invalid entries need no masking: their scatter index is h -> dropped
    new_table = table.at[drop].add(-upd.astype(table.dtype), mode="drop")
    new_accum = accum.at[drop].set(acc_rows, mode="drop")
    return new_table.astype(table.dtype), new_accum


def bag_grad_sums_abs(bag_offsets: jax.Array, bag_ids: jax.Array,
                      pooled: jax.Array) -> jax.Array:
    """`bag_grad_sums` for a SEGMENT whose offsets are ABSOLUTE positions
    into the shared `bag_ids` (a contiguous per-owner slice of a plan,
    `kernels.sparse_plan.split_plan_by_owner`): pairs before bag_offsets[0]
    or at/after bag_offsets[U] belong to other owners and drop; padded rows
    are empty runs (their offsets equal the segment end). Accumulation per
    run stays in ascending pair position — flat-batch order — so each row's
    sum is bit-identical to the unsegmented `bag_grad_sums`'s."""
    n = bag_ids.shape[0]
    u = bag_offsets.shape[0] - 1
    pos = jnp.arange(n)
    # run id per pair: offsets are nondecreasing, so the count of offsets
    # <= pos names the run even across empty (padded) runs
    seg = jnp.searchsorted(bag_offsets, pos, side="right") - 1
    in_seg = (pos >= bag_offsets[0]) & (pos < bag_offsets[u])
    seg = jnp.where(in_seg, jnp.clip(seg, 0, u - 1), u)  # u = dropped
    contrib = pooled[bag_ids].astype(jnp.float32)
    return jax.ops.segment_sum(contrib, seg, num_segments=u + 1)[:u]


def fused_bag_backward_adagrad_abs_ref(table: jax.Array, accum: jax.Array,
                                       unique_rows: jax.Array,
                                       bag_offsets: jax.Array,
                                       bag_ids: jax.Array,
                                       pooled: jax.Array,
                                       lr, eps: float = 1e-8):
    """`fused_bag_backward_adagrad_ref` over a segment plan with ABSOLUTE
    offsets (see `bag_grad_sums_abs`) — the jnp oracle behind the per-owner
    segmented update of the multi-host cached tier (docs/cache.md). Rows
    the segment doesn't cover are untouched; covered rows update with the
    exact unsegmented bits."""
    h, _ = table.shape
    gsum = bag_grad_sums_abs(bag_offsets, bag_ids, pooled)
    valid = unique_rows >= 0
    safe = jnp.where(valid, unique_rows, 0)
    drop = jnp.where(valid, unique_rows, h)          # h = dropped
    g2 = jnp.mean(jnp.square(gsum), axis=-1)
    acc_rows = accum[safe] + g2
    upd = lr * gsum * jax.lax.rsqrt(acc_rows[:, None] + eps)
    new_table = table.at[drop].add(-upd.astype(table.dtype), mode="drop")
    new_accum = accum.at[drop].set(acc_rows, mode="drop")
    return new_table.astype(table.dtype), new_accum


def cache_exchange_ref(capacity: jax.Array, cache: jax.Array,
                       cap_accum: jax.Array, cache_accum: jax.Array,
                       freq: jax.Array, slots: jax.Array,
                       evict_rows: jax.Array, fetch_rows: jax.Array,
                       counts: jax.Array):
    """Oracle for the cache_exchange kernel (cache_ops.py): one batched
    swap between the capacity tier and the device cache.

    capacity: (R, D) slow tier; cache: (C, D) device tier; cap_accum: (R,)
    and cache_accum: (C,) row-wise AdaGrad accumulators riding along;
    freq: (C,) LFU scores. The worklist is per-slot: entry i touches cache
    slot slots[i] (-1 = no-op pad) and
      * writes the slot back to capacity row evict_rows[i] if >= 0
        (dirty-victim writeback), then
      * fills it from capacity row fetch_rows[i] if >= 0 (fetch-on-miss),
        seeding its LFU score with counts[i].
    Worklist slots are distinct and evict/fetch row sets are disjoint
    (the manager's working-set protection guarantees this), so entry
    order does not matter. Returns all five arrays updated.
    """
    r = capacity.shape[0]
    c = cache.shape[0]
    safe_slot = jnp.where(slots >= 0, slots, 0)
    # 1) dirty-victim writeback: cache -> capacity
    wb = jnp.where(evict_rows >= 0, evict_rows, r)          # r drops
    capacity = capacity.at[wb].set(cache[safe_slot], mode="drop")
    cap_accum = cap_accum.at[wb].set(cache_accum[safe_slot], mode="drop")
    # 2) fetch-on-miss: capacity -> cache (+ seed the slot's LFU counter)
    take = jnp.where(fetch_rows >= 0, fetch_rows, 0)
    dst = jnp.where((fetch_rows >= 0) & (slots >= 0), slots, c)  # c drops
    cache = cache.at[dst].set(capacity[take], mode="drop")
    cache_accum = cache_accum.at[dst].set(cap_accum[take], mode="drop")
    freq = freq.at[dst].set(counts.astype(freq.dtype), mode="drop")
    return capacity, cache, cap_accum, cache_accum, freq


def cache_fetch_ref(capacity: jax.Array, cap_accum: jax.Array,
                    fetch_rows: jax.Array):
    """Oracle for the FETCH half of the split async exchange
    (cache_ops.cache_fetch): gather `fetch_rows` (+ their row-wise AdaGrad
    accumulators) from the capacity tier into a fresh SHADOW slab, without
    touching the device cache. -1 entries produce zero rows (padding).

    capacity: (R, D); cap_accum: (R,). Returns (shadow (N, D),
    shadow_accum (N,)). The shadow slab is what the async stream fills
    while the in-flight batch's dense compute runs — see core/cache.py.
    """
    valid = fetch_rows >= 0
    take = jnp.where(valid, fetch_rows, 0)
    shadow = jnp.where(valid[:, None], capacity[take].astype(jnp.float32),
                       0.0).astype(capacity.dtype)
    shadow_accum = jnp.where(valid, cap_accum[take], 0.0)
    return shadow, shadow_accum


def cache_fetch_chunked_ref(capacity: jax.Array, cap_accum: jax.Array,
                            chunk_starts: jax.Array, chunk: int):
    """Oracle for the CHUNK-granular fetch (cache_ops.cache_fetch_chunked).

    Gathers K contiguous row blocks of height `chunk` from the capacity
    tier into one (K*chunk, D) shadow slab — one DMA descriptor per block
    instead of one per row. chunk_starts: (K,) block start rows, already
    clamped so start+chunk <= R (kernels/sparse_plan.coalesce_rows); -1
    entries produce zero blocks (padding). Individual rows are addressed
    inside the slab as k*chunk + (row - chunk_starts[k]) — the `pos` array
    the coalescer returns. Returns (shadow (K*chunk, D),
    shadow_accum (K*chunk,)).
    """
    valid = chunk_starts >= 0
    base = jnp.where(valid, chunk_starts, 0)                  # (K,)
    rows = base[:, None] + jnp.arange(chunk)[None, :]         # (K, chunk)
    rows = rows.reshape(-1)
    keep = jnp.repeat(valid, chunk)
    shadow = jnp.where(keep[:, None], capacity[rows].astype(jnp.float32),
                       0.0).astype(capacity.dtype)
    shadow_accum = jnp.where(keep, cap_accum[rows], 0.0)
    return shadow, shadow_accum


def cache_commit_ref(capacity: jax.Array, cache: jax.Array,
                     cap_accum: jax.Array, cache_accum: jax.Array,
                     shadow: jax.Array, shadow_accum: jax.Array,
                     slots: jax.Array, evict_rows: jax.Array,
                     fetch_rows: jax.Array,
                     src_pos: jax.Array | None = None):
    """Oracle for the COMMIT half of the split async exchange
    (cache_ops.cache_commit): install a previously fetched shadow slab into
    the device cache at a step boundary. Entry i
      * writes cache slot slots[i] (post-update dirty victim) back to
        capacity row evict_rows[i] if >= 0, then
      * overwrites the slot with shadow row src_pos[i] (+ accumulator) if
        fetch_rows[i] >= 0 (pure-writeback entries pass -1 and keep the
        slot's contents). src_pos defaults to arange(n) — the classic
        one-row-per-entry shadow; a chunk-granular fetch passes the
        coalescer's `pos` so entry i reads its row out of the block slab.
    slots[i] < 0 skips the entry. Worklist slots are distinct and the
    evict-row set is disjoint from the fetched rows (the manager's
    working-set protection guarantees both), so entry order does not
    matter. fetch(fetch_rows) + commit over the same worklist is equivalent
    to one cache_exchange_ref call (modulo the LFU seed, which the async
    manager keeps on the host). Returns the four arrays updated.
    """
    r = capacity.shape[0]
    c = cache.shape[0]
    n = slots.shape[0]
    if src_pos is None:
        src_pos = jnp.arange(n)
    safe_slot = jnp.where(slots >= 0, slots, 0)
    wb = jnp.where((slots >= 0) & (evict_rows >= 0), evict_rows, r)  # r drops
    capacity = capacity.at[wb].set(cache[safe_slot], mode="drop")
    cap_accum = cap_accum.at[wb].set(cache_accum[safe_slot], mode="drop")
    dst = jnp.where((slots >= 0) & (fetch_rows >= 0), slots, c)      # c drops
    cache = cache.at[dst].set(shadow[src_pos].astype(cache.dtype),
                              mode="drop")
    cache_accum = cache_accum.at[dst].set(shadow_accum[src_pos], mode="drop")
    return capacity, cache, cap_accum, cache_accum


def lfu_touch_ref(freq: jax.Array, slots: jax.Array, counts: jax.Array,
                  decay: float) -> jax.Array:
    """Decay-then-bump LFU counter update: freq' = decay * freq, then
    freq'[slots[i]] += counts[i] for every valid (>= 0) slot. Dense decay +
    sparse scatter-add — the frequency half of the paper's observation that
    access skew, not table size, decides cacheability (Fig. 6/7)."""
    c = freq.shape[0]
    dst = jnp.where(slots >= 0, slots, c)                   # c drops
    return (freq * decay).at[dst].add(counts.astype(freq.dtype),
                                      mode="drop")


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True) -> jax.Array:
    """Oracle for the flash_attention kernel. q,k,v: (b, h, s, dh)."""
    dh = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(
        jnp.asarray(dh, jnp.float32))
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        mask = np.arange(sk)[None, :] > np.arange(sq)[:, None]
        s = jnp.where(jnp.asarray(mask)[None, None], -1e30, s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
