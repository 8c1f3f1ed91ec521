"""Bucketing planner for the fused sparse backward (docs/sparse_optimizer.md).

The legacy sparse path broadcast each bag's pooled gradient to every lookup
slot — a `(B*F*L, D)` float tensor — and then argsorted + segment-summed that
full-width payload before the optimizer kernel ran. The planner here sorts
ONLY the `(B*F*L,)` int32 index stream and emits a CSR-style layout over the
batch's unique rows:

  unique_rows (N,)    i-th unique mega-table row, -1 beyond the unique count
  bag_offsets (N+1,)  [bag_offsets[i], bag_offsets[i+1]) slices bag_ids for
                      unique row i (empty for i >= n_unique)
  bag_ids     (N,)    for each valid lookup slot, in sorted-row order, the
                      flat (example*F + feature) bag whose pooled gradient
                      the slot contributes; N = B*F*L, static

so the optimizer can gather each unique row's referenced POOLED `(1, D)`
gradients directly — per-lookup gradients are never materialized. Slots of
equal row keep their flat-batch order (stable sort), which is what makes the
fused accumulation bit-identical to the legacy scatter-add.

Two implementations with identical outputs:
  * `build_sparse_plan` — pure jnp, jits on-device (used inside train steps
    and shard_map bodies; lowering contains no float tensors — asserted in
    tests/test_sparse_fused.py); `build_sparse_plan_with_slots` adds each
    lookup slot's position in `unique_rows`, from the same sort, for the
    forward that builds its own plan (core/embedding.py);
  * `build_sparse_plan_host` — numpy, for the data-pipeline reader thread
    (`data.sparse_plan_hook`) so batch k+1's plan is built while batch k
    computes, mirroring the async cache-exchange overlap of PR 2.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.tracing import scope

# rows are mega-table offsets (< total_rows << 2**31), so int32 max is a safe
# sort-last sentinel for -1 padding slots
_SENTINEL = np.iinfo(np.int32).max


class SparsePlan(NamedTuple):
    """CSR layout of a batch's lookups, grouped by unique row. A NamedTuple
    of arrays — a pytree, so it rides through jit/shard_map/batch dicts.

    `unique_rows`/`bag_offsets` may be CAPACITY-TRIMMED to (U,)/(U+1,) with
    U < N (see the builders' `capacity`): the tail past the unique count is
    -1 / n_valid either way, and every consumer — the dedup'd forward
    gather, the fused backward, `ref.bag_grad_sums`, the cached tiers'
    miss planning — sizes itself from the arrays, so a trimmed plan just
    means smaller gathers and a shorter kernel grid. Invariant relied on
    by the forward's compact-buffer remap: the live prefix of
    `unique_rows` is STRICTLY ASCENDING (the planner sorts; `cache.
    plan_to_slots` re-sorts after its row->slot relabel to keep it)."""
    unique_rows: jax.Array     # (U,) int32, -1 past the unique count
    bag_offsets: jax.Array     # (U+1,) int32, nondecreasing
    bag_ids: jax.Array         # (N,) int32 flat (example*F + feature) bags

    def to_batch(self) -> dict:
        """The three arrays under the batch-dict keys the train steps read."""
        return {"plan_rows": self.unique_rows,
                "plan_offsets": self.bag_offsets,
                "plan_bags": self.bag_ids}


def plan_from_batch(batch: dict) -> SparsePlan | None:
    """Rehydrate a plan attached by `data.sparse_plan_hook` (or None)."""
    if "plan_rows" not in batch:
        return None
    return SparsePlan(jnp.asarray(batch["plan_rows"], jnp.int32),
                      jnp.asarray(batch["plan_offsets"], jnp.int32),
                      jnp.asarray(batch["plan_bags"], jnp.int32))


def host_plan_from_batch(batch: dict) -> SparsePlan | None:
    """numpy view of a hook-attached plan, no device transfer — what the
    cached tiers' host-side miss planning consumes (core/cache.py)."""
    if "plan_rows" not in batch:
        return None
    return SparsePlan(np.asarray(batch["plan_rows"]),
                      np.asarray(batch["plan_offsets"]),
                      np.asarray(batch["plan_bags"]))


def host_plans_from_batch(batch: dict) -> list[SparsePlan] | None:
    """numpy views of the PER-HOST sub-plans a `data.sparse_plan_hook`
    configured with `n_hosts` attaches (stacked under hplan_* keys) — what
    the multi-host cached tier's per-host miss planning consumes."""
    if "hplan_rows" not in batch:
        return None
    rows = np.asarray(batch["hplan_rows"])
    offs = np.asarray(batch["hplan_offsets"])
    bags = np.asarray(batch["hplan_bags"])
    return [SparsePlan(rows[h], offs[h], bags[h])
            for h in range(rows.shape[0])]


def split_plan_by_host(plan: SparsePlan, n_hosts: int,
                       bags_per_host: int) -> list[SparsePlan]:
    """Split a GLOBAL host-built plan into per-host sub-plans by bag range
    (host h owns the contiguous flat bags [h*bags_per_host,
    (h+1)*bags_per_host) — the data-parallel batch split). Each sub-plan is
    in HOST-LOCAL bag space and equals `build_sparse_plan_host` run on that
    host's sub-batch (asserted in tests/test_cache_multihost.py): the
    multiset of (row, bag) pairs partitions the global plan's and the
    ascending-rows live prefix survives per host.

    No sort runs here: the global plan's runs are row-ascending and each
    run's bags are flat-order ascending, so a host's pairs are found by a
    mask + stable selection and its rows by run-head detection.
    """
    rows = np.asarray(plan.unique_rows)
    offs = np.asarray(plan.bag_offsets).astype(np.int64)
    bags = np.asarray(plan.bag_ids).astype(np.int64)
    nh = bags.shape[0] // n_hosts          # per-host lookup capacity
    n_live = int((rows >= 0).sum())
    n_valid = int(offs[n_live])
    host_of = bags[:n_valid] // bags_per_host
    # run id per live pair: offsets' live prefix is sorted, pads trail
    run_of = np.searchsorted(offs[:n_live + 1], np.arange(n_valid),
                             side="right") - 1
    out = []
    for h in range(n_hosts):
        sel = np.flatnonzero(host_of == h)  # ascending pair position ==
        r_sel = run_of[sel]                 # ascending (row, local bag)
        sub_rows = np.full((nh,), -1, np.int32)
        sub_offs = np.zeros((nh + 1,), np.int32)
        sub_bags = np.zeros((nh,), np.int32)
        if len(sel):
            change = np.empty(len(sel), bool)
            change[0] = True
            change[1:] = r_sel[1:] != r_sel[:-1]
            head_pos = np.flatnonzero(change)
            k = len(head_pos)
            sub_rows[:k] = rows[r_sel[head_pos]]
            ends = np.append(head_pos[1:], len(sel)).astype(np.int64)
            sub_offs[:k + 1] = np.concatenate([[0], ends])
            sub_offs[k + 1:] = ends[-1]
            sub_bags[:len(sel)] = bags[sel] - h * bags_per_host
        out.append(SparsePlan(sub_rows, sub_offs, sub_bags))
    return out


def split_plan_by_ranges(plan: SparsePlan, starts, ends,
                         seg_cap: int | None = None
                         ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Slice a plan into segments over arbitrary DISJOINT ascending row
    ranges — the shared core of `split_plan_by_owner` (uniform contiguous
    owner blocks) and `split_plan_by_table` (each table's row span under
    any layout). Segment s covers global rows [starts[s], ends[s]).

    Because the plan's live prefix is sorted ascending and the ranges are
    ascending and disjoint, each segment's rows — and its (row, bag) pairs
    in `bag_ids` — form a CONTIGUOUS slice: the split is two searchsorted
    calls and pure slicing, no sort. Rows outside every range are simply
    not claimed by any segment (e.g. a table_wise mega table's per-shard
    tail padding).

    Returns (seg_rows (S, cap) int32 SEGMENT-LOCAL rows (global minus
    starts[s]) -1-padded, seg_offsets (S, cap+1) int32 ABSOLUTE positions
    into the shared `bag_ids` with pad entries equal to the segment's bag
    end, and seg_base (S,) int32 = starts — the base the segmented fused
    backward adds back). `seg_cap` fixes the per-segment capacity for
    stable jit shapes (raises on overflow); default is the tight
    per-call maximum.
    """
    rows = np.asarray(plan.unique_rows)
    offs = np.asarray(plan.bag_offsets).astype(np.int64)
    starts = np.asarray(starts, np.int64)
    ends = np.asarray(ends, np.int64)
    n_seg = len(starts)
    assert len(ends) == n_seg, (len(ends), n_seg)
    if n_seg:
        assert np.all(ends >= starts)
        assert np.all(starts[1:] >= ends[:-1]), \
            "ranges must be ascending and disjoint"
    n_live = int((rows >= 0).sum())
    live = rows[:n_live].astype(np.int64)
    lo = np.searchsorted(live, starts)
    hi = np.searchsorted(live, ends)
    widest = int((hi - lo).max()) if n_seg else 0
    cap = widest if seg_cap is None else seg_cap
    if widest > cap:
        raise ValueError(
            f"owner segment overflow: widest owner holds {widest} unique "
            f"rows > seg_cap={cap}")
    seg_rows = np.full((n_seg, cap), -1, np.int32)
    seg_offs = np.zeros((n_seg, cap + 1), np.int32)
    for s in range(n_seg):
        a, b = int(lo[s]), int(hi[s])
        k = b - a
        seg_rows[s, :k] = live[a:b] - starts[s]
        seg_offs[s, :k + 1] = offs[a:b + 1]
        seg_offs[s, k + 1:] = offs[b]
    seg_base = starts.astype(np.int32)
    return seg_rows, seg_offs, seg_base


def split_plan_by_owner(plan: SparsePlan, shard_rows: int, n_shards: int,
                        seg_cap: int | None = None
                        ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Slice a plan into per-OWNER segments for the routed sparse update:
    owner s of the row-sharded capacity tier — or of a table_wise placement,
    whose owners are the same contiguous blocks — holds rows
    [s*shard_rows, (s+1)*shard_rows). The uniform-blocks special case of
    `split_plan_by_ranges`; see it for the returned layout.
    """
    starts = np.arange(n_shards, dtype=np.int64) * shard_rows
    return split_plan_by_ranges(plan, starts, starts + shard_rows,
                                seg_cap=seg_cap)


def split_plan_by_table(plan: SparsePlan, table_offsets, table_rows,
                        seg_cap: int | None = None
                        ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Slice a plan into PER-TABLE segments: table t owns the mega rows
    [table_offsets[t], table_offsets[t] + table_rows[t]) under any layout
    whose tables don't interleave (all of core/placement.py's). Feeds the
    per-table pricing of `launch.analysis.recommend_placement` (each
    segment's live-row count is the table's per-batch unique footprint)
    and per-table update granularity.

    Segments are returned in TABLE order (the caller's table ids), not row
    order — `split_plan_by_ranges` requires ascending ranges, so the split
    runs in row order and is unpermuted here. Same layout as
    `split_plan_by_owner`, with seg_base[t] = table_offsets[t].
    """
    starts = np.asarray(table_offsets, np.int64)
    ends = starts + np.asarray(table_rows, np.int64)
    order = np.argsort(starts, kind="stable")
    seg_rows, seg_offs, seg_base = split_plan_by_ranges(
        plan, starts[order], ends[order], seg_cap=seg_cap)
    inv = np.empty_like(order)
    inv[order] = np.arange(len(order))
    return seg_rows[inv], seg_offs[inv], seg_base[inv]


def coalesce_rows(rows: np.ndarray, chunk: int, total_rows: int,
                  min_fill: int = 1) -> tuple[np.ndarray, np.ndarray]:
    """Greedily cover a sorted row list with contiguous `chunk`-row blocks —
    the run-coalescer behind the chunk-granular capacity<->cache transfers
    (kernels/cache_ops.cache_fetch_chunked).

    rows: (N,) int64/int32 ASCENDING capacity rows (the live prefix of a
    plan's miss list — `split_plan_by_host` sub-plans and `_split_batch`
    both emit sorted rows, so no sort runs here); chunk: block height >= 1;
    total_rows: capacity height R, used to clamp block starts so
    start+chunk <= R (a block may over-fetch rows below its first member —
    harmless, the fetch is read-only).

    `min_fill` is the density-adaptive fallback: blocks holding fewer than
    `min_fill` member rows are DROPPED (their rows get pos = -1) so the
    caller routes isolated misses through the per-row path instead of
    paying (chunk - 1) rows of over-fetch each. min_fill = 1 keeps every
    block (pure fixed-chunk coverage).

    Returns (starts (K,) int32 block start rows, pos (N,) int32 with
    pos[i] = k*chunk + (rows[i] - starts[k]) — row i's position inside the
    (K*chunk, D) shadow slab, the `src_pos` a chunked
    `cache_ops.cache_commit` consumes — or -1 for rows of dropped blocks).
    Greedy left-to-right: a new block opens at min(row, R-chunk) whenever
    the current block cannot hold the next row; on the frequency-reordered
    Zipf head (core/placement.frequency_reorder) consecutive misses
    collapse to K << N blocks.
    """
    rows = np.asarray(rows, np.int64)
    n = rows.shape[0]
    if chunk <= 1 or n == 0:
        starts = rows.astype(np.int32)
        return starts, np.arange(n, dtype=np.int32)
    chunk = min(chunk, total_rows)
    starts_list = []
    pos = np.empty((n,), np.int32)
    i = 0
    while i < n:
        start = min(int(rows[i]), total_rows - chunk)
        # all rows the block covers: rows are ascending, so one searchsorted
        j = int(np.searchsorted(rows, start + chunk, side="left"))
        if j - i >= min_fill:
            k = len(starts_list)
            starts_list.append(start)
            pos[i:j] = k * chunk + (rows[i:j] - start).astype(np.int32)
        else:
            pos[i:j] = -1
        i = j
    return np.asarray(starts_list, np.int32), pos


class _LookupSort(NamedTuple):
    """The sort of one lookup stream that every on-device builder reads."""
    order: jax.Array      # (N,) flat slot at each sorted position
    s: jax.Array          # (N,) sorted rows, pads as _SENTINEL last
    head: jax.Array       # (N,) bool, first slot of each unique row's run
    rank: jax.Array       # (N,) unique-row index of each sorted slot
    n_valid: jax.Array    # () int32 non-pad slots
    lk: int               # lookups per bag


def _sort_lookups(idx: jax.Array,
                  lookups_per_bag: int | None) -> _LookupSort:
    if idx.ndim == 3:
        _, _, lk = idx.shape
    else:
        assert lookups_per_bag is not None, "flat idx needs lookups_per_bag"
        lk = lookups_per_bag
    flat = idx.reshape(-1).astype(jnp.int32)
    valid = flat >= 0
    safe = jnp.where(valid, flat, _SENTINEL)          # pads sort last
    order = jnp.argsort(safe)                         # stable: flat order
    s = safe[order]                                   # kept within a run
    head = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]]) \
        & (s != _SENTINEL)
    rank = jnp.cumsum(head) - 1                       # unique id at heads
    return _LookupSort(order, s, head, rank,
                       valid.sum().astype(jnp.int32), lk)


def _plan_from_sort(srt: _LookupSort, capacity: int | None) -> SparsePlan:
    n = srt.s.shape[0]
    bag_ids = (srt.order // srt.lk).astype(jnp.int32)
    unique_rows = jnp.full((n,), -1, jnp.int32).at[
        jnp.where(srt.head, srt.rank, n)].set(srt.s, mode="drop")
    # run i starts at its head's sorted position; runs are contiguous and
    # valid slots sort first, so offsets[i+1] doubles as run i's end and the
    # n_valid fill closes the last run / empties the tail
    bag_offsets = jnp.full((n + 1,), srt.n_valid, jnp.int32).at[
        jnp.where(srt.head, srt.rank, n + 1)].set(
            jnp.arange(n, dtype=jnp.int32), mode="drop")
    if capacity is not None and capacity < n:
        unique_rows = unique_rows[:capacity]
        bag_offsets = bag_offsets[:capacity + 1]
    return SparsePlan(unique_rows, bag_offsets, bag_ids)


def build_sparse_plan(idx: jax.Array,
                      lookups_per_bag: int | None = None,
                      capacity: int | None = None) -> SparsePlan:
    """idx: (B, F, L) offset global rows with -1 pads (or already-flat (N,)
    with `lookups_per_bag=L`). Pure int32 compute; O(N log N) in LOOKUPS,
    independent of table height (the paper's flat CPU hash-size curve,
    Fig. 12, depends on exactly this property).

    `capacity` trims unique_rows/bag_offsets to (capacity,)/(capacity+1,)
    — the static unique budget the dedup'd forward gather sizes itself by.
    The trim is a static slice, so the CALLER owns the contract that the
    batch's unique count fits (jit cannot raise data-dependently; the host
    twin below DOES raise, which is what the reader-thread hook runs)."""
    return _plan_from_sort(_sort_lookups(idx, lookups_per_bag), capacity)


def build_sparse_plan_with_slots(idx: jax.Array
                                 ) -> tuple[SparsePlan, jax.Array]:
    """`build_sparse_plan` of idx (B, F, L) plus each lookup slot's
    position in the plan's `unique_rows`, (B, F, L): what
    `searchsorted(unique_rows, idx)` gives on every valid slot, read off
    the plan's own sort instead of searched for. A sorted slot's position
    is its run's `rank`; sorting (order, rank) by order carries it back
    to flat slot order. Pad slots read the last live position (0 when
    there is none), so every value indexes a row of the plan. The sort of
    the ids is the same ops as `build_sparse_plan`'s, so a step that
    builds both merges them into one; the one sort added here is the
    `embedding_remap` layer's."""
    srt = _sort_lookups(idx, None)
    plan = _plan_from_sort(srt, None)
    with scope("embedding_remap"):
        # order is a permutation: no ties, so no stable sort's extra key
        _, slots = jax.lax.sort((srt.order, jnp.maximum(srt.rank, 0)),
                                num_keys=1, is_stable=False)
    return plan, slots.astype(jnp.int32).reshape(idx.shape)


def build_sparse_plan_host(idx: np.ndarray,
                           lookups_per_bag: int | None = None,
                           capacity: int | None = None) -> SparsePlan:
    """numpy twin of `build_sparse_plan` with identical outputs (asserted in
    tests/test_sparse_fused.py) — runs in the pipeline reader thread so the
    sort overlaps the in-flight batch's device compute. Unlike the jnp
    twin, `capacity` overflow RAISES here (shapes are host-side)."""
    idx = np.asarray(idx)
    if idx.ndim == 3:
        lk = idx.shape[2]
    else:
        assert lookups_per_bag is not None, "flat idx needs lookups_per_bag"
        lk = lookups_per_bag
    flat = idx.reshape(-1).astype(np.int64)
    n = flat.shape[0]
    valid = flat >= 0
    safe = np.where(valid, flat, _SENTINEL)
    order = np.argsort(safe, kind="stable")
    s = safe[order]
    bag_ids = (order // lk).astype(np.int32)
    head = np.concatenate([np.ones((1,), bool), s[1:] != s[:-1]]) \
        & (s != _SENTINEL)
    n_valid = int(valid.sum())
    heads = np.flatnonzero(head)
    if capacity is not None and len(heads) > capacity:
        raise ValueError(
            f"plan capacity overflow: batch has {len(heads)} unique rows "
            f"> capacity={capacity}; raise the capacity or shrink the batch")
    u = n if capacity is None else min(capacity, n)
    unique_rows = np.full((u,), -1, np.int32)
    unique_rows[:len(heads)] = s[heads]
    bag_offsets = np.full((u + 1,), n_valid, np.int32)
    bag_offsets[:len(heads)] = heads
    return SparsePlan(unique_rows, bag_offsets, bag_ids)
