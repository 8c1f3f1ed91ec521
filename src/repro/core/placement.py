"""PlacementPlanner: decides WHERE each embedding table lives.

This is the TPU realization of the paper's Fig. 8 placement options
(section IV-B.1) and of its observation that access frequency does NOT
correlate with table size (Fig. 6/7) — so balanced placement must bin-pack
on *load* (lookups/step) under *capacity* (bytes/shard) constraints.

All tables are laid out in one row-concatenated MEGA TABLE (rows, d). The
plan fixes each table's row offset and the mega table's PartitionSpec:

  replicated   fits in one chip's budget -> paper's "EMB on (one) GPU"
  table_wise   whole tables bin-packed onto `model`-axis shards; offsets
               padded so no table straddles a shard boundary -> paper's
               "table-wise partitioning on GPUs"
  row_wise     rows striped across shards regardless of table boundaries ->
               paper's "row-wise partitioning" (large tables straddle)
  column_wise  embedding dim sharded -> balances tiny-but-hot tables
               (follow-up work to the paper; included as a beyond-paper
               option)

  cached_host  the paper's "system memory" tier, realized: the mega table
               lives replicated in a slow capacity tier (host-resident /
               pooled-HBM array) and a fixed-size device cache holds hot
               rows (core/cache.py). `cache_rows` is sized from the HBM
               budget; Fig. 6/7's skewed, size-uncorrelated access makes a
               small cache capture most traffic. The legacy `host_offload`
               strategy string maps here, keeping configs portable.

               Under data parallelism (`capacity_shards > 1`, the MTrainS
               heterogeneous-memory regime) the capacity tier is ROW-SHARDED
               across hosts — host h owns the contiguous range
               [h*shard_rows, (h+1)*shard_rows) — while every host still
               runs its own `cache_rows`-sized hot cache over the WHOLE row
               space (core/cache.py MultiHostCachedEmbeddingBagCollection).
"""
from __future__ import annotations

import dataclasses
from collections.abc import Sequence

import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels.row_move import LANE


#: device-side HBM overhead per CACHED row beyond the row payload:
#: row-wise AdaGrad accumulator (fp32) + LFU frequency score (fp32)
CACHED_ROW_META_BYTES = 8

#: every mega-table, per-shard and device-cache height is a multiple of
#: this: the TPU kernels move rows as lanes of 128-row tiles of the table's
#: (d, rows) view (kernels/row_move.py), which tiles such heights exactly
#: and in place
ROW_TILE = LANE


@dataclasses.dataclass(frozen=True)
class PlacementPlan:
    """Where every table's rows live: the fused mega-table layout, its
    sharding spec, and (cached_host) the device-cache sizing."""

    strategy: str   # replicated|table_wise|row_wise|column_wise|cached_host
    table_offsets: tuple[int, ...]   # row offset of each table in the mega table
    total_rows: int                  # padded row count of the mega table
    pspec: P                         # sharding of the (rows, d) mega table
    shard_of_table: tuple[int, ...] | None  # table_wise only
    n_shards: int
    # diagnostics
    bytes_per_shard: tuple[int, ...] = ()
    load_per_shard: tuple[float, ...] = ()
    # cached_host only: device-cache slots backing the host-resident table
    cache_rows: int = 0
    # cached_host under data parallelism (capacity row-sharded over hosts)
    # AND table_wise (owner s holds rows [s*shard_rows, (s+1)*shard_rows)):
    # hosts the rows are sharded across (1 = unsharded) and rows per shard
    capacity_shards: int = 1
    shard_rows: int = 0
    # table_wise only: per-table count of embedding-dim (column) slices the
    # executor should use — 1 for tables that fit their owner's budget, k>1
    # for tables whose bytes exceed one shard (the column_wise escape hatch
    # for huge tables; docs/parallelism.md). The mega layout itself stays
    # full-width — realizing the slice is the execution layer's job.
    column_shards: tuple[int, ...] = ()

    @property
    def load_imbalance(self) -> float:
        """max/mean expected lookup load across shards (1.0 = balanced)."""
        if not self.load_per_shard or max(self.load_per_shard) == 0:
            return 1.0
        mean = float(np.mean(self.load_per_shard))
        return float(max(self.load_per_shard)) / max(mean, 1e-9)


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def plan_placement(hash_sizes: Sequence[int],
                   mean_lookups: Sequence[float],
                   embed_dim: int,
                   n_shards: int,
                   hbm_budget_bytes: float,
                   itemsize: int = 4,
                   strategy: str = "auto",
                   model_axis: str = "model",
                   second_axis: str = "data",
                   second_axis_size: int = 1,
                   capacity_shards: int = 1,
                   table_costs: Sequence[float] | None = None
                   ) -> PlacementPlan:
    """Build a placement plan for one EmbeddingBagCollection.

    hbm_budget_bytes is the per-shard capacity available for embeddings
    (chip HBM minus activations/MLP budget — the caller decides).

    `table_costs` (table_wise only) prices each table for the greedy
    bin-pack — e.g. `launch.analysis.recommend_placement`'s per-table
    exchange+update byte estimate, or measured per-table step times.
    Default is `mean_lookups` (load-balanced packing, the paper's Fig. 6/7
    insight that hot != big).
    """
    hash_sizes = [int(h) for h in hash_sizes]
    loads = [float(ld) for ld in mean_lookups]
    total_bytes = sum(h * embed_dim * itemsize for h in hash_sizes)
    if strategy == "host_offload":  # legacy alias for the realized tier
        strategy = "cached_host"
    if strategy == "auto":
        if total_bytes <= hbm_budget_bytes:
            strategy = "replicated"
        elif (total_bytes <= hbm_budget_bytes * n_shards
              and max(hash_sizes) * embed_dim * itemsize
              <= hbm_budget_bytes):
            strategy = "table_wise"
        else:
            strategy = "row_wise"

    if strategy == "replicated":
        offsets, rows = _contiguous(hash_sizes, pad_mult=8)
        rows = _round_up(rows, ROW_TILE)
        return PlacementPlan(strategy, offsets, rows, P(None, None), None,
                             n_shards,
                             bytes_per_shard=(total_bytes,) * 1,
                             load_per_shard=(sum(loads),))

    if strategy == "row_wise":
        offsets, rows = _contiguous(hash_sizes, pad_mult=8)
        rows = _round_up(rows, n_shards * ROW_TILE)
        per = rows // n_shards * embed_dim * itemsize
        pspec = P(model_axis, None)
        shards = n_shards
        if per > hbm_budget_bytes and second_axis_size > 1:
            # one axis of shards is not enough (the paper's M3 regime, where
            # a single Big Basin cannot hold the tables): spread rows over
            # the full pod — pooled HBM is the Zion 2 TB tier (DESIGN 2)
            shards = n_shards * second_axis_size
            rows = _round_up(rows, shards * ROW_TILE)
            per = rows // shards * embed_dim * itemsize
            pspec = P((model_axis, second_axis), None)
        return PlacementPlan(strategy, offsets, rows, pspec,
                             None, shards,
                             bytes_per_shard=(per,) * shards,
                             load_per_shard=_rowwise_load(
                                 hash_sizes, loads, offsets, rows, shards))

    if strategy == "column_wise":
        # every table's embedding dim sliced across all shards: each shard
        # holds the full row space at width d/n_shards, so per-shard bytes
        # shrink by n_shards with NO per-table balance problem — the heavy
        # hammer for tables too big for any single owner (table_wise marks
        # those via column_shards; docs/parallelism.md).
        if embed_dim % n_shards:
            raise ValueError(
                f"column_wise needs embed_dim divisible by n_shards, got "
                f"{embed_dim} % {n_shards}; pad the dim or drop shards")
        offsets, rows = _contiguous(hash_sizes, pad_mult=8)
        rows = _round_up(rows, ROW_TILE)
        per = rows * embed_dim // n_shards * itemsize
        return PlacementPlan(strategy, offsets, rows, P(None, model_axis),
                             None, n_shards,
                             bytes_per_shard=(per,) * n_shards,
                             load_per_shard=(sum(loads) / n_shards,)
                             * n_shards,
                             column_shards=(n_shards,) * len(hash_sizes))

    if strategy == "table_wise":
        return _table_wise(hash_sizes, loads, embed_dim, n_shards,
                           hbm_budget_bytes, itemsize, model_axis,
                           costs=table_costs)

    if strategy == "cached_host":
        # capacity tier: the whole mega table in slow memory (host DRAM /
        # pooled HBM). Single-host (capacity_shards=1): replicated, no
        # sharding to plan. Data-parallel (capacity_shards=H): ROW-SHARDED
        # over the hosts' second (data) axis — each host owns a contiguous
        # shard_rows range and serves other hosts' misses for it. The
        # device tier either way is a per-host hot-row cache sized so
        # payload + per-row AdaGrad accumulator + LFU score fit the
        # per-chip budget.
        offsets, rows = _contiguous(hash_sizes, pad_mult=8)
        rows = _round_up(rows, capacity_shards * ROW_TILE)
        shard_rows = rows // capacity_shards
        row_bytes = embed_dim * itemsize + CACHED_ROW_META_BYTES
        cache_rows = int(hbm_budget_bytes // row_bytes)
        cache_rows = max(ROW_TILE,
                         min(cache_rows // ROW_TILE * ROW_TILE, rows))
        pspec = P(None, None) if capacity_shards == 1 \
            else P(second_axis, None)
        per_host = cache_rows * row_bytes + (
            0 if capacity_shards == 1
            else shard_rows * embed_dim * itemsize)
        return PlacementPlan("cached_host", offsets, rows, pspec,
                             None, n_shards,
                             bytes_per_shard=(per_host,) * n_shards,
                             load_per_shard=(sum(loads),) * n_shards,
                             cache_rows=cache_rows,
                             capacity_shards=capacity_shards,
                             shard_rows=shard_rows)

    raise ValueError(f"unknown placement strategy {strategy!r}")


def frequency_reorder(table_offsets: Sequence[int],
                      hash_sizes: Sequence[int],
                      freq: np.ndarray,
                      total_rows: int) -> tuple[np.ndarray, np.ndarray]:
    """Build a per-table ids-by-frequency row permutation of the mega table.

    The CacheEmbedding trick (`ChunkParamMgr.reorder`): renumber each
    table's rows so the most-frequent ids come first. Afterward the Zipf
    head occupies a CONTIGUOUS prefix of every table's row span, which is
    what makes chunk-granular capacity<->cache transfers (fetch_chunk > 1)
    pull in mostly-hot neighbours instead of random cold rows.

    Args:
      table_offsets: row offset of each table in the mega table.
      hash_sizes: logical (unpadded) row count of each table.
      freq: (total_rows,) observed access count / EMA per GLOBAL row.
      total_rows: padded row count of the mega table.

    Returns:
      (remap, inverse): int64 arrays of shape (total_rows,).
      ``remap[old_global_row] = new_global_row`` — apply to incoming ids.
      ``inverse[new_global_row] = old_global_row`` — recover the original
      layout (e.g. to permute pretrained weights to match). Rows outside
      every table span (padding) map to themselves; the permutation never
      crosses a table boundary, so the placement plan is unchanged.
    """
    freq = np.asarray(freq)
    if freq.shape != (total_rows,):
        raise ValueError(
            f"freq must have shape ({total_rows},), got {freq.shape}")
    remap = np.arange(total_rows, dtype=np.int64)
    for o, h in zip(table_offsets, hash_sizes):
        # stable sort: equal-frequency rows keep their original order,
        # making the reorder deterministic for a given counter state
        order = np.argsort(-freq[o:o + h], kind="stable")
        remap[o + order] = o + np.arange(h, dtype=np.int64)
    inverse = np.empty_like(remap)
    inverse[remap] = np.arange(total_rows, dtype=np.int64)
    return remap, inverse


def elastic_table_remap(old_plan: PlacementPlan, new_plan: PlacementPlan,
                        hash_sizes: Sequence[int]
                        ) -> tuple[np.ndarray, np.ndarray]:
    """Row worklist moving a mega table between two placements of the SAME
    tables (elastic restore after host loss: the table_wise bin-pack was
    re-run for the surviving owner count, so every table's row block moved
    to a new global offset).

    Args:
      old_plan / new_plan: placements sharing `hash_sizes` (any strategy —
        only `table_offsets` is consulted).
      hash_sizes: logical (unpadded) row count of each table.

    Returns:
      (src_rows, dst_rows): int64 arrays; copying
      ``new_mega[dst_rows] = old_mega[src_rows]`` (and likewise for the
      AdaGrad accumulator) re-scatters every logical row under the new
      placement. Padding rows are never moved — they are zero in both
      layouts and unreachable by construction.
    """
    if len(old_plan.table_offsets) != len(hash_sizes) or \
            len(new_plan.table_offsets) != len(hash_sizes):
        raise ValueError(
            "elastic_table_remap needs plans over the same tables: "
            f"{len(old_plan.table_offsets)} vs {len(new_plan.table_offsets)}"
            f" vs {len(hash_sizes)} tables")
    src, dst = [], []
    for t, h in enumerate(hash_sizes):
        rows = np.arange(h, dtype=np.int64)
        src.append(old_plan.table_offsets[t] + rows)
        dst.append(new_plan.table_offsets[t] + rows)
    return np.concatenate(src), np.concatenate(dst)


def _contiguous(hash_sizes, pad_mult: int):
    offsets, off = [], 0
    for h in hash_sizes:
        offsets.append(off)
        off += _round_up(h, pad_mult)
    return tuple(offsets), off


def _rowwise_load(hash_sizes, loads, offsets, rows, n_shards):
    """Expected lookups hitting each shard under uniform row access."""
    shard_rows = rows // n_shards
    per = np.zeros(n_shards)
    for h, ld, o in zip(hash_sizes, loads, offsets):
        lo, hi = o, o + h
        for s in range(n_shards):
            a, b = s * shard_rows, (s + 1) * shard_rows
            overlap = max(0, min(hi, b) - max(lo, a))
            if h:
                per[s] += ld * overlap / h
    return tuple(float(x) for x in per)


def _table_wise(hash_sizes, loads, embed_dim, n_shards, budget, itemsize,
                model_axis, costs=None):
    """Greedy LPT bin-packing on PRICED COST with BYTES capacity constraint.

    The paper's insight (Fig. 6/7): hot tables are often small, so packing by
    bytes alone strands bandwidth — we balance a per-table COST instead
    (default: lookups/step; callers may pass analytically priced costs, e.g.
    `launch.analysis.recommend_placement`'s exchange+update bytes) and treat
    bytes as the hard constraint.

    Every table lands whole on its owner: owner s holds the contiguous mega
    rows [s*shard_rows, (s+1)*shard_rows), which is what lets
    `kernels.split_plan_by_owner` slice a batch plan into per-owner routed
    segments with two searchsorted calls. A table whose bytes exceed one
    shard's budget still gets a row-contiguous home (least-byte shard) but
    is flagged in `column_shards` with the D-slice count the execution
    layer should use (the column_wise fallback for huge tables).
    """
    n = len(hash_sizes)
    costs = list(loads) if costs is None else [float(c) for c in costs]
    assert len(costs) == n, (len(costs), n)
    order = np.argsort([-c for c in costs])        # priciest table first
    shard_bytes = np.zeros(n_shards)
    shard_cost = np.zeros(n_shards)
    shard_load = np.zeros(n_shards)
    shard_tables = [[] for _ in range(n_shards)]
    shard_of = np.zeros(n, np.int32)
    col_shards = np.ones(n, np.int64)
    for t in order:
        tb = hash_sizes[t] * embed_dim * itemsize
        if budget > 0 and tb > budget:
            # no owner can hold this table whole: recommend a D-slice over
            # enough shards that each slice fits (clamped to the mesh)
            col_shards[t] = min(n_shards, -(-tb // int(budget)))
        # cheapest shard with room; fall back to least-byte shard
        cand = sorted(range(n_shards), key=lambda s: (shard_cost[s],
                                                      shard_bytes[s]))
        pick = next((s for s in cand if shard_bytes[s] + tb <= budget),
                    int(np.argmin(shard_bytes)))
        shard_of[t] = pick
        shard_bytes[pick] += tb
        shard_cost[pick] += costs[t]
        shard_load[pick] += loads[t]
        shard_tables[pick].append(t)

    # rows per shard = max shard allocation, padded so shards align
    rows_of = [_round_up(h, 8) for h in hash_sizes]
    shard_rows = max(sum(rows_of[t] for t in ts) for ts in shard_tables)
    shard_rows = _round_up(max(shard_rows, ROW_TILE), ROW_TILE)
    offsets = [0] * n
    for s, ts in enumerate(shard_tables):
        off = s * shard_rows
        for t in ts:
            offsets[t] = off
            off += rows_of[t]
    total = shard_rows * n_shards
    return PlacementPlan("table_wise", tuple(offsets), total,
                         P(model_axis, None), tuple(int(x) for x in shard_of),
                         n_shards,
                         bytes_per_shard=tuple(int(x) for x in shard_bytes),
                         load_per_shard=tuple(float(x) for x in shard_load),
                         capacity_shards=n_shards,
                         shard_rows=shard_rows,
                         column_shards=tuple(int(x) for x in col_shards))
