"""Software-managed cached embedding tier (paper section IV-B, Figs. 6-8).

The paper's central capacity problem: production embedding tables exceed
device memory, and its Fig. 6/7 show per-row access frequency is highly
skewed AND uncorrelated with table size — exactly the regime where a
software-managed hot-row cache beats static sharding. This module realizes
the "system memory" placement tier as two arrays:

  capacity tier  (total_rows, d)  the full mega table + row-wise AdaGrad
                 accumulator, host-resident / pooled-HBM, slow to touch;
  device cache   (cache_rows, d)  hot rows + their accumulators + an LFU
                 score per slot, sized by plan_placement("cached_host")
                 from the per-chip HBM budget.

`CachedEmbeddingBagCollection` wraps an EmbeddingBagCollection: each step the
host manager extracts the batch's unique global rows, remaps them to cache
slots (fetch-on-miss through the kernels/cache_ops.py exchange, which moves
row + accumulator together), and the device-side lookup/update then runs
entirely against the small cache array — so per-step cost scales with the
cache, not the table. Eviction is frequency-aware (LFU with decay): victims
are the coldest slots outside the current working set; dirty victims write
back to the capacity tier on the way out. Hit/miss/eviction/writeback
counters are first-class metrics (CacheStats).

State handling is split the only way JAX allows: payload arrays (capacity,
cache, accumulators, LFU scores) are jax Arrays updated functionally;
the slot maps (row<->slot, dirty bits) are host numpy, mutated in place —
eviction choice is data-dependent and lives on the host anyway (the same
split as CacheEmbedding's ChunkParamMgr and MTrainS's tier manager).
"""
from __future__ import annotations

import dataclasses
from typing import Any, ClassVar

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DLRMConfig
from repro.core.embedding import EmbeddingBagCollection
from repro.kernels import cache_ops
from repro.kernels.sparse_plan import coalesce_rows


@dataclasses.dataclass
class CacheStats:
    """First-class cache metrics. A miss is a CAPACITY-TIER FETCH: one per
    unique missing row per batch — that row's further accesses in the same
    batch are served from the just-filled slot and count as hits, like every
    other access (the FBGEMM/UVM-cache convention: hit_rate = 1 -
    unique_misses / accesses). fetches/evictions/writebacks count rows."""
    hits: int = 0
    misses: int = 0
    fetches: int = 0           # unique rows pulled from the capacity tier
    evictions: int = 0         # slots whose resident row was displaced
    writebacks: int = 0        # dirty evictions flushed to capacity
    prefetched: int = 0        # rows admitted ahead of use (pipeline hook)
    fetch_chunks: int = 0      # DMA descriptors issued by chunked fetches
    overfetch_rows: int = 0    # padding rows chunked fetches over-read
    steps: int = 0

    @property
    def hit_rate(self) -> float:
        """hits / (hits + misses); 0.0 before any traffic."""
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def snapshot(self) -> dict[str, float]:
        """Flat metrics dict (the train-loop logging payload)."""
        return {"cache_hits": float(self.hits),
                "cache_misses": float(self.misses),
                "cache_hit_rate": self.hit_rate,
                "cache_fetches": float(self.fetches),
                "cache_evictions": float(self.evictions),
                "cache_writebacks": float(self.writebacks),
                "cache_prefetched": float(self.prefetched),
                "cache_fetch_chunks": float(self.fetch_chunks),
                "cache_overfetch_rows": float(self.overfetch_rows)}

    def reset(self) -> None:
        """Zero every counter in place. Benchmark sweeps call this between
        candidates sharing one process (benchmarks/cache_bench.py) so
        per-candidate figures can never silently accumulate across runs;
        works for subclasses too (iterates the dataclass fields)."""
        for f in dataclasses.fields(self):
            setattr(self, f.name, 0)


@dataclasses.dataclass
class CacheState:
    """Mutable two-tier state: device hot-row cache over a host capacity
    tier, plus the host-side slot maps and frequency counters."""

    capacity: jax.Array        # (R, d) slow tier — the full mega table
    cap_accum: jax.Array       # (R,) fp32 AdaGrad accumulator, slow tier
    cache: jax.Array           # (C, d) device tier — hot rows
    cache_accum: jax.Array     # (C,) fp32 accumulators of cached rows
    freq: jax.Array            # (C,) fp32 LFU-with-decay score per slot
    slot_row: np.ndarray       # (C,) int64: global row held by slot, -1 free
    row_slot: np.ndarray       # (R,) int32: slot holding row, -1 uncached
    dirty: np.ndarray          # (C,) bool: slot updated since fetch
    ema: np.ndarray            # (R,) fp32 EMA-decayed per-row access counts
    ema_tick: np.ndarray       # (R,) int64 tick of each row's last EMA touch
    tick: int                  # EMA clock: one tick per planned batch
    stats: CacheStats

    @property
    def cache_rows(self) -> int:
        """Device-tier height C (slots)."""
        return int(self.cache.shape[0])

    @property
    def resident(self) -> int:
        """Number of occupied cache slots."""
        return int((self.slot_row >= 0).sum())


@dataclasses.dataclass
class PendingCommit:
    """One staged admission waiting for its step-boundary commit.

    The shadow slab holds the fetched capacity rows (dispatched while the
    in-flight batch computes); slots/evict_rows are the commit worklist
    (evict_rows[i] >= 0 means slot i's dirty victim writes back first).
    This is the pending-eviction writeback queue entry of the async design
    (docs/cache.md)."""
    epoch: int
    slots: np.ndarray          # (n,) cache slots to fill at commit
    evict_rows: np.ndarray     # (n,) capacity row for dirty writeback, -1 none
    rows: np.ndarray           # (n,) global rows being admitted
    victim_slots: np.ndarray   # (v,) slots whose resident was displaced
    ws_mask: np.ndarray        # (C,) bool: staged batch's full working set
    shadow: jax.Array | None        # (m, d) fetched rows, m >= n if chunked
    shadow_accum: jax.Array | None  # (m,) fetched accumulators
    src_pos: np.ndarray | None = None  # (n,) shadow row per entry (chunked
                                       # fetch); None = one row per entry


@dataclasses.dataclass
class StagedBatch:
    """A batch whose admission has been staged ahead of use: the remapped
    slot indices + the idx fingerprint `take` uses to match it. hits/misses
    record the plan's stat contribution so a discarded (mismatched) plan
    can be re-booked as a prefetch instead of a phantom step."""
    epoch: int
    idx_key: np.ndarray        # (B, F, L) global idx the plan was made for
    local: np.ndarray          # (B, F, L) slot-space remap
    ws_mask: np.ndarray        # (C,) bool working-set slots
    hits: int                  # stat delta booked at plan time
    misses: int


@dataclasses.dataclass
class AsyncCacheState:
    """Double-buffered cache state for the async exchange stream.

    Differences vs CacheState:
      * `freq` lives on the HOST (np.float32): victim selection must never
        block the planner on device work — the whole point of the stream is
        that planning + fetch overlap the in-flight batch's compute.
      * `slot_epoch` tags each slot with the epoch at which its resident
        row was admitted. Together with working-set protection it enforces
        the pipeline invariant: a slot admitted at epoch k+1 (pending) is
        never read or written by the in-flight epoch-k batch, so in-flight
        gradients always land in the slab their remap was planned against.
      * `pending` is the ordered commit queue (fetches in flight); host
        maps are flipped EAGERLY at plan time (the cheap slot-map swap), so
        later plans see the post-commit view while the device catches up.
    """
    capacity: jax.Array        # (R, d) slow tier — the full mega table
    cap_accum: jax.Array       # (R,) fp32 AdaGrad accumulator, slow tier
    cache: jax.Array           # (C, d) device tier — hot rows
    cache_accum: jax.Array     # (C,) fp32 accumulators of cached rows
    freq: np.ndarray           # (C,) HOST fp32 LFU-with-decay scores
    slot_row: np.ndarray       # (C,) int64: global row held by slot, -1 free
    row_slot: np.ndarray       # (R,) int32: slot holding row, -1 uncached
    dirty: np.ndarray          # (C,) bool: slot updated since fetch
    slot_epoch: np.ndarray     # (C,) int64: admission epoch per slot
    epoch: int                 # last epoch issued
    pending: list[PendingCommit]
    inflight_mask: np.ndarray | None   # (C,) bool: in-flight working set
    staged: StagedBatch | None
    ema: np.ndarray            # (R,) fp32 EMA-decayed per-row access counts
    ema_tick: np.ndarray       # (R,) int64 tick of each row's last EMA touch
    tick: int                  # EMA clock: one tick per planned batch
    stats: CacheStats

    @property
    def cache_rows(self) -> int:
        """Device-tier height C (slots)."""
        return int(self.cache.shape[0])

    @property
    def resident(self) -> int:
        """Number of occupied cache slots."""
        return int((self.slot_row >= 0).sum())


def _pick_slots(slot_row: np.ndarray, freq: np.ndarray, n: int,
                protect: np.ndarray, thrash_detail: str
                ) -> tuple[np.ndarray, np.ndarray]:
    """The ONE slot-selection policy of every admission path (sync, async,
    and per-host multi-host): free slots first, then the coldest
    unprotected residents (stable argsort of the LFU scores), with the
    cache-thrash guard raised when the protected working set leaves too
    few victims. Returns (slots (n,), victims) — victims occupy the TAIL
    of `slots`, the layout the exchange worklists rely on."""
    free = np.flatnonzero(slot_row < 0)
    need = n - len(free)
    victims = np.empty((0,), np.int64)
    if need > 0:
        evictable = np.flatnonzero((slot_row >= 0) & ~protect)
        if len(evictable) < need:
            raise ValueError(
                f"cache thrash: need {need} evictions but only "
                f"{len(evictable)} unprotected slots — {thrash_detail}")
        order = np.argsort(np.asarray(freq)[evictable], kind="stable")
        victims = evictable[order[:need]]
    return np.concatenate([free[:min(n, len(free))], victims])[:n], victims


def _ema_score(ema: np.ndarray, ema_tick: np.ndarray, rows: np.ndarray,
               now: int, decay: float) -> np.ndarray:
    """Lazily-decayed EMA read: each row's counter decays by `decay` per
    tick, but only the touched rows are ever written — the decay owed since
    a row's last touch is applied on read (score = ema * decay**age), so
    the (R,)-sized state needs no per-step dense pass."""
    age = (now - ema_tick[rows]).astype(np.float32)
    return ema[rows] * np.power(np.float32(decay), age)


def _ema_touch(ema: np.ndarray, ema_tick: np.ndarray, rows: np.ndarray,
               counts: np.ndarray, now: int, decay: float) -> None:
    """Fold one batch's access counts into the per-row EMA (in place):
    settle each touched row's owed decay, add its counts, stamp the tick.
    After the call `ema[rows]` holds the post-touch scores — the admission
    seeds of the EMA policy (a re-admitted row re-enters at its historical
    frequency instead of this batch's count, so one cold burst cannot
    churn it out of the cache before the burst rows themselves decay)."""
    ema[rows] = _ema_score(ema, ema_tick, rows, now, decay) \
        + counts.astype(np.float32)
    ema_tick[rows] = now


def _gate_admission(slot_row: np.ndarray, freq: np.ndarray,
                    protect: np.ndarray, missing: np.ndarray,
                    scores: np.ndarray) -> np.ndarray:
    """The adaptive admission threshold of the EMA policy, for best-effort
    paths (prefetch / stage_rows with `gate=True`): rows that fit free
    slots always admit; beyond that, candidates (EMA scores descending)
    admit only while they STRICTLY beat the coldest unprotected residents
    (slot freq ascending) — so admission is monotone in a row's access
    frequency and a one-off cold burst (score ~1) cannot displace the hot
    head (asserted in tests/test_cache_admission.py). Returns a (len
    (missing),) bool keep-mask; strict planned batches never gate (every
    planned row MUST become resident for bit-exactness)."""
    n = len(missing)
    free = int((slot_row < 0).sum())
    if n <= free:
        return np.ones((n,), bool)
    evictable = np.flatnonzero((slot_row >= 0) & ~protect)
    vic_scores = np.sort(np.asarray(freq)[evictable])
    order = np.argsort(-scores, kind="stable")
    admit = np.zeros((n,), bool)
    admit[order[:free]] = True
    rest = order[free:]
    k = min(len(rest), len(vic_scores))
    if k:
        beats = scores[rest[:k]] > vic_scores[:k]
        # descending candidates vs ascending victims: the first failure
        # ends the admitted prefix
        n_admit = k if beats.all() else int(np.argmin(beats))
        admit[rest[:n_admit]] = True
    return admit


def _chunk_min_fill(chunk: int) -> int:
    """Minimum member rows for a coalesced block to beat per-row DMAs:
    blocks at least ~3/4 full keep the over-fetch payload below the
    descriptor savings (launch/analysis.cache_admission_traffic prices the
    trade); sparser blocks fall back to the per-row fetch path."""
    return max(2, (3 * chunk + 3) // 4)


def _chunked_shadow_fetch(capacity: jax.Array, cap_accum: jax.Array,
                          missing: np.ndarray, chunk: int, stats: CacheStats,
                          use_kernel: bool | None, interpret: bool
                          ) -> tuple[jax.Array, jax.Array, np.ndarray]:
    """Chunk-granular shadow fetch with density-adaptive fallback, shared
    by the sync and async admission paths: coalesce the sorted miss list
    into contiguous blocks, fetch dense blocks block-wise
    (cache_ops.cache_fetch_chunked — one DMA descriptor per block) and the
    isolated remainder row-wise, concatenated into one shadow slab. Books
    `fetch_chunks` (descriptors) and `overfetch_rows` (block padding) on
    `stats`. Returns (shadow, shadow_accum, src_pos) — src_pos[i] is miss
    i's row inside the slab, the `cache_ops.cache_commit` install remap."""
    total = int(capacity.shape[0])
    chunk = min(chunk, total)
    starts, pos = coalesce_rows(missing, chunk, total,
                                min_fill=_chunk_min_fill(chunk))
    single = np.flatnonzero(pos < 0)
    src_pos = pos.copy()
    src_pos[single] = len(starts) * chunk + np.arange(len(single),
                                                      dtype=np.int32)
    parts = []
    if len(starts):
        parts.append(cache_ops.cache_fetch_chunked(
            capacity, cap_accum, jnp.asarray(starts), chunk,
            use_kernel=use_kernel, interpret=interpret))
    if len(single):
        parts.append(cache_ops.cache_fetch(
            capacity, cap_accum, jnp.asarray(missing[single], jnp.int32),
            use_kernel=use_kernel, interpret=interpret))
    if len(parts) == 2:
        shadow = jnp.concatenate([parts[0][0], parts[1][0]])
        shadow_accum = jnp.concatenate([parts[0][1], parts[1][1]])
    else:
        shadow, shadow_accum = parts[0]
    stats.fetch_chunks += len(starts) + len(single)
    stats.overfetch_rows += len(starts) * chunk - (len(missing) - len(single))
    return shadow, shadow_accum, src_pos


def _fetch_guard(injector, retry, site: str = "cache.fetch") -> int:
    """Fire a fault-injection `site` with bounded retry-with-backoff
    (docs/fault_tolerance.md). Default site: "cache.fetch" (training);
    the serving tier reuses the same guard with "serve.fetch" /
    "serve.admit" (serve/dlrm_engine.py).

    Stands in front of every capacity-tier fetch dispatch: a scheduled
    transient fault (any exception with a truthy `transient` attribute —
    duck-typed so core/ never imports train/fault_tolerance) is retried up
    to `retry.max_retries` times with `retry.sleep(attempt)` backoff;
    exhaustion or a non-transient fault propagates to the driver, whose
    DegradationManager decides whether to fall back to the strict_sync
    schedule. Crucially the guard sits BEFORE any host-map mutation of the
    admission path it protects, so a propagated fault leaves the tier
    consistent and the step can simply be replayed. Returns the number of
    retries burned (0 when no injector is armed or nothing fired)."""
    if injector is None:
        return 0
    attempt = 0
    while True:
        try:
            injector.fire(site)
        except Exception as e:
            if not getattr(e, "transient", False) or retry is None \
                    or attempt >= retry.max_retries:
                raise
            attempt += 1
            retry.sleep(attempt)
            continue
        return attempt


@dataclasses.dataclass
class StaleRowSnapshot:
    """Read-only last-known-good row values for degrade-don't-die serving.

    The serving tier records every row it successfully fetches from the
    capacity tier; when a later fetch faults (or the circuit breaker is in
    stale_only), misses resolve from this snapshot instead — zeros for rows
    never seen. The tier is READ-ONLY in serving, so a recorded value can
    never go stale relative to the capacity tier: "stale" responses differ
    from the oracle only on never-seen (zero-filled) rows, which is exactly
    the `degraded=True` contract (docs/serving.md).

    Host-side numpy on purpose: the degraded path must not depend on the
    device tier being reachable."""

    values: np.ndarray         # (R, d) last-known-good rows, host copy
    seen: np.ndarray           # (R,) bool: row has been recorded at least once

    @classmethod
    def empty(cls, total_rows: int, dim: int,
              dtype=np.float32) -> StaleRowSnapshot:
        """Zero-filled snapshot covering `total_rows` rows of width `dim`."""
        return cls(values=np.zeros((total_rows, dim), dtype),
                   seen=np.zeros((total_rows,), bool))

    def record(self, rows: np.ndarray, values: np.ndarray) -> None:
        """Remember `values` ((n, d), host or device) for global `rows`."""
        rows = np.asarray(rows)
        if len(rows) == 0:
            return
        self.values[rows] = np.asarray(values, self.values.dtype)
        self.seen[rows] = True

    def gather(self, rows: np.ndarray) -> np.ndarray:
        """(n, d) last-known-good values for `rows`; zeros where unseen."""
        rows = np.asarray(rows)
        out = self.values[rows].copy()
        out[~self.seen[rows]] = 0
        return out

    @property
    def coverage(self) -> float:
        """Fraction of the row space with a recorded value."""
        return float(self.seen.mean()) if len(self.seen) else 0.0


@dataclasses.dataclass(frozen=True)
class CachedEmbeddingBagCollection:
    """EmbeddingBagCollection whose device working set is a hot-row cache.

    The wrapped collection's `mega` param IS the capacity tier; `lookup`
    results are numerically identical to the uncached collection (rows are
    moved bit-exactly and pooled by the same code path).
    """
    ebc: EmbeddingBagCollection
    cache_rows: int
    decay: float = 0.98        # LFU decay per step (1.0 = pure LFU; lower
                               # adapts faster but churns the tail more)
    use_kernel: bool | None = None
    interpret: bool = False
    ema_admission: bool = True  # seed admitted slots with the row's EMA
                                # score (historical frequency) instead of
                                # this batch's count — False restores
                                # first-touch count seeding
    fetch_chunk: int = 1       # capacity->cache transfer granularity in
                               # rows: >1 coalesces the sorted miss list
                               # into contiguous blocks (one DMA descriptor
                               # per block); 1 = per-row transfers
    injector: Any = None       # train.fault_tolerance.FaultInjector firing
                               # the "cache.fetch" site ahead of every
                               # capacity-tier fetch dispatch (tests/chaos)
    retry: Any = None          # RetryPolicy (duck-typed: max_retries +
                               # sleep) bounding transient-fault retries in
                               # `_fetch_guard`; None = fail fast

    # stats flavour hook: the bulk-backed tier (core/tiers.py) swaps in
    # TierCacheStats so per-tier counters ride every state/checkpoint path
    _stats_cls: ClassVar[type] = CacheStats

    @classmethod
    def build(cls, cfg: DLRMConfig, cache_rows: int | None = None,
              strategy: str = "cached_host", decay: float = 0.98,
              use_kernel: bool | None = None,
              interpret: bool = False, ema_admission: bool = True,
              fetch_chunk: int = 1) -> CachedEmbeddingBagCollection:
        """Build over a fresh single-shard EmbeddingBagCollection; see the
        class fields for the knobs."""
        ebc = EmbeddingBagCollection.build(cfg, n_shards=1, strategy=strategy)
        rows = cache_rows if cache_rows is not None else ebc.plan.cache_rows
        assert rows > 0, "cached_host plan produced an empty cache"
        return cls(ebc, int(rows), decay, use_kernel, interpret,
                   ema_admission, int(fetch_chunk))

    # -- state ---------------------------------------------------------------

    def init_state(self, mega: jax.Array,
                   accum: jax.Array | None = None) -> CacheState:
        """mega: (total_rows, d) capacity-tier table (e.g. params["emb"]
        ["mega"]); accum: optional (total_rows,) AdaGrad accumulator.

        The state COPIES mega/accum once and owns its buffers from then on:
        every subsequent exchange donates them to XLA so the swap updates
        rows in place instead of moving the whole tier (the caller's arrays
        stay valid; arrays handed out by `materialize` may be donated again
        by later flushes). A host (numpy) `mega` is transferred, so the
        state's capacity array is the only device copy of the table — how
        the serve launcher builds it (launch/serve.py)."""
        r, d = mega.shape
        assert r == self.ebc.plan.total_rows, (r, self.ebc.plan.total_rows)
        c = self.cache_rows
        if accum is None:
            accum = jnp.zeros((r,), jnp.float32)
        return CacheState(
            capacity=jnp.array(mega, copy=True),
            cap_accum=jnp.array(accum, jnp.float32, copy=True),
            cache=jnp.zeros((c, d), mega.dtype),
            cache_accum=jnp.zeros((c,), jnp.float32),
            freq=jnp.zeros((c,), jnp.float32),
            slot_row=np.full((c,), -1, np.int64),
            row_slot=np.full((r,), -1, np.int32),
            dirty=np.zeros((c,), bool),
            ema=np.zeros((r,), np.float32),
            ema_tick=np.zeros((r,), np.int64),
            tick=0,
            stats=self._stats_cls())

    # -- admission -----------------------------------------------------------

    @staticmethod
    def _split_batch(idx, row_slot: np.ndarray, cache_rows: int, plan=None):
        """Shared batch parsing for the sync and async planners (their
        behavioural equality is the bit-exactness contract): pad mask,
        unique rows with counts, thrash guard, resident/missing split.

        `plan` (a host SparsePlan over idx in GLOBAL row space, e.g.
        `kernels.host_plan_from_batch`'s) short-circuits the np.unique sort:
        the plan's live prefix IS the sorted unique row set and its offset
        diffs are the counts — the batch was already bucketed once in the
        reader thread, so the miss planning rides that same artifact
        (identical outputs, asserted in tests/test_dedup_forward.py).
        Returns (idx, valid, rows, counts, hit_slots, hit_counts, missing,
        miss_counts)."""
        idx = np.asarray(idx)
        valid = idx >= 0
        if plan is not None:
            prows = np.asarray(plan.unique_rows)
            n_live = int((prows >= 0).sum())
            rows = prows[:n_live].astype(np.int64)
            counts = np.diff(np.asarray(plan.bag_offsets)[:n_live + 1]
                             .astype(np.int64))
        else:
            rows, counts = np.unique(idx[valid], return_counts=True)
        if len(rows) > cache_rows:
            raise ValueError(
                f"batch touches {len(rows)} unique rows > cache_rows="
                f"{cache_rows}; raise the HBM budget or shrink the "
                "batch")
        resident = row_slot[rows] >= 0
        return (idx, valid, rows, counts, row_slot[rows[resident]],
                counts[resident], rows[~resident], counts[~resident])

    @staticmethod
    def _remap(row_slot: np.ndarray, idx: np.ndarray,
               valid: np.ndarray) -> np.ndarray:
        """Global rows -> cache slots (-1 pads preserved)."""
        local = row_slot[np.where(valid, idx, 0)]
        return np.where(valid, local, -1).astype(np.int32)

    # -- tier hooks (overridden by the bulk-backed tier, core/tiers.py) ------

    def _stage_capacity(self, state, missing: np.ndarray) -> None:
        """Pre-fetch tier hook: every admission path calls this with the
        sorted unique `missing` rows right before the capacity tier is
        read. The two-tier collection stages nothing — capacity IS its
        slowest tier. The bulk-backed tier overrides this to promote
        bulk-resident rows into the DRAM capacity array (behind the
        "bulk.fetch" fault site, guard fired before any mutation) so the
        device fetch that follows reads current values."""

    def _absorb_evictions(self, state, evicted_rows: np.ndarray) -> None:
        """Post-eviction tier hook: every admission path calls this after
        the host maps are updated, with the global rows displaced from the
        device tier. The two-tier collection needs nothing — evicted rows
        already live in capacity. The bulk-backed tier overrides this to
        account the rows DRAM-resident and demote the coldest DRAM rows to
        the bulk store when the DRAM budget overflows."""

    def _admit(self, state: CacheState, missing: np.ndarray,
               seeds: np.ndarray, protect: np.ndarray) -> int:
        """Bring `missing` global rows (SORTED ascending) into cache slots,
        evicting the coldest unprotected slots. `seeds` holds the slots'
        initial LFU scores (batch counts, or EMA scores under the EMA
        admission policy); `protect` is a (C,) bool mask of slots that must
        survive (the current working set). Returns rows written back."""
        n = len(missing)
        if n == 0:
            return 0
        # fault-injection gate BEFORE any host-map mutation: a propagated
        # transient fault leaves the tier consistent for a step replay
        _fetch_guard(self.injector, self.retry)
        # tier hook: promote bulk-resident rows into capacity before the
        # fetch below reads it (no-op on the two-tier collection)
        self._stage_capacity(state, missing)
        slots, victims = _pick_slots(
            state.slot_row, state.freq, n, protect,
            f"the batch working set exceeds cache_rows={state.cache_rows};"
            " raise the HBM budget or shrink the batch")
        evicted_rows = state.slot_row[victims]
        wb_mask = state.dirty[victims]
        # worklist: dirty victims write back; every admitted slot fetches
        evict_rows = np.full((n,), -1, np.int64)
        evict_rows[len(slots) - len(victims):] = np.where(
            wb_mask, evicted_rows, -1)
        if self.fetch_chunk > 1:
            # chunk-granular transfer: coalesce the sorted miss list into
            # contiguous blocks, fetch dense blocks block-wise (isolated
            # misses fall back row-wise), install row-wise through the
            # commit's src_pos remap — bit-identical to the fused exchange
            # (values are copies either way)
            shadow, shadow_accum, pos = _chunked_shadow_fetch(
                state.capacity, state.cap_accum, missing, self.fetch_chunk,
                state.stats, self.use_kernel, self.interpret)
            (state.capacity, state.cache, state.cap_accum,
             state.cache_accum) = cache_ops.cache_commit(
                state.capacity, state.cache, state.cap_accum,
                state.cache_accum, shadow, shadow_accum,
                jnp.asarray(slots, jnp.int32),
                jnp.asarray(evict_rows, jnp.int32),
                jnp.asarray(missing, jnp.int32),
                use_kernel=self.use_kernel, interpret=self.interpret,
                src_pos=jnp.asarray(pos))
            state.freq = state.freq.at[jnp.asarray(slots, jnp.int32)].set(
                jnp.asarray(seeds, jnp.float32))
        else:
            (state.capacity, state.cache, state.cap_accum, state.cache_accum,
             state.freq) = cache_ops.cache_exchange(
                state.capacity, state.cache, state.cap_accum,
                state.cache_accum, state.freq, jnp.asarray(slots, jnp.int32),
                jnp.asarray(evict_rows, jnp.int32),
                jnp.asarray(missing, jnp.int32),
                jnp.asarray(seeds, jnp.float32),
                use_kernel=self.use_kernel, interpret=self.interpret)
        # host maps
        state.row_slot[evicted_rows] = -1
        state.slot_row[slots] = missing
        state.row_slot[missing] = slots.astype(np.int32)
        state.dirty[slots] = False
        # tier hook: evicted rows fall back to the next tier down
        self._absorb_evictions(state, evicted_rows)
        state.stats.fetches += n
        state.stats.evictions += len(victims)
        state.stats.writebacks += int(wb_mask.sum())
        return int(wb_mask.sum())

    def prepare(self, state: CacheState, idx, train: bool = True,
                plan=None) -> np.ndarray:
        """Make every row of `idx` cache-resident and remap to slot space.

        idx: (B, F, L) OFFSET global rows (-1 pads), host or device array.
        Returns (B, F, L) int32 cache-slot indices (-1 pads preserved) —
        feed these to `lookup_cached` / the cached train step. When `train`,
        the working set's slots are marked dirty (they will receive sparse
        updates) so eviction writes them back. `plan` (host SparsePlan in
        global row space) replaces the miss planner's np.unique sort with
        the reader thread's bucketing — see `_split_batch`.
        """
        (idx, valid, rows, counts, hit_slots, hit_counts, missing,
         miss_counts) = self._split_batch(idx, state.row_slot,
                                          state.cache_rows, plan)
        # LFU accounting: decay everything, bump hit slots; admitted slots
        # are seeded by _admit below.
        state.freq = cache_ops.lfu_touch(
            state.freq, jnp.asarray(hit_slots, jnp.int32),
            jnp.asarray(hit_counts, jnp.float32), decay=self.decay)
        # per-ROW EMA (capacity row space, survives eviction): one tick per
        # planned batch, decay settled lazily on touch
        state.tick += 1
        _ema_touch(state.ema, state.ema_tick, rows, counts, state.tick,
                   self.decay)
        protect = np.zeros((state.cache_rows,), bool)
        protect[hit_slots] = True
        # EMA admission: a re-admitted row re-enters at its historical
        # frequency (post-touch EMA score) instead of this batch's count
        seeds = state.ema[missing] if self.ema_admission \
            else miss_counts.astype(np.float32)
        self._admit(state, missing, seeds, protect)
        state.stats.hits += int(counts.sum()) - len(missing)
        state.stats.misses += len(missing)
        state.stats.steps += 1
        if train:
            state.dirty[state.row_slot[rows]] = True
        return self._remap(state.row_slot, idx, valid)

    def prefetch(self, state: CacheState, rows, gate: bool = False) -> int:
        """Best-effort admission of `rows` (unique global rows, e.g. the
        NEXT batch's deduplicated indices from the pipeline hook) so the
        capacity-tier fetch overlaps the current step's compute. Does not
        touch hit/miss accounting and never evicts the rows it brings in;
        overflow beyond free+evictable space is dropped. `gate=True` adds
        the EMA admission threshold (`_gate_admission`): beyond the free
        slots, a row is admitted only if its EMA score strictly beats the
        coldest unprotected resident's — speculative admissions cannot
        churn the hot head. Returns the number of rows admitted."""
        rows = np.unique(np.asarray(rows))
        rows = rows[rows >= 0]
        missing = rows[state.row_slot[rows] < 0]
        protect = np.zeros((state.cache_rows,), bool)
        keep = state.row_slot[rows[state.row_slot[rows] >= 0]]
        protect[keep] = True
        # seed = EMA score + 1 (this request counts as one access; EMA
        # itself is only touched by planned batches), or 1.0 first-touch
        if self.ema_admission:
            seeds = _ema_score(state.ema, state.ema_tick, missing,
                               state.tick, self.decay) + np.float32(1.0)
        else:
            seeds = np.ones((len(missing),), np.float32)
        if gate and len(missing):
            keep_mask = _gate_admission(state.slot_row,
                                        np.asarray(state.freq), protect,
                                        missing, seeds)
            missing, seeds = missing[keep_mask], seeds[keep_mask]
        evictable = int(((state.slot_row >= 0) & ~protect).sum())
        free = int((state.slot_row < 0).sum())
        missing, seeds = missing[:free + evictable], seeds[:free + evictable]
        self._admit(state, missing, seeds, protect)
        state.stats.prefetched += len(missing)
        return len(missing)

    # -- lookup --------------------------------------------------------------

    def lookup_cached(self, state: CacheState, local_idx,
                      rules=None) -> jax.Array:
        """Pooled lookup against the device cache. local_idx: (B, F, L)
        slot indices from `prepare`. Pure device function — jit-friendly."""
        return self.ebc.lookup({"mega": state.cache},
                               jnp.asarray(local_idx), rules)

    def lookup(self, state: CacheState, idx, train: bool = False,
               rules=None) -> jax.Array:
        """prepare + lookup_cached: numerically identical to
        `EmbeddingBagCollection.lookup` on the same (global) indices."""
        return self.lookup_cached(state, self.prepare(state, idx, train),
                                  rules)

    # -- training ------------------------------------------------------------

    def plan_to_slots(self, state, batch: dict) -> dict:
        """Relabel a host-built sparse bucketing plan (data.sparse_plan_hook,
        GLOBAL row space) onto the cache slab: unique rows map through
        row_slot (a bijection over the batch's — by now resident — working
        set), then the runs are RE-SORTED by slot so the plan invariant
        (live prefix strictly ascending) survives the relabel — the dedup'd
        forward's compact-buffer remap searches the row list and requires
        it sorted. Permuting whole runs is free for the fused backward:
        each unique row's update is independent and its within-run order is
        untouched, so the result stays bit-identical (asserted in
        tests/test_sparse_fused.py / test_dedup_forward.py). Call AFTER
        prepare/take_async. Accepts CacheState or AsyncCacheState; returns
        the three plan keys for the device batch.
        """
        rows = np.asarray(batch["plan_rows"])
        offs = np.asarray(batch["plan_offsets"]).astype(np.int64)
        bags = np.asarray(batch["plan_bags"], np.int32)
        n_live = int((rows >= 0).sum())        # pads trail (planner sorts)
        slots = state.row_slot[rows[:n_live]].astype(np.int64)
        order = np.argsort(slots, kind="stable")
        lengths = np.diff(offs[:n_live + 1])[order]
        new_rows = np.full(rows.shape, -1, np.int32)
        new_rows[:n_live] = slots[order]
        new_offs = offs.copy()                 # tail already == n_valid
        new_offs[:n_live + 1] = np.concatenate(
            [[0], np.cumsum(lengths)])
        # permute the bag list segment-wise to follow its runs
        n_valid = int(offs[n_live])
        starts = offs[:n_live][order]
        ends = np.cumsum(lengths)
        gather = (np.repeat(starts - np.concatenate([[0], ends[:-1]]),
                            lengths) + np.arange(n_valid)) \
            if n_live else np.empty((0,), np.int64)
        new_bags = bags.copy()
        new_bags[:n_valid] = bags[gather]
        return {"plan_rows": new_rows,
                "plan_offsets": new_offs.astype(np.int32),
                "plan_bags": new_bags}

    def mark_updated(self, state, new_cache: jax.Array,
                     new_cache_accum: jax.Array) -> None:
        """Install post-update cache arrays (dirty bits were already set by
        `prepare(train=True)` / the async plan). Accepts CacheState or
        AsyncCacheState."""
        state.cache = new_cache
        state.cache_accum = new_cache_accum

    # -- writeback -----------------------------------------------------------

    def flush(self, state: CacheState) -> int:
        """Write every dirty slot back to the capacity tier (rows stay
        cached, now clean). Returns rows written back."""
        slots = np.flatnonzero(state.dirty)
        if len(slots) == 0:
            return 0
        (state.capacity, state.cache, state.cap_accum, state.cache_accum,
         state.freq) = cache_ops.cache_exchange(
            state.capacity, state.cache, state.cap_accum, state.cache_accum,
            state.freq, jnp.asarray(slots, jnp.int32),
            jnp.asarray(state.slot_row[slots], jnp.int32),
            jnp.full((len(slots),), -1, jnp.int32),
            jnp.zeros((len(slots),), jnp.float32),
            use_kernel=self.use_kernel, interpret=self.interpret)
        state.dirty[slots] = False
        state.stats.writebacks += len(slots)
        return len(slots)

    def materialize(self, state: CacheState
                    ) -> tuple[jax.Array, jax.Array]:
        """Flush and return the up-to-date (mega, accum) capacity arrays —
        what a checkpoint or an uncached evaluator should read."""
        self.flush(state)
        return state.capacity, state.cap_accum

    # -- EmbeddingTier protocol surface (core/tiers.py) ----------------------

    def take(self, state: CacheState, idx, train: bool = True,
             plan=None) -> np.ndarray:
        """Protocol `take` (core/tiers.py EmbeddingTier): make the batch
        current and return its device-tier index remap. The sync tier
        plans, fetches, and installs inside this one call — `prepare` by
        its protocol name."""
        return self.prepare(state, idx, train=train, plan=plan)

    def stage(self, state: CacheState, idx, train: bool = True,
              plan=None) -> np.ndarray | None:
        """Protocol `stage` (overlap the NEXT batch's fetch): the sync
        tier performs every fetch inside its own `take`, so there is
        nothing to stage ahead — returns None."""
        return None

    def prefetch_rows(self, state: CacheState, rows,
                      gate: bool = False) -> int:
        """Protocol alias of `prefetch`: best-effort admission of unique
        global `rows` ahead of use. Returns rows admitted."""
        return self.prefetch(state, rows, gate=gate)

    def commit(self, state: CacheState) -> int:
        """Protocol `commit`: the sync tier installs fetched rows inside
        `take`, so nothing is ever pending — returns 0."""
        return 0

    def stats(self, state: CacheState) -> CacheStats:
        """Protocol accessor for the tier's CacheStats."""
        return state.stats

    def placement(self) -> dict:
        """Static tier layout, fastest level first (protocol accessor;
        the bulk-backed tier appends its third level)."""
        return {"strategy": "cached_host", "stream": "sync",
                "levels": [{"tier": "hbm", "rows": self.cache_rows},
                           {"tier": "dram",
                            "rows": self.ebc.plan.total_rows}]}

    # -- async exchange stream (docs/cache.md "Async fetch stream") ----------
    #
    # Per-step protocol (k = in-flight batch):
    #
    #   take_async(k)      pop the staged plan for batch k (or plan now on a
    #                      cold start / strict-sync fallback), mark its
    #                      working set in-flight, then COMMIT every pending
    #                      fetch — dispatched after batch k-1's update, so
    #                      dirty-victim writebacks read post-update rows.
    #   <device step k dispatched against the committed cache slab>
    #   stage_async(k+1)   plan batch k+1's admission on the host, dispatch
    #                      the capacity-tier fetch into a fresh shadow slab
    #                      (reads tiers only — overlaps step k's compute),
    #                      flip the host slot maps eagerly, queue the commit.
    #
    # Victim selection protects the union of the in-flight working set and
    # every queued plan's working set, so a slot admitted at epoch k+1 is
    # never one batch k still reads/writes (the slot_epoch invariant).

    def init_async_state(self, mega: jax.Array,
                         accum: jax.Array | None = None) -> AsyncCacheState:
        """Async twin of init_state: same owned-buffer contract (exchange
        kernels donate the tiers), host-resident LFU scores, empty commit
        queue at epoch 0."""
        r, d = mega.shape
        assert r == self.ebc.plan.total_rows, (r, self.ebc.plan.total_rows)
        c = self.cache_rows
        if accum is None:
            accum = jnp.zeros((r,), jnp.float32)
        return AsyncCacheState(
            capacity=jnp.array(mega, copy=True),
            cap_accum=jnp.array(accum, jnp.float32, copy=True),
            cache=jnp.zeros((c, d), mega.dtype),
            cache_accum=jnp.zeros((c,), jnp.float32),
            freq=np.zeros((c,), np.float32),
            slot_row=np.full((c,), -1, np.int64),
            row_slot=np.full((r,), -1, np.int32),
            dirty=np.zeros((c,), bool),
            slot_epoch=np.zeros((c,), np.int64),
            epoch=0,
            pending=[],
            inflight_mask=None,
            staged=None,
            ema=np.zeros((r,), np.float32),
            ema_tick=np.zeros((r,), np.int64),
            tick=0,
            stats=self._stats_cls())

    def _protected_mask(self, astate: AsyncCacheState) -> np.ndarray:
        """Slots no plan may evict: the in-flight batch's working set,
        every queued (uncommitted) plan's working set, AND the staged
        batch's working set. The staged mask must be carried independently
        of the queue: a drain (below) commits and clears the staged plan's
        pending entry while its remap is still outstanding — evicting its
        slots then would silently invalidate `StagedBatch.local`."""
        protect = np.zeros((astate.cache_rows,), bool)
        if astate.inflight_mask is not None:
            protect |= astate.inflight_mask
        if astate.staged is not None:
            protect |= astate.staged.ws_mask
        for p in astate.pending:
            protect |= p.ws_mask
        return protect

    def _drain_if_fetching_queued_victims(self, astate: AsyncCacheState,
                                          missing: np.ndarray) -> None:
        """A row being fetched whose DIRTY eviction is still queued would
        read a stale capacity value (its latest value lives in the victim
        slot until that writeback commits). Drain the queue first in that
        case — committing early is always safe: the queued writebacks
        consume `astate.cache`, which already carries every dispatched
        update, so ordering is preserved by data dependency. Only the
        fetch-ahead overlap of the drained entries is lost."""
        if not len(missing) or not astate.pending:
            return
        queued = [p.evict_rows[p.evict_rows >= 0] for p in astate.pending]
        queued_wb = np.concatenate(queued) if queued else queued
        if len(queued_wb) and np.intersect1d(missing, queued_wb).size:
            self.commit_async(astate)

    def _admit_async(self, astate: AsyncCacheState, missing: np.ndarray,
                     extra_protect: np.ndarray, seed: np.ndarray,
                     strict: bool, gate: bool = False) -> PendingCommit:
        """Shared admission core of `_plan_async` and `stage_rows`: drain
        the queue if a missing row's dirty eviction is still pending,
        choose free slots then coldest unprotected victims, dispatch the
        shadow fetch, flip the host maps eagerly, and queue the commit.

        `seed` holds per-missing-row LFU seeds (EMA scores under the EMA
        admission policy, else batch counts for plans / 1.0 for prefetch).
        `strict=True` raises on overflow (a planned batch MUST become
        resident); `strict=False` truncates `missing` (best-effort
        prefetch), and with `gate=True` also applies the EMA admission
        threshold (`_gate_admission`) first. Returns the queued
        PendingCommit, whose ws_mask covers the admitted slots (callers
        widen it for full batch working sets)."""
        self._drain_if_fetching_queued_victims(astate, missing)
        protect = self._protected_mask(astate) | extra_protect
        if not strict:
            if gate and len(missing):
                keep = _gate_admission(astate.slot_row, astate.freq,
                                       protect, missing, seed)
                missing, seed = missing[keep], seed[keep]
            free = int((astate.slot_row < 0).sum())
            evictable = int(((astate.slot_row >= 0) & ~protect).sum())
            missing = missing[:free + evictable]
            seed = seed[:len(missing)]
        n = len(missing)
        slots, victims = _pick_slots(
            astate.slot_row, astate.freq, n, protect,
            "the staged + in-flight working sets exceed cache_rows="
            f"{astate.cache_rows}; raise the HBM budget, shrink the "
            "batch, or reduce the lookahead depth")
        evicted_rows = astate.slot_row[victims]
        wb_mask = astate.dirty[victims]
        evict_rows = np.full((n,), -1, np.int64)
        evict_rows[len(slots) - len(victims):] = np.where(
            wb_mask, evicted_rows, -1)
        src_pos = None
        if n:
            # fault gate first: staged plans that die here leave the maps
            # unflipped and the queue intact (the batch re-plans at take)
            _fetch_guard(self.injector, self.retry)
            # tier hook: promote bulk-resident rows into capacity before
            # the shadow fetch below reads it (no-op on the two-tier
            # collection); its own "bulk.fetch" guard also fires pre-mutation
            self._stage_capacity(astate, missing)
            # fetch into a fresh shadow slab — reads the tiers only, so it
            # overlaps the in-flight batch's device compute
            if self.fetch_chunk > 1:
                shadow, shadow_accum, src_pos = _chunked_shadow_fetch(
                    astate.capacity, astate.cap_accum, missing,
                    self.fetch_chunk, astate.stats, self.use_kernel,
                    self.interpret)
            else:
                shadow, shadow_accum = cache_ops.cache_fetch(
                    astate.capacity, astate.cap_accum,
                    jnp.asarray(missing, jnp.int32),
                    use_kernel=self.use_kernel, interpret=self.interpret)
        else:
            shadow = shadow_accum = None
        epoch = astate.epoch + 1
        astate.epoch = epoch
        # eagerly flip the host maps to the post-commit view (the cheap
        # slot-map swap): later plans see these admissions as resident
        astate.row_slot[evicted_rows] = -1
        astate.slot_row[slots] = missing
        astate.row_slot[missing] = slots.astype(np.int32)
        astate.dirty[slots] = False
        astate.freq[slots] = seed.astype(np.float32)
        astate.slot_epoch[slots] = epoch
        ws_mask = np.zeros((astate.cache_rows,), bool)
        ws_mask[slots] = True
        astate.stats.fetches += n
        astate.stats.evictions += len(victims)
        astate.stats.writebacks += int(wb_mask.sum())
        pending = PendingCommit(epoch, slots.astype(np.int64), evict_rows,
                                missing, victims, ws_mask, shadow,
                                shadow_accum, src_pos)
        if n:                                  # nothing to commit for all-hit
            astate.pending.append(pending)
        # tier hook AFTER the queue append: an overflow demotion that must
        # drain pending dirty writebacks then sees this entry too
        self._absorb_evictions(astate, evicted_rows)
        return pending

    def _plan_async(self, astate: AsyncCacheState, idx: np.ndarray,
                    train: bool, plan=None) -> StagedBatch:
        """Plan one batch's admission: host-side LFU accounting + victim
        choice, dispatch the shadow fetch, flip the maps, queue the commit.
        Never blocks on device work. `plan` replaces the np.unique sort
        with the reader thread's bucketing — see `_split_batch`."""
        (idx, valid, rows, counts, hit_slots, hit_counts, missing,
         miss_counts) = self._split_batch(idx, astate.row_slot,
                                          astate.cache_rows, plan)
        # host LFU (same math as kernels/ref.lfu_touch_ref, in np.float32):
        # decay everything, bump hit slots; admitted slots seeded by admit
        astate.freq *= np.float32(self.decay)
        astate.freq[hit_slots] += hit_counts.astype(np.float32)
        # per-ROW EMA, same clock discipline as the sync `prepare`
        astate.tick += 1
        _ema_touch(astate.ema, astate.ema_tick, rows, counts, astate.tick,
                   self.decay)
        extra = np.zeros((astate.cache_rows,), bool)
        extra[hit_slots] = True
        n = len(missing)
        seeds = astate.ema[missing] if self.ema_admission \
            else miss_counts.astype(np.float32)
        pending = self._admit_async(astate, missing, extra, seeds,
                                    strict=True)
        ws_slots = astate.row_slot[rows]
        pending.ws_mask[ws_slots] = True       # widen: full batch working set
        if train:
            astate.dirty[ws_slots] = True
        hits = int(counts.sum()) - n
        astate.stats.hits += hits
        astate.stats.misses += n
        astate.stats.steps += 1
        return StagedBatch(pending.epoch, idx.copy(),
                           self._remap(astate.row_slot, idx, valid),
                           pending.ws_mask, hits, n)

    def stage_async(self, astate: AsyncCacheState, idx,
                    train: bool = True, plan=None) -> np.ndarray:
        """Stage the NEXT batch: plan + dispatch its shadow fetch while the
        in-flight batch computes. Returns the slot-space remap, which
        `take_async` hands back when the batch becomes current."""
        staged = self._plan_async(astate, idx, train, plan)
        astate.staged = staged
        return staged.local

    def stage_rows(self, astate: AsyncCacheState, rows,
                   gate: bool = False) -> int:
        """Best-effort k-step-lookahead admission (the async twin of
        `prefetch`): queue a fetch for `rows` without hit/miss accounting
        and without evicting any protected slot; overflow beyond
        free+evictable space is dropped. `gate=True` adds the EMA admission
        threshold (see `prefetch`). Returns rows admitted."""
        rows = np.unique(np.asarray(rows))
        rows = rows[rows >= 0]
        missing = rows[astate.row_slot[rows] < 0]
        if len(missing) == 0:
            return 0
        extra = np.zeros((astate.cache_rows,), bool)
        keep = astate.row_slot[rows[astate.row_slot[rows] >= 0]]
        extra[keep] = True                     # requested residents survive
        if self.ema_admission:
            seeds = _ema_score(astate.ema, astate.ema_tick, missing,
                               astate.tick, self.decay) + np.float32(1.0)
        else:
            seeds = np.ones((len(missing),), np.float32)
        pending = self._admit_async(astate, missing, extra, seeds,
                                    strict=False, gate=gate)
        n = len(pending.rows)
        astate.stats.prefetched += n
        return n

    def take_async(self, astate: AsyncCacheState, idx,
                   train: bool = True, plan=None) -> np.ndarray:
        """Make `idx`'s batch current: reuse its staged plan when one
        matches (the overlapped path), else plan it now (cold start /
        strict-sync fallback). Marks the working set in-flight and commits
        every pending fetch — the commit is dispatched after the previous
        batch's update, so dirty-victim writebacks read post-update rows.
        Returns the (B, F, L) slot-space indices."""
        idx = np.asarray(idx)
        st = astate.staged
        astate.staged = None
        if st is None or st.idx_key.shape != idx.shape or \
                not np.array_equal(st.idx_key, idx):
            if st is not None:
                # the discarded plan degrades to a prefetch: its rows were
                # admitted, but its batch never runs — re-book its stat
                # contribution so steps/hit-rate reflect real batches only
                astate.stats.hits -= st.hits
                astate.stats.misses -= st.misses
                astate.stats.steps -= 1
                astate.stats.prefetched += st.misses
            st = self._plan_async(astate, idx, train, plan)
        astate.inflight_mask = st.ws_mask
        self.commit_async(astate)
        return st.local

    def commit_async(self, astate: AsyncCacheState) -> int:
        """Drain the pending queue in order: each entry's dirty victims
        write back (post-update values) and its shadow rows install into
        their slots. Cheap device-side row copies — the slow capacity fetch
        already happened off the critical path. Returns entries committed."""
        done = 0
        for p in astate.pending:
            if len(p.slots) == 0:
                continue
            (astate.capacity, astate.cache, astate.cap_accum,
             astate.cache_accum) = cache_ops.cache_commit(
                astate.capacity, astate.cache, astate.cap_accum,
                astate.cache_accum, p.shadow, p.shadow_accum,
                jnp.asarray(p.slots, jnp.int32),
                jnp.asarray(p.evict_rows, jnp.int32),
                jnp.asarray(p.rows, jnp.int32),
                use_kernel=self.use_kernel, interpret=self.interpret,
                src_pos=None if p.src_pos is None
                else jnp.asarray(p.src_pos, jnp.int32))
            done += 1
        astate.pending.clear()
        return done

    def lookup_async(self, astate: AsyncCacheState, idx,
                     train: bool = False, rules=None) -> jax.Array:
        """take_async + cache lookup: numerically identical to the sync
        `lookup` and to the uncached collection on the same indices."""
        local = self.take_async(astate, idx, train)
        return self.ebc.lookup({"mega": astate.cache},
                               jnp.asarray(local), rules)

    def flush_async(self, astate: AsyncCacheState) -> int:
        """Commit all pending fetches, then write every dirty slot back to
        the capacity tier (rows stay cached, now clean). Returns rows
        written back."""
        self.commit_async(astate)
        slots = np.flatnonzero(astate.dirty)
        if len(slots) == 0:
            return 0
        (astate.capacity, astate.cache, astate.cap_accum, astate.cache_accum,
         _) = cache_ops.cache_exchange(
            astate.capacity, astate.cache, astate.cap_accum,
            astate.cache_accum, jnp.asarray(astate.freq),
            jnp.asarray(slots, jnp.int32),
            jnp.asarray(astate.slot_row[slots], jnp.int32),
            jnp.full((len(slots),), -1, jnp.int32),
            jnp.zeros((len(slots),), jnp.float32),
            use_kernel=self.use_kernel, interpret=self.interpret)
        astate.dirty[slots] = False
        astate.stats.writebacks += len(slots)
        return len(slots)

    def materialize_async(self, astate: AsyncCacheState
                          ) -> tuple[jax.Array, jax.Array]:
        """flush_async and return the up-to-date (mega, accum) capacity
        arrays — bit-identical to the sync path's `materialize` after the
        same batch sequence (asserted in tests/test_cache_async.py)."""
        self.flush_async(astate)
        return astate.capacity, astate.cap_accum

    # -- checkpointing -------------------------------------------------------

    def state_dict(self, state: CacheState | AsyncCacheState) -> dict:
        """Checkpoint-ready pytree of numpy leaves covering the WHOLE tier —
        both device arrays (capacity/cache/accumulators) and the host-side
        maps (slot_row/row_slot/dirty/EMA) that a params-only checkpoint
        would lose, leaving the restored job re-warming a cold cache and
        diverging from the uninterrupted run (accumulators live per-slot
        while a row is cached).

        For AsyncCacheState the pending queue is drained to a sync point
        first (commit_async) and a staged-but-unconsumed plan is unwound to
        a prefetch exactly as take_async does on an idx mismatch — its rows
        stay admitted, and the restored run re-plans the batch against the
        now-resident rows, so the model math is unchanged. Mutates `state`
        (drain + unwind) before snapshotting it."""
        is_async = isinstance(state, AsyncCacheState)
        if is_async:
            self.commit_async(state)
            st = state.staged
            state.staged = None
            if st is not None:
                state.stats.hits -= st.hits
                state.stats.misses -= st.misses
                state.stats.steps -= 1
                state.stats.prefetched += st.misses
            state.inflight_mask = None
        d = {k: np.asarray(getattr(state, k)) for k in
             ("capacity", "cap_accum", "cache", "cache_accum", "freq",
              "slot_row", "row_slot", "dirty", "ema", "ema_tick")}
        d["tick"] = np.int64(state.tick)
        d["stats"] = {k: np.int64(v)
                      for k, v in dataclasses.asdict(state.stats).items()}
        if is_async:
            d["slot_epoch"] = np.asarray(state.slot_epoch)
            d["epoch"] = np.int64(state.epoch)
        return d

    def load_state_dict(self, d: dict) -> CacheState | AsyncCacheState:
        """Rebuild the tier from a `state_dict` pytree (leaves may come back
        as jax arrays from CheckpointManager.restore — each is coerced to
        the side init_state/init_async_state put it on). The presence of
        the async-only `epoch` key selects the state flavour."""
        stats = self._stats_cls(**{k: int(v) for k, v in d["stats"].items()})
        dev = {k: jnp.asarray(d[k]) for k in
               ("capacity", "cap_accum", "cache", "cache_accum")}
        # restored leaves may alias read-only device buffers; the host-side
        # maps are mutated in place by the planner, so force owned copies
        host = dict(
            slot_row=np.array(d["slot_row"], np.int64),
            row_slot=np.array(d["row_slot"], np.int32),
            dirty=np.array(d["dirty"], bool),
            ema=np.array(d["ema"], np.float32),
            ema_tick=np.array(d["ema_tick"], np.int64))
        if "epoch" in d:
            return AsyncCacheState(
                **dev, freq=np.array(d["freq"], np.float32), **host,
                slot_epoch=np.array(d["slot_epoch"], np.int64),
                epoch=int(d["epoch"]), pending=[], inflight_mask=None,
                staged=None, tick=int(d["tick"]), stats=stats)
        return CacheState(**dev, freq=jnp.asarray(d["freq"]), **host,
                          tick=int(d["tick"]), stats=stats)


# ---------------------------------------------------------------------------
# Multi-host cache coherence (docs/cache.md "Multi-host coherence")
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RouteStats:
    """Per-row traffic counters of the multi-host tier: which shard served
    each capacity-tier touch. `local` means the touching host owns the row
    (owner == host); `remote` rows crossed the host interconnect — the
    all-to-all legs the exchange-traffic model prices
    (launch/analysis.py multihost_exchange_traffic)."""
    fetch_local: int = 0       # miss rows served by the host's own shard
    fetch_remote: int = 0      # miss rows pulled from a remote owner
    refresh_local: int = 0     # post-update working-set rows, own shard
    refresh_remote: int = 0    # ... returned by a remote owner
    grad_pairs_local: int = 0  # (row, bag) grads aggregated at a local owner
    grad_pairs_remote: int = 0  # pairs routed to a remote owner
    dup_rows: int = 0          # rows in >1 host's working set (reduced ONCE
                               # at the owner instead of updated twice)
    invalidations: int = 0     # cached copies dropped after a remote update
    fetch_chunks: int = 0      # per-(host, owner) DMA descriptors after
                               # run-coalescing the miss messages
    steps: int = 0

    @property
    def remote_fetch_fraction(self) -> float:
        """Fraction of fetched rows served by a REMOTE owner shard."""
        total = self.fetch_local + self.fetch_remote
        return self.fetch_remote / total if total else 0.0

    def snapshot(self) -> dict[str, float]:
        """Flat metrics dict (the train-loop logging payload)."""
        return {"route_fetch_local": float(self.fetch_local),
                "route_fetch_remote": float(self.fetch_remote),
                "route_refresh_remote": float(self.refresh_remote),
                "route_grad_pairs_remote": float(self.grad_pairs_remote),
                "route_dup_rows": float(self.dup_rows),
                "route_invalidations": float(self.invalidations),
                "route_fetch_chunks": float(self.fetch_chunks),
                "route_remote_fetch_fraction": self.remote_fetch_fraction}

    def reset(self) -> None:
        """Zero every counter in place (the RouteStats side of the sweep
        isolation contract — see `CacheStats.reset`)."""
        for f in dataclasses.fields(self):
            setattr(self, f.name, 0)


@dataclasses.dataclass
class MultiHostCacheState:
    """State of the data-parallel cached tier: ONE row-sharded capacity
    tier (owner h holds rows [h*shard_rows, (h+1)*shard_rows)) under H
    independent per-host hot caches over the WHOLE row space.

    Cached copies are CLEAN BY CONSTRUCTION — the coherence invariant that
    replaces the single-host dirty-bit machinery: sparse updates are routed
    to the owning shard and applied there ONCE (duplicate rows reduced in
    host order), each host's working set is refreshed from the post-update
    capacity inside the same step, and copies a REMOTE update left stale
    are invalidated before the next batch plans. Eviction therefore never
    writes back, and the AdaGrad accumulator never leaves the owner."""
    capacity: jax.Array        # (R, d) row-sharded capacity tier
    cap_accum: jax.Array       # (R,) fp32 AdaGrad accumulator, owner-only
    caches: jax.Array          # (H, C, d) per-host clean hot caches
    freq: np.ndarray           # (H, C) host fp32 LFU-with-decay scores
    slot_row: np.ndarray       # (H, C) int64: row held by slot, -1 free
    row_slot: np.ndarray       # (H, R) int32: slot holding row, -1 uncached
    ema: np.ndarray            # (R,) fp32 EMA-decayed GLOBAL per-row counts
    ema_tick: np.ndarray       # (R,) int64 tick of each row's last EMA touch
    tick: int                  # EMA clock: one tick per planned batch
    stats: CacheStats          # aggregate over hosts
    route: RouteStats

    @property
    def n_hosts(self) -> int:
        """Host count H (one hot cache each)."""
        return int(self.caches.shape[0])

    @property
    def cache_rows(self) -> int:
        """Per-host device-tier height C (slots)."""
        return int(self.caches.shape[1])


@dataclasses.dataclass
class MultiHostStepPlan:
    """One batch's host-planned device worklist: every array the jitted
    multi-host step consumes (train/steps.py). All index arrays are
    -1-padded to static shapes so the step compiles once."""
    local_idx: np.ndarray      # (H, B/H, F, L) slot-space remap
    miss_rows: np.ndarray      # (H, M) capacity rows to install pre-forward
    miss_slots: np.ndarray     # (H, M) destination cache slots
    ws_rows: np.ndarray        # (H, M) working-set rows to refresh post-update
    ws_slots: np.ndarray       # (H, M) their cache slots
    seg_rows: np.ndarray       # (H, U) OWNER-LOCAL unique rows per segment
    seg_offsets: np.ndarray    # (H, U+1) absolute positions into bag_ids
    seg_base: np.ndarray       # (H,) owner row bases
    bag_ids: np.ndarray        # (N,) shared flat-bag list of the global plan


@dataclasses.dataclass(frozen=True)
class MultiHostCachedEmbeddingBagCollection:
    """The cached embedding tier under data parallelism (ROADMAP multi-host
    coherence item; MTrainS's heterogeneous-memory tier): H hosts each run
    a `cache_rows` hot cache over a capacity tier row-sharded across the
    SAME H hosts. Misses resolve through a plan-driven all-to-all against
    the owning shard — the per-batch SparsePlan's sorted live prefix IS the
    miss set grouped by owner (searchsorted on shard boundaries, no sort) —
    and gradients for rows cached on several hosts are routed to the owner
    and reduced once before the fused AdaGrad update (per-owner segments,
    kernels/sparse_update.py).

    Numerics contract: with the data-parallel batch split h -> examples
    [h*B/H, (h+1)*B/H), owner-side reduction concatenates host runs in
    host order == flat-batch order, so the whole tier is BIT-EXACT vs the
    dense single-host oracle (asserted in tests/test_cache_multihost.py).
    """
    ebc: EmbeddingBagCollection
    n_hosts: int
    cache_rows: int
    decay: float = 0.98
    use_kernel: bool | None = None
    interpret: bool = False
    ema_admission: bool = True  # same policy as the single-host tier; the
                                # EMA is GLOBAL (row space), shared by all
                                # hosts' admission decisions
    fetch_chunk: int = 1       # all-to-all miss-message granularity in
                               # rows: >1 coalesces each (host, owner)
                               # message's sorted rows into contiguous
                               # blocks (booked in RouteStats.fetch_chunks)
    injector: Any = None       # FaultInjector firing "cache.fetch" once
                               # per planned global batch (before any host
                               # map mutates — a fault leaves plan_step
                               # cleanly replayable)
    retry: Any = None          # RetryPolicy for transient faults, as in
                               # the single-host tier

    @classmethod
    def build(cls, cfg: DLRMConfig, n_hosts: int,
              cache_rows: int | None = None, decay: float = 0.98,
              use_kernel: bool | None = None, interpret: bool = False,
              ema_admission: bool = True, fetch_chunk: int = 1
              ) -> MultiHostCachedEmbeddingBagCollection:
        """Build over a fresh `n_hosts`-sharded EmbeddingBagCollection; see
        the class fields for the knobs."""
        ebc = EmbeddingBagCollection.build(cfg, n_shards=n_hosts,
                                           strategy="cached_host",
                                           capacity_shards=n_hosts)
        rows = cache_rows if cache_rows is not None else ebc.plan.cache_rows
        assert rows > 0, "cached_host plan produced an empty cache"
        return cls(ebc, int(n_hosts), int(rows), decay, use_kernel,
                   interpret, ema_admission, int(fetch_chunk))

    @property
    def shard_rows(self) -> int:
        """Capacity rows owned by each host shard."""
        return self.ebc.plan.shard_rows

    # -- state ---------------------------------------------------------------

    def init_state(self, mega: jax.Array, accum: jax.Array | None = None,
                   capacity_sharding=None) -> MultiHostCacheState:
        """mega: (total_rows, d) capacity tier; accum: optional (rows,)
        fp32. `capacity_sharding` (e.g. NamedSharding(mesh, plan.pspec))
        places the copied capacity arrays on the host mesh — the train
        step's shard_map update then runs against real shards.

        A mega SHORTER than total_rows (a single-host layout, whose tail
        padding is 8-aligned rather than H*8-aligned) is zero-padded into
        the sharded layout; pad rows are unreachable by construction
        (indices stay below the logical row count)."""
        r, d = mega.shape
        total = self.ebc.plan.total_rows
        assert r <= total, (r, total)
        h, c = self.n_hosts, self.cache_rows
        if accum is None:
            accum = jnp.zeros((r,), jnp.float32)
        capacity = jnp.zeros((total, d), mega.dtype).at[:r].set(mega)
        cap_accum = jnp.zeros((total,), jnp.float32).at[:r].set(
            jnp.asarray(accum, jnp.float32))
        if capacity_sharding is not None:
            capacity = jax.device_put(capacity, capacity_sharding)
            from jax.sharding import NamedSharding, PartitionSpec as P
            cap_sh = NamedSharding(capacity_sharding.mesh,
                                   P(*capacity_sharding.spec[:1]))
            cap_accum = jax.device_put(cap_accum, cap_sh)
        return MultiHostCacheState(
            capacity=capacity,
            cap_accum=cap_accum,
            caches=jnp.zeros((h, c, d), mega.dtype),
            freq=np.zeros((h, c), np.float32),
            slot_row=np.full((h, c), -1, np.int64),
            row_slot=np.full((h, total), -1, np.int32),
            ema=np.zeros((total,), np.float32),
            ema_tick=np.zeros((total,), np.int64),
            tick=0,
            stats=CacheStats(),
            route=RouteStats())

    # -- per-host admission --------------------------------------------------

    def _admit_host(self, state: MultiHostCacheState, h: int,
                    missing: np.ndarray, seeds: np.ndarray,
                    protect: np.ndarray) -> np.ndarray:
        """Assign cache slots on host h for `missing` rows: free slots
        first, then the coldest unprotected residents. `seeds` holds the
        slots' initial LFU scores (EMA scores under the EMA admission
        policy, else batch counts). Clean caches make eviction
        writeback-free — the displaced copy is dropped (its authoritative
        value lives at the owner). Returns the slots."""
        n = len(missing)
        if n == 0:
            return np.empty((0,), np.int64)
        slots, victims = _pick_slots(
            state.slot_row[h], state.freq[h], n, protect,
            f"host {h}'s batch working set exceeds cache_rows="
            f"{state.cache_rows}; raise the HBM budget or shrink the "
            "per-host batch")
        evicted = state.slot_row[h, victims]
        state.row_slot[h, evicted] = -1
        state.slot_row[h, slots] = missing
        state.row_slot[h, missing] = slots.astype(np.int32)
        state.freq[h, slots] = seeds.astype(np.float32)
        state.stats.fetches += n
        state.stats.evictions += len(victims)
        return slots

    # -- step planning -------------------------------------------------------

    def plan_step(self, state: MultiHostCacheState, idx,
                  host_plans=None, global_plan=None,
                  train: bool = True) -> MultiHostStepPlan:
        """Plan one global batch: per host, split its contiguous sub-batch
        into hits/misses off its sub-plan (`kernels.split_plan_by_host` —
        the live prefix IS the host's sorted unique row set, so miss dedup
        stays sort-free), admit misses (LFU eviction, clean drop), and
        remap to slot space. Cross-host legs are booked in RouteStats by
        grouping each host's rows by owning shard (a row // shard_rows,
        order-preserving on the sorted prefix). When `train`, also slices
        the global plan into per-owner update segments
        (`split_plan_by_owner`) and invalidates cached copies that this
        step's REMOTE updates will leave stale (working-set copies are
        exempt — the step refreshes them from the post-update capacity).

        idx: (B, F, L) OFFSET global rows, B divisible by n_hosts;
        host_plans/global_plan: hook-attached artifacts
        (`kernels.host_plans_from_batch` / `host_plan_from_batch`), built
        here when absent. Mutates the host maps; returns the device
        worklist for the jitted step half."""
        from repro.kernels.sparse_plan import (build_sparse_plan_host,
                                               split_plan_by_host,
                                               split_plan_by_owner)
        # fault gate before ANY mutation (tick/EMA/maps): a propagated
        # transient fault makes this call a clean no-op to replay
        _fetch_guard(self.injector, self.retry)
        idx = np.asarray(idx)
        b, f, lk = idx.shape
        hn = self.n_hosts
        assert b % hn == 0, (b, hn)
        bh = b // hn
        if global_plan is None:
            global_plan = build_sparse_plan_host(idx)
        if host_plans is None:
            host_plans = split_plan_by_host(global_plan, hn, bh * f)
        m = bh * f * lk                       # per-host worklist capacity
        local_idx = np.empty((hn, bh, f, lk), np.int32)
        miss_rows = np.full((hn, m), -1, np.int32)
        miss_slots = np.full((hn, m), -1, np.int32)
        ws_rows = np.full((hn, m), -1, np.int32)
        ws_slots = np.full((hn, m), -1, np.int32)
        g_rows = np.asarray(global_plan.unique_rows)
        n_live = int((g_rows >= 0).sum())
        dup = -n_live
        state.tick += 1          # one EMA tick per planned global batch
        for h in range(hn):
            sub = idx[h * bh:(h + 1) * bh]
            (sub, valid, rows, counts, hit_slots, hit_counts, missing,
             miss_counts) = CachedEmbeddingBagCollection._split_batch(
                sub, state.row_slot[h], self.cache_rows, host_plans[h])
            dup += len(rows)
            # host LFU: decay everything, bump hits; admissions seed below
            state.freq[h] *= np.float32(self.decay)
            state.freq[h, hit_slots] += hit_counts.astype(np.float32)
            # GLOBAL per-row EMA: hosts touch sequentially, so shared rows
            # accumulate every host's counts at this tick
            _ema_touch(state.ema, state.ema_tick, rows, counts, state.tick,
                       self.decay)
            protect = np.zeros((self.cache_rows,), bool)
            protect[hit_slots] = True
            seeds = state.ema[missing] if self.ema_admission \
                else miss_counts.astype(np.float32)
            slots = self._admit_host(state, h, missing, seeds, protect)
            miss_rows[h, :len(missing)] = missing
            miss_slots[h, :len(missing)] = slots
            ws_rows[h, :len(rows)] = rows
            ws_slots[h, :len(rows)] = state.row_slot[h, rows]
            local_idx[h] = CachedEmbeddingBagCollection._remap(
                state.row_slot[h], sub, valid)
            state.stats.hits += int(counts.sum()) - len(missing)
            state.stats.misses += len(missing)
            owner_m = missing // self.shard_rows
            state.route.fetch_remote += int((owner_m != h).sum())
            state.route.fetch_local += int((owner_m == h).sum())
            if self.fetch_chunk > 1 and len(missing):
                # chunk the per-(host, owner) all-to-all messages: each
                # owner's slice of the sorted miss list coalesces on its
                # own (blocks never straddle shard boundaries)
                chunk = min(self.fetch_chunk, self.shard_rows)
                cuts = np.searchsorted(
                    missing, np.arange(hn + 1) * self.shard_rows)
                for s in range(hn):
                    a, b_ = int(cuts[s]), int(cuts[s + 1])
                    if b_ > a:
                        starts, pos = coalesce_rows(
                            missing[a:b_] - s * self.shard_rows, chunk,
                            self.shard_rows,
                            min_fill=_chunk_min_fill(chunk))
                        n_single = int((pos < 0).sum())
                        descs = len(starts) + n_single
                        state.route.fetch_chunks += descs
                        state.stats.fetch_chunks += descs
                        state.stats.overfetch_rows += \
                            len(starts) * chunk - (b_ - a - n_single)
            if train:
                owner_w = rows // self.shard_rows
                remote = owner_w != h
                state.route.refresh_remote += int(remote.sum())
                state.route.refresh_local += int((~remote).sum())
                state.route.grad_pairs_remote += int(counts[remote].sum())
                state.route.grad_pairs_local += int(counts[~remote].sum())
        state.stats.steps += 1
        state.route.steps += 1
        state.route.dup_rows += max(dup, 0)
        if train:
            touched = g_rows[:n_live].astype(np.int64)
            for h in range(hn):
                slots_t = state.row_slot[h, touched]
                resident = slots_t >= 0
                in_ws = np.zeros((self.cache_rows,), bool)
                wss = ws_slots[h]
                in_ws[wss[wss >= 0]] = True
                kill = resident & ~in_ws[np.clip(slots_t, 0, None)]
                state.slot_row[h, slots_t[kill]] = -1
                state.row_slot[h, touched[kill]] = -1
                state.freq[h, slots_t[kill]] = 0.0
                state.route.invalidations += int(kill.sum())
            seg_rows, seg_offs, seg_base = split_plan_by_owner(
                global_plan, self.shard_rows, hn, seg_cap=len(g_rows))
        else:
            u = len(g_rows)
            seg_rows = np.full((hn, u), -1, np.int32)
            seg_offs = np.zeros((hn, u + 1), np.int32)
            seg_base = np.zeros((hn,), np.int32)
        return MultiHostStepPlan(
            local_idx, miss_rows, miss_slots, ws_rows, ws_slots,
            seg_rows, seg_offs, seg_base,
            np.asarray(global_plan.bag_ids, np.int32))

    # -- slab install (shared by the jitted step and the eager paths) --------

    def fill_slabs(self, caches: jax.Array, source: jax.Array,
                   rows, slots) -> jax.Array:
        """Install `rows` gathered from `source` (the capacity tier) into
        each host's slab at `slots` (-1 pads drop). Pure jnp — traced
        inside the multi-host train step's jit (miss install AND
        post-update refresh) and run eagerly by eval lookups/prefetch, so
        every install leg is the same operation bit for bit.

        caches: (H, C, d); rows/slots: (H, M) int32, -1-padded."""
        c = self.cache_rows
        out = []
        for h in range(self.n_hosts):
            rows_h = jnp.asarray(rows[h], jnp.int32)
            slots_h = jnp.asarray(slots[h], jnp.int32)
            vals = jnp.take(source, jnp.maximum(rows_h, 0), axis=0)
            dst = jnp.where(slots_h >= 0, slots_h, c)
            out.append(caches[h].at[dst].set(vals.astype(caches.dtype),
                                             mode="drop"))
        return jnp.stack(out)

    # -- eval / serving ------------------------------------------------------

    def install_misses(self, state: MultiHostCacheState,
                       splan: MultiHostStepPlan) -> None:
        """Resolve the planned misses eagerly (the all-to-all fetch leg):
        gather each host's missing rows from the owning shards and install
        them in its slab. The train step performs this INSIDE its jit; this
        eager twin serves eval lookups and prefetch."""
        state.caches = self.fill_slabs(state.caches, state.capacity,
                                       splan.miss_rows, splan.miss_slots)

    def lookup(self, state: MultiHostCacheState, idx,
               host_plans=None, global_plan=None) -> jax.Array:
        """plan + fetch + per-host pooled lookup, concatenated back to the
        global batch: numerically identical to the uncached collection on
        the same indices. Eval path (no update legs)."""
        splan = self.plan_step(state, idx, host_plans, global_plan,
                               train=False)
        self.install_misses(state, splan)
        pooled = [self.ebc.lookup({"mega": state.caches[h]},
                                  jnp.asarray(splan.local_idx[h]))
                  for h in range(self.n_hosts)]
        return jnp.concatenate(pooled, axis=0)

    # -- prefetch ------------------------------------------------------------

    def prefetch(self, state: MultiHostCacheState, idx,
                 host_plans=None, global_plan=None,
                 gate: bool = False) -> int:
        """Best-effort admission of the NEXT batch's per-host miss rows so
        the owner fetch overlaps the in-flight step's device compute (the
        dispatch ordering guarantees post-update values — the gather
        consumes the updated capacity array). Never evicts a requested
        resident; overflow beyond free+evictable space is dropped.
        `gate=True` adds the EMA admission threshold per host (see the
        single-host `prefetch`). Returns rows admitted."""
        from repro.kernels.sparse_plan import (build_sparse_plan_host,
                                               split_plan_by_host)
        _fetch_guard(self.injector, self.retry)
        idx = np.asarray(idx)
        b, f, _ = idx.shape
        hn = self.n_hosts
        if global_plan is None:
            global_plan = build_sparse_plan_host(idx)
        if host_plans is None:
            host_plans = split_plan_by_host(global_plan, hn, b // hn * f)
        caches = state.caches
        c = self.cache_rows
        total = 0
        for h in range(hn):
            prows = np.asarray(host_plans[h].unique_rows)
            rows = prows[:int((prows >= 0).sum())].astype(np.int64)
            missing = rows[state.row_slot[h, rows] < 0]
            protect = np.zeros((c,), bool)
            keep = state.row_slot[h, rows[state.row_slot[h, rows] >= 0]]
            protect[keep] = True
            if self.ema_admission:
                seeds = _ema_score(state.ema, state.ema_tick, missing,
                                   state.tick, self.decay) + np.float32(1.0)
            else:
                seeds = np.ones((len(missing),), np.float32)
            if gate and len(missing):
                keep_mask = _gate_admission(state.slot_row[h],
                                            state.freq[h], protect,
                                            missing, seeds)
                missing, seeds = missing[keep_mask], seeds[keep_mask]
            evictable = int(((state.slot_row[h] >= 0) & ~protect).sum())
            free = int((state.slot_row[h] < 0).sum())
            missing, seeds = (missing[:free + evictable],
                              seeds[:free + evictable])
            slots = self._admit_host(state, h, missing, seeds, protect)
            if len(missing):
                vals = jnp.take(state.capacity,
                                jnp.asarray(missing, jnp.int32), axis=0)
                caches = caches.at[h, jnp.asarray(slots, jnp.int32)].set(
                    vals)
            owner = missing // self.shard_rows
            state.route.fetch_remote += int((owner != h).sum())
            state.route.fetch_local += int((owner == h).sum())
            total += len(missing)
        state.caches = caches
        state.stats.prefetched += total
        return total

    def mark_updated(self, state: MultiHostCacheState, capacity: jax.Array,
                     cap_accum: jax.Array, caches: jax.Array) -> None:
        """Install the jitted step's outputs (post-update capacity shards +
        refreshed host slabs)."""
        state.capacity = capacity
        state.cap_accum = cap_accum
        state.caches = caches

    def materialize(self, state: MultiHostCacheState
                    ) -> tuple[jax.Array, jax.Array]:
        """The up-to-date (mega, accum) capacity arrays. No flush needed:
        caches are clean by construction — every update already lives at
        its owner."""
        return state.capacity, state.cap_accum

    # -- EmbeddingTier protocol surface (core/tiers.py) ----------------------

    def take(self, state: MultiHostCacheState, idx, train: bool = True,
             plan=None) -> np.ndarray:
        """Protocol `take`: plan the batch, install its misses eagerly,
        and return the (H, B/H, F, L) slot-space remap. The jitted train
        step uses `plan_step` directly (its device worklist is richer than
        a remap); this entry serves eval / serving call sites. `plan` is
        the global host SparsePlan when the reader thread built one."""
        splan = self.plan_step(state, idx, global_plan=plan, train=train)
        self.install_misses(state, splan)
        return splan.local_idx

    def stage(self, state: MultiHostCacheState, idx, train: bool = True,
              plan=None) -> np.ndarray | None:
        """Protocol `stage`: the multi-host tier overlaps through
        `prefetch` (whole-batch idx) instead of a staged plan — returns
        None."""
        return None

    def prefetch_rows(self, state: MultiHostCacheState, rows,
                      gate: bool = False) -> int:
        """Protocol `prefetch_rows`: the multi-host planner needs the full
        (B, F, L) batch shape to split rows by host (see `prefetch`), so a
        bare row list admits nothing — returns 0."""
        return 0

    def commit(self, state: MultiHostCacheState) -> int:
        """Protocol `commit`: installs happen inside `plan_step`'s device
        worklist (or the eager `install_misses`) — nothing pending."""
        return 0

    def flush(self, state: MultiHostCacheState) -> int:
        """Protocol `flush`: caches are clean by construction (updates are
        owner-routed), so there is never a dirty slot — returns 0."""
        return 0

    def stats(self, state: MultiHostCacheState) -> CacheStats:
        """Protocol accessor for the tier's aggregate CacheStats."""
        return state.stats

    def placement(self) -> dict:
        """Static tier layout, fastest level first (protocol accessor)."""
        return {"strategy": "cached_host", "stream": "multihost",
                "n_hosts": self.n_hosts,
                "levels": [{"tier": "hbm", "rows": self.cache_rows},
                           {"tier": "dram",
                            "rows": self.ebc.plan.total_rows}]}

    # -- checkpointing -------------------------------------------------------

    def state_dict(self, state: MultiHostCacheState) -> dict:
        """Checkpoint-ready pytree of numpy leaves (see the single-host
        CachedEmbeddingBagCollection.state_dict). Nothing to drain: caches
        are clean by construction, so the snapshot is always consistent."""
        d = {k: np.asarray(getattr(state, k)) for k in
             ("capacity", "cap_accum", "caches", "freq",
              "slot_row", "row_slot", "ema", "ema_tick")}
        d["tick"] = np.int64(state.tick)
        d["stats"] = {k: np.int64(v)
                      for k, v in dataclasses.asdict(state.stats).items()}
        d["route"] = {k: np.int64(v)
                      for k, v in dataclasses.asdict(state.route).items()}
        return d

    def load_state_dict(self, d: dict) -> MultiHostCacheState:
        """Rebuild the multi-host tier from a `state_dict` pytree."""
        return MultiHostCacheState(
            capacity=jnp.asarray(d["capacity"]),
            cap_accum=jnp.asarray(d["cap_accum"]),
            caches=jnp.asarray(d["caches"]),
            freq=np.array(d["freq"], np.float32),
            slot_row=np.array(d["slot_row"], np.int64),
            row_slot=np.array(d["row_slot"], np.int32),
            ema=np.array(d["ema"], np.float32),
            ema_tick=np.array(d["ema_tick"], np.int64),
            tick=int(d["tick"]),
            stats=CacheStats(**{k: int(v) for k, v in d["stats"].items()}),
            route=RouteStats(**{k: int(v) for k, v in d["route"].items()}))
