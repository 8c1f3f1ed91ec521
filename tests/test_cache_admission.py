"""Frequency-aware cache management (docs/cache.md "EMA admission"):
EMA-seeded admission, the adaptive admission gate, the ids-by-frequency
reorder, and chunk-granular capacity<->cache transfers.

Covers the PR's contracts: admission is MONOTONE in a row's access
frequency (hypothesis property over `_gate_admission`), a one-off cold
burst cannot evict the Zipf head (the thrash scenario first-touch loses),
and chunked transfers are bit-exact vs per-row transfers (admission
changes *which* rows are cached, never lookup values).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import HAS_HYPOTHESIS, requires_hypothesis
from repro.configs import get_smoke_config
from repro.core.cache import (CachedEmbeddingBagCollection, _chunk_min_fill,
                              _gate_admission)
from repro.core.embedding import EmbeddingBagCollection
from repro.core.placement import frequency_reorder
from repro.data.pipeline import dedup_indices_hook, sparse_plan_hook
from repro.kernels.sparse_plan import coalesce_rows

if HAS_HYPOTHESIS:
    from hypothesis import given, settings
    from hypothesis import strategies as st


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("dlrm-m1")


@pytest.fixture(scope="module")
def ebc(cfg):
    return EmbeddingBagCollection.build(cfg, n_shards=1,
                                        strategy="replicated")


def _rand_mega(cfg, ebc, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(ebc.plan.total_rows, cfg.embed_dim)
                       .astype(np.float32))


def _rand_idx(rng, total, shape=(2, 3, 4)):
    idx = rng.randint(0, total, size=shape).astype(np.int64)
    idx[rng.rand(*shape) < 0.1] = -1           # pads
    return idx


# ---------------------------------------------------------------------------
# admission gate: monotone in access frequency
# ---------------------------------------------------------------------------


def _check_monotone(data):
    """If a row admits, every candidate with a STRICTLY higher EMA score
    admits too — admission is monotone in access frequency."""
    c = data.draw(st.integers(2, 24), label="cache_slots")
    n_res = data.draw(st.integers(0, c), label="residents")
    slot_row = np.full((c,), -1, np.int64)
    slot_row[:n_res] = np.arange(n_res)
    freq = np.array(data.draw(st.lists(
        st.floats(0.0, 50.0), min_size=c, max_size=c)), np.float32)
    protect = np.zeros((c,), bool)
    prot_ix = data.draw(st.lists(st.integers(0, c - 1), max_size=c),
                        label="protect")
    protect[prot_ix] = True
    n = data.draw(st.integers(1, 16), label="candidates")
    missing = 1000 + np.arange(n)
    scores = np.array(data.draw(st.lists(
        st.floats(0.0, 50.0), min_size=n, max_size=n)), np.float32)
    admit = _gate_admission(slot_row, freq, protect, missing, scores)
    for a in range(n):
        for b in range(n):
            if admit[b] and scores[a] > scores[b]:
                assert admit[a], (scores, admit)


if HAS_HYPOTHESIS:
    @settings(max_examples=200, deadline=None)
    @given(data=st.data())
    def test_admission_monotone_in_frequency(data):
        _check_monotone(data)
else:
    @requires_hypothesis
    def test_admission_monotone_in_frequency():
        """Placeholder so the property shows as SKIPPED, not absent."""


def test_admission_gate_prefers_hot_candidates():
    """With 2 free slots and 3 candidates, the two hottest admit; beyond
    the free slots a candidate admits only by strictly beating the coldest
    unprotected resident."""
    c = 4
    slot_row = np.array([7, 8, -1, -1], np.int64)   # 2 residents, 2 free
    freq = np.array([5.0, 1.0, 0.0, 0.0], np.float32)
    protect = np.zeros((c,), bool)
    missing = np.array([100, 101, 102])
    scores = np.array([0.5, 9.0, 3.0], np.float32)
    admit = _gate_admission(slot_row, freq, protect, missing, scores)
    # top-2 by score fill the free slots; 0.5 does not beat resident 1.0
    assert admit.tolist() == [False, True, True]
    # raise the cold candidate above the coldest resident: now it admits
    scores = np.array([1.5, 9.0, 3.0], np.float32)
    admit = _gate_admission(slot_row, freq, protect, missing, scores)
    assert admit.tolist() == [True, True, True]
    # protected residents are not evictable: only the freq-5.0 slot
    # remains a victim, and 1.5 does not beat it
    protect = np.array([False, True, False, False])
    scores = np.array([1.5, 9.0, 3.0], np.float32)
    admit = _gate_admission(slot_row, freq, protect, missing, scores)
    assert admit.tolist() == [False, True, True]


def test_cold_burst_cannot_evict_zipf_head(cfg, ebc):
    """The thrash scenario the EMA gate exists for: a one-off cold burst
    (every row EMA ~1) prefetched with gate=True admits nothing over the
    established head, while the ungated legacy path would churn the whole
    cache."""
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=32)
    st_ = cc.init_state(_rand_mega(cfg, ebc))
    head = np.arange(16)
    mid = np.arange(100, 116)
    for _ in range(6):                         # establish the hot head
        cc.prepare(st_, np.tile(head, 3).reshape(1, 1, 48), train=False)
    # fill the remaining slots; cache is now full, every resident freq >= 1
    cc.prepare(st_, np.concatenate([head, mid]).reshape(1, 1, 32),
               train=False)
    assert (st_.row_slot[head] >= 0).all()
    assert (st_.row_slot[mid] >= 0).all()
    cold = np.arange(500, 564)                 # one-off burst, 2x the cache
    admitted = cc.prefetch(st_, cold, gate=True)
    assert admitted == 0                       # seed 1.0 beats no resident
    assert (st_.row_slot[head] >= 0).all()
    assert (st_.row_slot[mid] >= 0).all()
    # the ungated path (pre-EMA behaviour) would have churned the head
    admitted = cc.prefetch(st_, cold, gate=False)
    assert admitted == 32
    assert (st_.row_slot[head] < 0).all()


def test_strict_planned_batches_never_gate(cfg, ebc):
    """Bit-exactness contract: every row of a PLANNED batch becomes
    resident regardless of its EMA score (the gate is best-effort only)."""
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=32)
    st_ = cc.init_state(_rand_mega(cfg, ebc))
    for _ in range(4):
        cc.prepare(st_, np.arange(16).reshape(1, 1, 16), train=False)
    cold = np.arange(500, 532)
    local = cc.prepare(st_, cold.reshape(1, 1, 32), train=False)
    assert (st_.row_slot[cold] >= 0).all()
    assert (local >= 0).all()


def test_ema_readmission_outlives_cold_burst(cfg, ebc):
    """A periodically-returning row re-admits at its HISTORICAL frequency
    under EMA seeding, but at ~its batch count under first-touch — the
    seed difference the admission bench measures."""
    out = {}
    for ema in (True, False):
        cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=32,
                                                ema_admission=ema)
        st_ = cc.init_state(_rand_mega(cfg, ebc))
        hot = np.arange(8)
        for _ in range(8):                     # hot rows, count 4 per step
            cc.prepare(st_, np.tile(hot, 4).reshape(1, 1, 32), train=False)
        # evict the hot rows via a full-cache batch of strangers
        cc.prepare(st_, np.arange(200, 232).reshape(1, 1, 32), train=False)
        assert (st_.row_slot[hot] < 0).all()
        # hot rows return ONCE each: EMA re-seeds them near their
        # historical rate, first-touch at their in-batch count (1)
        cc.prepare(st_, hot.reshape(1, 1, 8), train=False)
        out[ema] = np.asarray(st_.freq)[st_.row_slot[hot]].copy()
    assert (out[True] > 3.0).all()             # ~steady EMA of count-4 rows
    assert (out[False] == 1.0).all()           # in-batch count


# ---------------------------------------------------------------------------
# chunk-granular transfers: coalescing + bit-exactness
# ---------------------------------------------------------------------------


def test_coalesce_rows_min_fill_drops_sparse_blocks():
    rows = np.array([0, 1, 2, 3, 100, 200, 201, 202, 203], np.int64)
    starts, pos = coalesce_rows(rows, 4, 1000, min_fill=3)
    assert starts.tolist() == [0, 200]
    # dense runs keep their in-block positions; the isolated row drops
    assert pos.tolist() == [0, 1, 2, 3, -1, 4, 5, 6, 7]
    # min_fill=1 keeps every block (pure fixed-chunk coverage)
    starts, pos = coalesce_rows(rows, 4, 1000, min_fill=1)
    assert starts.tolist() == [0, 100, 200]
    assert (pos >= 0).all()


def test_coalesce_rows_clamps_trailing_block():
    rows = np.array([998, 999], np.int64)
    starts, pos = coalesce_rows(rows, 4, 1000, min_fill=2)
    assert starts.tolist() == [996]            # start+chunk <= total_rows
    assert pos.tolist() == [2, 3]


def test_chunk_min_fill_floor():
    assert _chunk_min_fill(2) == 2
    assert _chunk_min_fill(8) == 6             # ~3/4 full
    assert _chunk_min_fill(16) == 12


@pytest.mark.parametrize("interpret", [False, True])
def test_chunked_transfers_bit_exact_sync(cfg, ebc, interpret):
    """fetch_chunk>1 changes the transfer SHAPE, never lookup values:
    per-step outputs equal the per-row collection's bit-for-bit, on mixed
    dense-run + scattered traffic."""
    mega = _rand_mega(cfg, ebc)
    ccs = [CachedEmbeddingBagCollection.build(cfg, cache_rows=64,
                                              fetch_chunk=chunk,
                                              interpret=interpret)
           for chunk in (1, 8)]
    states = [cc.init_state(mega) for cc in ccs]
    rng = np.random.RandomState(3)
    total = ebc.plan.total_rows
    for step in range(4):
        idx = _rand_idx(rng, total)
        if step % 2 == 0:                      # dense contiguous run
            idx[0, 0, :] = np.arange(40, 44)
        outs = [cc.lookup(st_, idx, train=False)
                for cc, st_ in zip(ccs, states)]
        np.testing.assert_array_equal(np.asarray(outs[0]),
                                      np.asarray(outs[1]))
    assert states[1].stats.fetch_chunks > 0
    assert states[1].stats.fetch_chunks <= states[1].stats.fetches
    assert states[0].stats.fetch_chunks == 0


def test_chunked_transfers_bit_exact_async(cfg, ebc):
    """The async stream's chunked shadow fetch commits bit-identically."""
    mega = _rand_mega(cfg, ebc)
    ccs = [CachedEmbeddingBagCollection.build(cfg, cache_rows=64,
                                              fetch_chunk=chunk)
           for chunk in (1, 8)]
    states = [cc.init_async_state(mega) for cc in ccs]
    rng = np.random.RandomState(4)
    total = ebc.plan.total_rows
    batches = [_rand_idx(rng, total) for _ in range(4)]
    batches[0][0, 0, :] = np.arange(8, 12)
    for b in batches:
        outs = [cc.lookup_async(st_, b, train=False)
                for cc, st_ in zip(ccs, states)]
        np.testing.assert_array_equal(np.asarray(outs[0]),
                                      np.asarray(outs[1]))
    assert states[1].stats.fetch_chunks > 0


def test_chunked_overfetch_bounded(cfg, ebc):
    """The density-adaptive fallback keeps block padding below 1/3 of the
    fetched rows (the _chunk_min_fill contract) on scattered traffic."""
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=64,
                                            fetch_chunk=8)
    st_ = cc.init_state(_rand_mega(cfg, ebc))
    rng = np.random.RandomState(5)
    for _ in range(6):
        cc.lookup(st_, _rand_idx(rng, ebc.plan.total_rows), train=False)
    assert st_.stats.overfetch_rows <= st_.stats.fetches / 3 + 8


# ---------------------------------------------------------------------------
# ids-by-frequency reorder + pipeline remap
# ---------------------------------------------------------------------------


def test_frequency_reorder_head_contiguous():
    offs, sizes = [0, 10], [10, 6]
    freq = np.zeros((16,))
    freq[[3, 7, 9]] = [5, 9, 2]                # table 0 head
    freq[[12, 15]] = [4, 1]                    # table 1 head
    remap, inverse = frequency_reorder(offs, sizes, freq, 16)
    # hottest ids land at each table's row 0, in descending order
    assert remap[7] == 0 and remap[3] == 1 and remap[9] == 2
    assert remap[12] == 10 and remap[15] == 11
    # per-table bijection: each table's span maps onto itself
    assert sorted(remap[:10].tolist()) == list(range(10))
    assert sorted(remap[10:].tolist()) == list(range(10, 16))
    # inverse really inverts (the weight-permutation side)
    assert (inverse[remap] == np.arange(16)).all()
    # stable: untouched ids keep their relative order
    rest = [int(remap[i]) for i in [0, 1, 2, 4, 5, 6, 8]]
    assert rest == sorted(rest)


def test_frequency_reorder_validates_shape():
    with pytest.raises(ValueError):
        frequency_reorder([0], [4], np.zeros((3,)), 4)


def test_dedup_hook_row_remap(cfg, ebc):
    """The reader-thread remap: global rows permute BEFORE dedup/plan
    building, pads survive, and the remapped ids equal remap[original]."""
    offs = ebc.plan.table_offsets
    total = ebc.plan.total_rows
    rng = np.random.RandomState(6)
    freq = rng.rand(total)
    remap, _ = frequency_reorder(offs, cfg.hash_sizes, freq, total)
    raw = rng.randint(0, min(cfg.hash_sizes), size=(2, len(offs), 3))
    raw[0, 0, 0] = -1
    plain = dedup_indices_hook(offs)({"idx": raw.copy()})
    mapped = dedup_indices_hook(offs, row_remap=remap)({"idx": raw.copy()})
    valid = plain["idx"] >= 0
    assert (mapped["idx"][valid] == remap[plain["idx"][valid]]).all()
    assert (mapped["idx"][~valid] == -1).all()
    assert (mapped["uniq_rows"]
            == np.unique(remap[plain["idx"][valid]])).all()
    # the plan hook builds its SparsePlan over the REMAPPED row space
    planned = sparse_plan_hook(offs, row_remap=remap)({"idx": raw.copy()})
    prows = np.asarray(planned["plan_rows"])
    live = prows[prows >= 0]
    assert (live == np.unique(remap[plain["idx"][valid]])).all()
