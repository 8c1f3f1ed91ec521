"""Async cache-exchange stream (core/cache.py AsyncCacheState +
kernels/cache_ops.py fetch/commit pair + train/steps.py overlapped step).

The contract under test: the overlapped schedule — batch k+1's miss rows
fetched into a shadow slab while batch k computes, committed at the step
boundary — is BIT-IDENTICAL to the synchronous cache_exchange path: same
indices, same AdaGrad state, identical outputs (losses, dense params,
materialized capacity tier).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.cache import CachedEmbeddingBagCollection
from repro.core.dlrm import dlrm_param_specs
from repro.core.embedding import EmbeddingBagCollection
from repro.data.synthetic import make_dlrm_batch
from repro.kernels import cache_ops, ref
from repro.nn.params import init_params
from repro.optim.optimizers import adagrad
from repro.train.steps import (build_async_cached_dlrm_train_step,
                               build_cached_dlrm_train_step,
                               cached_dlrm_init_state)


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("dlrm-m1")


@pytest.fixture(scope="module")
def ebc(cfg):
    return EmbeddingBagCollection.build(cfg, n_shards=1,
                                        strategy="replicated")


def _batch_idx(cfg, ebc, step, batch=8):
    raw = make_dlrm_batch(cfg, batch, step=step)
    return np.asarray(ebc.offset_indices(jnp.asarray(raw["idx"])))


def _worklist(rng):
    """A hand worklist exercising every entry kind: writeback+fetch,
    fetch-only, writeback-only (fetch=-1 keeps the slot), full pad."""
    capacity = jnp.asarray(rng.randn(40, 48), jnp.float32)
    cache = jnp.asarray(rng.randn(8, 48), jnp.float32)
    cap_acc = jnp.asarray(rng.rand(40), jnp.float32)
    cache_acc = jnp.asarray(rng.rand(8), jnp.float32)
    freq = jnp.asarray(rng.rand(8), jnp.float32)
    slots = jnp.asarray([0, 2, 3, -1, 5, 7], jnp.int32)
    evict = jnp.asarray([10, -1, 12, -1, -1, 13], jnp.int32)
    fetch = jnp.asarray([20, 21, -1, -1, 22, 23], jnp.int32)
    counts = jnp.asarray([3, 1, 0, 0, 2, 5], jnp.float32)
    return capacity, cache, cap_acc, cache_acc, freq, slots, evict, fetch, \
        counts


def _cp(x):
    return jnp.array(x, copy=True)


# ---------------------------------------------------------------------------
# split kernels vs oracle / vs the fused exchange
# ---------------------------------------------------------------------------


def test_fetch_then_commit_equals_fused_exchange(rng):
    (capacity, cache, cap_acc, cache_acc, freq, slots, evict, fetch,
     counts) = _worklist(rng)
    want = ref.cache_exchange_ref(capacity, cache, cap_acc, cache_acc, freq,
                                  slots, evict, fetch, counts)
    shadow, shadow_acc = cache_ops.cache_fetch(capacity, cap_acc, fetch)
    got = cache_ops.cache_commit(_cp(capacity), _cp(cache), _cp(cap_acc),
                                 _cp(cache_acc), shadow, shadow_acc,
                                 slots, evict, fetch)
    for w, g in zip(want[:4], got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


def test_fetch_kernel_matches_ref_interpret(rng):
    capacity, _, cap_acc, _, _, _, _, fetch, _ = _worklist(rng)
    want_s, want_a = ref.cache_fetch_ref(capacity, cap_acc, fetch)
    got_s, got_a = cache_ops.cache_fetch(capacity, cap_acc, fetch,
                                         interpret=True)
    np.testing.assert_array_equal(np.asarray(want_s), np.asarray(got_s))
    np.testing.assert_array_equal(np.asarray(want_a), np.asarray(got_a))
    # -1 pad rows come back zeroed, not garbage
    np.testing.assert_array_equal(np.asarray(got_s)[2], 0.0)
    np.testing.assert_array_equal(np.asarray(got_s)[3], 0.0)


def test_commit_kernel_matches_ref_interpret(rng):
    (capacity, cache, cap_acc, cache_acc, _, slots, evict, fetch,
     _) = _worklist(rng)
    shadow, shadow_acc = ref.cache_fetch_ref(capacity, cap_acc, fetch)
    want = ref.cache_commit_ref(capacity, cache, cap_acc, cache_acc,
                                shadow, shadow_acc, slots, evict, fetch)
    got = cache_ops.cache_commit(_cp(capacity), _cp(cache), _cp(cap_acc),
                                 _cp(cache_acc), shadow, shadow_acc,
                                 slots, evict, fetch, interpret=True)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


def test_commit_writeback_only_entry_keeps_slot(rng):
    """fetch=-1 entries write the victim back WITHOUT clobbering the slot —
    the flush-shaped worklist."""
    capacity = jnp.zeros((10, 4), jnp.float32)
    cache = jnp.asarray(rng.randn(4, 4), jnp.float32)
    cap_acc = jnp.zeros((10,), jnp.float32)
    cache_acc = jnp.asarray(rng.rand(4), jnp.float32)
    shadow = jnp.zeros((1, 4), jnp.float32)
    shadow_acc = jnp.zeros((1,), jnp.float32)
    new_cap, new_cache, new_ca, new_cc = cache_ops.cache_commit(
        _cp(capacity), _cp(cache), _cp(cap_acc), _cp(cache_acc),
        shadow, shadow_acc, jnp.asarray([2], jnp.int32),
        jnp.asarray([7], jnp.int32), jnp.asarray([-1], jnp.int32))
    np.testing.assert_array_equal(np.asarray(new_cap)[7],
                                  np.asarray(cache)[2])
    np.testing.assert_array_equal(np.asarray(new_cache), np.asarray(cache))
    assert float(new_ca[7]) == float(cache_acc[2])


# ---------------------------------------------------------------------------
# async manager: lookup equivalence on the overlapped schedule
# ---------------------------------------------------------------------------


def test_async_lookup_equals_uncached_exact(cfg, ebc):
    params = init_params(ebc.param_specs(), jax.random.PRNGKey(0))
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=320)
    astate = cc.init_async_state(params["mega"])
    streams = [_batch_idx(cfg, ebc, s) for s in range(8)]
    local = cc.take_async(astate, streams[0], train=False)
    for k in range(8):
        want = ebc.lookup(params, jnp.asarray(streams[k]))
        got = cc.ebc.lookup({"mega": astate.cache}, jnp.asarray(local))
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
        if k + 1 < 8:
            # overlapped schedule: stage k+1 while k is "in flight"
            cc.stage_async(astate, streams[k + 1], train=False)
            local = cc.take_async(astate, streams[k + 1], train=False)
    assert astate.stats.evictions > 0          # the sweep really evicted
    assert astate.stats.writebacks == 0        # read-only: nothing dirty


def test_lookup_async_wrapper_matches_sync_manager(cfg, ebc):
    params = init_params(ebc.param_specs(), jax.random.PRNGKey(0))
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=320)
    astate = cc.init_async_state(params["mega"])
    state = cc.init_state(params["mega"])
    for step in range(4):
        idx = _batch_idx(cfg, ebc, step)
        got = cc.lookup_async(astate, idx, train=False)
        want = cc.lookup(state, idx, train=False)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_take_async_with_mismatched_staged_plan_recovers(cfg, ebc):
    """A staged plan for a batch that never arrives degrades to a prefetch:
    take plans the actual batch on the spot and the lookup stays exact."""
    params = init_params(ebc.param_specs(), jax.random.PRNGKey(0))
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=320)
    astate = cc.init_async_state(params["mega"])
    cc.stage_async(astate, _batch_idx(cfg, ebc, 5), train=False)
    actual = _batch_idx(cfg, ebc, 6)
    local = cc.take_async(astate, actual, train=False)
    want = ebc.lookup(params, jnp.asarray(actual))
    got = cc.ebc.lookup({"mega": astate.cache}, jnp.asarray(local))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    assert astate.staged is None
    assert not astate.pending                  # take committed everything
    # the discarded plan is re-booked as a prefetch: only the real batch
    # counts toward steps/hits/misses (no phantom-step stat skew)
    assert astate.stats.steps == 1
    n_actual = len(np.unique(actual[actual >= 0]))
    assert astate.stats.misses <= n_actual     # some rows prefetched by
    assert astate.stats.prefetched > 0         # the mismatched plan
    accesses = int((actual >= 0).sum())
    assert astate.stats.hits + astate.stats.misses == accesses


# ---------------------------------------------------------------------------
# overlapped train step: bit-exact vs the synchronous path
# ---------------------------------------------------------------------------


def _run_cached_training(cfg, ebc, params, mode, n_steps=6):
    opt = adagrad(0.01)
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=320)
    dense = {"bottom": params["bottom"], "top": params["top"]}
    state = cached_dlrm_init_state(cc, opt, params)
    batches = []
    for t in range(n_steps):
        raw = make_dlrm_batch(cfg, 8, step=t)
        batches.append({
            "dense": jnp.asarray(raw["dense"]),
            "idx": np.asarray(ebc.offset_indices(jnp.asarray(raw["idx"]))),
            "label": jnp.asarray(raw["label"])})
    losses = []
    if mode == "sync":
        cs = cc.init_state(params["emb"]["mega"])
        step = build_cached_dlrm_train_step(cfg, cc, opt)
        for t in range(n_steps):
            dense, state, m = step(dense, state, cs, batches[t],
                                   jnp.asarray(t, jnp.int32))
            losses.append(float(m["loss"]))
        mega, accum = cc.materialize(cs)
        stats = cs.stats
    else:
        astate = cc.init_async_state(params["emb"]["mega"])
        step = build_async_cached_dlrm_train_step(
            cfg, cc, opt, strict_sync=(mode == "strict"))
        for t in range(n_steps):
            nxt = batches[t + 1] if t + 1 < n_steps else None
            dense, state, m = step(dense, state, astate, batches[t],
                                   jnp.asarray(t, jnp.int32), next_batch=nxt)
            losses.append(float(m["loss"]))
        mega, accum = cc.materialize_async(astate)
        stats = astate.stats
    return (losses, np.asarray(mega), np.asarray(accum),
            jax.tree.map(np.asarray, dense), stats)


def test_async_train_step_bit_exact_vs_sync(cfg, ebc):
    """The acceptance contract: overlapped and synchronous cached training
    produce bit-identical losses, dense params, capacity tier, and AdaGrad
    accumulators over a multi-step stream with evictions."""
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(0))
    l_s, m_s, a_s, d_s, st_s = _run_cached_training(cfg, ebc, params, "sync")
    l_a, m_a, a_a, d_a, st_a = _run_cached_training(cfg, ebc, params,
                                                    "async")
    assert st_s.evictions > 0                  # the stream really evicted
    np.testing.assert_array_equal(l_s, l_a)
    np.testing.assert_array_equal(m_s, m_a)
    np.testing.assert_array_equal(a_s, a_a)
    for k in ("bottom", "top"):
        for w, g in zip(jax.tree.leaves(d_s[k]), jax.tree.leaves(d_a[k])):
            np.testing.assert_array_equal(w, g)
    assert st_a.steps == st_s.steps


def test_strict_sync_fallback_flag_is_bit_exact_too(cfg, ebc):
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(1))
    l_s, m_s, a_s, _, _ = _run_cached_training(cfg, ebc, params, "sync")
    l_f, m_f, a_f, _, st_f = _run_cached_training(cfg, ebc, params, "strict")
    np.testing.assert_array_equal(l_s, l_f)
    np.testing.assert_array_equal(m_s, m_f)
    np.testing.assert_array_equal(a_s, a_f)
    assert st_f.prefetched == 0                # fallback never stages ahead


# ---------------------------------------------------------------------------
# planning invariants: thrash guard, protection, epochs, prefetch
# ---------------------------------------------------------------------------


def test_async_thrash_guard_raises(cfg, ebc):
    params = init_params(ebc.param_specs(), jax.random.PRNGKey(0))
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=8)
    astate = cc.init_async_state(params["mega"])
    with pytest.raises(ValueError, match="cache_rows"):
        cc.take_async(astate, _batch_idx(cfg, ebc, 0))


def test_async_double_buffer_thrash_guard_mentions_lookahead(cfg, ebc):
    """Cache big enough for one working set but not two: the STAGED plan
    must refuse rather than evict in-flight rows."""
    params = init_params(ebc.param_specs(), jax.random.PRNGKey(0))
    idx0, idx1 = _batch_idx(cfg, ebc, 0), _batch_idx(cfg, ebc, 1)
    ws = max(len(np.unique(idx0[idx0 >= 0])),
             len(np.unique(idx1[idx1 >= 0])))
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=ws + 8)
    astate = cc.init_async_state(params["mega"])
    cc.take_async(astate, idx0, train=True)    # in-flight working set
    with pytest.raises(ValueError, match="in-flight"):
        cc.stage_async(astate, idx1, train=True)


def test_stage_rows_is_best_effort_and_drops_overflow(cfg, ebc):
    params = init_params(ebc.param_specs(), jax.random.PRNGKey(0))
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=64)
    astate = cc.init_async_state(params["mega"])
    rows = np.arange(200, dtype=np.int64)      # 3x the cache
    admitted = cc.stage_rows(astate, rows)
    assert admitted == 64                      # fills the cache, drops rest
    assert astate.stats.prefetched == 64
    cc.commit_async(astate)
    assert astate.resident == 64
    # staged rows are protected until committed: a second best-effort call
    # right behind them admits nothing rather than evicting them
    astate2 = cc.init_async_state(params["mega"])
    cc.stage_rows(astate2, rows[:64])
    assert cc.stage_rows(astate2, rows[100:164]) == 0


def test_refetch_of_queued_dirty_victim_sees_fresh_value(cfg, ebc):
    """Two pipeline invariants of the lookahead (stage_rows) path:

    1. a row whose DIRTY eviction is still queued must not be re-fetched
       from the stale capacity tier — the planner drains the commit queue
       first so the writeback lands before the fetch reads;
    2. the drain clears the staged plan's queue entry, but the staged
       batch's slots must STAY protected (via astate.staged) — evicting
       one would silently invalidate its outstanding remap."""
    import dataclasses as dc
    tiny = dc.replace(cfg, n_sparse_features=1, hash_sizes=(64,),
                      mean_lookups=(4,), bottom_mlp=(8, 16), top_mlp=(8, 1))
    cc = CachedEmbeddingBagCollection.build(tiny, cache_rows=32)
    mega = jnp.zeros((cc.ebc.plan.total_rows, tiny.embed_dim), jnp.float32)
    astate = cc.init_async_state(mega)

    def batch_of(rows, rep=1):
        return np.repeat(np.asarray(rows, np.int32), rep).reshape(1, 1, -1)

    # train rows 0-7: their cached values become 1000.0, capacity stale 0.0
    local = cc.take_async(astate, batch_of(range(8)), train=True)
    cc.mark_updated(astate, astate.cache.at[np.unique(local)].set(1000.0),
                    astate.cache_accum)
    # rows 8-15 hot (count 4 per row) so the LFU never picks them before
    # rows 0-7; rows 16-23 become the in-flight working set
    cc.take_async(astate, batch_of(range(8, 16), rep=4), train=True)
    cc.take_async(astate, batch_of(range(16, 24)), train=True)
    # the staged plan needs 8 victims: the coldest unprotected slots are
    # dirty rows 0-7 — their writeback is now queued
    cc.stage_async(astate, batch_of(range(24, 40)), train=True)
    assert astate.pending, "plan should be queued"
    assert (astate.pending[-1].evict_rows >= 0).sum() == 8
    staged_slots_before = astate.row_slot[np.arange(24, 40)].copy()
    # lookahead prefetch of row 0 while its dirty writeback is still
    # queued: must drain (stale-fetch guard), then admit row 0 WITHOUT
    # touching the staged batch's slots (even though the drain just
    # removed their pending-queue protection)
    assert cc.stage_rows(astate, np.asarray([0])) == 1
    np.testing.assert_array_equal(astate.row_slot[np.arange(24, 40)],
                                  staged_slots_before)
    cc.take_async(astate, batch_of(range(24, 40)), train=True)
    # row 0's slot must hold the updated value, not the stale capacity row
    slot = astate.row_slot[0]
    assert slot >= 0
    np.testing.assert_array_equal(np.asarray(astate.cache[slot]), 1000.0)
    # and the capacity tier received the queued writeback (row 1 stays out)
    np.testing.assert_array_equal(np.asarray(astate.capacity[1]), 1000.0)


def test_epoch_tags_are_monotone_and_match_admissions(cfg, ebc):
    params = init_params(ebc.param_specs(), jax.random.PRNGKey(0))
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=320)
    astate = cc.init_async_state(params["mega"])
    seen = []
    local = cc.take_async(astate, _batch_idx(cfg, ebc, 0), train=True)
    assert local is not None
    for k in range(1, 5):
        cc.stage_async(astate, _batch_idx(cfg, ebc, k), train=True)
        p = astate.pending[-1]
        assert p.epoch == astate.epoch
        # admitted slots carry this plan's epoch tag
        assert np.all(astate.slot_epoch[p.slots] == p.epoch)
        seen.append(p.epoch)
        cc.take_async(astate, _batch_idx(cfg, ebc, k), train=True)
    assert seen == sorted(seen)                # strictly advancing epochs


def test_staged_victims_never_in_flight(cfg, ebc):
    """The pipeline invariant behind bit-exactness: a slot admitted by the
    staged (epoch k+1) plan is never one the in-flight (epoch k) batch
    still reads or writes."""
    params = init_params(ebc.param_specs(), jax.random.PRNGKey(0))
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=240)
    astate = cc.init_async_state(params["mega"])
    cc.take_async(astate, _batch_idx(cfg, ebc, 0), train=True)
    evicting = 0
    for k in range(1, 8):
        inflight = astate.inflight_mask.copy()
        cc.stage_async(astate, _batch_idx(cfg, ebc, k), train=True)
        p = astate.pending[-1]
        evicting += len(p.victim_slots)
        assert not inflight[p.victim_slots].any()
        assert not inflight[p.slots].any()
        cc.take_async(astate, _batch_idx(cfg, ebc, k), train=True)
    assert evicting > 0                        # the invariant was exercised
