"""Cached embedding tier (core/cache.py + kernels/cache_ops.py).

Covers the acceptance contract: cached lookup is EXACTLY equal to the
uncached mega-table lookup (fp32), hit/miss accounting is deterministic,
eviction-writeback round-trips training updates, and the cached_host
placement sizes the device cache from the HBM budget.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.cache import CachedEmbeddingBagCollection
from repro.core.dlrm import dlrm_param_specs
from repro.core.embedding import EmbeddingBagCollection
from repro.core.placement import (CACHED_ROW_META_BYTES, ROW_TILE,
                                 plan_placement)
from repro.data.pipeline import DataPipeline, dedup_indices_hook
from repro.data.synthetic import bounded_zipf_rows, make_dlrm_batch
from repro.kernels import cache_ops, ops, ref
from repro.nn.params import init_params
from repro.optim.optimizers import adagrad
from repro.train.steps import (build_cached_dlrm_train_step,
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


# ---------------------------------------------------------------------------
# placement: cached_host capacity math
# ---------------------------------------------------------------------------


def test_plan_cached_host_capacity_math():
    d, itemsize = 64, 4
    budget = 1_000_000.0
    plan = plan_placement([5000, 7000, 100], [8, 2, 30], d, 4, budget,
                          itemsize=itemsize, strategy="cached_host")
    assert plan.strategy == "cached_host"
    assert plan.cache_rows % ROW_TILE == 0
    assert plan.total_rows % ROW_TILE == 0
    assert plan.cache_rows <= plan.total_rows
    row_bytes = d * itemsize + CACHED_ROW_META_BYTES
    assert plan.cache_rows * row_bytes <= budget
    # one more row tile would overflow the budget
    assert (plan.cache_rows + ROW_TILE) * row_bytes > budget
    # capacity tier is replicated (host-resident) — no model-axis sharding
    assert plan.pspec == jax.sharding.PartitionSpec(None, None)


def test_plan_cached_host_budget_covers_table():
    plan = plan_placement([100, 200], [1, 1], 16, 1, 1e12,
                          strategy="cached_host")
    assert plan.cache_rows == plan.total_rows     # degenerate: full cache


def test_host_offload_alias_maps_to_cached_host():
    plan = plan_placement([100, 200], [1, 1], 16, 1, 1e6,
                          strategy="host_offload")
    assert plan.strategy == "cached_host"
    assert plan.cache_rows > 0


# ---------------------------------------------------------------------------
# lookup equivalence + hit/miss accounting
# ---------------------------------------------------------------------------


def test_cached_lookup_equals_uncached_exact(cfg, ebc):
    params = init_params(ebc.param_specs(), jax.random.PRNGKey(0))
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=160)
    state = cc.init_state(params["mega"])
    for step in range(6):   # cache (160) < working set churn -> evictions
        idx = _batch_idx(cfg, ebc, step)
        want = ebc.lookup(params, jnp.asarray(idx))
        got = cc.lookup(state, idx, train=False)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    assert state.stats.evictions > 0              # the sweep really evicted
    assert state.stats.writebacks == 0            # read-only: nothing dirty


def test_cold_then_hot_counters(cfg, ebc):
    params = init_params(ebc.param_specs(), jax.random.PRNGKey(0))
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=256)
    state = cc.init_state(params["mega"])
    idx = _batch_idx(cfg, ebc, 0)
    uniq = len(np.unique(idx[idx >= 0]))
    accesses = int((idx >= 0).sum())
    cc.prepare(state, idx, train=False)
    # cold: one miss (= one fetch) per unique row; duplicate accesses of a
    # fetched row are served from the just-filled slot
    assert state.stats.misses == uniq
    assert state.stats.fetches == uniq
    assert state.stats.hits == accesses - uniq
    cc.prepare(state, idx, train=False)
    # hot: the identical batch hits every access
    assert state.stats.misses == uniq
    assert state.stats.hits == 2 * accesses - uniq
    assert state.stats.hit_rate > 0.5


def test_lfu_evicts_the_cold_slot():
    cfg = dataclasses.replace(
        get_smoke_config("dlrm-m1"),
        n_sparse_features=1, hash_sizes=(64,), mean_lookups=(2,),
        bottom_mlp=(8, 16), top_mlp=(8, 1))
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1,
                                      strategy="replicated")
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=2)
    mega = jnp.arange(ebc.plan.total_rows * cfg.embed_dim,
                      dtype=jnp.float32).reshape(-1, cfg.embed_dim)
    state = cc.init_state(mega)

    def prep(rows):
        idx = np.asarray(rows, np.int32).reshape(1, 1, -1)
        cc.prepare(state, idx, train=False)

    prep([5, 9])            # fill both slots
    prep([5])               # row 5 is now hotter than row 9
    prep([7])               # needs a slot: must evict the cold row 9
    assert state.row_slot[5] >= 0
    assert state.row_slot[7] >= 0
    assert state.row_slot[9] < 0


# ---------------------------------------------------------------------------
# training: eviction-writeback round trip
# ---------------------------------------------------------------------------


def test_eviction_writeback_roundtrip_matches_uncached_training(cfg, ebc):
    """Sparse updates applied to cached rows, flushed through evictions +
    final flush, equal the same updates applied directly to the full table
    (and so the post-flush uncached lookup matches too)."""
    lr, steps = 0.05, 5
    params = init_params(ebc.param_specs(), jax.random.PRNGKey(1))
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=160)
    state = cc.init_state(params["mega"])

    mega_ref = params["mega"]
    accum_ref = jnp.zeros((ebc.plan.total_rows,), jnp.float32)
    rng = np.random.RandomState(0)
    for step in range(steps):
        idx = _batch_idx(cfg, ebc, step)
        g_pooled = jnp.asarray(
            rng.randn(*idx.shape[:2], cfg.embed_dim), jnp.float32)
        # cached: remap -> update cache rows (marked dirty by prepare)
        local = cc.prepare(state, idx, train=True)
        fi, fg = ebc.per_lookup_grads(jnp.asarray(local), g_pooled)
        new_cache, new_accum = ops.rowwise_adagrad_update(
            state.cache, state.cache_accum, fi, fg, lr)
        cc.mark_updated(state, new_cache, new_accum)
        # uncached reference: same math on the full table with global rows
        fi_r, fg_r = ebc.per_lookup_grads(jnp.asarray(idx), g_pooled)
        mega_ref, accum_ref = ops.rowwise_adagrad_update(
            mega_ref, accum_ref, fi_r, fg_r, lr)
    assert state.stats.writebacks > 0             # evictions flushed rows
    mega_c, accum_c = cc.materialize(state)
    np.testing.assert_allclose(np.asarray(mega_c), np.asarray(mega_ref),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(accum_c), np.asarray(accum_ref),
                               rtol=0, atol=1e-6)
    # idle flush: nothing dirty remains
    assert cc.flush(state) == 0


def test_cached_train_step_runs_and_reports_cache_metrics(cfg, ebc):
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(0))
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=256)
    opt = adagrad(0.01)
    dense = {"bottom": params["bottom"], "top": params["top"]}
    cstate = cached_dlrm_init_state(cc, opt, params)
    cache_state = cc.init_state(params["emb"]["mega"])
    step = build_cached_dlrm_train_step(cfg, cc, opt)
    losses = []
    for t in range(4):
        raw = make_dlrm_batch(cfg, 8, step=t)
        b = {"dense": jnp.asarray(raw["dense"]),
             "idx": np.asarray(ebc.offset_indices(jnp.asarray(raw["idx"]))),
             "label": jnp.asarray(raw["label"])}
        dense, cstate, m = step(dense, cstate, cache_state, b,
                                jnp.asarray(t, jnp.int32))
        losses.append(float(m["loss"]))
        assert 0.0 <= m["cache_hit_rate"] <= 1.0
    assert losses[-1] < losses[0]                 # planted signal learns
    assert cache_state.stats.steps == 4


def test_checkpoint_restore_resumes_bit_exact(cfg, ebc, tmp_path):
    """Interrupt a cached-tier run mid-stream, round-trip the WHOLE tier
    (device slabs + host slot maps + EMA + stats) through the real
    CheckpointManager, and resume: every later loss and the final
    materialized table must be BIT-EQUAL to the uninterrupted run. A
    params-only checkpoint cannot pass this — the accumulators of cached
    rows live per-slot, so losing row_slot/cache_accum changes the AdaGrad
    trajectory after restore."""
    from repro.train.checkpoint import CheckpointManager

    def fresh():
        params = init_params(dlrm_param_specs(cfg, ebc),
                             jax.random.PRNGKey(7))
        cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=256)
        opt = adagrad(0.01)
        dense = {"bottom": params["bottom"], "top": params["top"]}
        return (cc, opt, dense, cached_dlrm_init_state(cc, opt, params),
                cc.init_state(params["emb"]["mega"]))

    def batch(t):
        raw = make_dlrm_batch(cfg, 8, step=t)
        return {"dense": jnp.asarray(raw["dense"]),
                "idx": np.asarray(ebc.offset_indices(jnp.asarray(raw["idx"]))),
                "label": jnp.asarray(raw["label"])}

    total, cut = 6, 3

    # uninterrupted reference
    cc, opt, dense, cstate, cache_state = fresh()
    step = build_cached_dlrm_train_step(cfg, cc, opt)
    ref_losses = []
    for t in range(total):
        dense, cstate, m = step(dense, cstate, cache_state, batch(t),
                                jnp.asarray(t, jnp.int32))
        ref_losses.append(float(m["loss"]))
    ref_mega, ref_accum = cc.materialize(cache_state)
    ref_dense = dense

    # interrupted run: save at `cut`, restore into FRESH objects, resume
    cc, opt, dense, cstate, cache_state = fresh()
    step = build_cached_dlrm_train_step(cfg, cc, opt)
    for t in range(cut):
        dense, cstate, m = step(dense, cstate, cache_state, batch(t),
                                jnp.asarray(t, jnp.int32))
        assert float(m["loss"]) == ref_losses[t]
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(cut, {"dense": dense, "opt": cstate,
                   "cache": cc.state_dict(cache_state)})

    cc2, opt2, dense2, cstate2, cache2 = fresh()   # restart from scratch
    tree = mgr.restore({"dense": dense2, "opt": cstate2,
                        "cache": cc2.state_dict(cache2)}, step=cut)
    dense2, cstate2 = tree["dense"], tree["opt"]
    cache2 = cc2.load_state_dict(tree["cache"])
    assert dataclasses.asdict(cache2.stats) == \
        dataclasses.asdict(cache_state.stats)
    step2 = build_cached_dlrm_train_step(cfg, cc2, opt2)
    for t in range(cut, total):
        dense2, cstate2, m = step2(dense2, cstate2, cache2, batch(t),
                                   jnp.asarray(t, jnp.int32))
        assert float(m["loss"]) == ref_losses[t]
    mega2, accum2 = cc2.materialize(cache2)
    np.testing.assert_array_equal(np.asarray(mega2), np.asarray(ref_mega))
    np.testing.assert_array_equal(np.asarray(accum2), np.asarray(ref_accum))
    for a, b in zip(jax.tree.leaves(ref_dense), jax.tree.leaves(dense2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_state_dict_drains_staged_and_roundtrips(cfg, ebc):
    """Snapshotting an AsyncCacheState with a staged-but-unconsumed plan
    must drain the pending queue and unwind the staged stats (the plan
    degrades to a prefetch, as take_async does on an idx mismatch); the
    restored state then continues bit-identically to the mutated
    original."""
    params = init_params(ebc.param_specs(), jax.random.PRNGKey(2))
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=256)
    astate = cc.init_async_state(params["mega"])
    idx0, idx1 = _batch_idx(cfg, ebc, 0), _batch_idx(cfg, ebc, 1)
    cc.take_async(astate, idx0, train=False)
    cc.stage_async(astate, idx1, train=False)
    assert astate.staged is not None and astate.pending

    d = cc.state_dict(astate)
    assert astate.staged is None and not astate.pending
    assert astate.stats.prefetched > 0            # staged -> prefetch
    restored = cc.load_state_dict(d)
    assert dataclasses.asdict(restored.stats) == \
        dataclasses.asdict(astate.stats)
    assert restored.epoch == astate.epoch

    # both continue with batch1: the staged rows are resident, so the
    # re-plan is all hits, and lookups/materialize stay bit-equal
    out_a = cc.lookup_async(astate, idx1, train=False)
    out_b = cc.lookup_async(restored, idx1, train=False)
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))
    mega_a, acc_a = cc.materialize_async(astate)
    mega_b, acc_b = cc.materialize_async(restored)
    np.testing.assert_array_equal(np.asarray(mega_a), np.asarray(mega_b))
    np.testing.assert_array_equal(np.asarray(acc_a), np.asarray(acc_b))


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------


def test_cache_exchange_kernel_matches_ref_interpret(rng):
    r, c, d, n = 40, 8, 48, 6                     # d pads 48 -> 128
    capacity = jnp.asarray(rng.randn(r, d), jnp.float32)
    cache = jnp.asarray(rng.randn(c, d), jnp.float32)
    cap_acc = jnp.asarray(rng.rand(r), jnp.float32)
    cache_acc = jnp.asarray(rng.rand(c), jnp.float32)
    freq = jnp.asarray(rng.rand(c), jnp.float32)
    slots = jnp.asarray([0, 2, 3, -1, 5, 7], jnp.int32)
    evict = jnp.asarray([10, -1, 12, -1, -1, 13], jnp.int32)
    fetch = jnp.asarray([20, 21, -1, -1, 22, 23], jnp.int32)
    counts = jnp.asarray([3, 1, 0, 0, 2, 5], jnp.float32)
    want = ref.cache_exchange_ref(capacity, cache, cap_acc, cache_acc, freq,
                                  slots, evict, fetch, counts)
    got = cache_ops.cache_exchange(capacity, cache, cap_acc, cache_acc, freq,
                                   slots, evict, fetch, counts,
                                   interpret=True)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


def test_lfu_touch_decays_and_bumps():
    freq = jnp.asarray([4.0, 2.0, 0.0], jnp.float32)
    out = cache_ops.lfu_touch(freq, jnp.asarray([1, -1], jnp.int32),
                              jnp.asarray([3.0, 9.0], jnp.float32),
                              decay=0.5)
    np.testing.assert_allclose(np.asarray(out), [2.0, 4.0, 0.0])


def test_cached_manager_kernel_interpret_equals_jnp_path(cfg, ebc):
    params = init_params(ebc.param_specs(), jax.random.PRNGKey(0))
    cc_k = CachedEmbeddingBagCollection.build(cfg, cache_rows=160,
                                              interpret=True)
    cc_j = CachedEmbeddingBagCollection.build(cfg, cache_rows=160)
    st_k = cc_k.init_state(params["mega"])
    st_j = cc_j.init_state(params["mega"])
    for step in range(3):
        idx = _batch_idx(cfg, ebc, step, batch=4)
        out_k = cc_k.lookup(st_k, idx, train=False)
        out_j = cc_j.lookup(st_j, idx, train=False)
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_j))
    assert st_k.stats.hits == st_j.stats.hits
    assert st_k.stats.misses == st_j.stats.misses


# ---------------------------------------------------------------------------
# pipeline prefetch hook + serving
# ---------------------------------------------------------------------------


def test_dedup_hook_and_prefetch_make_next_batch_all_hits(cfg, ebc):
    hook = dedup_indices_hook(ebc.plan.table_offsets)

    def gen(step):
        return make_dlrm_batch(cfg, 8, step=step)

    pipe = DataPipeline(gen, prefetch=2, transform=hook)
    _, b0 = next(pipe)
    _, b1 = next(pipe)
    pipe.close()
    # the hook rewrites "idx" to offset global rows + attaches the dedup set
    raw0 = make_dlrm_batch(cfg, 8, step=0)["idx"]
    glob0 = np.asarray(ebc.offset_indices(jnp.asarray(raw0)))
    np.testing.assert_array_equal(b0["idx"], glob0)
    np.testing.assert_array_equal(b0["uniq_rows"],
                                  np.unique(glob0[glob0 >= 0]))

    params = init_params(ebc.param_specs(), jax.random.PRNGKey(0))
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=512)
    state = cc.init_state(params["mega"])
    admitted = cc.prefetch(state, b1["uniq_rows"])
    assert admitted == len(b1["uniq_rows"])
    misses_before = state.stats.misses
    cc.prepare(state, b1["idx"], train=False)
    assert state.stats.misses == misses_before    # fully prefetched -> hits
    assert state.stats.prefetched == admitted


def test_pipeline_worker_error_surfaces_in_consumer():
    def gen(step):
        if step >= 2:
            raise KeyError("boom")
        return {"x": np.asarray([step])}

    pipe = DataPipeline(gen, prefetch=1)
    assert next(pipe)[1]["x"][0] == 0
    assert next(pipe)[1]["x"][0] == 1
    with pytest.raises(RuntimeError, match="step 2"):
        next(pipe)
        next(pipe)
    pipe.close()


def test_serve_engine_readonly_matches_uncached_forward(cfg, ebc):
    from repro.core.dlrm import dlrm_forward
    from repro.serve.engine import DLRMEngine
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(2))
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=160)
    engine = DLRMEngine(params, cfg, cc)
    cap_before = np.asarray(engine.state.capacity).copy()
    for step in range(3):
        raw = make_dlrm_batch(cfg, 8, step=step)
        b = {"dense": jnp.asarray(raw["dense"]),
             "idx": np.asarray(ebc.offset_indices(jnp.asarray(raw["idx"])))}
        probs = engine.predict(b)
        want = jax.nn.sigmoid(dlrm_forward(
            params, {"dense": b["dense"], "idx": jnp.asarray(b["idx"])},
            cfg, ebc))
        np.testing.assert_allclose(probs, np.asarray(want), rtol=1e-6,
                                   atol=1e-6)
    # read-only: eviction never writes back and capacity is untouched
    assert engine.cache_stats.writebacks == 0
    np.testing.assert_array_equal(cap_before,
                                  np.asarray(engine.state.capacity))
    assert engine.requests_served == 24


def test_thrash_guard_raises(cfg, ebc):
    params = init_params(ebc.param_specs(), jax.random.PRNGKey(0))
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=8)
    state = cc.init_state(params["mega"])
    with pytest.raises(ValueError, match="cache_rows"):
        cc.prepare(state, _batch_idx(cfg, ebc, 0))


def test_serve_engine_microbatches_when_cache_smaller_than_batch(cfg, ebc):
    """Read-only serving with a device cache SMALLER than one batch's
    working set: predict must micro-batch through the thrash guard instead
    of raising, every batch misses (capacity-bound regime), and the
    probabilities still match the dense uncached forward exactly."""
    from repro.core.dlrm import dlrm_forward
    from repro.serve.engine import DLRMEngine
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(2))
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=48)
    engine = DLRMEngine(params, cfg, cc)
    # same compiled forward over a cache big enough to never split: the
    # bit-equality oracle for the splitting path
    big = DLRMEngine(params, cfg,
                     CachedEmbeddingBagCollection.build(cfg, cache_rows=2048))
    # the full batch working set must NOT fit (else the test is vacuous)
    n_batches = 3
    for step in range(n_batches):
        idx = _batch_idx(cfg, ebc, step)
        assert len(np.unique(idx[idx >= 0])) > 48
    cap_before = np.asarray(engine.state.capacity).copy()
    for step in range(n_batches):
        raw = make_dlrm_batch(cfg, 8, step=step)
        b = {"dense": jnp.asarray(raw["dense"]),
             "idx": np.asarray(ebc.offset_indices(jnp.asarray(raw["idx"])))}
        misses_before = engine.cache_stats.misses
        probs = engine.predict(b)
        assert engine.cache_stats.misses > misses_before   # misses every batch
        np.testing.assert_array_equal(probs, big.predict(b))
        want = jax.nn.sigmoid(dlrm_forward(
            params, {"dense": b["dense"], "idx": jnp.asarray(b["idx"])},
            cfg, ebc))
        np.testing.assert_allclose(probs, np.asarray(want, np.float32),
                                   rtol=1e-6, atol=1e-6)
    # every batch split at least once: more planner steps than predicts
    assert engine.cache_stats.steps > n_batches
    assert engine.requests_served == 8 * n_batches
    # still read-only: nothing written back, capacity untouched
    assert engine.cache_stats.writebacks == 0
    np.testing.assert_array_equal(cap_before,
                                  np.asarray(engine.state.capacity))


def test_bounded_zipf_head_is_hot():
    rng = np.random.RandomState(0)
    draws = bounded_zipf_rows(rng, 10_000, 20_000, 1.05)
    assert draws.min() >= 0 and draws.max() < 10_000
    # top-10% ranks should carry well over half the mass at alpha ~ 1
    frac = (draws < 1000).mean()
    assert frac > 0.5


def test_serve_engine_split_covers_even_and_odd_batches(cfg, ebc):
    """The greedy prefix splitter must cover both parities (the old
    recursive-halving path only ever saw even halves): even and odd batch
    sizes through an undersized cache stay bit-equal to the no-split
    oracle."""
    from repro.serve.engine import DLRMEngine
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(2))
    engine = DLRMEngine(params, cfg,
                        CachedEmbeddingBagCollection.build(cfg,
                                                           cache_rows=48))
    big = DLRMEngine(params, cfg,
                     CachedEmbeddingBagCollection.build(cfg,
                                                        cache_rows=2048))
    for n in (8, 7):                           # even AND odd
        raw = make_dlrm_batch(cfg, n, step=n)
        b = {"dense": jnp.asarray(raw["dense"]),
             "idx": np.asarray(ebc.offset_indices(jnp.asarray(raw["idx"])))}
        idx = b["idx"]
        assert len(np.unique(idx[idx >= 0])) > 48   # must actually split
        np.testing.assert_array_equal(engine.predict(b), big.predict(b))
    assert engine.requests_served == 15


def test_serve_engine_single_example_over_capacity_is_actionable(cfg, ebc):
    """One example whose OWN unique rows exceed the cache can never be
    split: the error must say so and name both sizes, not recurse or
    surface the raw thrash-guard message."""
    from repro.serve.engine import DLRMEngine
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(2))
    engine = DLRMEngine(params, cfg,
                        CachedEmbeddingBagCollection.build(cfg,
                                                           cache_rows=8))
    raw = make_dlrm_batch(cfg, 2, step=0)
    idx = np.asarray(ebc.offset_indices(jnp.asarray(raw["idx"])))
    assert len(np.unique(idx[0][idx[0] >= 0])) > 8
    with pytest.raises(ValueError, match=r"cannot be split") as ei:
        engine.predict({"dense": jnp.asarray(raw["dense"]), "idx": idx})
    assert "cache_rows=8" in str(ei.value)
