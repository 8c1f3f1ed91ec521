"""N-tier heterogeneous memory (core/tiers.py) + the EmbeddingTier protocol.

Covers the PR-level acceptance contract: every cached collection conforms
to the `EmbeddingTier` protocol, the 3-tier path is bit-exact against the
dense single-host oracle AND against the 2-tier path when the bulk tier is
sized to zero, residency is exclusive under any promotion/demotion
interleaving (hypothesis property), the mmap-backed bulk store round-trips,
and the old step builders keep working behind DeprecationWarning aliases.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import HAS_HYPOTHESIS, requires_hypothesis
from repro.configs import get_smoke_config
from repro.core.cache import (CachedEmbeddingBagCollection, CacheStats,
                              MultiHostCachedEmbeddingBagCollection)
from repro.core.dlrm import dlrm_param_specs
from repro.core.embedding import EmbeddingBagCollection
from repro.core.tiers import (AsyncCachedTier, BulkCachedEmbeddingBagCollection,
                              EmbeddingTier, TierCacheStats, tier_conformance)
from repro.data.synthetic import make_dlrm_batch
from repro.kernels import ops
from repro.nn.params import init_params
from repro.optim.optimizers import adagrad
from repro.train.steps import (build_async_cached_dlrm_train_step,
                               build_cached_dlrm_train_step,
                               build_cached_train_step,
                               build_multihost_cached_train_step,
                               cached_dlrm_init_state)


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


def _batch(cfg, ebc, t, b=8):
    raw = make_dlrm_batch(cfg, b, step=t)
    return {"dense": jnp.asarray(raw["dense"]),
            "idx": np.asarray(ebc.offset_indices(jnp.asarray(raw["idx"]))),
            "label": jnp.asarray(raw["label"])}


def _batch_idx(cfg, ebc, t, b=8):
    return _batch(cfg, ebc, t, b)["idx"]


def _bulk(cfg, **kw):
    kw.setdefault("cache_rows", 256)
    kw.setdefault("dram_rows", 300)
    kw.setdefault("bulk_chunk", 16)
    kw.setdefault("bulk_latency_us", 0.0)
    return BulkCachedEmbeddingBagCollection.build(cfg, **kw)


# ---------------------------------------------------------------------------
# protocol conformance
# ---------------------------------------------------------------------------


def test_every_cached_tier_conforms_to_embedding_tier(cfg):
    """All four tiers present the full EmbeddingTier surface — the factory
    and every cached call site outside core/ consume them through it."""
    sync = CachedEmbeddingBagCollection.build(cfg, cache_rows=256)
    tiers = [sync,
             AsyncCachedTier(sync),
             MultiHostCachedEmbeddingBagCollection.build(cfg, n_hosts=2,
                                                         cache_rows=256),
             _bulk(cfg)]
    for t in tiers:
        assert tier_conformance(t), type(t).__name__
        assert isinstance(t, EmbeddingTier)


def test_factory_rejects_non_tier_with_protocol_hint(cfg, ebc):
    with pytest.raises(TypeError, match="EmbeddingTier"):
        build_cached_train_step(cfg, object(), adagrad(0.01))


def test_deprecated_builders_warn_and_delegate(cfg):
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=256)
    opt = adagrad(0.01)
    with pytest.warns(DeprecationWarning, match="build_cached_train_step"):
        build_cached_dlrm_train_step(cfg, cc, opt)
    with pytest.warns(DeprecationWarning, match="build_cached_train_step"):
        build_async_cached_dlrm_train_step(cfg, cc, opt)
    mc = MultiHostCachedEmbeddingBagCollection.build(cfg, n_hosts=2,
                                                     cache_rows=256)
    with pytest.warns(DeprecationWarning, match="build_cached_train_step"):
        build_multihost_cached_train_step(cfg, mc, opt)


def test_tier_stats_snapshot_and_reset():
    s = TierCacheStats(hits=5, misses=3, dram_hits=2, bulk_hits=1,
                       promotion_bytes=640, bulk_sched_us=100,
                       bulk_wait_us=25)
    snap = s.snapshot()
    assert snap["cache_hits"] == 5
    assert snap["tier_hit_dram"] == 2
    assert snap["tier_hit_bulk"] == 1
    assert snap["tier_promotion_bytes"] == 640
    assert s.dram_hit_rate == pytest.approx(2 / 3)
    assert s.hidden_fraction == pytest.approx(0.75)
    s.reset()
    assert s.hits == s.dram_hits == s.bulk_hits == s.promotion_bytes == 0
    # the generic reset covers the base class too
    b = CacheStats(hits=7, fetch_chunks=2)
    b.reset()
    assert b.hits == b.fetch_chunks == 0


# ---------------------------------------------------------------------------
# bit-exactness: dense oracle / 2-tier equivalence
# ---------------------------------------------------------------------------


def test_three_tier_roundtrip_matches_dense_oracle(cfg, ebc):
    """Training updates streamed through HBM-cache evictions, DRAM
    overflow demotions, and bulk promotions materialize to the SAME table
    as the dense single-host update — the 3-tier plumbing moves bits, it
    never transforms them."""
    lr, steps = 0.05, 5
    params = init_params(ebc.param_specs(), jax.random.PRNGKey(1))
    bc = _bulk(cfg, cache_rows=160)
    state = bc.init_state(params["mega"])

    mega_ref = params["mega"]
    accum_ref = jnp.zeros((ebc.plan.total_rows,), jnp.float32)
    rng = np.random.RandomState(0)
    for step in range(steps):
        idx = _batch_idx(cfg, ebc, step)
        g_pooled = jnp.asarray(
            rng.randn(*idx.shape[:2], cfg.embed_dim), jnp.float32)
        local = bc.take(state, idx, train=True)
        fi, fg = ebc.per_lookup_grads(jnp.asarray(local), g_pooled)
        new_cache, new_accum = ops.rowwise_adagrad_update(
            state.cache, state.cache_accum, fi, fg, lr)
        bc.mark_updated(state, new_cache, new_accum)
        fi_r, fg_r = ebc.per_lookup_grads(jnp.asarray(idx), g_pooled)
        mega_ref, accum_ref = ops.rowwise_adagrad_update(
            mega_ref, accum_ref, fi_r, fg_r, lr)
    assert state.stats.bulk_hits > 0              # promotions happened
    mega_c, accum_c = bc.materialize(state)
    np.testing.assert_array_equal(np.asarray(mega_c), np.asarray(mega_ref))
    np.testing.assert_array_equal(np.asarray(accum_c), np.asarray(accum_ref))


@pytest.mark.parametrize("mode", ["sync", "async", "strict"])
def test_three_tier_train_matches_two_tier(cfg, ebc, mode):
    """The factory-built 3-tier train step (budgeted DRAM, live bulk
    traffic) is bit-equal to the 2-tier step: same losses, same
    materialized table. With dram_rows=0 the bulk tier disables itself and
    the run must ALSO book zero bulk traffic."""
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(0))
    opt = adagrad(0.01)
    n = 4

    def run(col):
        is_async = mode != "sync"
        tier = AsyncCachedTier(col) if is_async else col
        dense = {"bottom": params["bottom"], "top": params["top"]}
        cstate = cached_dlrm_init_state(col, opt, params)
        tstate = tier.init_state(params["emb"]["mega"])
        step = build_cached_train_step(cfg, tier, opt,
                                       strict_sync=(mode == "strict"))
        losses = []
        for t in range(n):
            nxt = (_batch(cfg, ebc, t + 1)
                   if is_async and t + 1 < n else None)
            kw = {"next_batch": nxt} if is_async else {}
            dense, cstate, m = step(dense, cstate, tstate,
                                    _batch(cfg, ebc, t),
                                    jnp.asarray(t, jnp.int32), **kw)
            losses.append(float(m["loss"]))
        mega, accum = tier.materialize(tstate)
        return losses, np.asarray(mega), np.asarray(accum), tstate

    ref_l, ref_m, ref_a, _ = run(
        CachedEmbeddingBagCollection.build(cfg, cache_rows=256))
    got_l, got_m, got_a, tstate = run(_bulk(cfg))
    assert got_l == ref_l
    assert tstate.stats.bulk_hits > 0
    np.testing.assert_array_equal(got_m, ref_m)
    np.testing.assert_array_equal(got_a, ref_a)

    # bulk sized to zero: identical numbers AND zero bulk traffic
    off_l, off_m, off_a, off_state = run(_bulk(cfg, dram_rows=0))
    assert off_l == ref_l
    np.testing.assert_array_equal(off_m, ref_m)
    s = off_state.stats
    assert s.bulk_hits == s.demotions == s.promotion_bytes == 0
    assert s.bulk_read_chunks == s.bulk_write_chunks == 0


def test_mmap_backed_bulk_store_roundtrips(cfg, ebc, tmp_path):
    """`bulk_path` puts the bulk payload on disk (np.memmap) with no
    change in numbers vs the in-memory store."""
    params = init_params(ebc.param_specs(), jax.random.PRNGKey(2))
    mem = _bulk(cfg)
    dsk = _bulk(cfg, bulk_path=str(tmp_path / "bulk.npy"))
    s_mem = mem.init_state(params["mega"])
    s_dsk = dsk.init_state(params["mega"])
    assert isinstance(s_dsk.bulk.values, np.memmap)
    for t in range(3):
        idx = _batch_idx(cfg, ebc, t)
        a = mem.lookup(s_mem, idx, train=False)
        b = dsk.lookup(s_dsk, idx, train=False)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert s_dsk.stats.bulk_hits == s_mem.stats.bulk_hits > 0


# ---------------------------------------------------------------------------
# property: residency is exclusive under any interleaving
# ---------------------------------------------------------------------------


def _assert_residency_invariants(bc, state):
    masks = bc.tier_residency(state)
    hbm, dram, bulk = masks["hbm"], masks["dram"], masks["bulk"]
    total = len(hbm)
    # exclusive partition: every row in exactly one tier
    assert int((hbm & dram).sum()) == 0
    assert int((hbm & bulk).sum()) == 0
    assert int((dram & bulk).sum()) == 0
    assert int(hbm.sum() + dram.sum() + bulk.sum()) == total
    assert state.dram_occupancy <= bc._dram_cap()
    # bulk-resident rows carry their capacity bits verbatim
    rows = np.flatnonzero(bulk)
    if len(rows):
        cap = np.asarray(jnp.take(state.capacity, jnp.asarray(rows), axis=0))
        np.testing.assert_array_equal(np.asarray(state.bulk.values[rows]),
                                      cap)


def _residency_trip(cfg, ebc, seed, dram_rows):
    params = init_params(ebc.param_specs(), jax.random.PRNGKey(0))
    bc = _bulk(cfg, dram_rows=dram_rows)
    state = bc.init_state(params["mega"])
    for t in range(4):
        idx = _batch_idx(cfg, ebc, seed * 31 + t)
        bc.lookup(state, idx, train=True)
        _assert_residency_invariants(bc, state)
    mega, _ = bc.materialize(state)
    assert mega.shape == params["mega"].shape
    _assert_residency_invariants(bc, state)


def test_residency_exclusive_after_promotion_demotion(cfg, ebc):
    _residency_trip(cfg, ebc, seed=1, dram_rows=300)


if HAS_HYPOTHESIS:

    @requires_hypothesis
    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 10 ** 6),
           dram_rows=st.sampled_from([0, 200, 400, 1000]))
    def test_residency_property_under_any_interleaving(seed, dram_rows):
        """No row is ever resident in two tiers, DRAM occupancy never
        exceeds its budget, and bulk bits always mirror capacity —
        whatever promotion/demotion interleaving the traffic induces."""
        cfg = get_smoke_config("dlrm-m1")
        ebc = EmbeddingBagCollection.build(cfg, n_shards=1,
                                           strategy="replicated")
        _residency_trip(cfg, ebc, seed, dram_rows)
