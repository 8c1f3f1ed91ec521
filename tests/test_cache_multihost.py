"""Multi-host cache coherence (docs/cache.md "Multi-host coherence").

Covers the full stack of the sharded-capacity tier: plan sub-splitting
(by host bag range and by owner row range), the per-owner segmented fused
backward, the per-host cache manager (clean eviction, invalidation,
prefetch, thrash guard), and the train step's bit-exactness contracts —
vs the single-host cached path on 1 host, and vs the dense single-host
oracle with a hot row cached on several hosts (gradients routed and
reduced once at the owner). The 8-fake-device mesh test exercises the
shard_map owner update against a genuinely row-sharded capacity tier.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.cache import (CachedEmbeddingBagCollection,
                              MultiHostCachedEmbeddingBagCollection)
from repro.core.dlrm import dlrm_param_specs
from repro.core.embedding import EmbeddingBagCollection
from repro.core.placement import plan_placement
from repro.data.pipeline import sparse_plan_hook
from repro.data.synthetic import make_dlrm_batch
from repro.kernels import ops as kernel_ops
from repro.kernels.sparse_plan import (SparsePlan, build_sparse_plan_host,
                                       host_plan_from_batch,
                                       host_plans_from_batch,
                                       split_plan_by_host,
                                       split_plan_by_owner)
from repro.launch.analysis import multihost_exchange_traffic
from repro.nn.params import init_params
from repro.optim.optimizers import adagrad
from repro.train.steps import (build_cached_dlrm_train_step,
                               build_dlrm_train_step,
                               build_multihost_cached_train_step,
                               cached_dlrm_init_state, dlrm_init_state)


# ---------------------------------------------------------------------------
# corpus shared by the splitting tests
# ---------------------------------------------------------------------------


def _corpus():
    rng = np.random.RandomState(0)
    out = {
        "random": rng.randint(-1, 40, size=(16, 3, 5)).astype(np.int32),
        "all_dup": np.full((8, 2, 4), 7, np.int32),
        "all_pads": np.full((8, 2, 4), -1, np.int32),
        "zipfish": np.where(rng.rand(16, 2, 6) < 0.7,
                            rng.zipf(1.5, (16, 2, 6)) % 30,
                            -1).astype(np.int32),
    }
    hot = rng.randint(-1, 64, size=(16, 2, 4)).astype(np.int32)
    hot[:, 0, 0] = 3                       # one row on every host
    out["hot_everywhere"] = hot
    return out


def _live(plan):
    rows = np.asarray(plan.unique_rows)
    n = int((rows >= 0).sum())
    offs = np.asarray(plan.bag_offsets).astype(np.int64)
    return rows[:n], offs[: n + 1], np.asarray(plan.bag_ids)


def _pairs(plan):
    """Multiset of (row, bag) pairs a plan encodes (live prefix only)."""
    rows, offs, bags = _live(plan)
    out = []
    for i, r in enumerate(rows):
        for p in range(offs[i], offs[i + 1]):
            out.append((int(r), int(bags[p])))
    return sorted(out)

# ---------------------------------------------------------------------------
# split_plan_by_host
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", list(_corpus()))
@pytest.mark.parametrize("n_hosts", [1, 2, 4])
def test_split_by_host_equals_per_subbatch_plan(name, n_hosts):
    """Each sub-plan is EXACTLY build_sparse_plan_host on that host's
    contiguous sub-batch (rows, offsets, and the live bag prefix)."""
    idx = _corpus()[name]
    b, f, _ = idx.shape
    if b % n_hosts:
        pytest.skip("batch not divisible")
    subs = split_plan_by_host(build_sparse_plan_host(idx), n_hosts,
                              b // n_hosts * f)
    for h in range(n_hosts):
        want = build_sparse_plan_host(idx[h * (b // n_hosts):
                                          (h + 1) * (b // n_hosts)])
        rows_w, offs_w, bags_w = _live(want)
        n_valid = int(offs_w[-1]) if len(offs_w) else 0
        assert np.array_equal(np.asarray(subs[h].unique_rows),
                              np.asarray(want.unique_rows))
        assert np.array_equal(np.asarray(subs[h].bag_offsets),
                              np.asarray(want.bag_offsets))
        assert np.array_equal(np.asarray(subs[h].bag_ids)[:n_valid],
                              bags_w[:n_valid])


@pytest.mark.parametrize("name", list(_corpus()))
def test_split_by_host_partitions_global_plan(name):
    """The multiset of (row, GLOBAL bag) pairs across sub-plans
    reconstructs the global plan's exactly; each live prefix is strictly
    ascending (the planner invariant every consumer relies on)."""
    idx = _corpus()[name]
    b, f, _ = idx.shape
    n_hosts = 4
    plan = build_sparse_plan_host(idx)
    subs = split_plan_by_host(plan, n_hosts, b // n_hosts * f)
    got = []
    for h, sub in enumerate(subs):
        rows, _, _ = _live(sub)
        assert np.all(np.diff(rows) > 0)     # strictly ascending per host
        got += [(r, bag + h * (b // n_hosts) * f)
                for r, bag in _pairs(sub)]
    assert sorted(got) == _pairs(plan)


def test_split_by_host_partition_property():
    pytest.importorskip("hypothesis",
                        reason="hypothesis not installed (pip install "
                               ".[dev])")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=30, deadline=None)
    @given(b=st.sampled_from([4, 8, 16]), f=st.integers(1, 3),
           lk=st.integers(1, 5), rows=st.integers(1, 50),
           n_hosts=st.sampled_from([1, 2, 4]),
           seed=st.integers(0, 2**31 - 1))
    def check(b, f, lk, rows, n_hosts, seed):
        rng = np.random.RandomState(seed)
        idx = rng.randint(-1, rows, size=(b, f, lk)).astype(np.int32)
        plan = build_sparse_plan_host(idx)
        subs = split_plan_by_host(plan, n_hosts, b // n_hosts * f)
        got = []
        for h, sub in enumerate(subs):
            live, _, _ = _live(sub)
            assert np.all(np.diff(live) > 0)
            got += [(r, bag + h * (b // n_hosts) * f)
                    for r, bag in _pairs(sub)]
        assert sorted(got) == _pairs(plan)

    check()

# ---------------------------------------------------------------------------
# split_plan_by_owner + segmented fused backward
# ---------------------------------------------------------------------------


def test_split_by_owner_is_contiguous_slicing():
    rng = np.random.RandomState(1)
    idx = rng.randint(-1, 48, size=(8, 2, 6)).astype(np.int32)
    plan = build_sparse_plan_host(idx)
    shard_rows, n_shards = 12, 4
    seg_rows, seg_offs, seg_base = split_plan_by_owner(
        plan, shard_rows, n_shards)
    rows_g, offs_g, _ = _live(plan)
    rebuilt = []
    for s in range(n_shards):
        live = seg_rows[s][seg_rows[s] >= 0]
        assert np.all((live >= 0) & (live < shard_rows))   # owner-local
        rebuilt += list(live + seg_base[s])
        # pad offsets equal the segment's bag end (empty runs)
        k = len(live)
        assert np.all(seg_offs[s][k:] == seg_offs[s][k])
    assert np.array_equal(np.asarray(rebuilt), rows_g)
    with pytest.raises(ValueError, match="segment overflow"):
        split_plan_by_owner(plan, shard_rows, n_shards, seg_cap=1)


@pytest.mark.parametrize("name", ["random", "all_dup", "all_pads"])
def test_segmented_backward_bitmatches_global(name):
    """The per-owner segmented update == the unsegmented fused backward,
    bit for bit (jnp oracle path)."""
    rng = np.random.RandomState(2)
    idx = _corpus()[name] % 40                     # rows within the table
    idx = np.where(_corpus()[name] >= 0, idx, -1)
    b, f, _ = idx.shape
    h, d = 48, 16
    table = jnp.asarray(rng.randn(h, d), jnp.float32)
    accum = jnp.asarray(rng.rand(h), jnp.float32)
    gp = jnp.asarray(rng.randn(b, f, d), jnp.float32)
    plan = build_sparse_plan_host(idx)
    want = kernel_ops.fused_sparse_backward(
        table, accum, jnp.asarray(idx), gp, 0.05,
        plan=SparsePlan(jnp.asarray(plan.unique_rows),
                        jnp.asarray(plan.bag_offsets),
                        jnp.asarray(plan.bag_ids)))
    seg_rows, seg_offs, seg_base = split_plan_by_owner(
        plan, 12, 4, seg_cap=len(np.asarray(plan.unique_rows)))
    got = kernel_ops.fused_sparse_backward_segments(
        table, accum, jnp.asarray(seg_rows), jnp.asarray(seg_offs),
        jnp.asarray(plan.bag_ids), gp, 0.05,
        seg_base=jnp.asarray(seg_base))
    assert np.array_equal(np.asarray(want[0]), np.asarray(got[0]))
    assert np.array_equal(np.asarray(want[1]), np.asarray(got[1]))


def test_segmented_kernel_interpret_matches_oracle():
    """The generalized (S, C)-grid Pallas kernel body (interpret mode)
    against the jnp segment oracle, lane-width D."""
    rng = np.random.RandomState(3)
    b, f, lk, h, d = 6, 2, 4, 32, 128
    idx = rng.randint(-1, h, size=(b, f, lk)).astype(np.int32)
    table = jnp.asarray(rng.randn(h, d), jnp.float32)
    accum = jnp.asarray(rng.rand(h), jnp.float32)
    gp = jnp.asarray(rng.randn(b, f, d), jnp.float32)
    plan = build_sparse_plan_host(idx)
    seg_rows, seg_offs, seg_base = split_plan_by_owner(
        plan, 8, 4, seg_cap=len(np.asarray(plan.unique_rows)))
    args = (table, accum, jnp.asarray(seg_rows), jnp.asarray(seg_offs),
            jnp.asarray(plan.bag_ids), gp, 0.05)
    want = kernel_ops.fused_sparse_backward_segments(
        *args, seg_base=jnp.asarray(seg_base))
    got = kernel_ops.fused_sparse_backward_segments(
        *args, seg_base=jnp.asarray(seg_base), interpret=True)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               rtol=1e-6, atol=1e-6)

# ---------------------------------------------------------------------------
# placement: sharded capacity tier
# ---------------------------------------------------------------------------


def test_cached_host_sharded_capacity_plan():
    plan = plan_placement([1000, 500], [2.0, 1.0], 16, 4, 64_000,
                          strategy="cached_host", capacity_shards=4)
    assert plan.capacity_shards == 4
    assert plan.total_rows % (4 * 8) == 0
    assert plan.shard_rows * 4 == plan.total_rows
    assert plan.pspec == jax.sharding.PartitionSpec("data", None)
    # single-host plans are untouched by the new knob
    plan1 = plan_placement([1000, 500], [2.0, 1.0], 16, 4, 64_000,
                           strategy="cached_host")
    assert plan1.capacity_shards == 1
    assert plan1.pspec == jax.sharding.PartitionSpec(None, None)

# ---------------------------------------------------------------------------
# manager semantics
# ---------------------------------------------------------------------------


def _mc_setup(n_hosts=2, cache_rows=256):
    cfg = get_smoke_config("dlrm-m1")
    mc = MultiHostCachedEmbeddingBagCollection.build(
        cfg, n_hosts=n_hosts, cache_rows=cache_rows)
    total = mc.ebc.plan.total_rows
    rng = np.random.RandomState(0)
    mega = jnp.asarray(rng.randn(total, cfg.embed_dim), jnp.float32)
    return cfg, mc, mc.init_state(mega), mega


def test_multihost_lookup_matches_uncached():
    cfg, mc, state, mega = _mc_setup()
    rng = np.random.RandomState(1)
    total = mc.ebc.plan.total_rows
    for step in range(3):
        idx = rng.randint(-1, min(total, 200), size=(8, cfg.n_sparse_features,
                                                     4)).astype(np.int32)
        want = mc.ebc.lookup({"mega": mega}, jnp.asarray(idx))
        got = mc.lookup(state, idx)
        assert np.array_equal(np.asarray(want), np.asarray(got))
    assert state.stats.hits > 0 and state.stats.misses > 0
    assert state.stats.writebacks == 0          # clean caches never flush


def test_multihost_clean_eviction_and_stats():
    cfg, mc, state, _ = _mc_setup(n_hosts=2, cache_rows=32)
    rng = np.random.RandomState(2)
    for step in range(6):                        # force churn through 32 slots
        # sliding 24-row window: each batch's working set fits the cache
        # but the cumulative footprint forces evictions
        idx = (rng.randint(step * 20, step * 20 + 24,
                           size=(4, cfg.n_sparse_features, 4))
               .astype(np.int32))
        mc.lookup(state, idx)
    assert state.stats.evictions > 0
    assert state.stats.writebacks == 0
    # maps stay a bijection per host
    for h in range(2):
        resident = np.flatnonzero(state.slot_row[h] >= 0)
        rows = state.slot_row[h, resident]
        assert np.array_equal(state.row_slot[h, rows], resident)


def test_multihost_thrash_guard():
    cfg, mc, state, _ = _mc_setup(n_hosts=2, cache_rows=8)
    idx = np.arange(2 * cfg.n_sparse_features * 16).reshape(
        2, cfg.n_sparse_features, 16).astype(np.int32)
    with pytest.raises(ValueError, match="cache thrash|unique rows"):
        mc.plan_step(state, np.concatenate([idx, idx], axis=0))


def test_multihost_prefetch_admits_and_hits():
    cfg, mc, state, _ = _mc_setup(n_hosts=2, cache_rows=256)
    rng = np.random.RandomState(3)
    idx = rng.randint(0, 50, size=(8, cfg.n_sparse_features,
                                   4)).astype(np.int32)
    n = mc.prefetch(state, idx)
    assert n > 0 and state.stats.prefetched == n
    h0, m0 = state.stats.hits, state.stats.misses
    mc.plan_step(state, idx, train=False)
    assert state.stats.misses == m0              # everything was prefetched
    assert state.stats.hits > h0

# ---------------------------------------------------------------------------
# train-step bit-exactness
# ---------------------------------------------------------------------------


def _batches(cfg, ebc, n, b, plant_hot=True, hook=None):
    out = []
    for t in range(n):
        raw = make_dlrm_batch(cfg, b, step=t)
        if hook is not None:
            batch = hook({"dense": raw["dense"], "idx": np.asarray(raw["idx"]),
                          "label": raw["label"]})
            batch["dense"] = jnp.asarray(batch["dense"])
            batch["label"] = jnp.asarray(batch["label"])
        else:
            idx = np.array(ebc.offset_indices(jnp.asarray(raw["idx"])))
            batch = {"dense": jnp.asarray(raw["dense"]), "idx": idx,
                     "label": jnp.asarray(raw["label"])}
        if plant_hot:
            idx = np.array(batch["idx"])
            hot = int(idx[idx >= 0][0])
            idx[:, 0, 0] = hot                   # cached on EVERY host
            batch["idx"] = idx
            assert hook is None, "plant before hooking"
        out.append(batch)
    return out


def _run_oracle(cfg, ebc, params, batches):
    opt = adagrad(0.01)
    p = dict(params)
    state = dlrm_init_state(ebc, opt, p)
    step = jax.jit(build_dlrm_train_step(cfg, ebc, opt,
                                         sparse_apply="sparse"))
    losses = []
    for t, b in enumerate(batches):
        bb = dict(b)
        bb["idx"] = jnp.asarray(bb["idx"])
        p, state, m = step(p, state, bb, jnp.asarray(t, jnp.int32))
        losses.append(float(m["loss"]))
    return losses, np.asarray(p["emb"]["mega"]), np.asarray(state["accum"])


def _run_multihost(cfg, mc, params, batches, strict_sync, use_hook_plans):
    opt = adagrad(0.01)
    dense = {"bottom": params["bottom"], "top": params["top"]}
    state = cached_dlrm_init_state(mc, opt, params)
    mstate = mc.init_state(params["emb"]["mega"])
    step = build_multihost_cached_train_step(cfg, mc, opt,
                                             strict_sync=strict_sync)
    losses = []
    for t, b in enumerate(batches):
        nxt = batches[t + 1] if t + 1 < len(batches) else None
        dense, state, m = step(dense, state, mstate, b,
                               jnp.asarray(t, jnp.int32), next_batch=nxt)
        losses.append(float(m["loss"]))
    mega, accum = mc.materialize(mstate)
    return losses, np.asarray(mega), np.asarray(accum), mstate


def test_multihost_step_bitexact_vs_dense_oracle():
    """4 hosts, 4 steps, one hot row planted in every host's slice: losses,
    table, and accumulator must equal the dense single-host oracle's BIT
    FOR BIT — the routed duplicate-row gradients reduce once at the owner
    and every stale copy is refreshed/invalidated in time."""
    cfg = get_smoke_config("dlrm-m1")
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1,
                                       strategy="replicated")
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(0))
    batches = _batches(cfg, ebc, 4, 16)
    want_l, want_m, want_a = _run_oracle(cfg, ebc, params, batches)
    mc = MultiHostCachedEmbeddingBagCollection.build(cfg, n_hosts=4,
                                                     cache_rows=512)
    r = ebc.plan.total_rows
    for strict in (True, False):
        got_l, got_m, got_a, mstate = _run_multihost(
            cfg, mc, params, batches, strict, False)
        assert got_l == want_l
        assert np.array_equal(got_m[:r], want_m)
        assert np.array_equal(got_a[:r], want_a)
        assert mstate.route.dup_rows > 0         # the hot row, every step
        assert mstate.route.fetch_remote > 0
        assert mstate.route.grad_pairs_remote > 0
    # overlap mode actually prefetched
    assert mstate.stats.prefetched > 0


def test_multihost_step_with_hook_plans_bitexact():
    """The reader-thread artifacts (global plan + per-host sub-plans from
    sparse_plan_hook(n_hosts=H)) drive the same bits as on-the-fly
    planning."""
    cfg = get_smoke_config("dlrm-m1")
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1,
                                       strategy="replicated")
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(0))
    hook = sparse_plan_hook(ebc.plan.table_offsets, n_hosts=4)
    hooked = _batches(cfg, ebc, 3, 16, plant_hot=False, hook=hook)
    plain = [{"dense": b["dense"], "idx": np.asarray(b["idx"]),
              "label": b["label"]} for b in hooked]
    mc = MultiHostCachedEmbeddingBagCollection.build(cfg, n_hosts=4,
                                                     cache_rows=512)
    want = _run_multihost(cfg, mc, params, plain, True, False)
    got = _run_multihost(cfg, mc, params, hooked, True, True)
    assert want[0] == got[0]
    assert np.array_equal(want[1], got[1])
    assert np.array_equal(want[2], got[2])
    # the hook really attached the per-host artifacts the step consumed
    assert host_plans_from_batch(hooked[0]) is not None
    assert host_plan_from_batch(hooked[0]) is not None


def test_multihost_1host_bitexact_vs_single_host_cached():
    """On one host the tier degenerates to the single-host cached path:
    same losses, same materialized capacity + accumulator, zero cross-host
    traffic."""
    cfg = get_smoke_config("dlrm-m1")
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1,
                                       strategy="replicated")
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(0))
    batches = _batches(cfg, ebc, 4, 16, plant_hot=False)
    opt = adagrad(0.01)
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=512)
    dense = {"bottom": params["bottom"], "top": params["top"]}
    s1 = cached_dlrm_init_state(cc, opt, params)
    cstate = cc.init_state(params["emb"]["mega"])
    step1 = build_cached_dlrm_train_step(cfg, cc, opt)
    want_l = []
    for t, b in enumerate(batches):
        dense, s1, m = step1(dense, s1, cstate, b, jnp.asarray(t, jnp.int32))
        want_l.append(float(m["loss"]))
    want_m, want_a = cc.materialize(cstate)
    r = ebc.plan.total_rows
    mc = MultiHostCachedEmbeddingBagCollection.build(cfg, n_hosts=1,
                                                     cache_rows=512)
    got_l, got_m, got_a, mstate = _run_multihost(cfg, mc, params, batches,
                                                 True, False)
    assert got_l == want_l
    assert np.array_equal(got_m[:r], np.asarray(want_m))
    assert np.array_equal(got_a[:r], np.asarray(want_a))
    assert mstate.route.fetch_remote == 0
    assert mstate.route.refresh_remote == 0


def test_multihost_invalidation_keeps_copies_coherent():
    """A row cached on host 1 but updated by host 0 alone must be
    invalidated (counted) and re-fetched fresh on host 1's next touch."""
    cfg = get_smoke_config("dlrm-m1")
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1,
                                       strategy="replicated")
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(0))
    f, lk = cfg.n_sparse_features, 4
    row = 5

    def batch(idx):
        rng = np.random.RandomState(0)
        return {"dense": jnp.asarray(rng.randn(4, cfg.n_dense_features),
                                     jnp.float32),
                "idx": idx,
                "label": jnp.asarray(rng.rand(4) > 0.5, jnp.float32)}

    both = np.full((4, f, lk), -1, np.int32)
    both[:, 0, 0] = row                          # both hosts touch the row
    only0 = np.full((4, f, lk), -1, np.int32)
    only0[:2, 0, 0] = row                        # host 0 only
    only0[2:, 0, 1] = 8                          # host 1 touches another row
    mc = MultiHostCachedEmbeddingBagCollection.build(cfg, n_hosts=2,
                                                     cache_rows=64)
    opt = adagrad(0.01)
    dense = {"bottom": params["bottom"], "top": params["top"]}
    state = cached_dlrm_init_state(mc, opt, params)
    mstate = mc.init_state(params["emb"]["mega"])
    step = build_multihost_cached_train_step(cfg, mc, opt, strict_sync=True)
    dense, state, _ = step(dense, state, mstate, batch(both),
                           jnp.asarray(0, jnp.int32))
    assert mstate.row_slot[1, row] >= 0          # host 1 caches the row
    inv0 = mstate.route.invalidations
    dense, state, _ = step(dense, state, mstate, batch(only0),
                           jnp.asarray(1, jnp.int32))
    assert mstate.route.invalidations == inv0 + 1
    assert mstate.row_slot[1, row] < 0           # host 1's copy dropped
    m0 = mstate.stats.misses
    dense, state, _ = step(dense, state, mstate, batch(both),
                           jnp.asarray(2, jnp.int32))
    assert mstate.stats.misses > m0              # re-fetched fresh
    # end-to-end value check: capacity must match the dense oracle
    opt2 = adagrad(0.01)
    p = dict(params)
    st2 = dlrm_init_state(ebc, opt2, p)
    step_o = jax.jit(build_dlrm_train_step(cfg, ebc, opt2,
                                           sparse_apply="sparse"))
    for t, idx in enumerate([both, only0, both]):
        b = batch(idx)
        b["idx"] = jnp.asarray(b["idx"])
        p, st2, _ = step_o(p, st2, b, jnp.asarray(t, jnp.int32))
    r = ebc.plan.total_rows
    assert np.array_equal(np.asarray(mc.materialize(mstate)[0])[:r],
                          np.asarray(p["emb"]["mega"]))

# ---------------------------------------------------------------------------
# 8 fake devices: shard_map owner update against real capacity shards
# ---------------------------------------------------------------------------

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_multihost_step_on_8_device_mesh_bitexact_vs_oracle():
    """The acceptance test: 8 data-parallel hosts over a capacity tier
    genuinely row-sharded on an 8-fake-device mesh (shard_map per-owner
    update), ≥3 steps with the same hot row cached on every host — the
    materialized capacity must equal the dense single-host oracle's bits.
    """
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=8'\n" + """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding
from repro.configs import get_smoke_config
from repro.core.cache import MultiHostCachedEmbeddingBagCollection
from repro.core.dlrm import dlrm_param_specs
from repro.core.embedding import EmbeddingBagCollection
from repro.data.synthetic import make_dlrm_batch
from repro.launch.mesh import make_host_mesh
from repro.nn.params import init_params
from repro.optim.optimizers import adagrad
from repro.train.steps import (build_dlrm_train_step, dlrm_init_state,
                               build_multihost_cached_train_step,
                               cached_dlrm_init_state)

cfg = get_smoke_config("dlrm-m1")
H, N, B = 8, 4, 16
ebc = EmbeddingBagCollection.build(cfg, n_shards=1, strategy="replicated")
params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(0))
opt = adagrad(0.01)
batches = []
for t in range(N):
    raw = make_dlrm_batch(cfg, B, step=t)
    idx = np.array(ebc.offset_indices(jnp.asarray(raw["idx"])))
    hot = int(idx[idx >= 0][0])
    idx[:, 0, 0] = hot                 # cached on all 8 hosts
    batches.append({"dense": jnp.asarray(raw["dense"]), "idx": idx,
                    "label": jnp.asarray(raw["label"])})

p = dict(params)
state = dlrm_init_state(ebc, opt, p)
step_o = jax.jit(build_dlrm_train_step(cfg, ebc, opt, sparse_apply="sparse"))
losses_o = []
for t in range(N):
    b = dict(batches[t]); b["idx"] = jnp.asarray(b["idx"])
    p, state, m = step_o(p, state, b, jnp.asarray(t, jnp.int32))
    losses_o.append(float(m["loss"]))
R = ebc.plan.total_rows
mega_o = np.asarray(p["emb"]["mega"])
accum_o = np.asarray(state["accum"])

mesh = make_host_mesh(H)
mc = MultiHostCachedEmbeddingBagCollection.build(cfg, n_hosts=H,
                                                 cache_rows=512)
dense = {"bottom": params["bottom"], "top": params["top"]}
cstate = cached_dlrm_init_state(mc, opt, params)
mstate = mc.init_state(params["emb"]["mega"],
                       capacity_sharding=NamedSharding(mesh,
                                                       mc.ebc.plan.pspec))
assert mstate.capacity.sharding.spec == mc.ebc.plan.pspec
step_m = build_multihost_cached_train_step(cfg, mc, opt, strict_sync=True,
                                           mesh=mesh)
losses_m = []
for t in range(N):
    with mesh:
        dense, cstate, m = step_m(dense, cstate, mstate, batches[t],
                                  jnp.asarray(t, jnp.int32))
    losses_m.append(float(m["loss"]))
mega_m, accum_m = mc.materialize(mstate)
assert losses_o == losses_m, (losses_o, losses_m)
assert np.array_equal(mega_o, np.asarray(mega_m)[:R])
assert np.array_equal(accum_o, np.asarray(accum_m)[:R])
assert mstate.route.dup_rows >= N      # the hot row, each step
assert mstate.route.grad_pairs_remote > 0
print("MULTIHOST_MESH_OK")
""")
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=500)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MULTIHOST_MESH_OK" in out.stdout

# ---------------------------------------------------------------------------
# exchange-traffic model
# ---------------------------------------------------------------------------


def test_multihost_exchange_traffic_model():
    kw = dict(batch=4096, n_features=16, truncation=8, embed_dim=64)
    t8 = multihost_exchange_traffic(**kw, n_hosts=8, unique_per_host=9000,
                                    unique_global=30000, hit_rate=0.8)
    # one host -> no cross-host bytes on any leg
    t1 = multihost_exchange_traffic(**kw, n_hosts=1, unique_per_host=30000,
                                    unique_global=30000, hit_rate=0.8)
    for leg in ("fetch_bytes", "grad_bytes", "refresh_bytes",
                "total_bytes"):
        assert t1[leg] == 0.0
        assert t8[leg] > 0.0
    assert t8["dup_rows"] == 8 * 9000 - 30000
    # the dedup'd, cached exchange beats per-lookup shipping, and the
    # production row-sum variant beats the bit-exact per-pair routing
    assert t8["reduction"] > 1.0
    assert t8["rowsum_total_bytes"] < t8["total_bytes"]
    assert t8["rowsum_reduction"] > t8["reduction"]
    # better hit rate -> less fetch traffic, monotone total
    t8_hot = multihost_exchange_traffic(**kw, n_hosts=8,
                                        unique_per_host=9000,
                                        unique_global=30000, hit_rate=0.95)
    assert t8_hot["fetch_bytes"] < t8["fetch_bytes"]
    assert t8_hot["total_bytes"] < t8["total_bytes"]
