"""Plan-shared dedup'd embedding forward (docs/embedding_forward.md):
bit-exactness of the jnp fallback vs the legacy lookup on the stress
corpus, interpret-mode sweep of the new Pallas kernel, plan capacity
trimming, the index-only StableHLO gather check, the cached tiers' miss
planning through the plan, and the forward-traffic acceptance model."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import embedding
from repro.core.cache import CachedEmbeddingBagCollection
from repro.core.dlrm import dlrm_param_specs
from repro.core.embedding import EmbeddingBagCollection
from repro.data.pipeline import sparse_plan_hook
from repro.data.synthetic import make_dlrm_batch
from repro.kernels import ops, ref
from repro.kernels.row_move import gather_rows
from repro.kernels.sparse_plan import (SparsePlan, build_sparse_plan,
                                       build_sparse_plan_host,
                                       build_sparse_plan_with_slots)
from repro.launch.analysis import (embedding_forward_traffic,
                                   zipf_expected_unique)
from repro.nn.params import init_params
from repro.optim import adagrad


# ---------------------------------------------------------------------------
# index corpora: the ISSUE's stress patterns (2D bag layout)
# ---------------------------------------------------------------------------


def _zipf_idx2(rng, b, lk, h, a=1.1):
    idx = (rng.zipf(a, size=(b, lk)) - 1) % h
    lengths = rng.randint(0, lk + 1, size=(b,))
    mask = np.arange(lk)[None, :] < lengths[:, None]
    return np.where(mask, idx, -1).astype(np.int32)


def _corpus2(rng, h=60, b=12, lk=6):
    uniform = rng.randint(-1, h, size=(b, lk)).astype(np.int32)
    zipf = _zipf_idx2(rng, b, lk, h)
    all_pad = np.full((b, lk), -1, np.int32)
    all_dup = np.full((b, lk), 7, np.int32)
    empty_bags = uniform.copy()
    empty_bags[::2] = -1
    single = np.full((1, 1), h - 1, np.int32)
    return {"uniform": uniform, "zipf": zipf, "all_pad": all_pad,
            "all_dup": all_dup, "empty_bags": empty_bags, "single": single}


CASES = ["uniform", "zipf", "all_pad", "all_dup", "empty_bags", "single"]

# ---------------------------------------------------------------------------
# jnp fallback: bit-exact vs the legacy oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("mode", ["sum", "mean"])
def test_dedup_fallback_bit_matches_legacy_ref(rng, case, mode):
    idx = _corpus2(rng)[case]
    h, d = 60, 12
    table = jnp.asarray(rng.randn(h, d).astype(np.float32))
    want = ref.embedding_bag_ref(table, jnp.asarray(idx), mode)
    got = ops.dedup_embedding_bag(table, jnp.asarray(idx), mode=mode)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("case", ["uniform", "zipf", "all_dup"])
def test_dedup_fallback_with_trimmed_plan_bit_exact(rng, case):
    """Capacity-trimmed plans gather U rows instead of B*L and must still
    be bit-exact (the trim only drops dead -1 tail entries)."""
    idx = _corpus2(rng)[case]
    h, d = 60, 12
    n_unique = len(np.unique(idx[idx >= 0])) or 1
    cap = 1 << (n_unique - 1).bit_length()
    table = jnp.asarray(rng.randn(h, d).astype(np.float32))
    plan = build_sparse_plan_host(idx.reshape(-1),
                                  lookups_per_bag=idx.shape[1],
                                  capacity=cap)
    planj = SparsePlan(*(jnp.asarray(x) for x in plan))
    want = ref.embedding_bag_ref(table, jnp.asarray(idx), "sum")
    got = ops.dedup_embedding_bag(table, jnp.asarray(idx), plan=planj)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_dedup_vjp_matches_embedding_bag_vjp(rng):
    idx = jnp.asarray(rng.randint(-1, 30, size=(5, 4)).astype(np.int32))
    table = jnp.asarray(rng.randn(30, 8).astype(np.float32))
    g = jnp.asarray(rng.randn(5, 8).astype(np.float32))
    g1 = jax.grad(lambda t: (ops.embedding_bag(t, idx, "sum", False, False)
                             * g).sum())(table)
    g2 = jax.grad(lambda t: (ops.dedup_embedding_bag(t, idx)
                             * g).sum())(table)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))

# ---------------------------------------------------------------------------
# Pallas kernel body (interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("h,d,b,lk", [
    (64, 128, 8, 5),        # lane-aligned d
    (97, 48, 6, 7),         # padded d, odd sizes
    (33, 200, 3, 32),       # d > lane, truncation-sized lk
    (50, 16, 11, 6),        # n_bags not a sublane multiple
])
def test_dedup_kernel_interpret_matches_ref(rng, h, d, b, lk):
    idx = rng.randint(-1, h, size=(b, lk)).astype(np.int32)
    table = jnp.asarray(rng.randn(h, d).astype(np.float32))
    want = ref.embedding_bag_ref(table, jnp.asarray(idx), "sum")
    got = ops.dedup_embedding_bag(table, jnp.asarray(idx),
                                  use_kernel=None, interpret=True)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", CASES)
def test_dedup_kernel_interpret_corpus(rng, case):
    """Corpus sweep incl. the deep-CSR all-duplicate case (one unique row
    referenced by every bag — the longest expansion run) and all-pads
    (zero live rows: the kernel must still zero its resident out block)."""
    idx = _corpus2(rng)[case]
    table = jnp.asarray(rng.randn(60, 12).astype(np.float32))
    want = ref.embedding_bag_ref(table, jnp.asarray(idx), "sum")
    got = ops.dedup_embedding_bag(table, jnp.asarray(idx),
                                  use_kernel=None, interpret=True)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-5, atol=1e-5)


def test_fused_backward_interpret_with_deep_grad_stream(rng):
    """The double-buffered per-bag grad DMA stream (PR 3 follow-on): an
    all-duplicate batch routes EVERY bag's gradient through one unique
    row's stream — the deepest pipeline — and must still match the
    legacy oracle."""
    h, d, b, f, lk = 32, 128, 4, 2, 6
    idx = np.full((b, f, lk), 7, np.int32)
    table = rng.randn(h, d).astype(np.float32)
    accum = np.abs(rng.randn(h)).astype(np.float32)
    pooled = rng.randn(b, f, d).astype(np.float32)
    g = jnp.broadcast_to(jnp.asarray(pooled)[:, :, None, :], (b, f, lk, d))
    tr, ar = ref.rowwise_adagrad_ref(
        jnp.asarray(table), jnp.asarray(accum),
        jnp.asarray(idx.reshape(-1)), g.reshape(b * f * lk, d), 0.05)
    tk, ak = ops.fused_sparse_backward(
        jnp.asarray(table), jnp.asarray(accum), jnp.asarray(idx),
        jnp.asarray(pooled), 0.05, use_kernel=None, interpret=True)
    np.testing.assert_allclose(np.asarray(tk), np.asarray(tr),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ak), np.asarray(ar),
                               rtol=1e-5, atol=1e-6)

# ---------------------------------------------------------------------------
# plan capacity: trimming is behaviour-preserving, overflow raises
# ---------------------------------------------------------------------------


def test_plan_capacity_host_matches_jnp_and_preserves_backward(rng):
    idx = _zipf_idx2(rng, 10, 8, 40).reshape(5, 2, 8)
    n_unique = len(np.unique(idx[idx >= 0]))
    cap = n_unique + 3
    ph = build_sparse_plan_host(idx, capacity=cap)
    pj = build_sparse_plan(jnp.asarray(idx), capacity=cap)
    for a, b in zip(pj, ph):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ph.unique_rows.shape == (cap,)
    assert ph.bag_offsets.shape == (cap + 1,)
    # fused backward through the trimmed plan == untrimmed
    table = jnp.asarray(rng.randn(40, 16).astype(np.float32))
    accum = jnp.asarray(np.abs(rng.randn(40)).astype(np.float32))
    pooled = jnp.asarray(rng.randn(5, 2, 16).astype(np.float32))
    t1, a1 = ops.fused_sparse_backward(
        table, accum, None, pooled, 0.05,
        plan=SparsePlan(*(jnp.asarray(x) for x in ph)))
    t2, a2 = ops.fused_sparse_backward(
        table, accum, jnp.asarray(idx), pooled, 0.05)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))


def test_plan_capacity_overflow_raises_on_host(rng):
    idx = np.arange(24, dtype=np.int32).reshape(2, 2, 6)
    with pytest.raises(ValueError, match="capacity overflow"):
        build_sparse_plan_host(idx, capacity=8)


def test_sparse_plan_hook_capacity_rides_to_batch(rng):
    cfg = get_smoke_config("dlrm-m1")
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1,
                                       strategy="replicated")
    raw = make_dlrm_batch(cfg, 8)
    probe = sparse_plan_hook(ebc.plan.table_offsets)(
        {k: np.asarray(v) for k, v in raw.items()})
    n_unique = int((probe["plan_rows"] >= 0).sum())
    cap = n_unique + 5
    hook = sparse_plan_hook(ebc.plan.table_offsets, capacity=cap)
    batch = hook({k: np.asarray(v) for k, v in raw.items()})
    assert batch["plan_rows"].shape == (cap,)
    assert batch["plan_offsets"].shape == (cap + 1,)

# ---------------------------------------------------------------------------
# acceptance: the forward gathers n_unique rows, not B*F*L (StableHLO)
# ---------------------------------------------------------------------------


def test_forward_gather_is_unique_capacity_not_slot_count(rng):
    """Index-only StableHLO check: with a capacity-trimmed plan, the only
    gather that touches the (H, D) table has U rows; no table gather is
    B*L-sized."""
    h, d, b, lk, cap = 997, 16, 8, 16, 64
    idx = jax.ShapeDtypeStruct((b, lk), jnp.int32)
    plan = SparsePlan(jax.ShapeDtypeStruct((cap,), jnp.int32),
                      jax.ShapeDtypeStruct((cap + 1,), jnp.int32),
                      jax.ShapeDtypeStruct((b * lk,), jnp.int32))
    table = jax.ShapeDtypeStruct((h, d), jnp.float32)
    text = jax.jit(
        lambda t, i, p: ops.dedup_embedding_bag(t, i, plan=p)
    ).lower(table, idx, plan).as_text()
    table_gathers = [ln for ln in text.splitlines()
                     if "gather" in ln and f"tensor<{h}x{d}xf32>" in ln]
    assert table_gathers, "expected a gather from the table"
    for ln in table_gathers:
        res = ln.rsplit("-> tensor<", 1)[-1]
        assert res.startswith(f"{cap}x"), ln
        assert not res.startswith(f"{b * lk}x"), ln

# ---------------------------------------------------------------------------
# EBC / train-step integration
# ---------------------------------------------------------------------------


def _planned_vs_plain_lookup(cfg, rng):
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1,
                                       strategy="replicated")
    raw = make_dlrm_batch(cfg, 8)
    idx = np.asarray(ebc.offset_indices(jnp.asarray(raw["idx"])))
    mega = rng.randn(ebc.plan.total_rows, cfg.embed_dim).astype(np.float32)
    params = {"mega": jnp.asarray(mega)}
    plan = build_sparse_plan_host(idx)
    planj = SparsePlan(*(jnp.asarray(x) for x in plan))
    p0 = jax.jit(lambda p, i: ebc.lookup(p, i))(params, jnp.asarray(idx))
    p1 = jax.jit(lambda p, i, pl_: ebc.lookup(p, i, plan=pl_))(
        params, jnp.asarray(idx), planj)
    np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))


def test_lookup_with_plan_bit_exact_direct_path(rng):
    _planned_vs_plain_lookup(get_smoke_config("dlrm-m1"), rng)   # f <= 8


def test_lookup_with_plan_bit_exact_scan_path(rng):
    cfg = get_smoke_config("dlrm-m1")
    f = 10                                                        # f > 8
    cfg = dataclasses.replace(cfg, n_sparse_features=f,
                              hash_sizes=(40,) * f,
                              mean_lookups=(3,) * f)
    _planned_vs_plain_lookup(cfg, rng)


# ---------------------------------------------------------------------------
# slot positions read off the plan's own sort (the built-plan forward)
# ---------------------------------------------------------------------------


def _slot_cases(rng):
    """name -> (idx (B, F, L), capacity or None)."""
    dup = rng.randint(0, 30, size=(4, 3, 6)).astype(np.int32)
    dup[:, :, 1::2] = dup[:, :, ::2]                # each row twice per bag
    dup[0, 0, 3:] = -1
    pad_feature = rng.randint(-1, 30, size=(5, 4, 5)).astype(np.int32)
    pad_feature[:, 2] = -1                          # one feature all pads
    heights = np.array([5, 7, 3])                   # tables' rows
    offs = np.concatenate([[0], np.cumsum(heights)[:-1]])
    edge = np.stack([offs, offs + heights - 1])     # first, last row each
    bounds = edge[rng.randint(0, 2, size=(6, 3, 4)),
                  np.arange(3)[None, :, None]].astype(np.int32)
    bounds[1, :, 2:] = -1
    trimmed = _zipf_idx2(rng, 12, 8, 40).reshape(6, 2, 8)
    n_unique = len(np.unique(trimmed[trimmed >= 0]))
    scan = rng.randint(-1, 50, size=(4, 10, 3)).astype(np.int32)   # f > 8
    return {"dup_in_bag": (dup, None), "all_pad_feature": (pad_feature, None),
            "table_bounds": (bounds, None),
            "capacity_trimmed": (trimmed, n_unique + 2),
            "scan_shape": (scan, None)}


@pytest.mark.parametrize("case", ["dup_in_bag", "all_pad_feature",
                                  "table_bounds", "capacity_trimmed",
                                  "scan_shape"])
def test_plan_slots_equal_searchsorted(rng, case):
    """Each valid slot's position from the plan's sort is where a binary
    search over the plan's sorted rows finds the slot's row, in the
    full plan and in one trimmed to a capacity (every live position lies
    below the unique count); every position, pads' too, indexes a row of
    the plan; and the plan is `build_sparse_plan`'s."""
    idx, cap = _slot_cases(rng)[case]
    plan, slots = jax.jit(build_sparse_plan_with_slots)(jnp.asarray(idx))
    for a, b in zip(plan, build_sparse_plan(jnp.asarray(idx))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rows = np.asarray(
        build_sparse_plan(jnp.asarray(idx), capacity=cap).unique_rows)
    sent = np.where(rows >= 0, rows, np.iinfo(np.int32).max)
    want = np.searchsorted(sent, np.maximum(idx, 0))
    slots = np.asarray(slots)
    assert slots.shape == idx.shape and slots.dtype == np.int32
    valid = idx >= 0
    np.testing.assert_array_equal(slots[valid], want[valid])
    np.testing.assert_array_equal(rows[slots[valid]], idx[valid])
    assert slots.min() >= 0 and slots.max() < rows.shape[0]


def _scan_ebc(rng, strategy="replicated"):
    cfg = get_smoke_config("dlrm-m1")
    f = 10
    cfg = dataclasses.replace(cfg, n_sparse_features=f,
                              hash_sizes=(40,) * f, mean_lookups=(3,) * f)
    return _lookup_case(cfg, rng, strategy)


def _lookup_case(cfg, rng, strategy="replicated"):
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1, strategy=strategy)
    raw = make_dlrm_batch(cfg, 8)
    idx = ebc.offset_indices(jnp.asarray(raw["idx"]))
    mega = jnp.asarray(rng.randn(ebc.plan.total_rows,
                                 cfg.embed_dim).astype(np.float32))
    return ebc, {"mega": mega}, idx


def _lookup_fn(ebc, path, **kw):
    """The lookup a step runs on `path`, as fn(params, idx)."""
    if path == "psum":
        mesh = jax.make_mesh((1,), ("model",))
        return lambda p, i: ebc.lookup_pooled_psum(p, i, mesh, **kw)
    return lambda p, i: ebc.lookup(p, i, **kw)


@pytest.mark.parametrize("path", ["built", "supplied", "psum"])
def test_only_a_supplied_plan_searches(rng, path):
    """The TPU lookup traced on the CPU: a plan the lookup builds from idx
    (the uncached step's, the table-wise shard's) gives each slot's
    position from its own sort, so no `searchsorted` is traced; a plan
    handed in keeps the search."""
    ebc, params, idx = _scan_ebc(
        rng, "table_wise" if path == "psum" else "replicated")
    kw = {"use_kernel": True}
    if path == "supplied":
        kw["plan"] = build_sparse_plan(idx)
    jaxpr = str(jax.make_jaxpr(_lookup_fn(ebc, path, **kw))(params, idx))
    assert "gather_rows" in jaxpr or "move_rows" in jaxpr
    assert ("searchsorted" in jaxpr) == (path == "supplied")


@pytest.mark.parametrize("path", ["direct", "scan", "psum"])
def test_built_plan_lookup_bit_exact_vs_plain(rng, monkeypatch, path):
    """The TPU lookup (plan built from idx, slots from its sort, compact
    slab read by the row-move kernel, here interpreted) pools the same
    bits as the plan-less lookup, on the f <= 8 direct branch, the f > 8
    scan and the table-wise shard."""
    monkeypatch.setattr(embedding, "gather_rows",
                        functools.partial(gather_rows, interpret=True))
    if path == "direct":
        ebc, params, idx = _lookup_case(get_smoke_config("dlrm-m1"), rng)
    else:
        ebc, params, idx = _scan_ebc(
            rng, "table_wise" if path == "psum" else "replicated")
    plain = jax.jit(_lookup_fn(ebc, path, use_kernel=False))(params, idx)
    built = jax.jit(_lookup_fn(ebc, path, use_kernel=True))(params, idx)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(built))


def test_lookup_local_dedup_matches_legacy(rng):
    cfg = get_smoke_config("dlrm-m1")
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1,
                                       strategy="replicated")
    raw = make_dlrm_batch(cfg, 8)
    idx = ebc.offset_indices(jnp.asarray(raw["idx"]))
    mega = jnp.asarray(rng.randn(ebc.plan.total_rows,
                                 cfg.embed_dim).astype(np.float32))
    lo, hi = 0, ebc.plan.total_rows
    out0 = ebc.lookup_local(mega, idx, lo, hi)
    out1 = ebc.lookup_local(mega, idx, lo, hi, dedup=True)
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(out1))


def test_dlrm_forward_consumes_batch_plan_bit_exact(rng):
    """dlrm_grads picks the plan off the batch for the FORWARD too: loss
    and pooled grads must be bit-identical with and without plan keys."""
    from repro.core.dlrm import dlrm_grads
    cfg = get_smoke_config("dlrm-m1")
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1,
                                       strategy="replicated")
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(3))
    hook = sparse_plan_hook(ebc.plan.table_offsets)
    raw = make_dlrm_batch(cfg, 8)
    batch = hook({k: np.asarray(v) for k, v in raw.items()})
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    no_plan = {k: v for k, v in jb.items()
               if not k.startswith("plan_") and k != "uniq_rows"}
    l1, _, (_, g1) = dlrm_grads(params, jb, cfg, ebc)
    l2, _, (_, g2) = dlrm_grads(params, no_plan, cfg, ebc)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))

# ---------------------------------------------------------------------------
# cached tiers: miss planning through the plan
# ---------------------------------------------------------------------------


def _tiny_cached_cfg():
    return dataclasses.replace(
        get_smoke_config("dlrm-m1"), n_sparse_features=2,
        hash_sizes=(80, 40), mean_lookups=(4, 2), bottom_mlp=(8, 16),
        top_mlp=(26, 1))


def test_cache_prepare_with_plan_matches_without(rng):
    """The miss planner fed the reader-thread plan must produce the same
    remap, slot maps, and counters as the np.unique path — the plan's
    live prefix IS the sorted unique row set."""
    cfg = _tiny_cached_cfg()
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1,
                                       strategy="replicated")
    mega = jnp.asarray(rng.randn(ebc.plan.total_rows,
                                 cfg.embed_dim).astype(np.float32))
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=64)
    s1, s2 = cc.init_state(mega), cc.init_state(mega)
    for t in range(3):
        raw = make_dlrm_batch(cfg, 8, step=t)
        idx = np.asarray(ebc.offset_indices(jnp.asarray(raw["idx"])))
        plan = build_sparse_plan_host(idx)
        l1 = cc.prepare(s1, idx, train=True)
        l2 = cc.prepare(s2, idx, train=True, plan=plan)
        np.testing.assert_array_equal(l1, l2)
        np.testing.assert_array_equal(s1.slot_row, s2.slot_row)
        np.testing.assert_array_equal(s1.dirty, s2.dirty)
        assert s1.stats.snapshot() == s2.stats.snapshot()
        np.testing.assert_array_equal(np.asarray(s1.freq),
                                      np.asarray(s2.freq))


def test_plan_to_slots_keeps_rows_sorted_and_decodes(rng):
    """After the row->slot relabel the live prefix must stay strictly
    ascending (the dedup'd forward's invariant) and still decode to the
    same (slot, bag) multiset."""
    cfg = _tiny_cached_cfg()
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1,
                                       strategy="replicated")
    mega = jnp.asarray(rng.randn(ebc.plan.total_rows,
                                 cfg.embed_dim).astype(np.float32))
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=64)
    state = cc.init_state(mega)
    raw = make_dlrm_batch(cfg, 8, step=5)
    idx = np.asarray(ebc.offset_indices(jnp.asarray(raw["idx"])))
    plan = build_sparse_plan_host(idx)
    cc.prepare(state, idx, train=True, plan=plan)
    slot_plan = cc.plan_to_slots(state, plan.to_batch())
    rows, offs, bags = (slot_plan["plan_rows"], slot_plan["plan_offsets"],
                        slot_plan["plan_bags"])
    live = rows[rows >= 0]
    assert np.all(np.diff(live) > 0)
    # decode (slot, bag) pairs and compare against the direct remap
    decoded = sorted(
        (int(rows[i]), int(bags[j]))
        for i in range(len(live))
        for j in range(offs[i], offs[i + 1]))
    local = state.row_slot[np.maximum(idx, 0)]
    flat = np.where(idx >= 0, local, -1).reshape(-1)
    lk = idx.shape[2]
    expected = sorted((int(s), p // lk)
                      for p, s in enumerate(flat) if s >= 0)
    assert decoded == expected


def test_cached_step_forward_and_backward_share_slot_plan(rng):
    """End-to-end: cached train steps fed hook plans (which now drive the
    forward gather, the fused backward, AND the miss planner) leave
    bit-identical tiers vs the plan-less run."""
    from repro.train.steps import (build_cached_dlrm_train_step,
                                   cached_dlrm_init_state)
    cfg = _tiny_cached_cfg()
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1,
                                       strategy="replicated")
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(7))
    opt = adagrad(0.01)
    hook = sparse_plan_hook(ebc.plan.table_offsets)
    batches = [hook({k: np.asarray(v) for k, v in
                     make_dlrm_batch(cfg, 8, step=t).items()})
               for t in range(3)]

    def run(with_plan):
        cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=64)
        dense = {"bottom": params["bottom"], "top": params["top"]}
        state = cached_dlrm_init_state(cc, opt, params)
        cstate = cc.init_state(params["emb"]["mega"])
        step = build_cached_dlrm_train_step(cfg, cc, opt)
        losses = []
        for t, b in enumerate(batches):
            b = dict(b)
            if not with_plan:
                for k in ("plan_rows", "plan_offsets", "plan_bags"):
                    b.pop(k)
            dense, state, m = step(dense, state, cstate, b,
                                   jnp.asarray(t, jnp.int32))
            losses.append(float(m["loss"]))
        mega, accum = cc.materialize(cstate)
        return mega, accum, losses

    m1, a1, l1 = run(True)
    m2, a2, l2 = run(False)
    assert l1 == l2
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))

# ---------------------------------------------------------------------------
# acceptance: forward-traffic model
# ---------------------------------------------------------------------------


def test_embedding_forward_traffic_reduction_exceeds_truncation():
    """ISSUE acceptance: >= L-fold HBM row-read (and bytes) reduction at
    the prod shape (B=4096, F=127, L=32) in the Zipf-head reuse regime
    (Gupta et al.): hot batches reference at most one unique row per bag
    (n_unique <= B*F). The model is linear in n_unique, so any batch at
    least this duplicate-heavy does at least this well — asserted with
    the FULL plan charged to the forward (plan_shared=False), at both
    m3's real embed dim (64) and the bench dim (128)."""
    b, f, lk = 4096, 127, 32
    for d in (64, 128):
        t = embedding_forward_traffic(b, f, lk, d, n_unique=b * f,
                                      plan_shared=False)
        assert t["row_read_reduction"] >= lk
        assert t["reduction"] >= lk
    # sanity: legacy counts the three full-width per-slot tensors and
    # B*F*L row reads (the legacy kernel DMAs every slot)
    n = b * f * lk
    t = embedding_forward_traffic(b, f, lk, 128, n_unique=b * f)
    assert t["legacy_bytes"] == pytest.approx(3 * n * 128 * 4)
    assert t["legacy_row_reads"] == n
    assert t["dedup_bytes"] == pytest.approx(b * f * 128 * 4)


def test_zipf_expected_unique_exact_and_monotone():
    """The deterministic E[unique] helper: exact on a tiny enumerable
    case, monotone in draws, capped by the hash size."""
    # h=2, alpha->p = (0.659, 0.341); E[unique] for n=1 is 1 exactly
    assert zipf_expected_unique(1, 2) == pytest.approx(1.0)
    u1 = zipf_expected_unique(100, 1000)
    u2 = zipf_expected_unique(1000, 1000)
    assert 0 < u1 < u2 < 1000
    # saturation: far more draws than rows -> every row seen
    assert zipf_expected_unique(1e7, 50) == pytest.approx(50, rel=1e-6)
    # matches a direct dense computation on a small case
    r = np.arange(1, 301, dtype=np.float64)
    p = r ** -1.05
    p /= p.sum()
    want = (1 - (1 - p) ** 500).sum()
    assert zipf_expected_unique(500, 300) == pytest.approx(want, rel=1e-9)


def test_bag_grad_sums_capacity_trim_matches_full(rng):
    idx = _zipf_idx2(rng, 9, 7, 30)
    n = idx.size
    nu = len(np.unique(idx[idx >= 0]))
    full = build_sparse_plan_host(idx.reshape(-1), lookups_per_bag=7)
    trim = build_sparse_plan_host(idx.reshape(-1), lookups_per_bag=7,
                                  capacity=nu + 2)
    pooled = jnp.asarray(rng.randn(9, 16).astype(np.float32))
    g_full = ref.bag_grad_sums(*(jnp.asarray(x) for x in full),
                               pooled)
    g_trim = ref.bag_grad_sums(*(jnp.asarray(x) for x in trim), pooled)
    assert g_full.shape == (n, 16)
    assert g_trim.shape == (nu + 2, 16)
    np.testing.assert_array_equal(np.asarray(g_full[:nu + 2]),
                                  np.asarray(g_trim))
