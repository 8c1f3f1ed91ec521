"""Fused sparse backward (kernels/sparse_plan.py + sparse_update.py + the
rewired train steps): the bucketing planner, the bit-exactness contract vs
the legacy per-lookup layout, the Pallas kernel body, the pipeline plan
hook, and the index-only / intermediate-bytes acceptance checks."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.cache import CachedEmbeddingBagCollection
from repro.core.dlrm import dlrm_param_specs
from repro.core.embedding import EmbeddingBagCollection
from repro.data.pipeline import sparse_plan_hook
from repro.data.synthetic import make_dlrm_batch
from repro.kernels import ops, ref
from repro.kernels.sparse_plan import (SparsePlan, build_sparse_plan,
                                       build_sparse_plan_host,
                                       plan_from_batch)
from repro.launch.analysis import sparse_backward_traffic
from repro.nn.params import init_params
from repro.optim import adagrad
from repro.train.steps import build_dlrm_train_step, dlrm_init_state

from conftest import requires_hypothesis  # noqa: E402  (pytest test path)


# ---------------------------------------------------------------------------
# index corpora: the ISSUE's stress patterns
# ---------------------------------------------------------------------------


def _zipf_idx(rng, b, f, lk, h, a=1.1):
    """Duplicate-heavy (Zipf) multi-hot batch with ragged -1 padding."""
    idx = (rng.zipf(a, size=(b, f, lk)) - 1) % h
    lengths = rng.randint(0, lk + 1, size=(b, f))
    mask = np.arange(lk)[None, None, :] < lengths[..., None]
    return np.where(mask, idx, -1).astype(np.int32)


def _corpus(rng, h=60, b=5, f=3, lk=6):
    uniform = rng.randint(-1, h, size=(b, f, lk)).astype(np.int32)
    zipf = _zipf_idx(rng, b, f, lk, h)
    all_pad = np.full((b, f, lk), -1, np.int32)
    all_dup = np.full((b, f, lk), 7, np.int32)
    empty_bags = uniform.copy()
    empty_bags[::2] = -1                       # whole examples empty
    single = np.full((1, 1, 1), h - 1, np.int32)
    return {"uniform": uniform, "zipf": zipf, "all_pad": all_pad,
            "all_dup": all_dup, "empty_bags": empty_bags, "single": single}

# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", ["uniform", "zipf", "all_pad", "all_dup",
                                  "empty_bags", "single"])
def test_plan_host_matches_jnp(rng, case):
    idx = _corpus(rng)[case]
    pj = build_sparse_plan(jnp.asarray(idx))
    ph = build_sparse_plan_host(idx)
    for a, b in zip(pj, ph):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("case", ["uniform", "zipf", "empty_bags"])
def test_plan_reconstructs_lookup_multiset(rng, case):
    """Decoding the CSR layout must recover exactly the (row, bag) pair
    multiset of the raw batch — nothing dropped, nothing invented."""
    idx = _corpus(rng)[case]
    b, f, lk = idx.shape
    plan = build_sparse_plan_host(idx)
    rows, offs, bags = (np.asarray(x) for x in plan)
    decoded = []
    for i, r in enumerate(rows):
        if r < 0:
            assert offs[i + 1] == offs[i] or i >= (rows >= 0).sum()
            continue
        for j in range(offs[i], offs[i + 1]):
            decoded.append((int(r), int(bags[j])))
    expected = []
    flat = idx.reshape(-1)
    for pos, r in enumerate(flat):
        if r >= 0:
            expected.append((int(r), pos // lk))
    assert sorted(decoded) == sorted(expected)
    # unique rows are strictly increasing over the live prefix (sorted)
    live = rows[rows >= 0]
    assert np.all(np.diff(live) > 0)


def test_plan_lowering_is_index_only():
    """Acceptance: the bucketing plan aggregates on int32 indices only — its
    lowered StableHLO contains no float tensors at all."""
    idx = jax.ShapeDtypeStruct((8, 4, 16), jnp.int32)
    text = jax.jit(build_sparse_plan).lower(idx).as_text()
    for ft in ("f32", "f64", "bf16", "f16"):
        assert f"x{ft}" not in text and f"tensor<{ft}" not in text, ft

# ---------------------------------------------------------------------------
# fused ref == legacy rowwise_adagrad_ref, bit for bit
# ---------------------------------------------------------------------------


def _legacy(table, accum, idx, pooled, lr=0.05, eps=1e-8):
    b, f, lk = idx.shape
    d = pooled.shape[-1]
    g = jnp.broadcast_to(jnp.asarray(pooled)[:, :, None, :], (b, f, lk, d))
    return ref.rowwise_adagrad_ref(
        jnp.asarray(table), jnp.asarray(accum),
        jnp.asarray(idx.reshape(-1)), g.reshape(b * f * lk, d), lr, eps)


@pytest.mark.parametrize("case", ["uniform", "zipf", "all_pad", "all_dup",
                                  "empty_bags", "single"])
def test_fused_bit_matches_legacy_ref(rng, case):
    idx = _corpus(rng)[case]
    b, f, _ = idx.shape
    h, d = 60, 12
    table = rng.randn(h, d).astype(np.float32)
    accum = np.abs(rng.randn(h)).astype(np.float32)
    pooled = rng.randn(b, f, d).astype(np.float32)
    tl, al = _legacy(table, accum, idx, pooled)
    tf, af = ops.fused_sparse_backward(
        jnp.asarray(table), jnp.asarray(accum), jnp.asarray(idx),
        jnp.asarray(pooled), 0.05)
    np.testing.assert_array_equal(np.asarray(tl), np.asarray(tf))
    np.testing.assert_array_equal(np.asarray(al), np.asarray(af))


@requires_hypothesis
def test_fused_bit_matches_legacy_ref_fuzz():
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), b=st.integers(1, 6),
           f=st.integers(1, 4), lk=st.integers(1, 9),
           zipf=st.booleans())
    def run(seed, b, f, lk, zipf):
        rng = np.random.RandomState(seed)
        h, d = 40, 8
        idx = _zipf_idx(rng, b, f, lk, h) if zipf else \
            rng.randint(-1, h, size=(b, f, lk)).astype(np.int32)
        table = rng.randn(h, d).astype(np.float32)
        accum = np.abs(rng.randn(h)).astype(np.float32)
        pooled = rng.randn(b, f, d).astype(np.float32)
        tl, al = _legacy(table, accum, idx, pooled)
        tf, af = ops.fused_sparse_backward(
            jnp.asarray(table), jnp.asarray(accum), jnp.asarray(idx),
            jnp.asarray(pooled), 0.05)
        np.testing.assert_array_equal(np.asarray(tl), np.asarray(tf))
        np.testing.assert_array_equal(np.asarray(al), np.asarray(af))

    run()

# ---------------------------------------------------------------------------
# Pallas kernel body (interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("h,d,b,f,lk", [
    (64, 128, 4, 2, 5),      # lane-aligned d
    (97, 48, 6, 3, 7),       # padded d, odd sizes
    (33, 200, 2, 1, 32),     # d > lane, truncation-sized lk
])
def test_fused_kernel_interpret_matches_ref(rng, h, d, b, f, lk):
    idx = rng.randint(-1, h, size=(b, f, lk)).astype(np.int32)
    table = rng.randn(h, d).astype(np.float32)
    accum = np.abs(rng.randn(h)).astype(np.float32)
    pooled = rng.randn(b, f, d).astype(np.float32)
    tk, ak = ops.fused_sparse_backward(
        jnp.asarray(table), jnp.asarray(accum), jnp.asarray(idx),
        jnp.asarray(pooled), 0.05, use_kernel=None, interpret=True)
    tr, ar = _legacy(table, accum, idx, pooled)
    np.testing.assert_allclose(np.asarray(tk), np.asarray(tr),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ak), np.asarray(ar),
                               rtol=1e-5, atol=1e-6)


def test_fused_kernel_interpret_tight_when_lane_aligned(rng):
    """With D already lane-aligned nothing is padded or rescaled: the kernel
    body tracks the legacy oracle to ~1 ulp (the residual difference is
    mean()'s backend-dependent reduction order, same as the legacy rowwise
    kernel; the jnp FALLBACK is the bit-exact contract, asserted above)."""
    h, d, b, f, lk = 32, 128, 3, 2, 6
    idx = rng.randint(-1, h, size=(b, f, lk)).astype(np.int32)
    table = rng.randn(h, d).astype(np.float32)
    accum = np.abs(rng.randn(h)).astype(np.float32)
    pooled = rng.randn(b, f, d).astype(np.float32)
    tk, ak = ops.fused_sparse_backward(
        jnp.asarray(table), jnp.asarray(accum), jnp.asarray(idx),
        jnp.asarray(pooled), 0.05, use_kernel=None, interpret=True)
    tr, ar = _legacy(table, accum, idx, pooled)
    np.testing.assert_allclose(np.asarray(tk), np.asarray(tr),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ak), np.asarray(ar),
                               rtol=1e-6, atol=1e-7)

# ---------------------------------------------------------------------------
# plan passthrough: hook-built plan == on-device plan
# ---------------------------------------------------------------------------


def test_precomputed_plan_matches_on_device_plan(rng):
    idx = _zipf_idx(rng, 6, 3, 8, 50)
    table = rng.randn(50, 16).astype(np.float32)
    accum = np.abs(rng.randn(50)).astype(np.float32)
    pooled = rng.randn(6, 3, 16).astype(np.float32)
    plan = build_sparse_plan_host(idx)
    t1, a1 = ops.fused_sparse_backward(
        jnp.asarray(table), jnp.asarray(accum), None, jnp.asarray(pooled),
        0.05, plan=SparsePlan(*(jnp.asarray(x) for x in plan)))
    t2, a2 = ops.fused_sparse_backward(
        jnp.asarray(table), jnp.asarray(accum), jnp.asarray(idx),
        jnp.asarray(pooled), 0.05)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))


def test_sparse_plan_hook_attaches_relabelable_plan(rng):
    """The pipeline hook rewrites idx to offset rows AND attaches the CSR
    plan; plan_from_batch rehydrates it; the train step consumes it to the
    same result as planning on device."""
    cfg = get_smoke_config("dlrm-m1")
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1,
                                       strategy="replicated")
    hook = sparse_plan_hook(ebc.plan.table_offsets)
    raw = make_dlrm_batch(cfg, 8)
    batch = hook({k: np.asarray(v) for k, v in raw.items()})
    for key in ("plan_rows", "plan_offsets", "plan_bags", "uniq_rows"):
        assert key in batch
    want = build_sparse_plan_host(batch["idx"])
    got = plan_from_batch(batch)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(0))
    opt = adagrad(0.01)
    state = dlrm_init_state(ebc, opt, params)
    step = build_dlrm_train_step(cfg, ebc, opt, sparse_apply="sparse")
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    no_plan = {k: v for k, v in jb.items()
               if not k.startswith("plan_") and k != "uniq_rows"}
    p1, s1, m1 = jax.jit(step)(params, state, jb, jnp.asarray(0, jnp.int32))
    p2, s2, m2 = jax.jit(step)(params, state, no_plan,
                               jnp.asarray(0, jnp.int32))
    np.testing.assert_array_equal(np.asarray(p1["emb"]["mega"]),
                                  np.asarray(p2["emb"]["mega"]))
    np.testing.assert_array_equal(np.asarray(s1["accum"]),
                                  np.asarray(s2["accum"]))

# ---------------------------------------------------------------------------
# train-step rewiring: fused nrows == legacy math
# ---------------------------------------------------------------------------


def test_fused_train_step_matches_legacy_sparse_apply(rng):
    """The rewired sparse_apply="sparse" step must produce the same mega
    table as the legacy broadcast + dedup + rowwise update on the same
    batch (the semantics the seed tests pinned)."""
    cfg = get_smoke_config("dlrm-m1")
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1,
                                       strategy="replicated")
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(1))
    opt = adagrad(0.01)
    state = dlrm_init_state(ebc, opt, params)
    raw = make_dlrm_batch(cfg, 8)
    idx = ebc.offset_indices(jnp.asarray(raw["idx"]))
    batch = {"dense": jnp.asarray(raw["dense"]), "idx": idx,
             "label": jnp.asarray(raw["label"])}
    step = build_dlrm_train_step(cfg, ebc, opt, sparse_apply="sparse")
    p1, s1, _ = jax.jit(step)(params, state, batch, jnp.asarray(0, jnp.int32))

    from repro.core.dlrm import dlrm_grads
    _, _, (idx_blf, g_pooled) = dlrm_grads(params, batch, cfg, ebc)
    fi, fg = ebc.per_lookup_grads(idx_blf, g_pooled)
    want_mega, want_accum = ref.rowwise_adagrad_ref(
        params["emb"]["mega"], state["accum"], fi, fg, 0.05)
    np.testing.assert_allclose(np.asarray(p1["emb"]["mega"]),
                               np.asarray(want_mega), rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(s1["accum"]),
                               np.asarray(want_accum), rtol=1e-6, atol=1e-7)

# ---------------------------------------------------------------------------
# cached tier: slot-space plan relabel
# ---------------------------------------------------------------------------


def test_cached_step_with_plan_hook_bit_matches_plain(rng):
    """The cached train step fed hook-attached plans (relabelled to slot
    space) must leave bit-identical tiers vs the same batches without
    plans."""
    from repro.train.steps import (build_cached_dlrm_train_step,
                                   cached_dlrm_init_state)
    cfg = dataclasses.replace(
        get_smoke_config("dlrm-m1"), n_sparse_features=2,
        hash_sizes=(80, 40), mean_lookups=(4, 2), bottom_mlp=(8, 16),
        top_mlp=(26, 1))
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1,
                                       strategy="replicated")
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(2))
    opt = adagrad(0.01)
    hook = sparse_plan_hook(ebc.plan.table_offsets)
    batches = []
    for t in range(3):
        raw = make_dlrm_batch(cfg, 8, step=t)
        batches.append(hook({k: np.asarray(v) for k, v in raw.items()}))

    def run(with_plan):
        cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=64)
        dense = {"bottom": params["bottom"], "top": params["top"]}
        state = cached_dlrm_init_state(cc, opt, params)
        cstate = cc.init_state(params["emb"]["mega"])
        step = build_cached_dlrm_train_step(cfg, cc, opt)
        for t, b in enumerate(batches):
            b = dict(b)
            if not with_plan:
                for k in ("plan_rows", "plan_offsets", "plan_bags"):
                    b.pop(k)
            dense, state, _ = step(dense, state, cstate, b,
                                   jnp.asarray(t, jnp.int32))
        return cc.materialize(cstate)

    m1, a1 = run(True)
    m2, a2 = run(False)
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))

# ---------------------------------------------------------------------------
# 8-fake-device shard_map variant (subprocess — the main process pins 1 CPU
# device; same isolation discipline as tests/test_multidevice.py)
# ---------------------------------------------------------------------------


def test_fused_shardmap_update_routes_duplicates_across_shards():
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import get_smoke_config
from repro.core.dlrm import dlrm_param_specs
from repro.core.embedding import EmbeddingBagCollection
from repro.data.synthetic import make_dlrm_batch
from repro.nn.params import init_params
from repro.optim import adagrad
from repro.launch.mesh import make_test_mesh
from repro.train.steps import build_dlrm_train_step, dlrm_init_state

cfg = dataclasses.replace(get_smoke_config("dlrm-m1"),
                          placement="row_wise", lookup_impl="psum")
mesh = make_test_mesh((2, 4), ("data", "model"))
ebc = EmbeddingBagCollection.build(cfg, n_shards=4)
params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(0))
opt = adagrad(0.05)
state = dlrm_init_state(ebc, opt, params)
raw = make_dlrm_batch(cfg, 16)
idx = np.array(ebc.offset_indices(jnp.asarray(raw["idx"])))
hot = int(idx[idx >= 0][0])
idx[:, 0, 0] = hot      # same row in EVERY example: every data shard must
                        # contribute to one row's aggregated gradient
batch = {"dense": jnp.asarray(raw["dense"]), "idx": jnp.asarray(idx),
         "label": jnp.asarray(raw["label"])}
with mesh:
    # fused shard_map PS aggregation (psum) vs the pjit dense-scatter path
    p1, s1, m1 = jax.jit(build_dlrm_train_step(cfg, ebc, opt))(
        params, state, batch, jnp.asarray(0, jnp.int32))
    cfg_ref = dataclasses.replace(cfg, lookup_impl="gather")
    p2, s2, m2 = jax.jit(build_dlrm_train_step(cfg_ref, ebc, opt))(
        params, state, batch, jnp.asarray(0, jnp.int32))
np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
np.testing.assert_allclose(np.asarray(p1["emb"]["mega"]),
                           np.asarray(p2["emb"]["mega"]),
                           rtol=1e-4, atol=1e-5)
np.testing.assert_allclose(np.asarray(s1["accum"]), np.asarray(s2["accum"]),
                           rtol=1e-4, atol=1e-5)
# the planted row really aggregated across shards: its accumulator moved
assert float(s1["accum"][hot]) > 0.0
print("FUSED_SHARDMAP_OK")
"""
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=500)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "FUSED_SHARDMAP_OK" in out.stdout


# ---------------------------------------------------------------------------
# acceptance: intermediate-bytes accounting
# ---------------------------------------------------------------------------


def test_sparse_backward_traffic_reduction_exceeds_truncation():
    """ISSUE acceptance: >= L x reduction in sparse-backward intermediate
    bytes for a truncation-32 config (the m3/prod shape)."""
    t = sparse_backward_traffic(4096, 127, 32, 128)
    assert t["reduction"] >= 32
    # and the bench shape emitted by kernels_bench
    t2 = sparse_backward_traffic(256, 8, 32, 128)
    assert t2["reduction"] >= 32
    # sanity: legacy counts the three (B*F*L, D) fp32 intermediates
    n = 4096 * 127 * 32
    assert t["legacy_bytes"] == pytest.approx(3 * n * 128 * 4)
    assert t["fused_bytes"] == pytest.approx((3 * n + 1) * 4)
