"""Per-kernel validation: Pallas kernel bodies (interpret=True) vs the
pure-jnp oracles, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return {"rtol": 2e-2, "atol": 2e-2} if dtype == jnp.bfloat16 else \
        {"rtol": 1e-5, "atol": 1e-5}

# ---------------------------------------------------------------------------
# embedding_bag
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("h,d,b,lk", [
    (64, 8, 4, 3),        # tiny
    (97, 48, 16, 7),      # non-128 d, odd sizes
    (257, 128, 8, 32),    # lane-aligned d, truncation-sized lk
    (33, 200, 5, 1),      # single lookup, d > 128
])
@pytest.mark.parametrize("mode", ["sum", "mean"])
def test_embedding_bag_kernel_matches_ref(rng, h, d, b, lk, mode, dtype):
    table = jnp.asarray(rng.randn(h, d), dtype)
    idx = jnp.asarray(rng.randint(-1, h, size=(b, lk)), jnp.int32)
    out_k = ops.embedding_bag(table, idx, mode, None, True)
    out_r = ref.embedding_bag_ref(table, idx, mode)
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), **_tol(dtype))


def test_embedding_bag_all_padding(rng):
    table = jnp.asarray(rng.randn(10, 16), jnp.float32)
    idx = jnp.full((3, 4), -1, jnp.int32)
    out = ops.embedding_bag(table, idx, "sum", None, True)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_embedding_bag_grad_matches_ref(rng):
    table = jnp.asarray(rng.randn(50, 24), jnp.float32)
    idx = jnp.asarray(rng.randint(-1, 50, size=(8, 5)), jnp.int32)
    g = jnp.asarray(rng.randn(8, 24), jnp.float32)

    def f(t):
        return (ops.embedding_bag(t, idx, "sum", False, False) * g).sum()

    def fr(t):
        return (ref.embedding_bag_ref(t, idx, "sum") * g).sum()

    np.testing.assert_allclose(jax.grad(f)(table), jax.grad(fr)(table),
                               rtol=1e-5, atol=1e-5)

# ---------------------------------------------------------------------------
# dot_interaction
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("b,f,d", [
    (8, 4, 16), (8, 11, 33), (16, 27, 64), (4, 8, 128),
])
def test_dot_interaction_kernel_matches_ref(rng, b, f, d, dtype):
    z = jnp.asarray(rng.randn(b, f, d), dtype)
    out_k = ops.dot_interaction(z, 4, None, True)
    out_r = ref.dot_interaction_ref(z)
    assert out_k.shape == (b, f * (f - 1) // 2)
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=5e-1 if dtype == jnp.bfloat16 else 1e-4)


def test_dot_interaction_grad(rng):
    z = jnp.asarray(rng.randn(4, 6, 12), jnp.float32)
    gk = jax.grad(lambda z: (ops.dot_interaction(z, 4, False, False) ** 2)
                  .sum())(z)
    gr = jax.grad(lambda z: (ref.dot_interaction_ref(z) ** 2).sum())(z)
    np.testing.assert_allclose(gk, gr, rtol=1e-4, atol=1e-4)

# ---------------------------------------------------------------------------
# rowwise_adagrad
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("h,d,n", [(64, 8, 16), (97, 48, 23), (128, 64, 64)])
def test_rowwise_adagrad_kernel_matches_ref(rng, h, d, n):
    table = jnp.asarray(rng.randn(h, d), jnp.float32)
    accum = jnp.asarray(np.abs(rng.randn(h)), jnp.float32)
    idx = jnp.asarray(rng.randint(-1, h, size=(n,)), jnp.int32)
    grads = jnp.asarray(rng.randn(n, d), jnp.float32)
    tk, ak = ops.rowwise_adagrad_update(table, accum, idx, grads, 0.05,
                                        1e-8, None, True)
    tr, ar = ref.rowwise_adagrad_ref(table, accum, idx, grads, 0.05, 1e-8)
    np.testing.assert_allclose(ak, ar, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(tk, tr, rtol=1e-5, atol=1e-6)


def test_rowwise_adagrad_dedup_semantics(rng):
    """Duplicate rows must be aggregated BEFORE the update (one rsqrt), not
    applied per-duplicate — the sync replacement for HogWild (DESIGN 2)."""
    table = jnp.zeros((4, 8), jnp.float32)
    accum = jnp.zeros((4,), jnp.float32)
    g = jnp.ones((2, 8), jnp.float32)
    idx = jnp.asarray([2, 2], jnp.int32)
    t1, a1 = ref.rowwise_adagrad_ref(table, accum, idx, g, 1.0, 0.0)
    # aggregated grad = 2 -> accum = 4, step = 2/sqrt(4) = 1
    np.testing.assert_allclose(a1[2], 4.0)
    np.testing.assert_allclose(t1[2], -1.0 * jnp.ones(8), rtol=1e-6)


def test_dedup_grads_ref_aggregates_duplicates(rng):
    idx = jnp.asarray([5, 3, 5, -1, 3, 7], jnp.int32)
    grads = jnp.asarray(np.arange(6 * 2).reshape(6, 2), jnp.float32)
    uniq, gsum = ref.dedup_grads_ref(idx, grads, 10)
    got = {int(u): np.asarray(gsum[i]) for i, u in enumerate(np.asarray(uniq))
           if u >= 0}
    assert sorted(got) == [3, 5, 7]
    np.testing.assert_allclose(got[5], np.asarray(grads[0] + grads[2]))
    np.testing.assert_allclose(got[3], np.asarray(grads[1] + grads[4]))
    np.testing.assert_allclose(got[7], np.asarray(grads[5]))
    # every non-unique slot zeroed
    for i, u in enumerate(np.asarray(uniq)):
        if u < 0:
            np.testing.assert_array_equal(np.asarray(gsum[i]), 0.0)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("b,s,h,dh,bq,bk", [
    (2, 64, 3, 16, 16, 16),     # tiny, square blocks
    (1, 128, 2, 128, 32, 64),   # lane-aligned dh, rectangular blocks
    (2, 96, 2, 40, 32, 32),     # dh and seq need padding
])
def test_flash_attention_kernel_matches_ref(rng, b, s, h, dh, bq, bk, dtype):
    q = jnp.asarray(rng.randn(b, s, h, dh) * 0.5, dtype)
    k = jnp.asarray(rng.randn(b, s, h, dh) * 0.5, dtype)
    v = jnp.asarray(rng.randn(b, s, h, dh), dtype)
    out = ops.flash_attention(q, k, v, block_q=bq, block_k=bk, causal=True,
                              use_kernel=None, interpret=True)
    r = ref.flash_attention_ref(q.swapaxes(1, 2), k.swapaxes(1, 2),
                                v.swapaxes(1, 2), True).swapaxes(1, 2)
    tol = {"rtol": 3e-2, "atol": 3e-2} if dtype == jnp.bfloat16 else \
        {"rtol": 2e-4, "atol": 2e-4}
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(r, np.float32), **tol)


def test_flash_attention_is_causal(rng):
    b, s, h, dh = 1, 64, 2, 16
    q = jnp.asarray(rng.randn(b, s, h, dh), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, dh), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, dh), jnp.float32)
    base = ops.flash_attention(q, k, v, 16, 16, True, None, True)
    k2 = k.at[:, 40:].set(77.0)
    v2 = v.at[:, 40:].set(-77.0)
    pert = ops.flash_attention(q, k2, v2, 16, 16, True, None, True)
    np.testing.assert_allclose(np.asarray(base[:, :40]),
                               np.asarray(pert[:, :40]), rtol=1e-5,
                               atol=1e-5)
