"""Compile the chip smoke's Pallas kernels and its one-chip dlrm-m2 train
step for a described TPU v5e (no chip needed): the checks interpret mode
cannot make — Mosaic's tiling rules, SMEM/VMEM limits and the 16 GB of
device memory — at the shapes `chip_smoke.py` runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.core.dlrm import dlrm_param_specs
from repro.core.embedding import EmbeddingBagCollection
from repro.kernels import cache_ops, ops
from repro.launch.train import dlrm_train_step
from repro.nn.params import abstract_params
from repro.optim.optimizers import adagrad
from repro.train.steps import dlrm_init_state

HBM_BYTES = 16e9            # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)


@pytest.fixture(scope="module")
def m2():
    """The chip smoke's one-chip dlrm-m2: published widths, scaled heights,
    with the train phase's plan and the serve phase's cache plan."""
    _, cfg = chip_smoke.scaled_m2(chip_smoke.TABLE_SCALE)
    train = EmbeddingBagCollection.build(cfg, n_shards=1)
    cached = EmbeddingBagCollection.build(cfg, n_shards=1,
                                          strategy="cached_host")
    return cfg, train, cached.plan.cache_rows


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check(compiled, kernel: str | None):
    """Fits one chip; the named Pallas kernel is compiled in (not
    interpreted, not replaced by an XLA op). Returns the memory analysis."""
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total <= HBM_BYTES, total
    if kernel is not None:
        assert kernel in chip_smoke.kernels_in(compiled), kernel
    return mem


def test_cache_row_kernels_compile_at_serve_shapes(one_chip, m2):
    """The serve phase's exchange (and the split fetch/commit) at the
    scaled table height with the plan's cache: the (rows, 64) tables move
    in place — no whole-table copy in temporaries."""
    cfg, ebc, cache_rows = m2
    r, c, d = ebc.plan.total_rows, cache_rows, cfg.embed_dim
    n = cache_ops.ROW_BLOCK
    s = lambda *shape, dt=jnp.float32: _spec(one_chip, shape, dt)  # noqa: E731
    i = lambda: s(n, dt=jnp.int32)                                # noqa: E731
    table_bytes = r * d * 4
    mem = _check(cache_ops._exchange_kernel_jit.lower(
        s(r, d), s(c, d), s(r), s(c), s(c), i(), i(), i(), s(n),
        interpret=False).compile(), "move_rows")
    assert mem.temp_size_in_bytes < table_bytes / 8
    assert mem.alias_size_in_bytes >= 2 * table_bytes
    mem = _check(cache_ops._commit_kernel_jit.lower(
        s(r, d), s(c, d), s(r), s(c), s(n, d), s(n), i(), i(), i(), i(),
        interpret=False).compile(), "move_rows")
    assert mem.temp_size_in_bytes < table_bytes / 8
    mem = _check(cache_ops._fetch_kernel_jit.lower(
        s(r, d), s(r), i(), interpret=False).compile(), "move_rows")
    assert mem.temp_size_in_bytes < table_bytes / 8


def test_sparse_apply_kernel_compiles_at_train_shapes(one_chip, m2):
    """The unique-row AdaGrad apply at B=4096: the plan (B*F*L = 1.7M
    entries) streams through SMEM a block at a time, the table updates in
    place."""
    cfg, ebc, _ = m2
    b, f, lk, d = (chip_smoke.TRAIN_BATCH, cfg.n_sparse_features,
                   cfg.truncation, cfg.embed_dim)
    h = ebc.plan.total_rows

    def apply(table, accum, idx, pooled):
        return ops.fused_sparse_backward(table, accum, idx, pooled, 0.05,
                                         use_kernel=True)

    mem = _check(jax.jit(apply, donate_argnums=(0, 1)).lower(
        _spec(one_chip, (h, d)), _spec(one_chip, (h,)),
        _spec(one_chip, (b, f, lk), jnp.int32),
        _spec(one_chip, (b, f, d))).compile(), "rowwise_adagrad_apply")
    assert mem.alias_size_in_bytes >= h * d * 4
    assert mem.temp_size_in_bytes < h * d * 4


def test_dot_interaction_kernel_compiles_at_train_shapes(one_chip, m2):
    cfg, _, _ = m2
    z = _spec(one_chip, (chip_smoke.TRAIN_BATCH, cfg.n_sparse_features + 1,
                         cfg.embed_dim))
    _check(jax.jit(lambda z: ops.dot_interaction(z, use_kernel=True))
           .lower(z).compile(), "dot_interaction")


@pytest.fixture(scope="module")
def m2_step(one_chip, m2):
    """The launcher's one-chip step (unique-row apply, params and state
    donated) at the chip smoke's size, every kernel branch taken,
    compiled."""
    cfg, ebc, _ = m2
    opt = adagrad(0.01)
    params = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                          abstract_params(dlrm_param_specs(cfg, ebc)))
    state = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                         jax.eval_shape(lambda p: dlrm_init_state(ebc, opt, p),
                                        params))
    b = chip_smoke.TRAIN_BATCH
    batch = {"dense": _spec(one_chip, (b, cfg.n_dense_features)),
             "idx": _spec(one_chip, (b, cfg.n_sparse_features,
                                     cfg.truncation), jnp.int32),
             "label": _spec(one_chip, (b,))}
    return dlrm_train_step(cfg, ebc, opt, use_kernel=True).lower(
        params, state, batch, _spec(one_chip, (), jnp.int32)).compile()


def test_m2_train_step_compiles_for_one_chip(m2, m2_step):
    """The step fits 16 GB and updates the table in place."""
    cfg, ebc, _ = m2
    mem = _check(m2_step, None)
    assert set(chip_smoke.KERNELS) <= chip_smoke.kernels_in(m2_step)
    table = ebc.plan.total_rows * cfg.embed_dim * 4
    assert mem.alias_size_in_bytes >= table
    assert mem.temp_size_in_bytes < table


def test_m2_train_step_sorts_the_ids_once(m2_step):
    """The forward and the backward each build the plan from the batch's
    ids, and XLA merges the two into one argsort; each lookup slot's
    position in the plan comes from one more sort (`embedding_remap`),
    not from a binary search per slot."""
    text = m2_step.as_text()
    sorts = re.findall(r' sort\(.*op_name="([^"]*)"', text)
    assert sum(n.endswith("jit(argsort)/sort") for n in sorts) == 1, sorts
    assert sum("/embedding_remap/" in n for n in sorts) == 1, sorts
    assert "searchsorted" not in text
