import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, model, reference
from bench.tests.conftest import ROOT


@pytest.mark.parametrize("name", ["dlrm-m2", "dlrm-m3"])
def test_forward_flops_match_xla_count(name):
    """The benchmark's forward FLOPs per example equal what XLA counts for
    the reference forward at the configuration's widths (elementwise work
    aside, which XLA counts and the model FLOPs leave out)."""
    cfg = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    b, f, d = 8, cfg["n_sparse_features"], cfg["embed_dim"]
    dims_b, dims_t = model.mlp_dims(cfg)
    dense = {"bottom": [{"w": jnp.zeros(s), "b": jnp.zeros(s[1])}
                        for s in dims_b],
             "top": [{"w": jnp.zeros(s), "b": jnp.zeros(s[1])}
                     for s in dims_t]}
    fwd = jax.jit(lambda dp, x, p: reference.logits(dp, x, p, "highest"))
    cost = fwd.lower(dense, jnp.zeros((b, cfg["n_dense_features"])),
                     jnp.zeros((b, f, d))).compile().cost_analysis()
    counted = cost["flops"] / b
    model_flops = flops.mlp_flops(cfg) + flops.interaction_flops(cfg)
    # XLA counts the whole (F+1)^2 Gram matrix and the biases/ReLUs: the
    # model FLOPs are the lower-triangle share of it and the products
    gram = 2 * (f + 1) ** 2 * d
    assert model_flops <= counted <= model_flops + gram + 4 * sum(
        o for _, o in dims_b + dims_t)
    assert flops.train_flops_per_example(cfg) == 3 * model_flops


def test_m2_counts_by_hand():
    cfg = json.loads((ROOT / "bench/configs/dlrm-m2.json").read_text())
    macs = 504 * 1024 + 1024 * 64 + 155 * 1024 + 1024 * 1024 + 1024 * 512 \
        + 512 * 1
    assert flops.mlp_flops(cfg) == 2 * macs
    assert flops.interaction_flops(cfg) == 2 * 91 * 64
    fl, nbytes = flops.interaction_work(cfg, 4096)
    assert fl == 4096 * 2 * 91 * 64
    assert nbytes == 4096 * (14 * 64 + 91) * 4
    assert flops.gather_bytes(cfg, 10) == 10 * (2 * 64 * 4 + 4)
    assert flops.sparse_update_bytes(cfg, 10) == 10 * (3 * 256 + 8 + 4)


def test_peaks_table_is_keyed_by_device_kind():
    v5e = flops.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("cpu")
    t, bound = flops.least_seconds(197e12, 0, v5e)
    assert (t, bound) == (1.0, "compute")
    t, bound = flops.least_seconds(0, 819e9, v5e)
    assert (np.isclose(t, 1.0), bound) == (True, "memory")
