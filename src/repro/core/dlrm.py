"""The DLRM model (paper Fig. 3) and its split dense/sparse training step.

Architecture: bottom MLP over dense features -> EmbeddingBagCollection over
sparse features -> feature interaction -> top MLP -> sigmoid CTR logit.

The train step mirrors the paper's production split (Fig. 4): dense params
(MLPs) are data-parallel and optimized with (dense) AdaGrad; the embedding
mega table is model-parallel per the PlacementPlan and optimized with
row-wise AdaGrad applied to DEDUPLICATED per-lookup gradients. Gradients for
the mega table are never materialized densely: autodiff runs with the pooled
embeddings as an explicit leaf, and `per_lookup_grads` + the rowwise-adagrad
path consume (indices, pooled-grad) directly — the PS "gradient aggregation"
of section VII.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import DLRMConfig
from repro.core.embedding import EmbeddingBagCollection
from repro.core.interaction import interact, interaction_dim
from repro.nn.layers import linear, linear_specs
from repro.tracing import scope

# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def _mlp_specs(dims, in_dim: int, in_ax: str, out_ax: str):
    specs, d = [], in_dim
    for i, width in enumerate(dims):
        # alternate logical axes so consecutive layers shard on
        # opposite sides (megatron-style f/g pairing)
        a_in = in_ax if i % 2 == 0 else out_ax
        a_out = out_ax if i % 2 == 0 else in_ax
        specs.append(linear_specs(d, width, a_in, a_out, bias=True))
        d = width
    return specs, d


def dlrm_param_specs(cfg: DLRMConfig, ebc: EmbeddingBagCollection) -> dict:
    """ParamSpec tree for the full DLRM: bottom/top MLPs + the embedding
    collection's mega table."""
    bottom, bot_out = _mlp_specs(cfg.bottom_mlp, cfg.n_dense_features,
                                 None, "dense_ff")
    assert bot_out == cfg.embed_dim, (
        f"bottom MLP must end at embed_dim: {bot_out} != {cfg.embed_dim}")
    top_in = interaction_dim(cfg.n_sparse_features, cfg.embed_dim,
                             cfg.interaction)
    top, top_out = _mlp_specs(cfg.top_mlp, top_in, None, "dense_ff")
    assert top_out == 1
    return {
        "bottom": bottom,
        "top": top,
        "emb": ebc.param_specs(),
    }

# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _mlp_apply(layers, x, dtype):
    for i, p in enumerate(layers):
        x = linear(p, x, dtype)
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


def dlrm_forward_dense(params: dict, dense_x: jax.Array, pooled: jax.Array,
                       cfg: DLRMConfig, interpret: bool = False,
                       use_kernel: bool | None = None) -> jax.Array:
    """Everything downstream of the embedding lookup (autodiff runs here).

    dense_x: (B, n_dense); pooled: (B, F, d). Returns (B,) logits.
    `use_kernel=False` takes the jnp reference interaction on any backend.
    """
    dtype = jnp.float32 if cfg.compute_dtype == "float32" else jnp.bfloat16
    with scope("bottom_mlp"):
        bot = _mlp_apply(params["bottom"], dense_x.astype(dtype), dtype)
    with scope("interaction"):
        top_in = interact(bot, pooled.astype(dtype), cfg.interaction,
                          use_kernel=use_kernel, interpret=interpret)
    with scope("top_mlp"):
        logit = _mlp_apply(params["top"], top_in, dtype)
    return logit[..., 0].astype(jnp.float32)


def _lookup(params, batch, cfg, ebc, rules, use_kernel=None):
    if cfg.lookup_impl == "psum":
        from repro.nn.sharding import _live_mesh
        mesh = _live_mesh()
        if mesh is not None:
            return ebc.lookup_pooled_psum(params["emb"], batch["idx"], mesh,
                                          use_kernel=use_kernel)
    # a batch-attached bucketing plan (data.sparse_plan_hook, or the cached
    # steps' slot-relabelled copy) dedups the forward gather — the plan is
    # built once per batch and shared with the fused backward and the
    # cached tiers' miss planning (docs/embedding_forward.md)
    from repro.kernels.sparse_plan import plan_from_batch
    return ebc.lookup(params["emb"], batch["idx"], rules,
                      plan=plan_from_batch(batch), use_kernel=use_kernel)


def dlrm_forward(params: dict, batch: dict, cfg: DLRMConfig,
                 ebc: EmbeddingBagCollection,
                 interpret: bool = False, rules=None) -> jax.Array:
    """Full forward pass: embedding lookup + dense tower -> logits."""
    pooled = _lookup(params, batch, cfg, ebc, rules)
    return dlrm_forward_dense(params, batch["dense"], pooled, cfg, interpret)


def dlrm_loss(params: dict, batch: dict, cfg: DLRMConfig,
              ebc: EmbeddingBagCollection,
              interpret: bool = False, rules=None) -> jax.Array:
    """Binary cross-entropy (CTR) — the paper's NE metric is normalized BCE."""
    logits = dlrm_forward(params, batch, cfg, ebc, interpret, rules)
    return _bce(logits, batch["label"])


def _bce(logits: jax.Array, labels: jax.Array) -> jax.Array:
    with scope("loss"):
        return jnp.mean(
            jnp.maximum(logits, 0) - logits * labels
            + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def normalized_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """The paper's model-quality metric (section VI-C): BCE normalized by the
    entropy of the base CTR."""
    bce = _bce(logits, labels)
    p = jnp.clip(jnp.mean(labels), 1e-6, 1 - 1e-6)
    base = -(p * jnp.log(p) + (1 - p) * jnp.log(1 - p))
    return bce / base

# ---------------------------------------------------------------------------
# split dense/sparse gradient computation
# ---------------------------------------------------------------------------


def dlrm_grads(params: dict, batch: dict, cfg: DLRMConfig,
               ebc: EmbeddingBagCollection, interpret: bool = False,
               rules=None, use_kernel: bool | None = None
               ) -> tuple[jax.Array, dict, tuple[jax.Array, jax.Array]]:
    """Returns (loss, dense_grads, (idx (B,F,L), pooled_grads (B,F,d))).

    The mega table only ever sees sparse gradients: autodiff treats the
    pooled embeddings as a leaf input, and sum-pooling lets every valid
    lookup slot inherit its bag's gradient.
    """
    pooled = _lookup(params, batch, cfg, ebc, rules, use_kernel)
    dense_params = {"bottom": params["bottom"], "top": params["top"]}

    def loss_fn(dp, pl_):
        """BCE loss over the dense tower, pooled embeddings as a leaf."""
        logits = dlrm_forward_dense({**dp, "emb": None}, batch["dense"],
                                    pl_, cfg, interpret, use_kernel)
        return _bce(logits, batch["label"])

    loss, (g_dense, g_pooled) = jax.value_and_grad(
        loss_fn, argnums=(0, 1))(dense_params, pooled)
    return loss, g_dense, (batch["idx"], g_pooled.astype(jnp.float32))
