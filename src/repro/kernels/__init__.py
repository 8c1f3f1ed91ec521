"""Pallas TPU kernels for the paper's compute hot-spots.

The paper (section III-A.2) identifies irregular embedding-vector access as the
throughput limiter of recommendation training, and section VII notes prior
near-memory accelerators are "not optimized for gradient aggregation". The
kernels here cover exactly that path:

  embedding_bag    fused multi-hot gather + pooling (fwd) — the EMB lookup,
                   legacy one-row-read-per-slot AND the plan-driven dedup'd
                   design (each unique row leaves HBM once per batch)
  dot_interaction  pairwise-dot feature interaction (section III-A.3), MXU-shaped
  sparse_update    row-wise AdaGrad applied in place to a batch's unique
                   rows (gradients aggregated by ref.bag_grad_sums over
                   the sparse_plan.py CSR bucketing) — the EMB update
  row_move         one in-place row-copy kernel on the tables' (d, rows)
                   view: the TPU embedding gather, and every pass of
  cache_ops        the cached tier's exchange, fetch and commit
  flash_attention  causal streaming attention with static triangle
                   skipping — the prefill_32k hot spot of the LM family

Each kernel ships an `ops.py` jit wrapper and a pure-jnp oracle in `ref.py`;
tests sweep shapes/dtypes with interpret=True. On non-TPU backends the
wrappers transparently fall back to the oracle so the full system trains on
CPU; `interpret=True` executes the real kernel body for validation.
"""
from repro.kernels.cache_ops import cache_exchange, lfu_touch  # noqa: F401
from repro.kernels.ops import (  # noqa: F401
    dedup_embedding_bag,
    dot_interaction,
    embedding_bag,
    flash_attention,
    fused_sparse_backward,
    rowwise_adagrad_update,
)
from repro.kernels.sparse_plan import (  # noqa: F401
    SparsePlan,
    build_sparse_plan,
    build_sparse_plan_host,
    host_plan_from_batch,
    plan_from_batch,
)
