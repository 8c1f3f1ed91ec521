"""jit'd public wrappers around the Pallas kernels.

Responsibilities:
  * pad the embedding dim to the TPU lane width (128) and the feature count
    to the sublane width (8) before invoking kernels, un-pad after;
  * dispatch: real Pallas kernel on TPU, `interpret=True` kernel body when
    explicitly requested (tests), pure-jnp oracle otherwise (CPU runtime);
  * differentiability: embedding_bag carries a custom VJP (scatter-add);
    dot_interaction is natively differentiable through the oracle and uses
    the kernel only for the forward pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.dot_interaction import dot_interaction_kernel
from repro.kernels.embedding_bag import (dedup_embedding_bag_kernel,
                                         embedding_bag_kernel)
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.sparse_plan import SparsePlan, build_sparse_plan
from repro.kernels.sparse_update import rowwise_adagrad_apply
from repro.tracing import scope

LANE = 128
SUBLANE = 8


def use_pallas(force: bool | None) -> bool:
    """Kernel dispatch shared by every wrapper: `force` when given, else
    the Pallas kernels exactly when the default backend is a TPU."""
    if force is not None:
        return force
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)

# ---------------------------------------------------------------------------
# embedding_bag
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def embedding_bag(table: jax.Array, indices: jax.Array, mode: str = "sum",
                  use_kernel: bool | None = None,
                  interpret: bool = False) -> jax.Array:
    """Pooled multi-hot lookup. table: (H, D); indices: (B, L) int32, -1 pads.
    Returns (B, D)."""
    if use_pallas(use_kernel) or interpret:
        d = table.shape[1]
        tp = _pad_to(table, LANE, 1)
        out = embedding_bag_kernel(tp, indices, mode=mode,
                                   interpret=interpret)
        return out[:, :d]
    return ref.embedding_bag_ref(table, indices, mode)


def _bag_fwd(table, indices, mode, use_kernel, interpret):
    out = embedding_bag(table, indices, mode, use_kernel, interpret)
    return out, (indices, table.shape[0],
                 (indices >= 0).sum(1) if mode == "mean" else None)


def _bag_bwd(mode, use_kernel, interpret, res, g):
    indices, h, cnt = res
    b, lk = indices.shape
    gf = g.astype(jnp.float32)
    if mode == "mean":
        gf = gf / jnp.maximum(cnt, 1)[:, None]
    valid = indices >= 0
    idx = jnp.where(valid, indices, h)
    gexp = jnp.broadcast_to(gf[:, None, :], (b, lk, g.shape[-1]))
    gtab = jnp.zeros((h + 1, g.shape[-1]), jnp.float32).at[idx.reshape(-1)] \
        .add(jnp.where(valid.reshape(-1)[:, None], gexp.reshape(b * lk, -1),
                       0.0))[:h]
    return gtab.astype(g.dtype), None


embedding_bag.defvjp(_bag_fwd, _bag_bwd)

# ---------------------------------------------------------------------------
# dedup_embedding_bag — the plan-shared forward (docs/embedding_forward.md)
# ---------------------------------------------------------------------------


def dedup_embedding_bag(table: jax.Array, indices: jax.Array,
                        plan: SparsePlan | None = None, mode: str = "sum",
                        use_kernel: bool | None = None,
                        interpret: bool = False) -> jax.Array:
    """Deduplicated pooled multi-hot lookup: the table is gathered once per
    plan entry (unique row), not once per lookup slot.

    table: (H, D); indices: (B, L) int32, -1 pads; plan: SparsePlan built
    over indices' FLAT stream (bag = slot // L) — e.g. the reader thread's
    `data.sparse_plan_hook` product, possibly capacity-trimmed; built on
    device when None. Returns (B, D).

    The jnp fallback is BIT-EXACT vs `embedding_bag`/`ref.embedding_bag_ref`
    (the forward's acceptance contract); the Pallas kernel expands bags in
    the plan's CSR order and is tested allclose like every kernel body.
    """
    if plan is None:
        plan = build_sparse_plan(indices.reshape(-1),
                                 lookups_per_bag=indices.shape[1])
    return _dedup_bag(table, indices, plan.unique_rows, plan.bag_offsets,
                      plan.bag_ids, mode, use_kernel, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _dedup_bag(table, indices, rows, offs, bags, mode, use_kernel,
               interpret):
    if use_pallas(use_kernel) or interpret:
        d = table.shape[1]
        tp = _pad_to(table, LANE, 1)
        out = dedup_embedding_bag_kernel(tp, rows, offs, bags,
                                         n_bags=indices.shape[0],
                                         interpret=interpret)[:, :d]
        if mode == "mean":
            cnt = jnp.maximum((indices >= 0).sum(1, keepdims=True), 1)
            out = out / cnt
        return out.astype(table.dtype)
    return ref.dedup_embedding_bag_ref(table, indices, rows, mode)


def _dedup_fwd(table, indices, rows, offs, bags, mode, use_kernel,
               interpret):
    out = _dedup_bag(table, indices, rows, offs, bags, mode, use_kernel,
                     interpret)
    # identical residual layout to embedding_bag's VJP — same backward
    return out, (indices, table.shape[0],
                 (indices >= 0).sum(1) if mode == "mean" else None)


def _dedup_bwd(mode, use_kernel, interpret, res, g):
    gtab, _ = _bag_bwd(mode, use_kernel, interpret, res, g)
    return gtab, None, None, None, None


_dedup_bag.defvjp(_dedup_fwd, _dedup_bwd)

# ---------------------------------------------------------------------------
# dot_interaction
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def dot_interaction(z: jax.Array, tile_b: int = 8,
                    use_kernel: bool | None = None,
                    interpret: bool = False) -> jax.Array:
    """z: (B, F, D) -> (B, F*(F-1)//2) strict-lower-triangle pairwise dots."""
    if use_pallas(use_kernel) or interpret:
        b, f, d = z.shape
        zp = _pad_to(_pad_to(z, LANE, 2), SUBLANE, 1)
        tb = tile_b if b % tile_b == 0 else 1
        s = dot_interaction_kernel(zp, tile_b=tb, interpret=interpret)
        rows, cols = np.tril_indices(f, -1)     # static pack, fuses in XLA
        return s[:, rows, cols]
    return ref.dot_interaction_ref(z)


def _dot_fwd(z, tile_b, use_kernel, interpret):
    return dot_interaction(z, tile_b, use_kernel, interpret), z


def _dot_bwd(tile_b, use_kernel, interpret, z, g):
    b, f, d = z.shape
    rows, cols = np.tril_indices(f, -1)
    s_bar = jnp.zeros((b, f, f), jnp.float32)
    s_bar = s_bar.at[:, rows, cols].set(g.astype(jnp.float32))
    s_bar = s_bar + jnp.swapaxes(s_bar, 1, 2)   # d(zi.zj) hits both rows
    gz = jnp.einsum("bfg,bgd->bfd", s_bar, z.astype(jnp.float32))
    return (gz.astype(z.dtype),)


dot_interaction.defvjp(_dot_fwd, _dot_bwd)

# ---------------------------------------------------------------------------
# rowwise_adagrad (not differentiated through — it IS the optimizer)
# ---------------------------------------------------------------------------


def rowwise_adagrad_update(table: jax.Array, accum: jax.Array,
                           indices: jax.Array, grads: jax.Array,
                           lr, eps: float = 1e-8,
                           use_kernel: bool | None = None,
                           interpret: bool = False
                           ) -> tuple[jax.Array, jax.Array]:
    """Apply deduplicated row-wise AdaGrad (legacy two-pass layout).

    table: (H, D); accum: (H,) fp32; indices: (N,) int32 per-lookup rows
    (-1 pads); grads: (N, D) per-lookup gradients. Returns (table', accum').

    Prefer `fused_sparse_backward` where the caller holds (idx, pooled
    grads): it skips the per-lookup broadcast this signature forces.
    """
    if use_pallas(use_kernel) or interpret:
        uniq, gsum = ref.dedup_grads_ref(indices, grads, table.shape[0])
        return rowwise_adagrad_apply(table, accum, uniq, gsum, lr, eps,
                                     interpret)
    return ref.rowwise_adagrad_ref(table, accum, indices, grads, lr, eps)


def fused_sparse_backward(table: jax.Array, accum: jax.Array,
                          idx: jax.Array | None, pooled_grad: jax.Array,
                          lr, eps: float = 1e-8,
                          plan: SparsePlan | None = None,
                          use_kernel: bool | None = None,
                          interpret: bool = False
                          ) -> tuple[jax.Array, jax.Array]:
    """One-pass sparse backward + row-wise AdaGrad from POOLED gradients —
    per-lookup gradients are never materialized (docs/sparse_optimizer.md).

    table: (H, D); accum: (H,) fp32; idx: (B, F, L) int32 rows (-1 pads) —
    may be None when `plan` is given; pooled_grad: (B, F, D) bag gradients
    straight from autodiff. `plan` short-circuits the on-device bucketing
    with one built ahead of time (`data.sparse_plan_hook` builds batch k+1's
    in the reader thread while batch k computes). Returns (table', accum').

    The jnp path is bit-identical to `rowwise_adagrad_update` fed the
    legacy broadcast layout (same per-row accumulation order — the
    planner's stable sort), minus the (B*F*L, D) intermediates. The kernel
    path aggregates with the same `ref.bag_grad_sums` and applies the
    update in place (kernels/sparse_update.py).
    """
    d = table.shape[1]
    if plan is None:
        assert idx is not None, "need idx to build a SparsePlan"
        with scope("sparse_plan"):
            plan = build_sparse_plan(idx)
    pooled2 = pooled_grad.reshape(-1, d)
    if use_pallas(use_kernel) or interpret:
        with scope("bag_grad_sums"):
            gsum = ref.bag_grad_sums(plan.unique_rows, plan.bag_offsets,
                                     plan.bag_ids, pooled2)
        with scope("rowwise_adagrad"):
            return rowwise_adagrad_apply(table, accum, plan.unique_rows,
                                         gsum, lr, eps, interpret)
    return ref.fused_bag_backward_adagrad_ref(
        table, accum, plan.unique_rows, plan.bag_offsets, plan.bag_ids,
        pooled2, lr, eps)


def fused_sparse_backward_segments(table: jax.Array, accum: jax.Array,
                                   seg_rows: jax.Array,
                                   seg_offsets: jax.Array,
                                   bag_ids: jax.Array,
                                   pooled_grad: jax.Array, lr,
                                   seg_base: jax.Array | None = None,
                                   eps: float = 1e-8,
                                   use_kernel: bool | None = None,
                                   interpret: bool = False
                                   ) -> tuple[jax.Array, jax.Array]:
    """`fused_sparse_backward` over PER-OWNER SEGMENTS of one plan — the
    routed update of the multi-host cached tier (docs/cache.md): segment s
    covers the rows the s-th capacity shard owns, with SEGMENT-LOCAL row
    ids rebased by seg_base[s] (`kernels.sparse_plan.split_plan_by_owner`).

    seg_rows: (S, C) int32 -1-padded; seg_offsets: (S, C+1) int32 ABSOLUTE
    into bag_ids (N,); pooled_grad: (B, F, D) or (B*F, D); seg_base
    defaults to all-zero (segments already in table row space — the
    shard_map per-owner body, where `table` IS the owner's shard). Each
    covered row updates with bits identical to the unsegmented
    `fused_sparse_backward` (asserted in tests/test_cache_multihost.py).
    """
    d = table.shape[1]
    s = seg_rows.shape[0]
    if seg_base is None:
        seg_base = jnp.zeros((s,), jnp.int32)
    pooled2 = pooled_grad.reshape(-1, d)
    # segments are disjoint row ranges of one plan, so the flattened (rows
    # rebased, offsets kept absolute) view is itself a valid abs-offset
    # plan over the whole table
    rows_flat = jnp.where(seg_rows >= 0,
                          seg_rows + jnp.asarray(seg_base, jnp.int32)[:, None],
                          -1).reshape(-1)
    offs_flat = jnp.concatenate(
        [seg_offsets[:, :-1].reshape(-1), seg_offsets[-1:, -1]])
    if use_pallas(use_kernel) or interpret:
        with scope("bag_grad_sums"):
            gsum = ref.bag_grad_sums_abs(offs_flat, bag_ids, pooled2)
        with scope("rowwise_adagrad"):
            return rowwise_adagrad_apply(table, accum, rows_flat, gsum, lr,
                                         eps, interpret)
    return ref.fused_bag_backward_adagrad_abs_ref(
        table, accum, rows_flat, offs_flat, bag_ids, pooled2, lr, eps)

# ---------------------------------------------------------------------------
# flash_attention (forward; training uses the XLA blockwise fallback)
# ---------------------------------------------------------------------------


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    block_q: int = 128, block_k: int = 128,
                    causal: bool = True,
                    use_kernel: bool | None = None,
                    interpret: bool = False) -> jax.Array:
    """q, k, v: (b, s, h, dh) (layer-zoo layout). Pads dh to the lane width
    and s to the block size; padded KV rows are masked by causality."""
    if not (use_pallas(use_kernel) or interpret):
        from repro.kernels.ref import flash_attention_ref
        out = flash_attention_ref(q.swapaxes(1, 2), k.swapaxes(1, 2),
                                  v.swapaxes(1, 2), causal)
        return out.swapaxes(1, 2)
    assert causal, "kernel path masks seq padding via causality"
    b, s, h, dh = q.shape
    qt = _pad_to(_pad_to(q.swapaxes(1, 2), LANE, 3), block_q, 2)
    kt = _pad_to(_pad_to(k.swapaxes(1, 2), LANE, 3), block_k, 2)
    vt = _pad_to(_pad_to(v.swapaxes(1, 2), LANE, 3), block_k, 2)
    # dh padding changes softmax scale: kernel divides by sqrt(padded dh);
    # pre-scale q to compensate
    scale_fix = np.sqrt(qt.shape[-1] / dh).astype(np.float32)
    out = flash_attention_kernel(qt * scale_fix, kt, vt, block_q=block_q,
                                 block_k=block_k, causal=True,
                                 interpret=interpret)
    return out[:, :, :s, :dh].swapaxes(1, 2)
