"""EmbeddingBagCollection: all sparse-feature tables of one model as a single
row-concatenated mega table + a PlacementPlan.

Lookup semantics (paper section III-A.2): each sparse feature is a multi-hot
index list of up to `truncation` entries; each entry fetches one d-vector;
vectors are sum-pooled per (example, feature). Index preprocessing (hashing
into [0, hash_size) and adding the table's row offset) happens in the data
pipeline; the collection consumes offset global indices with -1 padding.

Two lookup paths:
  * `lookup` — pure-jnp gather+pool with GLOBAL semantics: under pjit the
    XLA SPMD partitioner turns the gather-from-sharded-table into partial
    local gathers + an all-reduce over the `model` axis (the embedding
    "all-to-all" of the paper's PS architecture). Used for training and the
    dry-run (collectives must be visible to the roofline pass).
  * `lookup_local` — the Pallas embedding_bag kernel on one shard's rows;
    used inside shard_map on real TPUs and by serving.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DLRMConfig
from repro.core.placement import PlacementPlan, plan_placement
from repro.kernels import ops
from repro.kernels.row_move import gather_rows
from repro.kernels.sparse_plan import build_sparse_plan_with_slots
from repro.nn.params import ParamSpec
from repro.tracing import scope


def _row_taker(mega: jax.Array, idx: jax.Array, plan, use_kernel):
    """(slots, take) for the lookups of `idx` (B, F, L): `slots` has idx's
    shape, and take(flat_slots) -> the mega rows those slots look up.

    Without a plan, the slots are idx's rows and take gathers them from
    the table directly. With one, the table is read once per unique row
    into a compact slab that the slots index. On TPU with no plan handed
    in, one is built here from idx, and each slot's position in the slab
    comes from the plan's own sort (`build_sparse_plan_with_slots`); a
    plan handed in (a pipeline hook's, the cached steps' slot-relabelled
    one) was sorted elsewhere, so take finds each slot's row in it by a
    binary search over its sorted rows (`jnp.searchsorted`). On TPU the
    compact slab is read by the row-move kernel
    (kernels/row_move.gather_rows): an XLA gather from the table would
    first relayout all of it into a padded copy twice its size."""
    kernel = ops.use_pallas(use_kernel)
    slots = None
    if plan is None and kernel:
        with scope("sparse_plan"):
            plan, slots = build_sparse_plan_with_slots(idx)
    if plan is None:
        return idx, lambda ids: jnp.take(mega, jnp.maximum(ids, 0), axis=0)
    with scope("embedding_gather"):
        if kernel:
            compact = gather_rows(mega, plan.unique_rows)
        else:
            compact = jnp.take(mega, jnp.maximum(plan.unique_rows, 0),
                               axis=0)
    if slots is not None:
        return slots, lambda pos: jnp.take(compact, pos, axis=0)
    with scope("embedding_gather"):
        sent = jnp.where(plan.unique_rows >= 0, plan.unique_rows,
                         jnp.iinfo(jnp.int32).max)

    def take(ids):
        ids = jnp.maximum(ids, 0)
        with scope("embedding_remap"):
            pos = jnp.searchsorted(sent, ids)
        return jnp.take(compact, pos, axis=0)

    return idx, take


@dataclasses.dataclass(frozen=True)
class EmbeddingBagCollection:
    """All embedding tables fused into one (total_rows, d) mega table,
    looked up bag-pooled per feature under a placement plan."""

    cfg: DLRMConfig
    plan: PlacementPlan

    @classmethod
    def build(cls, cfg: DLRMConfig, n_shards: int,
              strategy: str | None = None,
              second_axis_size: int = 1,
              capacity_shards: int = 1) -> EmbeddingBagCollection:
        """Plan placement for cfg's tables and wrap it."""
        plan = plan_placement(
            cfg.hash_sizes, cfg.mean_lookups, cfg.embed_dim, n_shards,
            hbm_budget_bytes=cfg.hbm_budget_gb * 1e9,
            itemsize=4 if cfg.param_dtype == "float32" else 2,
            strategy=strategy or cfg.placement,
            second_axis_size=second_axis_size,
            capacity_shards=capacity_shards)
        return cls(cfg, plan)

    # -- params ------------------------------------------------------------

    def param_specs(self) -> dict:
        """The fused mega-table ParamSpec."""
        dt = jnp.float32 if self.cfg.param_dtype == "float32" else jnp.bfloat16
        return {"mega": ParamSpec(
            (self.plan.total_rows, self.cfg.embed_dim),
            ("hash", "table_dim"), dtype=dt, init="normal",
            scale=1.0 / np.sqrt(self.cfg.embed_dim))}

    def optimizer_specs(self) -> dict:
        """Row-wise AdaGrad second-moment accumulator."""
        return {"accum": ParamSpec((self.plan.total_rows,), ("hash",),
                                   dtype=jnp.float32, init="zeros")}

    def pspecs(self) -> dict:
        """Partition specs for the params, from the plan."""
        return {"mega": self.plan.pspec}

    def optimizer_pspecs(self) -> dict:
        """Partition specs for the optimizer state (row dim only)."""
        return {"accum": jax.sharding.PartitionSpec(*self.plan.pspec[:1])}

    # -- index preprocessing -----------------------------------------------

    def offset_indices(self, raw: jax.Array) -> jax.Array:
        """raw: (B, F, L) per-table indices in [0, hash_size_f) or -1 pad.
        Returns global mega-table rows (still -1 padded)."""
        off = jnp.asarray(self.plan.table_offsets, jnp.int32)
        out = raw + off[None, :, None]
        return jnp.where(raw >= 0, out, -1)

    # -- lookup ------------------------------------------------------------

    def lookup(self, params: dict, idx: jax.Array, rules=None,
               plan=None, use_kernel: bool | None = None) -> jax.Array:
        """idx: (B, F, L) offset global rows, -1 pads. Returns (B, F, d)
        sum-pooled embeddings. Pure-jnp global-semantics path: under pjit the
        gather from the model-sharded mega table lowers to local gathers +
        the cross-shard reduce — the paper's PS pull.

        `plan` (a kernels.SparsePlan over idx's flat stream, e.g. the one
        `data.sparse_plan_hook` attaches and `kernels.plan_from_batch`
        rehydrates) DEDUPLICATES the mega-table gather: the table is
        touched once per plan entry (its unique capacity U, not B*F*L) into
        a compact hot buffer, and every lookup slot then reads that buffer
        at its row's position, found by a binary search over the plan's
        sorted rows. On TPU, with no plan given, the lookup builds one from
        idx and reads each slot's position off that plan's own sort
        instead: no search. The pooling that follows is the SAME code
        either way, so the planned path is BIT-EXACT vs the plan-less one
        (asserted in tests/test_dedup_forward.py) — the forward half of
        the plan-once-used-thrice contract (docs/embedding_forward.md). On
        TPU the table rows are read by the row-move kernel
        (`use_kernel=False` keeps the XLA gather; see `_row_taker`)."""
        from repro.nn.sharding import shard_activation
        mega = params["mega"]
        b, f, lk = idx.shape
        slots, take = _row_taker(mega, idx, plan, use_kernel)

        def pool_one(_, xs):
            """Pool one feature's bags; scanned over the feature axis."""
            idx_f, slots_f = xs        # (b, lk) one feature's bags
            valid = idx_f >= 0
            rows = take(slots_f.reshape(-1))
            rows = rows.reshape(b, lk, -1)
            rows = jnp.where(valid[..., None], rows.astype(jnp.float32), 0.0)
            return None, rows.sum(axis=1).astype(mega.dtype)

        with scope("embedding_pool"):
            if f > 8:
                # scan over features: bounds the (b, lk, d) gather transient
                # to one feature at a time (m3 has 127 tables x 32 lookups)
                _, pooled = jax.lax.scan(
                    pool_one, None,
                    (jnp.swapaxes(idx, 0, 1), jnp.swapaxes(slots, 0, 1)))
                pooled = jnp.swapaxes(pooled, 0, 1)          # (b, f, d)
            else:
                valid = idx >= 0
                rows = take(slots.reshape(-1)).reshape(b, f, lk, -1)
                rows = jnp.where(valid[..., None],
                                 rows.astype(jnp.float32), 0.0)
                pooled = rows.sum(axis=2).astype(mega.dtype)
        return shard_activation(pooled, ("act_batch", None, None),
                                rules or {})

    def lookup_pooled_psum(self, params: dict, idx: jax.Array,
                           mesh, model_axis: str = "model",
                           use_kernel: bool | None = None) -> jax.Array:
        """shard_map lookup with PS-SIDE POOLING: each model shard pools its
        local rows per bag, then a psum of the (B, F, d) POOLED tensor
        crosses shards — instead of the naive gather whose cross-shard
        payload is the (B, F, L, d) un-pooled rows (truncation x more
        bytes; the paper's PS architecture pools at the PS for exactly this
        reason). Requires plan.pspec == P(model_axis, None) and the batch
        sharded over the remaining axes."""
        from jax.sharding import PartitionSpec as P
        assert self.plan.pspec == P(model_axis, None), self.plan.pspec
        batch_axes = tuple(a for a in mesh.axis_names if a != model_axis)
        rows_local = self.plan.total_rows // mesh.shape[model_axis]
        d = self.cfg.embed_dim

        def local_fn(mega_shard, idx_local):
            """Per-shard masked lookup; psum recombines across shards."""
            shard = jax.lax.axis_index(model_axis)
            lo = shard * rows_local
            loc = jnp.where((idx_local >= lo)
                            & (idx_local < lo + rows_local),
                            idx_local - lo, -1)
            b, f, lk = loc.shape
            valid = loc >= 0
            slots, take = _row_taker(mega_shard, loc, None, use_kernel)
            with scope("embedding_pool"):
                rows = take(slots.reshape(-1)).reshape(b, f, lk, d)
                rows = jnp.where(valid[..., None], rows.astype(jnp.float32),
                                 0.0)
                pooled = rows.sum(axis=2)      # POOL BEFORE the collective
            with scope("embedding_exchange"):
                return jax.lax.psum(pooled, model_axis)

        return jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(P(model_axis, None), P(batch_axes, None, None)),
            out_specs=P(batch_axes, None, None),
            # the TPU row gather is a pallas_call, whose outputs carry no
            # varying-axes metadata
            check_vma=False,
        )(params["mega"], idx).astype(params["mega"].dtype)

    def lookup_local(self, mega_shard: jax.Array, idx: jax.Array,
                     row_lo: int, row_hi: int,
                     interpret: bool = False,
                     dedup: bool = False) -> jax.Array:
        """Per-shard lookup for shard_map/serving: gather only rows owned by
        this shard ([row_lo, row_hi)); callers all-reduce partial pools.

        `dedup=True` routes through the plan-driven dedup'd kernel
        (ops.dedup_embedding_bag, plan built on device over the shard-local
        stream): each locally-owned unique row leaves HBM once per batch
        instead of once per referencing slot."""
        b, f, lk = idx.shape
        local = jnp.where((idx >= row_lo) & (idx < row_hi),
                          idx - row_lo, -1).reshape(b * f, lk)
        if dedup:
            out = ops.dedup_embedding_bag(mega_shard, local, None, "sum",
                                          None, interpret)
        else:
            out = ops.embedding_bag(mega_shard, local, "sum", None,
                                    interpret)
        return out.reshape(b, f, -1)

    # -- gradient layout for the sparse optimizer ---------------------------

    def per_lookup_grads(self, idx: jax.Array, pooled_grad: jax.Array
                         ) -> tuple[jax.Array, jax.Array]:
        """LEGACY layout: sum pooling => each valid lookup slot inherits its
        bag's grad, materializing the (B*F*L, d) broadcast the fused path
        exists to avoid. Kept as the reference input for
        rowwise_adagrad_update and the equivalence tests.

        idx: (B, F, L); pooled_grad: (B, F, d).
        Returns (flat_idx (B*F*L,), flat_grads (B*F*L, d)).
        """
        b, f, lk = idx.shape
        g = jnp.broadcast_to(pooled_grad[:, :, None, :],
                             (b, f, lk, pooled_grad.shape[-1]))
        return idx.reshape(-1), g.reshape(b * f * lk, -1)

    # -- stats ---------------------------------------------------------------

    def table_bytes(self) -> int:
        """Total mega-table bytes at the param dtype."""
        item = 4 if self.cfg.param_dtype == "float32" else 2
        return self.plan.total_rows * self.cfg.embed_dim * item

    def lookups_per_example(self) -> float:
        """Mean pooled lookups per example across features."""
        return float(sum(self.cfg.mean_lookups))
