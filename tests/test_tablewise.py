"""Table-wise hybrid parallelism (core/placement.py `table_wise`,
train/steps.py `build_tablewise_train_step`, docs/parallelism.md).

Covers the acceptance contract of the hybrid placement: the priced greedy
bin-pack (whole tables on owners, oversized tables flagged column_wise),
the per-owner/per-table plan splits over the general range core, the
analytic pooled-exchange traffic model + `recommend_placement`'s regime
picks, and the train step's BIT-EXACTNESS vs the dense single-host oracle
— sync and overlap, single-host and on a real (data, model) mesh of 8
fake devices (subprocess, shard_map owner update over genuinely
table-sharded params).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.dlrm import dlrm_param_specs
from repro.core.embedding import EmbeddingBagCollection
from repro.core.placement import plan_placement
from repro.data.synthetic import make_dlrm_batch
from repro.kernels.sparse_plan import (build_sparse_plan_host,
                                       split_plan_by_owner,
                                       split_plan_by_ranges,
                                       split_plan_by_table)
from repro.launch.analysis import (recommend_placement,
                                   tablewise_exchange_traffic)
from repro.nn.params import init_params
from repro.optim.optimizers import adagrad
from repro.train.steps import (build_dlrm_train_step,
                               build_tablewise_train_step, dlrm_init_state)


# ---------------------------------------------------------------------------
# placement: priced bin-pack
# ---------------------------------------------------------------------------


def test_table_wise_plan_shape_and_owners():
    plan = plan_placement([1000, 500, 800, 300], [4.0, 1.0, 3.0, 2.0], 16,
                          2, 1e9, strategy="table_wise")
    assert plan.strategy == "table_wise"
    assert plan.capacity_shards == 2 and plan.shard_rows > 0
    assert plan.total_rows == 2 * plan.shard_rows
    assert plan.pspec == jax.sharding.PartitionSpec("model", None)
    assert plan.column_shards == (1, 1, 1, 1)
    owners = np.asarray(plan.table_offsets) // plan.shard_rows
    # every table sits whole inside its owner's row block
    rows_of = [-(-h // 8) * 8 for h in [1000, 500, 800, 300]]
    for t, off in enumerate(plan.table_offsets):
        assert off + rows_of[t] <= (owners[t] + 1) * plan.shard_rows
    # LPT on cost: the two priciest tables (0 and 2) land on DIFFERENT
    # owners, so neither shard carries both heavy hitters
    assert owners[0] != owners[2]


def test_table_wise_priced_costs_override_loads():
    """With costs inverting the load order, the bin-pack must separate the
    tables the COSTS call heavy, not the ones the loads do."""
    sizes, loads = [400, 400, 400, 400], [10.0, 10.0, 1.0, 1.0]
    by_load = plan_placement(sizes, loads, 16, 2, 1e9,
                             strategy="table_wise")
    by_cost = plan_placement(sizes, loads, 16, 2, 1e9,
                             strategy="table_wise",
                             table_costs=[1.0, 1.0, 10.0, 10.0])
    o_load = np.asarray(by_load.table_offsets) // by_load.shard_rows
    o_cost = np.asarray(by_cost.table_offsets) // by_cost.shard_rows
    assert o_load[0] != o_load[1]          # loads split 0 and 1 ...
    assert o_cost[2] != o_cost[3]          # ... costs split 2 and 3
    # cost balance: per-shard summed cost is even
    assert by_cost.load_per_shard[0] == by_cost.load_per_shard[1]


def test_table_wise_oversized_table_flagged_column_wise():
    d, itemsize = 16, 4
    budget = 100 * d * itemsize            # one shard holds 100 rows
    plan = plan_placement([350, 40], [1.0, 1.0], d, 4, budget,
                          strategy="table_wise")
    # 350-row table needs ceil(350/100) = 4 slices; the small one is whole
    assert plan.column_shards[0] == 4
    assert plan.column_shards[1] == 1


def test_column_wise_requires_divisible_dim():
    plan = plan_placement([100, 50], [1.0, 1.0], 64, 4, 1e9,
                          strategy="column_wise")
    assert plan.column_shards == (4, 4)
    assert plan.pspec == jax.sharding.PartitionSpec(None, "model")
    with pytest.raises(ValueError, match="divisible"):
        plan_placement([100, 50], [1.0, 1.0], 30, 4, 1e9,
                       strategy="column_wise")


def test_tablewise_step_rejects_wrong_plans():
    cfg = get_smoke_config("dlrm-m1")
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1,
                                       strategy="replicated")
    with pytest.raises(ValueError, match="table_wise"):
        build_tablewise_train_step(cfg, ebc, adagrad(0.01))

# ---------------------------------------------------------------------------
# plan splitting: ranges core, owner special case, per-table recovery
# ---------------------------------------------------------------------------


def _live_rows(plan):
    rows = np.asarray(plan.unique_rows)
    return rows[: int((rows >= 0).sum())].astype(np.int64)


def test_split_by_ranges_equals_owner_split():
    rng = np.random.RandomState(0)
    idx = rng.randint(-1, 48, size=(8, 3, 5)).astype(np.int32)
    plan = build_sparse_plan_host(idx)
    starts = np.arange(4, dtype=np.int64) * 12
    a = split_plan_by_ranges(plan, starts, starts + 12)
    b = split_plan_by_owner(plan, 12, 4)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)


def test_split_by_ranges_skips_unclaimed_gaps():
    """Rows between ranges (per-shard tail padding in a table_wise mega)
    belong to NO segment."""
    rng = np.random.RandomState(1)
    idx = rng.randint(0, 30, size=(6, 2, 4)).astype(np.int32)
    plan = build_sparse_plan_host(idx)
    seg_rows, _, seg_base = split_plan_by_ranges(plan, [0, 20], [10, 30])
    live = _live_rows(plan)
    claimed = sorted(r + seg_base[s] for s in range(2)
                     for r in seg_rows[s][seg_rows[s] >= 0])
    want = sorted(int(r) for r in live if r < 10 or r >= 20)
    assert claimed == want


def test_split_by_ranges_rejects_overlapping():
    plan = build_sparse_plan_host(np.zeros((2, 1, 1), np.int32))
    with pytest.raises(AssertionError, match="ascending and disjoint"):
        split_plan_by_ranges(plan, [0, 5], [10, 15])


def test_split_by_table_recovers_per_table_footprints():
    """Under a table_wise layout (tables at arbitrary offsets, row order
    != table order), the per-table segments' local rows + base reconstruct
    exactly the global live rows falling in each table's span, in TABLE
    order."""
    cfg = get_smoke_config("dlrm-m1")
    ebc = EmbeddingBagCollection.build(cfg, n_shards=2,
                                       strategy="table_wise")
    raw = make_dlrm_batch(cfg, 8, step=0)
    idx = np.asarray(ebc.offset_indices(jnp.asarray(raw["idx"])))
    plan = build_sparse_plan_host(idx)
    offs = np.asarray(ebc.plan.table_offsets, np.int64)
    rows_of = np.asarray([-(-h // 8) * 8 for h in cfg.hash_sizes], np.int64)
    seg_rows, seg_offs, seg_base = split_plan_by_table(plan, offs, rows_of)
    assert np.array_equal(seg_base, offs.astype(np.int32))
    live = _live_rows(plan)
    for t in range(len(offs)):
        mine = seg_rows[t][seg_rows[t] >= 0] + offs[t]
        want = live[(live >= offs[t]) & (live < offs[t] + rows_of[t])]
        assert np.array_equal(mine, want)
        # per-table unique footprint = the pricing quantity
        assert len(mine) == len(np.unique(idx[(idx >= offs[t]) &
                                              (idx < offs[t] + rows_of[t])]))


def test_split_overflow_message_names_cap():
    rng = np.random.RandomState(2)
    idx = rng.randint(0, 40, size=(8, 2, 4)).astype(np.int32)
    plan = build_sparse_plan_host(idx)
    with pytest.raises(ValueError, match="segment overflow"):
        split_plan_by_owner(plan, 40, 1, seg_cap=2)

# ---------------------------------------------------------------------------
# analytic exchange model + placement recommendation
# ---------------------------------------------------------------------------


def test_tablewise_exchange_traffic_math():
    b, f, lk, d, h = 8192, 16, 32, 64, 16
    t = tablewise_exchange_traffic(b, f, lk, d, h)
    assert t["fwd_bytes"] == t["bwd_bytes"]
    assert t["total_bytes"] == 2 * t["fwd_bytes"]
    assert t["fwd_bytes"] == (h - 1) / h * b * f * d * 4
    # pooling removes exactly the bag length L vs un-pooled row shipping
    assert t["pooling_reduction"] == lk
    # the per-pair leg stays under the B*F*d*itemsize ceiling
    assert t["pair_leg_bytes"] <= b * f * d * 4
    # a real (imbalanced) owner histogram sharpens the leg: the widest
    # owner, not the uniform ceil(F/H), sets the pair maximum
    t2 = tablewise_exchange_traffic(b, f, lk, d, h,
                                    features_per_owner=[f // 2] + [1] *
                                    (h - 1))
    assert t2["pair_leg_bytes"] == (f // 2) * -(-b // h) * d * 4
    assert t2["pair_leg_bytes"] > t["pair_leg_bytes"]
    # one host: nothing crosses
    assert tablewise_exchange_traffic(b, f, lk, d, 1)["total_bytes"] == 0.0


def test_recommend_placement_three_regimes():
    kw = dict(embed_dim=64, batch=8192, truncation=32, n_hosts=16)
    small = [10_000] * 8
    # everything fits one host -> replicated, zero exchange
    rec = recommend_placement(small, [8.0] * 8, **kw,
                              hbm_budget_bytes=1e12)
    assert rec["pick"] == "replicated" and rec["fits_one_host"]
    assert all(t["strategy"] == "replicated" for t in rec["per_table"])
    # doesn't fit one host, long bags -> pooled tablewise wins
    big = [40_000_000] * 8
    rec = recommend_placement(big, [30.0] * 8, **kw,
                              hbm_budget_bytes=32e9)
    assert rec["pick"] == "table_wise" and not rec["fits_one_host"]
    assert rec["plan"].strategy == "table_wise"
    assert rec["tablewise"]["total_bytes"] <= rec["rowshard"]["total_bytes"]
    # hot skewed traffic with a high hit rate -> the cached tier's
    # unique-row exchange undercuts the pooled all-to-all
    rec = recommend_placement(big, [1.0] * 8, **kw, hbm_budget_bytes=32e9,
                              hit_rate=0.99, alpha=1.2)
    assert rec["pick"] == "cached_host"
    # a table too big for any single host is flagged column_wise
    rec = recommend_placement([4_000_000_000, 1000], [8.0, 8.0], **kw,
                              hbm_budget_bytes=32e9)
    per = rec["per_table"]
    assert per[0]["strategy"] == "column_wise"
    assert per[0]["column_shards"] > 1
    assert per[1]["strategy"] == "table_wise"

# ---------------------------------------------------------------------------
# train-step bit-exactness: single host
# ---------------------------------------------------------------------------


def _batches(cfg, ebc, n, b):
    out = []
    for t in range(n):
        raw = make_dlrm_batch(cfg, b, step=t)
        out.append({"dense": jnp.asarray(raw["dense"]),
                    "idx": np.asarray(
                        ebc.offset_indices(jnp.asarray(raw["idx"]))),
                    "label": jnp.asarray(raw["label"])})
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
    return losses, p, state


@pytest.mark.parametrize("overlap", [False, True])
def test_tablewise_step_bitexact_vs_oracle_single_host(overlap):
    """The owner-routed segmented update (and the staged pooled forward
    under overlap) must reproduce the dense single-host oracle BIT FOR
    BIT: same losses, same mega, same accumulator, same dense params."""
    cfg = get_smoke_config("dlrm-m1")
    ebc = EmbeddingBagCollection.build(cfg, n_shards=4,
                                       strategy="table_wise")
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(0))
    batches = _batches(cfg, ebc, 4, 16)
    want_l, want_p, want_s = _run_oracle(cfg, ebc, params, batches)

    opt = adagrad(0.01)
    p = dict(params)
    state = dlrm_init_state(ebc, opt, p)
    step = build_tablewise_train_step(cfg, ebc, opt, overlap=overlap)
    got_l = []
    for t, b in enumerate(batches):
        nxt = batches[t + 1] if t + 1 < len(batches) else None
        p, state, m = step(p, state, b, jnp.asarray(t, jnp.int32),
                           next_batch=nxt)
        got_l.append(float(m["loss"]))
        assert m["exchange_pooled_fwd_bytes"] == \
            m["exchange_pooled_bwd_bytes"]
        assert m["exchange_pair_leg_bytes"] > 0
    assert got_l == want_l
    assert np.array_equal(np.asarray(p["emb"]["mega"]),
                          np.asarray(want_p["emb"]["mega"]))
    assert np.array_equal(np.asarray(state["accum"]),
                          np.asarray(want_s["accum"]))
    for a, b in zip(jax.tree.leaves({"bottom": p["bottom"],
                                     "top": p["top"]}),
                    jax.tree.leaves({"bottom": want_p["bottom"],
                                     "top": want_p["top"]})):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_tablewise_step_metrics_match_traffic_model():
    """The step's host-computed exchange metrics must equal the analytic
    model exactly (the invariant the deterministic bench row gates)."""
    cfg = get_smoke_config("dlrm-m1")
    ebc = EmbeddingBagCollection.build(cfg, n_shards=4,
                                       strategy="table_wise")
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(0))
    opt = adagrad(0.01)
    state = dlrm_init_state(ebc, opt, params)
    step = build_tablewise_train_step(cfg, ebc, opt)
    b = _batches(cfg, ebc, 1, 16)[0]
    _, _, m = step(dict(params), state, b, jnp.asarray(0, jnp.int32))
    owners = np.asarray(ebc.plan.table_offsets) // ebc.plan.shard_rows
    t = tablewise_exchange_traffic(
        16, cfg.n_sparse_features, b["idx"].shape[2], cfg.embed_dim, 4,
        features_per_owner=np.bincount(owners, minlength=4))
    assert m["exchange_pooled_fwd_bytes"] == t["fwd_bytes"]
    assert m["exchange_pooled_bwd_bytes"] == t["bwd_bytes"]
    assert m["exchange_pair_leg_bytes"] == t["pair_leg_bytes"]

# ---------------------------------------------------------------------------
# 8 fake devices: pooled psum forward + shard_map owner update
# ---------------------------------------------------------------------------

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_tablewise_step_on_mesh_bitexact_vs_oracle():
    """The acceptance test, on a mesh of 8 fake devices. Two meshes:

    (data=1, model=8): the mega table genuinely table-sharded over all 8
    devices, pooled (B, F, d) psum exchange forward, shard_map per-owner
    fused update backward — sync AND overlap runs must equal the dense
    single-host oracle BIT FOR BIT (the model-parallel machinery adds no
    numerics of its own: other owners contribute exact fp32 zeros to the
    psum, and the routed segments reduce in flat-batch order).

    (data=2, model=4): the full hybrid. Batch-sharding the MLPs splits the
    dense-gradient reductions 8+8, so dense params drift by reduction
    order (standard data-parallel numerics, ~1 ulp). After step 0 the
    table and accumulator are still bit-equal and only dense params
    differ; from step 1 that drift reaches the pooled grads, and the loss
    of steps 2-3 moves by 1 ulp. Losses and every array must match to
    1e-6."""
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=8'\n" + """
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.core.dlrm import dlrm_param_specs
from repro.core.embedding import EmbeddingBagCollection
from repro.data.synthetic import make_dlrm_batch
from repro.nn.params import init_params
from repro.optim.optimizers import adagrad
from repro.train.steps import (build_dlrm_train_step, dlrm_init_state,
                               build_tablewise_train_step)

cfg = get_smoke_config("dlrm-m1")
N, B = 4, 16


def run(n_shards, mesh_shape, overlap):
    ebc = EmbeddingBagCollection.build(cfg, n_shards=n_shards,
                                      strategy="table_wise")
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(0))
    batches = []
    for t in range(N):
        raw = make_dlrm_batch(cfg, B, step=t)
        batches.append({"dense": jnp.asarray(raw["dense"]),
                        "idx": np.asarray(
                            ebc.offset_indices(jnp.asarray(raw["idx"]))),
                        "label": jnp.asarray(raw["label"])})
    opt = adagrad(0.01)
    p = dict(params)
    state = dlrm_init_state(ebc, opt, p)
    step_o = jax.jit(build_dlrm_train_step(cfg, ebc, opt,
                                           sparse_apply="sparse"))
    losses_o = []
    for t in range(N):
        b = dict(batches[t]); b["idx"] = jnp.asarray(b["idx"])
        p, state, m = step_o(p, state, b, jnp.asarray(t, jnp.int32))
        losses_o.append(float(m["loss"]))
    mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(*mesh_shape),
                             ("data", "model"))
    p2 = dict(params)
    state2 = dlrm_init_state(ebc, opt, p2)
    step_t = build_tablewise_train_step(cfg, ebc, opt, mesh=mesh,
                                        overlap=overlap)
    losses_t = []
    for t in range(N):
        nxt = batches[t + 1] if t + 1 < N else None
        with mesh:
            p2, state2, m = step_t(p2, state2, batches[t],
                                   jnp.asarray(t, jnp.int32),
                                   next_batch=nxt)
        losses_t.append(float(m["loss"]))
    pairs = [(p2["emb"]["mega"], p["emb"]["mega"]),
             (state2["accum"], state["accum"])]
    pairs += list(zip(
        jax.tree.leaves({"bottom": p2["bottom"], "top": p2["top"]}),
        jax.tree.leaves({"bottom": p["bottom"], "top": p["top"]})))
    return losses_t, losses_o, [(np.asarray(a), np.asarray(b))
                                for a, b in pairs]


for overlap in (False, True):
    # model-parallel only: bit-exact, all 8 devices own tables
    losses_t, losses_o, pairs = run(8, (1, 8), overlap)
    assert losses_t == losses_o, (overlap, losses_t, losses_o)
    for a, b in pairs:
        assert np.array_equal(a, b), overlap
    # hybrid data x model: dense grads reduce 8+8, 1-ulp drift allowed
    losses_t, losses_o, pairs = run(4, (2, 4), overlap)
    np.testing.assert_allclose(losses_t, losses_o, rtol=1e-6, atol=1e-6)
    for a, b in pairs:
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
print("TABLEWISE_MESH_OK")
""")
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=500)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "TABLEWISE_MESH_OK" in out.stdout
