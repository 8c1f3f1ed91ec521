"""Multi-device integration tests. The main test process pins ONE CPU
device (smoke tests must see a single device), so these spawn
subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8 and
assert on their output — the same isolation discipline as launch/dryrun.
"""
import os
import subprocess
import sys


SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(body: str) -> str:
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=8'\n"
        "from repro.launch.mesh import make_test_mesh\n" + body)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=500)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


def test_psum_lookup_matches_gather_on_mesh():
    print(_run("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import get_smoke_config
from repro.core.embedding import EmbeddingBagCollection
from repro.nn.params import init_params
cfg = dataclasses.replace(get_smoke_config("dlrm-m1"), placement="row_wise")
mesh = make_test_mesh((2, 4), ("data", "model"))
ebc = EmbeddingBagCollection.build(cfg, n_shards=4)
params = init_params(ebc.param_specs(), jax.random.PRNGKey(0))
rng = np.random.RandomState(0)
idx = ebc.offset_indices(jnp.asarray(
    rng.randint(-1, 90, size=(8, cfg.n_sparse_features, 4)), jnp.int32))
with mesh:
    ref = ebc.lookup(params, idx)
    out = jax.jit(lambda p, i: ebc.lookup_pooled_psum(p, i, mesh))(params, idx)
np.testing.assert_allclose(np.asarray(out, np.float32),
                           np.asarray(ref, np.float32), rtol=1e-5, atol=1e-5)
print("PSUM_OK")
"""))


def test_shardmap_sparse_update_matches_pjit():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import get_smoke_config
from repro.core.embedding import EmbeddingBagCollection
from repro.core.dlrm import dlrm_param_specs
from repro.nn.params import init_params
from repro.optim import adagrad
from repro.train.steps import build_dlrm_train_step, dlrm_init_state
from repro.data import make_dlrm_batch
cfg = dataclasses.replace(get_smoke_config("dlrm-m1"),
                          placement="row_wise", lookup_impl="psum")
cfg_ref = dataclasses.replace(cfg, lookup_impl="gather")
mesh = make_test_mesh((2, 4), ("data", "model"))
ebc = EmbeddingBagCollection.build(cfg, n_shards=4)
params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(0))
opt = adagrad(0.05)
state = dlrm_init_state(ebc, opt, params)
raw = make_dlrm_batch(cfg, 16)
batch = {"dense": jnp.asarray(raw["dense"]),
         "idx": ebc.offset_indices(jnp.asarray(raw["idx"])),
         "label": jnp.asarray(raw["label"])}
with mesh:
    p1, s1, m1 = jax.jit(build_dlrm_train_step(cfg, ebc, opt))(
        params, state, batch, jnp.asarray(0, jnp.int32))
    p2, s2, m2 = jax.jit(build_dlrm_train_step(cfg_ref, ebc, opt))(
        params, state, batch, jnp.asarray(0, jnp.int32))
np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
np.testing.assert_allclose(np.asarray(p1["emb"]["mega"]),
                           np.asarray(p2["emb"]["mega"]),
                           rtol=1e-4, atol=1e-5)
print("SHARDMAP_OK")
""")
    assert "SHARDMAP_OK" in out


def test_lm_train_step_lowers_on_mesh_with_all_rule_tables():
    """Every rules table must produce a lowerable, compilable train step on
    a small mesh (the dry-run in miniature)."""
    out = _run("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.models.lm import lm_param_specs
from repro.nn.params import abstract_params, specs_to_pspecs
from repro.nn.sharding import FSDP_RULES, TRAIN_RULES, ZERO_DP_RULES
from repro.optim import adamw
from repro.train.steps import build_lm_train_step
from repro.data.synthetic import lm_batch_specs

cfg = get_smoke_config("stablelm-1.6b")
mesh = make_test_mesh((2, 4), ("data", "model"))
for name, rules in [("train", TRAIN_RULES), ("fsdp", FSDP_RULES),
                    ("zero_dp", ZERO_DP_RULES)]:
    specs = lm_param_specs(cfg)
    params_abs = abstract_params(specs)
    psh = jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                       specs_to_pspecs(specs, rules, mesh=mesh),
                       is_leaf=lambda x: isinstance(x, P))
    opt = adamw(1e-3)
    opt_abs = jax.eval_shape(opt.init, params_abs)
    batch = lm_batch_specs(cfg, 8, 32)
    step = build_lm_train_step(cfg, opt, rules)
    with mesh:
        compiled = jax.jit(step, in_shardings=(
            psh, {"m": psh, "v": psh}, None, None)).lower(
            params_abs, opt_abs, batch,
            jax.ShapeDtypeStruct((), jnp.int32)).compile()
    assert compiled.memory_analysis() is not None
    print(name, "LOWER_OK")
""")
    assert out.count("LOWER_OK") == 3


def test_easgd_pod_axis_semantics():
    """EASGD replicas sharded over a mesh axis: elastic sync must produce
    the same result as the single-host reference math."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.optim.easgd import easgd_init, easgd_sync
mesh = make_test_mesh((4, 2), ("pod", "model"))
state = easgd_init({"w": jnp.arange(6.0)}, n_replicas=4)
state = state._replace(replicas={"w": jnp.stack(
    [jnp.arange(6.0) + i for i in range(4)])})
ref = easgd_sync(state, 0.3, 0.3)
sh = NamedSharding(mesh, P("pod", None))
state_sharded = state._replace(
    replicas={"w": jax.device_put(state.replicas["w"], sh)})
with mesh:
    got = jax.jit(lambda s: easgd_sync(s, 0.3, 0.3))(state_sharded)
np.testing.assert_allclose(np.asarray(got.center["w"]),
                           np.asarray(ref.center["w"]), rtol=1e-6)
np.testing.assert_allclose(np.asarray(got.replicas["w"]),
                           np.asarray(ref.replicas["w"]), rtol=1e-6)
print("EASGD_OK")
""")
    assert "EASGD_OK" in out


def test_elastic_remesh_restore():
    """Checkpoint written under one mesh restores onto a DIFFERENT mesh
    shape with new shardings — the elastic-downscale path."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train.checkpoint import CheckpointManager

tmp = tempfile.mkdtemp()
mesh_a = make_test_mesh((4, 2), ("data", "model"))
mesh_b = make_test_mesh((2, 4), ("data", "model"))
w = jnp.arange(64.0).reshape(8, 8)
tree = {"w": jax.device_put(w, NamedSharding(mesh_a, P("data", "model"))),
        "b": jnp.arange(8.0, dtype=jnp.bfloat16)}
mgr = CheckpointManager(tmp)
mgr.save(7, tree)
# restore under the re-shaped mesh
new_sh = {"w": NamedSharding(mesh_b, P("data", "model")),
          "b": NamedSharding(mesh_b, P())}
out = mgr.restore(jax.tree.map(jnp.zeros_like, tree), shardings=new_sh)
np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(w))
np.testing.assert_array_equal(np.asarray(out["b"], np.float32),
                              np.arange(8.0, dtype=np.float32))
assert out["w"].sharding.mesh.shape["data"] == 2   # lives on the NEW mesh
print("ELASTIC_OK")
""")
    assert "ELASTIC_OK" in out


def test_async_cached_step_on_data_mesh_routes_shared_rows():
    """The overlapped cached train step on the 8-fake-device mesh: the
    batch is sharded over the data axis with the SAME global row planted on
    every replica's shard, so gradient aggregation + dirty writeback must
    route duplicate-row contributions across replicas. The materialized
    capacity tier must match the single-device run exactly."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.core.cache import CachedEmbeddingBagCollection
from repro.core.dlrm import dlrm_param_specs
from repro.core.embedding import EmbeddingBagCollection
from repro.data.synthetic import make_dlrm_batch
from repro.nn.params import init_params
from repro.optim import adagrad
from repro.train.steps import (build_async_cached_dlrm_train_step,
                               cached_dlrm_init_state)

cfg = get_smoke_config("dlrm-m1")
ebc = EmbeddingBagCollection.build(cfg, n_shards=1, strategy="replicated")
params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(0))
opt = adagrad(0.01)
mesh = make_test_mesh((8,), ("data",))
N, B = 4, 16
batches = []
for t in range(N):
    raw = make_dlrm_batch(cfg, B, step=t)
    idx = np.array(ebc.offset_indices(jnp.asarray(raw["idx"])))
    hot = int(idx[idx >= 0][0])
    idx[:, 0, 0] = hot          # same row on every data-parallel replica
    batches.append({"dense": jnp.asarray(raw["dense"]), "idx": idx,
                    "label": jnp.asarray(raw["label"])})

def run(sharded):
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=512)
    dense = {"bottom": params["bottom"], "top": params["top"]}
    state = cached_dlrm_init_state(cc, opt, params)
    astate = cc.init_async_state(params["emb"]["mega"])
    step = build_async_cached_dlrm_train_step(cfg, cc, opt)
    losses = []
    for t in range(N):
        b = dict(batches[t])
        if sharded:
            b["dense"] = jax.device_put(
                b["dense"], NamedSharding(mesh, P("data", None)))
            b["label"] = jax.device_put(
                b["label"], NamedSharding(mesh, P("data")))
        nxt = batches[t + 1] if t + 1 < N else None
        with mesh:
            dense, state, m = step(dense, state, astate, b,
                                   jnp.asarray(t, jnp.int32),
                                   next_batch=nxt)
        losses.append(float(m["loss"]))
    mega, accum = cc.materialize_async(astate)
    return losses, np.asarray(mega), np.asarray(accum)

l1, m1, a1 = run(False)
l2, m2, a2 = run(True)
np.testing.assert_allclose(l1, l2, rtol=1e-6, atol=1e-7)
np.testing.assert_allclose(m1, m2, rtol=1e-6, atol=1e-6)
np.testing.assert_allclose(a1, a2, rtol=1e-6, atol=1e-6)
print("ASYNC_MESH_OK")
""")
    assert "ASYNC_MESH_OK" in out


def test_pallas_embedding_bag_inside_shard_map():
    """The Pallas kernel body (interpret mode) composes with shard_map —
    the per-shard PS lookup path on real TPUs."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.kernels import ops, ref

mesh = make_test_mesh((4,), ("model",))
H, D, B, L = 64, 16, 8, 5          # 16 rows per shard
rng = np.random.RandomState(0)
table = jnp.asarray(rng.randn(H, D), jnp.float32)
idx = jnp.asarray(rng.randint(-1, H, size=(B, L)), jnp.int32)

def local(table_sh, idx_rep):
    shard = jax.lax.axis_index("model")
    lo = shard * (H // 4)
    loc = jnp.where((idx_rep >= lo) & (idx_rep < lo + H // 4),
                    idx_rep - lo, -1)
    part = ops.embedding_bag(table_sh, loc, "sum", None, True)
    return jax.lax.psum(part, "model")

with mesh:
    # check_vma=False: pallas_call's out_shape carries no varying-axes
    # metadata (kernel outputs are shard-local by construction)
    got = jax.jit(jax.shard_map(local, mesh=mesh,
                            in_specs=(P("model", None), P(None, None)),
                            out_specs=P(None, None),
                            check_vma=False))(table, idx)
want = ref.embedding_bag_ref(table, idx, "sum")
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=1e-5, atol=1e-5)
print("KERNEL_SHARDMAP_OK")
""")
    assert "KERNEL_SHARDMAP_OK" in out
