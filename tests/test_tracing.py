"""The program's own tracing (repro/tracing.py): the layer scopes reach a
DLRM step's compiled HLO, the launcher loop writes its host spans into a
profiler trace in order, and its counters add up and count compilations
where they happen."""
import dataclasses
import glob
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.core import EmbeddingBagCollection, dlrm_param_specs
from repro.data import make_dlrm_batch
from repro.kernels.sparse_plan import build_sparse_plan_host
from repro.launch.train import train_loop
from repro.nn.params import init_params
from repro.optim import adagrad
from repro.tracing import PHASES, SCOPES, LoopCounters, scope
from repro.train.steps import build_dlrm_train_step, dlrm_init_state


def _op_names(compiled) -> list[str]:
    return re.findall(r'op_name="([^"]*)"', compiled.as_text())


def _scopes_in(op_names) -> set[str]:
    return {s for n in op_names for s in SCOPES
            if re.search(rf"(^|[/(]){s}($|[/)])", n)}


def test_every_scope_reaches_the_compiled_step():
    """A tiny m3-shaped step (ten features, so the lookup scans them),
    once with a pipeline-built plan attached (the forward's compact
    gather and remap) and once without (the backward builds the plan),
    and the table-wise lookup's exchange on a one-device mesh."""
    cfg = get_smoke_config("dlrm-m3")
    f = 10
    cfg = dataclasses.replace(cfg, n_sparse_features=f,
                              hash_sizes=(50,) * f, mean_lookups=(3,) * f)
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1)
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(0))
    opt = adagrad(0.01)
    state = dlrm_init_state(ebc, opt, params)
    raw = make_dlrm_batch(cfg, 8, 0, 0)
    raw["idx"] = np.asarray(ebc.offset_indices(jnp.asarray(raw["idx"])))
    planned = {**raw, **build_sparse_plan_host(raw["idx"]).to_batch()}
    step = jax.jit(build_dlrm_train_step(cfg, ebc, opt,
                                         sparse_apply="sparse",
                                         interpret=True))
    names = []
    for batch in (raw, planned):
        names += _op_names(step.lower(
            params, state, {k: jnp.asarray(v) for k, v in batch.items()},
            jnp.asarray(0, jnp.int32)).compile())

    mesh = jax.make_mesh((1,), ("model",))
    tw = EmbeddingBagCollection.build(cfg, n_shards=1, strategy="table_wise")
    mega = jnp.zeros((tw.plan.total_rows, cfg.embed_dim))
    names += _op_names(jax.jit(
        lambda mega, idx: tw.lookup_pooled_psum({"mega": mega}, idx, mesh)
    ).lower(mega, jnp.asarray(raw["idx"])).compile())

    assert _scopes_in(names) == set(SCOPES)
    for mlp in ("bottom_mlp", "top_mlp"):
        assert any(f"transpose(jvp({mlp}))" in n for n in names), mlp
        assert any(f"/jvp({mlp})/" in n for n in names), mlp


def test_scope_refuses_an_unknown_name():
    with pytest.raises(ValueError, match="not one of the layer scopes"):
        scope("embedding")


def _pipeline(shapes):
    """(step, batch) pairs whose dense input has the k-th step's shape."""
    for k, n in enumerate(shapes):
        yield k, {"dense": np.full((n,), k, np.float32)}


@jax.jit
def _toy_step(params, state, batch, step_idx):
    loss = jnp.sum(batch["dense"] * params) + step_idx
    return params, state, {"loss": loss}


def test_loop_spans_in_order_under_the_profiler(tmp_path):
    n = 4
    saved = []
    _toy_step(jnp.float32(1.0), None, {"dense": jnp.zeros(3)},
              jnp.asarray(0, jnp.int32))
    with jax.profiler.trace(str(tmp_path)):
        train_loop(_toy_step, jnp.float32(1.0), None, _pipeline([3] * n),
                   n, log_every=1 << 30, ckpt_every=1,
                   save=lambda step, p, s: saved.append(step))
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = sorted((ev.start_ns, ev.name, dict(ev.stats))
                  for plane in ProfileData.from_file(path).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events
                  if ev.name == "train" or ev.name.startswith("train."))
    got = [name for _, name, _ in host]
    one = ["train"] + [f"train.{p}" for p in PHASES]
    assert got == one * n
    assert [st["step_num"] for _, name, st in host
            if name == "train"] == list(range(n))
    assert saved == list(range(1, n + 1))


def test_phase_seconds_fit_in_the_loop():
    counters = LoopCounters()
    t0 = time.perf_counter()
    _, _, losses, last = train_loop(
        _toy_step, jnp.float32(1.0), None, _pipeline([3] * 5), 5,
        log_every=1 << 30, counters=counters)
    loop_s = time.perf_counter() - t0
    assert last == 5 and len(losses) == 5 and counters.steps == 5
    assert set(counters.phase_s) == set(PHASES)
    assert 0 < sum(counters.phase_s.values()) <= loop_s
    step, seconds, split = counters.slowest
    assert 0 <= step < 5 and sum(split.values()) <= seconds <= loop_s


def test_a_new_batch_shape_counts_one_compilation_at_its_step():
    k = 3

    @jax.jit
    def step_fn(params, state, batch, step_idx):
        return params, state, {"loss": jnp.sum(batch["dense"]) * params}

    step_fn(jnp.float32(1.0), None, {"dense": jnp.zeros(4)},
            jnp.asarray(0, jnp.int32))         # warm: the first shape
    counters = LoopCounters()
    train_loop(step_fn, jnp.float32(1.0), None,
               _pipeline([4] * k + [5] * 3), k + 3, log_every=1 << 30,
               counters=counters)
    assert counters.compiles == [k]
    # the listener is gone once the loop ends
    step_fn(jnp.float32(1.0), None, {"dense": jnp.zeros(6)},
            jnp.asarray(0, jnp.int32))
    assert counters.compiles == [k]
