"""Chaos soak suite (train/fault_tolerance.py, docs/fault_tolerance.md).

The invariant every scenario asserts: ANY seeded fault schedule — reader
death, transient-fetch bursts with degradation to strict_sync, preemption
plus a torn checkpoint leaf, host loss with an elastic table-wise re-pack —
yields final losses (and the materialized capacity tier, accumulators, and
dense params) BIT-EQUAL to the fault-free run. Recovery restores the
TrainState bundle (params + optimizer + cache `state_dict` + pipeline
cursor) from the newest intact checkpoint and replays; replayed steps
recompute identical losses because synthetic batches are deterministic per
step and the bundle round-trips bit-exactly.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import HAS_HYPOTHESIS, requires_hypothesis
from repro.configs import get_smoke_config
from repro.core.cache import (CachedEmbeddingBagCollection,
                              MultiHostCachedEmbeddingBagCollection)
from repro.core.dlrm import dlrm_param_specs
from repro.core.embedding import EmbeddingBagCollection
from repro.core.tiers import BulkCachedEmbeddingBagCollection
from repro.data.pipeline import DataPipeline
from repro.data.synthetic import make_dlrm_batch
from repro.nn.params import init_params
from repro.optim.optimizers import adagrad
from repro.train.checkpoint import CheckpointManager
from repro.train.fault_tolerance import (DegradationManager, FaultInjector,
                                         FaultSpec, PreemptionHandler,
                                         RetryPolicy, TrainState,
                                         elastic_tablewise_repack,
                                         restore_train_state, run_chaos_loop,
                                         save_train_state)
from repro.train.steps import (build_async_cached_dlrm_train_step,
                               build_cached_train_step,
                               build_multihost_cached_train_step,
                               build_tablewise_train_step,
                               cached_dlrm_init_state, dlrm_init_state)


if HAS_HYPOTHESIS:
    from hypothesis import given, settings
    from hypothesis import strategies as st


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("dlrm-m1")


@pytest.fixture(scope="module")
def ebc(cfg):
    return EmbeddingBagCollection.build(cfg, n_shards=1,
                                        strategy="replicated")


def _batch(cfg, ebc, t, b=8):
    raw = make_dlrm_batch(cfg, b, step=t)
    return {"dense": jnp.asarray(raw["dense"]),
            "idx": np.asarray(ebc.offset_indices(jnp.asarray(raw["idx"]))),
            "label": jnp.asarray(raw["label"])}


# ---------------------------------------------------------------------------
# fault-free oracle (async cached tier)
# ---------------------------------------------------------------------------


def _oracle_async(cfg, ebc, n_steps, cache_rows=256):
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(0))
    opt = adagrad(0.01)
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=cache_rows)
    dense = {"bottom": params["bottom"], "top": params["top"]}
    cstate = cached_dlrm_init_state(cc, opt, params)
    astate = cc.init_async_state(params["emb"]["mega"])
    step = build_async_cached_dlrm_train_step(cfg, cc, opt)
    losses = {}
    for t in range(n_steps):
        nxt = _batch(cfg, ebc, t + 1) if t + 1 < n_steps else None
        dense, cstate, m = step(dense, cstate, astate, _batch(cfg, ebc, t),
                                jnp.asarray(t, jnp.int32), next_batch=nxt)
        losses[t] = float(m["loss"])
    mega, accum = cc.materialize_async(astate)
    return (losses, np.asarray(mega), np.asarray(accum),
            jax.tree.map(np.asarray, dense))


# ---------------------------------------------------------------------------
# chaos harness: async cached DLRM + pipeline + checkpoint bundle
# ---------------------------------------------------------------------------


def _run_chaos(cfg, ebc, ckpt_dir, injector, *, n_steps=8, checkpoint_every=2,
               retry=None, degradation=None, cache_rows=256, max_restarts=10,
               keep=4):
    """Drive `run_chaos_loop` over the full stack: DataPipeline (injector
    threaded into the reader), async cached tier (injector + retry on the
    fetch path), CheckpointManager (torn-leaf injection + CRC fallback),
    TrainState bundle save/restore."""
    params0 = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(0))
    opt = adagrad(0.01)
    mgr = CheckpointManager(str(ckpt_dir), keep=keep, injector=injector)
    losses: dict[int, float] = {}
    job: dict = {}

    def gen(t):
        raw = make_dlrm_batch(cfg, 8, step=t)
        return {"dense": raw["dense"],
                "idx": np.asarray(ebc.offset_indices(jnp.asarray(raw["idx"]))),
                "label": raw["label"]}

    def fresh():
        cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=cache_rows)
        cc = dataclasses.replace(cc, injector=injector, retry=retry)
        dense = {"bottom": params0["bottom"], "top": params0["top"]}
        cstate = cached_dlrm_init_state(cc, opt, params0)
        astate = cc.init_async_state(params0["emb"]["mega"])
        return cc, dense, cstate, astate

    def restore_cb():
        # simulated restart: tear the whole job down and rebuild it from
        # the newest intact checkpoint (or from scratch when none exists)
        if job.get("pipe") is not None:
            job["pipe"].close()
        cc, dense, cstate, astate = fresh()
        example = TrainState(dense, cstate, cc.state_dict(astate), 0)
        try:
            ts = restore_train_state(mgr, example)
            astate = cc.load_state_dict(ts.cache)
            dense, cstate = ts.params, ts.opt_state
            start = ts.step
        except FileNotFoundError:
            start = 0
        job.update(cc=cc, dense=dense, cstate=cstate, astate=astate,
                   step=build_async_cached_dlrm_train_step(cfg, cc, opt),
                   pipe=DataPipeline(gen, prefetch=2, start_step=start,
                                     injector=injector))
        return start

    def save_cb(step):
        ts = TrainState(job["dense"], job["cstate"],
                        job["cc"].state_dict(job["astate"]), step)
        save_train_state(mgr, ts)

    def step_fn(step):
        t, raw = next(job["pipe"])
        assert t == step
        batch = {"dense": jnp.asarray(raw["dense"]), "idx": raw["idx"],
                 "label": jnp.asarray(raw["label"])}
        degraded = degradation is not None and degradation.degraded
        nxt = None
        if not degraded and step + 1 < n_steps:
            peek = job["pipe"].peek(0)
            if peek is not None:
                nxt = {"dense": jnp.asarray(peek["dense"]),
                       "idx": peek["idx"],
                       "label": jnp.asarray(peek["label"])}
        dense, cstate, m = job["step"](
            job["dense"], job["cstate"], job["astate"], batch,
            jnp.asarray(step, jnp.int32), next_batch=nxt)
        job["dense"], job["cstate"] = dense, cstate
        losses[step] = float(m["loss"])

    preempt = PreemptionHandler(signals=())
    rep = run_chaos_loop(step_fn, n_steps, save_cb=save_cb,
                         restore_cb=restore_cb,
                         checkpoint_every=checkpoint_every,
                         preemption=preempt, injector=injector,
                         degradation=degradation, max_restarts=max_restarts)
    job["pipe"].close()
    mega, accum = job["cc"].materialize_async(job["astate"])
    return (rep, mgr, losses, np.asarray(mega), np.asarray(accum),
            jax.tree.map(np.asarray, job["dense"]))


def _assert_matches_oracle(cfg, ebc, got, n_steps=8):
    losses, mega, accum, dense = got
    want_l, want_m, want_a, want_d = _oracle_async(cfg, ebc, n_steps)
    assert losses == want_l
    np.testing.assert_array_equal(mega, want_m)
    np.testing.assert_array_equal(accum, want_a)
    for a, b in zip(jax.tree.leaves(dense), jax.tree.leaves(want_d)):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# scenario 1: reader-thread death mid-run
# ---------------------------------------------------------------------------


def test_chaos_reader_death_resumes_bitexact(cfg, ebc, tmp_path):
    """A killed reader thread (SystemExit inside the worker) surfaces as a
    RuntimeError in the consumer; the chaos loop restores the bundle and
    reopens the pipeline at the restored cursor — final state bit-equal to
    the fault-free run."""
    inj = FaultInjector([FaultSpec("pipeline.batch", 4, "kill")])
    rep, mgr, *got = _run_chaos(cfg, ebc, tmp_path, inj)
    assert rep.restarts >= 1 and rep.last_step == 8
    assert ("pipeline.batch", 4, "kill") in inj.fired
    assert len(rep.recovery_s) == rep.restarts
    _assert_matches_oracle(cfg, ebc, got)


# ---------------------------------------------------------------------------
# scenario 2: transient-fetch burst -> retry -> degrade -> promote
# ---------------------------------------------------------------------------


def test_chaos_fetch_fault_absorbed_by_retry(cfg, ebc, tmp_path):
    """An ISOLATED transient fetch fault never surfaces: the bounded
    retry inside the cache's fetch guard absorbs it. Zero restarts."""
    inj = FaultInjector([FaultSpec("cache.fetch", 2, "error"),
                         FaultSpec("cache.fetch", 5, "latency", arg=1e-4)])
    rep, mgr, *got = _run_chaos(cfg, ebc, tmp_path, inj,
                                retry=RetryPolicy(max_retries=2,
                                                  backoff_s=1e-5))
    assert rep.restarts == 0
    assert len(inj.fired) == 2
    _assert_matches_oracle(cfg, ebc, got)


def test_chaos_fetch_burst_degrades_then_promotes(cfg, ebc, tmp_path):
    """A BURST of consecutive fetch faults exhausts the retry budget: the
    step fails, the loop restores, and after `demote_after` consecutive
    failures the DegradationManager flips the schedule to strict_sync.
    Once the storage heals, a clean window promotes it back. Both
    schedules are bit-identical, so the soak still matches the oracle."""
    burst = [FaultSpec("cache.fetch", at, "error") for at in range(3, 15)]
    inj = FaultInjector(burst)
    deg = DegradationManager(demote_after=2, promote_after=2)
    rep, mgr, *got = _run_chaos(cfg, ebc, tmp_path, inj,
                                retry=RetryPolicy(max_retries=1,
                                                  backoff_s=1e-5),
                                degradation=deg)
    assert rep.restarts >= 2
    assert deg.demotions >= 1 and deg.promotions >= 1
    assert rep.degraded_steps > 0
    assert deg.mode == "async"              # promoted back by the end
    _assert_matches_oracle(cfg, ebc, got)


# ---------------------------------------------------------------------------
# scenario 3: preemption at step k + torn checkpoint leaf
# ---------------------------------------------------------------------------


def test_chaos_preempt_with_torn_checkpoint_falls_back(cfg, ebc, tmp_path):
    """Preemption at step 4 forces an off-schedule save whose leaf is torn
    AFTER the atomic publish (a storage-level tear only the CRC catches).
    The simulated restart's restore() skips the corrupt step and falls
    back to the previous intact one; the replay converges bit-exactly."""
    inj = FaultInjector([FaultSpec("loop.step", 4, "preempt"),
                         FaultSpec("checkpoint.write", 2, "torn", arg=1)])
    rep, mgr, *got = _run_chaos(cfg, ebc, tmp_path, inj)
    # saves: step 2 (write 0), step 4 (write 1), preemption save at step 5
    # (write 2, TORN) -> restore falls back past 5 to 4
    assert rep.restarts == 1
    assert mgr.last_restored_step == 4
    assert 8 in mgr.saved_steps()
    _assert_matches_oracle(cfg, ebc, got)


def test_byte_flip_on_disk_falls_back_to_previous_step(cfg, ebc, tmp_path):
    """Acceptance check, no injector: flipping ONE byte of a saved leaf
    file on disk makes restore() reject that step on CRC and fall back to
    the previous intact one."""
    mgr = CheckpointManager(str(tmp_path))
    tree = {"w": np.arange(8, dtype=np.float32), "b": np.ones(3, np.float32)}
    mgr.save(1, tree)
    tree2 = {"w": tree["w"] * 2, "b": tree["b"] * 3}
    mgr.save(2, tree2)
    leaf = sorted((tmp_path / "step_000000002").glob("leaf_*.npy"))[0]
    raw = bytearray(leaf.read_bytes())
    raw[-1] ^= 0xFF
    leaf.write_bytes(bytes(raw))
    got = mgr.restore(tree)
    assert mgr.last_restored_step == 1
    np.testing.assert_array_equal(np.asarray(got["w"]), tree["w"])


# ---------------------------------------------------------------------------
# scenario 4: host loss -> elastic table-wise re-pack
# ---------------------------------------------------------------------------


def test_chaos_host_loss_elastic_repack_bitexact(cfg, tmp_path):
    """Losing one of 4 table-wise owners mid-run: checkpoint the bundle,
    re-run the bin-pack for 3 survivors, re-scatter mega/accum rows under
    the new placement, and continue. Row renumbering is invariant for
    per-bag pooling and per-row AdaGrad, so the remaining losses are
    bit-equal to the uninterrupted 4-owner run."""
    ebc4 = EmbeddingBagCollection.build(cfg, n_shards=4,
                                        strategy="table_wise")
    # numpy master copy: the table-wise step DONATES the mega buffer, so
    # each run must start from fresh device arrays
    params_np = jax.tree.map(np.asarray, init_params(
        dlrm_param_specs(cfg, ebc4), jax.random.PRNGKey(3)))
    opt = adagrad(0.01)

    def run_oracle():
        params = jax.tree.map(jnp.asarray, params_np)
        p, s = dict(params), dlrm_init_state(ebc4, opt, params)
        step = build_tablewise_train_step(cfg, ebc4, opt)
        out = []
        for t in range(6):
            p, s, m = step(p, s, _batch(cfg, ebc4, t, b=16),
                           jnp.asarray(t, jnp.int32))
            out.append(float(m["loss"]))
        return out

    want = run_oracle()

    inj = FaultInjector([FaultSpec("loop.step", 3, "host_loss", arg=1)])
    mgr = CheckpointManager(str(tmp_path), injector=inj)
    params = jax.tree.map(jnp.asarray, params_np)
    e, p, s = ebc4, dict(params), dlrm_init_state(ebc4, opt, params)
    step = build_tablewise_train_step(cfg, ebc4, opt)
    got = []
    for t in range(6):
        spec = inj.fire("loop.step", step=t)
        if spec is not None and spec.kind == "host_loss":
            mgr.save(t, {"params": p, "state": s})
            tree = mgr.restore({"params": p, "state": s})
            e, mega, accum = elastic_tablewise_repack(
                cfg, e, tree["params"]["emb"]["mega"],
                tree["state"]["accum"], 3)
            p = {"bottom": tree["params"]["bottom"],
                 "top": tree["params"]["top"], "emb": {"mega": mega}}
            s = {"dense": tree["state"]["dense"], "accum": accum}
            step = build_tablewise_train_step(cfg, e, opt)
        p, s, m = step(p, s, _batch(cfg, e, t, b=16),
                       jnp.asarray(t, jnp.int32))
        got.append(float(m["loss"]))
    assert e.plan.strategy == "table_wise" and e is not ebc4
    assert got == want


def test_chaos_seeded_schedule_is_deterministic():
    a = FaultInjector.from_seed(11, 16)
    b = FaultInjector.from_seed(11, 16)
    c = FaultInjector.from_seed(12, 16)
    assert [dataclasses.astuple(s) for s in a.schedule] == \
        [dataclasses.astuple(s) for s in b.schedule]
    assert [dataclasses.astuple(s) for s in a.schedule] != \
        [dataclasses.astuple(s) for s in c.schedule]


# ---------------------------------------------------------------------------
# property: snapshot/restore + faults == uninterrupted, on every tier
# ---------------------------------------------------------------------------


def _tier_tools(cfg, ebc, tier, injector=None, retry=None):
    """(collection, init_tier_state, step_adapter, snapshot, load) for one
    cache tier; the adapters normalize the three step signatures."""
    opt = adagrad(0.01)
    if tier == "multihost":
        col = MultiHostCachedEmbeddingBagCollection.build(cfg, n_hosts=2,
                                                          cache_rows=256)
    elif tier == "bulk":
        # 3-tier flavor: DRAM budget below the table height so promotions
        # pull from bulk and evictions overflow DRAM back into it
        col = BulkCachedEmbeddingBagCollection.build(
            cfg, cache_rows=256, dram_rows=300, bulk_chunk=16,
            bulk_latency_us=0.0)
    else:
        col = CachedEmbeddingBagCollection.build(cfg, cache_rows=256)
    col = dataclasses.replace(col, injector=injector, retry=retry)

    if tier in ("sync", "bulk"):
        step = build_cached_train_step(cfg, col, opt)

        def run(dense, cstate, tstate, t, batch, nxt):
            return step(dense, cstate, tstate, batch,
                        jnp.asarray(t, jnp.int32))
        init = col.init_state
    elif tier == "async":
        step = build_async_cached_dlrm_train_step(cfg, col, opt)

        def run(dense, cstate, tstate, t, batch, nxt):
            return step(dense, cstate, tstate, batch,
                        jnp.asarray(t, jnp.int32), next_batch=nxt)
        init = col.init_async_state
    else:
        step = build_multihost_cached_train_step(cfg, col, opt)

        def run(dense, cstate, tstate, t, batch, nxt):
            return step(dense, cstate, tstate, batch,
                        jnp.asarray(t, jnp.int32), next_batch=nxt)
        init = col.init_state
    return col, opt, init, run


def _tier_segment(cfg, ebc, tier, tools, dense, cstate, tstate, t0, t1,
                  n_total):
    col, opt, init, run = tools
    losses = []
    for t in range(t0, t1):
        nxt = _batch(cfg, ebc, t + 1) if t + 1 < n_total else None
        dense, cstate, m = run(dense, cstate, tstate, t,
                               _batch(cfg, ebc, t), nxt)
        losses.append(float(m["loss"]))
    return dense, cstate, losses


def _tier_materialize(tier, col, tstate):
    if tier == "async":
        return col.materialize_async(tstate)
    return col.materialize(tstate)


def _check_resume_equivalence(tier, seed):
    """state_dict -> load_state_dict -> N more steps (into a FRESH
    collection whose fetch path has a seeded schedule of retryable
    transient faults) is bit-equal to running uninterrupted — on the
    sync, async, and multi-host tiers alike."""
    cfg = get_smoke_config("dlrm-m1")
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1,
                                       strategy="replicated")
    params = init_params(dlrm_param_specs(cfg, ebc),
                         jax.random.PRNGKey(seed % 97))
    n1, n2 = 2, 2

    def boot(tools):
        col, opt, init, run = tools
        dense = {"bottom": params["bottom"], "top": params["top"]}
        cstate = cached_dlrm_init_state(col, opt, params)
        return dense, cstate, init(params["emb"]["mega"])

    # uninterrupted oracle
    tools = _tier_tools(cfg, ebc, tier)
    dense, cstate, tstate = boot(tools)
    dense, cstate, l1 = _tier_segment(cfg, ebc, tier, tools, dense,
                                      cstate, tstate, 0, n1 + n2, n1 + n2)
    want_m, want_a = _tier_materialize(tier, tools[0], tstate)

    # interrupted: snapshot after n1, reload into a FAULTY collection
    tools = _tier_tools(cfg, ebc, tier)
    dense, cstate, tstate = boot(tools)
    dense, cstate, l2a = _tier_segment(cfg, ebc, tier, tools, dense,
                                       cstate, tstate, 0, n1, n1 + n2)
    snap = tools[0].state_dict(tstate)
    sites = (("cache.fetch", "bulk.fetch") if tier == "bulk"
             else ("cache.fetch",))
    inj = FaultInjector.from_seed(seed, 32, sites=sites, n_faults=2)
    tools2 = _tier_tools(cfg, ebc, tier, injector=inj,
                         retry=RetryPolicy(max_retries=3, backoff_s=1e-5))
    tstate2 = tools2[0].load_state_dict(snap)
    dense, cstate, l2b = _tier_segment(cfg, ebc, tier, tools2, dense,
                                       cstate, tstate2, n1, n1 + n2,
                                       n1 + n2)
    got_m, got_a = _tier_materialize(tier, tools2[0], tstate2)

    assert l2a + l2b == l1
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(want_m))
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a))


@pytest.mark.parametrize("tier", ["sync", "async", "multihost", "bulk"])
def test_resume_under_faults_equals_uninterrupted(tier):
    _check_resume_equivalence(tier, seed=5)


def test_chaos_bulk_latency_fault_with_preemption_bitexact(cfg, ebc):
    """3-tier chaos: multi-millisecond latency faults armed on the bulk
    promotion path (`bulk.fetch`) PLUS a mid-run preemption (snapshot ->
    discard live state -> restore into a fresh faulty collection) leave
    the run bit-equal to the fault-free uninterrupted oracle. Latency
    faults only stretch wall time, and the capacity tier is always
    current, so the restored bulk store reseeds bit-identically."""
    params = init_params(dlrm_param_specs(cfg, ebc), jax.random.PRNGKey(3))
    n1, n2 = 2, 3

    def boot(tools):
        col, opt, init, run = tools
        dense = {"bottom": params["bottom"], "top": params["top"]}
        return dense, cached_dlrm_init_state(col, opt, params), \
            init(params["emb"]["mega"])

    tools = _tier_tools(cfg, ebc, "bulk")
    dense, cstate, tstate = boot(tools)
    dense, cstate, l1 = _tier_segment(cfg, ebc, "bulk", tools, dense,
                                      cstate, tstate, 0, n1 + n2, n1 + n2)
    want_m, want_a = _tier_materialize("bulk", tools[0], tstate)

    tools = _tier_tools(cfg, ebc, "bulk")
    dense, cstate, tstate = boot(tools)
    dense, cstate, l2a = _tier_segment(cfg, ebc, "bulk", tools, dense,
                                       cstate, tstate, 0, n1, n1 + n2)
    # preemption: checkpoint, then throw the live collection away and
    # restore into one whose bulk reads fire latency + transient faults
    snap = tools[0].state_dict(tstate)
    del tstate
    inj = FaultInjector([FaultSpec("bulk.fetch", 0, "latency", 0.002),
                         FaultSpec("bulk.fetch", 1, "error"),
                         FaultSpec("bulk.fetch", 2, "latency", 0.002)])
    tools2 = _tier_tools(cfg, ebc, "bulk", injector=inj,
                         retry=RetryPolicy(max_retries=3, backoff_s=1e-5))
    tstate2 = tools2[0].load_state_dict(snap)
    dense, cstate, l2b = _tier_segment(cfg, ebc, "bulk", tools2, dense,
                                       cstate, tstate2, n1, n1 + n2,
                                       n1 + n2)
    got_m, got_a = _tier_materialize("bulk", tools2[0], tstate2)

    assert l2a + l2b == l1
    assert any(site == "bulk.fetch" for site, _, _ in inj.fired)
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(want_m))
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a))


if HAS_HYPOTHESIS:

    @requires_hypothesis
    @settings(max_examples=4, deadline=None)
    @given(tier=st.sampled_from(["sync", "async", "multihost", "bulk"]),
           seed=st.integers(0, 10 ** 6))
    def test_resume_under_fuzzed_faults_equals_uninterrupted(tier, seed):
        _check_resume_equivalence(tier, seed)
