"""Training launcher (end-to-end driver, deliverable b).

Runs REAL training on one device (a TPU chip, or the CPU with
JAX_PLATFORMS=cpu). For CPU runs use a smoke arch:
`python -m repro.launch.train --arch stablelm-1.6b --smoke --steps 50`.
`dlrm_job` + `train_loop` are the pieces `chip_smoke.py` drives at
dlrm-m2's published widths.

Features exercised: sharded params, data pipeline with host prefetch,
AdamW/AdaGrad split, checkpoint/restore (resumable), preemption handling,
straggler logging, EASGD / local-SGD pod sync (optional).

At exit a run prints, beside the stragglers flagged, the loop's counters
(`repro.tracing.LoopCounters.summary`): seconds in each phase of the loop
(next batch, host-to-device copy, dispatch, loss read, checkpoint), the
slowest step and how its time split, and the steps in which anything was
compiled. Under `jax.profiler` the same phases are host spans of the trace.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.configs.base import DLRMConfig
from repro.core.dlrm import dlrm_param_specs
from repro.core.embedding import EmbeddingBagCollection
from repro.data.pipeline import ShardedLoader
from repro.data.synthetic import make_dlrm_batch, make_lm_batch
from repro.launch.compile_cache import use_compile_cache
from repro.models.lm import lm_param_specs
from repro.nn.params import init_params
from repro.nn.sharding import TRAIN_RULES
from repro.optim.optimizers import adagrad, adamw
from repro.tracing import LoopCounters
from repro.train.checkpoint import CheckpointManager
from repro.train.fault_tolerance import (FaultInjector, PreemptionHandler,
                                         StragglerDetector, TrainState,
                                         restore_train_state,
                                         run_chaos_loop, run_resilient_loop,
                                         save_train_state)
from repro.train.steps import (build_dlrm_train_step, build_lm_train_step,
                               dlrm_init_state)


def dlrm_train_step(cfg: DLRMConfig, ebc: EmbeddingBagCollection, opt,
                    use_kernel: bool | None = None):
    """The launcher's jitted DLRM step (see `dlrm_job`)."""
    return jax.jit(build_dlrm_train_step(cfg, ebc, opt,
                                         sparse_apply="sparse",
                                         use_kernel=use_kernel),
                   donate_argnums=(0, 1))


def dlrm_job(cfg: DLRMConfig, batch: int, key,
             use_kernel: bool | None = None):
    """The DLRM half of the launcher: collection, params, optimizer state
    factory, the jitted step and the batch generator for `ShardedLoader`.

    The launcher runs on one device (it builds no mesh), so the step takes
    the unique-row apply — O(lookups) rows per step, not the O(table
    height) dense scatter — and donates params and state so the table is
    updated in place. `use_kernel=False` builds the same step on the jnp
    reference ops. Returns (ebc, specs, params, fresh_state, step_fn,
    gen)."""
    ebc = EmbeddingBagCollection.build(cfg, n_shards=1)
    specs = dlrm_param_specs(cfg, ebc)
    params = init_params(specs, key)
    opt = adagrad(0.01)

    def fresh_state(p):
        return dlrm_init_state(ebc, opt, p)

    step_fn = dlrm_train_step(cfg, ebc, opt, use_kernel)

    def gen(step, seed):
        raw = make_dlrm_batch(cfg, batch, step, seed)
        raw["idx"] = np.asarray(ebc.offset_indices(
            jnp.asarray(raw["idx"])))
        return raw

    return ebc, specs, params, fresh_state, step_fn, gen


def train_loop(step_fn, params, state, pipeline, n_steps: int, *,
               start: int = 0, log_every: int = 10, save=None,
               ckpt_every: int = 0, preempt=None, straggler=None,
               counters: LoopCounters | None = None):
    """The launcher's training loop: pull (step, batch) from `pipeline`,
    run `step_fn`, checkpoint through `save(step, params, state)` every
    `ckpt_every` steps (none when `save` is None), stop early on
    preemption. Returns (params, state, losses, last_step).

    Each step is a profiler step `train` whose phases are host spans
    (`repro.tracing.PHASES`): `train.next_batch`, `train.h2d` (the batch to
    the device), `train.dispatch`, `train.loss_read` (which waits for the
    device), and `train.checkpoint` around each save. `counters`, when
    given, sums the phases, keeps the slowest step's split and counts the
    compilations made while the loop runs."""
    live = {"params": params, "state": state}
    losses = []
    counters = counters if counters is not None else LoopCounters()

    def one_step(step):
        with jax.profiler.StepTraceAnnotation("train", step_num=step):
            counters.begin_step(step)
            with counters.phase("next_batch"):
                _, batch = next(pipeline)
            with counters.phase("h2d"):
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
            with counters.phase("dispatch"):
                live["params"], live["state"], metrics = step_fn(
                    live["params"], live["state"], batch,
                    jnp.asarray(step, jnp.int32))
            with counters.phase("loss_read"):
                loss = float(metrics["loss"])
        losses.append(loss)
        if step % log_every == 0:
            print(f"step {step:5d} loss {loss:.4f}")

    def checkpoint(step):
        if save is not None:
            with counters.phase("checkpoint"):
                save(step, live["params"], live["state"])

    with counters.watch_compiles():
        last = run_resilient_loop(one_step, n_steps, checkpoint,
                                  ckpt_every or max(n_steps, 1), preempt,
                                  straggler, start_step=start,
                                  on_step=counters.end_step)
    return live["params"], live["state"], losses, last


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="runs/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--chaos", action="store_true",
                    help="run under a seeded fault schedule (reader death, "
                         "torn checkpoints, preemption) with crash-"
                         "consistent recovery — docs/fault_tolerance.md")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the fault schedule (same seed => same "
                         "schedule)")
    ap.add_argument("--chaos-faults", type=int, default=3,
                    help="number of scheduled faults over the run")
    args = ap.parse_args()

    use_compile_cache()
    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    is_dlrm = isinstance(cfg, DLRMConfig)
    key = jax.random.PRNGKey(0)

    inj = None
    if args.chaos:
        # cache.fetch is excluded: this launcher drives the UNCACHED step
        inj = FaultInjector.from_seed(
            args.chaos_seed, args.steps, n_faults=args.chaos_faults,
            sites=("pipeline.batch", "checkpoint.write", "loop.step"))
        print("chaos schedule: " + ", ".join(
            f"{s.site}[{s.at}]={s.kind}" for s in inj.schedule))
    ckpt = CheckpointManager(f"{args.ckpt_dir}/{args.arch}", injector=inj)
    preempt = PreemptionHandler()
    straggler = StragglerDetector()

    if is_dlrm:
        _, specs, params, fresh_state, step_fn, gen = dlrm_job(
            cfg, args.batch, key)
    else:
        specs = lm_param_specs(cfg)
        params = init_params(specs, key)
        opt = adamw(args.lr)

        def fresh_state(p):
            return opt.init(p)

        step_fn = jax.jit(build_lm_train_step(cfg, opt, TRAIN_RULES))

        def gen(step, seed):
            return make_lm_batch(cfg, args.batch, args.seq, step, seed)

    state = fresh_state(params)
    loader = ShardedLoader(gen, args.batch)

    if args.chaos:
        return _chaos_main(args, inj, ckpt, preempt, loader, specs, key,
                           fresh_state, step_fn)

    pipeline = loader.pipeline(prefetch=2)

    start = 0
    if args.resume and ckpt.latest_step() is not None:
        blob = ckpt.restore({"params": params, "state": state})
        params, state = blob["params"], blob["state"]
        start = ckpt.latest_step()
        print(f"resumed from step {start}")

    def save(step, params, state):
        ckpt.save(step, {"params": params, "state": state}, async_=True)

    counters = LoopCounters()
    _, _, losses, last = train_loop(
        step_fn, params, state, pipeline, args.steps, start=start,
        log_every=args.log_every, save=save, ckpt_every=args.ckpt_every,
        preempt=preempt, straggler=straggler, counters=counters)
    ckpt.wait()
    pipeline.close()
    print(f"done at step {last}; loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
          f"stragglers flagged: {len(straggler.flagged_steps)}")
    print(counters.summary())


def _chaos_main(args, inj, ckpt, preempt, loader, specs, key,
                fresh_state, step_fn):
    """--chaos: seeded fault schedule + crash-consistent recovery. Every
    failure rebuilds the job from the newest INTACT TrainState bundle
    (params + optimizer + pipeline cursor) and replays; losses stay
    bit-equal to a fault-free run (tests/test_chaos.py proves the
    invariant; this path demos it end-to-end on the launcher)."""
    job: dict = {"pipe": None, "params": None, "state": None}
    losses: dict[int, float] = {}

    def restore_cb():
        if job["pipe"] is not None:
            job["pipe"].close()
        params = init_params(specs, key)
        state = fresh_state(params)
        start = 0
        try:
            ts = restore_train_state(ckpt, TrainState(params, state, None, 0))
            params, state, start = ts.params, ts.opt_state, ts.step
            print(f"chaos: restored step {ts.step} "
                  f"(intact checkpoint: {ckpt.last_restored_step})")
        except FileNotFoundError:
            pass
        job.update(params=params, state=state,
                   pipe=loader.pipeline(prefetch=2, start_step=start,
                                        injector=inj))
        return start

    def save_cb(step):
        save_train_state(ckpt, TrainState(job["params"], job["state"],
                                          None, step))

    def one_step(step):
        t, batch = next(job["pipe"])
        assert t == step, (t, step)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, state, metrics = step_fn(job["params"], job["state"], batch,
                                         jnp.asarray(step, jnp.int32))
        job["params"], job["state"] = params, state
        losses[step] = float(metrics["loss"])
        if step % args.log_every == 0:
            print(f"step {step:5d} loss {losses[step]:.4f}")

    rep = run_chaos_loop(one_step, args.steps, save_cb=save_cb,
                         restore_cb=restore_cb,
                         checkpoint_every=args.ckpt_every,
                         preemption=preempt, injector=inj)
    job["pipe"].close()
    fired = ", ".join(f"{s}[{at}]={k}" for s, at, k in inj.fired)
    print(f"chaos: fired {fired or 'nothing'}")
    print(f"chaos done at step {rep.last_step}: {rep.restarts} restarts; "
          f"loss {losses[0]:.4f} -> {losses[max(losses)]:.4f}")


if __name__ == "__main__":
    main()
