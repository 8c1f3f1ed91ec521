"""Fault injection + crash-consistent recovery (docs/fault_tolerance.md).

At thousands of nodes (the scale the paper's fleet data comes from),
*something* is always failing: the training loop treats preemption as a
normal event (checkpoint-now + clean exit, resumable), watches per-step host
time for stragglers (the paper's section VII cites tail-at-scale and
CPR-style partial recovery), and can resume the SAME global state on a
DIFFERENT mesh shape (checkpoint.py restore with new shardings; elastic
table-wise re-pack below).

This module holds the whole resilience stack:

  * `FaultInjector` — deterministic, seed-driven fault schedules fired at
    named hook points (`pipeline.batch`, `cache.fetch`, `checkpoint.write`,
    `loop.step`, plus the serving-side `serve.fetch` / `serve.admit`)
    threaded through data/pipeline.py, core/cache.py, train/checkpoint.py
    and serve/dlrm_engine.py. Faults: reader-thread death, transient
    capacity-fetch error, fetch latency spike, torn checkpoint leaf,
    preemption at step k, simulated host loss.
  * `RetryPolicy` — bounded retry-with-backoff for transient fetch faults
    (consumed inside core/cache.py's fetch paths, duck-typed so core never
    imports train).
  * `DegradationManager` — the async -> strict_sync degradation state
    machine: demote after N consecutive async failures, promote back after
    a clean window (both paths are bit-identical, only the schedule
    changes, so degradation never perturbs numerics).
  * `TrainState` + save/restore helpers — params, optimizer state, cache
    tier `state_dict`, pipeline cursor and RNG checkpointed as ONE atomic
    unit (per-leaf CRCs live in the manifest, checkpoint.py).
  * `run_resilient_loop` / `run_chaos_loop` — the chaos soak drivers; the
    invariant (any fault schedule => final losses identical to the
    fault-free run) is asserted in tests/test_chaos.py.
  * `elastic_tablewise_repack` — host-loss recovery for table_wise
    placements: re-run the bin-pack for the surviving owner count and
    re-scatter restored rows under the new placement.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import signal
import threading
import time
from collections.abc import Callable
from typing import Any

import numpy as np

# -- fault taxonomy ---------------------------------------------------------

#: hook points a FaultSpec can target (call sites fire these by name).
#: `serve.fetch` guards the serving tier's capacity fetches and
#: `serve.admit` its admission path (serve/dlrm_engine.py); `bulk.fetch`
#: guards the bulk-tier promotion reads (core/tiers.py).
SITES = ("pipeline.batch", "cache.fetch", "bulk.fetch", "checkpoint.write",
         "loop.step", "serve.fetch", "serve.admit")

#: raising kinds ("error"/"kill") throw at the hook point; cooperative kinds
#: ("latency"/"torn"/"preempt"/"host_loss") return the spec for the call
#: site to interpret
KINDS = ("error", "kill", "latency", "torn", "preempt", "host_loss")


class InjectedFault(RuntimeError):
    """Base class for faults raised by `FaultInjector.fire`."""

    transient = False


class TransientFetchFault(InjectedFault):
    """Retryable capacity-fetch failure (storage hiccup / RPC timeout).

    Carries `transient = True`, which is what core/cache.py's retry guard
    keys on (duck-typed: core never imports this module)."""

    transient = True


@dataclasses.dataclass
class FaultSpec:
    """One scheduled fault: fire `kind` at the `at`-th call of `site`.

    `at` is a 0-based per-site call counter over the injector's lifetime
    (for `pipeline.batch` with a fresh pipeline from step 0 it coincides
    with the batch step; for `cache.fetch` it counts fetch dispatches).
    `arg` is kind-specific: latency seconds, torn leaf index, lost host."""

    site: str
    at: int
    kind: str = "error"
    arg: float | int | None = None
    fired: bool = False


class FaultInjector:
    """Deterministic fault-schedule registry.

    Call sites invoke `fire(site)`; the injector matches the site's call
    counter against the schedule. Raising kinds throw (`error` ->
    TransientFetchFault on the fetch/admit sites (`cache.fetch`,
    `serve.fetch`, `serve.admit`), InjectedFault elsewhere; `kill`
    -> SystemExit, the reader-thread death). Cooperative kinds return the
    FaultSpec for the call site to act on (`torn` -> checkpoint leaf
    corruption, `preempt` -> SIGTERM-equivalent stop, `host_loss` ->
    elastic re-pack) — and `latency` sleeps in place. Thread-safe: the
    pipeline reader thread and the train loop share one injector.
    """

    def __init__(self, schedule: list[FaultSpec] | tuple[FaultSpec, ...] = ()):
        self.schedule = list(schedule)
        for s in self.schedule:
            if s.site not in SITES:
                raise ValueError(f"unknown fault site {s.site!r}")
            if s.kind not in KINDS:
                raise ValueError(f"unknown fault kind {s.kind!r}")
        self.calls: collections.Counter = collections.Counter()
        self.fired: list[tuple[str, int, str]] = []
        self._lock = threading.Lock()

    @classmethod
    def from_seed(cls, seed: int, n_steps: int,
                  sites: tuple[str, ...] = ("pipeline.batch", "cache.fetch",
                                            "loop.step"),
                  n_faults: int = 3) -> FaultInjector:
        """Seed-driven schedule: `n_faults` faults over `n_steps` calls,
        each at a random site with a site-appropriate random kind. Same
        seed => same schedule (the chaos tests' determinism contract)."""
        kinds = {"pipeline.batch": ("kill", "error"),
                 "cache.fetch": ("error", "latency"),
                 "bulk.fetch": ("error", "latency"),
                 "checkpoint.write": ("torn",),
                 "loop.step": ("preempt",),
                 "serve.fetch": ("error", "latency"),
                 "serve.admit": ("error",)}
        rng = np.random.RandomState(seed)
        seen: set[tuple[str, int]] = set()
        sched: list[FaultSpec] = []
        while len(sched) < n_faults:
            site = sites[int(rng.randint(len(sites)))]
            opts = kinds[site]
            kind = opts[int(rng.randint(len(opts)))]
            at = int(rng.randint(1, max(n_steps, 2)))
            if (site, at) in seen:
                continue
            seen.add((site, at))
            arg = 0.002 if kind == "latency" else None
            sched.append(FaultSpec(site, at, kind, arg))
        sched.sort(key=lambda s: (s.site, s.at))
        return cls(sched)

    def fire(self, site: str, **ctx) -> FaultSpec | None:
        """Advance `site`'s call counter; raise or return the matching
        scheduled fault (None when nothing is due). `ctx` is recorded on
        cooperative specs for debugging (e.g. step=...)."""
        with self._lock:
            at = self.calls[site]
            self.calls[site] += 1
            spec = next((s for s in self.schedule
                         if not s.fired and s.site == site and s.at == at),
                        None)
            if spec is None:
                return None
            spec.fired = True
            self.fired.append((site, at, spec.kind))
        if spec.kind == "latency":
            time.sleep(float(spec.arg or 0.002))
            return spec
        if spec.kind == "error":
            if site in ("cache.fetch", "bulk.fetch", "serve.fetch",
                        "serve.admit"):
                raise TransientFetchFault(
                    f"injected transient fetch fault at {site}[{at}]")
            raise InjectedFault(f"injected fault at {site}[{at}]")
        if spec.kind == "kill":
            raise SystemExit(f"injected kill at {site}[{at}]")
        return spec            # cooperative: torn / preempt / host_loss


# -- retry + degradation ----------------------------------------------------


@dataclasses.dataclass
class RetryPolicy:
    """Bounded retry-with-backoff for transient fetch faults. Consumed by
    core/cache.py's fetch guard (duck-typed: `max_retries` + `sleep`)."""

    max_retries: int = 3
    backoff_s: float = 1e-3
    multiplier: float = 2.0
    max_backoff_s: float = 0.05

    def sleep(self, attempt: int) -> None:
        """Exponential backoff before retry number `attempt` (1-based)."""
        time.sleep(min(self.backoff_s * self.multiplier ** (attempt - 1),
                       self.max_backoff_s))


class DegradationManager:
    """The async -> strict_sync degradation state machine.

    After `demote_after` CONSECUTIVE async-path failures (transient fetch
    faults that exhausted their retries), `mode` flips to "strict_sync":
    the driver stops staging next batches, so every batch plans + commits
    inside its own step — no overlap to lose to a flaky capacity tier.
    After `promote_after` consecutive clean steps it flips back. Both
    schedules are bit-identical (tests/test_cache_async.py), so the state
    machine trades throughput for stability without touching numerics.
    """

    def __init__(self, demote_after: int = 2, promote_after: int = 4):
        self.demote_after = demote_after
        self.promote_after = promote_after
        self.mode = "async"
        self.demotions = 0
        self.promotions = 0
        self.transitions: list[tuple[str, int]] = []   # (mode, event count)
        self._failures = 0
        self._clean = 0
        self._events = 0

    @property
    def degraded(self) -> bool:
        """True while the strict_sync fallback schedule is active."""
        return self.mode == "strict_sync"

    def record_failure(self) -> None:
        """One async-path failure (retries exhausted)."""
        self._events += 1
        self._failures += 1
        self._clean = 0
        if self.mode == "async" and self._failures >= self.demote_after:
            self.mode = "strict_sync"
            self.demotions += 1
            self.transitions.append(("strict_sync", self._events))

    def record_success(self) -> None:
        """One clean step in the current mode."""
        self._events += 1
        self._failures = 0
        if self.mode == "strict_sync":
            self._clean += 1
            if self._clean >= self.promote_after:
                self.mode = "async"
                self.promotions += 1
                self._clean = 0
                self.transitions.append(("async", self._events))


# -- atomic TrainState bundle ----------------------------------------------


@dataclasses.dataclass
class TrainState:
    """Everything a resumed run needs, checkpointed as ONE atomic unit:
    dense params, dense optimizer state, the cache tier's `state_dict`
    (device slabs + host slot maps + EMA counters + stats, PR 7), the
    pipeline cursor (next step to run — ShardedLoader/synthetic batches
    are deterministic per step, so the cursor IS the data state), and an
    optional host RNG state. A params-only checkpoint cannot resume the
    cached tiers bit-exactly (accumulators live per-slot while a row is
    cached), which is why the bundle exists."""

    params: Any
    opt_state: Any
    cache: Any = None
    step: int = 0
    rng: Any = None

    def tree(self) -> dict:
        """The checkpointable pytree (numpy/jax leaves only)."""
        t = {"params": self.params, "opt": self.opt_state,
             "cursor": np.int64(self.step)}
        if self.cache is not None:
            t["cache"] = self.cache
        if self.rng is not None:
            t["rng"] = np.asarray(self.rng)
        return t


def save_train_state(mgr, state: TrainState, async_: bool = False) -> None:
    """Checkpoint the bundle at its cursor step (atomic + CRC'd leaves)."""
    mgr.save(state.step, state.tree(), async_=async_)


def restore_train_state(mgr, example: TrainState, step: int | None = None,
                        shardings=None) -> TrainState:
    """Restore the bundle; `example` fixes the tree structure (fresh
    params/opt/cache state_dict from the restarting job). With step=None
    the manager falls back past corrupt checkpoints to the newest intact
    one (mgr.last_restored_step says which)."""
    tree = mgr.restore(example.tree(), step=step, shardings=shardings)
    return TrainState(params=tree["params"], opt_state=tree["opt"],
                      cache=tree.get("cache"), step=int(tree["cursor"]),
                      rng=None if "rng" not in tree
                      else np.asarray(tree["rng"]))


# -- elastic table-wise restore --------------------------------------------


def elastic_tablewise_repack(cfg, old_ebc, mega, accum, n_shards_new: int):
    """Host-loss recovery for a table_wise placement: re-run the
    `plan_placement` LPT bin-pack for the surviving `n_shards_new` owners
    and re-scatter the restored mega/accum rows under the new placement.

    Row renumbering does not change the math — per-bag pooling order and
    per-row AdaGrad are invariant under a permutation of global row ids —
    so a repacked run's losses are bit-equal to the uninterrupted one
    (tests/test_chaos.py). Returns (new_ebc, new_mega, new_accum); batches
    must be re-offset with the NEW collection's `offset_indices`.
    """
    import jax.numpy as jnp

    from repro.core.embedding import EmbeddingBagCollection
    from repro.core.placement import elastic_table_remap

    new_ebc = EmbeddingBagCollection.build(cfg, n_shards=n_shards_new,
                                           strategy="table_wise")
    src, dst = elastic_table_remap(old_ebc.plan, new_ebc.plan,
                                   cfg.hash_sizes)
    mega = jnp.asarray(mega)
    accum = jnp.asarray(accum)
    new_mega = jnp.zeros((new_ebc.plan.total_rows, mega.shape[1]),
                         mega.dtype).at[jnp.asarray(dst)].set(
        mega[jnp.asarray(src)])
    new_accum = jnp.zeros((new_ebc.plan.total_rows,),
                          accum.dtype).at[jnp.asarray(dst)].set(
        accum[jnp.asarray(src)])
    return new_ebc, new_mega, new_accum


# -- preemption / stragglers ------------------------------------------------


class PreemptionHandler:
    """SIGTERM/SIGINT -> checkpoint-now flag. The train loop polls
    `should_stop` each step and exits through the checkpoint path."""

    def __init__(self, signals=(signal.SIGTERM,)):
        self._stop = False
        self._prev = {}
        for s in signals:
            with contextlib.suppress(ValueError):    # non-main thread (tests)
                self._prev[s] = signal.signal(s, self._handler)

    def _handler(self, signum, frame):
        self._stop = True

    @property
    def should_stop(self) -> bool:
        """True once a preemption signal (or `trigger`) has fired."""
        return self._stop

    def trigger(self):
        """Raise the stop flag in-process (tests / manual drain)."""
        self._stop = True

    def clear(self):
        """Re-arm after a handled preemption (simulated-restart drivers)."""
        self._stop = False

    def restore(self):
        """Reinstall the signal handlers this handler displaced."""
        for s, h in self._prev.items():
            signal.signal(s, h)


class StragglerDetector:
    """EWMA + z-score on step wall-times.

    On a real pod each host reports step time; a controller flags hosts whose
    time is `z_threshold` sigmas above the fleet EWMA and triggers hot-spare
    swap (the paper's remedy for PS imbalance is re-partitioning — same
    signal). Here it watches the single-process step time and exposes the
    flag + history for the loop/tests.
    """

    def __init__(self, window: int = 50, z_threshold: float = 3.0,
                 warmup: int = 5):
        self.window = window
        self.z_threshold = z_threshold
        self.warmup = warmup
        self.times: collections.deque[float] = collections.deque(maxlen=window)
        self.flagged_steps: list[int] = []
        self._step = 0

    def record(self, seconds: float) -> bool:
        """Returns True when this step is a straggler."""
        is_straggler = False
        if len(self.times) >= self.warmup:
            mean = float(np.mean(self.times))
            std = float(np.std(self.times)) + 1e-9
            if (seconds - mean) / std > self.z_threshold:
                is_straggler = True
                self.flagged_steps.append(self._step)
        self.times.append(seconds)
        self._step += 1
        return is_straggler


class StepTimer:
    """Monotonic lap timer for per-step wall times."""

    def __init__(self):
        self.t0 = time.monotonic()

    def lap(self) -> float:
        """Seconds since construction or the previous lap."""
        now = time.monotonic()
        dt = now - self.t0
        self.t0 = now
        return dt


# -- loop drivers -----------------------------------------------------------


def run_resilient_loop(step_fn: Callable, n_steps: int,
                       checkpoint_cb: Callable[[int], None],
                       checkpoint_every: int,
                       preemption: PreemptionHandler | None = None,
                       straggler: StragglerDetector | None = None,
                       on_straggler: Callable[[int], None] | None = None,
                       start_step: int = 0,
                       injector: FaultInjector | None = None,
                       on_step: Callable[[int, float], None] | None = None
                       ) -> int:
    """Generic resilient loop driver; returns the last completed step.

    step_fn(step) performs one train step (device sync included). A
    preemption coinciding with a scheduled checkpoint saves ONCE (the
    scheduled save already covers the step). `injector` fires the
    "loop.step" site before each step; a "preempt" spec triggers the
    preemption handler exactly as a SIGTERM would. `on_step(step, seconds)`
    gets each step's wall time: the lap since the previous step's, which
    holds that step's checkpoint.
    """
    timer = StepTimer()
    step = start_step
    while step < n_steps:
        if injector is not None and preemption is not None:
            spec = injector.fire("loop.step", step=step)
            if spec is not None and spec.kind == "preempt":
                preemption.trigger()
        step_fn(step)
        dt = timer.lap()
        if on_step is not None:
            on_step(step, dt)
        if straggler is not None and straggler.record(dt) and on_straggler:
            on_straggler(step)
        step += 1
        saved = False
        if step % checkpoint_every == 0:
            checkpoint_cb(step)
            saved = True
        if preemption is not None and preemption.should_stop:
            if not saved:
                checkpoint_cb(step)
            break
    return step


def _recoverable(e: BaseException) -> bool:
    """Faults the chaos driver restores from: anything flagged transient,
    injected faults, and pipeline/runtime failures (a dead reader surfaces
    as RuntimeError). Programming errors (ValueError etc.) propagate."""
    return getattr(e, "transient", False) or isinstance(e, RuntimeError)


@dataclasses.dataclass
class ChaosReport:
    """What a `run_chaos_loop` soak actually did."""

    last_step: int = 0
    restarts: int = 0
    degraded_steps: int = 0
    recovery_s: list = dataclasses.field(default_factory=list)


def run_chaos_loop(step_fn: Callable[[int], None], n_steps: int, *,
                   save_cb: Callable[[int], None],
                   restore_cb: Callable[[], int],
                   checkpoint_every: int = 10,
                   preemption: PreemptionHandler | None = None,
                   injector: FaultInjector | None = None,
                   degradation: DegradationManager | None = None,
                   max_restarts: int = 8) -> ChaosReport:
    """Chaos soak driver: run to `n_steps` through any recoverable fault.

    `step_fn(step)` runs one step and may raise (injected transients that
    exhausted their retries, reader-thread death, torn state...).
    `save_cb(step)` checkpoints the TrainState bundle AFTER `step` steps;
    `restore_cb()` rebuilds the whole job from the newest intact
    checkpoint — params, optimizer, cache tier, pipeline — and returns the
    step to resume from (0 when nothing is saved yet). On a recoverable
    failure the driver restores and replays; replayed steps recompute
    identical losses (synthetic batches are deterministic per step and the
    bundle is bit-exact), which is the chaos invariant tests assert. A
    preemption saves (once) and then simulates the restart in-process:
    clear the flag, restore, continue. `degradation` is notified of
    failures/successes so the caller's step_fn can consult `.mode`.
    """
    rep = ChaosReport()
    step = restore_cb()
    while step < n_steps:
        if injector is not None:
            spec = injector.fire("loop.step", step=step)
            if (spec is not None and spec.kind == "preempt"
                    and preemption is not None):
                preemption.trigger()
        try:
            step_fn(step)
        except Exception as e:
            if not _recoverable(e) or rep.restarts >= max_restarts:
                raise
            if degradation is not None and getattr(e, "transient", False):
                degradation.record_failure()
            rep.restarts += 1
            t0 = time.monotonic()
            step = restore_cb()
            rep.recovery_s.append(time.monotonic() - t0)
            continue
        if degradation is not None:
            degradation.record_success()
            if degradation.degraded:
                rep.degraded_steps += 1
        step += 1
        saved = False
        if checkpoint_every and step % checkpoint_every == 0:
            save_cb(step)
            saved = True
        if preemption is not None and preemption.should_stop:
            if not saved:
                save_cb(step)
            preemption.clear()
            rep.restarts += 1
            t0 = time.monotonic()
            step = restore_cb()
            rep.recovery_s.append(time.monotonic() - t0)
    rep.last_step = step
    return rep
