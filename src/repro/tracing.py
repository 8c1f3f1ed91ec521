"""The program's own tracing: named scopes on the layers of the DLRM train
step, and host spans and counters on the launcher's loop.

Scopes (`scope`) are `jax.named_scope`s: HLO metadata, free when the step
runs. Every op of a layer carries the layer's name in its `op_name`
metadata, as `jvp(<scope>)` in the forward of a differentiated function
and `transpose(jvp(<scope>))` in its backward; a fusion carries its root
op's. A profiler trace's device ops map to the scopes through the compiled
step's HLO text (`compiled.as_text()`).

Spans (`LoopCounters.phase`) are `jax.profiler.TraceAnnotation`s named
`train.<phase>`, inside one `StepTraceAnnotation("train")` per step: while
a profiler session is open they go into its trace, on the device ops'
clock, so an idle gap of the device can be put down to what the loop was
doing. Whether or not one is open, `LoopCounters` sums each phase's
seconds on the host clock, keeps the split of the slowest step, and counts
the compilations made while the loop runs.
"""
from __future__ import annotations

import contextlib
import time

import jax

#: the layers of the DLRM train step, in step order; every named scope in
#: the program is one of these
SCOPES = ("sparse_plan", "embedding_gather", "embedding_remap",
          "embedding_pool", "bottom_mlp", "interaction", "top_mlp", "loss",
          "dense_optimizer", "bag_grad_sums", "rowwise_adagrad",
          "embedding_exchange")

#: the phases of one step of the launcher's loop, in order; each is a host
#: span `train.<phase>`
PHASES = ("next_batch", "h2d", "dispatch", "loss_read", "checkpoint")

#: JAX records this duration once per executable it builds on a jit cache
#: miss, after tracing and lowering: a backend compile or a read from the
#: persistent compilation cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def scope(name: str):
    """`jax.named_scope(name)` for one of SCOPES."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not one of the layer scopes {SCOPES}")
    return jax.named_scope(name)


class LoopCounters:
    """What one run of the launcher's loop spent in each phase.

    `phase_s` sums each phase's seconds over the loop. `slowest` is
    (step, seconds, {phase: seconds}) for the step whose wall time (the
    loop's own per-step lap, handed to `end_step`) was longest; its split
    counts what ran since the previous step ended, so a checkpoint lands in
    the step after the one it saved. `compiles` lists, for each
    compilation made while `watch_compiles` was open, the step it fell in
    (None before the first step)."""

    def __init__(self):
        self.phase_s = dict.fromkeys(PHASES, 0.0)
        self.steps = 0
        self.slowest: tuple[int, float, dict] | None = None
        self.compiles: list[int | None] = []
        self._step: int | None = None
        self._split = dict.fromkeys(PHASES, 0.0)

    def begin_step(self, step: int) -> None:
        """Open `step`: compilations from here on fall in it."""
        self._step = step

    @contextlib.contextmanager
    def phase(self, name: str):
        """The host span `train.<name>`, timed into the counters."""
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"train.{name}"):
                yield
        finally:
            dt = time.perf_counter() - t0
            self.phase_s[name] += dt
            self._split[name] += dt

    def end_step(self, step: int, seconds: float) -> None:
        """Close `step`, whose wall time was `seconds`."""
        self.steps += 1
        if self.slowest is None or seconds > self.slowest[1]:
            self.slowest = (step, seconds, self._split)
        self._split = dict.fromkeys(PHASES, 0.0)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.compiles.append(self._step)

    @contextlib.contextmanager
    def watch_compiles(self):
        """Count the process's compilations while the body runs."""
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        try:
            yield
        finally:
            jax.monitoring.unregister_event_duration_listener(
                self._on_duration)

    def summary(self) -> str:
        """One line: seconds per phase, the slowest step's split, and the
        compilations by step."""
        def split(d):
            return ", ".join(f"{k} {v:.4f}" for k, v in d.items())
        line = f"loop: {self.steps} steps; s per phase: {split(self.phase_s)}"
        if self.slowest is not None:
            step, s, parts = self.slowest
            line += f"; slowest step {step} ({s:.4f} s): {split(parts)}"
        return line + f"; compiles at steps {self.compiles}"
