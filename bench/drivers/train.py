"""Train driver: the launcher's DLRM step, fed by its own loop and
pipeline, for one window.

Set-up builds the program once: the launcher's jitted step
(`launch/train.py` `dlrm_train_step`, unique-row apply, table donated)
compiled for the cell's batch, the benchmark's weights (bench/model.py)
and the program's fresh optimizer state. It then drives that same object
from the seed through its first three steps with the launcher's
`train_loop` and a `ShardedLoader` pipeline, reading the numbers the
comparison needs: each step's loss, each leaf's first gradient norm (from
the optimizer state after one step) and each leaf's change after three.
The window continues the same loop, pipeline and state until `seconds`
have passed. The batches are a pool drawn from the seed in set-up, cycled
by the pipeline; their synthesis is not timed, the pipeline, the transfer
to the device and the per-step loss read are.

After the window and the peak-memory read, the program's state is freed
and the reference (bench/reference.py) reruns the first three steps from
the same weights.
"""
from __future__ import annotations

import contextlib
import gc
import sys
import time

import numpy as np

#: the Pallas kernels of the step, by the name their ops carry
KERNELS = ("move_rows", "rowwise_adagrad_apply", "dot_interaction")
#: steps driven in set-up and compared with the reference
CHECK_STEPS = 3
#: leaves whose reference gradient is below this share of the median
#: leaf's are moved by round-off alone and are left out of `change_gap`
STILL_LEAF = 1e-3


class TimedPipe:
    """The pipeline as `train_loop` sees it, with the time spent waiting
    in `next()` summed."""

    def __init__(self, pipe):
        self.pipe = pipe
        self.wait_s = 0.0

    def __iter__(self):
        return self

    def __next__(self):
        t = time.perf_counter()
        try:
            return next(self.pipe)
        finally:
            self.wait_s += time.perf_counter() - t


class Deadline:
    """`train_loop`'s preemption hook: stops the loop at the first step
    boundary after `seconds`."""

    def __init__(self, seconds: float):
        self.end = time.perf_counter() + seconds

    @property
    def should_stop(self) -> bool:
        return time.perf_counter() >= self.end


def program_config(cfg: dict):
    """The system's DLRMConfig for a configuration file."""
    from repro.configs.base import DLRMConfig
    keys = ("n_dense_features", "n_sparse_features", "embed_dim",
            "truncation", "interaction", "param_dtype", "compute_dtype")
    return DLRMConfig(
        name=cfg["name"], hash_sizes=tuple(cfg["hash_sizes"]),
        mean_lookups=tuple(cfg["mean_lookups"]),
        bottom_mlp=tuple(cfg["bottom_mlp"]), top_mlp=tuple(cfg["top_mlp"]),
        **{k: cfg[k] for k in keys})


class Program:
    """The system under test, built once for a configuration and batch:
    its compiled step and the small jitted reads of its state.

    The step is compiled as the launcher builds it. `matmul_precision`
    compiles it under `jax.default_matmul_precision` instead: only
    bench/calibrate.py sets it, for its witness; a run never does."""

    def __init__(self, cfg: dict, batch: int,
                 matmul_precision: str | None = None):
        import jax
        import jax.numpy as jnp

        from bench import model, traffic
        from repro.core.embedding import EmbeddingBagCollection
        from repro.launch.train import dlrm_train_step
        from repro.optim.optimizers import adagrad
        from repro.train.steps import dlrm_init_state

        self.cfg, self.batch = cfg, batch
        self.matmul_precision = matmul_precision
        self.pcfg = program_config(cfg)
        self.ebc = EmbeddingBagCollection.build(self.pcfg, n_shards=1)
        offsets, self.rows = traffic.table_layout(cfg["hash_sizes"])
        plan = self.ebc.plan
        if list(plan.table_offsets) != offsets or plan.total_rows != self.rows:
            raise SystemExit("the program's table layout differs from the "
                             "benchmark's (bench/traffic.table_layout)")
        self.opt = adagrad(cfg["optimizer"]["dense_lr"],
                           cfg["optimizer"]["dense_eps"])
        self.step_fn = dlrm_train_step(self.pcfg, self.ebc, self.opt)
        self.init_state = lambda p: dlrm_init_state(self.ebc, self.opt, p)
        self.compiled = None
        d = cfg["embed_dim"]

        @jax.jit
        def grad_norms(state):
            dense = [jnp.sqrt(jnp.sum(s)) for s in model.flat_dense(
                state["dense"])]
            return dense + [jnp.sqrt(d * jnp.sum(state["accum"]))]

        @jax.jit
        def change_norms(params, dense0, key):
            dense = [jnp.linalg.norm(p - q) for p, q in zip(
                model.flat_dense(params), model.flat_dense(dense0))]
            return dense + [jnp.sqrt(model.table_change_sq(
                params["emb"]["mega"], key))]

        self.grad_norms, self.change_norms = grad_norms, change_norms

    def start(self, seed: int):
        """Fresh weights and optimizer state from `seed`; compiles the
        step on first use. Returns (params, state, dense0)."""
        import jax
        import jax.numpy as jnp

        from bench import model
        params = model.init_weights(self.cfg, self.rows, seed)
        dense0 = jax.tree.map(jnp.copy, {"bottom": params["bottom"],
                                         "top": params["top"]})
        state = self.init_state(params)
        if self.compiled is None:
            cfg, b = self.cfg, self.batch
            shapes = {
                "dense": jax.ShapeDtypeStruct(
                    (b, cfg["n_dense_features"]), jnp.float32),
                "idx": jax.ShapeDtypeStruct(
                    (b, cfg["n_sparse_features"], cfg["truncation"]),
                    jnp.int32),
                "label": jax.ShapeDtypeStruct((b,), jnp.float32)}
            with (jax.default_matmul_precision(self.matmul_precision)
                  if self.matmul_precision else contextlib.nullcontext()):
                self.compiled = self.step_fn.lower(
                    params, state, shapes,
                    jnp.asarray(0, jnp.int32)).compile()
        return params, state, dense0


def first_steps(prog: Program, seed: int, pool: list, prefetch: int):
    """Drive the program from `seed` through CHECK_STEPS steps of its own
    loop and pipeline. Returns (params, state, pipe, readings)."""
    from bench import model
    from repro.data.pipeline import ShardedLoader
    from repro.launch.train import train_loop

    params, state, dense0 = prog.start(seed)
    loader = ShardedLoader(lambda step, _seed: pool[step % len(pool)],
                           prog.batch, seed=seed)
    pipe = TimedPipe(loader.pipeline(prefetch=prefetch))
    with contextlib.redirect_stdout(sys.stderr):
        params, state, losses, _ = train_loop(
            prog.compiled, params, state, pipe, 1, log_every=1 << 30)
        grad = [float(x) for x in prog.grad_norms(state)]
        params, state, more, _ = train_loop(
            prog.compiled, params, state, pipe, CHECK_STEPS, start=1,
            log_every=1 << 30)
    change = [float(x) for x in prog.change_norms(
        params, dense0, model.table_key(seed))]
    names = model.leaf_names(params)
    readings = {"losses": losses + more, "grad": dict(zip(names, grad)),
                "change": dict(zip(names, change))}
    return params, state, pipe, readings


def reference_readings(cfg: dict, rows: int, seed: int, pool: list,
                       mode: str = "highest", half: bool = False) -> dict:
    """The reference's readings of the first CHECK_STEPS steps from the
    same seed's weights (made anew: nothing of the program is reused)."""
    from bench import model, reference
    w = model.init_weights(cfg, rows, seed)
    table = np.asarray(w["emb"]["mega"])
    del w["emb"]
    out = reference.train_readings(cfg, w, table, pool[:CHECK_STEPS],
                                   mode=mode, half=half)
    del w, table
    gc.collect()
    return out


def leaf_gaps(got: dict, want: dict, key: str) -> dict:
    """Per leaf, |program's norm - reference's| over the larger of that
    leaf's and the median leaf's reference norm; for the change, only
    leaves that the reference's gradient moves (STILL_LEAF)."""
    g_ref = want["grad"]
    med = float(np.median(list(g_ref.values())))
    ref = g_ref if key == "grad" else {
        k: want[key][k] for k, v in g_ref.items() if v >= STILL_LEAF * med}
    med = float(np.median(list(ref.values())))
    return {k: abs(got[key][k] - v) / max(v, med) for k, v in ref.items()}


def gaps(got: dict, want: dict) -> dict:
    """The numbers compared: the first step's relative loss gap; the
    worst leaf's first-gradient gap (`leaf_gaps`), which covers the
    backward through the interaction, the bottom MLP and the table; the
    same gap on the top MLP's last weight, the leaf nearest the loss; and
    the worst leaf's gap of the change after CHECK_STEPS steps.

    The last weight's gap is read on its own because it lies above every
    ReLU: below it, an input within rounding of zero can take the other
    branch in the program and in the reference, which moves every leaf
    under it (PERF.md, section 4), so the worst leaf's limit has to leave
    room for that and the last weight's need not. The losses of the later
    steps are left out: the first AdaGrad step multiplies their
    rounding."""
    tops = [k for k in want["grad"] if k.startswith("top.")]
    last = f"top.{max(int(k.split('.')[1]) for k in tops)}.w"
    a, b = got["losses"][0], want["losses"][0]
    grad = leaf_gaps(got, want, "grad")
    return {"loss_gap.0": abs(a - b) / abs(b),
            "grad_gap": max(grad.values()),
            "grad_gap.last": grad[last],
            "change_gap": max(leaf_gaps(got, want, "change").values())}


def run(cell) -> dict:
    """One run of a train cell; see the module docstring."""
    import jax

    from bench import trace, traffic
    cfg, tr = cell.cfg, cell.traffic
    prog = Program(cfg, tr["batch"])
    pool = traffic.train_pool(cfg, tr, cell.seed)
    params, state, pipe, got = first_steps(prog, cell.seed, pool,
                                           tr["prefetch"])
    jax.block_until_ready((params, state))
    setup_s = time.perf_counter() - cell.t_start

    from repro.launch.train import train_loop
    from repro.train.fault_tolerance import StragglerDetector
    pipe.wait_s = 0.0
    timer = StragglerDetector(window=1 << 20)      # keeps every step's time
    with trace.capture(cell.trace, cell.tmpdir) as cap, \
            contextlib.redirect_stdout(sys.stderr):
        t0 = time.perf_counter()
        params, state, losses, last = train_loop(
            prog.compiled, params, state, pipe, 1 << 40, start=CHECK_STEPS,
            log_every=1 << 62, preempt=Deadline(cell.seconds),
            straggler=timer)
        jax.block_until_ready((params, state))
        window_s = time.perf_counter() - t0
    pipe.pipe.close()
    steps = last - CHECK_STEPS
    step_s = np.asarray(timer.times)
    print(f"window: {steps} steps in {window_s:.3f} s; step s min "
          f"{step_s.min():.4f} median {np.median(step_s):.4f} max "
          f"{step_s.max():.4f}", file=sys.stderr, flush=True)
    peak = cell.peak_bytes()
    del params, state, prog
    gc.collect()

    record = {"window_s": window_s, "steps": steps,
              "examples": steps * tr["batch"], "input_wait_s": pipe.wait_s,
              "batch": tr["batch"]}
    if cell.trace:
        # the window's k-th step ran pool[(CHECK_STEPS + k) % len(pool)]
        per = [len(traffic.unique_rows(b)) for b in pool]
        record["unique_rows"] = [per[(CHECK_STEPS + k) % len(pool)]
                                 for k in range(steps)]
        record.update(cap.reduce(KERNELS))
    want = reference_readings(cfg, traffic.table_layout(
        cfg["hash_sizes"])[1], cell.seed, pool)
    bad = int(np.sum(~np.isfinite(np.asarray(losses, np.float64))))
    return {"e2e": {"train_examples_per_s": steps * tr["batch"] / window_s,
                    "setup_s": setup_s},
            "record": record, "numbers": gaps(got, want),
            "attempted": steps, "failed": bad, "memory_peak_bytes": peak}
