"""Bring-up check of the DLRM main path on a TPU, at dlrm-m2's widths.

    python3 chip_smoke.py             one chip: train + serve phases
    python3 chip_smoke.py --chips 4   four chips: the table-wise step only

One chip. dlrm-m2 (configs/dlrm_prod.py) keeps every published width: 13
tables of d=64, 504 dense features, bottom MLP 1024-64, top MLP
1024-1024-512-1, dot interaction, fp32. Its 24.3 GB of tables cannot fit
one 16 GB chip, so every table height is scaled by TABLE_SCALE (printed as
`reduced`).
  train  `launch/train.py`'s `dlrm_job` + `train_loop` for TRAIN_STEPS
         steps of TRAIN_BATCH examples, then the same steps on the jnp
         reference ops (`use_kernel=False`, kernels/ref.py) from the same
         seed: per-step losses must agree, and every touched table row
         must lie as close to the reference as a one-ulp nudge of the
         reference's own weights leaves it (see `check_rows`).
  serve  `launch/serve.py`'s `dlrm_serve_engine` (cache sized by the
         placement plan) answers SERVE_REQUESTS requests; every answered
         probability must agree with the dense forward over a host gather
         of the same table.

Four chips. `build_tablewise_train_step` on a (data=1, model=4) mesh: at
the one-chip height against the single-device step (same checks), then
at m2's full
published heights, with the table initialised on its owners.

Earlier lines report the implementation of each layer (Pallas kernel or
XLA op, read from the compiled program), compile seconds and peak device
memory. The last line is the JSON result. Without a TPU, or when a phase
fails or disagrees, the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import re
import sys
import time

import numpy as np

TABLE_SCALE = 0.165          # 15.66M rows, 4.01 GB of fp32 tables
TRAIN_BATCH = 4096
TRAIN_STEPS = 5
SERVE_REQUESTS = 32
SERVE_EXAMPLES = 4           # per request (launch/serve.py's default)
SERVE_MAX_BATCH = 16         # engine slots (launch/serve.py's default)
FULL_STEPS = 3
SEED = 0

# tolerances fixed before the first chip run, from fp32 rounding over a few
# dependent steps (reduction orders differ between kernel and reference)
LOSS_RTOL = 1e-4
ROW_RTOL, ROW_ATOL = 1e-4, 1e-5
PROB_ATOL = 1e-3
# Touched rows after TRAIN_STEPS steps are held to the reference's own fp32
# sensitivity instead of a fixed tolerance: a row-wise AdaGrad step moves a
# row by lr * g * rsqrt(mean(g^2) + eps), which turns the last-bit
# differences of any reordered sum into ~1e-4 row differences wherever g is
# small. The floor is the gap between the reference and the reference
# rerun with its bottom-MLP weights nudged by one ulp (`nudge_bottom`); a
# wrong row or lane is off by a whole update (~lr = 0.05), far above it.

#: Pallas kernels by the name they carry in a compiled program
KERNELS = ("move_rows", "rowwise_adagrad_apply", "dot_interaction")


def log(msg: str) -> None:
    print(msg, flush=True)


def kernels_in(compiled) -> set[str]:
    """Names of the Pallas kernels compiled into `compiled` (Mosaic custom
    calls); interpret mode would leave none."""
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    return {k for k in KERNELS for ln in calls if re.search(rf"\b{k}\b", ln)}


def report_layers(phase: str, found: set[str], layers) -> None:
    for layer, kernel, xla in layers:
        impl = f"Pallas {kernel}" if kernel in found else f"XLA {xla}"
        log(f"{phase} layer {layer}: {impl}")


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def scaled_m2(scale: float):
    from repro.configs import get_config
    cfg = get_config("dlrm-m2")
    heights = tuple(max(1, round(h * scale)) for h in cfg.hash_sizes)
    return cfg, dataclasses.replace(cfg, hash_sizes=heights)


def touched_rows(gen, steps: int) -> np.ndarray:
    idx = np.concatenate([gen(s, SEED)["idx"].reshape(-1)
                          for s in range(steps)])
    return np.unique(idx[idx >= 0]).astype(np.int32)


def nudge_bottom(params: dict) -> dict:
    """params with every bottom-MLP weight moved up by one ulp."""
    import jax
    import jax.numpy as jnp
    return {**params, "bottom": jax.tree.map(
        lambda w: jnp.nextafter(w, jnp.inf), params["bottom"])}


def check_rows(what: str, got, want, nudged) -> None:
    """`got` must match `want` (the reference) within ROW_RTOL/ROW_ATOL,
    or else differ from it by no more than `nudged` (the reference from a
    one-ulp nudge) does, both in the largest difference and in the count of
    elements outside ROW_RTOL/ROW_ATOL."""
    def gap(x):
        out = ~np.isclose(x, want, rtol=ROW_RTOL, atol=ROW_ATOL)
        return float(np.abs(x - want).max()), int(out.sum())

    (d, n), (fd, fn) = gap(got), gap(nudged)
    log(f"{what}: max_abs_diff={d:.3e} outside_tol={n} of {want.size}; "
        f"one-ulp floor max_abs_diff={fd:.3e} outside_tol={fn}")
    if n and (d > fd or n > fn):
        raise SystemExit(f"{what} differ from the reference by more than "
                         "fp32 rounding moves it")


def train_run(cfg, use_kernel, rows_of, label: str, nudge: bool = False):
    """One launcher run of TRAIN_STEPS steps (`nudge`: from `nudge_bottom`
    params). Returns (losses, table rows and accumulators at
    `rows_of(gen)`, found kernels, rows)."""
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import ShardedLoader
    from repro.kernels import cache_ops
    from repro.launch.train import dlrm_job, train_loop

    _, _, params, fresh_state, step_fn, gen = dlrm_job(
        cfg, TRAIN_BATCH, jax.random.PRNGKey(SEED), use_kernel=use_kernel)
    if nudge:
        params = nudge_bottom(params)
    state = fresh_state(params)
    first = {k: jnp.asarray(v) for k, v in gen(0, SEED).items()}
    t0 = time.perf_counter()
    compiled = step_fn.lower(params, state, first,
                             jnp.asarray(0, jnp.int32)).compile()
    log(f"train[{label}] compile_s={time.perf_counter() - t0:.3f}")
    del first
    pipe = ShardedLoader(gen, TRAIN_BATCH, seed=SEED).pipeline(prefetch=2)
    t0 = time.perf_counter()
    try:
        params, state, losses, _ = train_loop(
            compiled, params, state, pipe, TRAIN_STEPS, log_every=1)
    finally:
        pipe.close()
    log(f"train[{label}] steps={TRAIN_STEPS} wall_s="
        f"{time.perf_counter() - t0:.3f} (includes host batch generation)")
    rows = rows_of(gen)
    vals, acc = cache_ops.cache_fetch(params["emb"]["mega"], state["accum"],
                                      jnp.asarray(rows),
                                      use_kernel=use_kernel)
    out = (np.asarray(losses), np.asarray(vals), np.asarray(acc),
           kernels_in(compiled), rows)
    del params, state, compiled, vals, acc
    gc.collect()
    return out


def train_phase(cfg, dev) -> None:
    rows_cache: dict = {}

    def rows_of(gen):
        if "rows" not in rows_cache:
            rows_cache["rows"] = touched_rows(gen, TRAIN_STEPS)
        return rows_cache["rows"]

    losses, vals, acc, found, rows = train_run(cfg, None, rows_of, "kernel")
    log(f"train[kernel] peak_bytes_in_use={peak_bytes(dev)}")
    report_layers("train", found, [
        ("bottom_mlp", None, "dot"),
        ("embedding_forward", "move_rows", "gather"),
        ("interaction", "dot_interaction", "einsum"),
        ("top_mlp", None, "dot"),
        ("sparse_update", "rowwise_adagrad_apply", "scatter")])
    missing = {"move_rows", "rowwise_adagrad_apply",
               "dot_interaction"} - found
    if missing:
        raise SystemExit(f"train step compiled without kernels {missing}")
    r_losses, r_vals, r_acc, r_found, _ = train_run(cfg, False, rows_of,
                                                    "reference")
    log(f"train[reference] peak_bytes_in_use={peak_bytes(dev)} "
        f"kernels={sorted(r_found) or 'none'}")
    if r_found:
        raise SystemExit(f"reference step ran kernels {r_found}")
    n_losses, n_vals, n_acc, _, _ = train_run(cfg, False, rows_of,
                                              "reference, one-ulp nudge",
                                              nudge=True)
    for k, (a, b, c) in enumerate(zip(losses, r_losses, n_losses)):
        log(f"train step {k}: loss kernel={a!r} reference={b!r} "
            f"rel_diff={abs(a - b) / abs(b):.3e} (one-ulp nudge "
            f"{abs(c - b) / abs(b):.3e})")
    if not np.all(np.isfinite(losses)):
        raise SystemExit("non-finite training loss")
    np.testing.assert_allclose(losses, r_losses, rtol=LOSS_RTOL)
    log(f"train touched_rows={len(rows)}")
    check_rows("train rows", vals, r_vals, n_vals)
    check_rows("train accum", acc, r_acc, n_acc)
    log("train: kernel path matches the reference")


def serve_phase(cfg, dev) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.dlrm import dlrm_forward_dense
    from repro.data.synthetic import make_dlrm_batch
    from repro.kernels import cache_ops
    from repro.launch.serve import dlrm_serve_engine
    from repro.serve import ServeRequest

    t0 = time.perf_counter()
    ebc, params, engine = dlrm_serve_engine(
        cfg, None, seed=SEED, max_queue=64, max_batch=SERVE_MAX_BATCH)
    log(f"serve setup_s={time.perf_counter() - t0:.3f} cache_rows="
        f"{engine.cc.cache_rows} (placement plan)")
    reqs = []
    t0 = time.perf_counter()
    for uid in range(SERVE_REQUESTS):
        raw = make_dlrm_batch(cfg, SERVE_EXAMPLES, step=uid, seed=SEED,
                              zipf_alpha=1.05)
        idx = np.asarray(ebc.offset_indices(np.asarray(raw["idx"])))
        reqs.append((uid, raw["dense"], idx))
        shed = engine.submit(ServeRequest(uid, raw["dense"], idx))
        if shed is not None:
            raise SystemExit(f"request {uid} shed: {shed.reason}")
        if (uid + 1) % 4 == 0:
            engine.step()
    engine.run()
    log(f"serve requests={SERVE_REQUESTS} wall_s="
        f"{time.perf_counter() - t0:.3f} (includes compiles)")
    # each layer is read from its own compiled program: the exchange from
    # the cache-exchange compile, the rest from the engine's forward
    n = 1 << 10
    args = (engine.state.capacity, engine.state.cache,
            engine.state.cap_accum, engine.state.cache_accum,
            engine.state.freq) + (jnp.full((n,), -1, jnp.int32),) * 3 + (
        jnp.zeros((n,), jnp.float32),)
    found_x = kernels_in(cache_ops._exchange_kernel_jit.lower(
        *args, interpret=False).compile())
    found_f = kernels_in(engine._fwd.lower(
        engine.dense, engine.state.cache,
        jnp.zeros((SERVE_MAX_BATCH, cfg.n_dense_features), jnp.float32),
        jnp.zeros((SERVE_MAX_BATCH,) + reqs[0][2].shape[1:],
                  jnp.int32)).compile())
    report_layers("serve", found_x, [("cache_exchange", "move_rows",
                                      "scatter")])
    report_layers("serve", found_f, [
        ("embedding_forward", "move_rows", "gather"),
        ("interaction", "dot_interaction", "einsum"),
        ("mlps", None, "dot")])
    if "move_rows" not in found_x:
        raise SystemExit(f"cache exchange compiled without move_rows: "
                         f"{found_x}")
    if {"move_rows", "dot_interaction"} - found_f:
        raise SystemExit(f"serve forward compiled without kernels: "
                         f"{found_f}")
    log(f"serve peak_bytes_in_use={peak_bytes(dev)} "
        f"hit_rate={engine.cache_stats.hit_rate:.4f}")

    # reference: host gather + pool of the same (host) table, dense
    # forward on the jnp reference ops
    mega = params["emb"]["mega"]
    ref_fwd = jax.jit(lambda dp, x, pooled: jax.nn.sigmoid(
        dlrm_forward_dense({**dp, "emb": None}, x, pooled, cfg,
                           use_kernel=False)))
    worst = 0.0
    for uid, dense, idx in reqs:
        res = engine.results[uid]
        if not hasattr(res, "probs") or res.degraded:
            raise SystemExit(f"request {uid} not served cleanly: {res}")
        valid = idx >= 0
        rows = np.where(valid[..., None], mega[np.where(valid, idx, 0)], 0)
        want = np.asarray(ref_fwd(engine.dense, jnp.asarray(dense),
                                  jnp.asarray(rows.sum(axis=2))))
        worst = max(worst, float(np.abs(res.probs - want).max()))
        np.testing.assert_allclose(res.probs, want, rtol=0, atol=PROB_ATOL)
    log(f"serve answered={SERVE_REQUESTS} max_abs_diff_prob={worst:.3e} "
        "vs dense forward on the same table")
    del engine
    gc.collect()


def tablewise_phase(cfg_full, cfg_small) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.dlrm import dlrm_param_specs
    from repro.core.embedding import EmbeddingBagCollection
    from repro.data.synthetic import make_dlrm_batch
    from repro.launch.mesh import make_test_mesh
    from repro.nn.params import init_params
    from repro.optim.optimizers import adagrad
    from repro.train.steps import (build_dlrm_train_step,
                                   build_tablewise_train_step,
                                   dlrm_init_state)

    mesh = make_test_mesh((1, 4), ("data", "model"))
    opt = adagrad(0.01)

    def batches(cfg, ebc, n):
        out = []
        for s in range(n):
            raw = make_dlrm_batch(cfg, TRAIN_BATCH, s, SEED)
            raw["idx"] = np.asarray(ebc.offset_indices(
                jnp.asarray(raw["idx"])))
            out.append(raw)
        return out

    def owner_init(ebc, cfg):
        """Params and state created where they live: the table row-sharded
        over `model` by its owners, the MLPs replicated."""
        specs = dlrm_param_specs(cfg, ebc)
        rep = NamedSharding(mesh, P())
        shard = {k: jax.tree.map(lambda _: rep, specs[k],
                                 is_leaf=lambda x: hasattr(x, "init"))
                 for k in ("bottom", "top")}
        shard["emb"] = {"mega": NamedSharding(mesh, P("model", None))}
        params = jax.jit(lambda k: init_params(specs, k),
                         out_shardings=shard)(jax.random.PRNGKey(SEED))
        state = jax.jit(lambda p: dlrm_init_state(ebc, opt, p),
                        out_shardings={"dense": rep, "accum": NamedSharding(
                            mesh, P("model"))})(params)
        return params, state

    def run_tablewise(cfg, ebc, data, label):
        params, state = owner_init(ebc, cfg)
        step = build_tablewise_train_step(cfg, ebc, opt, mesh=mesh)
        losses = []
        t0 = time.perf_counter()
        for s, raw in enumerate(data):
            with mesh:
                params, state, m = step(params, state, raw,
                                        jnp.asarray(s, jnp.int32))
            losses.append(float(m["loss"]))
            log(f"tablewise[{label}] step {s}: loss={losses[-1]!r} "
                f"t_s={time.perf_counter() - t0:.3f}")
        peaks = [peak_bytes(d) for d in jax.devices()]
        log(f"tablewise[{label}] peak_bytes_in_use per device={peaks}")
        return params, state, np.asarray(losses)

    log("tablewise layer interaction: XLA einsum (the dense tail is "
        "partitioned by XLA on the mesh)")
    # 1) one-chip height: the table-wise step against the single-device step
    ebc = EmbeddingBagCollection.build(cfg_small, n_shards=4,
                                       strategy="table_wise")
    data = batches(cfg_small, ebc, TRAIN_STEPS)
    params, state, losses_t = run_tablewise(cfg_small, ebc, data,
                                            "one-chip height")
    rows = np.unique(np.concatenate([d["idx"].reshape(-1) for d in data]))
    rows = jnp.asarray(rows[rows >= 0].astype(np.int32))
    from repro.kernels import cache_ops
    got = [np.asarray(a) for a in cache_ops.cache_fetch(
        jax.device_put(params["emb"]["mega"], jax.devices()[0]),
        jax.device_put(state["accum"], jax.devices()[0]), rows)]
    del params, state
    gc.collect()
    dev0 = jax.devices()[0]
    step1 = jax.jit(build_dlrm_train_step(cfg_small, ebc, opt,
                                          sparse_apply="sparse"),
                    donate_argnums=(0, 1))

    def single_device(nudge: bool):
        p1 = jax.device_put(init_params(dlrm_param_specs(cfg_small, ebc),
                                        jax.random.PRNGKey(SEED)), dev0)
        if nudge:
            p1 = nudge_bottom(p1)
        s1 = dlrm_init_state(ebc, opt, p1)
        losses_1 = []
        for s, raw in enumerate(data):
            b = {k: jnp.asarray(v) for k, v in raw.items()}
            p1, s1, m = step1(p1, s1, b, jnp.asarray(s, jnp.int32))
            losses_1.append(float(m["loss"]))
        want = [np.asarray(a) for a in cache_ops.cache_fetch(
            p1["emb"]["mega"], s1["accum"], rows)]
        del p1, s1
        gc.collect()
        return np.asarray(losses_1), want

    losses_1, want = single_device(False)
    losses_n, nudged = single_device(True)
    for k, (a, b, c) in enumerate(zip(losses_t, losses_1, losses_n)):
        log(f"tablewise step {k}: loss mesh={a!r} single-device={b!r} "
            f"rel_diff={abs(a - b) / abs(b):.3e} (one-ulp nudge "
            f"{abs(c - b) / abs(b):.3e})")
    exact = (np.array_equal(losses_t, losses_1)
             and all(np.array_equal(g, w) for g, w in zip(got, want)))
    log(f"tablewise touched_rows={rows.shape[0]} bit_exact={exact}")
    np.testing.assert_allclose(losses_t, losses_1, rtol=LOSS_RTOL)
    check_rows("tablewise rows", got[0], want[0], nudged[0])
    check_rows("tablewise accum", got[1], want[1], nudged[1])

    # 2) m2's full published heights over the four owners
    ebc = EmbeddingBagCollection.build(cfg_full, n_shards=4,
                                       strategy="table_wise")
    real = sum(cfg_full.hash_sizes) * cfg_full.embed_dim * 4
    log(f"tablewise full height: {sum(cfg_full.hash_sizes)} rows = {real} B "
        f"of fp32 tables; {ebc.plan.shard_rows} rows per owner "
        f"(padded mega {ebc.plan.total_rows} rows)")
    _, _, losses = run_tablewise(cfg_full, ebc,
                                 batches(cfg_full, ebc, FULL_STEPS),
                                 "full height")
    if not np.all(np.isfinite(losses)):
        raise SystemExit("non-finite loss at full height")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the table-wise step on a 4-chip mesh")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import use_compile_cache

    log(f"device platform={devices[0].platform} "
        f"kind={devices[0].device_kind!r} count={len(devices)}")
    log(f"compile cache: {use_compile_cache()}")
    cfg_full, cfg = scaled_m2(TABLE_SCALE)
    rows = sum(cfg.hash_sizes)
    log(f"reduced: dlrm-m2 table heights x{TABLE_SCALE} -> {rows} rows, "
        f"{rows * cfg.embed_dim * 4} B of fp32 tables (published "
        f"{sum(cfg_full.hash_sizes)} rows, "
        f"{sum(cfg_full.hash_sizes) * cfg.embed_dim * 4} B); widths as "
        "published")
    if args.chips == 4:
        tablewise_phase(cfg_full, cfg)
    else:
        train_phase(cfg, devices[0])
        serve_phase(cfg, devices[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
