import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell on the production mesh, prove it fits, and extract the roofline
terms from the compiled artifact.

MUST be executed as its own process (`python -m repro.launch.dryrun ...`):
the XLA_FLAGS line above runs before any other import — jax locks the device
count on first init. Never import this module from tests.

Per cell this emits <out>/<arch>__<shape>__<mesh>.json with:
  flops / vpu_flops / major_bytes (global, loop-trip-corrected StableHLO)
  collectives by type (per-chip bytes, post-SPMD HLO, loop-trip-corrected)
  memory_analysis (per-device arg/output/temp bytes — the "fits" proof)
  roofline terms in seconds + the dominant term
  MODEL_FLOPS = 6*N*D (train) / 2*N*D (inference), N = active params
"""
import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from typing import Any  # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (DLRM_SHAPES, LM_SHAPES, get_config,  # noqa: E402
                           shapes_for)
from repro.configs.base import DLRMConfig, Shape  # noqa: E402
from repro.configs.registry import ARCHS, DLRMS  # noqa: E402
from repro.core.embedding import EmbeddingBagCollection  # noqa: E402
from repro.data.synthetic import dlrm_batch_specs, lm_batch_specs  # noqa: E402
from repro.launch.analysis import (CollectiveAnalysis,  # noqa: E402
                                   StableHloAnalysis)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.roofline import (HW, roofline_terms)  # noqa: E402
from repro.models.lm import (cache_abstract, cache_pspecs,  # noqa: E402
                             decode_step, lm_param_specs, prefill_step)
from repro.nn.params import (abstract_params, param_count,  # noqa: E402
                             specs_to_pspecs)
from repro.nn.sharding import (FSDP_RULES, LONG_SERVE_RULES,  # noqa: E402
                               SERVE_RULES, TRAIN_RULES, _resolve)
from repro.optim.optimizers import adagrad, adamw  # noqa: E402
from repro.train.steps import (build_dlrm_train_step,  # noqa: E402
                               build_lm_train_step, dlrm_init_state)

# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------


def _rules_for(cfg, shape: Shape, overrides: dict | None = None):
    if shape.kind in ("dlrm_train", "dlrm_infer"):
        rules = dict(TRAIN_RULES)        # DLRM: paper-faithful DP+PS mapping
    elif shape.kind == "train":
        # FSDP + sequence parallelism is the fit-first default for every LM
        # arch (replicated fp32 grads alone exceed 16 GB/chip at >= 1.6B)
        rules = dict(FSDP_RULES)
    elif shape.name.startswith("long"):
        rules = dict(LONG_SERVE_RULES)
    else:
        rules = dict(SERVE_RULES)
    if overrides:
        rules.update(overrides)
    return rules


def _named(mesh, pspec_tree):
    return jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), pspec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _batch_shardings(mesh, rules, batch_specs):
    from repro.nn.sharding import resolve_sized

    def one(s):
        sp = resolve_sized(("batch",) + (None,) * (len(s.shape) - 1), rules,
                           mesh, s.shape)
        return NamedSharding(mesh, sp)
    return jax.tree.map(one, batch_specs)


def build_cell(arch: str, shape: Shape, mesh,
               rules_overrides: dict | None = None,
               config_overrides: dict | None = None):
    """Returns (fn, args_abstract, in_shardings, out_shardings, meta)."""
    cfg = get_config(arch)
    if config_overrides:
        cfg = dataclasses.replace(cfg, **config_overrides)
    rules = _rules_for(cfg, shape, rules_overrides)

    if isinstance(cfg, DLRMConfig):
        return _build_dlrm_cell(cfg, shape, mesh, rules)
    return _build_lm_cell(cfg, shape, mesh, rules)


def _dp_size(mesh, rules) -> int:
    """Effective data-parallel degree = product of mesh axes carrying the
    batch dim (zero_dp maps batch over model too)."""
    axes = rules.get("batch") or ("pod", "data")
    if isinstance(axes, str):
        axes = (axes,)
    out = 1
    for a in axes:
        out *= mesh.shape.get(a, 1)
    return out


def _auto_accum(cfg, shape: Shape, mesh, rules) -> int:
    """Gradient-accumulation factor so saved activations + the CE region fit
    the per-chip HBM budget (the paper's section V-B batch-size lever used
    as a memory knob).

    saves  = tokens_per_datashard x d x 2B x n_layers   (scan carry, bf16)
    ce     = tokens_per_datashard x vocab/TP x 12B      (logits fp32 region)
    """
    dp = _dp_size(mesh, rules)
    tp = mesh.shape.get("model", 1) if "model" not in (
        rules.get("batch") or ()) else 1
    tokens = shape.global_batch * shape.seq_len / dp
    saves = tokens * cfg.d_model * 2 * cfg.n_layers
    if cfg.family == "ssm" or cfg.layer_pattern:
        saves *= 2.2                       # conv/ssd intermediates
    if cfg.n_experts:
        # dispatch tables + (g, e, cap, d) tiles + their backward
        saves += tokens * cfg.d_model * cfg.top_k * cfg.capacity_factor * 10
    vocab_eff = cfg.vocab_size * (cfg.n_codebooks
                                  if cfg.frontend == "audio" else 1)
    ce = tokens * (vocab_eff / tp) * 12
    if cfg.frontend == "audio":
        ce += tokens * cfg.d_model * 8     # fp32 frame-embedding inputs
    budget = 6e9
    accum = 1
    max_accum = max(1, shape.global_batch // dp)
    while (saves + ce) / accum > budget and accum < max_accum:
        accum *= 2
    return min(accum, max_accum)


def _sharded_gb(specs, pspecs, mesh) -> float:
    """Analytic per-chip GB of a ParamSpec tree under its PartitionSpecs."""
    import math as _m
    is_spec = lambda x: hasattr(x, "logical_axes")  # noqa: E731
    total = 0.0
    for s, sp in zip(jax.tree.leaves(specs, is_leaf=is_spec),
                     jax.tree.leaves(pspecs,
                                     is_leaf=lambda x: isinstance(x, P))):
        shards = 1
        for e in sp:
            for a in (e if isinstance(e, tuple) else ((e,) if e else ())):
                shards *= mesh.shape[a]
        total += _m.prod(s.shape) * jnp.dtype(s.dtype).itemsize / shards
    return total / 1e9


def _hbm_estimate_lm(cfg, shape, mesh, specs, pspecs, accum) -> float:
    """Analytic per-chip HBM (GB): params (+grads/opt for train) + saved
    activations + CE region + caches. The CPU-backend memory_analysis
    OVERSTATES bf16 programs ~2-3x (f32-upcast temp copies — evidence in
    EXPERIMENTS.md section Dry-run); this is the TPU-native estimate."""
    dp = mesh.shape.get("pod", 1) * mesh.shape.get("data", 1)
    tp = mesh.shape.get("model", 1)
    p_gb = _sharded_gb(specs, pspecs, mesh)
    gb = p_gb
    if shape.kind == "train":
        gb += 3 * p_gb                       # grads + adam m,v (fp32 = p)
        tokens = shape.global_batch * shape.seq_len / dp / max(accum, 1)
        ssd = 2.2 if (cfg.family == "ssm" or cfg.layer_pattern) else 1.0
        gb += tokens * cfg.d_model * 2 * cfg.n_layers * ssd / 1e9
        vocab_eff = cfg.vocab_size * (cfg.n_codebooks
                                      if cfg.frontend == "audio" else 1)
        gb += tokens * (vocab_eff / tp) * 12 / 1e9
        if cfg.n_experts:
            gb += tokens * cfg.d_model * cfg.top_k * 6 / 1e9
    else:
        import math as _m
        caches = cache_abstract(cfg, shape.global_batch, shape.seq_len)
        cache_bytes = sum(_m.prod(c.shape) * jnp.dtype(c.dtype).itemsize
                          for c in jax.tree.leaves(caches))
        gb += cache_bytes / (dp * tp) / 1e9  # batch x (kv|seq) sharded
        if shape.kind == "prefill":
            tokens = shape.global_batch * shape.seq_len / dp
            gb += tokens * cfg.d_model * 2 * 4 / 1e9   # transient acts
    return gb


def _build_lm_cell(cfg, shape: Shape, mesh, rules):
    tp = mesh.shape.get("model", 1)
    dp = mesh.shape.get("pod", 1) * mesh.shape.get("data", 1)
    if cfg.n_experts > 0 and shape.kind != "decode":
        # GShard grouped dispatch: one group per data shard
        dpe = _dp_size(mesh, rules) if shape.kind == "train" else dp
        tokens = shape.global_batch * max(shape.seq_len, 1)
        g = dpe if tokens % dpe == 0 else 1
        cfg = dataclasses.replace(cfg, moe_groups=g)
    if shape.kind in ("prefill", "decode") and cfg.n_kv_heads % tp != 0:
        # kv heads can't shard over the TP axis -> shard the cache seq dim
        # instead (flash-decoding layout)
        rules = dict(rules, cache_seq="model", cache_kv=None)
    if shape.kind == "prefill":
        # prefill: dh-fallback would all-reduce 32k-seq score matrices
        # (measured 70x worse); store weights FSDP-sharded over `data` and
        # gather per layer instead (bf16 weight all-gather ~0.25s/pass).
        rules = dict(rules)
        rules.pop("_fallback", None)
        rules.update(embed=("data",), _gather_weights=True)
    specs = lm_param_specs(cfg)
    if shape.kind in ("prefill", "decode"):
        # serving holds bf16 weights (no optimizer master copies)
        from repro.nn.params import cast_specs
        specs = cast_specs(specs, jnp.bfloat16)
    params_abs = abstract_params(specs)
    pspecs = specs_to_pspecs(specs, rules, mesh=mesh)
    params_sh = _named(mesh, pspecs)
    n_params = param_count(specs)
    n_active = cfg.active_param_count_estimate()
    accum0 = _auto_accum(cfg, shape, mesh, rules) if shape.kind == "train" \
        else 1
    extra: dict[str, Any] = {
        "hbm_estimate_gb": round(
            _hbm_estimate_lm(cfg, shape, mesh, specs, pspecs, accum0), 2)}

    if shape.kind == "train":
        opt = adamw(3e-4, weight_decay=0.1)
        opt_abs = jax.eval_shape(opt.init, params_abs)
        opt_sh = {"m": params_sh, "v": params_sh}
        batch_abs = lm_batch_specs(cfg, shape.global_batch, shape.seq_len)
        batch_sh = _batch_shardings(mesh, rules, batch_abs)
        idx_abs = jax.ShapeDtypeStruct((), jnp.int32)
        rep = NamedSharding(mesh, P())
        accum = accum0
        step = build_lm_train_step(cfg, opt, rules, accum_steps=accum,
                                   grad_dtype=cfg.grad_reduce_dtype)
        fn = jax.jit(step,
                     in_shardings=(params_sh, opt_sh, batch_sh, rep),
                     out_shardings=(params_sh, opt_sh, None),
                     donate_argnums=(0, 1))
        args = (params_abs, opt_abs, batch_abs, idx_abs)
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens
        extra["accum_steps"] = accum
    elif shape.kind == "prefill":
        caches_abs = cache_abstract(cfg, shape.global_batch, shape.seq_len)
        caches_sh = _named(mesh, cache_pspecs(cfg, rules, mesh,
                                              shape.global_batch,
                                              shape.seq_len))
        batch_abs = lm_batch_specs(cfg, shape.global_batch, shape.seq_len)
        for k in ("targets", "loss_mask"):
            batch_abs.pop(k, None)
        batch_sh = _batch_shardings(mesh, rules, batch_abs)
        fn = jax.jit(
            lambda p, b, c: prefill_step(p, b, c, cfg, rules),
            in_shardings=(params_sh, batch_sh, caches_sh),
            out_shardings=(None, caches_sh),
            donate_argnums=(2,))
        args = (params_abs, batch_abs, caches_abs)
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_active * tokens
    else:  # decode
        caches_abs = cache_abstract(cfg, shape.global_batch, shape.seq_len)
        caches_sh = _named(mesh, cache_pspecs(cfg, rules, mesh,
                                              shape.global_batch,
                                              shape.seq_len))
        if cfg.frontend == "audio":
            tok_abs = jax.ShapeDtypeStruct(
                (shape.global_batch, 1, cfg.n_codebooks), jnp.int32)
        else:
            tok_abs = jax.ShapeDtypeStruct((shape.global_batch, 1),
                                           jnp.int32)
        tok_sh = _batch_shardings(mesh, rules, tok_abs)
        idx_abs = jax.ShapeDtypeStruct((), jnp.int32)
        fn = jax.jit(
            lambda p, t, c, i: decode_step(p, t, c, i, cfg, rules),
            in_shardings=(params_sh, tok_sh, caches_sh, NamedSharding(
                mesh, P())),
            out_shardings=(None, caches_sh),
            donate_argnums=(2,))
        args = (params_abs, tok_abs, caches_abs, idx_abs)
        tokens = shape.global_batch            # one token per sequence
        model_flops = 2.0 * n_active * tokens
    return fn, args, {"model_flops": model_flops, "params": n_params,
                      "active_params": n_active, "cfg": cfg, **extra}


def _build_dlrm_cell(cfg: DLRMConfig, shape: Shape, mesh, rules):
    n_shards = mesh.shape.get("model", 1)
    dp = mesh.shape.get("pod", 1) * mesh.shape.get("data", 1)
    ebc = EmbeddingBagCollection.build(cfg, n_shards, second_axis_size=dp)
    from repro.core.dlrm import dlrm_forward, dlrm_param_specs
    specs = dlrm_param_specs(cfg, ebc)
    params_abs = abstract_params(specs)
    pspecs = specs_to_pspecs(specs, rules, mesh=mesh)
    pspecs["emb"]["mega"] = ebc.plan.pspec     # planner overrides rules
    params_sh = _named(mesh, pspecs)
    import math
    dense_params = sum(
        math.prod(s.shape) for s in jax.tree.leaves(
            {"bottom": specs["bottom"], "top": specs["top"]},
            is_leaf=lambda x: hasattr(x, "logical_axes")))

    if shape.kind == "dlrm_train":
        opt = adagrad(0.01)
        step = build_dlrm_train_step(cfg, ebc, opt, rules=rules)
        state_abs = jax.eval_shape(
            lambda p: dlrm_init_state(ebc, opt, p), params_abs)
        state_sh = {
            "dense": {"bottom": pspecs["bottom"], "top": pspecs["top"]},
            "accum": P(*ebc.plan.pspec[:1]),
        }
        state_sh = _named(mesh, state_sh)
        batch_abs = dlrm_batch_specs(cfg, shape.global_batch)
        batch_sh = _batch_shardings(mesh, rules, batch_abs)
        idx_abs = jax.ShapeDtypeStruct((), jnp.int32)
        fn = jax.jit(step,
                     in_shardings=(params_sh, state_sh, batch_sh,
                                   NamedSharding(mesh, P())),
                     out_shardings=(params_sh, state_sh, None),
                     donate_argnums=(0, 1))
        args = (params_abs, state_abs, batch_abs, idx_abs)
        model_flops = 6.0 * dense_params * shape.global_batch
    else:  # dlrm_infer
        batch_abs = dlrm_batch_specs(cfg, shape.global_batch)
        batch_sh = _batch_shardings(mesh, rules, batch_abs)
        fn = jax.jit(
            lambda p, b: dlrm_forward(p, b, cfg, ebc, rules=rules),
            in_shardings=(params_sh, batch_sh), out_shardings=None)
        args = (params_abs, batch_abs)
        model_flops = 2.0 * dense_params * shape.global_batch
    lookup_bytes = (shape.global_batch * ebc.lookups_per_example()
                    * cfg.embed_dim * 4)
    # analytic per-chip HBM: table + gradient-aggregation copy + accумulator
    # + dense stack (params/grads/adagrad) + batch transients
    table_gb = max(ebc.plan.bytes_per_shard) / 1e9
    est = (2 * table_gb                        # mega + gsum aggregation
           + table_gb / cfg.embed_dim         # rowwise accum (1 fp32/row)
           + dense_params * 12 / 1e9          # p + grad + adagrad accum
           + shape.global_batch / dp * cfg.n_sparse_features
           * (cfg.truncation * 4 + cfg.embed_dim * 8) / 1e9)
    return fn, args, {"hbm_estimate_gb": round(est, 2),
                      "model_flops": model_flops,
                      "params": param_count(specs),
                      "active_params": param_count(specs), "cfg": cfg,
                      "placement": ebc.plan.strategy,
                      "lookup_bytes": lookup_bytes,
                      "load_imbalance": ebc.plan.load_imbalance}

# ---------------------------------------------------------------------------
# run one cell
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape: Shape, multi_pod: bool,
             rules_overrides=None, config_overrides=None,
             skip_collectives: bool = False) -> dict[str, Any]:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = len(mesh.devices.flatten())
    rec: dict[str, Any] = {
        "arch": arch, "shape": shape.name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": n_chips,
        "ok": False,
    }
    t0 = time.time()
    try:
        # the mesh context makes with_sharding_constraint (shard_activation /
        # gather_weight) resolve logical axes — without it every activation
        # constraint silently no-ops and GSPMD guesses.
        with mesh:
            fn, args, meta = build_cell(arch, shape, mesh, rules_overrides,
                                        config_overrides)
            lowered = fn.lower(*args)
            rec["lower_s"] = round(time.time() - t0, 1)
            sa = StableHloAnalysis(lowered.as_text())
            cost = sa.cost()
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 1)
        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device": (mem.argument_size_in_bytes
                                + mem.output_size_in_bytes
                                + mem.temp_size_in_bytes
                                - mem.alias_size_in_bytes),
        }
        xla_cost = compiled.cost_analysis()
        rec["xla_flops_uncorrected"] = xla_cost.get("flops", -1.0)
        if skip_collectives:
            coll_by_type, coll_total = {}, 0.0
        else:
            ca = CollectiveAnalysis(compiled.as_text())
            coll_by_type, coll_total = ca.by_type, ca.total_bytes
            rec["collective_warnings"] = ca.warnings[:5]
            rec["per_chip_dot_flops"] = ca.dot_flops
            rec["compute_s_per_chip"] = ca.dot_flops / HW.peak_flops_bf16
            top = sorted(ca.op_log, key=lambda t: -t[1] * t[2])[:8]
            rec["top_collectives"] = [
                {"op": o, "bytes_per_call": b, "mult": m} for o, b, m in top]
        rec.update({
            "flops": cost.mxu_flops,
            "vpu_flops": cost.vpu_flops,
            "major_bytes": cost.major_bytes,
            "gather_bytes": cost.gather_bytes,
            "scatter_bytes": cost.scatter_bytes,
            "collectives_per_chip": coll_by_type,
            "collective_bytes_per_chip": coll_total,
            "model_flops": meta["model_flops"],
            "params": meta["params"],
            "active_params": meta["active_params"],
            "stablehlo_warnings": sa.warnings[:5],
        })
        for k in ("placement", "lookup_bytes", "load_imbalance",
                  "accum_steps", "hbm_estimate_gb"):
            if k in meta:
                rec[k] = meta[k]
        rec.update(roofline_terms(
            flops=cost.mxu_flops, bytes_hbm=cost.major_bytes,
            collective_bytes_per_chip=coll_total, chips=n_chips,
            model_flops=meta["model_flops"]))
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 1)
    return rec

# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all",
                    help="arch id, comma list, or 'all' / 'lm' / 'dlrm'")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--skip-collectives", action="store_true",
                    help="skip post-SPMD HLO parse (faster)")
    ap.add_argument("--force", action="store_true",
                    help="rerun cells that already have a result file")
    args = ap.parse_args()

    if args.arch == "all":
        archs = list(ARCHS) + list(DLRMS)
    elif args.arch == "lm":
        archs = list(ARCHS)
    elif args.arch == "dlrm":
        archs = list(DLRMS)
    else:
        archs = args.arch.split(",")

    os.makedirs(args.out, exist_ok=True)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    for arch in archs:
        shapes = shapes_for(arch)
        names = (list(shapes) if args.shape == "all"
                 else [s for s in args.shape.split(",") if s in shapes])
        for sname in names:
            for multi in meshes:
                tag = f"{arch}__{sname}__{'multi' if multi else 'single'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[skip] {tag}")
                    continue
                print(f"[run ] {tag}", flush=True)
                rec = run_cell(arch, shapes[sname], multi,
                               skip_collectives=args.skip_collectives)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                status = "OK" if rec["ok"] else "FAIL " + rec.get("error", "")
                print(f"[done] {tag}: {status} ({rec['total_s']}s)",
                      flush=True)


if __name__ == "__main__":
    main()
