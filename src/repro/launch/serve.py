"""Serving launcher: LM slot engine or the overload-robust DLRM tier.

CPU-sized demos:

    python -m repro.launch.serve --arch stablelm-1.6b --smoke --requests 8
    python -m repro.launch.serve --arch dlrm-m1 --smoke --requests 32
    python -m repro.launch.serve --arch dlrm-m1 --smoke --requests 32 --chaos

The DLRM mode replays seeded Zipf traffic through `DLRMServeEngine` and
prints a parseable SLO summary (`serve[dlrm]: key=value ...` — asserted in
tests/test_cli_e2e.py). `--chaos` arms a seeded FaultInjector on the
`serve.fetch` / `serve.admit` sites: the replay then demonstrates the
degrade-don't-die contract (docs/serving.md) instead of dying.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.configs.base import DLRMConfig
from repro.launch.compile_cache import use_compile_cache


def _serve_lm(cfg, args) -> None:
    from repro.models.lm import lm_param_specs
    from repro.nn.params import init_params
    from repro.serve.engine import Request, ServeEngine

    assert cfg.frontend is None, "serve demo drives token-only archs"
    params = init_params(lm_param_specs(cfg), jax.random.PRNGKey(0))
    engine = ServeEngine(params, cfg, batch_slots=args.slots,
                         max_len=args.max_len, rules={})

    rng = np.random.RandomState(0)
    t0 = time.time()
    for uid in range(args.requests):
        prompt = rng.randint(0, cfg.vocab_size,
                             size=(rng.randint(4, 12),)).astype(np.int32)
        engine.submit(Request(uid=uid, prompt=prompt,
                              max_new_tokens=args.new_tokens))
    done = engine.run_until_drained()
    dt = time.time() - t0
    total_tokens = sum(len(v) for v in done.values())
    print(f"served {len(done)} requests, {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens / dt:.1f} tok/s, {engine.steps_run} engine steps)")
    for uid in sorted(done)[:4]:
        print(f"  req {uid}: {done[uid][:8]}...")


def dlrm_serve_engine(cfg, cache_rows: int | None = None, seed: int = 0,
                      **engine_kw):
    """The DLRM serve tier as the launcher builds it: random weights from
    `seed`, a cached embedding tier of `cache_rows` slots (None: the
    placement plan's size) and a `DLRMServeEngine` (`engine_kw` are its
    options). The table is generated on the host, so the engine's
    capacity tier holds the only device copy of it. Returns (ebc, params,
    engine); params["emb"]["mega"] is that host table."""
    from repro.core.cache import CachedEmbeddingBagCollection
    from repro.core.dlrm import dlrm_param_specs
    from repro.core.embedding import EmbeddingBagCollection
    from repro.nn.params import init_params
    from repro.serve import DLRMServeEngine

    ebc = EmbeddingBagCollection.build(cfg, n_shards=1,
                                       strategy="replicated")
    specs = dlrm_param_specs(cfg, ebc)
    params = init_params({"bottom": specs["bottom"], "top": specs["top"]},
                         jax.random.PRNGKey(seed))
    spec = specs["emb"]["mega"]
    mega = np.random.default_rng(seed).standard_normal(spec.shape,
                                                       dtype=np.float32)
    mega *= np.float32(spec.scale)
    params["emb"] = {"mega": mega}
    cc = CachedEmbeddingBagCollection.build(cfg, cache_rows=cache_rows)
    return ebc, params, DLRMServeEngine(params, cfg, cc, **engine_kw)


def _serve_dlrm(cfg, args) -> None:
    from repro.data.synthetic import make_dlrm_batch
    from repro.serve import ServeRequest

    injector = retry = None
    if args.chaos:
        from repro.train.fault_tolerance import FaultInjector, RetryPolicy
        injector = FaultInjector.from_seed(
            args.chaos_seed, args.requests,
            sites=("serve.fetch", "serve.admit"), n_faults=3)
        retry = RetryPolicy(max_retries=1, backoff_s=1e-4)
    ebc, _, engine = dlrm_serve_engine(
        cfg, args.cache_rows, max_queue=args.max_queue,
        max_batch=args.max_batch, injector=injector, retry=retry)

    t0 = time.time()
    for uid in range(args.requests):
        raw = make_dlrm_batch(cfg, args.batch, step=uid,
                              zipf_alpha=args.zipf_alpha)
        idx = np.asarray(ebc.offset_indices(np.asarray(raw["idx"])))
        engine.submit(ServeRequest(uid, raw["dense"], idx))
        # offered load: submit a burst, then let the engine catch up
        if (uid + 1) % args.burst == 0:
            engine.step()
    engine.run()
    dt = time.time() - t0
    m = engine.metrics.snapshot()
    print(f"serve[dlrm]: served={int(m['served'])} shed={int(m['shed'])} "
          f"degraded={int(m['degraded'])} "
          f"hit_rate={engine.cache_stats.hit_rate:.4f} "
          f"shed_rate={m['shed_rate']:.4f} "
          f"degraded_fraction={m['degraded_fraction']:.4f} "
          f"p50_ms={m['p50_latency'] * 1e3:.3f} "
          f"p99_ms={m['p99_latency'] * 1e3:.3f} "
          f"batches={int(m['batches'])} breaker={engine.breaker.state} "
          f"wall_s={dt:.2f}")
    if args.chaos:
        print(f"  chaos: fired={injector.fired} "
              f"transitions={engine.breaker.transitions}")


def main():
    """Entry point: dispatch on the arch's config type (LM vs DLRM)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    # LM knobs
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=16)
    # DLRM knobs
    ap.add_argument("--batch", type=int, default=4,
                    help="examples per DLRM request")
    ap.add_argument("--max-batch", type=int, default=16,
                    help="engine batch slots (examples per dispatch)")
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--cache-rows", type=int, default=None,
                    help="device cache slots (default: the placement "
                         "plan's size for the arch)")
    ap.add_argument("--burst", type=int, default=4,
                    help="requests submitted per engine step (offered load)")
    ap.add_argument("--zipf-alpha", type=float, default=1.05)
    ap.add_argument("--chaos", action="store_true",
                    help="arm a seeded FaultInjector on serve.fetch/admit")
    ap.add_argument("--chaos-seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    if isinstance(cfg, DLRMConfig):
        _serve_dlrm(cfg, args)
    else:
        _serve_lm(cfg, args)


if __name__ == "__main__":
    main()
