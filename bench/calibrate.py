"""Readings that the limits of a train cell are set from, on the chip.

    python3 bench/calibrate.py --workload m2-train-b4096 --seeds 1 2 3 ... \
        [--witness] [--stand-in-seeds N]

In one process (each step compiles once), for every seed, against the
reference at the stated precision:

- `program`: the system under test, built as the launcher builds it and
  as a run of the cell drives it;
- `witness` (with --witness): the same step compiled under
  `jax.default_matmul_precision("highest")`, so that every matrix product
  runs at the precision the configuration states, also those that name
  none. Where the program departs from the configuration only there, its
  readings are those of a sound run: the lower readings.

And, on the first N seeds, the reference put in the program's place:

- `control`: every product at the next precision down, the three-pass
  bfloat16 split that Precision.HIGH computes;
- `bwd_control`: the forward as stated, the backward's products in three
  bfloat16 passes;
- `bwd_bf16`: the forward as stated, the backward's products in one
  bfloat16 pass;
- `half_batch`: the fault of a step that leaves out half of its batch.

A step that returns its state unchanged reads 1 on `grad_gap` and
`change_gap` and needs no run. One JSON line per seed, with every reading
(each loss, each leaf's norms) beside the numbers compared, and the
seconds each part took. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent

#: what stands in the program's place: (name, reference precision mode,
#: half batch)
STAND_INS = (("control", "bf16x3", False),
             ("bwd_control", "bwd_bf16x3", False),
             ("bwd_bf16", "bwd_bf16", False),
             ("half_batch", "highest", True))


def seed_readings(progs: dict, cfg: dict, traffic: dict, seed: int,
                  stand_ins=STAND_INS) -> dict:
    """The numbers of each program in `progs` (name -> train.Program) for
    one seed, and those of `stand_ins`."""
    import numpy as np

    from bench import model, reference, traffic as tr
    from bench.drivers import train

    pool = tr.train_pool(cfg, dict(traffic, pool=train.CHECK_STEPS), seed)
    got, secs = {}, {}
    for name, prog in progs.items():
        t = time.perf_counter()
        params, state, pipe, got[name] = train.first_steps(
            prog, seed, pool, traffic["prefetch"])
        pipe.pipe.close()
        del params, state
        gc.collect()
        secs[name] = time.perf_counter() - t
    rows = next(iter(progs.values())).rows
    w = model.init_weights(cfg, rows, seed)
    table = np.asarray(w.pop("emb")["mega"])

    def ref(name, mode, half):
        t = time.perf_counter()
        out = reference.train_readings(cfg, w, table, pool, mode, half)
        secs[name] = time.perf_counter() - t
        return out

    want = ref("reference", "highest", False)
    out = {"seed": seed}
    readings = dict(got, reference=want)
    for name in progs:
        out[name] = train.gaps(got[name], want)
    for name, mode, half in stand_ins:
        readings[name] = ref(name, mode, half)
        out[name] = train.gaps(readings[name], want)
    out["seconds"] = secs
    out["readings"] = readings
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--witness", action="store_true",
                    help="also read the step compiled at the stated "
                         "precision throughout")
    ap.add_argument("--stand-in-seeds", type=int, default=None,
                    help="read the control and the faults on only the "
                         "first N seeds (default: all)")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run
    from bench.drivers import train

    spec = run.load_json(ROOT / "BENCHMARK.json")
    w, cfg, traffic, _ = run.find_cell(spec, args.workload)
    run.device_or_exit(w["chips"])
    run.use_compile_cache()
    progs = {"program": train.Program(cfg, traffic["batch"])}
    if args.witness:
        progs["witness"] = train.Program(cfg, traffic["batch"],
                                         cfg["matmul_precision"])
    n = len(args.seeds) if args.stand_in_seeds is None \
        else args.stand_in_seeds
    for i, seed in enumerate(args.seeds):
        print(json.dumps(seed_readings(
            progs, cfg, traffic, seed, STAND_INS if i < n else ())),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
