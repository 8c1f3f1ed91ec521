"""Run one cell of BENCHMARK.json on the chip and print its result line.

    python3 bench/run.py --workload m2-train-b4096 --seed 7 --seconds 30 \
        --trace 0

The harness is driven by data, found by the names in BENCHMARK.json:

- a configuration `<config>` is `bench/configs/<config>.json`;
- a traffic mix `<traffic>` is `bench/traffic/<traffic>.json`, whose
  "driver" names the module `bench/drivers/<driver>.py` that runs it;
- a cell's limits for the comparison that decides `correct` are
  `bench/limits/<workload>.json`;
- a per-layer metric `<name>` is read by `bench/metrics/<name>.py`'s
  `read(record)`, which returns None where it finds nothing to read.

With `--trace 0` the result carries the cell's end-to-end metrics; with
`--trace 1` the window is traced and the result carries its per-layer
metrics. The last line of standard output is the JSON result; the last
lines of standard error are the numbers compared, each with its limit.
Without a TPU, or with fewer chips than the cell asks for, or without the
system under test beside the benchmark, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()        # set-up counts from process start

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def fail(msg: str, code: int = 2):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        fail(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        fail(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """What a driver is given for one run."""

    name: str
    cfg: dict
    traffic: dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    t_start: float
    tmpdir: str

    def peak_bytes(self) -> int:
        """Peak bytes in use on the fullest chip so far (0 where the
        backend keeps no count, as the CPU's)."""
        import jax
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.devices()[:self.chips])


def find_cell(spec: dict, workload: str):
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        fail(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg = load_json(BENCH / "configs" / f"{w['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{workload}.json")
    return w, cfg, traffic, limits


def cell_metrics(spec: dict, workload: str, trace: bool) -> list[dict]:
    """The metric entries this cell reports in this mode."""
    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in spec["end_to_end"] if mine(m)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if mine(m) and m["moves"] in names]


def device_or_exit(chips: int) -> list:
    """The chips JAX sees; exits non-zero where they are not TPUs or too
    few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"needs a TPU, JAX found {devs[0].platform!r}", 3)
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips, JAX sees {len(devs)}", 3)
    return devs[:chips]


def use_compile_cache() -> None:
    """The program's persistent compilation cache (`use_compile_cache()`:
    JAX_COMPILATION_CACHE_DIR, else `.jax_cache/` in the checkout), kept
    for every program however short its compile."""
    import jax

    from repro.launch.compile_cache import use_compile_cache as program_cache
    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(spec: dict, workload: str, seed: int, seconds: float,
             trace: bool, devices, t_start: float = T_START,
             files=None) -> dict:
    """Run the cell on `devices`; returns the result dict (checks last).
    `files` stands in for the cell's (workload entry, configuration,
    traffic, limits) as `find_cell` reads them."""
    w, cfg, traffic, limits = files or find_cell(spec, workload)
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py",
                         f"bench_driver_{traffic['driver']}")
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        cell = Cell(workload, cfg, traffic, w["chips"], seed, seconds,
                    trace, t_start, tmp)
        out = driver.run(cell)
    numbers = out["numbers"]
    if set(numbers) != set(limits["limits"]):
        fail(f"compared {sorted(numbers)} but limits name "
             f"{sorted(limits['limits'])}")
    checks = {k: {"value": v, "limit": limits["limits"][k]}
              for k, v in numbers.items()}
    correct = (out["failed"] == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values()))

    metrics = {}
    record = dict(out["record"], cfg=cfg, traffic=traffic,
                  device_kind=devices[0].device_kind)
    for m in cell_metrics(spec, workload, trace):
        if not trace:
            value = out["e2e"].get(m["name"])
        else:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = out["record"]["busy_s"]
        device["window_s"] = out["record"]["window_s"]
        result["breakdown"] = out["record"]["breakdown"]
    result["checks"] = checks
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    spec = load_json(ROOT / "BENCHMARK.json")
    w, _, _, _ = find_cell(spec, args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        fail("the system under test (src/repro) is not in this checkout")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    devices = device_or_exit(w["chips"])
    use_compile_cache()
    result = run_cell(spec, args.workload, args.seed, args.seconds,
                      bool(args.trace), devices)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
