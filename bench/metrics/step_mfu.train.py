"""Whole train step's share of the chip's bf16 peak: model FLOPs per
example (bench/flops.py) times examples per second over the window."""
from bench import flops


def read(rec):
    if not rec.get("examples"):
        return None
    rate = rec["examples"] / rec["window_s"]
    peak = flops.peaks(rec["device_kind"])["bf16_flops_per_s"]
    return 100.0 * rate * flops.train_flops_per_example(rec["cfg"]) / peak
