"""The benchmark's own CPU checks: run with `python -m pytest bench/tests`
from the checkout's root (JAX on the CPU)."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


@pytest.fixture
def tiny():
    """A small stand-in for the train cell: dlrm-m3's file with three small
    tables and narrow MLPs, batch 64, and the m3 cell's limits."""
    cfg = json.loads((ROOT / "bench/configs/dlrm-m3.json").read_text())
    cfg.update(hash_sizes=[1000, 3000, 2000], mean_lookups=[4, 10, 2],
               n_sparse_features=3, n_dense_features=16,
               bottom_mlp=[32, 64], top_mlp=[32, 1])
    traffic = json.loads(
        (ROOT / "bench/traffic/train-b2048.json").read_text())
    traffic.update(batch=64, pool=4)
    limits = json.loads(
        (ROOT / "bench/limits/m3-train-b2048.json").read_text())
    entry = {"name": "m3-train-b2048", "config": "dlrm-m3",
             "traffic": "train-b2048", "chips": 1}
    return entry, cfg, traffic, limits
