"""Operations and bytes the DLRM step needs, from the configuration's
widths and the batch's counts alone; and the chip's peaks.

These are the least work of the algorithm, not what a kernel happens to
move: a share of a roofline built on them cannot pass 100% unless a time
leaves out part of the work.
"""
from __future__ import annotations

import json
import pathlib

F32 = 4
I32 = 4


def peaks(device_kind: str) -> dict:
    """The peaks of `device_kind` from bench/peaks.json; a device that is
    not in the table is an error."""
    table = json.loads((pathlib.Path(__file__).parent / "peaks.json")
                       .read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]


def n_features(cfg: dict) -> int:
    """Vectors the dot interaction pairs: the bottom output and one pooled
    vector per table."""
    return cfg["n_sparse_features"] + 1


def mlp_flops(cfg: dict) -> int:
    """Forward FLOPs per example of both MLPs: 2 * sum(in * out)."""
    from bench.model import mlp_dims
    bottom, top = mlp_dims(cfg)
    return 2 * sum(i * o for i, o in bottom + top)


def interaction_flops(cfg: dict) -> int:
    """Forward FLOPs per example of the dot interaction: one d-long dot
    product per strictly-lower-triangle pair."""
    f = n_features(cfg)
    return 2 * (f * (f - 1) // 2) * cfg["embed_dim"]


def train_flops_per_example(cfg: dict) -> int:
    """Model FLOPs of one trained example: the forward, and a backward
    counted as twice the forward."""
    return 3 * (mlp_flops(cfg) + interaction_flops(cfg))


def gather_bytes(cfg: dict, unique_rows: int) -> int:
    """Embedding forward: every unique row read once and written once to
    the compact slab, and its row id read."""
    return unique_rows * (2 * cfg["embed_dim"] * F32 + I32)


def sparse_update_bytes(cfg: dict, unique_rows: int) -> int:
    """Row-wise AdaGrad apply per unique row: the gradient sum, the row
    and the accumulator read; the row and the accumulator written; the
    row id read."""
    d = cfg["embed_dim"] * F32
    return unique_rows * (3 * d + 2 * F32 + I32)


def interaction_work(cfg: dict, batch: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one forward interaction call: the stacked
    vectors read, the triangle written."""
    f = n_features(cfg)
    pairs = f * (f - 1) // 2
    return (batch * interaction_flops(cfg),
            batch * (f * cfg["embed_dim"] + pairs) * F32)


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline's least time for the work, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
