"""The benchmark's traffic generator: DLRM click-log batches from a seed.

One general generator for every traffic file under `bench/traffic/`: a
file gives the numbers (batch size, Zipf exponent, pool size), this module
draws the batches. It is the benchmark's own copy of the repo's
`make_dlrm_batch(..., zipf_alpha=...)` (data/synthetic.py), vectorised per
table and drawing only the lookup slots that a bag uses:

- each bag's length is Poisson around its table's mean lookups, clipped to
  [1, truncation];
- each id is drawn from the bounded Zipf(alpha) over the table's rows,
  p(r) ~ (r + 1)^-alpha, row 0 the hottest, by inverse CDF;
- ids are offset into the mega table by `table_layout`, -1 pads the bag;
- labels follow the same planted logistic model, so the loss has signal.

The same seed gives the same batches on every machine; every seed gives
the same batch size, table count and length distribution.
"""
from __future__ import annotations

import numpy as np

#: rows per table are padded to this many, and the mega table to ROW_TILE
#: rows: the layout the system under test keeps (checked at set-up)
TABLE_PAD = 8
ROW_TILE = 128


def table_layout(hash_sizes) -> tuple[list[int], int]:
    """Row offset of each table in the mega table, and its padded height."""
    offsets, total = [], 0
    for h in hash_sizes:
        offsets.append(total)
        total += -(-int(h) // TABLE_PAD) * TABLE_PAD
    return offsets, -(-total // ROW_TILE) * ROW_TILE


def _zipf_cdf(h: int, alpha: float, cache: dict) -> np.ndarray:
    key = (h, alpha)
    if key not in cache:
        p = np.arange(1, h + 1, dtype=np.float64) ** (-alpha)
        c = np.cumsum(p)
        cache[key] = c / c[-1]
    return cache[key]


def seeded_rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for `stream` of `seed` (any non-negative int)."""
    return np.random.default_rng([int(seed), int(stream)])


def draw_batch(rng: np.random.Generator, cfg: dict, batch: int,
               alpha: float, offsets, cdfs: dict) -> dict[str, np.ndarray]:
    """One batch: dense (B, n_dense) f32, idx (B, F, L) int32 offset
    mega-table rows with -1 pads, label (B,) f32."""
    f, trunc = cfg["n_sparse_features"], cfg["truncation"]
    dense = rng.standard_normal((batch, cfg["n_dense_features"]),
                                dtype=np.float32)
    idx = np.full((batch, f, trunc), -1, np.int32)
    planted = np.zeros(batch, np.float64)
    slot = np.arange(trunc)[None, :]
    for t in range(f):
        h = cfg["hash_sizes"][t]
        lens = np.clip(rng.poisson(min(cfg["mean_lookups"][t], trunc),
                                   size=batch), 1, trunc)
        mask = slot < lens[:, None]
        vals = np.searchsorted(_zipf_cdf(h, alpha, cdfs),
                               rng.random(int(mask.sum())))
        vals = np.minimum(vals, h - 1)
        col = np.full((batch, trunc), -1, np.int64)
        col[mask] = vals
        planted += col[:, 0] % 7 - 3
        idx[:, t, :] = np.where(mask, col + offsets[t], -1)
    score = dense[:, :8].mean(axis=1) * 2.0 + planted * 0.3
    prob = 1.0 / (1.0 + np.exp(-score))
    label = (rng.random(batch) < prob).astype(np.float32)
    return {"dense": dense, "idx": idx, "label": label}


def train_pool(cfg: dict, traffic: dict, seed: int) -> list[dict]:
    """`traffic["pool"]` distinct batches of `traffic["batch"]` examples:
    what the window cycles through."""
    offsets, _ = table_layout(cfg["hash_sizes"])
    rng = seeded_rng(seed, 0)
    cdfs: dict = {}
    return [draw_batch(rng, cfg, traffic["batch"], traffic["zipf_alpha"],
                       offsets, cdfs) for _ in range(traffic["pool"])]


def unique_rows(batch: dict) -> np.ndarray:
    """Sorted distinct mega-table rows a batch looks up."""
    idx = batch["idx"]
    return np.unique(idx[idx >= 0])
