import numpy as np

from bench import traffic


def test_layout_matches_the_programs_plan():
    from repro.configs.base import DLRMConfig
    from repro.core.embedding import EmbeddingBagCollection
    sizes = (1000, 3001, 7, 129)
    cfg = DLRMConfig(name="t", n_dense_features=4, n_sparse_features=4,
                     hash_sizes=sizes, mean_lookups=(1, 2, 3, 4))
    plan = EmbeddingBagCollection.build(cfg, n_shards=1).plan
    offsets, total = traffic.table_layout(sizes)
    assert offsets == list(plan.table_offsets)
    assert total == plan.total_rows


def test_same_seed_same_batches_and_ids_in_range(tiny):
    _, cfg, tr, _ = tiny
    seed = 2**31 + 12345
    a = traffic.train_pool(cfg, tr, seed)
    b = traffic.train_pool(cfg, tr, seed)
    c = traffic.train_pool(cfg, tr, seed + 1)
    assert len(a) == tr["pool"]
    for x, y in zip(a, b):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])
    assert not np.array_equal(a[0]["idx"], c[0]["idx"])
    offsets, _ = traffic.table_layout(cfg["hash_sizes"])
    for batch in a:
        idx = batch["idx"]
        assert idx.shape == (tr["batch"], 3, cfg["truncation"])
        lens = (idx >= 0).sum(axis=2)
        assert lens.min() >= 1 and lens.max() <= cfg["truncation"]
        for t, (o, h) in enumerate(zip(offsets, cfg["hash_sizes"])):
            col = idx[:, t][idx[:, t] >= 0]
            assert col.min() >= o and col.max() < o + h
        # a bag's pads follow its ids
        assert np.all(np.diff((idx >= 0).astype(int), axis=2) <= 0)


def test_zipf_head_is_hottest(tiny):
    _, cfg, tr, _ = tiny
    rng = traffic.seeded_rng(3, 0)
    cfg = dict(cfg, hash_sizes=[1000] * 3, mean_lookups=[32] * 3)
    b = traffic.draw_batch(rng, cfg, 2000, 1.05, [0, 1000, 2000], {})
    ids = b["idx"][:, 0][b["idx"][:, 0] >= 0]
    counts = np.bincount(ids, minlength=1000)
    p = np.arange(1, 1001, dtype=float) ** -1.05
    p /= p.sum()
    # rank 0's share within 5 standard errors of the bounded Zipf's
    n = len(ids)
    assert abs(counts[0] / n - p[0]) < 5 * np.sqrt(p[0] * (1 - p[0]) / n)


def test_unique_rows(tiny):
    _, cfg, tr, _ = tiny
    batch = traffic.train_pool(cfg, dict(tr, pool=1), 7)[0]
    want = sorted({int(v) for v in batch["idx"].ravel() if v >= 0})
    assert traffic.unique_rows(batch).tolist() == want
