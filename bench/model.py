"""The benchmark's DLRM weights, made on the device from the seed.

The same function makes the weights that the system under test is given
and, after its window, the weights that the reference starts from; the
reference takes nothing that the program made. Leaves follow the layout
the program takes (`bottom`/`top` lists of {"w": (in, out), "b": (out,)},
`emb.mega` the row-concatenated tables), and are drawn as its own
initialiser draws them: w ~ N(0, 1/fan_in), b = 0, table ~ N(0, 1/d).

The table is drawn in blocks of `BLOCK` rows, each from its own key, so
that `table_change_sq` can redraw any block without holding a second
table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 128
#: blocks per chunk when the table is drawn, or redrawn for a comparison
CHUNK_BLOCKS = 1024


def mlp_dims(cfg: dict) -> tuple[list[tuple[int, int]], list[tuple[int, int]]]:
    """(in, out) of every bottom and top layer."""
    f = cfg["n_sparse_features"] + 1
    d = cfg["embed_dim"]
    top_in = d + f * (f - 1) // 2 if cfg["interaction"] == "dot" \
        else d + cfg["n_sparse_features"] * d

    def dims(widths, d_in):
        out = []
        for w in widths:
            out.append((d_in, w))
            d_in = w
        return out

    return dims(cfg["bottom_mlp"], cfg["n_dense_features"]), \
        dims(cfg["top_mlp"], top_in)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (PRNGKey keeps 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def _table_blocks(key, first_block, n_blocks: int, d: int) -> jax.Array:
    ids = first_block + jnp.arange(n_blocks)
    # each block is drawn as (d, BLOCK), lane-dense like the table's own
    # rows-minor layout, so no padded copy is made
    blocks = jax.vmap(lambda i: jax.random.normal(
        jax.random.fold_in(key, i), (d, BLOCK), jnp.float32))(ids)
    return (jnp.swapaxes(blocks, 1, 2).reshape(n_blocks * BLOCK, d)
            * (1.0 / np.sqrt(d)))


def _chunks(rows: int):
    """(chunk rows, count, start of chunk i): the last chunk is moved back
    to end at `rows`, overlapping the one before it."""
    chunk = min(CHUNK_BLOCKS * BLOCK, rows)
    return chunk, -(-rows // chunk), \
        lambda i: jnp.minimum(i * chunk, rows - chunk)


def _fill_table(key, rows: int, d: int) -> jax.Array:
    """The whole table, drawn chunk by chunk into one buffer so that no
    temporary of the table's size is made."""
    chunk, n, start = _chunks(rows)

    def body(i, buf):
        s = start(i)
        return jax.lax.dynamic_update_slice_in_dim(
            buf, _table_blocks(key, s // BLOCK, chunk // BLOCK, d), s, 0)

    return jax.lax.fori_loop(0, n, body, jnp.zeros((rows, d), jnp.float32))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _init(dims_b, dims_t, table_shape, key):
    k_b, k_t, k_e = jax.random.split(key, 3)

    def layers(dims, k):
        keys = jax.random.split(k, len(dims))
        return [{"w": jax.random.normal(kk, (i, o), jnp.float32)
                 / np.sqrt(i), "b": jnp.zeros((o,), jnp.float32)}
                for kk, (i, o) in zip(keys, dims)]

    return {"bottom": layers(dims_b, k_b), "top": layers(dims_t, k_t),
            "emb": {"mega": _fill_table(k_e, *table_shape)}}


def table_key(seed: int) -> jax.Array:
    """The key the table's blocks are drawn from."""
    return jax.random.split(seed_key(seed), 3)[2]


def init_weights(cfg: dict, total_rows: int, seed: int) -> dict:
    """All weights in one jitted call on the default device."""
    assert total_rows % BLOCK == 0, total_rows
    dims_b, dims_t = mlp_dims(cfg)
    return _init(tuple(dims_b), tuple(dims_t),
                 (total_rows, cfg["embed_dim"]), seed_key(seed))


@jax.jit
def table_change_sq(mega: jax.Array, key: jax.Array) -> jax.Array:
    """sum((mega - initial table)^2), the initial table redrawn chunk by
    chunk from `key` (no second table is held)."""
    rows, d = mega.shape
    chunk, n, start = _chunks(rows)

    def body(i, acc):
        s = start(i)
        got = jax.lax.dynamic_slice_in_dim(mega, s, chunk, 0)
        want = _table_blocks(key, s // BLOCK, chunk // BLOCK, d)
        row = s + jnp.arange(chunk)
        sq = jnp.sum(jnp.square(got - want), axis=1)
        return acc + jnp.sum(jnp.where(row >= i * chunk, sq, 0.0))

    return jax.lax.fori_loop(0, n, body, jnp.zeros((), jnp.float32))


def leaf_names(tree: dict) -> list[str]:
    """'bottom.0.w', ..., 'emb.mega' in the order of `flat_leaves`."""
    names = []
    for part in ("bottom", "top"):
        for i, layer in enumerate(tree[part]):
            names += [f"{part}.{i}.{k}" for k in sorted(layer)]
    return names + ["emb.mega"]


def flat_dense(tree: dict) -> list:
    """The MLP leaves of a weights-shaped tree, in `leaf_names` order."""
    return [layer[k] for part in ("bottom", "top")
            for layer in tree[part] for k in sorted(layer)]
