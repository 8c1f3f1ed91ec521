"""Plain reference of the DLRM train step, for the comparison that decides
`correct`. Imports nothing of the program.

The model (arXiv:2011.05497 Fig. 3, the original DLRM): bottom MLP over
the dense features; every sparse feature sum-pools its bag of table rows;
the dot interaction stacks the bottom output with the pooled rows, takes
every strictly-lower-triangle pairwise dot product and concatenates them
with the bottom output; the top MLP ends in one CTR logit; the loss is the
mean binary cross-entropy. ReLU between layers, none after the last.

The optimizer is the launcher's: dense AdaGrad (s += g^2; p -= lr g /
sqrt(s + eps)) on the MLPs, and row-wise AdaGrad on the tables, where a
row's gradient is the sum over every lookup slot that reads it of its
bag's pooled gradient (acc += mean(g^2); row -= lr g / sqrt(acc + eps)).

It runs in blocks so that it fits beside nothing else on the chip: the
table stays on the host and only the rows a step reads go to the device;
pooling and the row-gradient sums run over fixed-size chunks of lookup
slots.

`mode` picks the matrix products' precision:
  "highest"  float32 (Precision.HIGHEST), what the configuration states;
  "bf16x3"   the three-pass bfloat16 split (hi*hi + hi*lo + lo*hi), what
             TPU's Precision.HIGH computes, spelled out so that the control
             reads the same on any backend;
  "bf16"     one bfloat16 pass;
  "bwd_<m>"  the forward at "highest" and the backward's products (each
             matrix product's two transposes) at <m>: a step whose
             backward alone runs below the stated precision.
`half=True` plants the fault of a step that leaves out the second half of
its batch and takes the mean over the rest.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: lookup slots per chunk of the pooling and of the row-gradient sums
SLOT_CHUNK = 1 << 20
#: a step's rows go to the device padded to a power of two at least this
#: long, so that the programs compile once per cell and not once per step
MIN_ROWS = 1 << 17


def padded(n: int) -> int:
    return max(MIN_ROWS, 1 << (n - 1).bit_length())


def matmul(x, w, mode: str, spec: str = "...i,io->...o"):
    """einsum in the precision `mode` names, accumulated in float32."""
    f32 = jnp.float32
    if mode.startswith("bwd_"):
        return _low_backward(x, w, mode[4:], spec)
    if mode == "highest":
        return jnp.einsum(spec, x, w, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=f32)

    def one(a, b):
        return jnp.einsum(spec, a, b, preferred_element_type=f32)

    def split(a):
        # reduce_precision, not a cast round trip, which XLA may drop
        hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)

    if mode == "bf16":
        return one(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16))
    if mode == "bf16x3":
        (xh, xl), (wh, wl) = split(x), split(w)
        return one(xh, wh) + one(xh, wl) + one(xl, wh)
    raise ValueError(f"unknown precision mode {mode!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _low_backward(x, w, mode, spec):
    return matmul(x, w, "highest", spec)


def _low_backward_fwd(x, w, mode, spec):
    return matmul(x, w, "highest", spec), (x, w)


def _low_backward_bwd(mode, spec, res, g):
    x, w = res
    ins, out = spec.split("->")
    a, b = ins.split(",")
    return (matmul(g, w, mode, f"{out},{b}->{a}"),
            matmul(x, g, mode, f"{a},{out}->{b}"))


_low_backward.defvjp(_low_backward_fwd, _low_backward_bwd)


def mlp(layers, x, mode):
    for i, p in enumerate(layers):
        x = matmul(x, p["w"], mode) + p["b"]
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


def interact(bottom, pooled, mode):
    z = jnp.concatenate([bottom[:, None, :], pooled], axis=1)
    s = matmul(z, z, mode, "bfd,bgd->bfg")
    rows, cols = np.tril_indices(z.shape[1], -1)
    return jnp.concatenate([bottom, s[:, rows, cols]], axis=-1)


def logits(dense_params, dense_x, pooled, mode):
    bot = mlp(dense_params["bottom"], dense_x, mode)
    return mlp(dense_params["top"], interact(bot, pooled, mode), mode)[:, 0]


@functools.partial(jax.jit, static_argnames=("mode", "half"))
def dense_loss_and_grads(dense_params, dense_x, pooled, label, mode, half):
    """(loss, (d loss / d MLP params, d loss / d pooled))."""
    b = label.shape[0]
    weight = (jnp.arange(b) < b // 2).astype(jnp.float32) if half \
        else jnp.ones((b,), jnp.float32)

    def loss_fn(dp, pl):
        z = logits(dp, dense_x, pl, mode)
        bce = (jnp.maximum(z, 0) - z * label
               + jnp.log1p(jnp.exp(-jnp.abs(z))))
        return jnp.sum(bce * weight) / jnp.sum(weight)

    return jax.value_and_grad(loss_fn, argnums=(0, 1))(dense_params, pooled)


@functools.partial(jax.jit, static_argnames=("n_bags",))
def _pool_chunk(acc, rows, slot_row, slot_bag, n_bags):
    # slot_bag == n_bags marks a padding slot; it lands in a dropped bag
    vals = jnp.take(rows, slot_row, axis=0)
    return acc + jax.ops.segment_sum(vals, slot_bag, n_bags + 1)


@functools.partial(jax.jit, static_argnames=("n_rows",))
def _grad_chunk(acc, g_bags, slot_row, slot_bag, n_rows):
    vals = jnp.take(g_bags, slot_bag, axis=0)   # row n_bags of g_bags is 0
    return acc + jax.ops.segment_sum(vals, slot_row, n_rows)


def _slot_chunks(slot_row, slot_bag, n_bags):
    n = slot_row.shape[0]
    pad = (-n) % SLOT_CHUNK
    slot_row = np.concatenate([slot_row, np.zeros(pad, np.int32)])
    slot_bag = np.concatenate([slot_bag, np.full(pad, n_bags, np.int32)])
    for lo in range(0, n + pad, SLOT_CHUNK):
        yield (jnp.asarray(slot_row[lo:lo + SLOT_CHUNK]),
               jnp.asarray(slot_bag[lo:lo + SLOT_CHUNK]))


def pool(rows, slot_row, slot_bag, n_bags):
    """(n_bags, d) sums of `rows[slot_row]` per bag, chunk by chunk."""
    d = rows.shape[1]
    acc = jnp.zeros((n_bags + 1, d), jnp.float32)
    for r, b in _slot_chunks(slot_row, slot_bag, n_bags):
        acc = _pool_chunk(acc, rows, r, b, n_bags)
    return acc[:n_bags]


def row_grads(g_bags, slot_row, slot_bag, n_rows):
    """(n_rows, d): each row's sum of the pooled gradients of the bags
    that read it, once per reading slot."""
    g = jnp.concatenate([g_bags, jnp.zeros((1, g_bags.shape[1]),
                                           g_bags.dtype)])
    acc = jnp.zeros((n_rows, g_bags.shape[1]), jnp.float32)
    for r, b in _slot_chunks(slot_row, slot_bag, g_bags.shape[0]):
        acc = _grad_chunk(acc, g, r, b, n_rows)
    return acc


@jax.jit
def _adagrad(p, g, s, lr, eps):
    s = s + jnp.square(g)
    return p - lr * g * jax.lax.rsqrt(s + eps), s


@jax.jit
def _rowwise_adagrad(rows, g, acc, lr, eps):
    acc = acc + jnp.mean(jnp.square(g), axis=1)
    return rows - lr * g * jax.lax.rsqrt(acc + eps)[:, None], acc


def train_readings(cfg: dict, weights: dict, table: np.ndarray,
                   batches: list[dict], mode: str = "highest",
                   half: bool = False) -> dict:
    """Run len(batches) reference steps from `weights` (MLP leaves on the
    device) and the host `table` (rows, d). Returns per-step `losses`, the
    first step's gradient norm per leaf (`grad`) and the norm of each
    leaf's change over all the steps (`change`), keyed as
    model.leaf_names keys them."""
    from bench import model

    opt = cfg["optimizer"]
    dense = {"bottom": weights["bottom"], "top": weights["top"]}
    state = jax.tree.map(jnp.zeros_like, dense)
    dense0 = jax.tree.map(jnp.copy, dense)
    touched = np.unique(np.concatenate(
        [b["idx"][b["idx"] >= 0] for b in batches]))
    rows0 = table[touched]                    # the rows any step reads
    rows = rows0.copy()
    acc = np.zeros(len(touched), np.float32)
    f, lk = cfg["n_sparse_features"], cfg["truncation"]
    losses, grad = [], None
    for k, batch in enumerate(batches):
        idx = batch["idx"]
        b = idx.shape[0]
        valid = idx.reshape(-1) >= 0
        step_rows, inv = np.unique(idx.reshape(-1)[valid],
                                   return_inverse=True)
        slot_bag = (np.nonzero(valid)[0] // lk).astype(np.int32)
        at = np.searchsorted(touched, step_rows)
        u, up = len(step_rows), padded(len(step_rows))
        r_dev = jnp.asarray(np.pad(rows[at], ((0, up - u), (0, 0))))
        pooled = pool(r_dev, inv.astype(np.int32), slot_bag, b * f)
        loss, (g_dense, g_pooled) = dense_loss_and_grads(
            dense, jnp.asarray(batch["dense"]),
            pooled.reshape(b, f, -1), jnp.asarray(batch["label"]),
            mode=mode, half=half)
        g_rows = row_grads(g_pooled.reshape(b * f, -1),
                           inv.astype(np.int32), slot_bag, up)
        if k == 0:
            norms = [float(jnp.linalg.norm(g))
                     for g in model.flat_dense(g_dense)]
            norms.append(float(jnp.linalg.norm(g_rows)))
            grad = dict(zip(model.leaf_names(dense | {"emb": None}), norms))
        out = jax.tree.map(
            lambda p, g, s: _adagrad(p, g, s, opt["dense_lr"],
                                     opt["dense_eps"]),
            dense, g_dense, state)
        dense = jax.tree.map(lambda _, o: o[0], dense, out)
        state = jax.tree.map(lambda _, o: o[1], dense, out)
        new_rows, new_acc = _rowwise_adagrad(
            r_dev, g_rows, jnp.asarray(np.pad(acc[at], (0, up - u))),
            opt["sparse_lr"], opt["sparse_eps"])
        rows[at] = np.asarray(new_rows)[:u]
        acc[at] = np.asarray(new_acc)[:u]
        losses.append(float(loss))
    change = [float(jnp.linalg.norm(p - p0)) for p, p0 in
              zip(model.flat_dense(dense), model.flat_dense(dense0))]
    change.append(float(np.sqrt(np.sum(np.square(
        rows.astype(np.float64) - rows0)))))
    return {"losses": losses, "grad": grad,
            "change": dict(zip(model.leaf_names(dense | {"emb": None}),
                               change))}
