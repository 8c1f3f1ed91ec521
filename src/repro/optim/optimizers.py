"""Dense optimizers (the embedding path uses kernels/sparse_update).

Functional, optax-shaped but dependency-free:
  opt = adamw(lr=...); state = opt.init(params)
  new_params, new_state = opt.apply(params, grads, state, step)

The paper's production split (section IV, Fig. 4): MLP ("dense") parameters on
dense PSs with AdaGrad/SGD; embedding rows on sparse PSs with row-wise
AdaGrad. `adamw` is included for the LM-family archs.

Optimizer state mirrors the parameter pytree leaf-for-leaf, so parameter
PartitionSpecs apply verbatim to the state (ZeRO-style sharded optimizer
state falls out of fsdp param sharding for free).
"""
from __future__ import annotations

import dataclasses
from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable[[Any], Any]
    apply: Callable[[Any, Any, Any, jax.Array], tuple[Any, Any]]
    name: str = "opt"


def clip_by_global_norm(grads, max_norm: float):
    """Returns (clipped_grads, pre_clip_norm)."""
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in leaves))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads), norm


def sgd(lr: float, momentum: float = 0.0) -> Optimizer:
    def init(params):
        if momentum == 0.0:
            return ()
        return jax.tree.map(jnp.zeros_like, params)

    def apply(params, grads, state, step):
        del step
        if momentum == 0.0:
            new = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype),
                               params, grads)
            return new, state
        new_state = jax.tree.map(
            lambda m, g: momentum * m + g.astype(m.dtype), state, grads)
        new = jax.tree.map(lambda p, m: p - lr * m.astype(p.dtype),
                           params, new_state)
        return new, new_state

    return Optimizer(init, apply, "sgd")


def adagrad(lr: float, eps: float = 1e-8) -> Optimizer:
    """Dense AdaGrad — the paper's dense-PS optimizer."""
    def init(params):
        return jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def apply(params, grads, state, step):
        del step
        new_state = jax.tree.map(
            lambda s, g: s + jnp.square(g.astype(jnp.float32)), state, grads)
        new = jax.tree.map(
            lambda p, g, s: (p.astype(jnp.float32)
                             - lr * g.astype(jnp.float32)
                             * jax.lax.rsqrt(s + eps)).astype(p.dtype),
            params, grads, new_state)
        return new, new_state

    return Optimizer(init, apply, "adagrad")


def adamw(lr: float, b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
          weight_decay: float = 0.0,
          clip_norm: float | None = 1.0) -> Optimizer:
    """AdamW with decoupled weight decay and optional global-norm clipping,
    fp32 moments regardless of param dtype."""
    def init(params):
        z = lambda p: jnp.zeros(p.shape, jnp.float32)  # noqa: E731
        return {"m": jax.tree.map(z, params), "v": jax.tree.map(z, params)}

    def apply(params, grads, state, step):
        if clip_norm is not None:
            grads, _ = clip_by_global_norm(grads, clip_norm)
        t = step.astype(jnp.float32) + 1.0
        c1 = 1.0 - jnp.power(b1, t)
        c2 = 1.0 - jnp.power(b2, t)
        new_m = jax.tree.map(
            lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32),
            state["m"], grads)
        new_v = jax.tree.map(
            lambda v, g: b2 * v + (1 - b2)
            * jnp.square(g.astype(jnp.float32)),
            state["v"], grads)

        def upd(p, m, v):
            u = (m / c1) * jax.lax.rsqrt(v / c2 + eps)
            if weight_decay:
                u = u + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * u).astype(p.dtype)

        new = jax.tree.map(upd, params, new_m, new_v)
        return new, {"m": new_m, "v": new_v}

    return Optimizer(init, apply, "adamw")
