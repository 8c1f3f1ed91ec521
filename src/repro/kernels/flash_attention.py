"""Pallas TPU kernel: causal flash attention (the prefill_32k hot spot).

TPU-native design: grid (batch, heads, q_blocks); the (block_q, dh) query
tile and the fp32 running (max, denom, acc) live in VMEM; K/V stay in HBM
(`MemorySpace.ANY`) and stream through double-buffered DMA in (block_k, dh)
tiles. The causal bound truncates the kv loop per q block (the static-skip
that the XLA fallback only gets via `causal_skip` unrolling). dh is padded
to the 128-lane width and block sizes to the 8-sublane width by ops.py.

This is the kernel counterpart of nn/layers.blockwise_attention (the pure-
XLA fallback used under pjit); interpret=True validates the body on CPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.experimental.pallas.tpu import MemorySpace, SemaphoreType


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, kbuf, vbuf, sems, *,
                  block_q: int, block_k: int, sk: int, causal: bool,
                  scale: float):
    """One grid step = one (b, h, q_block).

    q_ref: (block_q, dh) VMEM block; k_ref/v_ref: (b, h, sk, dh) HBM;
    o_ref: (block_q, dh) VMEM block; kbuf/vbuf: (2, block_k, dh) VMEM
    scratch; sems: (2, 2) DMA semaphores (slot x {k, v}).
    """
    b, h, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    dh = q_ref.shape[-1]
    nk = sk // block_k
    if causal:
        hi = jnp.minimum((qi * block_q + block_q - 1) // block_k + 1, nk)
    else:
        hi = nk

    def start(slot, ki):
        """Kick off K/V block ki's DMAs into double-buffer slot."""
        pltpu.make_async_copy(
            k_ref.at[b, h, pl.ds(ki * block_k, block_k)],
            kbuf.at[slot], sems.at[slot, 0]).start()
        pltpu.make_async_copy(
            v_ref.at[b, h, pl.ds(ki * block_k, block_k)],
            vbuf.at[slot], sems.at[slot, 1]).start()

    def wait(slot):
        """Await the K/V DMAs parked in slot."""
        pltpu.make_async_copy(k_ref.at[b, h, pl.ds(0, block_k)],
                              kbuf.at[slot], sems.at[slot, 0]).wait()
        pltpu.make_async_copy(v_ref.at[b, h, pl.ds(0, block_k)],
                              vbuf.at[slot], sems.at[slot, 1]).wait()

    start(0, 0)
    q = q_ref[0, 0].astype(jnp.float32) * scale   # (block_q, dh)
    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(ki, carry):
        """Online-softmax update over K/V block ki."""
        m, den, acc = carry
        slot = jax.lax.rem(ki, 2)

        @pl.when(ki + 1 < hi)
        def _():
            start(jax.lax.rem(ki + 1, 2), ki + 1)

        wait(slot)
        k = kbuf[slot].astype(jnp.float32)           # (block_k, dh)
        v = vbuf[slot].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos > qpos, -1e30, s)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        den_new = den * corr + p.sum(axis=-1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, den_new, acc_new

    m0 = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    a0 = jnp.zeros((block_q, dh), jnp.float32)
    m, den, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(den, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "causal",
                                             "interpret"))
def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                           block_q: int = 128, block_k: int = 128,
                           causal: bool = True,
                           interpret: bool = False) -> jax.Array:
    """q, k, v: (b, h, s, dh) with dh % 128 == 0 and s % block == 0
    (pad in ops.py). Returns (b, h, s, dh)."""
    b, h, sq, dh = q.shape
    sk = k.shape[2]
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk)
    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, sk=sk,
        causal=causal, scale=1.0 / math.sqrt(dh))
    return pl.pallas_call(
        kernel,
        grid=(b, h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec(memory_space=MemorySpace.ANY),
            pl.BlockSpec(memory_space=MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
        scratch_shapes=[
            MemorySpace.VMEM((2, block_k, dh), k.dtype),
            MemorySpace.VMEM((2, block_k, dh), v.dtype),
            SemaphoreType.DMA((2, 2)),
        ],
        interpret=interpret,
    )(q, k, v)
