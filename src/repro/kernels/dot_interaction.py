"""Pallas TPU kernel: fused pairwise-dot feature interaction.

DLRM's interaction (paper section III-A.3) forms Z Z^T per example over the
stacked feature matrix Z = [dense_proj; pooled_emb_1; ...] (F, D) and keeps
the strictly-lower triangle. This kernel keeps Z in VMEM per batch tile,
runs the (F, D) x (D, F) contraction on the MXU at full fp32 precision, and
masks the upper triangle with an iota comparison in VREGs (no gather — TPU
vector units have no efficient in-kernel gather). The cheap triangle packing
(a static-index gather over the already-masked (F, F) tile) remains in XLA
where it fuses with the downstream concat.

Tiling: grid over batch tiles; block (TB, F, D) with F padded to the sublane
(8) and D to the lane (128) width by ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dot_int_kernel(z_ref, out_ref):
    z = z_ref[...]                                           # (tb, F, D)
    f = z.shape[1]
    s = jax.lax.dot_general(
        z, z, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                  # (tb, F, F)
    rows = jax.lax.broadcasted_iota(jnp.int32, (f, f), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (f, f), 1)
    s = jnp.where((cols < rows)[None], s, 0.0)               # strict lower
    out_ref[...] = s.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def dot_interaction_kernel(z: jax.Array, tile_b: int = 8,
                           interpret: bool = False) -> jax.Array:
    """z: (B, F, D), B % tile_b == 0. Returns (B, F, F) strictly-lower-
    triangular pairwise-dot matrix (zeros elsewhere)."""
    b, f, d = z.shape
    assert b % tile_b == 0, (b, tile_b)
    return pl.pallas_call(
        _dot_int_kernel,
        grid=(b // tile_b,),
        in_specs=[pl.BlockSpec((tile_b, f, d), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((tile_b, f, f), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, f, f), z.dtype),
        interpret=interpret,
        name="dot_interaction",
    )(z)
