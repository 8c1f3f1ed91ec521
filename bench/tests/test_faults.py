"""A whole run of a train cell, the chip check skipped, with the step
broken underneath: `correct` must come out false for each fault a
one-chip train cell can have, and for the interaction's backward in one
bfloat16 pass, and true for the step as it is."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from repro.kernels import ops
from repro.launch import train as launcher
from repro.train.steps import build_dlrm_train_step


def unchanged(cfg, ebc, opt, use_kernel=None):
    """A step that computes its loss and returns its state unchanged."""
    inner = build_dlrm_train_step(cfg, ebc, opt, sparse_apply="sparse",
                                  use_kernel=use_kernel)

    def step(p, s, b, i):
        _, _, m = inner(p, s, b, i)
        return p, s, m
    return jax.jit(step)


def half_batch(cfg, ebc, opt, use_kernel=None):
    """A step that leaves out the second half of its batch."""
    inner = build_dlrm_train_step(cfg, ebc, opt, sparse_apply="sparse",
                                  use_kernel=use_kernel)

    def step(p, s, b, i):
        n = b["label"].shape[0] // 2
        return inner(p, s, {k: v[:n] for k, v in b.items()}, i)
    return jax.jit(step, donate_argnums=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def bf16_backward_dot(z, tile_b=8, use_kernel=None, interpret=False):
    """The program's dot interaction, its backward in one bfloat16 pass
    (what TPU runs for a float32 product that names no precision)."""
    return PROGRAM_DOT(z, tile_b, use_kernel, interpret)


def _bf16_fwd(z, tile_b, use_kernel, interpret):
    return PROGRAM_DOT(z, tile_b, use_kernel, interpret), z


def _bf16_bwd(tile_b, use_kernel, interpret, z, g):
    b, f, _ = z.shape
    rows, cols = np.tril_indices(f, -1)
    s = jnp.zeros((b, f, f), jnp.float32).at[:, rows, cols].set(g)
    s = s + jnp.swapaxes(s, 1, 2)
    gz = jnp.einsum("bfg,bgd->bfd", s.astype(jnp.bfloat16),
                    z.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
    return (gz.astype(z.dtype),)


PROGRAM_DOT = ops.dot_interaction
bf16_backward_dot.defvjp(_bf16_fwd, _bf16_bwd)


#: each fault as (module, attribute, what replaces it)
FAULTS = {"none": None,
          "unchanged": (launcher, "dlrm_train_step", unchanged),
          "half_batch": (launcher, "dlrm_train_step", half_batch),
          "bf16_backward": (ops, "dot_interaction", bf16_backward_dot)}


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_caught(tiny, monkeypatch, fault):
    if FAULTS[fault] is not None:
        monkeypatch.setattr(*FAULTS[fault])
    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    res = run.run_cell(spec, tiny[0]["name"], 2**31 + 99, 0.5, False,
                       jax.devices(), files=tiny)
    assert res["correct"] is (fault == "none"), res["checks"]
    assert list(res)[-1] == "checks"
