"""Layer zoo: linear, norms, RoPE (neox / glm-2d / none), GQA attention
(full, blockwise-flash, and cached decode incl. int8 KV), MLPs.

All functions are pure; params are dicts produced by the matching *_specs
function.  compute happens in cfg-selected dtype (bf16 default), params are
stored in fp32 and cast at the point of use.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.nn.params import ParamSpec
from repro.nn.sharding import gather_weight

# ---------------------------------------------------------------------------
# linear / norm
# ---------------------------------------------------------------------------


def linear_specs(d_in: int, d_out: int, in_ax: str, out_ax: str,
                 bias: bool = False, scale: float = 1.0) -> dict[str, ParamSpec]:
    specs = {"w": ParamSpec((d_in, d_out), (in_ax, out_ax), init="fan_in",
                            scale=scale, fan_axis=-2)}
    if bias:
        specs["b"] = ParamSpec((d_out,), (out_ax,), init="zeros")
    return specs


def linear(p: dict[str, jax.Array], x: jax.Array,
           dtype=jnp.bfloat16) -> jax.Array:
    w = p["w"].astype(dtype)
    # an fp32 matmul at default precision runs as bf16 passes on TPU
    prec = (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else None)
    y = jnp.einsum("...i,io->...o", x.astype(dtype), w, precision=prec)
    if "b" in p:
        y = y + p["b"].astype(dtype)
    return y


def norm_specs(d: int, kind: str = "rmsnorm") -> dict[str, ParamSpec]:
    specs = {"scale": ParamSpec((d,), ("embed",), init="ones")}
    if kind == "layernorm":
        specs["bias"] = ParamSpec((d,), ("embed",), init="zeros")
    return specs


def apply_norm(p: dict[str, jax.Array], x: jax.Array, kind: str = "rmsnorm",
               eps: float = 1e-5, dtype=jnp.bfloat16, rules=None) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps)
    elif kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    else:
        raise ValueError(kind)
    y = y * gather_weight(p["scale"].astype(jnp.float32), ("embed",), rules)
    if "bias" in p:
        y = y + gather_weight(p["bias"].astype(jnp.float32), ("embed",),
                              rules)
    return y.astype(dtype)

# ---------------------------------------------------------------------------
# positions: RoPE (neox split-half, glm interleaved-half) + sinusoidal
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, rotary_pct: float, theta: float,
                     style: str) -> jax.Array:
    """Inverse frequencies for the rotated sub-dimension."""
    if style == "glm":
        rot = head_dim // 2          # ChatGLM rotates the first half, 2D style
    else:
        rot = int(head_dim * rotary_pct)
    rot -= rot % 2
    return 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))


def apply_rope(x: jax.Array, positions: jax.Array, head_dim: int,
               rotary_pct: float = 1.0, theta: float = 10000.0,
               style: str = "neox") -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    if style == "none":
        return x
    inv = rope_frequencies(head_dim, rotary_pct, theta, style)
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., s, rot/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    sin = sin[..., :, None, :]  # broadcast over heads
    cos = cos[..., :, None, :]
    rot = inv.shape[0] * 2
    xr, xp = x[..., :rot], x[..., rot:]
    xf = xr.astype(jnp.float32)
    if style == "glm":
        # interleaved pairing (x0,x1),(x2,x3),... — ChatGLM's 2D RoPE halves
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        o1 = x1 * cos - x2 * sin
        o2 = x2 * cos + x1 * sin
        out = jnp.stack([o1, o2], axis=-1).reshape(xf.shape)
    else:
        # neox split-half pairing (x_i, x_{i+rot/2})
        half = rot // 2
        x1, x2 = xf[..., :half], xf[..., half:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)


def sinusoidal_positions(positions: jax.Array, d_model: int) -> jax.Array:
    """MusicGen-style sinusoidal absolute position embedding."""
    inv = 1.0 / (10000.0 ** (jnp.arange(0, d_model, 2, dtype=jnp.float32)
                             / d_model))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)

# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def attention_specs(cfg) -> dict[str, Any]:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    return {
        "wq": ParamSpec((d, h, dh), ("embed", "heads", "head_dim"),
                        init="fan_in", fan_axis=0),
        "wk": ParamSpec((d, kv, dh), ("embed", "kv_heads", "head_dim"),
                        init="fan_in", fan_axis=0),
        "wv": ParamSpec((d, kv, dh), ("embed", "kv_heads", "head_dim"),
                        init="fan_in", fan_axis=0),
        "wo": ParamSpec((h, dh, d), ("heads", "head_dim", "embed"),
                        init="fan_in", fan_axis=1,
                        scale=1.0 / math.sqrt(2 * max(cfg.n_layers, 1))),
        **({"bq": ParamSpec((h, dh), ("heads", "head_dim"), init="zeros"),
            "bk": ParamSpec((kv, dh), ("kv_heads", "head_dim"), init="zeros"),
            "bv": ParamSpec((kv, dh), ("kv_heads", "head_dim"), init="zeros")}
           if cfg.qkv_bias else {}),
    }


def _qkv(p, x, cfg, positions, dtype, rules=None):
    wq = gather_weight(p["wq"].astype(dtype),
                       ("embed", "heads", "head_dim"), rules)
    wk = gather_weight(p["wk"].astype(dtype),
                       ("embed", "kv_heads", "head_dim"), rules)
    wv = gather_weight(p["wv"].astype(dtype),
                       ("embed", "kv_heads", "head_dim"), rules)
    q = jnp.einsum("bsd,dhk->bshk", x, wq)
    k = jnp.einsum("bsd,dhk->bshk", x, wk)
    v = jnp.einsum("bsd,dhk->bshk", x, wv)
    if "bq" in p:
        q = q + p["bq"].astype(dtype)
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    q = apply_rope(q, positions, cfg.d_head, cfg.rotary_pct, cfg.rope_theta,
                   cfg.rope_style)
    k = apply_rope(k, positions, cfg.d_head, cfg.rotary_pct, cfg.rope_theta,
                   cfg.rope_style)
    return q, k, v


def _repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(b, s, kv, dh) -> (b, s, h, dh) by repeating each kv group."""
    kv = k.shape[-2]
    if kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // kv, axis=-2)


def full_attention(q, k, v, q_offset: int = 0, causal: bool = True,
                   kv_valid_len: jax.Array | None = None) -> jax.Array:
    """Materialized-scores attention. q:(b,sq,h,dh) k,v:(b,sk,h,dh).
    kv_valid_len: scalar or (b,) per-sequence valid cache length."""
    dh = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(dh)
    b, sq = q.shape[0], q.shape[1]
    sk = k.shape[1]
    mask = jnp.zeros((1, 1, sq, sk), jnp.bool_)
    if causal:
        qpos = jnp.arange(sq)[:, None] + q_offset
        kpos = jnp.arange(sk)[None, :]
        mask = mask | (kpos > qpos)[None, None]
    if kv_valid_len is not None:
        valid = jnp.asarray(kv_valid_len)
        valid = jnp.broadcast_to(valid, (b,))          # scalar or (b,)
        mask = mask | (jnp.arange(sk)[None, None, None, :]
                       >= valid[:, None, None, None])
    scores = jnp.where(mask, -1e30, scores)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def blockwise_attention(q, k, v, block_q: int = 512, block_k: int = 1024,
                        causal: bool = True) -> jax.Array:
    """Flash-style attention in pure XLA: scan over KV blocks with a running
    (max, denom, acc) carry so the (sq, sk) score matrix never materializes.
    Used for long sequences (prefill_32k / train_4k) where materialized
    scores would blow VMEM/HBM."""
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    scale = 1.0 / math.sqrt(dh)

    qb = q.reshape(b, nq, block_q, h, dh)

    def per_qblock(qi, qblk):
        # qblk: (b, block_q, h, dh)
        qpos = qi * block_q + jnp.arange(block_q)

        def kv_step(carry, ki):
            m, l, acc = carry
            kblk = jax.lax.dynamic_slice_in_dim(k, ki * block_k, block_k, 1)
            vblk = jax.lax.dynamic_slice_in_dim(v, ki * block_k, block_k, 1)
            s = jnp.einsum("bqhd,bkhd->bhqk", qblk, kblk) * scale
            s = s.astype(jnp.float32)
            if causal:
                kpos = ki * block_k + jnp.arange(block_k)
                s = jnp.where(kpos[None, None, None, :]
                              > qpos[None, None, :, None], -1e30, s)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(q.dtype), vblk).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, block_q), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, block_q), jnp.float32)
        a0 = jnp.zeros((b, h, block_q, dh), jnp.float32)
        if causal:
            # only blocks ki <= (qi*block_q + block_q-1)//block_k contribute
            n_kv = jnp.minimum(
                (qi * block_q + block_q - 1) // block_k + 1, nk)
        else:
            n_kv = nk
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), jnp.arange(nk), length=nk) \
            if not causal else _bounded_scan(kv_step, (m0, l0, a0), n_kv, nk)
        out = acc / l[..., None]
        return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (b, bq, h, dh)

    outs = jax.lax.map(lambda args: per_qblock(args[0], args[1]),
                       (jnp.arange(nq), qb.swapaxes(0, 1)))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, dh)


def blockwise_attention_skip(q, k, v, block_q: int = 512,
                             block_k: int = 1024) -> jax.Array:
    """Causal blockwise attention with STATIC upper-triangle skipping.

    Python loop over q blocks; each q block scans only its own causal prefix
    of kv blocks (static trip count), so no FLOPs are spent above the
    diagonal. ~2x fewer attention FLOPs than `blockwise_attention` for long
    sequences, at the cost of a larger (unrolled over q blocks) HLO.
    Enabled via ModelConfig.causal_skip — a §Perf hillclimb lever.
    """
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    assert sq % block_q == 0 and sk % block_k == 0
    scale = 1.0 / math.sqrt(dh)
    outs = []
    for qi in range(nq):
        qblk = jax.lax.slice_in_dim(q, qi * block_q, (qi + 1) * block_q, axis=1)
        qpos = qi * block_q + jnp.arange(block_q)
        hi = min((qi * block_q + block_q - 1) // block_k + 1, nk)

        def kv_step(carry, ki, qblk=qblk, qpos=qpos):
            m, l, acc = carry
            kblk = jax.lax.dynamic_slice_in_dim(k, ki * block_k, block_k, 1)
            vblk = jax.lax.dynamic_slice_in_dim(v, ki * block_k, block_k, 1)
            s = jnp.einsum("bqhd,bkhd->bhqk", qblk, kblk) * scale
            s = s.astype(jnp.float32)
            kpos = ki * block_k + jnp.arange(block_k)
            s = jnp.where(kpos[None, None, None, :]
                          > qpos[None, None, :, None], -1e30, s)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(q.dtype), vblk).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, block_q), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, block_q), jnp.float32)
        a0 = jnp.zeros((b, h, block_q, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      jnp.arange(hi), length=hi)
        outs.append((acc / l[..., None]).transpose(0, 2, 1, 3).astype(q.dtype))
    return jnp.concatenate(outs, axis=1)


def _bounded_scan(step, carry, n_dyn, n_max):
    """scan over range(n_max) but mask iterations >= n_dyn (causal skip)."""
    def wrapped(c, ki):
        new_c, _ = step(c, ki)
        take = ki < n_dyn
        c_out = jax.tree.map(
            lambda a, b_: jnp.where(take, a, b_), new_c, c)
        return c_out, None
    return jax.lax.scan(wrapped, carry, jnp.arange(n_max), length=n_max)


def attention(p, x, cfg, positions, *, mode: str = "train",
              cache: dict[str, jax.Array] | None = None,
              cache_index: jax.Array | None = None,
              dtype=jnp.bfloat16,
              rules=None) -> tuple[jax.Array, dict | None]:
    """GQA attention. mode: train | prefill | decode.

    decode: x is (b, 1, d); cache holds k/v (+ scales if int8) and is updated
    functionally at position `cache_index`.
    """
    q, k, v = _qkv(p, x.astype(dtype), cfg,
                   positions, dtype, rules)
    if mode == "decode":
        assert cache is not None and cache_index is not None
        cache = update_kv_cache(cache, k, v, cache_index)
        kf, vf = read_kv_cache(cache, dtype)
        kf = _repeat_kv(kf, cfg.n_heads)
        vf = _repeat_kv(vf, cfg.n_heads)
        out = full_attention(q, kf, vf, causal=False,
                             kv_valid_len=cache_index + 1)
    else:
        if mode == "prefill":
            assert cache is not None
            # write the whole prefix into the cache at offset 0
            cache = write_kv_prefix(cache, k, v)
        k = _repeat_kv(k, cfg.n_heads)
        v = _repeat_kv(v, cfg.n_heads)
        if x.shape[1] > cfg.attn_block_q and x.shape[1] % cfg.attn_block_q == 0:
            if cfg.causal_skip:
                out = blockwise_attention_skip(q, k, v, cfg.attn_block_q,
                                               cfg.attn_block_k)
            else:
                out = blockwise_attention(q, k, v, cfg.attn_block_q,
                                          cfg.attn_block_k)
        else:
            out = full_attention(q, k, v)
    wo = gather_weight(p["wo"].astype(dtype),
                       ("heads", "head_dim", "embed"), rules)
    y = jnp.einsum("bshk,hkd->bsd", out, wo)
    return y, cache

# ---------------------------------------------------------------------------
# KV cache (bf16 or int8 with per-token-head scales)
# ---------------------------------------------------------------------------


def init_kv_cache(batch: int, max_len: int, n_kv: int, d_head: int,
                  dtype=jnp.bfloat16, quantized: bool = False) -> dict:
    if quantized:
        return {
            "k": jnp.zeros((batch, max_len, n_kv, d_head), jnp.int8),
            "v": jnp.zeros((batch, max_len, n_kv, d_head), jnp.int8),
            "k_scale": jnp.zeros((batch, max_len, n_kv, 1), jnp.float32),
            "v_scale": jnp.zeros((batch, max_len, n_kv, 1), jnp.float32),
        }
    return {
        "k": jnp.zeros((batch, max_len, n_kv, d_head), dtype),
        "v": jnp.zeros((batch, max_len, n_kv, d_head), dtype),
    }


def kv_cache_abstract(batch: int, max_len: int, n_kv: int, d_head: int,
                      dtype=jnp.bfloat16, quantized: bool = False) -> dict:
    c = init_kv_cache(1, 1, 1, 1, dtype, quantized)
    shapes = {
        "k": (batch, max_len, n_kv, d_head),
        "v": (batch, max_len, n_kv, d_head),
        "k_scale": (batch, max_len, n_kv, 1),
        "v_scale": (batch, max_len, n_kv, 1),
    }
    return {k: jax.ShapeDtypeStruct(shapes[k], v.dtype) for k, v in c.items()}


def _quantize_i8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(scale, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def update_kv_cache(cache: dict, k_new: jax.Array, v_new: jax.Array,
                    index: jax.Array) -> dict:
    """Insert one token (b, 1, kv, dh) at position `index` (scalar shared
    by the batch, or (b,) per-slot — continuous batching writes each
    sequence at its own depth)."""
    out = dict(cache)
    index = jnp.asarray(index)

    def put(buf, val):
        val = val.astype(buf.dtype)
        if index.ndim == 0:
            return jax.lax.dynamic_update_slice_in_dim(buf, val, index, 1)
        b = buf.shape[0]
        return buf.at[jnp.arange(b), index].set(val[:, 0])

    if "k_scale" in cache:
        kq, ks = _quantize_i8(k_new)
        vq, vs = _quantize_i8(v_new)
        out["k"] = put(cache["k"], kq)
        out["v"] = put(cache["v"], vq)
        out["k_scale"] = put(cache["k_scale"], ks)
        out["v_scale"] = put(cache["v_scale"], vs)
    else:
        out["k"] = put(cache["k"], k_new)
        out["v"] = put(cache["v"], v_new)
    return out


def write_kv_prefix(cache: dict, k: jax.Array, v: jax.Array) -> dict:
    out = dict(cache)
    pl = k.shape[1]
    if "k_scale" in cache:
        kq, ks = _quantize_i8(k)
        vq, vs = _quantize_i8(v)
        out["k"] = cache["k"].at[:, :pl].set(kq)
        out["v"] = cache["v"].at[:, :pl].set(vq)
        out["k_scale"] = cache["k_scale"].at[:, :pl].set(ks)
        out["v_scale"] = cache["v_scale"].at[:, :pl].set(vs)
    else:
        out["k"] = cache["k"].at[:, :pl].set(k.astype(cache["k"].dtype))
        out["v"] = cache["v"].at[:, :pl].set(v.astype(cache["v"].dtype))
    return out


def read_kv_cache(cache: dict, dtype=jnp.bfloat16):
    if "k_scale" in cache:
        k = cache["k"].astype(jnp.float32) * cache["k_scale"]
        v = cache["v"].astype(jnp.float32) * cache["v_scale"]
        return k.astype(dtype), v.astype(dtype)
    return cache["k"].astype(dtype), cache["v"].astype(dtype)

# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_specs(cfg) -> dict[str, Any]:
    d, f = cfg.d_model, cfg.d_ff
    out_scale = 1.0 / math.sqrt(2 * max(cfg.n_layers, 1))
    if cfg.mlp_type == "swiglu":
        return {
            "wi": ParamSpec((d, f), ("embed", "ff"), init="fan_in"),
            "wg": ParamSpec((d, f), ("embed", "ff"), init="fan_in"),
            "wo": ParamSpec((f, d), ("ff", "embed"), init="fan_in",
                            scale=out_scale),
        }
    return {  # gelu
        "wi": ParamSpec((d, f), ("embed", "ff"), init="fan_in"),
        "bi": ParamSpec((f,), ("ff",), init="zeros"),
        "wo": ParamSpec((f, d), ("ff", "embed"), init="fan_in",
                        scale=out_scale),
        "bo": ParamSpec((d,), ("embed",), init="zeros"),
    }


def mlp(p, x, cfg, dtype=jnp.bfloat16, rules=None) -> jax.Array:
    x = x.astype(dtype)
    gw = lambda k, axes: gather_weight(p[k].astype(dtype), axes, rules)  # noqa: E731
    if cfg.mlp_type == "swiglu":
        h = jax.nn.silu(x @ gw("wg", ("embed", "ff"))) \
            * (x @ gw("wi", ("embed", "ff")))
        return h @ gw("wo", ("ff", "embed"))
    h = jax.nn.gelu(x @ gw("wi", ("embed", "ff")) + p["bi"].astype(dtype))
    return h @ gw("wo", ("ff", "embed")) + gw("bo", ("embed",))
