"""Shared building blocks: initializers, norms, RoPE, activation."""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32,
               scale: Optional[float] = None) -> jax.Array:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.truncated_normal(key, -3, 3, (d_in, d_out),
                                        jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32) -> jax.Array:
    # 1/sqrt(d): unit-scale rows after the gemma-style sqrt(d) input
    # multiplier, and O(1) tied logits from RMS-normed hidden states.
    return (jax.random.truncated_normal(key, -3, 3, (vocab, d),
                                        jnp.float32)
            * (d ** -0.5)).astype(dtype)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6,
             zero_centered: bool = False) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    w = weight.astype(jnp.float32)
    if zero_centered:  # gemma-style (1 + w)
        w = 1.0 + w
    return (x * w).astype(dt)


def layer_norm(x: jax.Array, weight: jax.Array, bias: Optional[jax.Array],
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    x = x * weight.astype(jnp.float32)
    if bias is not None:
        x = x + bias.astype(jnp.float32)
    return x.astype(dt)


@jax.named_scope("norm")
def apply_norm(kind: str, x, params, eps=1e-6):
    if kind == "rms":
        return rms_norm(x, params["w"], eps)
    if kind == "rms_zc":
        return rms_norm(x, params["w"], eps, zero_centered=True)
    if kind == "ln":
        return layer_norm(x, params["w"], params.get("b"), eps)
    raise ValueError(kind)


def norm_init(kind: str, d: int, dtype=jnp.float32):
    if kind in ("rms",):
        return {"w": jnp.ones((d,), dtype)}
    if kind in ("rms_zc",):
        return {"w": jnp.zeros((d,), dtype)}
    if kind == "ln":
        return {"w": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}
    raise ValueError(kind)


def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
            "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------- RoPE

def rope_freqs(head_dim: int, rotary_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies for the rotary dims (rotary_dim <= head_dim)."""
    return 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                            / rotary_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
               rotary_pct: float = 1.0) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32. Rotates the first
    rotary_pct * D dims (GPT-NeoX/llama convention, pairwise halves)."""
    b, s, h, d = x.shape
    rot = int(d * rotary_pct)
    rot -= rot % 2
    if rot == 0:
        return x
    inv = rope_freqs(d, rot, theta)                      # (rot/2,)
    ang = positions.astype(jnp.float32)[:, :, None] * inv  # (B,S,rot/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    x_rot = jnp.concatenate([out1, out2], -1).astype(x.dtype)
    return jnp.concatenate([x_rot, x_pass], -1) if rot < d else x_rot


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    if cap is None:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)
