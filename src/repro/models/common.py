"""Shared model building blocks (pure JAX, no framework)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def silu(x: jax.Array) -> jax.Array:
    return x * jax.nn.sigmoid(x)


def swiglu(x, w_gate, w_up, w_down):
    g = x @ w_gate
    u = x @ w_up
    return (silu(g) * u) @ w_down


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate pairs (x[..., :h], x[..., h:]) by position-dependent angles.

    x: (..., seq, head_dim); positions: broadcastable to (..., seq).
    """
    head_dim = x.shape[-1]
    half = head_dim // 2
    freqs = rope_freqs(head_dim, theta)  # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def embed(tokens: jax.Array, table: jax.Array, dtype) -> jax.Array:
    """Token embedding lookup; vocab may be sharded over 'model'."""
    out = jnp.take(table, tokens, axis=0).astype(dtype)
    return constrain(out, "batch", "seq", "embed")


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Token-mean cross entropy; stable over a (possibly sharded) vocab."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


def init_dense(key, shape, scale: float = 1.0, dtype=jnp.float32):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale / (fan_in ** 0.5)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))
