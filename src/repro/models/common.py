"""Shared model primitives: norms, RoPE, initializers.

All functions are pure jnp/lax — they compose under jit/pjit/shard_map and
under ``jax.eval_shape`` (abstract init for the multi-pod dry-run, which
never allocates full-size parameters).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dt)


def layer_norm(
    x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray, eps: float = 1e-5
) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def apply_norm(x: jnp.ndarray, p: Dict[str, jnp.ndarray], kind: str) -> jnp.ndarray:
    if kind == "rmsnorm":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p["bias"])


def norm_params(kind: str, dim: int, dtype) -> Dict[str, jnp.ndarray]:
    if kind == "rmsnorm":
        return {"scale": jnp.ones((dim,), dtype)}
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


# -- RoPE ----------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10_000.0
) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: (..., S) int32. Split-half convention."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# -- initializers ----------------------------------------------------------------

def dense_init(key, shape, dtype, fan_in: Optional[int] = None) -> jnp.ndarray:
    """Truncated-normal scaled by 1/sqrt(fan_in) (first dim by default)."""
    fi = fan_in if fan_in is not None else shape[0]
    std = fi ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(
        dtype
    )


def embed_init(key, shape, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


class KeyGen:
    """Splits a PRNG key on demand (keeps init code linear)."""

    def __init__(self, key: jax.Array):
        self._key = key

    def __call__(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub
