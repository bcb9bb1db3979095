"""Shared NN building blocks: norms, initializers, rotary embeddings."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def he_init(rng, shape, fan_in=None, dtype=jnp.float32):
    fan_in = fan_in if fan_in is not None else shape[0]
    scale = 1.0 / jnp.sqrt(jnp.maximum(fan_in, 1)).astype(jnp.float32)
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(dtype)


def embed_init(rng, shape, dtype=jnp.float32):
    return (jax.random.normal(rng, shape, jnp.float32) * 0.02).astype(dtype)


# ----------------------------------------------------------------- RMSNorm
def rmsnorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps: float = 1e-5):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dtype)


def rmsnorm_nohead(x, eps: float = 1e-5):
    """Scale-free RMS norm (used for per-head RWKV group norm)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype)


# -------------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float):
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponents)  # (head_dim//2,)


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max_position: int, beta_fast: float,
                  beta_slow: float):
    """YaRN's (arXiv:2309.00071) inverse frequencies, as Hugging Face's
    `_compute_yarn_parameters` gives them: the dimensions that turn more
    than `beta_fast` times over the original context keep their
    frequency, those that turn fewer than `beta_slow` times are divided by
    `factor`, and a linear ramp between the two (correction dimensions
    rounded outward) blends them."""
    def correction_dim(rotations):
        return (head_dim * math.log(original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    extrapolation = rope_freqs(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp  # the share of the original frequency
    return extrapolation / factor * (1.0 - keep) + extrapolation * keep


def apply_rope(x, positions, theta: float, inv_freq=None, scale: float = 1.0):
    """x: (..., S, H, hd); positions: broadcastable to (..., S).
    `inv_freq` (hd//2,) replaces the plain frequencies (YaRN), and `scale`
    multiplies cos and sin (YaRN's attention factor)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta) if inv_freq is None else inv_freq
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd//2)
    cos = scale * jnp.cos(angles)[..., None, :]  # (..., S, 1, hd//2)
    sin = scale * jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def silu(x):
    return x * jax.nn.sigmoid(x)
