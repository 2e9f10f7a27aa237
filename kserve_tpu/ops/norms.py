"""Normalization ops (RMSNorm) — f32 accumulation, bf16 in/out."""

from __future__ import annotations

import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = x32 * jnp.reciprocal(jnp.sqrt(var + eps))
    return (normed * weight.astype(jnp.float32)).astype(dtype)


def rms_norm_plus_one(x: jnp.ndarray, weight: jnp.ndarray,
                      eps: float = 1e-6) -> jnp.ndarray:
    """Gemma-style RMSNorm: multiplies by (1 + weight), with the product
    taken in f32 BEFORE the cast (HF PR #29402 semantics)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = x32 * jnp.reciprocal(jnp.sqrt(var + eps))
    return (normed * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias, eps: float = 1e-12) -> jnp.ndarray:
    """`bias` None: a LayerNorm of a weight alone (the Cohere family's)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = x32.mean(axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    normed = (x32 - mean) * jnp.reciprocal(jnp.sqrt(var + eps))
    out = normed * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(dtype)
