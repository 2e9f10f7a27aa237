"""Rotary position embeddings: the half-rotation layout (Llama/NeoX style,
`apply_rope`) and the interleaved one (GPT-J style, `apply_rope_interleaved`).

Computed on the fly from positions (no host-side cache tables) so the same
function serves prefill ([B,T]) and decode ([B,1]) under one jit.

Supports the HF `rope_scaling` variants needed for real checkpoints:
- "llama3" (Llama-3.1/3.2): low/high-frequency wavelength scaling applied
  at ALL positions (config.json rope_type "llama3")
- "linear": uniform inv_freq / factor
Unsupported types raise instead of being silently dropped.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def rope_frequencies(
    head_dim: int,
    theta: float = 10000.0,
    rope_scaling: Optional[dict] = None,
) -> jnp.ndarray:
    """[head_dim/2] inverse frequencies, with optional HF rope_scaling."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    inv_freq = 1.0 / (theta**exponent)
    if not rope_scaling:
        return inv_freq
    rope_type = rope_scaling.get("rope_type") or rope_scaling.get("type") or "default"
    if rope_type == "default":
        return inv_freq
    if rope_type == "linear":
        return inv_freq / float(rope_scaling["factor"])
    if rope_type == "llama3":
        # Per-frequency interpolation: wavelengths shorter than
        # orig_ctx/high_freq_factor are kept, longer than
        # orig_ctx/low_freq_factor are divided by `factor`, and the band in
        # between is linearly blended.  The clip form below is exactly
        # equivalent to the three-way where() in HF modeling_rope_utils.
        factor = float(rope_scaling["factor"])
        low = float(rope_scaling["low_freq_factor"])
        high = float(rope_scaling["high_freq_factor"])
        orig_ctx = float(rope_scaling["original_max_position_embeddings"])
        wavelen = 2.0 * math.pi / inv_freq
        smooth = jnp.clip((orig_ctx / wavelen - low) / (high - low), 0.0, 1.0)
        return (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
    raise ValueError(
        f"unsupported rope_scaling type {rope_type!r}; supported: "
        "default, linear, llama3"
    )


def apply_rope(
    x: jnp.ndarray,  # [B, T, H, D]
    positions: jnp.ndarray,  # [B, T] int32
    theta: float = 10000.0,
    rope_scaling: Optional[dict] = None,
) -> jnp.ndarray:
    """Rotate q/k by position-dependent phases.  Half-rotation layout:
    pairs are (x[..., :D/2], x[..., D/2:]) as in Llama."""
    head_dim = x.shape[-1]
    inv_freq = rope_frequencies(head_dim, theta, rope_scaling)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [B,T,D/2]
    cos = jnp.cos(angles)[:, :, None, :]  # [B,T,1,D/2]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope_interleaved(
    x: jnp.ndarray,  # [N, H, D]
    positions: jnp.ndarray,  # [N] int32
    theta: float = 10000.0,
) -> jnp.ndarray:
    """The interleaved pairing (`rope_gptj`): columns (2j, 2j+1) turn by
    `pos * theta^(-2j/D)`.  Written without splitting the lanes: every
    column keeps its place, `x * cos + partner(x) * sin` with the partner of
    column 2j being -x[2j+1] and of 2j+1 being x[2j].  The partner is a
    product with a fixed [D, D] matrix of 0 and +-1 (exact in any dtype: one
    term a column): on the TPU a shift along the lanes is slices and a
    concatenation over the whole array, three passes and 5 % of a step's
    device time at 128 heads (PERF.md section 6, PR 43), and this is one
    small matmul."""
    head_dim = x.shape[-1]
    inv_freq = jnp.repeat(rope_frequencies(head_dim, theta), 2)  # [D]
    angles = positions.astype(jnp.float32)[:, None] * inv_freq  # [N, D]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    col = jnp.arange(head_dim)
    # swap[d, e]: what column d of x adds to column e of the partner
    swap = (jnp.where((col[:, None] == col[None, :] + 1) & (col[None, :] % 2 == 0), -1.0, 0.0)
            + jnp.where((col[:, None] + 1 == col[None, :]) & (col[:, None] % 2 == 0), 1.0, 0.0))
    partner = jnp.einsum(
        "nhd,de->nhe", x, swap.astype(x.dtype),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * cos + partner * sin).astype(x.dtype)
