"""Latent attention (the DeepSeek-V2 family's "MLA"; here `model_type:
glm4_moe_lite`): the mixer of a `latent_attention` row of the per-layer
table (models/llama.LayerSpec), run from models/hybrid.py.

For a normed token n at position p, with H heads:

    cq = RMSNorm(n W_dq)                         [q_lora_rank]
    q  = cq W_uq -> H x (nope | rope);  q_rope = rope(q_rope, p)
    ckv | kr = n W_dkv                           [kv_lora_rank | rope]
    c = RMSNorm(ckv);  k_rope = rope(kr, p)      ONE k_rope for all heads
    k_nope_i | v_i = c W_ukv,i                   per head: [nope | v]
    s_i[t,u] = (q_nope_i[t] k_nope_i[u] + q_rope_i[t] k_rope[u]) / sqrt(nope + rope)

What a token leaves in the cache is the ROW (c, k_rope), `latent_width`
values, not K and V per head (engine/kvcache.StateLayout, `latent`).  Both
programs read it in the ABSORBED form: `q'_i = W_uk,i^T q_nope_i`
[kv_lora_rank] makes every head a query over the row itself,

    s_i[t,u] = (q'_i[t] c[u] + q_rope_i[t] k_rope[u]) * scale
    o_i = W_uv,i sum_u p_i[t,u] c[u]

which is attention with H query heads over ONE key row whose first
kv_lora_rank columns are also the value: ops/attention.latent_paged_attention
(one token a lane) and latent_ragged_attention (the packed step), each a
Pallas kernel that fetches a page once.  The packed step runs the absorbed
form too: one cache layout, one read path, and at this repo's chunks (up to
2048 tokens over contexts up to a few thousand) its extra FLOPs are a few
per cent of the step (docs/kernels.md has the arithmetic and where the
materialised form would win).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..ops.norms import rms_norm
from ..ops.rotary import apply_rope
from .quant import dense

Params = Dict[str, Any]


def param_shapes(config) -> Dict[str, tuple]:
    """{name: (shape, init)} of the mixer (models/hybrid.layer_param_shapes)."""
    h, H = config.hidden_size, config.n_heads
    qk = config.qk_nope_head_dim + config.qk_rope_head_dim
    return {
        "wq_a": ((h, config.q_lora_rank), "normal"),
        "q_a_norm": ((config.q_lora_rank,), "ones"),
        "wq_b": ((config.q_lora_rank, H * qk), "normal"),
        "wkv_a": ((h, config.latent_width), "normal"),
        "kv_a_norm": ((config.kv_lora_rank,), "ones"),
        "wkv_b": ((config.kv_lora_rank,
                   H * (config.qk_nope_head_dim + config.v_head_dim)), "normal"),
        "wo": ((H * config.v_head_dim, h), "normal"),
    }


def scale(config) -> float:
    return float(config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5


def _rope(x, pos, config):
    """x [N, heads, rope] at positions pos [N]."""
    return apply_rope(x[:, None], pos[:, None], config.rope_theta,
                      config.rope_scaling)[:, 0]


def project(layer: Params, u: jnp.ndarray, pos: jnp.ndarray, config,
            row: int):
    """u [N, h] normed tokens at positions pos [N] -> (the absorbed queries
    [N, H, row], the rows to cache [N, row]), both zero where `row` pads
    the `latent_width` values."""
    N, H = u.shape[0], config.n_heads
    nope, rope = config.qk_nope_head_dim, config.qk_rope_head_dim
    rank = config.kv_lora_rank
    eps = config.rms_norm_eps
    cq = rms_norm(dense(u, layer["wq_a"]), layer["q_a_norm"], eps)
    q = dense(cq, layer["wq_b"]).reshape(N, H, nope + rope)
    q_rope = _rope(q[..., nope:], pos, config)
    ckv = dense(u, layer["wkv_a"])
    c = rms_norm(ckv[:, :rank], layer["kv_a_norm"], eps)
    k_rope = _rope(ckv[:, None, rank:], pos, config)[:, 0]
    w_uk = layer["wkv_b"].reshape(rank, H, -1)[..., :nope]
    q_abs = jnp.einsum("nhd,chd->nhc", q[..., :nope], w_uk,
                       preferred_element_type=jnp.float32).astype(u.dtype)
    pad = row - rank - rope
    queries = jnp.concatenate(
        [q_abs, q_rope, jnp.zeros((N, H, pad), u.dtype)], axis=-1)
    rows = jnp.concatenate([c, k_rope, jnp.zeros((N, pad), u.dtype)], axis=-1)
    return queries, rows


def output(layer: Params, attn: jnp.ndarray, config) -> jnp.ndarray:
    """attn [N, H, kv_lora_rank] (the softmax's sum of compressed rows) ->
    the layer's output [N, h]."""
    N, H = attn.shape[0], config.n_heads
    w_uv = layer["wkv_b"].reshape(
        config.kv_lora_rank, H, -1)[..., config.qk_nope_head_dim:]
    o = jnp.einsum("nhc,chd->nhd", attn, w_uv,
                   preferred_element_type=jnp.float32).astype(attn.dtype)
    return dense(o.reshape(N, -1), layer["wo"])
