"""Ring attention: sequence-parallel causal attention over a mesh axis.

Long-context prefill shards the sequence across devices ("seq" axis); each
step computes attention of the local Q chunk against the currently-held K/V
chunk while K/V rotate around the ring via ppermute — comms overlap with
compute, memory per device stays O(T/n), and the full [T, T] score matrix
never exists anywhere.

The reference has no sequence parallelism at all (SURVEY.md §2.3: long
context is delegated to vLLM paged attention + KV offload); this op is the
TPU-native answer for prompts past a single chip's HBM.

Use under shard_map with the sequence dim sharded over `axis_name`:
    shard_map(lambda q, k, v, vl: ring_attention(q, k, v, vl, "seq"),
              mesh, in_specs=(P(None, "seq", None, None), ...), ...)
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def ring_attention(
    q: jnp.ndarray,  # [B, C, nq, d] local query chunk (C = T / ring_size)
    k: jnp.ndarray,  # [B, C, nkv, d] local key chunk
    v: jnp.ndarray,  # [B, C, nkv, d] local value chunk
    valid_len: jnp.ndarray,  # [B] global valid token count
    axis_name: str,
    causal: bool = True,
    logit_softcap: float = 0.0,
) -> jnp.ndarray:
    """Returns the local output chunk [B, C, nq, d]."""
    B, C, nq, d = q.shape
    nkv = k.shape[2]
    group = nq // nkv
    # static (`perm` below is a host-side list comprehension)
    ring = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))

    q32 = q.astype(jnp.float32).reshape(B, C, nkv, group, d)
    q_pos = my * C + jnp.arange(C, dtype=jnp.int32)  # [C] global positions

    # ring neighbors: chunk travels to the next device each step
    perm = [(i, (i + 1) % ring) for i in range(ring)]

    def step(r, carry):
        m, l, acc, k_r, v_r = carry
        src = (my - r) % ring  # origin device of the chunk we hold
        k_pos = src * C + jnp.arange(C, dtype=jnp.int32)
        s = jnp.einsum(
            "bckgd,bskd->bckgs",
            q32,
            k_r.astype(jnp.float32),
        ) * scale  # [B, C, nkv, group, C_k]
        if logit_softcap > 0.0:
            s = jnp.tanh(s / logit_softcap) * logit_softcap
        mask = k_pos[None, :] < valid_len[:, None]  # [B, C_k]
        if causal:
            mask = mask[:, None, :] & (k_pos[None, None, :] <= q_pos[None, :, None])
        else:
            mask = jnp.broadcast_to(mask[:, None, :], (B, C, C))
        s = jnp.where(mask[:, :, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        pv = jnp.einsum("bckgs,bskd->bckgd", p, v_r.astype(jnp.float32))
        acc_new = acc * alpha + pv
        k_next = lax.ppermute(k_r, axis_name, perm)
        v_next = lax.ppermute(v_r, axis_name, perm)
        return m_new, l_new, acc_new, k_next, v_next

    # derive the initial accumulators from q so they carry the same varying
    # manual axes as the loop outputs (plain constants are axis-invariant and
    # the scan carry types would mismatch under shard_map)
    zero = q32[..., :1] * 0.0  # [B, C, nkv, group, 1]
    m0 = zero - 1e30
    l0 = zero
    acc0 = jnp.zeros_like(q32)
    m, l, acc, _, _ = lax.fori_loop(0, ring, step, (m0, l0, acc0, k, v))
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(B, C, nq, d).astype(q.dtype)
