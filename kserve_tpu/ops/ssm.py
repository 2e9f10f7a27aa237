"""State-space (Mamba-1) operations over the engine's two token layouts.

A Mamba mixer keeps two pieces of per-lane state between calls: the last
`d_conv - 1` inputs of its depthwise causal convolution (the "tail") and
the selective scan's state `s` [d_inner, d_state], float32.  Both are
indexed by LANE (the engine's slot), never by page: engine/kvcache.py
allocates one slot per lane and the programs carry the arrays.

Two forms of each operation, the same mathematics:

- `*_step`: one token per lane (the `steps_per_sync` decode scan);
- `*_ragged`: the packed `[T]` buffer of the mixed program, where every
  lane contributes a slice (a prompt chunk or one decode token) at a
  `block`-aligned offset, padding in between.  A slice starts from its
  lane's stored state (zero where the lane's slice begins at position 0:
  a newly admitted request), padding passes state through unchanged, and
  the state after a lane's last token is what the lane keeps.

The recurrence `s_t = a_t * s_{t-1} + b_t` is evaluated exactly in float32
in both forms; the ragged form only re-associates it: sequentially inside
each `block` of tokens (vectorized over the blocks), and by an associative
scan over the blocks, which is where segments restart.  `block` is the
packing alignment (ops/pallas_paged_attention.RAGGED_BQ where the ragged
kernel can run, 1 on the XLA path), so a block never holds two lanes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_conv_step(x, tail, w, b):
    """x [B, D] (this token), tail [B, K-1, D] (oldest first), w [K, D]
    (w[K-1] multiplies the current token: torch's Conv1d with left padding
    K-1), b [D] -> (y [B, D] float32, new tail)."""
    window = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], axis=1)
    y = jnp.einsum("bkd,kd->bd", window.astype(jnp.float32),
                   w.astype(jnp.float32)) + b.astype(jnp.float32)
    return y, window[:, 1:]


def causal_conv_ragged(x, tail, w, b, token_seq, token_off, q_start, q_len,
                       fresh):
    """The same convolution over the packed buffer.  x [T, D]; tail
    [B, K-1, D]; token_seq [T] lane per token (-1 = padding); token_off [T]
    the token's offset inside its lane's slice; q_start, q_len [B]; fresh
    [B] bool: the slice starts at position 0, so its tail is zero.
    Returns (y [T, D] float32, new tail [B, K-1, D])."""
    T = x.shape[0]
    K = w.shape[0]
    lane = jnp.maximum(token_seq, 0)
    tail = jnp.where(fresh[:, None, None], jnp.zeros_like(tail), tail)
    x32 = x.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    t = jnp.arange(T, dtype=jnp.int32)
    y = x32 * w32[K - 1]
    for k in range(1, K):
        in_buffer = x32[jnp.maximum(t - k, 0)]
        # offset o < k: the tap lies before the slice, in the stored tail
        from_tail = tail[lane, jnp.clip(K - 1 + token_off - k, 0, K - 2)]
        tap = jnp.where((token_off >= k)[:, None], in_buffer,
                        from_tail.astype(jnp.float32))
        y = y + tap * w32[K - 1 - k]
    y = y + b.astype(jnp.float32)
    # new tail: entries q_len .. q_len+K-2 of (old tail ++ the slice)
    m = q_len[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]  # [B, K-1]
    from_old = jnp.take_along_axis(
        tail, jnp.clip(m, 0, K - 2)[:, :, None], axis=1)
    from_new = x[jnp.clip(q_start[:, None] + m - (K - 1), 0, T - 1)]
    new_tail = jnp.where((m < K - 1)[:, :, None], from_old,
                         from_new.astype(tail.dtype))
    return y, new_tail


def selective_scan_step(x, dt, A, Bm, Cm, D, state, live):
    """One token per lane.  x, dt [B, Di] float32; A [Di, N] (negative);
    Bm, Cm [B, N]; D [Di]; state [B, Di, N] float32; live [B] bool (a lane
    that is not live keeps its state).  Returns (y [B, Di], new state)."""
    dA = jnp.exp(dt[:, :, None] * A[None])
    s = dA * state + (dt * x)[:, :, None] * Bm[:, None, :]
    y = jnp.einsum("bdn,bn->bd", s, Cm) + D[None] * x
    return y, jnp.where(live[:, None, None], s, state)


def selective_scan_ragged(x, dt, A, Bm, Cm, D, state, token_seq, q_start,
                          q_len, last_idx, fresh, block: int):
    """The packed buffer.  x, dt [T, Di] float32; Bm, Cm [T, N]; state
    [B, Di, N]; token_seq [T]; q_start, q_len, last_idx [B]; fresh [B]
    bool (start from zero); `block` divides T and every slice starts at a
    multiple of it.  Returns (y [T, Di], new state [B, Di, N])."""
    T, Di = x.shape
    N = A.shape[1]
    nb = T // block
    valid = (token_seq >= 0).reshape(nb, block)
    dt = dt.reshape(nb, block, Di)
    dtx = dt * x.reshape(nb, block, Di)
    Bb = Bm.reshape(nb, block, N)
    Cb = Cm.reshape(nb, block, N)

    def step(q):
        """a, b [nb, Di, N] of each block's q-th token; padding: a = 1,
        b = 0, so state passes through it unchanged."""
        v = valid[:, q, None, None]
        a = jnp.where(v, jnp.exp(dt[:, q, :, None] * A[None]), 1.0)
        b = jnp.where(v, dtx[:, q, :, None] * Bb[:, q, None, :], 0.0)
        return a, b

    # first pass, inside each block from a zero state: the block's decay p
    # and what it adds, l (only the blocks' ends are kept: the per-token
    # [T, Di, N] arrays never exist)
    p = jnp.ones((nb, Di, N), jnp.float32)
    l = jnp.zeros((nb, Di, N), jnp.float32)
    for q in range(block):
        a, b = step(q)
        l = a * l + b
        p = a * p
    # across blocks: S[k] (the state entering block k) = alpha_k S[k-1] +
    # beta_k; a block that opens a lane's slice takes the lane's stored
    # state (or zero) instead of its predecessor's end
    first = jnp.arange(nb, dtype=jnp.int32) * block
    blk_lane = token_seq[first]
    lane = jnp.maximum(blk_lane, 0)
    opens = ((blk_lane >= 0) & (q_start[lane] == first))[:, None, None]
    stored = jnp.where(fresh[:, None, None], 0.0, state)[lane]
    p_prev = jnp.concatenate([jnp.ones_like(p[:1]), p[:-1]], axis=0)
    l_prev = jnp.concatenate([jnp.zeros_like(l[:1]), l[:-1]], axis=0)
    alpha = jnp.where(opens, 0.0, p_prev)
    beta = jnp.where(opens, stored, l_prev)

    def combine(left, right):
        a1, b1 = left
        a2, b2 = right
        return a1 * a2, a2 * b1 + b2

    _, s = jax.lax.associative_scan(combine, (alpha, beta), axis=0)
    # second pass: the same recurrence again, now from the entering states
    ys = []
    for q in range(block):
        a, b = step(q)
        s = a * s + b
        ys.append(jnp.einsum("kdn,kn->kd", s, Cb[:, q]))
    y = jnp.stack(ys, axis=1).reshape(T, Di) + D[None] * x
    # what each lane keeps: the state at the end of its last block (the
    # padding behind its last token changed nothing)
    new_state = jnp.where((q_len > 0)[:, None, None],
                          s[last_idx // block], state)
    return y, new_state
