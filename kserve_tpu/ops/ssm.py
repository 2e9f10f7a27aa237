"""State-space (Mamba-1 and Mamba-2) operations over the engine's two token
layouts.

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

Mamba-2 (`ssd_*`, arXiv:2405.21060; `model_type: nemotron_h`) keeps a
MATRIX a head: `S [heads, head_dim, d_state]` float32, 26 times the values
of the Mamba-1 state above, so one state a block of 8 tokens cannot exist
(256 x 2.1 MB an array at T = 2048).  Its decay is one scalar a head and
token, which is what lets the packed form work in chunks of `SSD_CHUNK`
tokens: inside a chunk the outputs are matrix products over a decay mask
(`(C B^T o L) X`), and only one state a CHUNK exists.  The convolution is
the Mamba-1 one (`causal_conv_*`), over x, B and C together.
"""

from __future__ import annotations

import math

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


def _conv_rows(window, w32, b32):
    """window [..., n + K - 1, D] -> float32 [..., n, D]: row i is the
    convolution's output for the window's row K - 1 + i, the taps as static
    slices, newest first, the bias last."""
    K = w32.shape[0]
    n = window.shape[-2] - (K - 1)
    y = window[..., K - 1:, :].astype(jnp.float32) * w32[K - 1]
    for k in range(1, K):
        y = y + (window[..., K - 1 - k:K - 1 - k + n, :].astype(jnp.float32)
                 * w32[K - 1 - k])
    return y + b32


def causal_conv_ragged(x, tail, w, b, token_seq, token_off, q_start, q_len,
                       fresh):
    """The same convolution over the packed buffer.  x [T, D]; tail
    [B, K-1, D]; token_seq [T] lane per token (-1 = padding); token_off [T]
    the token's offset inside its lane's slice; q_start, q_len [B]; fresh
    [B] bool: the slice starts at position 0, so its tail is zero.
    Returns (y [T, D] float32, new tail [B, K-1, D]).

    A slice is contiguous, so tap k of row t is row t - k of the buffer
    wherever `token_off[t] >= k`: every row is computed from STATIC shifts
    of the buffer (K - 1 zero rows in front of it and K slices: one
    elementwise pass that reads x once and writes y once, no gather along
    the token axis).  That is wrong only where a tap reaches before its
    slice, in a slice's first K - 1 rows, and those are computed again per
    LANE, from the stored tail followed by the slice's first K - 1 rows,
    and put over what the shifts gave there: lanes x (K - 1) rows, not T.
    Rows 0 .. k - 1 of the buffer need no guard: a slice that holds row t
    starts at or before it, so `token_off[t] <= t < k` and the row is one of
    those; the same holds for what a shift brings in across a slice's
    start.  Both passes add the same terms in the same order, so a row's
    value does not depend on which of them made it.  `token_seq` and
    `token_off` say nothing that `q_start` and `q_len` do not."""
    del token_seq, token_off
    T, D = x.shape
    K = w.shape[0]
    B = q_start.shape[0]
    tail = jnp.where(fresh[:, None, None], jnp.zeros_like(tail), tail)
    w32 = w.astype(jnp.float32)
    b32 = b.astype(jnp.float32)
    y = _conv_rows(jnp.pad(x, ((K - 1, 0), (0, 0))), w32, b32)
    # a slice's first K-1 rows, from (stored tail ++ those rows) per lane;
    # rows past the slice's end go nowhere (indices past T, each its own)
    j = jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    head = x[jnp.clip(q_start[:, None] + j, 0, T - 1)]  # [B, K-1, D]
    opening = _conv_rows(
        jnp.concatenate([tail.astype(jnp.float32),
                         head.astype(jnp.float32)], axis=1), w32, b32)
    rows = jnp.where(
        j < q_len[:, None], q_start[:, None] + j,
        T + jnp.arange(B * (K - 1), dtype=jnp.int32).reshape(B, K - 1))
    y = y.at[rows.reshape(-1)].set(
        opening.reshape(-1, D), mode="drop", unique_indices=True)
    # new tail: entries q_len .. q_len+K-2 of (old tail ++ the slice)
    m = q_len[:, None] + j  # [B, K-1]
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


# ---------------- Mamba-2 ----------------

#: tokens a chunk of the packed form holds.  The decay mask is
#: [T / chunk, heads, chunk, chunk] float32 and the per-lane windows
#: [lanes, chunk, heads, head_dim]: both grow with it; the chunk-end states
#: [T / chunk, heads, head_dim, d_state] shrink.  Measured on the chip by
#: chunk size in docs/kernels.md ("Mamba-2").
SSD_CHUNK = 64

#: the state's matrix products run in full float32: at the default
#: precision the TPU would round the state and the decay-weighted inputs to
#: bf16, which the one-step form (elementwise, float32) does not
_HP = jax.lax.Precision.HIGHEST


def ssd_step(x, dt, A, Bm, Cm, D, state, live):
    """One token per lane.  x [B, H, P] float32; dt [B, H] (after its
    softplus); A [H] (negative); Bm, Cm [B, G, N] (head h reads group
    h // (H / G)); D [H]; state [B, H, P, N] float32; live [B] bool (a lane
    that is not live keeps its state).  `S = exp(dt A) S + dt x (x) B`,
    `y = S C + D x`.  Returns (y [B, H, P], new state)."""
    H, G = x.shape[1], Bm.shape[1]
    Bh = jnp.repeat(Bm, H // G, axis=1)  # [B, H, N]
    Ch = jnp.repeat(Cm, H // G, axis=1)
    dA = jnp.exp(dt * A[None])
    s = (dA[:, :, None, None] * state
         + (dt[:, :, None] * x)[..., None] * Bh[:, :, None, :])
    y = jnp.sum(s * Ch[:, :, None, :], axis=-1) + D[None, :, None] * x
    return y, jnp.where(live[:, None, None, None], s, state)


def ssd_ragged(x, dt, A, Bm, Cm, D, state, token_seq, q_start, q_len,
               last_idx, fresh, chunk: int = SSD_CHUNK):
    """The packed buffer.  x [T, H, P] float32; dt [T, H]; A [H]; Bm, Cm
    [T, G, N]; D [H]; state [B, H, P, N]; token_seq [T] (-1 = padding);
    q_start, q_len, last_idx [B]; fresh [B] bool (start from zero).  Every
    lane has at most one slice, of adjacent tokens.  Returns (y [T, H, P],
    new state [B, H, P, N]).

    The buffer is cut into chunks of Q = gcd(T, chunk) tokens wherever the
    slices lie; a SEGMENT is what one lane has of one chunk.  A token's
    output is the sum of

    - `intra`: the tokens before it in its segment, as one masked product a
      chunk and head: `((C B^T) o L) (dt x)`, `L[t, s] = exp(sum of dt A
      over (s, t])` where s <= t lie in one segment, else 0;
    - `inter`: where its segment continues its lane's slice from the chunk
      before, the state at that chunk's end, decayed: the chunk-end states
      obey `F_c = alpha_c F_{c-1} + beta_c` (alpha: the chunk's decay where
      it is all one continuing segment, else 0; beta: what the chunk's LAST
      segment adds, and the lane's stored state where that segment opens
      the slice), solved by an associative scan over the chunks;
    - `head`: where its segment opens its lane's slice, the lane's stored
      state, decayed, computed per LANE over a window of Q tokens from the
      slice's start (a chunk holds up to Q lanes; a state per token or per
      block would not fit).

    What a lane keeps is computed per lane likewise, over the window of Q
    tokens that ends at its last token.  Padding has decay 1 and adds
    nothing.  Arrays over [T, H, P, N] never exist."""
    T, H, P = x.shape
    G, N = Bm.shape[1:]
    B = state.shape[0]
    Q = math.gcd(T, chunk)
    nc, rep = T // Q, H // G
    f32 = jnp.float32
    valid = token_seq >= 0
    lane = jnp.maximum(token_seq, 0)
    a = jnp.where(valid[:, None], dt * A[None], 0.0)  # log decay, <= 0
    dtx = jnp.where(valid[:, None, None], dt[:, :, None] * x, 0.0)
    stored = jnp.where(fresh[:, None, None, None], 0.0, state)
    stored_g = stored.reshape(B, G, rep, P, N)

    ac = a.reshape(nc, Q, H)
    cum = jnp.cumsum(ac, axis=1)  # inclusive, inside the chunk
    cumex = cum - ac  # exclusive
    cum_t, cumex_t = cum.reshape(T, H), cumex.reshape(T, H)
    seq = token_seq.reshape(nc, Q)
    first = jnp.arange(nc, dtype=jnp.int32) * Q  # a chunk's first token
    Bc = Bm.astype(f32).reshape(nc, Q, G, N)
    Cc = Cm.astype(f32).reshape(nc, Q, G, N)
    dtx_c = dtx.reshape(nc, Q, G, rep, P)

    def decay(exponent, keep):
        """exp(exponent) where `keep`, else 0 (the exponent is <= 0 there;
        elsewhere it may be anything)."""
        return jnp.exp(jnp.where(keep, exponent, -jnp.inf))

    # intra: [nc, H, Q, Q] masks, never per token and state
    t_ix = jnp.arange(Q, dtype=jnp.int32)
    same = ((seq[:, :, None] == seq[:, None, :]) & (seq[:, :, None] >= 0)
            & (t_ix[:, None] >= t_ix[None, :])[None])  # [nc, t, s]
    cum_h = cum.transpose(0, 2, 1)  # [nc, H, Q]
    L = decay(cum_h[:, :, :, None] - cum_h[:, :, None, :], same[:, None])
    scores = jnp.einsum("ctgn,csgn->cgts", Cc, Bc, precision=_HP)
    W = L.reshape(nc, G, rep, Q, Q) * scores[:, :, None]
    y = jnp.einsum("cgrts,csgrp->ctgrp", W, dtx_c, precision=_HP)

    # the chunk-end states F_c, of the lane that holds the chunk's end
    tail = seq[:, -1]
    tl = jnp.maximum(tail, 0)
    in_tail = (seq == tail[:, None]) & (tail[:, None] >= 0)
    w_end = decay(cum[:, -1:, :] - cum, in_tail[..., None])  # [nc, Q, H]
    S_end = jnp.einsum(
        "cqgrp,cqgn->cgrpn", w_end.reshape(nc, Q, G, rep, 1) * dtx_c, Bc,
        precision=_HP).reshape(nc, H, P, N)
    continues = (tail >= 0) & (q_start[tl] < first)  # one segment, all of it
    opens = (tail >= 0) & ~continues
    off = jnp.clip(q_start[tl] - first, 0, Q - 1)
    from_open = cum[:, -1] - jnp.take_along_axis(
        cumex, off[:, None, None], axis=1)[:, 0]  # [nc, H]
    alpha = decay(cum[:, -1], continues[:, None])
    beta = S_end + (decay(from_open, opens[:, None])[:, :, None, None]
                    * stored[tl])

    def combine(left, right):
        a1, b1 = left
        a2, b2 = right
        return a1 * a2, a2[:, :, None, None] * b1 + b2

    _, F = jax.lax.associative_scan(combine, (alpha, beta), axis=0)
    E = jnp.concatenate([jnp.zeros_like(F[:1]), F[:-1]], axis=0)  # entering

    # inter: the first segment of a chunk, where it continues a slice
    head_lane = jnp.maximum(seq[:, 0], 0)
    cont = (seq[:, 0] >= 0) & (q_start[head_lane] < first)
    in_first = (seq == seq[:, :1]) & cont[:, None]
    y = y + (decay(cum, in_first[..., None]).reshape(nc, Q, G, rep, 1)
             * jnp.einsum("cqgn,cgrpn->cqgrp", Cc,
                          E.reshape(nc, G, rep, P, N), precision=_HP))
    y = y.reshape(T, H, P)

    # head: per lane, the first segment of its slice from its stored state
    q_ix = jnp.arange(Q, dtype=jnp.int32)[None, :]
    idx = q_start[:, None] + q_ix  # [B, Q]
    chunk_end = (q_start // Q + 1) * Q
    ok = (q_ix < q_len[:, None]) & (idx < chunk_end[:, None])
    idx = jnp.minimum(idx, T - 1)
    start_ex = cumex_t[q_start]  # [B, H]
    w_head = decay(cum_t[idx] - start_ex[:, None, :], ok[..., None])
    y_head = (w_head.reshape(B, Q, G, rep, 1)
              * jnp.einsum("bqgn,bgrpn->bqgrp", Cm.astype(f32)[idx], stored_g,
                           precision=_HP)).reshape(B * Q, H, P)
    t_all = jnp.arange(T, dtype=jnp.int32)
    into = t_all - q_start[lane]
    in_head = valid & (t_all // Q == q_start[lane] // Q)
    y = y + jnp.where(in_head[:, None, None],
                      y_head[lane * Q + jnp.clip(into, 0, Q - 1)], 0.0)
    y = y + D[None, :, None] * x

    # what each lane keeps: its last segment's sum, over the window that
    # ends at its last token, and what that segment started from
    c_last = last_idx // Q
    seg_start = jnp.maximum(q_start, c_last * Q)
    idx = last_idx[:, None] - (Q - 1) + q_ix
    ok = (idx >= seg_start[:, None]) & (q_len > 0)[:, None]
    idx = jnp.clip(idx, 0, T - 1)
    end_cum = cum_t[last_idx]  # [B, H]
    w_last = decay(end_cum[:, None, :] - cum_t[idx], ok[..., None])
    S_last = jnp.einsum(
        "bqgrp,bqgn->bgrpn",
        w_last.reshape(B, Q, G, rep, 1) * dtx[idx].reshape(B, Q, G, rep, P),
        Bm.astype(f32)[idx], precision=_HP).reshape(B, H, P, N)
    carried = q_start < c_last * Q  # the last segment continues the slice
    started = jnp.where(carried[:, None, None, None], E[c_last], stored)
    w_init = jnp.where(carried[:, None], jnp.exp(end_cum),
                       jnp.exp(end_cum - start_ex))
    new_state = jnp.where(
        (q_len > 0)[:, None, None, None],
        S_last + w_init[:, :, None, None] * started, state)
    return y, new_state
