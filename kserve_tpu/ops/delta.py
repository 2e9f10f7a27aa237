"""The gated delta rule with a decay per channel (Kimi Delta Attention,
arXiv:2510.26692) over the engine's two token layouts.

A head keeps a float32 matrix `S [d_k, d_v]` a lane (keys down, values
across).  One token with key `k`, value `v`, query `q` (all `[d]`), a log
decay `g [d_k]` (<= 0: one decay a KEY channel, where Mamba-2 has one a
head) and a step size `beta`:

    S' = Diag(exp(g)) S
    S  = S' + beta k (v - S'^T k)^T     = (I - beta k k^T) S' + beta k v^T
    o  = S^T q

Two forms, the same mathematics (as ops/ssm.py's `ssd_*`):

- `kda_step`: one token a lane, elementwise over `[lanes, heads, d, d]` and
  two contractions over the key channel;
- `kda_ragged`: the packed `[T]` buffer.  A lane's slice is cut into PIECES
  of `KDA_CHUNK` tokens from the slice's own start, wherever it lies in the
  buffer.  A piece reads its lane's state and leaves it the state after its
  last token, so a slice's second piece starts from its first's, a slice
  that continues a request from the state the lane stored, and nothing of
  shape `[T, heads, d, d]` exists.  Inside a piece the WY / UT form of the
  delta rule: with `G` the running sum of `g` inside the piece,

      A[i, j] = beta_i sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])     (j < i)
      (I + A) [U | W] = [beta v | beta exp(G) k]      (one unit lower system,
                                                       `_unit_lower_inverse`)
      u = U - W S_in                                  (the pieces' "new values")
      o = (exp(G) q) S_in + B u,    B[i, j] = sum_c q_i k_j exp(G_i - G_j)  (j <= i)
      S_out = Diag(exp(G_n)) S_in + (k exp(G_n - G))^T u

  Only the last three lines read the state: the pieces go one after the
  other in one scan, live pieces first, and a dead one is skipped.  A piece
  cuts its window out of each buffer by one gather of rows (`first +
  arange`, clamped to the buffer; rows past its count are masked) and
  writes its rows to the output by one scatter that drops the others; a
  lane without a slice is written by nothing.  Grouping the pieces to run
  what does not read the state for several at once LOSES on the v5e at 64
  heads of 128: a piece's arrays (2 MB each, 33.5 MB of differences) stay
  in the chip's fast memory and a group's do not (docs/kernels.md, "Delta
  rule": PR 53's rows).

  A lane that adds ONE token (a decode lane of the `mixed` program's packed
  step) takes `kda_step` instead of a piece of its own.

The numerical trap of a decay per channel.  `exp(G_i - G_j)` does not factor
into `exp(G_i) exp(-G_j)`: `-G_j` passes float32's range within a piece
once a channel decays fast (g = -5 a token: exp(320) at token 64).  Every
exponent formed here is a DIFFERENCE `G_i - G_j` with `i >= j`, so <= 0
(`_decayed_scores`): inside a sub-block of `KDA_SUB` tokens the `[sub, sub,
d]` block of differences itself, between sub-blocks through a reference
point at the row sub-block's start, `exp(G_i - G_ref) exp(G_ref - G_j)`,
both <= 0, which is a matrix product again.  An exponent that underflows is
a contribution that is zero to float32 anyway.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: tokens a piece of the packed form holds, and the sub-block inside it whose
#: decays are formed as a block of differences (measured on the chip by
#: size in docs/kernels.md, "Delta rule, a decay per channel")
KDA_CHUNK = 64
KDA_SUB = 16

#: the state's matrix products and the solve run in full float32 (ops/ssm._HP)
_HP = jax.lax.Precision.HIGHEST


def kda_step(q, k, v, g, beta, state, live):
    """One token a lane.  q, k, v, g [B, H, d] float32 (q and k as the
    recurrence takes them: normalised, q scaled); beta [B, H]; state
    [B, H, d, d] float32; live [B] bool (a lane that is not live keeps its
    state).  Returns (o [B, H, d], new state)."""
    decayed = jnp.exp(g)[..., None] * state
    seen = jnp.sum(k[..., None] * decayed, axis=-2)  # S'^T k
    u = beta[..., None] * (v - seen)
    s = decayed + k[..., None] * u[..., None, :]
    o = jnp.sum(q[..., None] * s, axis=-2)
    return o, jnp.where(live[:, None, None, None], s, state)


def _decayed_scores(x, k, G, sub: int):
    """`P[n, h, i, j] = sum_c x[n, i, h, c] k[j, h, c] exp(G[i, h, c] -
    G[j, h, c])` for j <= i, 0 above the diagonal.  x [n, Q, H, d] (the
    row operands, stacked), k, G [Q, H, d] with G non-increasing along Q.
    No exponent above 0 is formed (module docstring)."""
    n, Q, H, d = x.shape
    ns = Q // sub
    xb = x.reshape(n, ns, sub, H, d)
    kb, Gb = k.reshape(ns, sub, H, d), G.reshape(ns, sub, H, d)
    # the reference of row sub-block I: G just before its first token
    ref = jnp.concatenate([jnp.zeros_like(G[:1]), G[sub - 1:-1:sub]], axis=0)
    rows = xb * jnp.exp(Gb - ref[:, None])[None]
    before = (jnp.arange(Q)[None, :] < (jnp.arange(ns) * sub)[:, None])
    cols = k[None] * jnp.exp(jnp.where(
        before[:, :, None, None], ref[:, None] - G[None], -jnp.inf))
    off = jnp.einsum("nIthc,Ijhc->nhItj", rows, cols, precision=_HP)
    tri = jnp.tril(jnp.ones((sub, sub), bool))
    inside = jnp.exp(jnp.where(tri[None, :, :, None, None],
                               Gb[:, :, None] - Gb[:, None, :], -jnp.inf))
    diag = jnp.sum(xb[:, :, :, None] * (kb[:, None] * inside)[None], axis=-1)
    diag = diag.transpose(0, 4, 1, 2, 3)  # [n, H, ns, sub(t), sub(j)]
    full = (off.reshape(n, H, ns, sub, ns, sub)
            + diag[:, :, :, :, None, :]
            * jnp.eye(ns, dtype=diag.dtype)[:, None, :, None])
    return full.reshape(n, H, Q, Q)


def _unit_lower_inverse(A):
    """`(I + A)^-1` of strictly lower triangular A [H, Q, Q], by halves:
    `[[L11, 0], [L21, L22]]^-1 = [[L11^-1, 0], [-L22^-1 L21 L11^-1,
    L22^-1]]`, from blocks of one row up, every level two products of all
    its blocks.  No power of A is formed (a Neumann series' terms pass
    float32 where keys repeat and beta nears 2), and no library call:
    `lax.linalg.triangular_solve` is a LAPACK custom call on the CPU, and a
    `mixed` program that holds one does not survive the AOT cache there (it
    crashes when loaded again).

    The blocks' rows and columns stand IN FRONT of the heads and the
    products are sums of elementwise products over them, not matrix
    products: a level's blocks are `[s, s]` with s from 1 up, and as the
    last two axes of an array the chip pads each to a tile of 8 x 128 (as
    batched matrix products the levels took 5.7 of a 4096-token call's 19.2
    ms, so about 1: PR 53's rows in docs/kernels.md); with the heads last
    every lane multiplies."""
    H, Q, _ = A.shape
    P = 1 << max(Q - 1, 0).bit_length()  # rows of padding solve to themselves
    low = jnp.pad(A, ((0, 0), (0, P - Q), (0, P - Q))).transpose(1, 2, 0)

    def times(x, y):  # [nb, s, s, H] each
        return (x[:, :, :, None] * y[:, None]).sum(axis=2)

    inv = jnp.ones((P, 1, 1, H), A.dtype)
    s = 1
    while s < P:
        nb = P // (2 * s)
        # the blocks under the diagonal of this level's [2 s, 2 s] blocks
        same = jnp.eye(nb, dtype=bool).reshape(nb, 1, nb, 1, 1)
        below = jnp.where(
            same, low.reshape(nb, 2, s, nb, 2, s, H)[:, 1, :, :, 0],
            0.0).sum(axis=2)  # [nb, s, s, H]
        halves = inv.reshape(nb, 2, s, s, H)
        first, second = halves[:, 0], halves[:, 1]
        off = -times(times(second, below), first)
        inv = jnp.concatenate([
            jnp.concatenate([first, jnp.zeros_like(first)], axis=2),
            jnp.concatenate([off, second], axis=2)], axis=1)
        s *= 2
    return inv[0, :Q, :Q].transpose(2, 0, 1)


def _piece(q, k, v, g, beta, n, S, sub: int):
    """One piece of one lane: q, k, v, g [Q, H, d], beta [Q, H], its first
    `n` rows tokens, S [H, d, d] the state it starts from.  Returns
    (o [Q, H, d], the state after token n)."""
    Q = q.shape[0]
    live = jnp.arange(Q) < n
    g = jnp.where(live[:, None, None], g, 0.0)
    beta = jnp.where(live[:, None], beta, 0.0)  # a dead row adds nothing
    G = jnp.cumsum(g, axis=0)
    since_start = jnp.exp(G)  # what is left of the entering state, a row
    P = _decayed_scores(jnp.stack([k, q]), k, G, sub)
    A = jnp.tril(P[0], -1) * beta.T[:, :, None]
    rhs = beta[..., None] * jnp.concatenate([v, since_start * k], axis=-1)
    solved = jnp.einsum("hqj,jhe->hqe", _unit_lower_inverse(A), rhs,
                        precision=_HP)  # [H, Q, 2 d]
    d = v.shape[-1]
    U, W = solved[..., :d], solved[..., d:]
    u = U - jnp.einsum("hqc,hce->hqe", W, S, precision=_HP)
    o = (jnp.einsum("qhc,hce->qhe", since_start * q, S, precision=_HP)
         + jnp.einsum("hqj,hje->qhe", P[1], u, precision=_HP))
    to_end = k * jnp.exp(G[-1:] - G)
    S_out = (since_start[-1][..., None] * S
             + jnp.einsum("jhc,hje->hce", to_end, u, precision=_HP))
    return o, S_out


def kda_ragged(q, k, v, g, beta, state, q_start, q_len, fresh,
               chunk: int = KDA_CHUNK, sub: int = KDA_SUB):
    """The packed buffer.  q, k, v, g [T, H, d] float32; beta [T, H]; state
    [B, H, d, d]; q_start, q_len [B] (padding belongs to no slice); fresh
    [B] bool (the slice starts from zero).  Every lane has at most one
    slice, of adjacent tokens.  Returns (o [T, H, d], new state): a lane
    without a slice keeps its state (module docstring)."""
    T, H, d = q.shape
    B = state.shape[0]
    Q = min(chunk, T)
    sub = math.gcd(Q, sub)
    # a lane without a slice is never written below: it keeps its state
    states = jnp.where((fresh & (q_len > 0))[:, None, None, None], 0.0, state)
    # lanes that add one token: the one-step form, all of them at once
    single = q_len == 1
    at = jnp.clip(q_start, 0, T - 1)
    o_single, states = kda_step(
        q[at], k[at], v[at], g[at], beta[at], states, single)
    # the pieces of the longer slices, in lane order: the live ones first
    pieces = jnp.where(q_len > 1, -(-q_len // Q), 0)
    ends = jnp.cumsum(pieces)
    n_pieces = T // Q + min(B, T // 2)  # full pieces + one partial a lane
    index = jnp.arange(n_pieces, dtype=jnp.int32)
    lane = jnp.minimum(
        jnp.searchsorted(ends, index, side="right").astype(jnp.int32), B - 1)
    offset = (index - (ends - pieces)[lane]) * Q
    first = q_start[lane] + offset
    count = jnp.where(index < ends[-1],
                      jnp.clip(q_len[lane] - offset, 0, Q), 0)

    def run(carry, piece):
        b, t0, n = piece

        def one(carry):
            states, out = carry
            rows = t0 + jnp.arange(Q)
            window = jnp.minimum(rows, T - 1)  # rows past `n` are masked
            o, s = _piece(q[window], k[window], v[window], g[window],
                          beta[window], n, states[b], sub)
            to = jnp.where(jnp.arange(Q) < n, rows, T)  # the others: dropped
            return states.at[b].set(s), out.at[to].set(o, mode="drop")

        return jax.lax.cond(n > 0, one, lambda c: c, carry), None

    (states, out), _ = jax.lax.scan(
        run, (states, jnp.zeros((T, H, d), jnp.float32)), (lane, first, count))
    out = out.at[jnp.where(single, q_start, T)].set(o_single, mode="drop")
    return out, states
