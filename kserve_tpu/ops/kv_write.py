"""K/V rows into the page-major cache (engine/kvcache.py builds it), as
traced inside the models' forwards: a decode step's one token a lane, a
packed step's ragged slices, a legacy prefill's padded batch.  Each is XLA's
row scatter (`_scatter_kv`) or, where ops/attention.kv_write_path says so,
the page kernel of ops/pallas_kv_write.py.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .attention import kv_write_path


@jax.named_scope("kv_write")
def write_prompt_kv_batch(
    kv_pages: jnp.ndarray,  # [num_pages, 2, n_kv, ps, d]
    k: jnp.ndarray,  # [B, T, n_kv, d]
    v: jnp.ndarray,  # [B, T, n_kv, d]
    page_ids: jnp.ndarray,  # [B, max_pages] int32
    valid_len: jnp.ndarray,  # [B] int32
    page_size: int,
) -> jnp.ndarray:
    """Batched prompt scatter (one op for the whole prefill batch)."""
    B, T = k.shape[:2]
    t = jnp.arange(T, dtype=jnp.int32)
    page_idx = jnp.broadcast_to(t // page_size, (B, T))
    page_of = jnp.take_along_axis(page_ids, page_idx, axis=1)  # [B, T]
    page_of = jnp.where(t[None, :] < valid_len[:, None], page_of, 0)
    slot_of = jnp.broadcast_to(t % page_size, (B, T)).reshape(-1)
    pages_flat = page_of.reshape(-1)
    return _scatter_kv(kv_pages, k, v, pages_flat, slot_of)


@jax.named_scope("kv_write")
def write_chunk_kv_batch(
    kv_pages,  # [num_pages, 2, nkv, ps, d] or (int8 pages, scales)
    k: jnp.ndarray,  # [B, C, n_kv, d] — chunk keys
    v: jnp.ndarray,  # [B, C, n_kv, d]
    page_ids: jnp.ndarray,  # [B, max_pages] int32 — the SEQUENCE's pages
    chunk_start: jnp.ndarray,  # [B] absolute position of chunk token 0
    valid_len: jnp.ndarray,  # [B] valid tokens within the chunk
    page_size: int,
):
    """write_prompt_kv_batch generalized to an offset chunk (chunked
    prefill): chunk token t lands at absolute position chunk_start+t."""
    B, C = k.shape[:2]
    t = jnp.arange(C, dtype=jnp.int32)
    pos = chunk_start[:, None] + t[None, :]  # [B, C]
    page_idx = pos // page_size
    page_of = jnp.take_along_axis(page_ids, page_idx, axis=1)
    page_of = jnp.where(t[None, :] < valid_len[:, None], page_of, 0)
    slot_of = (pos % page_size).reshape(-1)
    pages_flat = page_of.reshape(-1)
    return _scatter_kv(kv_pages, k, v, pages_flat, slot_of)


def _scatter_kv(kv_pages, k, v, pages_flat, slot_flat):
    """Scatter K/V rows (k/v: [N, ..., n_kv, d] flattened to [Nf, n_kv, d])
    into a plain or quantized ((int8 pages, scales)) cache at the given
    flat (page, slot) indices.  `v` None: LATENT pages [num_pages, 1, 1,
    ps, row] (StateLayout): one plane, `k` the rows as [N, 1, row].

    A ROW scatter: every [d] row is addressed by all four leading dims
    (page, k/v, head, slot), so the update window is the minor dim alone.
    Indexing (page, slot) with a [2, n_kv, d] window instead makes XLA's
    TPU layout assignment move the slot dim out of the tiled minor pair —
    the cache then no longer has the row-major layout the Pallas kernels'
    page DMAs require, and every layer of every step copies the WHOLE
    cache into the scatter's layout and back (at 4096 pages x 28 layers
    that is 12 GiB of temporaries: the `mixed` program did not fit a
    16 GB chip).  The head index is an iota, which GSPMD partitions along
    the model-sharded head dim with no collective."""
    lead = int(np.prod(k.shape[:-2])) if k.ndim > 3 else k.shape[0]
    kf = k.reshape(lead, k.shape[-2], k.shape[-1])
    if v is None:
        return kv_pages.at[pages_flat[:, None, None], 0, 0,
                           slot_flat[:, None, None], :].set(
            kf[:, None].astype(kv_pages.dtype), mode="drop",
            unique_indices=False)
    vf = v.reshape(lead, v.shape[-2], v.shape[-1])
    page_ix = pages_flat[:, None, None]
    kv_ix = jnp.arange(2, dtype=jnp.int32)[None, :, None]
    head_ix = jnp.arange(kf.shape[1], dtype=jnp.int32)[None, None, :]
    slot_ix = slot_flat[:, None, None]
    if isinstance(kv_pages, tuple):
        pages, scales = kv_pages
        qk, sk = quantize_rows(kf)  # [Nf, n_kv, d] int8, [Nf, n_kv]
        qv, sv = quantize_rows(vf)
        values = jnp.stack([qk, qv], axis=1)  # [Nf, 2, n_kv, d]
        svals = jnp.stack([sk, sv], axis=1)  # [Nf, 2, n_kv]
        pages = pages.at[page_ix, kv_ix, head_ix, slot_ix, :].set(
            values, mode="drop", unique_indices=False
        )
        scales = scales.at[page_ix, kv_ix, head_ix, slot_ix].set(
            svals, mode="drop", unique_indices=False
        )
        return pages, scales
    values = jnp.stack([kf, vf], axis=1).astype(kv_pages.dtype)
    return kv_pages.at[page_ix, kv_ix, head_ix, slot_ix, :].set(
        values, mode="drop", unique_indices=False
    )


def slice_runs(q_start, q_len, kv_start):
    """The packed buffer's slices as the page write's runs
    (`write_ragged_kv`): one set, lane b's q_len[b] tokens from buffer
    index q_start[b] to positions kv_start[b] .. of its own pages."""
    return [(jnp.arange(q_start.shape[0], dtype=jnp.int32), q_start, q_len,
             kv_start)]


def _page_kernel(page_kernel, kv_pages, v) -> bool:
    """Whether a write runs as the page kernel (ops/pallas_kv_write.py):
    the caller's word, or ops/attention.kv_write_path's from what the trace
    can see.  A caller whose cache is sharded over a mesh says False."""
    if page_kernel is None:
        return kv_write_path(kv_pages, v) == "page_kernel"
    return page_kernel


@jax.named_scope("kv_write")
def write_ragged_kv(
    kv_pages,  # [num_pages, 2, n_kv, ps, d] or (int8 pages, scales)
    k: jnp.ndarray,  # [T, n_kv, d] — packed ragged slice keys
    v,  # [T, n_kv, d]; None: latent pages, k the rows [T, 1, row]
    page_table: jnp.ndarray,  # [B, max_pages_per_seq]
    token_seq: jnp.ndarray,  # [T] sequence index per packed token (-1 = pad)
    token_pos: jnp.ndarray,  # [T] absolute position per packed token
    page_size: int,
    runs=None,  # the same tokens as sets of runs (row, src, n, pos), each
    # [M]: run m is buffer rows src[m] .. src[m] + n[m] at positions pos[m]
    # .. of page_table[row[m]].  No two runs of a set on one page; the sets
    # are written one after the other
    page_kernel: Optional[bool] = None,  # None: kv_write_path decides
):
    """Ragged-batch write: each packed token lands at its sequence's
    (page, slot) for its absolute position.  Decode steps (one token per
    sequence) and prompt chunks (many) are the same write — the write half
    of the ragged contract (docs/kernels.md).  Given the tokens as `runs`
    it is a page write where the kernel runs (padding tokens then write
    nothing); else a row scatter (padding tokens, seq -1, write to the null
    page)."""
    if runs is not None and _page_kernel(page_kernel, kv_pages, v):
        from .pallas_kv_write import write_runs

        for one in runs:
            kv_pages = write_runs(kv_pages, k, v, page_table, *one)
        return kv_pages
    valid = token_seq >= 0
    seq_ix = jnp.maximum(token_seq, 0)
    page = jnp.where(
        valid, page_table[seq_ix, token_pos // page_size], 0)
    slot = token_pos % page_size
    return _scatter_kv(
        kv_pages, k[:, None], None if v is None else v[:, None], page, slot)


@jax.named_scope("kv_write")
def append_token_kv(
    kv_pages: jnp.ndarray,  # [num_pages, 2, n_kv, ps, d]
    k: jnp.ndarray,  # [B, n_kv, d]
    v,  # [B, n_kv, d]; None: latent pages, k the rows [B, 1, row]
    page_table: jnp.ndarray,  # [B, max_pages_per_seq]
    pos: jnp.ndarray,  # [B] position being written
    active: jnp.ndarray,  # [B] bool
    page_size: int,
    page_kernel: Optional[bool] = None,  # None: kv_write_path decides
) -> jnp.ndarray:
    """Decode-step write: one new token per active sequence.  An inactive
    lane writes nothing where the page kernel runs, and to the null page
    where the scatter does."""
    if _page_kernel(page_kernel, kv_pages, v):
        from .pallas_kv_write import append_rows

        return append_rows(kv_pages, k, v, page_table, pos, active)
    B = k.shape[0]
    b = jnp.arange(B, dtype=jnp.int32)
    page = jnp.where(active, page_table[b, pos // page_size], 0)
    slot = pos % page_size
    return _scatter_kv(
        kv_pages, k[:, None], None if v is None else v[:, None], page, slot)


# ---------------- int8 KV quantization (opt-in, kv_quant="int8") ----------------
#
# Decode is KV-bandwidth-bound (the gather reads the live context every
# step); int8 halves that traffic vs bf16 and doubles KV capacity.  Scales
# are per (page, k/v, head, token-row) — absmax over head_dim — stored in a
# parallel [num_pages, 2, n_kv, ps] f32 array (~3% overhead at d=128).  A
# quantized layer cache travels as the tuple (pages_int8, scales).

def quantize_rows(x: jnp.ndarray) -> tuple:
    """x [..., d] -> (int8 rows, f32 row scales): symmetric absmax."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale[..., 0]


def dequantize_rows(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)).astype(dtype)
