"""Attention ops: causal prefill, paged-KV decode, and the unified ragged
paged-attention contract for mixed prefill+decode batches.

Decode attention over the paged cache has two implementations:
- `paged_attention_xla`: pure-XLA gather + masked softmax (portable, used on
  CPU test meshes and as the safety net).
- `paged_attention_pallas` (ops/pallas_paged_attention.py): fused kernel that
  streams pages HBM->VMEM without materializing the gathered KV (the Ragged
  Paged Attention approach; see PAPERS.md).

The RAGGED contract (docs/kernels.md) generalizes both: every sequence in
the batch contributes an arbitrary-length query slice — a full prompt, a
prompt chunk, or a single decode token — packed into one [T, nq, d] token
buffer with per-sequence (q_start, q_len, kv_start) metadata.  The caller
writes the slice's K/V into the paged cache FIRST (ops/kv_write.write_ragged_kv),
then attention reads everything from pages with a causal mask anchored at
each sequence's kv offset, so prompt chunks and decode steps fold into the
same online-softmax program:
- `ragged_paged_attention_xla`: the gather-based reference (CPU-runnable
  numerics ground truth; also the production path off-TPU).
- `ragged_paged_attention_pallas` (ops/pallas_paged_attention.py): the
  fused kernel, verified against the reference in interpret mode.
- `ragged_single_token_split_pallas` (same file): the lanes that bring ONE
  token through the decode kernel in one call, the longer slices through
  the ragged kernel; `ragged_attention_path` says where it is traced.

Role parity: replaces vLLM's CUDA PagedAttention, which the reference uses
through the vLLM engine (SURVEY.md §2.3 "Sequence/context parallel" row).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp


def _gqa_scores(q: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """q:[B,Tq,nq,d] k:[B,Tk,nkv,d] -> scores [B,nq,Tq,Tk] with GQA groups."""
    B, Tq, nq, d = q.shape
    nkv = k.shape[2]
    group = nq // nkv
    qg = q.reshape(B, Tq, nkv, group, d)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg.astype(jnp.float32), k.astype(jnp.float32))
    return scores.reshape(B, nkv * group, Tq, k.shape[1])


def _gqa_out(weights: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """weights:[B,nq,Tq,Tk] v:[B,Tk,nkv,d] -> [B,Tq,nq,d]."""
    B, nq, Tq, Tk = weights.shape
    nkv = v.shape[2]
    group = nq // nkv
    wg = weights.reshape(B, nkv, group, Tq, Tk)
    out = jnp.einsum("bkgts,bskd->btkgd", wg, v.astype(jnp.float32))
    return out.reshape(B, Tq, nq, v.shape[3])


def causal_prefill_attention(
    q: jnp.ndarray,  # [B, T, nq, d]
    k: jnp.ndarray,  # [B, T, nkv, d]
    v: jnp.ndarray,  # [B, T, nkv, d]
    valid_len: jnp.ndarray,  # [B] int32
    logit_softcap: float = 0.0,
    scale: Optional[float] = None,  # default 1/sqrt(d); Gemma overrides
    window=None,  # traced int32 scalar; >0 = sliding-window width
) -> jnp.ndarray:
    """Causal self-attention over the prompt (no cache read)."""
    B, T, nq, d = q.shape
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    scores = _gqa_scores(q, k) * scale  # [B,nq,T,T]
    if logit_softcap > 0.0:
        scores = jnp.tanh(scores / logit_softcap) * logit_softcap
    t = jnp.arange(T)
    causal = t[None, :] <= t[:, None]  # [Tq, Tk]
    valid = t[None, :] < valid_len[:, None]  # [B, Tk]
    mask = causal[None, None, :, :] & valid[:, None, None, :]
    if window is not None:
        dist = t[:, None] - t[None, :]  # q - k
        wmask = (dist < window) | (window <= 0)
        mask = mask & wmask[None, None, :, :]
    scores = jnp.where(mask, scores, -1e30)
    weights = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(weights, v)
    return out.astype(q.dtype)


def _gather_history(kv_pages, page_table):
    """Gather history pages from a plain or quantized cache ->
    (k [B,H,nkv,d], v [B,H,nkv,d]) dequantized."""
    if isinstance(kv_pages, tuple):
        pages, scales = kv_pages
        B, W = page_table.shape
        nkv, ps, d = pages.shape[2], pages.shape[3], pages.shape[4]
        g = pages[page_table]  # [B, W, 2, nkv, ps, d] int8
        s = scales[page_table]  # [B, W, 2, nkv, ps]
        from .kv_write import dequantize_rows

        # dequantize to bf16: the attention math upcasts to f32 internally,
        # and a f32 intermediate would double the bandwidth the int8 cache
        # exists to save
        deq = dequantize_rows(
            g.transpose(0, 1, 2, 4, 3, 5), s.transpose(0, 1, 2, 4, 3),
            jnp.bfloat16,
        )  # [B, W, 2, ps, nkv, d]
        k = deq[:, :, 0].reshape(B, W * ps, nkv, d)
        v = deq[:, :, 1].reshape(B, W * ps, nkv, d)
        return k, v
    B, W = page_table.shape
    nkv, ps, d = kv_pages.shape[2], kv_pages.shape[3], kv_pages.shape[4]
    gathered = kv_pages[page_table]  # [B, W, 2, nkv, ps, d]
    k = gathered[:, :, 0].transpose(0, 1, 3, 2, 4).reshape(B, W * ps, nkv, d)
    v = gathered[:, :, 1].transpose(0, 1, 3, 2, 4).reshape(B, W * ps, nkv, d)
    return k, v


def chunked_prefill_attention(
    q: jnp.ndarray,  # [B, C, nq, d] — current chunk queries
    k_chunk: jnp.ndarray,  # [B, C, nkv, d] — current chunk keys
    v_chunk: jnp.ndarray,  # [B, C, nkv, d]
    kv_pages,  # [num_pages, 2, nkv, ps, d] (or (int8, scales)) — cache w/ history
    page_table: jnp.ndarray,  # [B, W] pages holding positions 0..history-1
    history_len: jnp.ndarray,  # [B] tokens already in the cache
    valid_len: jnp.ndarray,  # [B] valid tokens within THIS chunk
    logit_softcap: float = 0.0,
    scale: Optional[float] = None,
    window=None,  # traced int32 scalar; >0 = sliding-window width
) -> jnp.ndarray:
    """Causal attention for a prefill CHUNK: queries attend to the cached
    history (gathered from pages) plus the causal prefix of the chunk
    itself.  This is what makes chunked prefill and prefix-cache reuse
    possible — the first chunk (history_len=0) degenerates to plain causal
    prefill attention."""
    B, C, nq, d = q.shape
    k_hist, v_hist = _gather_history(kv_pages, page_table)
    H = k_hist.shape[1]
    k_all = jnp.concatenate([k_hist, k_chunk.astype(k_hist.dtype)], axis=1)
    v_all = jnp.concatenate([v_hist, v_chunk.astype(v_hist.dtype)], axis=1)
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    scores = _gqa_scores(q, k_all) * scale  # [B, nq, C, H+C]
    if logit_softcap > 0.0:
        scores = jnp.tanh(scores / logit_softcap) * logit_softcap
    hist_pos = jnp.arange(H, dtype=jnp.int32)
    hist_mask = hist_pos[None, :] < history_len[:, None]  # [B, H]
    c = jnp.arange(C, dtype=jnp.int32)
    causal = c[None, :] <= c[:, None]  # [Cq, Ck]
    chunk_mask = causal[None, :, :] & (c[None, None, :] < valid_len[:, None, None])
    mask = jnp.concatenate(
        [
            jnp.broadcast_to(hist_mask[:, None, :], (B, C, H)),
            chunk_mask,
        ],
        axis=-1,
    )  # [B, C, H+C]
    if window is not None:
        # absolute positions: history keys 0..H-1; chunk token c sits at
        # chunk_start + c
        q_pos = history_len[:, None] + c[None, :]  # [B, C]
        k_pos = jnp.concatenate([
            jnp.broadcast_to(hist_pos[None, :], (B, H)),
            history_len[:, None] + c[None, :],
        ], axis=1)  # [B, H+C]
        dist = q_pos[:, :, None] - k_pos[:, None, :]
        mask = mask & ((dist < window) | (window <= 0))
    scores = jnp.where(mask[:, None, :, :], scores, -1e30)
    weights = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(weights, v_all)  # [B, C, nq, d]
    return out.astype(q.dtype)


def paged_attention_xla(
    q: jnp.ndarray,  # [B, nq, d] — one decode token per sequence
    kv_pages,  # [num_pages, 2, nkv, ps, d] or (int8 pages, scales)
    page_table: jnp.ndarray,  # [B, max_pages]
    seq_lens: jnp.ndarray,  # [B] int32 (length INCLUDING current token)
    logit_softcap: float = 0.0,
    scale: Optional[float] = None,
    window=None,  # traced int32 scalar; >0 = sliding-window width
) -> jnp.ndarray:
    """Decode attention: gather this batch's pages and do masked softmax.
    Materializes [B, L, nkv, d]; the Pallas kernel avoids that copy."""
    B, nq, d = q.shape
    k, v = _gather_history(kv_pages, page_table)
    L = k.shape[1]
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    scores = _gqa_scores(q[:, None], k) * scale  # [B,nq,1,L]
    if logit_softcap > 0.0:
        scores = jnp.tanh(scores / logit_softcap) * logit_softcap
    pos = jnp.arange(L, dtype=jnp.int32)
    mask = pos[None, :] < seq_lens[:, None]  # [B, L]
    if window is not None:
        # the query sits at pos seq_len-1: keep keys within the window
        dist = (seq_lens[:, None] - 1) - pos[None, :]
        mask = mask & ((dist < window) | (window <= 0))
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    weights = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(weights, v)  # [B,1,nq,d]
    return out[:, 0].astype(q.dtype)


def pallas_min_pages(d: int, kv_heads: int,
                     page_size: int) -> Optional[int]:
    """The narrowest page table (in pages) from which the decode kernel
    beats the gather for one compiled shape: 0 = at every width, None = at
    no width.  Set from per-call times measured on a v5e at widths 8-128
    (the table in docs/kernels.md "Kernel against gather", re-measured by
    scripts/decode_attention_crossover.py), not carried.

    The size of one page DMA decides.  The kernel spends ~0.45 us per
    (8-lane block, page) iteration whatever the page holds and streams at
    ~630 GB/s once a page covers that; the gather moves the same pages
    three times but in a few large operations.  K+V pages of 32 KB and more
    (4+ KV heads x 16 tokens of head size 128): the kernel wins at every
    width, 1.2-1.6x at 8 pages, 6-8x from 40 up, at 8, 16 and 48 lanes.
    16 KB (2 KV heads, a tp=4 shard of 8): the gather is 1-10 % ahead up
    to 56 pages, the kernel 1.2-1.9x from 64.  8 KB (1 KV head): the
    gather is 2-3x ahead at every width.

    `kv_heads` is what ONE device holds (the local shard under shard_map),
    so a model's answer changes with its tp, from the shape alone."""
    page_bytes = 2 * kv_heads * page_size * d * 2  # K and V, bf16
    if page_bytes >= 32 * 1024:
        return 0
    if page_bytes >= 16 * 1024:
        return 64
    return None


def _should_use_pallas(d: int, quantized: bool, table_width: int, batch: int,
                       backend: str, page_size, kv_heads: int) -> bool:
    """The use_pallas=None auto-dispatch predicate (factored out so tests
    assert the production decision, not a re-inlined copy)."""
    from .pallas_paged_attention import _pick_sb

    if not (
        # a narrower head's rows are not whole 128-lane tiles: the gather,
        # or rows stored side by side in rows of 128 (models/hybrid.py)
        d % 128 == 0
        and not quantized  # kernel reads bf16 pages only (today)
        # a batch with no divisor <= MAX_SB would run the serialized
        # sb=1 kernel shape, which loses to the gather
        and _pick_sb(batch) > 1
        # Mosaic only lowers on TPU; CPU smoke runs of a real model at
        # long context must take the gather, not fail to compile
        and backend == "tpu"
    ):
        return False
    min_pages = pallas_min_pages(d, kv_heads, page_size)
    return min_pages is not None and table_width >= min_pages


def _decode_auto(lanes: int, d: int, kv_pages, page_table,
                 backend: Optional[str] = None) -> bool:
    """`_should_use_pallas` read off the arrays of a call: `lanes` queries
    of head size `d`, one a lane, over `kv_pages` through `page_table`."""
    quantized = isinstance(kv_pages, tuple)
    pages = kv_pages[0] if quantized else kv_pages
    return _should_use_pallas(
        d, quantized, int(page_table.shape[1]), lanes,
        backend or jax.default_backend(), int(pages.shape[3]),
        int(pages.shape[2]))


def _should_use_page_write(d: int, quantized: bool, latent: bool,
                           backend: str, sharded: bool = False) -> bool:
    """Whether a K/V write runs as the page kernel
    (ops/pallas_kv_write.py) or as XLA's row scatter
    (ops/kv_write._scatter_kv): the ONE predicate, from what a trace can
    see.  The kernel on a TPU, over a plain cache of K and V planes whose
    rows are whole 128-lane tiles.  The scatter for: an int8 cache (the
    (pages, scales) tuple: Mosaic refuses the scale page's DMA, as it does
    the ragged kernel's); latent pages (one row a token and layer: the
    scatter's cost is the count of rows, and that is already one); other
    head sizes; every other backend; and a cache sharded over a mesh
    (`sharded`: tp, sp or pp > 1, where GSPMD partitions the scatter along
    the heads and has no rule for the kernel; no four-chip cell times it)."""
    return (backend == "tpu" and not quantized and not latent
            and d % 128 == 0 and not sharded)


def _kv_write_name(page_kernel: bool) -> str:
    return "page_kernel" if page_kernel else "row_scatter"


def kv_write_path(kv_pages, v, backend: Optional[str] = None) -> str:
    """`page_kernel` or `row_scatter` for a write of (k, `v`) into
    `kv_pages`: `_should_use_page_write` read off the arrays.  A caller
    whose cache is sharded does not ask (ops/kv_write `page_kernel`)."""
    quantized = isinstance(kv_pages, tuple)
    d = (kv_pages[0] if quantized else kv_pages).shape[-1]
    return _kv_write_name(_should_use_page_write(
        d, quantized, v is None, backend or jax.default_backend()))


def make_sharded_paged_attention(
    mesh,
    logit_softcap: float = 0.0,
    use_pallas: Optional[bool] = None,
    quantized: bool = False,
    interpret: bool = False,
    scale: Optional[float] = None,
    windowed: bool = False,
):
    """Decode attention under `shard_map` over the model (head) axis.

    The Pallas kernel has no GSPMD partitioning rule, so under tp>1 XLA
    would replicate the model-axis-sharded KV cache at the custom-call
    boundary.  shard_map sidesteps GSPMD entirely: each device runs the
    kernel (or the gather, per the same auto-dispatch) on its LOCAL heads —
    q heads and KV heads shard together on the model axis, so GQA group
    structure is preserved per shard and the op is embarrassingly parallel
    (no collectives).  This is what un-boxes the kernel for the multi-chip
    path (round-2 VERDICT weak #3).

    Returns fn(q [B,nq,d], kv_pages, page_table [B,W], seq_lens [B],
    window [] int32) -> [B,nq,d].  `windowed` is STATIC: when False the
    traced window arg is ignored (0 at every call site) and the Pallas
    auto-dispatch stays available; when True the scalar rides through to
    the gather path (per-layer sliding windows are data, and a traced
    window always forces the gather — threading it unconditionally would
    silently disable the kernel for every non-windowed tp>1 model).
    `quantized` selects the (int8 pages, scales) cache layout.
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel.sharding import MODEL_AXIS

    if interpret and (windowed or scale is not None):
        # the interpret path exists to test the KERNEL's math on CPU, and
        # the kernel takes neither a window nor a scale override — dropping
        # them here would make a parity test compare the wrong math
        raise ValueError(
            "interpret mode tests the Pallas kernel, which supports "
            "neither `windowed` nor a scale override")

    q_spec = P(None, MODEL_AXIS, None)
    kv_spec = P(None, None, MODEL_AXIS, None, None)
    if quantized:
        kv_spec = (kv_spec, P(None, None, MODEL_AXIS, None))

    def inner(q, kv_pages, page_table, seq_lens, window):
        if interpret:
            from .pallas_paged_attention import paged_attention_pallas

            return paged_attention_pallas(
                q, kv_pages, page_table, seq_lens,
                logit_softcap=logit_softcap, interpret=True)
        return paged_attention(
            q, kv_pages, page_table, seq_lens,
            logit_softcap=logit_softcap, use_pallas=use_pallas,
            scale=scale, window=window if windowed else None)

    return jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(q_spec, kv_spec, P(None, None), P(None), P()),
        out_specs=q_spec,
        check_vma=False,
    )


# ---------------- ragged paged attention (mixed prefill+decode) ----------------


def ragged_token_metadata(q_start, q_len, T: int):
    """Per-token (seq index, local offset, validity) for a packed ragged
    buffer of T tokens, derived ON DEVICE from the per-sequence metadata —
    packing metadata must never round-trip through the host inside traced
    code (jaxlint: ragged-metadata-host-sync).  Tokens outside every
    sequence's slice get seq index -1."""
    idx = jnp.arange(T, dtype=jnp.int32)
    member = (idx[None, :] >= q_start[:, None]) & (
        idx[None, :] < (q_start + q_len)[:, None]
    )  # [B, T]
    valid = member.any(axis=0)
    token_seq = jnp.where(
        valid, jnp.argmax(member, axis=0).astype(jnp.int32), -1)
    token_loc = idx - q_start[jnp.maximum(token_seq, 0)]
    return token_seq, token_loc, valid


def ragged_paged_attention_xla(
    q: jnp.ndarray,  # [T, nq, d] — packed ragged query buffer
    kv_pages,  # [num_pages, 2, nkv, ps, d] or (int8 pages, scales)
    page_table: jnp.ndarray,  # [B, W]
    q_start: jnp.ndarray,  # [B] first packed index of each sequence's slice
    q_len: jnp.ndarray,  # [B] slice length (0 = inactive lane)
    kv_start: jnp.ndarray,  # [B] tokens already cached BEFORE this slice
    logit_softcap: float = 0.0,
    scale: Optional[float] = None,
    window=None,  # traced int32 scalar; >0 = sliding-window width
) -> jnp.ndarray:
    """XLA gather reference for the ragged contract (docs/kernels.md).

    The caller has already written the slice's K/V into the pages
    (ops/kv_write.write_ragged_kv), so attention reads ONLY the paged cache:
    query token j of sequence i sits at absolute position kv_start[i]+j and
    attends causally to positions 0..kv_start[i]+j.  Padded table entries
    point at the null page, whose positions lie beyond every query's causal
    horizon — the causal mask is the null-page mask.  This is the numerics
    ground truth the Pallas ragged kernel is tested against, and the
    production path off-TPU."""
    T, nq, d = q.shape
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    k_all, v_all = _gather_history(kv_pages, page_table)  # [B, L, nkv, d]
    L = k_all.shape[1]
    nkv = k_all.shape[2]
    group = nq // nkv
    token_seq, token_loc, valid = ragged_token_metadata(q_start, q_len, T)
    seq_ix = jnp.maximum(token_seq, 0)
    q_pos = kv_start[seq_ix] + token_loc  # [T] absolute query positions
    k_t = k_all[seq_ix]  # [T, L, nkv, d]
    v_t = v_all[seq_ix]
    qg = q.reshape(T, nkv, group, d).astype(jnp.float32)
    scores = jnp.einsum(
        "tkgd,tlkd->tkgl", qg, k_t.astype(jnp.float32)) * scale
    if logit_softcap > 0.0:
        scores = jnp.tanh(scores / logit_softcap) * logit_softcap
    kpos = jnp.arange(L, dtype=jnp.int32)
    mask = (kpos[None, :] <= q_pos[:, None]) & valid[:, None]  # [T, L]
    if window is not None:
        dist = q_pos[:, None] - kpos[None, :]
        mask = mask & ((dist < window) | (window <= 0))
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("tkgl,tlkd->tkgd", weights, v_t.astype(jnp.float32))
    out = jnp.where(valid[:, None, None], out.reshape(T, nq, d), 0.0)
    return out.astype(q.dtype)


def _should_use_ragged_pallas(d: int, backend: str,
                              quantized: bool = False) -> bool:
    """Auto-dispatch predicate for the ragged kernel: lane-aligned heads and
    unquantized pages on a TPU backend.  Unlike the decode kernel there is
    no gather-vs-kernel width crossover — the ragged gather reference
    materializes [T, L, ...] per token and is strictly a correctness/CPU
    path.  int8 pages stay on the gather: Mosaic refuses the kernel's
    per-page scale DMA (a [2, nkv, ps] f32 slice whose minor dim is below
    the 128-lane tiling — docs/kernels.md)."""
    return d % 128 == 0 and not quantized and backend == "tpu"


def dense_stride_for(width: int, align: int) -> int:
    """Packed-slice stride for lanes carrying `width` query tokens each
    under a kernel block alignment of `align` (RAGGED_BQ on the kernel
    path, 1 on the XLA reference — docs/kernels.md dense packing).

    - align <= 1 (XLA reference): pack densely, stride == width.
    - width a multiple of align, or larger than it: round up to the next
      align multiple — every block still belongs to ONE lane, so the solo
      kernel's invariant holds unchanged.
    - width < align: the smallest power of two >= width (align is a power
      of two, so the result divides it) — lanes SHARE blocks at this
      stride and the dense-block kernel variant serves them.  This is
      what stops a single-token decode lane burning a whole align-token
      block (K+1-token speculative slices included)."""
    if width <= 0:
        raise ValueError(f"slice width must be positive, got {width}")
    if align <= 1 or width % align == 0:
        return width
    if width > align:
        return -(-width // align) * align
    sp = 1
    while sp < width:
        sp *= 2
    return sp


def ragged_attention_path(
    q, kv_pages, page_table, use_pallas: Optional[bool] = None,
    scale: Optional[float] = None, window=None,
    dense_stride: Optional[int] = None, backend: Optional[str] = None,
) -> str:
    """Which form of the ragged contract `ragged_paged_attention` traces for
    these arguments (arrays or their shapes), from what a trace can see:

    - `xla_gather`: the reference, off the TPU, over int8 pages and for
      heads the kernel cannot tile (`_should_use_ragged_pallas`);
    - `pallas_ragged`: the ragged kernel over every slice;
    - `pallas_ragged+decode`: the ragged kernel over the slices longer than
      one token and the DECODE kernel, in one call over the lanes, for the
      lanes that bring one token (a decode lane of length `kv_start + 1`;
      ops/pallas_paged_attention.ragged_single_token_split_pallas).  Taken
      exactly where the decode steps of the same program take that kernel
      for the same lanes over the same pages, by `paged_attention`'s own
      rule: full attention (`window is None`), no `scale` override, and
      `_should_use_pallas` on (head size, page shape, table width, lanes),
      or `use_pallas=True`.  A shape whose decode steps gather (pages of
      1-2 K/V heads under the measured width) keeps the ragged kernel for
      its single-token lanes too, and the lanes of the dense packing
      (`dense_stride`, the speculative program) share blocks already."""
    d = q.shape[-1]
    quantized = isinstance(kv_pages, tuple)
    backend = backend or jax.default_backend()
    ragged = use_pallas
    if ragged is None:
        ragged = _should_use_ragged_pallas(d, backend, quantized)
    if not ragged:
        return "xla_gather"
    decode = use_pallas
    if decode is None:
        decode = _decode_auto(
            int(page_table.shape[0]), d, kv_pages, page_table, backend)
    if decode and window is None and scale is None and dense_stride is None:
        return "pallas_ragged+decode"
    return "pallas_ragged"


def ragged_paged_attention(
    q: jnp.ndarray,  # [T, nq, d]
    kv_pages,
    page_table: jnp.ndarray,  # [B, W]
    q_start: jnp.ndarray,  # [B]
    q_len: jnp.ndarray,  # [B]
    kv_start: jnp.ndarray,  # [B]
    logit_softcap: float = 0.0,
    use_pallas: Optional[bool] = None,
    scale: Optional[float] = None,
    window=None,  # traced int32 scalar (None = full attention)
    dense_stride: Optional[int] = None,  # static lane stride for dense
    # decode/spec-verify packing (< RAGGED_BQ shares blocks between lanes;
    # ignored by the XLA reference, which is per-token already)
) -> jnp.ndarray:
    """Dispatch the ragged contract between the fused Pallas kernels and
    the XLA gather reference (`ragged_attention_path`).  The ragged kernel
    (unlike the decode kernel) masks sliding windows and applies scale
    overrides natively, so the dispatch is head-alignment + page dtype +
    backend; use_pallas=True forces the kernel (raising on an unsupported
    head_dim or int8 pages), False forces the reference."""
    path = ragged_attention_path(
        q, kv_pages, page_table, use_pallas, scale, window, dense_stride)
    if path == "xla_gather":
        return ragged_paged_attention_xla(
            q, kv_pages, page_table, q_start, q_len, kv_start,
            logit_softcap=logit_softcap, scale=scale, window=window,
        )
    if isinstance(kv_pages, tuple):
        raise ValueError(
            "the ragged pallas kernel does not compile over the int8 "
            "KV cache (docs/kernels.md)")
    from .pallas_paged_attention import (
        ragged_paged_attention_pallas,
        ragged_single_token_split_pallas,
    )

    if path == "pallas_ragged+decode":
        return ragged_single_token_split_pallas(
            q, kv_pages, page_table, q_start, q_len, kv_start,
            logit_softcap=logit_softcap)
    return ragged_paged_attention_pallas(
        q, kv_pages, page_table, q_start, q_len, kv_start,
        window=window, logit_softcap=logit_softcap, scale=scale,
        dense_stride=dense_stride,
    )


def make_sharded_ragged_attention(
    mesh,
    logit_softcap: float = 0.0,
    use_pallas: Optional[bool] = None,
    quantized: bool = False,
    interpret: bool = False,
    scale: Optional[float] = None,
    dense_stride: Optional[int] = None,  # static: the spec-verify dense
    # packing stride (compiled.py builds a second sharded fn with it set)
):
    """Ragged paged attention under `shard_map` over the model (head) axis
    — same seam as make_sharded_paged_attention: q heads and KV heads shard
    together so GQA group structure is preserved per shard and the op needs
    no collectives.  Ragged packing metadata is replicated (tiny int32
    arrays).  The window scalar is always threaded: the ragged kernel masks
    the window natively, so no static `windowed` escape hatch is needed.

    Returns fn(q [T,nq,d], kv_pages, page_table [B,W], q_start [B],
    q_len [B], kv_start [B], window [] int32) -> [T,nq,d]."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.sharding import MODEL_AXIS

    q_spec = P(None, MODEL_AXIS, None)
    kv_spec = P(None, None, MODEL_AXIS, None, None)
    if quantized:
        kv_spec = (kv_spec, P(None, None, MODEL_AXIS, None))

    def inner(q, kv_pages, page_table, q_start, q_len, kv_start, window):
        if interpret:
            from .pallas_paged_attention import ragged_paged_attention_pallas

            return ragged_paged_attention_pallas(
                q, kv_pages, page_table, q_start, q_len, kv_start,
                window=window, logit_softcap=logit_softcap, scale=scale,
                interpret=True, dense_stride=dense_stride)
        return ragged_paged_attention(
            q, kv_pages, page_table, q_start, q_len, kv_start,
            logit_softcap=logit_softcap, use_pallas=use_pallas,
            scale=scale, window=window, dense_stride=dense_stride)

    return jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(q_spec, kv_spec, P(None, None), P(None), P(None),
                  P(None), P()),
        out_specs=q_spec,
        check_vma=False,
    )


def _window_mixed_name(model_config, engine_config, backend: str) -> str:
    """How the packed step's window attention of a model with rings runs:
    `_should_use_window_pallas` at the model's sizes."""
    from .pallas_paged_attention import RAGGED_BQ

    mc, cfg = model_config, engine_config
    if cfg.use_pallas is None:
        kernel = _should_use_window_pallas(
            mc.cache_head_dim, mc.n_heads, mc.cache_kv_heads,
            mc.sliding_window, RAGGED_BQ, backend)
    else:
        kernel = bool(cfg.use_pallas)
    return "pallas_window_ragged" if kernel else "xla_ring_window"


def _packed_ragged_layers(model_config) -> int:
    """Layers whose packed step attends over the pool's pages through
    `ragged_paged_attention` with neither a window nor a scale override:
    every layer of a Llama-family model without sliding windows
    (models/llama.forward_ragged: with them every layer carries a traced
    `attn_window`, 0 on its full rows); of a hybrid table its
    `gqa_attention` rows but the last layer that writes state, whose
    output is taken at the sampled rows (models/hybrid.forward_ragged)."""
    mc = model_config
    if not mc.is_hybrid:
        plain = mc.sliding_window <= 0 and mc.attn_scale is None
        return mc.n_layers if plain else 0
    table = mc.layer_table()
    last_writer = max(i for i, row in enumerate(table) if row.writes != "none")
    return sum(row.kind == "gqa_attention" and i != last_writer
               for i, row in enumerate(table))


def describe_attention_dispatch(model_config, engine_config,
                                backend: str) -> dict:
    """Which implementation each program's attention is built with, from
    the SAME predicates the dispatch functions above consult at trace time
    — the engine logs this at start and serves it on
    /v1/internal/scheduler/state, so a TPU replica that quietly serves
    from an `*_xla` path (a correctness/CPU path) is visible.

    `mixed` is the ragged attention of the unified program; `decode` is
    the single-token decode attention (the mixed program's scan tail AND
    the legacy decode programs a logprobs/penalty lane falls back to).
    Its kernel is gated per compiled shape by `pallas_min_pages`:
    `decode_pallas_min_pages` is the width it starts at, None where it
    runs at every width (or, with `decode: xla_gather`, at none).
    `kv_write` says how each kind of cache the model has is written
    (`_should_use_page_write`).  `packed_single_token_min_pages` is the
    table width from which `mixed`'s packed step hands the lanes that
    bring one token to the decode kernel (`ragged_attention_path`'s
    `pallas_ragged+decode`: wherever the decode steps take that kernel and
    a layer of the packed step reads the pool's pages through
    `ragged_paged_attention` with no window), None where it never does."""
    mc, cfg = model_config, engine_config
    quantized = cfg.kv_quant == "int8"
    min_pages = None
    # the write half, per kind of cache the model's layers write
    # (models/llama.LayerSpec.writes): the kernel or the scatter
    written = {row.writes for row in mc.layer_table()}
    kv_write = {
        kind: _kv_write_name(_should_use_page_write(
            mc.cache_head_dim, quantized, kind == "latent", backend,
            sharded=cfg.tp > 1 or cfg.sp > 1 or cfg.pp > 1))
        for kind in ("paged", "window", "latent") if kind + "_kv" in written}
    if mc.is_latent:
        # models/latent.py: both reads of the latent pages are the kernels
        # on a TPU and the XLA references on a K/V view elsewhere
        pallas = latent_uses_pallas(cfg.use_pallas, backend)
        return {
            "backend": backend,
            "mixed": "pallas_latent_ragged" if pallas else "xla_ragged_gather",
            "decode": "pallas_latent_decode" if pallas else "xla_gather",
            "decode_pallas_min_pages": None,
            "packed_single_token_min_pages": None,
            "shard_map": False,
            "kv_write": kv_write,
        }
    if mc.is_hybrid and "gqa_attention" not in mc.mixer_kinds:
        # models/hybrid.py's differential rows (a table whose attention is
        # `gqa_attention` rows takes the Llama path's kernels, below):
        # every read of a cache is one query per lane (the
        # decode kernel or its gather, gated as below at the cache's row
        # width); a window layer's packed slice is the XLA ring attention
        # or the window kernel, by `_should_use_window_pallas`
        if cfg.use_pallas is None:
            decode = _should_use_pallas(
                mc.cache_head_dim, False, cfg.max_pages_per_seq,
                cfg.max_batch_size, backend, cfg.page_size, mc.cache_kv_heads)
            if decode:
                min_pages = pallas_min_pages(
                    mc.cache_head_dim, mc.cache_kv_heads,
                    cfg.page_size) or None
        else:
            decode = bool(cfg.use_pallas)
        return {
            "backend": backend,
            "mixed": _window_mixed_name(mc, cfg, backend) + "+" + (
                "pallas_decode" if decode else "xla_gather"),
            "decode": "pallas_decode" if decode else "xla_gather",
            "decode_pallas_min_pages": min_pages,
            # the packed step's slices go through the ring form, and the
            # full layer is taken at the sampled rows, one query a lane
            "packed_single_token_min_pages": None,
            "shard_map": False,
            "kv_write": kv_write,
        }
    if cfg.use_pallas is None:
        # the cache's rows: a table's 64-wide heads lie two a row
        # (LlamaConfig.pairs_kv_heads); every other model's are its heads
        d = mc.cache_head_dim
        ragged = _should_use_ragged_pallas(d, backend, quantized)
        kv_heads = mc.cache_kv_heads // cfg.tp  # one device's
        # the widest table this replica compiles: is the kernel built at all
        # a hybrid table's window rows keep rings: its full rows have none
        decode = (
            (mc.is_hybrid or mc.sliding_window <= 0) and mc.attn_scale is None
            and _should_use_pallas(
                d, quantized, cfg.max_pages_per_seq,
                cfg.max_batch_size, backend, cfg.page_size, kv_heads))
        if decode:
            min_pages = pallas_min_pages(d, kv_heads, cfg.page_size) or None
    else:
        ragged = decode = bool(cfg.use_pallas)
    mixed = "pallas_ragged" if ragged else "xla_ragged_gather"
    if "window_kv" in written:  # plain window rows beside the paged ones
        mixed = _window_mixed_name(mc, cfg, backend) + "+" + mixed
    # `ragged_attention_path` at the model's sizes: both kernels, one device
    # (tp / sp > 1 thread a window through shard_map), and a layer whose
    # packed step calls `ragged_paged_attention` with no window
    splits = (ragged and decode and cfg.tp == 1 and cfg.sp == 1
              and _packed_ragged_layers(mc) > 0)
    return {
        "backend": backend,
        "mixed": mixed,
        "decode": "pallas_decode" if decode else "xla_gather",
        "decode_pallas_min_pages": min_pages,
        "packed_single_token_min_pages": (min_pages or 0) if splits else None,
        # tp/sp>1: both run per shard inside shard_map over the model axis
        "shard_map": cfg.tp > 1 or cfg.sp > 1,
        "kv_write": kv_write,
    }


def paged_attention(
    q: jnp.ndarray,
    kv_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    seq_lens: jnp.ndarray,
    logit_softcap: float = 0.0,
    use_pallas: Optional[bool] = None,
    scale: Optional[float] = None,
    window=None,  # sliding window (forces the gather path)
) -> jnp.ndarray:
    """Dispatch between the fused Pallas kernel and the XLA gather path.

    use_pallas=None (default) auto-selects per compiled shape: the kernel
    wherever it measured faster on the chip (`pallas_min_pages`: for pages
    of 4+ KV heads x 128 at every table width), the gather for what the
    kernel cannot do (CPU, int8 pages, sliding windows, scale overrides,
    other head sizes, a batch with no divisor up to MAX_SB) and for the
    small-page shapes where it loses.  True forces the kernel (raising on
    an unsupported head_dim rather than silently benchmarking the gather);
    False forces the gather."""
    d = q.shape[-1]
    quantized = isinstance(kv_pages, tuple)
    if window is not None:
        # the kernel has no sliding-window mask yet; windowed layers take
        # the gather (scale/softcap still apply).  An explicit opt-in
        # stays loud — silently measuring the gather would corrupt a
        # benchmark that forced the kernel
        if use_pallas:
            raise ValueError(
                "pallas paged attention has no sliding-window mask; "
                "windowed layers cannot run with use_pallas=True")
        use_pallas = False
    if scale is not None and use_pallas is None:
        # same for a non-default scale (query_pre_attn_scalar without a
        # sliding window): auto-dispatch falls back rather than raising
        use_pallas = False
    if use_pallas is None:
        use_pallas = _decode_auto(int(q.shape[0]), d, kv_pages, page_table)
    if use_pallas:
        if quantized:
            raise ValueError(
                "pallas paged attention does not support the int8 KV cache"
            )
        # loud, not silent: an explicit opt-in with an unsupported head_dim
        # must not quietly benchmark the XLA path
        from .pallas_paged_attention import paged_attention_pallas

        if scale is not None:
            raise ValueError(
                "pallas paged attention does not take a scale override")
        # the arguments, given and left out, of the packed step's call
        # (ragged_single_token_split_pallas): a jitted entry point keys its
        # traces on them, and a program holds the kernel once for both
        return paged_attention_pallas(
            q, kv_pages, page_table, seq_lens, logit_softcap=logit_softcap,
            interpret=False)
    return paged_attention_xla(
        q, kv_pages, page_table, seq_lens, logit_softcap,
        scale=scale, window=window,
    )


# ---------------- hybrid families (models/hybrid.py) ----------------
#
# A model whose attention layers read two kinds of cache: pages of a shared
# full-attention cache, and a per-lane RING holding the last `window`
# tokens of a window layer.  The model has no positional encoding, so the
# order of the keys inside a ring does not matter to a softmax: one decode
# token attends to its lane's ring as to a short paged sequence, with the
# decode kernel as it is.


def paged_attention_scaled(
    q: jnp.ndarray,  # [B, nq, d]
    kv_pages: jnp.ndarray,  # [num_pages, 2, nkv, ps, d]
    page_table: jnp.ndarray,  # [B, W]
    seq_lens: jnp.ndarray,  # [B]
    scale: float,
    name: str,  # the Pallas call's name: tells layer kinds apart in a trace
    use_pallas: Optional[bool] = None,
) -> jnp.ndarray:
    """`paged_attention` with the score scale stated and the kernel's call
    named by the caller.  The same gate picks kernel or gather."""
    if use_pallas is None:
        use_pallas = _should_use_pallas(
            int(q.shape[-1]), False, int(page_table.shape[1]), int(q.shape[0]),
            jax.default_backend(), int(kv_pages.shape[3]), int(kv_pages.shape[2]))
    if use_pallas:
        from .pallas_paged_attention import paged_attention_pallas

        return paged_attention_pallas(
            q, kv_pages, page_table, seq_lens, scale=scale, name=name)
    return paged_attention_xla(q, kv_pages, page_table, seq_lens, scale=scale)


def ring_window_attention_ragged(
    q: jnp.ndarray,  # [T, nq, d] packed queries
    k_new: jnp.ndarray,  # [T, nkv, d] the buffer's own keys (not yet in the ring)
    v_new: jnp.ndarray,  # [T, nkv, d]
    ring_pages: jnp.ndarray,  # [pages, 2, nkv, ps, d] as it was BEFORE this buffer
    ring_table: jnp.ndarray,  # [B, Wr] each lane's ring pages; Wr * ps = window
    token_seq: jnp.ndarray,  # [T] lane per token (-1 = padding)
    token_pos: jnp.ndarray,  # [T] absolute positions
    kv_start: jnp.ndarray,  # [B] tokens the lane had before its slice
    scale: float,
    block: int,  # packing alignment: a block of tokens holds one lane
) -> jnp.ndarray:
    """Window attention for the mixed program's packed buffer, in XLA.

    A query at position i sees keys j with i - window < j <= i: those of its
    own slice, taken from the buffer, and those the lane's ring held before
    the slice.  Ring slot s holds the newest position below kv_start that is
    congruent to s modulo the window; positions the slice will overwrite
    are exactly those that have left the window of the query that would
    need them last, so the ring is read before it is written.  The ring is
    gathered once per BLOCK of queries (one lane), not per token."""
    T, nq, d = q.shape
    nkv = k_new.shape[1]
    group = nq // nkv
    Wr, ps = ring_table.shape[1], ring_pages.shape[3]
    R = Wr * ps
    nb = T // block
    f32 = jnp.float32
    blk_lane = jnp.maximum(token_seq[::block], 0)  # [nb]
    ring = ring_pages[ring_table[blk_lane]]  # [nb, Wr, 2, nkv, ps, d]
    start = kv_start[blk_lane][:, None]  # [nb, 1]
    slot = jnp.arange(R, dtype=jnp.int32)[None, :]
    slot_pos = start - 1 - ((start - 1 - slot) % R)  # [nb, R]; < 0: empty
    q_pos = token_pos.reshape(nb, block)
    ring_mask = (slot_pos[:, None, :] >= 0) & (
        slot_pos[:, None, :] > q_pos[:, :, None] - R)  # [nb, block, R]
    same = (token_seq[:, None] == token_seq[None, :]) & (token_seq[:, None] >= 0)
    buf_mask = same & (token_pos[None, :] <= token_pos[:, None]) & (
        token_pos[None, :] > token_pos[:, None] - R)  # [T, T]
    # operands stay in the cache's dtype, products accumulate in float32
    # (on the chip a float32 operand would be rounded to bfloat16 anyway)
    qg = q.reshape(nb, block, nkv, group, d)
    s_ring = jnp.einsum("nbkgd,nwkpd->nkgbwp", qg, ring[:, :, 0],
                        preferred_element_type=f32).reshape(
                            nb, nkv, group, block, R) * scale
    s_buf = jnp.einsum("nbkgd,skd->nkgbs", qg, k_new.astype(q.dtype),
                       preferred_element_type=f32) * scale
    s_ring = jnp.where(ring_mask[:, None, None], s_ring, -1e30)
    s_buf = jnp.where(
        buf_mask.reshape(nb, block, T)[:, None, None], s_buf, -1e30)
    weights = jax.nn.softmax(
        jnp.concatenate([s_ring, s_buf], axis=-1), axis=-1).astype(v_new.dtype)
    out = jnp.einsum(
        "nkgbwp,nwkpd->nbkgd",
        weights[..., :R].reshape(nb, nkv, group, block, Wr, ps), ring[:, :, 1],
        preferred_element_type=f32)
    out = out + jnp.einsum("nkgbs,skd->nbkgd", weights[..., R:], v_new,
                           preferred_element_type=f32)
    return out.reshape(T, nq, d).astype(q.dtype)


#: what the XLA window attention builds for ONE block of queries, in bytes
#: (the lane's gathered ring and the block's float32 scores over it), from
#: which the packed step's window attention runs as the kernel
WINDOW_XLA_MAX_BLOCK_BYTES = 8 << 20


def _should_use_window_pallas(d: int, nq: int, nkv: int, ring_tokens: int,
                              block: int, backend: str,
                              itemsize: int = 2) -> bool:
    """Whether the packed step's window attention runs as the kernel
    (ops/pallas_paged_attention.window_attention_ragged_pallas) or as
    `ring_window_attention_ragged`: the ONE predicate, from sizes.  The XLA
    form gathers a lane's whole ring once for every block of queries and
    builds the block's scores over it in HBM: [blocks, ring] arrays, small
    at a window of 512 (3.3 MB a block at 40 query heads over 10 rows of
    128: the Phi-4-flash cell, measured on this path and kept on it) and
    past the chip's memory at 4096 x 128 heads (33.5 MB a block, 8.6 GB at
    2048 tokens).  The kernel needs rows of whole 128-lane tiles, blocks of
    whole sublane tiles and a TPU; where the two meet has not been
    measured, so the bound sits between the two shapes that exist."""
    if backend != "tpu" or d % 128 or block % 8:
        return False
    block_bytes = ring_tokens * (2 * nkv * d * itemsize + nq * block * 4)
    return block_bytes > WINDOW_XLA_MAX_BLOCK_BYTES


def window_attention_ragged(
    q: jnp.ndarray,  # [T, nq, d] packed queries
    k_new: jnp.ndarray,  # [T, nkv, d] the buffer's own keys (not yet in the ring)
    v_new: jnp.ndarray,  # [T, nkv, d]
    ring_pages: jnp.ndarray,  # [pages, 2, nkv, ps, d] as it was BEFORE this buffer
    ring_table: jnp.ndarray,  # [B, Wr]
    token_seq: jnp.ndarray,  # [T]
    token_pos: jnp.ndarray,  # [T]
    q_start: jnp.ndarray,  # [B]
    q_len: jnp.ndarray,  # [B]
    kv_start: jnp.ndarray,  # [B]
    scale: float,
    block: int,
    use_pallas: Optional[bool] = None,
) -> jnp.ndarray:
    """The packed step's window attention over (ring, the buffer's own
    slice): the kernel where `_should_use_window_pallas` says so (or
    `use_pallas` forces it), else the XLA form, which is also its oracle."""
    if use_pallas is None:
        _, nq, d = q.shape
        use_pallas = _should_use_window_pallas(
            d, nq, int(k_new.shape[1]),
            int(ring_table.shape[1]) * int(ring_pages.shape[3]), block,
            jax.default_backend(), ring_pages.dtype.itemsize)
    if use_pallas:
        from .pallas_paged_attention import window_attention_ragged_pallas

        return window_attention_ragged_pallas(
            q, k_new, v_new, ring_pages, ring_table, q_start, q_len,
            kv_start, scale, block)
    return ring_window_attention_ragged(
        q, k_new, v_new, ring_pages, ring_table, token_seq, token_pos,
        kv_start, scale, block)


# ---------------- latent pages (models/latent.py) ----------------
#
# A latent-attention layer's cache is one row a token (engine/kvcache.
# StateLayout, `latent`): pages [num_pages, 1, 1, ps, row].  In the absorbed
# form attention is ONE key/value head over those rows, the value the row's
# first `value_dim` columns.  On the TPU the Pallas kernels read a page once
# for both (ops/pallas_paged_attention.py, `latent_attention_decode` /
# `latent_attention_ragged`); elsewhere the XLA references above run on a
# K/V view of the pages (a copy: a correctness path).


def _latent_as_kv(pages: jnp.ndarray) -> jnp.ndarray:
    """[P, 1, 1, ps, row] -> [P, 2, 1, ps, row]: the row as key and as value."""
    return jnp.concatenate([pages, pages], axis=1)


def latent_uses_pallas(use_pallas: Optional[bool],
                       backend: Optional[str] = None) -> bool:
    """The kernels on a TPU at every shape (whatever `head_dim` says: the
    row is padded to the lanes), the XLA references elsewhere."""
    if use_pallas is not None:
        return bool(use_pallas)
    return (backend or jax.default_backend()) == "tpu"


def latent_paged_attention(
    q: jnp.ndarray,  # [B, nq, row] absorbed queries (zero where the row pads)
    pages: jnp.ndarray,  # [num_pages, 1, 1, ps, row]
    page_table: jnp.ndarray,  # [B, W]
    seq_lens: jnp.ndarray,  # [B]
    scale: float,
    value_dim: int,
    use_pallas: Optional[bool] = None,
) -> jnp.ndarray:
    """One query token a lane over its latent pages -> [B, nq, value_dim].
    The kernel at every table width on a TPU: the gather it would be
    weighed against copies [B, W ps, row] three times (docs/kernels.md)."""
    if latent_uses_pallas(use_pallas):
        from .pallas_paged_attention import latent_attention_decode_pallas

        return latent_attention_decode_pallas(
            q, pages, page_table, seq_lens, scale, value_dim)
    out = paged_attention_xla(
        q, _latent_as_kv(pages), page_table, seq_lens, scale=scale)
    return out[..., :value_dim]


def latent_ragged_attention(
    q: jnp.ndarray,  # [T, nq, row]
    pages: jnp.ndarray,  # [num_pages, 1, 1, ps, row]
    page_table: jnp.ndarray,  # [B, W]
    q_start: jnp.ndarray,  # [B]
    q_len: jnp.ndarray,  # [B]
    kv_start: jnp.ndarray,  # [B]
    scale: float,
    value_dim: int,
    use_pallas: Optional[bool] = None,
) -> jnp.ndarray:
    """The packed step's attention over latent pages (the ragged contract:
    the slice's rows are already written) -> [T, nq, value_dim]."""
    if latent_uses_pallas(use_pallas):
        from .pallas_paged_attention import latent_attention_ragged_pallas

        return latent_attention_ragged_pallas(
            q, pages, page_table, q_start, q_len, kv_start, scale, value_dim)
    out = ragged_paged_attention_xla(
        q, _latent_as_kv(pages), page_table, q_start, q_len, kv_start,
        scale=scale)
    return out[..., :value_dim]
