"""Fused paged-attention decode kernel (Pallas/TPU).

Kernel shape (v3, sequence-block parallel): each grid step owns SB
sequences.  At inner iteration i it streams page i of ALL SB sequences from
HBM into an NBUF-deep VMEM ring (SB concurrent DMAs per iteration — the
page-major cache layout [num_pages, 2, nkv, ps, d] in kvcache.py makes each
page one contiguous 64KB-class descriptor covering K and V for every local
head) and folds them into a batched online-softmax accumulator
[SB, nkv, group, ·].  The compute is the same batched shape XLA uses for
the gather path — but the gathered KV only ever exists in VMEM, so HBM
traffic is ONE read of the table width instead of gather's read + write +
re-read.

A block's loop runs to its LONGEST sequence, and every sequence of the
block fetches and folds a page in every iteration: past its own length that
is the null page (page 0, where its padded table entries point), masked out
and paid for all the same, in DMA and in compute alike.  So the entry
points hand the kernel its sequences SORTED BY LENGTH and put its rows back
(`_by_length`): a block then holds sequences of like length and walks
little past any of them.  Leaving the null-page DMAs out instead (a
`pl.when` a sequence around its start and its wait) was measured and is
slower than fetching them: docs/kernels.md "Kernel against gather".

Why not one-sequence-per-grid-step (v1/v2): the grid is sequential on a
TPU core, so per-sequence page loops serialize B small DMA bursts and the
per-page compute ([group, ps] matmuls) is far below MXU granularity —
measured 1146 vs 1671 tok/s e2e against the gather at 256-token context.
Batching SB sequences multiplies both the DMA parallelism and the matmul
batch.

This is the Ragged Paged Attention design point (see PAPERS.md) specialized
to decode (query length 1 per sequence).  The FULL ragged generalization —
arbitrary per-sequence query slices (prompt chunks and decode tokens in one
program) — is `ragged_paged_attention_pallas` below; its packing contract,
masking rules, VMEM ring budget, int8/sliding-window composition and the
engine's legacy-fallback flag are documented in docs/kernels.md.

Numerics match ops/attention.paged_attention_xla and
ops/attention.ragged_paged_attention_xla respectively (tests compare the
paths in interpret mode; bench exercises the compiled kernels on hardware).

Every entry point (`*_pallas`) is a jitted function (`_entry`) whose
Python-level choices are static: a model's layers are unrolled in Python,
and a plain function would be traced, and its `pallas_call` lowered, once a
LAYER; a jitted one is traced and lowered once a PROGRAM for each set of
static choices and shapes, and every layer after the first calls that one
function (docs/kernels.md "A kernel's entry point is a jitted function").
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NBUF = 4  # VMEM ring depth (iterations in flight); NBUF-1 ahead
MAX_SB = 8  # sequences per grid step (VMEM budget: NBUF*SB pages resident)

_HBM = pltpu.MemorySpace.HBM  # stay in device memory, no VMEM block


def _entry(*static: str):
    """Decorator of a kernel's entry point: `jax.jit` with the arguments
    `static` (what is a Python value: a mode, a label, a scale, a block
    size) held static, everything else an array or a pytree of arrays."""
    return functools.partial(jax.jit, static_argnames=static)


def _pick_sb(B: int) -> int:
    """Largest divisor of B up to MAX_SB (any divisor, not just powers of
    two — an odd batch must not silently degrade to the serialized sb=1
    shape)."""
    for sb in range(min(MAX_SB, B), 0, -1):
        if B % sb == 0:
            return sb
    return 1


def _heads_dot(a, b, contract_b: int):
    """`a` [..., M, K] against `b` batched over EVERY leading dim: scores
    (contract_b=2: b is [..., N, K]) or pv (contract_b=1: b is [..., K, N]).
    Mosaic's tpu.matmul takes at most ONE batch dim, so the leading dims
    ([SB, nkv] / [L, nkv]) fold into one for the dot and unfold after —
    leading-dim reshapes leave the tiled minor dims untouched."""
    lead = a.shape[:-2]
    out = jax.lax.dot_general(
        a.reshape((-1,) + a.shape[-2:]), b.reshape((-1,) + b.shape[-2:]),
        dimension_numbers=(((2,), (contract_b,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(lead + out.shape[-2:])


# ---- DMA-ring scaffolding shared by both kernel variants ----


def _block_pages(seq_lens_ref, g, sb, page_size):
    """Pages needed by the longest sequence in block g (bounds the loop)."""
    max_len = seq_lens_ref[g * sb]
    for s in range(1, sb):
        max_len = jnp.maximum(max_len, seq_lens_ref[g * sb + s])
    return (max_len + page_size - 1) // page_size


def _make_start_iter(page_table_ref, kv_hbm_ref, kv_bufs, sems, g, sb):
    """start_iter(i, slot): kick off this block's SB concurrent page DMAs
    for iteration i.  Shorter sequences' padded table entries point at the
    null page (page 0) — a valid, masked-out fetch."""

    def start_iter(i, slot):
        for s in range(sb):
            page = page_table_ref[g * sb + s, i]
            pltpu.make_async_copy(
                kv_hbm_ref.at[page], kv_bufs.at[slot, s], sems.at[slot, s]
            ).start()

    return start_iter


def _ring_prologue(start_iter, num_pages):
    """Prime the first NBUF-1 ring slots."""
    for j in range(NBUF - 1):
        @pl.when(j < num_pages)
        def _(j=j):
            start_iter(j, j)


def _ring_wait_and_refill(start_iter, kv_hbm_ref, kv_bufs, sems, sb, i,
                          num_pages):
    """Wait for iteration i's slot, then refill the slot consumed LAST
    iteration ((i-1) mod NBUF — already read, safe to overwrite) with
    iteration i+NBUF-1's pages.  Returns the slot index."""
    slot = jax.lax.rem(i, NBUF)
    for s in range(sb):
        pltpu.make_async_copy(
            kv_hbm_ref.at[0], kv_bufs.at[slot, s], sems.at[slot, s]
        ).wait()

    @pl.when(i + NBUF - 1 < num_pages)
    def _():
        start_iter(i + NBUF - 1, jax.lax.rem(i + NBUF - 1, NBUF))

    return slot


def length_order(seq_lens):
    """(order, rank) that put B sequences in order of length, or None where
    the decode kernel's blocks leave nothing to sort (one block holds them
    all, or each holds one): `order[r]` is the sequence at place r,
    `rank[b]` the place of sequence b (ties by index), so `x[order]` sorts
    rows and `y[rank]` puts sorted rows back.  One [B, B] comparison; no
    `sort`, which the programs are kept free of
    (tests/test_tpu_lowering.py)."""
    B = seq_lens.shape[0]
    if _pick_sb(B) in (1, B):
        return None
    lane = jnp.arange(B, dtype=jnp.int32)
    shorter = (seq_lens[None, :] < seq_lens[:, None]) | (
        (seq_lens[None, :] == seq_lens[:, None]) & (lane[None, :] < lane[:, None]))
    rank = shorter.sum(axis=1, dtype=jnp.int32)
    order = ((rank[None, :] == lane[:, None]) * lane[None, :]).sum(
        axis=1, dtype=jnp.int32)
    return order, rank


def rows_at(x, index):
    """x[index] along the first axis for an `index` that is a permutation:
    nothing to clip, nothing met twice."""
    return x.at[index].get(unique_indices=True, mode="promise_in_bounds")


def _by_length(call, page_table, seq_lens, q, kv):
    """`call(page_table, seq_lens, q, kv)` with the sequences in order of
    length, its rows put back in the order they came in.  The grid takes
    `sb` sequences a block in the order given and walks every one of them
    out to the block's longest (`_block_pages`); sorted, a block's
    sequences are of like length.  Each row is computed from its own
    sequence alone, so the outputs are the unsorted call's bit for bit."""
    by_length = length_order(seq_lens)
    if by_length is None:
        return call(page_table, seq_lens, q, kv)
    order, rank = by_length
    out = call(rows_at(page_table, order), rows_at(seq_lens, order),
               rows_at(q, order), kv)
    return rows_at(out, rank)


def _per_row(ref, base, n):
    """SMEM scalars ref[base .. base+n) as an int32 [n, 1, 1, 1] vector for
    masking.  Built by select-on-iota: Mosaic has no layout for the
    [n] -> [n, 1, 1, 1] shape cast a stack+reshape would need."""
    row = jax.lax.broadcasted_iota(jnp.int32, (n, 1, 1, 1), 0)
    out = jnp.zeros((n, 1, 1, 1), jnp.int32)
    for r in range(n):
        out = jnp.where(row == r, ref[base + r], out)
    return out


def _pallas_call(kernel, B, sb, nq, lane, kv_arr, out_lane=None):
    """Shared PrefetchScalarGridSpec + pallas_call builder: q blocks are
    [SB, nq, lane] and out blocks [SB, nq, out_lane or lane], the cache
    stays in HBM, scratch is the NBUF-deep VMEM ring + DMA semaphores."""
    return functools.partial(
        pl.pallas_call,
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B // sb,),
            in_specs=[
                pl.BlockSpec((sb, nq, lane), lambda g, *_: (g, 0, 0)),
                pl.BlockSpec(memory_space=_HBM),
            ],
            out_specs=pl.BlockSpec(
                (sb, nq, out_lane or lane), lambda g, *_: (g, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((NBUF, sb) + kv_arr.shape[1:], kv_arr.dtype),
                pltpu.SemaphoreType.DMA((NBUF, sb)),
            ],
        ),
    )


def _decode_kernel(
    # scalar prefetch
    page_table_ref,  # [B, W] int32 (SMEM)
    seq_lens_ref,  # [B] int32 (SMEM)
    # inputs
    q_ref,  # [SB, nq, d] VMEM block for this sequence block
    kv_hbm_ref,  # [num_pages, 2, nkv, ps, d] in HBM
    # output
    out_ref,  # [SB, nq, d] VMEM
    # scratch
    kv_bufs,  # [NBUF, SB, 2, nkv, ps, d] VMEM ring
    sems,  # DMA semaphores [NBUF, SB]
    *,
    sb: int,
    page_size: int,
    num_kv_heads: int,
    head_dim: int,
    scale: float,
    logit_softcap: float,
    value_dim: Optional[int] = None,
):
    """`value_dim` set: a LATENT page [1, 1, ps, d] (one plane, one row a
    token; engine/kvcache.StateLayout): the row is the key, its first
    `value_dim` columns are the value, so a page is fetched once."""
    g = pl.program_id(0)
    nq = q_ref.shape[1]
    group = nq // num_kv_heads
    vd = value_dim or head_dim

    num_pages = _block_pages(seq_lens_ref, g, sb, page_size)
    start_iter = _make_start_iter(
        page_table_ref, kv_hbm_ref, kv_bufs, sems, g, sb)
    _ring_prologue(start_iter, num_pages)

    # q per kv-head group: [SB, nkv, group, d] f32
    q = q_ref[...].astype(jnp.float32).reshape(sb, num_kv_heads, group, head_dim)
    lens = _per_row(seq_lens_ref, g * sb, sb)

    def body(i, carry):
        m, l, acc = carry
        slot = _ring_wait_and_refill(
            start_iter, kv_hbm_ref, kv_bufs, sems, sb, i, num_pages)

        k = kv_bufs[slot, :, 0].astype(jnp.float32)  # [SB, nkv, ps, d]
        if value_dim is None:
            v = kv_bufs[slot, :, 1].astype(jnp.float32)
        else:
            v = k[..., :value_dim]
        s_ = _heads_dot(q, k, 2) * scale  # [SB, nkv, group, ps]
        if logit_softcap > 0.0:
            s_ = jnp.tanh(s_ / logit_softcap) * logit_softcap
        token_pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, 1, page_size), 3
        )
        s_ = jnp.where(token_pos < lens, s_, -1e30)
        m_new = jnp.maximum(m, s_.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s_ - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        pv = _heads_dot(p, v, 1)  # [SB, nkv, group, d]
        acc_new = acc * alpha + pv
        return m_new, l_new, acc_new

    m0 = jnp.full((sb, num_kv_heads, group, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((sb, num_kv_heads, group, 1), jnp.float32)
    acc0 = jnp.zeros((sb, num_kv_heads, group, vd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_pages, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)
    out_ref[...] = out.reshape(sb, nq, vd).astype(out_ref.dtype)


@_entry("logit_softcap", "interpret", "scale", "name")
def paged_attention_pallas(
    q: jnp.ndarray,  # [B, nq, d]
    kv_pages: jnp.ndarray,  # [num_pages, 2, nkv, ps, d]
    page_table: jnp.ndarray,  # [B, max_pages] int32
    seq_lens: jnp.ndarray,  # [B] int32
    logit_softcap: float = 0.0,
    interpret: bool = False,
    scale: Optional[float] = None,  # None = 1/sqrt(d)
    name: str = "paged_attention_decode",  # the trace label of this call
) -> jnp.ndarray:
    B, nq, d = q.shape
    num_pages_total, _, nkv, ps, _ = kv_pages.shape
    if d % 128 != 0 and not interpret:
        # Lane tiling pads head_dim to 128 and Mosaic rejects both DMA
        # slices of the padded trailing dim and the shape-cast that would
        # unpack a token-packed row: a narrower head takes the XLA path, or
        # stores its rows side by side in rows of 128 (models/hybrid.py).
        raise ValueError(
            f"pallas paged attention requires head_dim % 128 == 0, got {d}"
        )
    sb = _pick_sb(B)
    scale = float(1.0 / (d ** 0.5)) if scale is None else float(scale)
    kernel = functools.partial(
        _decode_kernel,
        sb=sb,
        page_size=ps,
        num_kv_heads=nkv,
        head_dim=d,
        scale=scale,
        logit_softcap=logit_softcap,
    )
    return _by_length(_pallas_call(kernel, B, sb, nq, d, kv_pages)(
        out_shape=jax.ShapeDtypeStruct((B, nq, d), q.dtype),
        interpret=interpret,
        name=name,
    ), page_table, seq_lens, q, kv_pages)


# ---------------- ragged paged attention (mixed prefill+decode) ----------------
#
# The generalization of the decode kernel above to arbitrary per-sequence
# query lengths (docs/kernels.md): sequences pack their query slices — a
# full prompt, a prompt chunk, or a single decode token — into one [T, nq,
# d] buffer at RAGGED_BQ-aligned offsets.  The grid walks BQ-token blocks;
# each block belongs to exactly ONE sequence (the alignment invariant) and
# streams that sequence's KV pages through the same VMEM DMA ring as the
# decode kernel, folding them into an online-softmax accumulator under a
# causal mask anchored at the sequence's kv offset.  Decode (q_len=1) and
# prefill chunks (q_len=C) are the same program; sliding windows, int8 KV
# pages and scale overrides are masked/dequantized/applied in-kernel.

RAGGED_BQ = 8  # query tokens per grid block (f32 sublane granularity)


def _ragged_block_metadata(q_start, q_len, G: int, bq: int):
    """[G] (sequence index, local query offset) per BQ block, derived on
    device from the per-sequence metadata (no host reads on packing
    metadata — the jaxlint ragged-metadata-host-sync contract).  Blocks
    outside every slice get sequence -1 (the kernel skips them)."""
    blk0 = jnp.arange(G, dtype=jnp.int32) * bq
    member = (blk0[None, :] >= q_start[:, None]) & (
        blk0[None, :] < (q_start + q_len)[:, None]
    )  # [B, G]
    hit = member.any(axis=0)
    block_seq = jnp.where(
        hit, jnp.argmax(member, axis=0).astype(jnp.int32), -1)
    block_qoff = jnp.where(
        hit, blk0 - q_start[jnp.maximum(block_seq, 0)], 0)
    return block_seq, block_qoff


def _ragged_kernel(
    # scalar prefetch (SMEM)
    block_seq_ref,  # [G] int32 — sequence owning each BQ block (-1 = pad)
    block_qoff_ref,  # [G] int32 — block's first query offset in its slice
    page_table_ref,  # [B, W] int32
    kv_start_ref,  # [B] int32 — history length per sequence
    q_len_ref,  # [B] int32
    window_ref,  # [1] int32 — sliding window (0 = full attention)
    # inputs
    q_ref,  # [BQ, nq, d] VMEM block
    kv_hbm_ref,  # [num_pages, 2, nkv, ps, d] in HBM (int8 when quantized)
    *rest,  # (scales_hbm?) out_ref, kv_bufs, kv_sems, (s_bufs, s_sems?)
    bq: int,
    page_size: int,
    num_kv_heads: int,
    head_dim: int,
    scale: float,
    logit_softcap: float,
    quantized: bool,
    value_dim: Optional[int] = None,  # set: latent pages (_decode_kernel)
):
    if quantized:
        scales_hbm_ref, out_ref, kv_bufs, kv_sems, s_bufs, s_sems = rest
    else:
        out_ref, kv_bufs, kv_sems = rest
        scales_hbm_ref = s_bufs = s_sems = None
    vd = value_dim or head_dim

    g = pl.program_id(0)
    s_raw = block_seq_ref[g]
    s = jnp.maximum(s_raw, 0)
    qoff = block_qoff_ref[g]
    kv0 = kv_start_ref[s]
    qn = q_len_ref[s]
    w = window_ref[0]
    # keys this block needs: positions 0 .. kv0 + min(qoff+BQ, qn) - 1
    kv_hi = kv0 + jnp.minimum(qoff + bq, qn)
    num_pages = jnp.where(
        s_raw < 0, 0, (kv_hi + page_size - 1) // page_size)

    def start_iter(i, slot):
        page = page_table_ref[s, i]
        pltpu.make_async_copy(
            kv_hbm_ref.at[page], kv_bufs.at[slot], kv_sems.at[slot]
        ).start()
        if quantized:
            pltpu.make_async_copy(
                scales_hbm_ref.at[page], s_bufs.at[slot], s_sems.at[slot]
            ).start()

    for j in range(NBUF - 1):
        @pl.when(j < num_pages)
        def _(j=j):
            start_iter(j, j)

    nq = q_ref.shape[1]
    group = nq // num_kv_heads
    rows = bq * group
    # [nkv, BQ*group, d]: row r*group+j is query token r, q-head group j
    q = (
        q_ref[...].astype(jnp.float32)
        .reshape(bq, num_kv_heads, group, head_dim)
        .transpose(1, 0, 2, 3)
        .reshape(num_kv_heads, rows, head_dim)
    )
    rowq = jax.lax.broadcasted_iota(jnp.int32, (1, rows, 1), 1) // group
    qpos = kv0 + qoff + rowq  # absolute position per query row
    qvalid = (qoff + rowq) < qn

    def body(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, NBUF)
        pltpu.make_async_copy(
            kv_hbm_ref.at[0], kv_bufs.at[slot], kv_sems.at[slot]
        ).wait()
        if quantized:
            pltpu.make_async_copy(
                scales_hbm_ref.at[0], s_bufs.at[slot], s_sems.at[slot]
            ).wait()

        @pl.when(i + NBUF - 1 < num_pages)
        def _():
            start_iter(i + NBUF - 1, jax.lax.rem(i + NBUF - 1, NBUF))

        k = kv_bufs[slot, 0].astype(jnp.float32)  # [nkv, ps, d]
        if value_dim is None:
            v = kv_bufs[slot, 1].astype(jnp.float32)
        else:
            v = k[..., :value_dim]
        if quantized:
            k = k * s_bufs[slot, 0].astype(jnp.float32)[..., None]
            v = v * s_bufs[slot, 1].astype(jnp.float32)[..., None]
        s_ = _heads_dot(q, k, 2) * scale  # [nkv, BQ*group, ps]
        if logit_softcap > 0.0:
            s_ = jnp.tanh(s_ / logit_softcap) * logit_softcap
        kpos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page_size), 2)
        mask = (kpos <= qpos) & qvalid
        mask = mask & ((qpos - kpos < w) | (w <= 0))
        s_ = jnp.where(mask, s_, -1e30)
        m_new = jnp.maximum(m, s_.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s_ - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        pv = _heads_dot(p, v, 1)  # [nkv, BQ*group, d]
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((num_kv_heads, rows, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((num_kv_heads, rows, 1), jnp.float32)
    acc0 = jnp.zeros((num_kv_heads, rows, vd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_pages, body, (m0, l0, acc0))
    # rows past their slice's q_len never see a valid key: their running
    # max stays -1e30, so exp(s - m) saturates to 1 and acc collects a
    # garbage mean of V — mask them to exact zero instead
    out = jnp.where(qvalid, acc / jnp.maximum(l, 1e-30), 0.0)
    out_ref[...] = (
        out.reshape(num_kv_heads, bq, group, vd)
        .transpose(1, 0, 2, 3)
        .reshape(bq, nq, vd)
        .astype(out_ref.dtype)
    )


def _dense_ragged_kernel(
    # scalar prefetch (SMEM)
    page_table_ref,  # [B, W] int32
    kv_start_ref,  # [B] int32 — history length per lane
    q_len_ref,  # [B] int32 — valid slice tokens (<= sp; 0 = inactive)
    window_ref,  # [1] int32 — sliding window (0 = full attention)
    # inputs
    q_ref,  # [BQ, nq, d] VMEM block
    kv_hbm_ref,  # [num_pages, 2, nkv, ps, d] in HBM (int8 when quantized)
    *rest,  # (scales_hbm?) out_ref, kv_bufs, kv_sems, (s_bufs, s_sems?)
    sp: int,
    page_size: int,
    num_kv_heads: int,
    head_dim: int,
    scale: float,
    logit_softcap: float,
    quantized: bool,
):
    """Dense-block variant of `_ragged_kernel` (docs/kernels.md): every BQ
    block holds L = BQ // sp lanes at a STATIC stride of `sp` query rows
    each — the speculative-decode packing, where lane i's verify slice
    (its last token + K drafts, padded to sp) sits at offset i*sp.  The
    one-sequence-per-block invariant is relaxed to
    one-sequence-per-STRIDE-SLOT: row j belongs to relative lane j // sp,
    a static index, so the compute stays the decode kernel's batched
    [L, nkv, rows, ·] shape while each iteration streams page i of all L
    member lanes concurrently (L DMAs, like the decode kernel's SB)."""
    if quantized:
        scales_hbm_ref, out_ref, kv_bufs, kv_sems, s_bufs, s_sems = rest
    else:
        out_ref, kv_bufs, kv_sems = rest
        scales_hbm_ref = s_bufs = s_sems = None

    g = pl.program_id(0)
    lanes = q_ref.shape[0] // sp  # L member lanes per block (static)
    nq = q_ref.shape[1]
    group = nq // num_kv_heads
    rows = sp * group

    kv0 = _per_row(kv_start_ref, g * lanes, lanes)
    qn = _per_row(q_len_ref, g * lanes, lanes)
    # keys each lane needs; a lane with no valid query rows (inactive, or
    # capacity-starved mid-dispatch with a large kv_start) must not drive
    # the page loop — all its rows are masked, so streaming its history
    # would be pure wasted DMA.  Scalar max over SMEM reads (like
    # _block_pages): the loop bound must be a scalar, not a vector reduce.
    max_hi = jnp.int32(0)
    for l in range(lanes):
        qn_l = q_len_ref[g * lanes + l]
        max_hi = jnp.maximum(
            max_hi,
            jnp.where(qn_l > 0, kv_start_ref[g * lanes + l] + qn_l, 0))
    num_pages = (max_hi + page_size - 1) // page_size

    def start_iter(i, slot):
        for l in range(lanes):
            # inactive lanes' padded table entries are the null page — a
            # valid, masked-out fetch (same contract as the decode kernel)
            page = page_table_ref[g * lanes + l, i]
            pltpu.make_async_copy(
                kv_hbm_ref.at[page], kv_bufs.at[slot, l], kv_sems.at[slot, l]
            ).start()
            if quantized:
                pltpu.make_async_copy(
                    scales_hbm_ref.at[page], s_bufs.at[slot, l],
                    s_sems.at[slot, l]
                ).start()

    for j in range(NBUF - 1):
        @pl.when(j < num_pages)
        def _(j=j):
            start_iter(j, j)

    # [L, nkv, sp*group, d]: row r*group+j is the lane's query token r,
    # q-head group j — the decode kernel's batched shape with sp query
    # rows per lane instead of one
    q = (
        q_ref[...].astype(jnp.float32)
        .reshape(lanes, sp, num_kv_heads, group, head_dim)
        .transpose(0, 2, 1, 3, 4)
        .reshape(lanes, num_kv_heads, rows, head_dim)
    )
    rowq = jax.lax.broadcasted_iota(jnp.int32, (1, 1, rows, 1), 2) // group
    qpos = kv0 + rowq  # absolute position per query row
    qvalid = rowq < qn
    w = window_ref[0]

    def body(i, carry):
        m, l_, acc = carry
        slot = jax.lax.rem(i, NBUF)
        for l in range(lanes):
            pltpu.make_async_copy(
                kv_hbm_ref.at[0], kv_bufs.at[slot, l], kv_sems.at[slot, l]
            ).wait()
            if quantized:
                pltpu.make_async_copy(
                    scales_hbm_ref.at[0], s_bufs.at[slot, l],
                    s_sems.at[slot, l]
                ).wait()

        @pl.when(i + NBUF - 1 < num_pages)
        def _():
            start_iter(i + NBUF - 1, jax.lax.rem(i + NBUF - 1, NBUF))

        k = kv_bufs[slot, :, 0].astype(jnp.float32)  # [L, nkv, ps, d]
        v = kv_bufs[slot, :, 1].astype(jnp.float32)
        if quantized:
            k = k * s_bufs[slot, :, 0].astype(jnp.float32)[..., None]
            v = v * s_bufs[slot, :, 1].astype(jnp.float32)[..., None]
        s_ = _heads_dot(q, k, 2) * scale  # [L, nkv, rows, ps]
        if logit_softcap > 0.0:
            s_ = jnp.tanh(s_ / logit_softcap) * logit_softcap
        kpos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, 1, page_size), 3)
        mask = (kpos <= qpos) & qvalid
        mask = mask & ((qpos - kpos < w) | (w <= 0))
        s_ = jnp.where(mask, s_, -1e30)
        m_new = jnp.maximum(m, s_.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s_ - m_new)
        l_new = l_ * alpha + p.sum(axis=-1, keepdims=True)
        pv = _heads_dot(p, v, 1)  # [L, nkv, rows, d]
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((lanes, num_kv_heads, rows, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((lanes, num_kv_heads, rows, 1), jnp.float32)
    acc0 = jnp.zeros((lanes, num_kv_heads, rows, head_dim), jnp.float32)
    m, l_, acc = jax.lax.fori_loop(0, num_pages, body, (m0, l0, acc0))
    # rows past q_len (slice padding / inactive lanes) never see a valid
    # key: mask them to exact zero (same contract as the solo-block kernel)
    out = jnp.where(qvalid, acc / jnp.maximum(l_, 1e-30), 0.0)
    out_ref[...] = (
        out.reshape(lanes, num_kv_heads, sp, group, head_dim)
        .transpose(0, 2, 1, 3, 4)
        .reshape(lanes * sp, nq, head_dim)
        .astype(out_ref.dtype)
    )


def _dense_ragged_call(q, pages, scales, page_table, q_len, kv_start, win,
                       sp, logit_softcap, scale, interpret):
    """pallas_call plumbing for the dense-stride kernel (shared scratch
    ring shape with the solo kernel, widened to L pages per iteration)."""
    T, nq, d = q.shape
    quantized = scales is not None
    nkv, ps = pages.shape[2], pages.shape[3]
    lanes = RAGGED_BQ // sp
    kernel = functools.partial(
        _dense_ragged_kernel,
        sp=sp,
        page_size=ps,
        num_kv_heads=nkv,
        head_dim=d,
        scale=float(scale),
        logit_softcap=logit_softcap,
        quantized=quantized,
    )
    in_specs = [
        pl.BlockSpec((RAGGED_BQ, nq, d), lambda g, *_: (g, 0, 0)),
        pl.BlockSpec(memory_space=_HBM),
    ]
    scratch = [
        pltpu.VMEM((NBUF, lanes) + pages.shape[1:], pages.dtype),
        pltpu.SemaphoreType.DMA((NBUF, lanes)),
    ]
    operands = [q, pages]
    if quantized:
        in_specs.append(pl.BlockSpec(memory_space=_HBM))
        scratch += [
            pltpu.VMEM((NBUF, lanes) + scales.shape[1:], scales.dtype),
            pltpu.SemaphoreType.DMA((NBUF, lanes)),
        ]
        operands.append(scales)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(T // RAGGED_BQ,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (RAGGED_BQ, nq, d), lambda g, *_: (g, 0, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((T, nq, d), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention_dense",
    )(page_table, kv_start, q_len, win, *operands)


@_entry("logit_softcap", "scale", "interpret", "dense_stride")
def ragged_paged_attention_pallas(
    q: jnp.ndarray,  # [T, nq, d] — packed at RAGGED_BQ-aligned offsets
    kv_pages,  # [num_pages, 2, nkv, ps, d] or (int8 pages, scales)
    page_table: jnp.ndarray,  # [B, W] int32
    q_start: jnp.ndarray,  # [B] int32 (each a multiple of RAGGED_BQ)
    q_len: jnp.ndarray,  # [B] int32 (0 = inactive lane)
    kv_start: jnp.ndarray,  # [B] int32
    window=None,  # traced int32 scalar or None (full attention)
    logit_softcap: float = 0.0,
    scale: Optional[float] = None,
    interpret: bool = False,
    dense_stride: Optional[int] = None,  # static lane stride < RAGGED_BQ:
    # lane i's slice sits at offset i*dense_stride and blocks hold
    # BQ // dense_stride lanes (the speculative-verify packing)
) -> jnp.ndarray:
    T, nq, d = q.shape
    if T % RAGGED_BQ != 0:
        raise ValueError(
            f"ragged buffer length {T} not a multiple of RAGGED_BQ="
            f"{RAGGED_BQ}; pad the packed buffer")
    if d % 128 != 0 and not interpret:
        raise ValueError(
            f"ragged pallas kernel requires head_dim % 128 == 0, got {d}")
    quantized = isinstance(kv_pages, tuple)
    if quantized:
        pages, scales = kv_pages
        nkv, ps = pages.shape[2], pages.shape[3]
    else:
        pages, scales = kv_pages, None
        nkv, ps = kv_pages.shape[2], kv_pages.shape[3]
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    if dense_stride is not None and dense_stride < RAGGED_BQ:
        # dense-block packing (speculative verify): lanes share blocks at
        # a static stride, so the one-sequence-per-block invariant becomes
        # one-sequence-per-stride-slot (_dense_ragged_kernel)
        if RAGGED_BQ % dense_stride != 0:
            raise ValueError(
                f"dense_stride {dense_stride} must divide RAGGED_BQ="
                f"{RAGGED_BQ}")
        B = page_table.shape[0]
        if B * dense_stride != T:
            raise ValueError(
                f"dense packing expects T == B*stride "
                f"({B}*{dense_stride}), got T={T}")
        win = jnp.reshape(jnp.asarray(
            window if window is not None else 0, jnp.int32), (1,))
        return _dense_ragged_call(
            q, pages, scales, page_table, q_len, kv_start, win,
            dense_stride, logit_softcap, scale, interpret)
    G = T // RAGGED_BQ
    block_seq, block_qoff = _ragged_block_metadata(q_start, q_len, G, RAGGED_BQ)
    win = jnp.reshape(jnp.asarray(
        window if window is not None else 0, jnp.int32), (1,))
    kernel = functools.partial(
        _ragged_kernel,
        bq=RAGGED_BQ,
        page_size=ps,
        num_kv_heads=nkv,
        head_dim=d,
        scale=float(scale),
        logit_softcap=logit_softcap,
        quantized=quantized,
    )
    in_specs = [
        pl.BlockSpec((RAGGED_BQ, nq, d), lambda g, *_: (g, 0, 0)),
        pl.BlockSpec(memory_space=_HBM),
    ]
    scratch = [
        pltpu.VMEM((NBUF,) + pages.shape[1:], pages.dtype),
        pltpu.SemaphoreType.DMA((NBUF,)),
    ]
    operands = [q, pages]
    if quantized:
        in_specs.append(pl.BlockSpec(memory_space=_HBM))
        scratch += [
            pltpu.VMEM((NBUF,) + scales.shape[1:], scales.dtype),
            pltpu.SemaphoreType.DMA((NBUF,)),
        ]
        operands.append(scales)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(G,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (RAGGED_BQ, nq, d), lambda g, *_: (g, 0, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((T, nq, d), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(block_seq, block_qoff, page_table, kv_start, q_len, win,
      *operands)


@_entry("logit_softcap", "interpret")
def ragged_single_token_split_pallas(
    q: jnp.ndarray,  # [T, nq, d] — packed at RAGGED_BQ-aligned offsets
    kv_pages: jnp.ndarray,  # [num_pages, 2, nkv, ps, d]
    page_table: jnp.ndarray,  # [B, W] int32
    q_start: jnp.ndarray,  # [B] int32
    q_len: jnp.ndarray,  # [B] int32
    kv_start: jnp.ndarray,  # [B] int32
    logit_softcap: float = 0.0,
    interpret: bool = False,
) -> jnp.ndarray:
    """The ragged contract with the work split by slice length: the lanes
    whose slice is ONE token go through the decode kernel in one call over
    the lanes, every longer slice through the ragged kernel.

    The ragged kernel gives each RAGGED_BQ-token block to one sequence and
    walks that sequence's pages one page an iteration, alone: a batch of
    single decode tokens is a block a lane in series, seven of each block's
    eight query rows masked.  The decode kernel takes the same lanes
    `MAX_SB` to a block, sorted by length, a page DMA a lane in flight
    every iteration (48 lanes of ~350 tokens: 98 us against 447,
    docs/kernels.md "The packed step's single-token lanes").  The slice's
    K/V is in the pages before attention runs (ops/kv_write.write_ragged_kv), so
    a one-token slice at `kv_start` IS a decode lane of length `kv_start +
    1`: the same keys, the same float32 products and online softmax.

    Which lanes are single-token is read from `q_len` here, in the
    program: the decode call sees length 0 for every other lane (an empty
    lane costs a block nothing: its loop runs to its longest lane, and
    `_by_length` puts the empty ones together), the ragged call sees
    `q_len` 0 for the single-token ones (`_ragged_block_metadata` then
    owns their blocks to nobody and the kernel's loop runs 0 pages there;
    rows outside every slice come back exact zero).  The decode rows are
    put back at `q_start` by one scatter of B rows."""
    T = q.shape[0]
    single = q_len == 1
    rows = q.at[jnp.where(single, q_start, 0)].get(
        mode="promise_in_bounds")  # [B, nq, d]
    decoded = paged_attention_pallas(
        rows, kv_pages, page_table, jnp.where(single, kv_start + 1, 0),
        logit_softcap=logit_softcap, interpret=interpret)
    chunks = ragged_paged_attention_pallas(
        q, kv_pages, page_table, q_start, jnp.where(single, 0, q_len),
        kv_start, logit_softcap=logit_softcap, interpret=interpret)
    # a lane that is not single-token aims past the buffer and is dropped
    lane = jnp.arange(q_len.shape[0], dtype=jnp.int32)
    return chunks.at[jnp.where(single, q_start, T + lane)].set(
        decoded, mode="drop", unique_indices=True)


# ---------------- latent pages (models/latent.py) ----------------
#
# A latent-attention layer keeps ONE row a token: [compressed K/V | the
# roped key all heads share | zeros up to a multiple of 128 lanes], in pages
# [num_pages, 1, 1, ps, row] (engine/kvcache.StateLayout).  In the absorbed
# form every query head is a query over that row, and the row's first
# `value_dim` columns are the value: attention with ONE key/value head whose
# value is a slice of its key.  The two kernels above run it with
# `value_dim` set: a page is fetched once and serves scores and values.


@_entry("scale", "value_dim", "interpret")
def latent_attention_decode_pallas(
    q: jnp.ndarray,  # [B, nq, row]: absorbed queries, zero where the row pads
    pages: jnp.ndarray,  # [num_pages, 1, 1, ps, row]
    page_table: jnp.ndarray,  # [B, W] int32
    seq_lens: jnp.ndarray,  # [B] int32
    scale: float,
    value_dim: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """One query token a lane over its latent pages -> [B, nq, value_dim]."""
    B, nq, d = q.shape
    sb = _pick_sb(B)
    kernel = functools.partial(
        _decode_kernel, sb=sb, page_size=pages.shape[3], num_kv_heads=1,
        head_dim=d, scale=float(scale), logit_softcap=0.0,
        value_dim=value_dim)
    return _by_length(_pallas_call(kernel, B, sb, nq, d, pages, out_lane=value_dim)(
        out_shape=jax.ShapeDtypeStruct((B, nq, value_dim), q.dtype),
        interpret=interpret,
        name="latent_attention_decode",
    ), page_table, seq_lens, q, pages)


@_entry("scale", "value_dim", "interpret")
def latent_attention_ragged_pallas(
    q: jnp.ndarray,  # [T, nq, row] packed at RAGGED_BQ-aligned offsets
    pages: jnp.ndarray,  # [num_pages, 1, 1, ps, row]
    page_table: jnp.ndarray,  # [B, W] int32
    q_start: jnp.ndarray,  # [B]
    q_len: jnp.ndarray,  # [B]
    kv_start: jnp.ndarray,  # [B]
    scale: float,
    value_dim: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """The packed step's attention over latent pages (the ragged contract
    of `ragged_paged_attention_pallas`) -> [T, nq, value_dim]."""
    T, nq, d = q.shape
    if T % RAGGED_BQ != 0:
        raise ValueError(
            f"ragged buffer length {T} not a multiple of RAGGED_BQ={RAGGED_BQ}")
    G = T // RAGGED_BQ
    block_seq, block_qoff = _ragged_block_metadata(q_start, q_len, G, RAGGED_BQ)
    kernel = functools.partial(
        _ragged_kernel, bq=RAGGED_BQ, page_size=pages.shape[3],
        num_kv_heads=1, head_dim=d, scale=float(scale), logit_softcap=0.0,
        quantized=False, value_dim=value_dim)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(G,),
            in_specs=[
                pl.BlockSpec((RAGGED_BQ, nq, d), lambda g, *_: (g, 0, 0)),
                pl.BlockSpec(memory_space=_HBM),
            ],
            out_specs=pl.BlockSpec(
                (RAGGED_BQ, nq, value_dim), lambda g, *_: (g, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((NBUF,) + pages.shape[1:], pages.dtype),
                pltpu.SemaphoreType.DMA((NBUF,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((T, nq, value_dim), q.dtype),
        interpret=interpret,
        name="latent_attention_ragged",
    )(block_seq, block_qoff, page_table, kv_start, q_len,
      jnp.zeros((1,), jnp.int32), q, pages)


# ---------------- window rings (models/hybrid.py, plain grouped-query rows) ----------------
#
# The packed step's attention of a window layer that keeps a RING a lane
# (engine/kvcache.StateLayout, `window_kv`).  The ring holds what the lane
# had BEFORE this buffer; the buffer's own keys are not in it yet (a slice
# overwrites slots its own first queries still need), so a block of queries
# reads two sources through one DMA ring: its lane's ring pages, then the
# pages of the buffer itself that cover the lane's slice.  Keys are turned
# by position before they are stored, so the softmax needs no order among
# them: the mask alone needs each slot's position.
#
# Slot s of a ring of R = Wr x ps slots holds the newest position below
# kv_start that is congruent to s modulo R, so position p lies on ring page
# (p // ps) % Wr: a block walks the pages of positions [lo, kv_start) with
# lo the oldest position its FIRST query still sees, and no others.  Work
# and bytes a query are those of its window, whatever the context.

WINDOW_BQ = 32  # query tokens a grid step: shares a lane's pages among them
#: the step's accumulators, scores and page ring (~12 MB at 128 query heads
#: of 128) pass the compiler's default scoped limit of 16 MB with its own
#: temporaries; the chip has 128 MB
WINDOW_VMEM_BYTES = 64 << 20


def _window_ragged_kernel(
    # scalar prefetch (SMEM)
    block_seq_ref,  # [G * nsub] int32: the lane of each `bq`-token block (-1 = pad)
    block_qoff_ref,  # [G * nsub] int32: the block's first query's offset in its slice
    ring_table_ref,  # [B, Wr] int32
    q_start_ref,  # [B] int32
    q_len_ref,  # [B] int32
    kv_start_ref,  # [B] int32
    # inputs
    q_ref,  # [nkv, nsub * bq * group, d] VMEM: row (t, j) is token t, head j of the group
    ring_hbm_ref,  # [pages, 2, nkv, ps, d] in HBM
    buf_hbm_ref,  # [T / ps, 2, nkv, ps, d] in HBM: the buffer's own K/V as pages
    # output
    out_ref,  # [nkv, nsub * bq * group, d] VMEM
    # scratch
    kv_bufs,  # [NBUF, 2, nkv, 2 ps, d] VMEM ring: two pages a slot
    sems,  # DMA semaphores [NBUF, 2]
    *,
    nsub: int,
    bq: int,
    group: int,
    page_size: int,
    ring_width: int,
    scale: float,
):
    g = pl.program_id(0)
    ps, Wr = page_size, ring_width
    R = Wr * ps
    nkv = q_ref.shape[0]
    d = q_ref.shape[2]

    def attend(q, s_raw, qoff, ntok: int):
        """`ntok` tokens of ONE lane (q [nkv, ntok * group, d]) over the
        lane's ring and its slice of the buffer -> [nkv, ntok * group, d]."""
        rows = ntok * group
        s = jnp.maximum(s_raw, 0)
        kv0, qn, qs = kv_start_ref[s], q_len_ref[s], q_start_ref[s]
        live = (s_raw >= 0) & (qoff < qn)
        # ring pages: positions [lo, kv0), lo the oldest the first query sees
        lo = jnp.maximum(kv0 + qoff + 1 - R, 0)
        u0 = lo // ps
        n_ring = jnp.where(live & (kv0 > lo), (kv0 - 1) // ps - u0 + 1, 0)
        # buffer pages: indices [b_lo, t1] of the packed buffer
        t0 = qs + qoff
        t1 = t0 + jnp.minimum(qn - qoff, ntok) - 1
        v0 = jnp.maximum(qs, t0 + 1 - R) // ps
        n_buf = jnp.where(live, t1 // ps - v0 + 1, 0)
        n_pages = n_ring + n_buf

        # TWO pages an iteration, side by side in one slot: 128 keys fill the
        # lanes of the scores' tiles and the MXU's columns, which 64 leave
        # half empty
        n_iters = (n_pages + 1) // 2

        def start_iter(i, slot):
            for half in range(2):
                vp = 2 * i + half
                into = kv_bufs.at[slot, :, :, pl.ds(half * ps, ps), :]
                sem = sems.at[slot, half]

                @pl.when(vp < n_ring)
                def _(vp=vp, into=into, sem=sem):
                    page = ring_table_ref[s, jax.lax.rem(u0 + vp, Wr)]
                    pltpu.make_async_copy(
                        ring_hbm_ref.at[page], into, sem).start()

                @pl.when((vp >= n_ring) & (vp < n_pages))
                def _(vp=vp, into=into, sem=sem):
                    pltpu.make_async_copy(
                        buf_hbm_ref.at[v0 + vp - n_ring], into, sem).start()

                # an odd count's last half: the null page, masked out, so
                # that no byte the kernel multiplies is one nobody wrote
                @pl.when(vp >= n_pages)
                def _(into=into, sem=sem):
                    pltpu.make_async_copy(
                        ring_hbm_ref.at[0], into, sem).start()

        for j in range(NBUF - 1):
            @pl.when(j < n_iters)
            def _(j=j):
                start_iter(j, j)

        rowq = jax.lax.broadcasted_iota(jnp.int32, (1, rows, 1), 1) // group
        qpos = kv0 + qoff + rowq  # absolute position per query row
        qvalid = ((qoff + rowq) < qn) & (s_raw >= 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 2 * ps), 2)
        half_of, within = col // ps, jax.lax.rem(col, ps)

        def body(i, carry):
            m, l, acc = carry
            slot = jax.lax.rem(i, NBUF)
            for half in range(2):
                pltpu.make_async_copy(
                    ring_hbm_ref.at[0],
                    kv_bufs.at[slot, :, :, pl.ds(half * ps, ps), :],
                    sems.at[slot, half]).wait()

            @pl.when(i + NBUF - 1 < n_iters)
            def _():
                start_iter(i + NBUF - 1, jax.lax.rem(i + NBUF - 1, NBUF))

            k = kv_bufs[slot, 0]  # [nkv, 2 ps, d], the cache's dtype
            v = kv_bufs[slot, 1]
            s_ = _heads_dot(q, k, 2) * scale  # [nkv, rows, 2 ps] float32
            vp = 2 * i + half_of  # each column's page of the walk
            in_ring = vp < n_ring
            tk = (v0 + vp - n_ring) * ps + within  # its index in the buffer
            kpos = jnp.where(in_ring, (u0 + vp) * ps + within, kv0 + tk - qs)
            # a ring page's slots hold positions below kv0 (those at and
            # above it are stale), a buffer page's rows positions from kv0
            # on (those below it are another lane's): bounds as numbers,
            # since Mosaic selects no vector of booleans
            below = jnp.where(in_ring, kv0, jnp.int32(2 ** 30))
            from_ = jnp.where(in_ring, 0, kv0)
            mask = ((kpos < below) & (kpos >= from_) & (vp < n_pages)
                    & (kpos <= qpos) & (kpos > qpos - R) & qvalid)
            s_ = jnp.where(mask, s_, -1e30)
            m_new = jnp.maximum(m, s_.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s_ - m_new)
            l_new = l * alpha + p.sum(axis=-1, keepdims=True)
            pv = _heads_dot(p.astype(v.dtype), v, 1)  # [nkv, rows, d]
            return m_new, l_new, acc * alpha + pv

        m0 = jnp.full((nkv, rows, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((nkv, rows, 1), jnp.float32)
        acc0 = jnp.zeros((nkv, rows, d), jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, n_iters, body, (m0, l0, acc0))
        # rows past their slice never see a key: exact zero, not a mean of V
        return jnp.where(qvalid, acc / jnp.maximum(l, 1e-30), 0.0
                         ).astype(out_ref.dtype)

    first = block_seq_ref[g * nsub]
    if nsub == 1:
        out_ref[...] = attend(q_ref[...], first, block_qoff_ref[g], bq)
        return
    # one lane holds the whole step (blocks behind its slice's end are
    # padding): its pages are fetched once for all nsub blocks
    uniform = first >= 0
    for j in range(1, nsub):
        other = block_seq_ref[g * nsub + j]
        uniform = uniform & ((other == first) | (other < 0))

    @pl.when(uniform)
    def _():
        out_ref[...] = attend(
            q_ref[...], first, block_qoff_ref[g * nsub], nsub * bq)

    @pl.when(jnp.logical_not(uniform))
    def _():
        def one(j, _):
            rows = pl.ds(pl.multiple_of(j * bq * group, bq * group), bq * group)
            out_ref[:, rows, :] = attend(
                q_ref[:, rows, :], block_seq_ref[g * nsub + j],
                block_qoff_ref[g * nsub + j], bq)
            return 0

        jax.lax.fori_loop(0, nsub, one, 0)


@_entry("scale", "block", "interpret")
def window_attention_ragged_pallas(
    q: jnp.ndarray,  # [T, nq, d] packed queries, slices at multiples of `block`
    k_new: jnp.ndarray,  # [T, nkv, d] the buffer's own keys (not yet in the ring)
    v_new: jnp.ndarray,  # [T, nkv, d]
    ring_pages: jnp.ndarray,  # [pages, 2, nkv, ps, d] as it was BEFORE this buffer
    ring_table: jnp.ndarray,  # [B, Wr] int32; Wr * ps = window
    q_start: jnp.ndarray,  # [B] int32
    q_len: jnp.ndarray,  # [B] int32 (0 = no slice)
    kv_start: jnp.ndarray,  # [B] int32
    scale: float,
    block: int = RAGGED_BQ,
    interpret: bool = False,
) -> jnp.ndarray:
    """The contract of ops/attention.ring_window_attention_ragged (its
    oracle), as one kernel: nothing over [T, T] or [blocks, window] exists
    outside VMEM.  The queries go in, and the result comes out, by K/V head
    ([nkv, T x group, d]: two XLA transposes of the buffer), so that the
    kernel's matmuls take whole tiles whatever the group's size."""
    T, nq, d = q.shape
    nkv, ps = ring_pages.shape[2], ring_pages.shape[3]
    group = nq // nkv
    if T % block:
        raise ValueError(f"buffer of {T} tokens not a multiple of {block}")
    if d % 128 != 0 and not interpret:
        raise ValueError(
            f"window pallas kernel requires head_dim % 128 == 0, got {d}")
    nsub = max(1, WINDOW_BQ // block)
    step = nsub * block
    Tq = -(-T // step) * step
    block_seq, block_qoff = _ragged_block_metadata(
        q_start, q_len, Tq // block, block)
    qg = jnp.pad(q, ((0, Tq - T), (0, 0), (0, 0))).reshape(
        Tq, nkv, group, d).transpose(1, 0, 2, 3).reshape(nkv, Tq * group, d)
    Tk = -(-T // ps) * ps
    buf = jnp.pad(jnp.stack([k_new, v_new], axis=1).astype(ring_pages.dtype),
                  ((0, Tk - T), (0, 0), (0, 0), (0, 0)))
    buf = buf.reshape(Tk // ps, ps, 2, nkv, d).transpose(0, 2, 3, 1, 4)
    kernel = functools.partial(
        _window_ragged_kernel, nsub=nsub, bq=block, group=group,
        page_size=ps, ring_width=ring_table.shape[1], scale=float(scale))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(Tq // step,),
            in_specs=[
                pl.BlockSpec((nkv, step * group, d), lambda g, *_: (0, g, 0)),
                pl.BlockSpec(memory_space=_HBM),
                pl.BlockSpec(memory_space=_HBM),
            ],
            out_specs=pl.BlockSpec(
                (nkv, step * group, d), lambda g, *_: (0, g, 0)),
            scratch_shapes=[
                pltpu.VMEM((NBUF, 2, nkv, 2 * ps, d), ring_pages.dtype),
                pltpu.SemaphoreType.DMA((NBUF, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((nkv, Tq * group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=WINDOW_VMEM_BYTES),
        interpret=interpret,
        name="window_attention_ragged",
    )(block_seq, block_qoff, ring_table, q_start, q_len, kv_start,
      qg, ring_pages, buf)
    return out.reshape(nkv, Tq, group, d).transpose(1, 0, 2, 3).reshape(
        Tq, nq, d)[:T]
