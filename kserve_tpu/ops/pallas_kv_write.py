"""The K/V row write as a PAGE write (Pallas/TPU).

XLA's scatter in ops/kv_write._scatter_kv addresses every [head_dim] row
by (page, k/v, head, slot) and writes them one after another: 0.07-0.11 us
a row of 256 bytes on a v5e, padding rows included, whatever the cache's
size (docs/kernels.md "K/V page write").  This kernel moves the same bytes a page at a time: for
every page a call touches it copies the whole page [2, n_kv, page_size,
head_dim] (contiguous: the unit the attention kernels already DMA) into
VMEM, selects the new rows in by a mask on the slot dimension, and copies
it back.  The cache stays in HBM and is aliased onto the output, so the
program updates it in place exactly as it did the scatter.

The contract is the ragged one, in RUNS: run m writes `n[m]` consecutive
rows of the new-row buffer, starting at buffer index `src[m]`, to positions
`pos[m] ..` of the sequence whose pages are `page_table[row[m]]`.  A decode
step is one run of length 1 a live lane (0: a dead lane writes NOTHING); a
packed step one run a lane's slice; a ring's slice that wraps is two runs.
Tokens that belong to no run (the packed buffer's padding) are not written
anywhere, where the scatter sent them to the null page.

A whole-page read-modify-write is only right while no two runs of one call
touch the same page: pages are private to a sequence from its first
unshared token on (engine/prefix_cache.py shares FULL pages of a prompt,
which nothing writes again), and tests/test_pallas_kv_write.py asserts it
for the engine's own page tables.

The copy is exact (bf16 -> f32 -> bf16 is the identity): the cache holds
bit for bit what the scatter would have put there.

The entry points (`append_rows`, `write_runs`) are jitted functions whose
Python-level choices are static: a model's layers are unrolled in Python,
and a plain function would be traced, and its `pallas_call` lowered, once a
LAYER; a jitted one is traced and lowered once a PROGRAM, and every layer
after the first calls that one function (docs/kernels.md "A kernel's entry
point is a jitted function").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

AHEAD = 4  # page reads in flight ahead of the page being merged
BEHIND = 4  # page writes still in flight behind it
NBUF = AHEAD + BEHIND  # VMEM ring: a slot is refilled once its write is done

_HBM = pltpu.MemorySpace.HBM
VMEM_DEFAULT_BYTES = 16 << 20  # the compiler's scoped limit for one call
VMEM_SLACK_BYTES = 4 << 20  # its own temporaries beside what the call names


def _kv_page_write_kernel(
    # scalar prefetch (SMEM), one entry a work item = one page of one run
    page_ref,  # [NW] int32 — the page
    lo_ref,  # [NW] int32 — first slot written
    hi_ref,  # [NW] int32 — one past the last (== lo: nothing to do)
    start_ref,  # [NW] int32 — padded-buffer row that lands on slot 0
    # inputs
    rows_ref,  # [2 * n_kv * d / 128, page_size + N + page_size, 128] f32 VMEM
    kv_in_ref,  # [num_pages, 2 * n_kv, page_size, d] in HBM: pages are read
    # output
    kv_out_ref,  # the same buffer (aliased): pages are written back
    # scratch
    bufs,  # [NBUF, 2 * n_kv, page_size, d] VMEM ring
    read_sems,  # DMA semaphores [NBUF]
    write_sems,  # DMA semaphores [NBUF]
):
    nw = page_ref.shape[0]
    page_size = bufs.shape[2]
    chunks = bufs.shape[3] // 128  # a row's 128-lane tiles: a plane each

    def live(w):
        return hi_ref[w] > lo_ref[w]

    def read(w, slot):
        return pltpu.make_async_copy(
            kv_in_ref.at[page_ref[w]], bufs.at[slot], read_sems.at[slot])

    def write(w, slot):
        return pltpu.make_async_copy(
            bufs.at[slot], kv_out_ref.at[page_ref[w]], write_sems.at[slot])

    slot_of_row = jax.lax.broadcasted_iota(jnp.int32, (page_size, 128), 0)

    def merge(w, slot):
        mask = (slot_of_row >= lo_ref[w]) & (slot_of_row < hi_ref[w])
        start = start_ref[w]

        def plane(p, _):
            # a plane is (k/v, head); a row wider than 128 lanes is
            # `chunks` planes of the buffer side by side
            for chunk in range(chunks):
                at = (slot, p, slice(None), pl.ds(chunk * 128, 128))
                new = rows_ref[p * chunks + chunk, pl.ds(start, page_size), :]
                bufs[at] = jnp.where(
                    mask, new, bufs[at].astype(jnp.float32)).astype(bufs.dtype)

        jax.lax.fori_loop(0, bufs.shape[1], plane, None)

    def step(i, _):
        """Item i is merged while the reads of the AHEAD items after it and
        the writes of the BEHIND before it are in flight.  ONE loop from
        -AHEAD (the first reads) to nw + BEHIND (the last writes' waits),
        every stage under its own guard: Mosaic compiles this body once,
        where a prologue and an epilogue beside it cost as much again in
        every layer of every program."""
        # the slot item i + AHEAD reads into is the one item i - BEHIND
        # wrote from: that write has had BEHIND iterations to finish
        refill = jax.lax.rem(i + NBUF + AHEAD, NBUF)
        done = jnp.clip(i - BEHIND, 0, nw - 1)

        @pl.when((i >= BEHIND) & live(done))
        def _():
            write(done, refill).wait()

        ahead = jnp.minimum(i + AHEAD, nw - 1)

        @pl.when((i + AHEAD < nw) & live(ahead))
        def _():
            read(ahead, refill).start()

        w = jnp.clip(i, 0, nw - 1)

        @pl.when((i >= 0) & (i < nw) & live(w))
        def _():
            slot = jax.lax.rem(w, NBUF)
            read(w, slot).wait()
            merge(w, slot)
            write(w, slot).start()

    jax.lax.fori_loop(-AHEAD, nw + BEHIND, step, None)


def kv_page_write(
    kv_pages: jnp.ndarray,  # [num_pages, 2, n_kv, page_size, d]
    k: jnp.ndarray,  # [N, n_kv, d] the new keys, in buffer order
    v: jnp.ndarray,  # [N, n_kv, d]
    page: jnp.ndarray,  # [NW] int32 work items: the page ...
    lo: jnp.ndarray,  # [NW] ... its first slot written
    hi: jnp.ndarray,  # [NW] ... one past its last (== lo: no work)
    src: jnp.ndarray,  # [NW] ... the buffer row that lands on slot `lo`
    interpret: bool = False,
) -> jnp.ndarray:
    """Pages `page[w]` with slots [lo[w], hi[w]) replaced by rows
    src[w] .. of (k, v); every other byte of the cache as it was.  No two
    live work items may name the same page."""
    num_pages, _, n_kv, page_size, d = kv_pages.shape
    N = k.shape[0]
    # plane-major (k/v, head, 128-lane tile of the row), so a page's rows of
    # one plane are consecutive sublanes; float32 and 128 lanes wide,
    # because only of such a ref does Mosaic load a window that starts at
    # ANY sublane, and a run starts at any slot; a page of zeros either
    # side, so the window that lands on slot 0 never leaves the buffer
    rows = jnp.stack([k, v]).astype(jnp.float32)  # [2, N, n_kv, d]
    rows = rows.reshape(2, N, n_kv * d // 128, 128).transpose(0, 2, 1, 3)
    rows = jnp.pad(rows.reshape(-1, N, 128),
                   ((0, 0), (page_size, page_size), (0, 0)))
    start = src - lo + page_size
    # k/v and head as one dim of planes (a bitcast: the tiled dims stay)
    planes = kv_pages.reshape(num_pages, 2 * n_kv, page_size, d)
    # the new rows lie in VMEM whole: past the compiler's default scoped
    # limit (16 MB) for a long buffer of many heads (4096 tokens x 8 K/V
    # heads x 128: 35 MB of the chip's 128), where the call states its need
    need = rows.size * 4 + NBUF * planes[0].size * planes.dtype.itemsize
    params = {}
    if need > VMEM_DEFAULT_BYTES - VMEM_SLACK_BYTES:
        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=need + VMEM_SLACK_BYTES)
    return pl.pallas_call(
        _kv_page_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=_HBM),
            ],
            out_specs=pl.BlockSpec(memory_space=_HBM),
            scratch_shapes=[
                pltpu.VMEM((NBUF,) + planes.shape[1:], planes.dtype),
                pltpu.SemaphoreType.DMA((NBUF,)),
                pltpu.SemaphoreType.DMA((NBUF,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(planes.shape, planes.dtype),
        # operands count the scalar-prefetch arrays: the cache is the sixth
        input_output_aliases={5: 0},
        interpret=interpret,
        name="kv_page_write",
        **params,
    )(page, lo, hi, start, rows, planes).reshape(kv_pages.shape)


def run_work_items(page_table, row, src, n, pos, page_size: int,
                   num_items: int):
    """The work items (page, lo, hi, src) [num_items] of runs (row, src, n,
    pos) [M]: run m's pages in order, runs in order, then idle items.
    `num_items` must hold every page the runs can touch
    (`max_work_items`)."""
    first = pos // page_size
    pages = jnp.where(n > 0, (pos + n - 1) // page_size - first + 1, 0)
    ends = jnp.cumsum(pages)
    w = jnp.arange(num_items, dtype=jnp.int32)
    m = jnp.sum(w[:, None] >= ends[None, :], axis=1).astype(jnp.int32)
    live = m < n.shape[0]
    m = jnp.minimum(m, n.shape[0] - 1)
    nth = w - (ends - pages)[m]  # which page of its run
    base = (first[m] + nth) * page_size  # the page's first position
    lo = jnp.maximum(pos[m], base) - base
    hi = jnp.minimum(pos[m] + n[m], base + page_size) - base
    page = page_table[row[m], jnp.minimum(
        first[m] + nth, page_table.shape[1] - 1)]
    return (page, jnp.where(live, lo, 0), jnp.where(live, hi, 0),
            src[m] + base + lo - pos[m])


def max_work_items(tokens: int, runs: int, page_size: int) -> int:
    """Pages `runs` runs of `tokens` rows in all can touch: a run of n rows
    touches at most (n - 1) // page_size + 2."""
    return tokens // page_size + 2 * runs


@functools.partial(jax.jit, static_argnames=("interpret",))
def append_rows(kv_pages, k, v, page_table, pos, active, interpret=False):
    """The decode step: lane b's row (k[b], v[b]) to position pos[b] of
    its sequence; a lane that is not `active` writes nothing."""
    page_size = kv_pages.shape[3]
    B = k.shape[0]
    b = jnp.arange(B, dtype=jnp.int32)
    lo = pos % page_size
    return kv_page_write(
        kv_pages, k, v, page_table[b, pos // page_size], lo,
        lo + active.astype(jnp.int32), b, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def write_runs(kv_pages, k, v, page_table, row, src, n, pos,
               interpret=False):
    """Runs (row, src, n, pos) [M] of the buffer (k, v) [T]: see the
    module's contract."""
    page_size = kv_pages.shape[3]
    items = run_work_items(
        page_table, row, src, n, pos, page_size,
        max_work_items(k.shape[0], n.shape[0], page_size))
    return kv_page_write(kv_pages, k, v, *items, interpret=interpret)
