"""Paged KV cache, the host's side: device arrays' builders, what a lane owns
(StateLayout), the page allocator.  The K/V writes are ops/kv_write.py.

Layout per layer: [num_pages, 2, n_kv_heads, page_size, head_dim] —
page-MAJOR so one page is one contiguous block holding K and V for ALL
local KV heads: the Pallas decode kernel streams it with a single 64KB-class
DMA descriptor per page (K+V together), while tensor parallelism still
shards the head axis over the `model` mesh with no resharding at attention
time.  Sequences own pages through a page table [B_slots,
max_pages_per_seq]; page 0 is reserved as the null page so padded table
entries are always valid gathers.

A looped model (models/llama.LlamaConfig.n_passes > 1) keeps K/V rows of
its own for every (pass, layer): `cache_rows` = passes x layers.  A layer's
array then holds `n_passes * num_pages` pages, pass u's at [u * num_pages,
(u + 1) * num_pages), and the forward reads and writes pass u through the
page table + u * num_pages (models/llama._run_passes): one page id of the
allocator, the prefix cache or the wire stands for that page in every row.

Role parity: replaces vLLM's block allocator + CUDA paged attention cache
(the reference delegates this entirely to vLLM; see SURVEY.md §2.3) with an
XLA-native design.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List

import jax
import jax.numpy as jnp


@dataclass
class KVCacheConfig:
    n_layers: int
    n_kv_heads: int
    head_dim: int
    page_size: int = 16
    num_pages: int = 1024
    max_pages_per_seq: int = 128
    dtype: str = "bfloat16"
    n_passes: int = 1  # a looped model's passes over its layers

    @property
    def max_seq_len(self) -> int:
        return self.max_pages_per_seq * self.page_size

    @property
    def cache_rows(self) -> int:
        """K/V rows a token holds: one per (pass, layer).  THE count every
        size of a page, a pool, a transfer or a spill multiplies by."""
        return self.n_passes * self.n_layers

    def bytes_per_page(self) -> int:
        """One page of ONE row."""
        itemsize = 2 if self.dtype in ("bfloat16", "float16") else 4
        return 2 * self.n_kv_heads * self.page_size * self.head_dim * itemsize

    def page_bytes(self) -> int:
        """One page of the pool, all its rows: what a transfer, a spill or
        a prefix page moves."""
        return self.cache_rows * self.bytes_per_page()


def device_filler(sharding, shape, dtype, value):
    """A function creating one `shape` array of `value` ON `sharding` (None
    = the default device): the fill runs under jit with out_shardings, so a
    cache sized for the whole mesh is never staged on one device and then
    moved.  One compiled program however often it is called."""
    return jax.jit(
        lambda: jnp.full(shape, value, dtype), out_shardings=sharding)


def init_kv_pages(config: KVCacheConfig, sharding=None) -> List[jnp.ndarray]:
    """[n_layers] list of page-major K/V pages:
    [n_passes * num_pages, 2, n_kv_heads, page_size, head_dim]."""
    shape = (config.n_passes * config.num_pages, 2, config.n_kv_heads,
             config.page_size, config.head_dim)
    make = device_filler(sharding, shape, jnp.dtype(config.dtype), 0)
    return [make() for _ in range(config.n_layers)]


def pages_of_passes(ids: jnp.ndarray, n_passes: int, pool: int) -> jnp.ndarray:
    """[n_passes, P]: where a looped model's layer array holds the pool's
    pages `ids` [P] for every pass (pass u's pages lie at + u * pool)."""
    return ids[None, :] + pool * jnp.arange(n_passes, dtype=ids.dtype)[:, None]


@dataclass(frozen=True)
class StateLayout:
    """What a lane owns, per layer kind: the one place that answers it.

    Derived from the model's per-layer table (models/llama.LayerSpec rows),
    three kinds of per-lane state live side by side:

    - `shared_kv`: pages of the pool, grown on demand through the
      PageAllocator and the page table, for every layer that writes
      `paged_kv` (all of a Llama's layers; one layer of a decoder whose
      other attention layers read it);
    - `window_kv`: for every layer that writes `window_kv` a RING of the
      last `window` tokens per lane, at fixed pages of its own array
      (lane b holds ring pages 1 + b * ring_width ..; page 0 is the null
      page), so it never grows;
    - `ssm` and `conv`: for every layer that writes `recurrent` one slot
      per lane, sized by the mixer: the scan's float32 state (Mamba-1:
      [d_inner, d_state]; Mamba-2: a matrix a head, [heads, head_dim,
      d_state]; a Kimi-delta mixer: [heads, head_dim, head_dim]; a gated
      short convolution: none, `ssm` holds no array and costs 0 bytes) and
      the convolution's tail ([d_conv - 1, columns the convolution runs
      over]: d_inner, x, B and C together, q, k and v together, or the
      hidden columns);
    - `latent`: for every layer that writes `latent_kv` (latent attention,
      models/latent.py) pages of the SAME pool and page table, holding ONE
      row a token and no K/V planes or heads: `latent_width` values (the
      compressed K/V and the roped key all heads share), stored in
      `latent_row` columns (padded to the TPU's 128 lanes, which is what
      the device's tiled layout would hold anyway; the padding is counted
      in every byte count here).

    A model with expert layers also carries `stats`: two int32 sums its
    forward adds to in the program (models/hybrid.py `_ffn`), which the
    `mixed` program zeroes at its start and returns with its tokens.

    Ring and slots belong to the LANE (the engine's slot index): nothing is
    allocated at admission, and a program starts a lane from zero state
    whenever the lane's slice begins at position 0."""

    paged_layers: tuple
    window_layers: tuple
    recurrent_layers: tuple
    kv_heads: int  # as stored (models/llama.LlamaConfig.cache_kv_heads)
    head_dim: int
    page_size: int
    num_pages: int
    lanes: int
    window: int
    d_inner: int
    d_state: int
    d_conv: int
    dtype: str = "bfloat16"
    n_passes: int = 1  # a looped model: every paged layer has a row a pass
    latent_layers: tuple = ()
    latent_width: int = 0  # values a latent row holds
    expert_layers: int = 0
    expert_sums: int = 2  # int32 sums an expert layer adds to `stats`
    # a recurrent slot's shapes, by the mixer; () / 0 = Mamba-1's; a state
    # of no elements (models/llama.NO_SCAN_STATE) = the tail is all a lane keeps
    ssm_shape: tuple = ()
    conv_width: int = 0

    def __post_init__(self):
        if not self.ssm_shape:
            object.__setattr__(self, "ssm_shape", (self.d_inner, self.d_state))
        if not self.conv_width:
            object.__setattr__(self, "conv_width", self.d_inner)

    @classmethod
    def of(cls, model_config, page_size: int, num_pages: int, lanes: int,
           dtype: str = "bfloat16") -> "StateLayout":
        table = model_config.layer_table()

        def rows(writes):
            return tuple(i for i, r in enumerate(table) if r.writes == writes)

        # the recurrent slot is the mixer's to size
        ssm_shape, conv_width, d_conv = model_config.recurrent_slot()

        return cls(
            paged_layers=rows("paged_kv"), window_layers=rows("window_kv"),
            recurrent_layers=rows("recurrent"),
            kv_heads=model_config.cache_kv_heads,
            head_dim=model_config.cache_head_dim,
            page_size=page_size, num_pages=num_pages, lanes=lanes,
            window=model_config.sliding_window if rows("window_kv") else 0,
            d_inner=model_config.mamba_d_inner,
            d_state=model_config.mamba_d_state,
            d_conv=d_conv, dtype=dtype,
            n_passes=model_config.n_passes,
            latent_layers=rows("latent_kv"),
            latent_width=model_config.latent_width,
            expert_layers=sum(r.ffn == "experts" for r in table),
            expert_sums=4 if model_config.counts_routed_pairs else 2,
            ssm_shape=ssm_shape, conv_width=conv_width)

    @property
    def _itemsize(self) -> int:
        return jnp.dtype(self.dtype).itemsize

    @property
    def ring_page_size(self) -> int:
        return math.gcd(self.window, self.page_size) if self.window else 0

    @property
    def ring_width(self) -> int:
        """Ring pages per lane and window layer."""
        return self.window // self.ring_page_size if self.window else 0

    @property
    def cache_rows(self) -> int:
        """K/V rows of the pool a token holds: one per (pass, paged layer)."""
        return self.n_passes * len(self.paged_layers)

    @property
    def latent_row(self) -> int:
        """Columns a latent row is stored in: `latent_width` rounded up to
        the TPU's 128 lanes."""
        return -(-self.latent_width // 128) * 128 if self.latent_layers else 0

    def bytes_per_token(self) -> dict:
        """Bytes of the pool one token of context holds, by kind: K and V
        of every (pass, paged layer); one row as stored of every latent
        layer."""
        out = {"shared_kv": self.cache_rows * 2 * self.kv_heads
               * self.head_dim * self._itemsize}
        if self.latent_layers:
            out["latent_kv"] = (len(self.latent_layers) * self.latent_row
                                * self._itemsize)
        return out

    def token_bytes(self) -> int:
        """Bytes of the pool one token of context holds, all its rows."""
        return sum(self.bytes_per_token().values())

    def page_bytes(self) -> int:
        """One page of the pool, all its rows: what a spill or a transfer
        of one page id moves."""
        return self.page_size * self.token_bytes()

    def lane_bytes(self) -> dict:
        """Bytes one lane holds whatever its context's length, by kind."""
        n = len(self.recurrent_layers)
        return {
            "window_kv": len(self.window_layers) * self.window * 2
            * self.kv_heads * self.head_dim * self._itemsize,
            "ssm": n * math.prod(self.ssm_shape) * 4,
            "conv": n * max(self.d_conv - 1, 0) * self.conv_width * self._itemsize,
        }

    def bytes_in_use(self, lanes_seated: int, pages_held: int) -> dict:
        """engine_state_bytes{kind}: what the seated lanes hold now."""
        out = {k: v * lanes_seated for k, v in self.lane_bytes().items()}
        for kind, n in self.bytes_per_token().items():
            out[kind] = pages_held * self.page_size * n
        return out

    def init_state(self, sharding=None) -> dict:
        """The device arrays, zeroed: {"paged": per paged layer the pool's
        pages, "window": per window layer the rings, "ssm" / "conv": per
        recurrent layer the slots}."""
        dtype = jnp.dtype(self.dtype)
        page = (2, self.kv_heads, self.page_size, self.head_dim)
        ring = (2, self.kv_heads, self.ring_page_size, self.head_dim)

        def fill(shape, dt, n):
            make = device_filler(sharding, shape, dt, 0)
            return [make() for _ in range(n)]

        n = len(self.recurrent_layers)
        extra = {}
        if self.latent_layers:
            extra["latent"] = fill(
                (self.num_pages, 1, 1, self.page_size, self.latent_row),
                dtype, len(self.latent_layers))
        if self.expert_layers:
            extra["stats"] = fill((self.expert_sums,), jnp.int32, 1)
        return {
            **extra,
            "paged": fill((self.num_pages,) + page, dtype, len(self.paged_layers)),
            "window": fill((1 + self.lanes * self.ring_width,) + ring, dtype,
                           len(self.window_layers)),
            "ssm": fill((self.lanes,) + self.ssm_shape, jnp.float32,
                        n if math.prod(self.ssm_shape) else 0),
            "conv": fill((self.lanes, max(self.d_conv - 1, 0), self.conv_width),
                         dtype, n),
        }


class PageAllocator:
    """Host-side free-list with refcounts; page 0 is reserved (null page for
    padding).  Refcounts let prefix-cached pages be shared by concurrent
    sequences AND the cache itself — a page returns to the free list only
    when its last reference drops."""

    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, 0, -1))  # stack, page 0 reserved
        self._refs = [0] * num_pages

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def can_allocate(self, n: int) -> bool:
        return len(self._free) >= n

    def allocate(self, n: int) -> List[int]:
        if n > len(self._free):
            raise MemoryError(f"KV cache exhausted: need {n} pages, {len(self._free)} free")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def share(self, pages: List[int]) -> None:
        for p in pages:
            if p != 0:
                self._refs[p] += 1

    def free(self, pages: List[int]) -> None:
        for p in pages:
            if p == 0:
                continue
            if self._refs[p] <= 0:
                # double-free must not duplicate the page on the free list
                # (two sequences would then share it and corrupt KV)
                continue
            self._refs[p] -= 1
            if self._refs[p] == 0:
                self._free.append(p)


def pages_needed(n_tokens: int, page_size: int) -> int:
    return (n_tokens + page_size - 1) // page_size


def init_kv_scales(config: KVCacheConfig, sharding=None) -> List[jnp.ndarray]:
    shape = (config.n_passes * config.num_pages, 2, config.n_kv_heads,
             config.page_size)
    make = device_filler(sharding, shape, jnp.float32, 1)
    return [make() for _ in range(config.n_layers)]
