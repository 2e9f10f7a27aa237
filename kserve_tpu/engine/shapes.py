"""The shape of a dispatch: which (T, W) program a step runs in.

The engine compiles one `mixed` program per pair (T, the length of the
packed token buffer; W, the width of the page table).  Which pair a step
takes is a policy — the token buckets, the width ladder, the alignment
slices are packed at, the steps a dispatch runs — and this module is the
one place that knows it: the engine's planners, the program table
(`compiled.program_defs`), AOT warm-up and the HLO oracle's canonical
signatures all ask a `DispatchShapes`, and `/v1/internal/scheduler/state`
publishes it (`dispatch.shapes`), so a client that warms shapes before a
measurement reads the policy instead of copying it.

The pair a mixed dispatch NEEDS comes from the policy alone
(`DispatchShapes.tokens`, `.width`); the pair it RUNS in also depends on
which executables the engine holds (`LoadedPairs.fit`): a dispatch compiles
only when no loaded pair holds it, since a compile stalls every request in
flight and a longer buffer or a wider table only pads.

How a mixed dispatch's host-built inputs reach the program is here too
(`MixedLayout`): three int32 buffers whose shapes spell T, B and W, filled
by the engine's planner and cut apart by the program, the sim's stub and
the HLO oracle through the one description.

A change of the policy (coarser buckets, a step count from queue depth) is
a change to this file.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable, List, Set, Tuple

import numpy as np

from .sampling import COLUMNS as SAMPLER_COLUMNS
from .sampling import INT_COLUMNS as SAMPLER_INT_COLUMNS
from .sampling import as_bits, as_float32

#: the first rung of the width ladder, in pages
MIN_WIDTH = 8


def width_ladder(max_pages: int) -> Tuple[int, ...]:
    """Page-table widths: doubling from MIN_WIDTH, ending at the most
    pages a sequence may own — attention gathers only about as many pages
    as the longest lane of a dispatch holds."""
    rungs = []
    b = MIN_WIDTH
    while b < max_pages:
        rungs.append(b)
        b *= 2
    return tuple(rungs) + (max_pages,)


def rung(ladder: Tuple[int, ...], n: int) -> int:
    """The first rung that holds n; the last for anything larger."""
    for b in ladder:
        if n <= b:
            return b
    return ladder[-1]


#: how a mixed dispatch's pair was found (`LoadedPairs.fit`):
#: engine_dispatch_shape_total's label
FITS = ("exact", "padded", "compiled")


#: mixed dispatches in a row that ran in an already loaded pair after which
#: an engine counts as settled (`LoadedPairs`)
SETTLED_AFTER = 16


class LoadedPairs:
    """The (T, W) pairs an engine's `mixed` program is loaded in: every pair
    a launch has run in and every pair the AOT cache preloaded at start-up
    (so a warm start plans as the cold run that filled the cache did).

    An engine that loaded a new pair within its last SETTLED_AFTER
    dispatches is still being warmed, by its first traffic or by a client
    that drives shapes on purpose, and compiles the pair a dispatch needs,
    as it always did.  Once settled it stalls its streams for a compile
    only where no loaded pair holds the dispatch."""

    def __init__(self, pairs: Iterable[Tuple[int, int]] = ()):
        self._pairs: Set[Tuple[int, int]] = set(pairs)
        self._since_new = 0  # dispatches since one ran in a pair new here

    def ran(self, pair: Tuple[int, int]) -> None:
        """A launch ran in `pair`."""
        if pair in self._pairs:
            self._since_new += 1
        else:
            self._pairs.add(pair)
            self._since_new = 0

    @property
    def settled(self) -> bool:
        return self._since_new >= SETTLED_AFTER

    def fit(self, tokens: int, width: int) -> Tuple[Tuple[int, int], str]:
        """The pair a mixed dispatch that needs (tokens, width) runs in, and
        which of FITS that is: the needed pair where it is loaded; else, in
        a settled engine, the loaded pair of least T, then least W, that
        holds it (the buffer pads with rows of no sequence, the table with
        null pages); else the needed pair itself, which compiles and joins
        the set."""
        need = (tokens, width)
        if need in self._pairs:
            return need, "exact"
        if self.settled:
            holding = [p for p in self._pairs
                       if p[0] >= tokens and p[1] >= width]
            if holding:
                return min(holding), "padded"
        return need, "compiled"

    def published(self) -> List[List[int]]:
        """As served under `dispatch.shapes.loaded`, smallest first."""
        return [list(p) for p in sorted(self._pairs)]


@dataclass(frozen=True)
class DispatchShapes:
    #: tokens a slice of the packed buffer is aligned to: the Pallas
    #: ragged kernel walks blocks of RAGGED_BQ tokens that each belong to
    #: ONE sequence, so wherever it can be selected slices start at its
    #: multiples; the XLA reference packs densely (1)
    align: int
    token_buckets: Tuple[int, ...]
    width_buckets: Tuple[int, ...]
    #: decode steps one dispatch runs per lane
    steps: int
    lanes: int

    @classmethod
    def of(cls, model_config, engine_config, backend: str) -> "DispatchShapes":
        """The policy under one resolved configuration.  `backend` is an
        argument because the programs are also built with no engine
        (`compiled.program_defs`, the HLO oracle)."""
        from ..ops.attention import _should_use_ragged_pallas, latent_uses_pallas
        from ..ops.pallas_paged_attention import RAGGED_BQ

        buckets = tuple(engine_config.prefill_buckets)
        if engine_config.sp > 1:
            bad = [b for b in buckets if b % engine_config.sp]
            if bad:
                raise ValueError(
                    f"prefill buckets {bad} not divisible by sp={engine_config.sp} "
                    "(ring-attention prefill shards the prompt dim over seq)"
                )
        if getattr(model_config, "is_latent", False):
            kernel_possible = latent_uses_pallas(
                engine_config.use_pallas, backend)
        else:
            kernel_possible = engine_config.use_pallas or (
                engine_config.use_pallas is None
                and _should_use_ragged_pallas(
                    model_config.cache_head_dim, backend,
                    engine_config.kv_quant == "int8")
            )
        return cls(
            align=RAGGED_BQ if kernel_possible else 1,
            token_buckets=buckets,
            width_buckets=width_ladder(engine_config.max_pages_per_seq),
            steps=engine_config.steps_per_sync,
            lanes=engine_config.max_batch_size,
        )

    @property
    def token_budget(self) -> int:
        """The most tokens one dispatch packs: the cap of a prompt chunk
        and the budget a mixed step shares between its lanes."""
        return self.token_buckets[-1]

    @property
    def fits_pure_decode(self) -> bool:
        """A pure-decode mixed step packs one aligned single-token slice
        per lane, so the largest bucket must cover the batch."""
        return self.lanes * self.align <= self.token_budget

    def admits_mixed(self, engine_config) -> bool:
        """Whether the engine can step through `mixed`: the program is not
        built staged or sequence-sharded."""
        return (engine_config.pp == 1 and engine_config.sp == 1
                and self.fits_pure_decode)

    def aligned(self, n: int) -> int:
        return -(-n // self.align) * self.align

    def bucket(self, n: int) -> int:
        """The token bucket of n tokens: the row length the legacy prefill
        programs pad to."""
        return rung(self.token_buckets, n)

    def tokens(self, n: int) -> int:
        """T of a mixed dispatch whose packed slices end at offset n: their
        bucket, in whole slices."""
        return self.aligned(self.bucket(n))

    def width(self, pages: int) -> int:
        """W of a dispatch whose longest lane owns `pages` pages."""
        return rung(self.width_buckets, pages)

    def pairs(self) -> List[Tuple[int, int]]:
        """Every (T, W) a mixed dispatch can take, smallest first."""
        ts = sorted({self.tokens(b) for b in self.token_buckets})
        return [(t, w) for t in ts for w in self.width_buckets]

    def published(self) -> Dict[str, object]:
        """The policy as served under `dispatch.shapes`, in the key names
        of the benchmark's own copy (`deployment.engine_policy`)."""
        return {
            "lane_tokens": self.align,
            "tokens_per_dispatch": self.steps,
            "token_buckets": list(self.token_buckets),
            "width_buckets": list(self.width_buckets),
            "min_width": self.width_buckets[0],
        }


#: the rows of `mixed`'s token buffer, [3, T] int32: the packed slices'
#: tokens, the lane each row belongs to (-1: padding) and its position
TOKEN_ROWS = ("q_tokens", "token_seq", "token_pos")
#: what the planner sets a lane: a row each of the lanes' buffer, in order
PLAN_ROWS = ("q_start", "q_len", "kv_start", "last_idx", "joins",
             "scan_tok0", "scan_pos0", "step0_emits", "capacity", "counters",
             "adapters")
#: the rows of `mixed`'s lanes' buffer, [20, B] int32: the plan's, the
#: sampler's columns (sampling.COLUMNS: a float32 one as its bits, so a seed
#: or a top_p arrives as it left) and `step`, the dispatch's number, which the
#: program folds into its base key (read at lane 0)
LANE_ROWS = PLAN_ROWS + SAMPLER_COLUMNS + ("step",)


@dataclass(frozen=True)
class MixedLayout:
    """Where the host-built inputs of ONE `mixed` dispatch lie, by its
    static sizes: T tokens in the packed buffer, B lanes, a page table W
    wide.  Three int32 arrays, three transfers: the tokens' buffer
    (TOKEN_ROWS), the lanes' buffer (LANE_ROWS) and the page table [B, W].
    Their shapes are the program's signature and spell T, B and W apart, so
    no two pairs of a grid share an executable (one flat buffer would:
    3 T + B W collides)."""

    tokens: int
    lanes: int
    width: int

    @property
    def shapes(self) -> Tuple[Tuple[int, int], ...]:
        """Of the tokens' buffer, the lanes' buffer and the page table."""
        return ((len(TOKEN_ROWS), self.tokens), (len(LANE_ROWS), self.lanes),
                (self.lanes, self.width))

    def pack(self, columns: Dict[str, np.ndarray],
             step: int) -> Tuple[np.ndarray, np.ndarray]:
        """The tokens' and the lanes' buffer from the planner's numpy
        columns by name (TOKEN_ROWS, as long as the packed slices reach:
        what lies behind them belongs to no lane; PLAN_ROWS and the
        sampler's columns, a value a lane) and the dispatch's number."""
        tokens_shape, lanes_shape, _ = self.shapes
        tokens_buf = np.zeros(tokens_shape, np.int32)
        lanes_buf = np.zeros(lanes_shape, np.int32)
        rows = self.rows(tokens_buf, lanes_buf)
        rows["token_seq"][:] = -1
        for name in TOKEN_ROWS:
            rows[name][:len(columns[name])] = columns[name]
        for name in PLAN_ROWS + SAMPLER_COLUMNS:
            rows[name][:] = as_bits(columns[name])
        rows["step"][:] = step & 0x7FFFFFFF  # an int32, however long it ran
        return tokens_buf, lanes_buf

    def rows(self, tokens_buf, lanes_buf) -> Dict[str, object]:
        """Every input by name as it lies: an int32 row of its buffer (a
        view of a numpy buffer, a static slice of a program's argument)."""
        if (tuple(tokens_buf.shape), tuple(lanes_buf.shape)) != self.shapes[:2]:
            raise ValueError(
                f"buffers of {tokens_buf.shape} and {lanes_buf.shape} are not "
                f"a mixed dispatch of {self}")
        return {**dict(zip(TOKEN_ROWS, tokens_buf)),
                **dict(zip(LANE_ROWS, lanes_buf))}

    def unpack(self, tokens_buf, lanes_buf) -> Dict[str, object]:
        """Every input by name as the program's body takes it: `joins` a
        boolean, the sampler's float32 columns from their bits, `step` a
        scalar, the rest int32 rows.  numpy in, numpy out (the sim's stub,
        the tests); inside a program, slices and bitcasts that cost the
        device nothing."""
        cols = self.rows(tokens_buf, lanes_buf)
        cols["joins"] = cols["joins"] != 0
        cols["step"] = cols["step"][0]
        for name in SAMPLER_COLUMNS:
            if name not in SAMPLER_INT_COLUMNS:
                cols[name] = as_float32(cols[name])
        return cols
