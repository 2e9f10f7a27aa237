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

A change of the policy (coarser buckets, a step count from queue depth) is
a change to this file.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable, List, Set, Tuple

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
