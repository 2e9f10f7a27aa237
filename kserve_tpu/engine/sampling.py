"""Batched token sampling, fully inside jit.

Per-sequence parameters travel as a struct-of-arrays (`SamplingParams`
batch) so one compiled program serves any mix of greedy/temperature/top-k/
top-p/min-p requests — no recompiles per request.

top-k and top-p read their cutoffs from a threshold search over the
floats' key (`_cutoff`), not from a sort of the vocabulary: top-k and min-p
keep exactly what a sort would, the nucleus what the exact one of mass
`top_p` -/+ 1e-5 bounds (the sums run in another order than a cumulative
sum's: the acceptance rule is in tests/test_sampling.py).

Role parity: vLLM's Sampler (the reference delegates sampling to vLLM).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class SamplingParams:
    """Host-side per-request sampling config."""

    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0  # 0 = disabled
    min_p: float = 0.0
    repetition_penalty: float = 1.0
    frequency_penalty: float = 0.0
    presence_penalty: float = 0.0
    max_tokens: int = 16
    min_tokens: int = 0
    ignore_eos: bool = False
    stop: Optional[List[str]] = None
    seed: Optional[int] = None
    logprobs: Optional[int] = None

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0

    @property
    def has_penalties(self) -> bool:
        """True when this request needs the penalized decode path (which
        carries a [B, V] output-count array; the fast path skips it)."""
        return (
            self.repetition_penalty != 1.0
            or self.frequency_penalty != 0.0
            or self.presence_penalty != 0.0
        )


#: SamplingState's columns in the order a launch packs them (`packed`), the
#: int32 ones first; the float32 ones travel as their bits
INT_COLUMNS = ("top_k", "seed")
FLOAT_COLUMNS = ("temperature", "top_p", "min_p", "repetition_penalty",
                 "frequency_penalty", "presence_penalty")
COLUMNS = INT_COLUMNS + FLOAT_COLUMNS


def as_bits(column: np.ndarray) -> np.ndarray:
    """A host column as it travels in an int32 buffer: a float32 one as its
    bits, so nothing is rounded on the way."""
    return column.view(np.int32) if column.dtype == np.float32 else column


def as_float32(bits):
    """The float32 whose bits an int32 array holds: numpy on the host, a
    bitcast in a program.  Bit for bit either way."""
    if isinstance(bits, np.ndarray):
        return bits.view(np.float32)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


@jax.tree_util.register_dataclass
@dataclass
class SamplingState:
    """Device-side struct-of-arrays for a batch of B slots (a jit-traversable
    pytree)."""

    temperature: jnp.ndarray  # [B] f32 (0 => greedy)
    top_p: jnp.ndarray  # [B] f32
    top_k: jnp.ndarray  # [B] i32 (0 => off)
    min_p: jnp.ndarray  # [B] f32
    seed: jnp.ndarray  # [B] i32 (-1 => draw from the shared batch rng)
    repetition_penalty: jnp.ndarray  # [B] f32 (1.0 => off)
    frequency_penalty: jnp.ndarray  # [B] f32 (0.0 => off)
    presence_penalty: jnp.ndarray  # [B] f32 (0.0 => off)

    @staticmethod
    def planned(
        params_list: List[SamplingParams],
    ) -> Tuple[Dict[str, np.ndarray], str]:
        """The rows' columns on the HOST (numpy, float32 / int32 by the
        fields above) and, from the same columns, the path `sample_tokens`
        will take for them (SAMPLER_PATHS: engine_sampler_dispatches_total's
        label).  Nothing is uploaded: a launch packs the columns into what
        it hands the device (`packed`; `mixed` into its lanes' buffer:
        shapes.MixedLayout)."""
        def column(name, dtype):
            return np.asarray([getattr(p, name) for p in params_list], dtype)

        cols = {name: column(name, np.float32) for name in FLOAT_COLUMNS}
        cols["top_k"] = column("top_k", np.int32)
        cols["seed"] = np.asarray(
            [p.seed if p.seed is not None else -1 for p in params_list],
            np.int32)
        path = SAMPLER_PATHS[int(_truncates(
            cols["temperature"], cols["top_k"], cols["top_p"], cols["min_p"]))]
        return cols, path

    @staticmethod
    def packed(cols: Dict[str, np.ndarray]) -> np.ndarray:
        """`planned`'s columns as ONE int32 [8, B] array, in COLUMNS' order:
        one transfer instead of eight."""
        return np.stack([as_bits(cols[name]) for name in COLUMNS])

    @staticmethod
    def of_rows(rows) -> "SamplingState":
        """The state from `packed`'s rows, in COLUMNS' order: numpy rows on
        the host, static slices of an argument inside a program."""
        return SamplingState(**{
            name: row if name in INT_COLUMNS else as_float32(row)
            for name, row in zip(COLUMNS, rows)})

    @staticmethod
    def from_params(params_list: List[SamplingParams]) -> "SamplingState":
        """The state on the device, for the programs that take it as an
        argument: ONE transfer, then one small program that cuts the rows
        apart."""
        cols, _ = SamplingState.planned(params_list)
        return unpacked(jnp.asarray(SamplingState.packed(cols)))

    @staticmethod
    def defaults(batch: int) -> "SamplingState":
        return SamplingState(
            temperature=jnp.ones((batch,), jnp.float32),
            top_p=jnp.ones((batch,), jnp.float32),
            top_k=jnp.zeros((batch,), jnp.int32),
            min_p=jnp.zeros((batch,), jnp.float32),
            seed=jnp.full((batch,), -1, jnp.int32),
            repetition_penalty=jnp.ones((batch,), jnp.float32),
            frequency_penalty=jnp.zeros((batch,), jnp.float32),
            presence_penalty=jnp.zeros((batch,), jnp.float32),
        )


@jax.jit
def unpacked(packed: jnp.ndarray) -> SamplingState:
    """`SamplingState.packed`'s array, on the device, as the state."""
    return SamplingState.of_rows(tuple(packed))


#: the work a batch asks of the sampler: `truncate` where a sampled row
#: carries top-k, top-p or min-p (threshold searches, each 32
#: compare-and-reduce passes over [rows, vocab] a step: one for the nucleus,
#: one more where a sampled row sets top-k; nothing is sorted), `plain`
#: where none does (no pass); indexed by `_truncates`
SAMPLER_PATHS = ("plain", "truncate")


def _truncates(temperature, top_k, top_p, min_p):
    """Whether any sampled row asks for top-k, top-p or min-p.  A greedy row
    never does, whatever else it carries: its sampled value is discarded.
    Written for numpy and jax arrays alike, so the host's label and the
    device's branch are one predicate."""
    sampled = ~(temperature <= 0.0)
    return (sampled & ((top_k > 0) | (top_p < 1.0) | (min_p > 0.0))).any()


def sampler_truncates(state: SamplingState) -> jnp.ndarray:
    """`_truncates` of the batch, on the device.  A program that samples in
    a loop computes it once, outside the loop."""
    return _truncates(state.temperature, state.top_k, state.top_p, state.min_p)


def sampler_top_k(state: SamplingState) -> jnp.ndarray:
    """`_truncates` with top-p and min-p left out: whether any sampled row
    sets top-k.  The truncation searches for the k-th values only then.
    Computed beside `sampler_truncates`."""
    return _truncates(state.temperature, state.top_k, 1.0, 0.0)


#: bits of a float32; a cutoff search settles one of them a pass
_KEY_BITS = 32
_TOP_BIT = np.uint32(1 << 31)


def _threshold(key: jnp.ndarray) -> jnp.ndarray:
    """The float32 that a key stands for, [rows] -> [rows, 1].  Keys are
    uint32 in the floats' order: the bits, with the sign flipped for a
    positive number and every bit for a negative one.  Below -inf's and
    above +inf's lie NaNs, which compare false with everything."""
    bits = jax.lax.bitcast_convert_type(key ^ _TOP_BIT, jnp.int32)
    bits = jnp.where(bits < 0, bits ^ np.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)[:, None]


def _cutoff(scaled, weight, target, passes=_KEY_BITS):
    """Per row ([rows, 1]) the LARGEST float32 `t` for which the `weight` of
    the values >= t reaches `target`: the k-th largest value for a weight of
    1 each and a target of k, the nucleus's edge for a mass.  What is
    reached only falls as `t` rises, so `t`'s key is built from its top bit
    down, one fused compare-and-reduce over [rows, vocab] a bit: exact,
    whatever the row's distribution, and always a value of the row.  NaN,
    which masks nothing, where the row's numbers do not reach the target."""
    def probe(_, carry):
        key, bit = carry
        raised = key | bit
        reached = jnp.sum(
            jnp.where(scaled >= _threshold(raised), weight, 0.0), axis=-1)
        return jnp.where(reached >= target, raised, key), bit >> 1

    key, _ = jax.lax.fori_loop(
        0, passes, probe,
        (jnp.zeros(scaled.shape[:1], jnp.uint32), jnp.uint32(_TOP_BIT)))
    return _threshold(key)


def _truncated(
    scaled: jnp.ndarray,
    state: SamplingState,
    top_k: Optional[jnp.ndarray] = None,  # sampler_top_k(state)
) -> jnp.ndarray:
    """`scaled` with what top-k, top-p and min-p drop set to -inf.  Nothing
    is sorted: each cutoff is a threshold search over the floats' key
    (`_cutoff`), and rows mask with a float compare against it, so equal
    values stay together."""
    V = scaled.shape[-1]
    if top_k is None:
        top_k = sampler_top_k(state)
    # top-k: mask logits below the k-th largest (k==0 disables); searched
    # only where a sampled row of the batch sets it.  The count is a
    # float32's, exact for any vocabulary under 2 ** 24
    kth_val = _cutoff(
        scaled, 1.0, jnp.clip(state.top_k, 1, V).astype(jnp.float32),
        passes=jnp.where(top_k, _KEY_BITS, 0))
    topk_mask = jnp.where(
        (state.top_k > 0)[:, None], scaled < kth_val, jnp.zeros_like(scaled, bool)
    )
    scaled = jnp.where(topk_mask, -jnp.inf, scaled)

    # top-p (nucleus): keep the values with less than top_p of the mass
    # STRICTLY above them, that is down to the largest threshold with top_p
    # of the mass at or above it (top_p <= 0: the maximum, whose weight is 1)
    weight = jnp.exp(scaled - scaled.max(axis=-1, keepdims=True))
    cutoff_val = _cutoff(scaled, weight, jnp.maximum(
        state.top_p * weight.sum(axis=-1), np.finfo(np.float32).tiny))
    topp_mask = jnp.where(
        (state.top_p < 1.0)[:, None], scaled < cutoff_val, jnp.zeros_like(scaled, bool)
    )
    scaled = jnp.where(topp_mask, -jnp.inf, scaled)

    # min-p: drop tokens with prob < min_p * max_prob
    probs = jax.nn.softmax(scaled, axis=-1)
    max_prob = probs.max(axis=-1, keepdims=True)
    minp_mask = jnp.where(
        (state.min_p > 0.0)[:, None],
        probs < state.min_p[:, None] * max_prob,
        jnp.zeros_like(scaled, bool),
    )
    return jnp.where(minp_mask, -jnp.inf, scaled)


@jax.named_scope("sampler")
def sample_tokens(
    logits: jnp.ndarray,  # [B, V] f32
    state: SamplingState,
    rng: jax.Array,
    counters: Optional[jnp.ndarray] = None,  # [B] i32: tokens generated so far
    truncates: Optional[jnp.ndarray] = None,  # sampler_truncates(state)
    top_k: Optional[jnp.ndarray] = None,  # sampler_top_k(state)
) -> jnp.ndarray:
    """Returns [B] sampled token ids.  temperature==0 rows are greedy.
    Rows with state.seed >= 0 draw from their own PRNG stream
    (PRNGKey(seed) folded with the row's token counter) so a client-supplied
    seed reproduces output regardless of batching.

    Only a batch in which a sampled row carries top-k, top-p or min-p runs
    the truncation and its searches (SAMPLER_PATHS).  The tokens do not
    depend on that: with every mask off, truncation leaves the scaled
    logits as they are."""
    B = logits.shape[0]
    greedy = jnp.argmax(logits, axis=-1)

    temp = jnp.maximum(state.temperature, 1e-6)[:, None]
    scaled = logits / temp
    if truncates is None:
        truncates = sampler_truncates(state)
    scaled = jax.lax.cond(
        truncates, lambda: _truncated(scaled, state, top_k), lambda: scaled)

    if counters is None:
        counters = jnp.zeros((B,), jnp.int32)
    batch_keys = jax.random.split(rng, B)
    seeded_keys = jax.vmap(
        lambda s, c: jax.random.fold_in(jax.random.PRNGKey(s), c)
    )(jnp.maximum(state.seed, 0), counters)
    keys = jnp.where((state.seed >= 0)[:, None], seeded_keys, batch_keys)
    sampled = jax.vmap(lambda k, row: jax.random.categorical(k, row))(keys, scaled)
    return jnp.where(state.temperature <= 0.0, greedy, sampled).astype(jnp.int32)


def compute_logprobs(
    logits: jnp.ndarray,  # [B, V] f32 — post-penalty, pre-temperature
    sampled: jnp.ndarray,  # [B] i32 sampled token ids
    k: int,  # static top-k width (engine config max_logprobs)
) -> tuple:
    """Log-probabilities for OpenAI `logprobs` surfaces.

    Computed from the post-penalty, pre-temperature/filter logits: reported
    logprobs describe the model's distribution, not the sampling filters
    (matches vLLM's default behaviour the reference inherits through
    `huggingfaceserver/vllm/vllm_model.py`).

    Returns (lp [B], top_vals [B, k], top_ids [B, k])."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    lp = jnp.take_along_axis(logp, sampled[:, None].astype(jnp.int32), axis=1)[:, 0]
    top_vals, top_ids = jax.lax.top_k(logp, k)
    return lp, top_vals, top_ids.astype(jnp.int32)


def apply_penalties(
    logits: jnp.ndarray,  # [B, V]
    output_counts: jnp.ndarray,  # [B, V] int32 — counts of generated tokens
    repetition_penalty: jnp.ndarray,  # [B]
    frequency_penalty: jnp.ndarray,  # [B]
    presence_penalty: jnp.ndarray,  # [B]
    prompt_mask: Optional[jnp.ndarray] = None,  # [B, V] bool — in-prompt tokens
) -> jnp.ndarray:
    """vLLM-parity penalty semantics: repetition_penalty applies to tokens
    seen in the prompt OR the output; frequency/presence (OpenAI) apply to
    generated output only."""
    seen_out = output_counts > 0
    seen_rep = seen_out if prompt_mask is None else (seen_out | prompt_mask)
    rp = repetition_penalty[:, None]
    penalized = jnp.where(logits > 0, logits / rp, logits * rp)
    logits = jnp.where(seen_rep, penalized, logits)
    logits = logits - frequency_penalty[:, None] * output_counts
    logits = logits - presence_penalty[:, None] * seen_out.astype(logits.dtype)
    return logits
