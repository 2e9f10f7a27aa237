"""The one general traffic generator: a mix's data file in, a schedule out.

Steadiness comes from the schedule being FIXED, not drawn: a block of N
requests takes the N evenly spaced quantiles of each stated distribution
(prompt length, output length, gap), so every run of a cell offers the same
multiset of (prompt length, output length, gap) to the token.  `--seed`
only permutes the order, picks the token ids and the per-request sampling
seeds.  The burstiness of a Poisson stream is kept (the gaps are an
exponential's quantiles); the run-to-run draw of its total is not.

The order is a low-discrepancy one: a block is cut into strata of
`stratum` requests that each span the whole range of quantiles, with the
strata and the requests inside each shuffled, so any few seconds of a run
carry a sample of the whole distribution.  An open loop's seed rotates one
such sequence; a closed loop's deals fixed lists to the clients.
"""

import math
import random
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import List, Optional

_NORMAL = NormalDist()


def phase_of(seed: int, n: int) -> int:
    """Where in a period of n requests seed's window begins: the seed's
    multiple of the golden ratio (2654435761 / 2**32), so that neighbouring
    seeds cut at phases spread over the whole period, not one request
    apart, and any run of seeds covers it evenly."""
    return (seed * 2654435761 % 2**32) * n >> 32


def quantile(dist: dict, u: float) -> float:
    """The `u`-quantile (0 < u < 1) of a distribution stated as data."""
    kind = dist["dist"]
    if kind == "fixed":
        value = float(dist["value"])
    elif kind == "uniform":
        value = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        value = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
    elif kind == "exponential":  # mean 1; the caller scales
        value = -math.log1p(-u)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist:
        value = max(value, dist["min"])
    if "max" in dist:
        value = min(value, dist["max"])
    return value


def stratified(dist: dict, n: int) -> List[float]:
    """The n evenly spaced quantiles (i + 0.5) / n, in rising order."""
    return [quantile(dist, (i + 0.5) / n) for i in range(n)]


def low_discrepancy_order(n: int, stratum: int, rng: random.Random) -> List[int]:
    """A permutation of range(n): strata that each span the whole range
    (indices s, s + k, s + 2k, ...), their order and their insides shuffled
    by `rng`."""
    k = max(1, -(-n // max(1, stratum)))  # number of strata
    strata = [list(range(s, n, k)) for s in range(k)]
    rng.shuffle(strata)
    order = []
    for members in strata:
        rng.shuffle(members)
        order.extend(members)
    return order


@dataclass
class Request:
    index: int
    due_s: Optional[float]  # open loop: seconds from the window's opening
    prompt_len: int
    output_len: int
    prompt: List[int] = field(repr=False, default_factory=list)
    sampling_seed: int = 0
    phase: str = "window"  # ramp | window | cooldown (open loop)
    client: int = -1  # closed loop
    gap_s: Optional[float] = None  # open loop: seconds to the next request


def _lengths(mix: dict, n: int, rng: random.Random, scale: float):
    stratum = int(mix.get("stratum", 16))
    prompts = stratified(mix["prompt_len"], n)
    outputs = stratified(mix["output_len"], n)
    p_order = low_discrepancy_order(n, stratum, rng)
    o_order = low_discrepancy_order(n, stratum, rng)
    return (
        [max(1, round(prompts[i] * scale)) for i in p_order],
        [max(1, round(outputs[i] * scale)) for i in o_order],
    )


def _token_ids(n: int, vocab: int, rng: random.Random) -> List[int]:
    return [rng.randrange(vocab) for _ in range(n)]


def open_loop_period(mix: dict, rate: float, seconds: float,
                     rng: random.Random, vocab: int,
                     scale: float = 1.0) -> List[Request]:
    """round(rate * seconds) requests due inside [0, seconds): the gaps are
    the quantiles of the mix's gap distribution, rescaled so that they sum
    to `seconds` exactly — every run has the same count."""
    n = max(1, round(rate * seconds))
    gaps = stratified(mix.get("gaps", {"dist": "exponential"}), n)
    total = sum(gaps)
    gaps = [g * seconds / total for g in gaps]
    order = low_discrepancy_order(n, int(mix.get("stratum", 16)), rng)
    gaps = [gaps[i] for i in order]
    prompt_lens, output_lens = _lengths(mix, n, rng, scale)
    out, t = [], 0.0
    for i in range(n):
        out.append(Request(
            index=i, due_s=t, prompt_len=prompt_lens[i],
            output_len=output_lens[i],
            prompt=_token_ids(prompt_lens[i], vocab, rng),
            sampling_seed=rng.randrange(1 << 31), gap_s=gaps[i]))
        t += gaps[i]
    return out


def ramp_head(mix: dict, start_s: float, rng: random.Random, vocab: int,
              scale: float = 1.0) -> List[Request]:
    """How a ramp leaves idle without passing through program shapes that
    steady traffic never uses (each would stall the ramp for a compile): a
    PRIMER request of stated lengths, alone in the first dispatch, and 50 ms
    later a BURST of stated size and lengths that seats enough lanes at
    once.  Both are data of the mix, the same in every run."""
    out = []
    primer = mix.get("ramp_primer")
    if primer:
        n = max(1, round(primer["prompt_len"] * scale))
        out.append(Request(
            index=0, due_s=start_s, prompt_len=n,
            output_len=max(1, round(primer["output_len"] * scale)),
            prompt=_token_ids(n, vocab, rng),
            sampling_seed=rng.randrange(1 << 31), phase="ramp"))
    burst = mix.get("ramp_burst")
    if burst:
        prompts, outputs = _lengths(burst, burst["n"], rng, scale)
        for p, o in zip(prompts, outputs):
            out.append(Request(
                index=0, due_s=start_s + 0.05, prompt_len=p, output_len=o,
                prompt=_token_ids(p, vocab, rng),
                sampling_seed=rng.randrange(1 << 31), phase="ramp"))
    return out


def open_loop_schedule(mix: dict, rate: float, seconds: float, seed: int,
                       vocab: int, scale: float = 1.0) -> List[Request]:
    """Ramp, window and cool-down of one open-loop run.  The window opens
    at 0; only its requests are judged.

    The traffic is PERIODIC with the window as its period: one fixed
    low-discrepancy sequence of round(rate * seconds) requests (lengths and
    gaps the stratified quantiles, their order drawn once with a constant),
    repeated end to end.  `--seed` picks the phase at which the window cuts
    into it, the token ids and the sampling seeds.  So the window always
    holds exactly one period — the same multiset of (prompt length, output
    length, gap), in a rotated order — the ramp is the stretch of the
    previous period that leads up to it, and the cool-down the start of the
    next.  Over a whole period of a periodic load, what a phase shifts
    between the window's two ends cancels; a free permutation per seed moved
    `output_tok_s` by 2 % and `ttft_mean_ms` by 6 % between seeds
    (PERF.md, PR 24)."""
    base = open_loop_period(mix, rate, seconds, random.Random(0), vocab, scale)
    n = len(base)
    k = phase_of(seed, n)
    rng = random.Random(seed)
    ramp = float(mix.get("ramp_s", 0.0))
    cool = float(mix.get("cooldown_s", 0.0))

    def at(i: int, due: float, phase: str) -> Request:
        b = base[i % n]
        return Request(
            index=0, due_s=due, prompt_len=b.prompt_len,
            output_len=b.output_len, gap_s=b.gap_s,
            prompt=_token_ids(b.prompt_len, vocab, rng),
            sampling_seed=rng.randrange(1 << 31), phase=phase)

    before, i, t = [], k - 1, 0.0
    while ramp > 0:
        t -= base[i % n].gap_s
        if t < -ramp:
            break
        before.append(at(i, t, "ramp"))
        i -= 1
    window, t = [], 0.0
    for i in range(k, k + n):
        window.append(at(i, t, "window"))
        t += base[i % n].gap_s
    after, i = [], k + n
    while t < seconds + cool:
        after.append(at(i, t, "cooldown"))
        t += base[i % n].gap_s
        i += 1
    head = ramp_head(mix, -ramp, rng, vocab, scale) if ramp > 0 else []
    out = head + before[::-1] + window + after
    for index, r in enumerate(out):
        r.index = index
    return out


def closed_loop_schedule(mix: dict, clients: int, seed: int, vocab: int,
                         scale: float = 1.0) -> List[List[Request]]:
    """One list of requests per client; a client sends its next as its last
    ends.  Requests here are long against the window, so the order inside a
    list decides how much work falls into the window: the lists of lengths
    are therefore FIXED (drawn once, from the stratified pool, with a
    constant), and `--seed` deals them to the clients in another order and
    picks the token ids and sampling seeds.  Each client's FIRST output
    length is cut to a stratified share of itself (the residual life of a
    request already under way), so lanes start at spread phases and do not
    finish in waves."""
    fixed = random.Random(0)
    per_client = int(mix.get("per_client", 8))
    first_prompts, first_outputs = _lengths(mix, clients, fixed, scale)
    n = clients * (per_client - 1)
    prompt_lens, output_lens = _lengths(mix, n, fixed, scale)
    lists = []
    for c in range(clients):
        first = (first_prompts[c],
                 max(1, round(first_outputs[c] * (c + 0.5) / clients)))
        rest = [(prompt_lens[k], output_lens[k])
                for k in range(c * (per_client - 1), (c + 1) * (per_client - 1))]
        lists.append([first] + rest)
    fixed.shuffle(lists)  # residual lives not in client order
    rng = random.Random(seed)
    rng.shuffle(lists)
    out, idx = [], 0
    for c, lens in enumerate(lists):
        mine = []
        for prompt_len, out_len in lens:
            mine.append(Request(
                index=idx, due_s=None, prompt_len=prompt_len,
                output_len=out_len,
                prompt=_token_ids(prompt_len, vocab, rng),
                sampling_seed=rng.randrange(1 << 31), client=c))
            idx += 1
        out.append(mine)
    return out


def multiset(requests) -> dict:
    """What a run offers, order forgotten: per phase the sorted prompt
    lengths, output lengths and (open loop) gaps to the next request."""
    out = {}
    by_phase = {}
    for r in requests:
        by_phase.setdefault(r.phase, []).append(r)
    for phase, rs in by_phase.items():
        gaps = sorted(round(r.gap_s, 9) for r in rs if r.gap_s is not None)
        out[phase] = {
            "prompt_len": sorted(r.prompt_len for r in rs),
            "output_len": sorted(r.output_len for r in rs),
            "gaps": gaps,
        }
    return out
