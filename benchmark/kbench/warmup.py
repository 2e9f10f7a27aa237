"""Warm the program shapes a cell's traffic reaches, before the window.

The engine compiles one `mixed` program per pair (token-buffer bucket T,
page-table width bucket W), about 50 s each cold and 10 s each from JAX's
persistent cache (tracing a 36-layer program is not cached), and a compile
stalls every request in flight.  A dispatch's T is the bucket of its packed
tokens (a decode lane's slice takes `lane_tokens`, a prompt chunk its
aligned length) and its W the bucket of the most pages a seated request
holds.  The cell's file names the pairs its traffic reaches in steady
state (`warm`: `tokens` x `widths`); the engine's packing policy (slice
alignment, steps per dispatch, bucket ladders) is copied ONCE, in the
configuration's `engine_policy`, because the program does not publish it;
`grid_of` cuts the ladders to the cell's server sizes.  The grid drives
each pair on purpose, with the engine otherwise idle.  One STAGE per W:

  an ANCHOR request whose prompt just reaches W's bucket, and `pacers`
  short requests, all decode for the stage's life: together they pack into
  the smallest warmed T and keep a dispatch in flight at all times.  After
  a burst of the anchor's tokens (a dispatch has just ended, the next is in
  flight) a WAVE of k prompts is sent at once (each small enough to stay
  inside W pages, `max_tokens` 1), sized so that background + wave pack
  into the next T.  Arriving during a dispatch, the wave is planned whole
  into the one after it.

What the grid misses shows: a shape first met inside the window makes the
run not correct (the compile counter, and the server not answering at the
window's opening).
"""

import asyncio
import random
import time

import aiohttp

from .loadgen import COMPLETIONS

REQUEST_TIMEOUT_S = 1200.0


def _previous(buckets: list, b: int) -> int:
    i = buckets.index(b)
    return buckets[i - 1] if i else 0


def grid_of(policy: dict, flags: dict, warm: dict) -> dict:
    """The grid of one cell: the configuration's copy of the engine's
    policy, cut to the server sizes the cell runs with (the token ladder
    ends at `max_prefill_len`, the width ladder doubles from `min_width`
    up to the pages of `max_model_len`), and the pairs the cell warms (all
    of them where its `warm` names none)."""
    page = flags["page_size"]
    cap_t, cap_w = flags["max_prefill_len"], flags["max_model_len"] // page
    tokens = sorted({b for b in policy["token_buckets"] if b <= cap_t} | {cap_t})
    widths, b = [], policy["min_width"]
    while b < cap_w:
        widths.append(b)
        b *= 2
    widths.append(cap_w)
    return {
        "page_size": page, "lanes": flags["max_batch_size"],
        "lane_tokens": policy["lane_tokens"],
        "tokens_per_dispatch": policy["tokens_per_dispatch"],
        "token_buckets": tokens, "width_buckets": widths,
        "pacers": warm["pacers"],
        "warm_tokens": warm.get("tokens", tokens),
        "warm_widths": warm.get("widths", widths),
    }


def grid_plan(grid: dict) -> list:
    """[(W, anchor_prompt_len, life_tokens, [(T, k, p), ...])]: per stage
    the anchor's prompt length, how many tokens anchor and pacers decode,
    and per wave its k prompts of p tokens."""
    page, lane = grid["page_size"], grid["lane_tokens"]
    tokens, widths = grid["token_buckets"], grid["width_buckets"]
    base = lane * (grid["pacers"] + 1)  # what the background packs
    plan = []
    for w in grid["warm_widths"]:
        anchor_prompt = min(_previous(widths, w) * page + lane, tokens[-1])
        waves = []
        for t in grid["warm_tokens"]:
            prev_t = _previous(tokens, t)
            if prev_t < base <= t:
                continue  # the background alone is this pair
            if base > t:
                raise ValueError(
                    f"{grid['pacers']} pacers already pack past T={t}")
            fill = t - base - 2 * lane  # beside the background, with slack
            p_max = w * page - 2 * lane  # a wave request stays inside W pages
            k = -(-fill // p_max)
            p = fill // k // lane * lane
            if not (p >= lane and prev_t < base + k * p <= t
                    and k + grid["pacers"] + 1 <= grid["lanes"]):
                raise ValueError(f"no wave reaches T={t} at W={w}")
            waves.append((t, k, p))
        # two dispatches a wave: the one it waits out, the one it rides
        life = grid["tokens_per_dispatch"] * (2 * len(waves) + 4)
        if anchor_prompt + life > w * page:
            raise ValueError(f"the anchor outgrows W={w}")
        plan.append((w, anchor_prompt, life, waves))
    return plan


async def _complete(session, base_url, model, prompt, max_tokens, on_token=None):
    body = {"model": model, "prompt": prompt, "max_tokens": max_tokens,
            "temperature": 0, "ignore_eos": True, "stream": True}
    async with session.post(
            base_url + COMPLETIONS, json=body,
            timeout=aiohttp.ClientTimeout(total=REQUEST_TIMEOUT_S)) as resp:
        if resp.status != 200:
            raise RuntimeError(
                f"warm-up request -> HTTP {resp.status}: "
                f"{(await resp.text())[:200]}")
        async for raw in resp.content:
            line = raw.strip()
            if line.startswith(b"data:") and line[5:].strip() != b"[DONE]":
                if on_token is not None:
                    on_token()


async def grid_warmup(base_url: str, model: str, grid: dict, vocab: int,
                      log=lambda msg: None) -> int:
    """Drive every (T, W) pair of `grid`; returns the number of waves."""
    rng = random.Random(0)

    def ids(n):
        return [rng.randrange(vocab) for _ in range(n)]

    n_waves = 0
    connector = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=connector) as session:
        for w, a_prompt, life, waves in grid_plan(grid):
            t0 = time.monotonic()
            burst = asyncio.Event()
            anchor = asyncio.ensure_future(_complete(
                session, base_url, model, ids(a_prompt), life, burst.set))
            await asyncio.sleep(0.05)  # the pacers join the anchor's dispatch
            pacers = [asyncio.ensure_future(_complete(
                session, base_url, model, ids(grid["lane_tokens"]), life))
                for _ in range(grid["pacers"])]
            for t, k, p in waves:
                # wait for the anchor's next burst of tokens (a dispatch
                # has just ended and the next is in flight), then send
                burst.clear()
                if not anchor.done():
                    waiter = asyncio.ensure_future(burst.wait())
                    await asyncio.wait(
                        [waiter, anchor], return_when=asyncio.FIRST_COMPLETED)
                    waiter.cancel()
                if anchor.done():
                    log(f"grid W={w} T={t}: the anchor ended before its wave")
                await asyncio.gather(*[
                    _complete(session, base_url, model, ids(p), 1)
                    for _ in range(k)])
                n_waves += 1
            await asyncio.gather(anchor, *pacers)
            log(f"grid W={w}: anchor {a_prompt}+{life}, "
                f"{len(waves)} waves, {time.monotonic() - t0:.1f} s")
    return n_waves
