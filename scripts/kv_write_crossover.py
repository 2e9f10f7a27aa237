#!/usr/bin/env python3
"""The K/V write, page kernel against row scatter, per call on the device.

The measurement behind docs/kernels.md "K/V page write" and the rows in
docs/data/kv_write_crossover.v5e.json: `ops/kv_write.append_token_kv` (a
decode step's write) and `write_ragged_kv` (a packed step's) with
`page_kernel=True` and `page_kernel=False`, bf16 pages of 16 tokens, at the
head shapes, lanes and packed lengths the benchmark's cells compile.  Run
it on the chip (it refuses any other backend):

    python3 scripts/kv_write_crossover.py
    python3 scripts/kv_write_crossover.py --families qwen3-4b --tokens 512

How a call is timed: as scripts/decode_attention_crossover.py does.  The
write runs `n` times inside ONE jitted loop that carries the cache (in
place, as the served program's layers do; the positions move with the
loop's counter, so nothing is loop-invariant) and the time per call is the
slope between n = 72 and n = 216.  Before timing, both paths write the same
rows into the same cache and every page but the null page must be equal
bit for bit.

A packed call holds what a saturated cell's packed step holds: every lane
one decode token at an 8-aligned offset, and one prompt of what is left of
the buffer (`prompt`), or nothing but padding there (`decode-only`).

Results go to stdout as a markdown table and to
chiprun_out/kv_write_crossover.json.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from kserve_tpu.ops.kv_write import append_token_kv, write_ragged_kv

PAGE = 16
ALIGN = 8  # ops/pallas_paged_attention.RAGGED_BQ: a lane's slice offset
#: name -> (lanes, KV heads, head size, cache pages, table width)
FAMILIES = {
    "qwen3-4b": (48, 8, 128, 2300, 40),  # decode-sat, chat
    "ouro-2.6b": (12, 16, 128, 1200, 24),  # eval-sat: 4 passes x 300 pages
    "phi4-mini-flash/ring": (48, 10, 128, 1537, 32),  # reason-sat's rings
    "phi4-mini-flash/pool": (48, 10, 128, 2048, 40),
}
TOKENS = (128, 256, 512, 1024)
N_LO, N_HI = 72, 216


def _table(rng, lanes, width, num_pages):
    return jnp.asarray(
        rng.permutation(np.arange(1, num_pages))[: lanes * width]
        .reshape(lanes, width), jnp.int32)


def _decode_loop(page_kernel: bool, width: int):
    def run(n, kv, k, v, table, pos, active):
        def body(i, kv):
            p = (pos + i) % (width * PAGE)
            return append_token_kv(kv, k + i.astype(k.dtype), v, table, p,
                                   active, PAGE, page_kernel=page_kernel)

        return jax.lax.fori_loop(0, n, body, kv)

    return jax.jit(run, donate_argnums=(1,))


def _packed_loop(page_kernel: bool, width: int):
    def run(n, kv, k, v, table, seq, off, q_start, q_len, kv_start):
        lanes = jnp.arange(q_start.shape[0], dtype=jnp.int32)

        def body(i, kv):
            # every slice one position on: other slots, other pages
            start = (kv_start + i) % (width * PAGE - q_len)
            pos = start[jnp.maximum(seq, 0)] + off
            return write_ragged_kv(
                kv, k + i.astype(k.dtype), v, table, seq, pos, PAGE,
                runs=[(lanes, q_start, q_len, start)], page_kernel=page_kernel)

        return jax.lax.fori_loop(0, n, body, kv)

    return jax.jit(run, donate_argnums=(1,))


def _time(fn, n, kv, args, budget_s: float):
    kv = fn(n, kv, *args)
    kv.block_until_ready()
    t0 = time.perf_counter()
    kv = fn(n, kv, *args)
    kv.block_until_ready()
    once = time.perf_counter() - t0
    out = []
    for _ in range(int(min(40, max(9, budget_s / max(once, 1e-4))))):
        t0 = time.perf_counter()
        kv = fn(n, kv, *args)
        kv.block_until_ready()
        out.append(time.perf_counter() - t0)
    return out, kv


def _per_call(loop, kv, args, budget_s):
    lo, kv = _time(loop, N_LO, kv, args, budget_s / 4)
    hi, kv = _time(loop, N_HI, kv, args, budget_s * 3 / 4)
    per = [(h - statistics.median(lo)) / (N_HI - N_LO) for h in hi]
    q1, _, q3 = statistics.quantiles(per, n=4)
    med = statistics.median(per)
    return med * 1e6, (q3 - q1) / med


def _measure(loops, args, shape, budget_s, row):
    """Both paths once on equal caches (compared), then timed."""
    written = {}
    for path, loop in loops.items():
        kv = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.bfloat16)
        written[path] = loop(3, kv, *args)
    row["equal_but_null_page"] = bool(jnp.array_equal(
        written["kernel"][1:], written["scatter"][1:]))
    for path, loop in loops.items():
        row[f"{path}_us"], row[f"{path}_spread"] = _per_call(
            loop, written.pop(path), args, budget_s)
    return row


def measure_decode(family: str, budget_s: float) -> dict:
    lanes, nkv, d, num_pages, width = FAMILIES[family]
    rng = np.random.RandomState(lanes)
    k = jax.random.normal(jax.random.PRNGKey(1), (lanes, nkv, d), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (lanes, nkv, d), jnp.bfloat16)
    pos = jnp.asarray(rng.randint(0, width * PAGE, lanes), jnp.int32)
    args = (k, v, _table(rng, lanes, width, num_pages), pos,
            jnp.ones((lanes,), bool))
    loops = {"kernel": _decode_loop(True, width),
             "scatter": _decode_loop(False, width)}
    row = {"family": family, "form": "decode", "lanes": lanes, "nkv": nkv,
           "d": d, "cache_pages": num_pages, "rows": lanes * 2 * nkv}
    return _measure(loops, args, (num_pages, 2, nkv, PAGE, d), budget_s, row)


def measure_packed(family: str, tokens: int, prompt: bool,
                   budget_s: float) -> dict:
    lanes, nkv, d, num_pages, width = FAMILIES[family]
    rng = np.random.RandomState(tokens + lanes)
    q_start = np.arange(lanes, dtype=np.int32) * ALIGN
    q_len = np.ones(lanes, np.int32)
    left = tokens - lanes * ALIGN
    if left < 0:
        return {}
    if prompt and left > 0:
        # the last lane prefills what is left of the buffer instead
        q_len[-1] = min(left + ALIGN, width * PAGE // 2)
    seq = np.full(tokens, -1, np.int32)
    off = np.zeros(tokens, np.int32)
    for b in range(lanes):
        seq[q_start[b]: q_start[b] + q_len[b]] = b
        off[q_start[b]: q_start[b] + q_len[b]] = np.arange(q_len[b])
    kv_start = rng.randint(0, width * PAGE // 2, lanes).astype(np.int32)
    k = jax.random.normal(jax.random.PRNGKey(1), (tokens, nkv, d), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (tokens, nkv, d), jnp.bfloat16)
    args = (k, v, _table(rng, lanes, width, num_pages), jnp.asarray(seq),
            jnp.asarray(off), jnp.asarray(q_start), jnp.asarray(q_len),
            jnp.asarray(kv_start))
    loops = {"kernel": _packed_loop(True, width),
             "scatter": _packed_loop(False, width)}
    row = {"family": family, "form": "packed", "lanes": lanes, "nkv": nkv,
           "d": d, "cache_pages": num_pages, "tokens": tokens,
           "real_tokens": int(q_len.sum()),
           "mix": "prompt" if prompt else "decode-only",
           "rows": tokens * 2 * nkv}
    return _measure(loops, args, (num_pages, 2, nkv, PAGE, d), budget_s, row)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--families", default=",".join(FAMILIES))
    ap.add_argument("--tokens", default=",".join(map(str, TOKENS)))
    ap.add_argument("--budget_s", type=float, default=1.5,
                    help="seconds of repeats per (shape, path)")
    ap.add_argument("--out", default="chiprun_out/kv_write_crossover.json")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"this measures the device; found {dev.platform}")
    rows = []
    print(f"device: {dev.device_kind}, jax {jax.__version__}", flush=True)
    print("| family (lanes, kv heads x head) | form | T (real) | rows "
          "| kernel us | scatter us | scatter/kernel | equal |")
    print("|---|---|---|---|---|---|---|---|")

    def emit(r):
        if not r:
            return
        rows.append(r)
        form = r["form"] + (" " + r["mix"] if "mix" in r else "")
        t = f"{r['tokens']} ({r['real_tokens']})" if "tokens" in r else "-"
        print(f"| {r['family']} ({r['lanes']}, {r['nkv']}x{r['d']}) | {form} "
              f"| {t} | {r['rows']} "
              f"| {r['kernel_us']:.1f} (±{r['kernel_spread']:.1%}) "
              f"| {r['scatter_us']:.1f} (±{r['scatter_spread']:.1%}) "
              f"| {r['scatter_us'] / r['kernel_us']:.2f} "
              f"| {r['equal_but_null_page']} |", flush=True)
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump({"device": dev.device_kind, "jax": jax.__version__,
                       "page": PAGE, "rows": rows}, f, indent=1)

    for family in a.families.split(","):
        emit(measure_decode(family, a.budget_s))
        for tokens in map(int, a.tokens.split(",")):
            for prompt in (True, False):
                emit(measure_packed(family, tokens, prompt, a.budget_s))


if __name__ == "__main__":
    main()
