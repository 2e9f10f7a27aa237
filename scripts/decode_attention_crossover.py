#!/usr/bin/env python3
"""Decode attention, kernel against gather, per call on the device.

The measurement behind `ops/attention._should_use_pallas` and the table in
docs/kernels.md ("Kernel against gather"): `paged_attention` jitted with
`use_pallas=True` and with `use_pallas=False`, bf16 pages of 16 tokens, at
the page-table widths and head shapes the served configurations compile.
Run it on the chip (it refuses any other backend: a CPU time says nothing
about either path):

    python3 scripts/decode_attention_crossover.py            # every family
    python3 scripts/decode_attention_crossover.py --families qwen3-4b

How a call is timed.  One call lasts 0.05-3 ms, the same order as a jit
dispatch from the host, so the call runs `n` times inside ONE jitted
while-loop (the output feeds the next query; the page table is rotated by
the loop counter so XLA cannot hoist the gather out of the loop) and the
time per call is the slope between n = 72 and n = 216: the fixed cost of a
dispatch cancels.  Median over repeats, with the repeats' quartile spread
beside it.  Lengths: `aged` spreads the lanes evenly over 10-100 % of the
table's capacity, shuffled (what lanes look like in a saturated server:
mean 55 %); `full` puts every lane at the capacity.

Results go to stdout as a markdown table and to
chiprun_out/decode_attention_crossover.json.
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

from kserve_tpu.ops.attention import _should_use_pallas, paged_attention

PAGE = 16
WIDTHS = (8, 16, 32, 40, 64, 128)
#: name -> (lanes, query heads, KV heads, head size) as ONE device sees them
FAMILIES = {
    "qwen3-4b": (48, 32, 8, 128),  # the benchmark's cells
    "qwen3-4b/b8": (8, 32, 8, 128),  # one grid block
    "qwen3-4b/b16": (16, 32, 8, 128),
    "llama3-8b/tp2": (48, 16, 4, 128),  # one shard of two
    "mistral-7b/tp4": (48, 8, 2, 128),  # one shard of four
    "mistral-7b/tp4/b8": (8, 8, 2, 128),
    "llama3-8b/tp8": (48, 4, 1, 128),  # one shard of eight
    # Phi-4-mini-flash: 20 K/V heads of 64 stored as 10 rows of 128 (a
    # differential pair's heads side by side)
    "phi4-mini-flash": (48, 40, 10, 128),
    # Ouro-2.6B: 12 lanes = two blocks of six, 128 KB pages
    "ouro-2.6b": (12, 16, 16, 128),
}
N_LO, N_HI = 72, 216


def _lens(kind: str, lanes: int, width: int, rng) -> np.ndarray:
    cap = width * PAGE
    if kind == "full":
        return np.full((lanes,), cap, np.int32)
    spread = np.linspace(0.1, 1.0, lanes) * cap
    return rng.permutation(np.maximum(spread.astype(np.int32), 1))


def _looped(use_pallas: bool, num_pages: int):
    def run(n, q, kv, table, lens):
        def body(i, q):
            # another set of pages each iteration: nothing is loop-invariant
            t = 1 + (table - 1 + i) % (num_pages - 1)
            return paged_attention(q, kv, t, lens, use_pallas=use_pallas)

        return jax.lax.fori_loop(0, n, body, q)

    return jax.jit(run)


def _time(fn, n, args, budget_s: float):
    fn(n, *args).block_until_ready()
    t0 = time.perf_counter()
    fn(n, *args).block_until_ready()
    once = time.perf_counter() - t0
    reps = int(min(40, max(9, budget_s / max(once, 1e-4))))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(n, *args).block_until_ready()
        out.append(time.perf_counter() - t0)
    return out


def measure(family: str, width: int, budget_s: float,
            cache_pages: int = 2300) -> dict:
    lanes, nq, nkv, d = FAMILIES[family]
    rng = np.random.RandomState(width * 1000 + lanes)
    num_pages = max(cache_pages, lanes * width + 1)
    key = jax.random.PRNGKey(width)
    kv = jax.random.normal(
        key, (num_pages, 2, nkv, PAGE, d), jnp.bfloat16)
    q = jax.random.normal(jax.random.PRNGKey(1), (lanes, nq, d), jnp.bfloat16)
    table = jnp.asarray(
        rng.permutation(np.arange(1, num_pages))[: lanes * width]
        .reshape(lanes, width), jnp.int32)
    row = {"family": family, "lanes": lanes, "nq": nq, "nkv": nkv, "d": d,
           "width": width, "cache_pages": num_pages, "auto": _should_use_pallas(
               d, False, width, lanes, jax.default_backend(), PAGE, nkv)}
    fns = {"kernel": _looped(True, num_pages),
           "gather": _looped(False, num_pages)}
    for kind in ("aged", "full"):
        lens = jnp.asarray(_lens(kind, lanes, width, rng))
        one = {
            path: jax.jit(
                lambda q, kv, t, s, p=(path == "kernel"): paged_attention(
                    q, kv, t, s, use_pallas=p))(q, kv, table, lens)
            for path in fns}
        row[f"{kind}.max_abs_diff"] = float(jnp.max(jnp.abs(
            one["kernel"].astype(jnp.float32)
            - one["gather"].astype(jnp.float32))))
        for path, fn in fns.items():
            args = (q, kv, table, lens)
            lo = _time(fn, N_LO, args, budget_s / 4)
            hi = _time(fn, N_HI, args, budget_s * 3 / 4)
            per_call = [
                (h - statistics.median(lo)) / (N_HI - N_LO) for h in hi]
            q1, _, q3 = statistics.quantiles(per_call, n=4)
            med = statistics.median(per_call)
            row[f"{kind}.{path}_us"] = med * 1e6
            row[f"{kind}.{path}_spread"] = (q3 - q1) / med
    return row


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--families", default=",".join(FAMILIES))
    ap.add_argument("--widths", default=",".join(map(str, WIDTHS)))
    ap.add_argument("--budget_s", type=float, default=2.0,
                    help="seconds of repeats per (shape, path, lengths)")
    ap.add_argument("--cache_pages", type=int, default=2300,
                    help="pages in the cache array (one layer's); the "
                    "benchmark's cells hold 2300")
    ap.add_argument("--out", default="chiprun_out/decode_attention_crossover.json")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"this measures the device; found {dev.platform}")
    rows = []
    print(f"device: {dev.device_kind}, jax {jax.__version__}", flush=True)
    print("| family (lanes, q/kv x head) | W | aged kernel us | aged gather us "
          "| gather/kernel | full kernel us | full gather us | gather/kernel "
          "| max abs diff | auto |")
    print("|---|---|---|---|---|---|---|---|---|---|")
    for family in a.families.split(","):
        lanes, nq, nkv, d = FAMILIES[family]
        for width in map(int, a.widths.split(",")):
            r = measure(family, width, a.budget_s, a.cache_pages)
            rows.append(r)
            print(
                f"| {family} ({lanes}, {nq}/{nkv}x{d}) | {width} "
                f"| {r['aged.kernel_us']:.1f} (±{r['aged.kernel_spread']:.1%}) "
                f"| {r['aged.gather_us']:.1f} (±{r['aged.gather_spread']:.1%}) "
                f"| {r['aged.gather_us'] / r['aged.kernel_us']:.2f} "
                f"| {r['full.kernel_us']:.1f} (±{r['full.kernel_spread']:.1%}) "
                f"| {r['full.gather_us']:.1f} (±{r['full.gather_spread']:.1%}) "
                f"| {r['full.gather_us'] / r['full.kernel_us']:.2f} "
                f"| {max(r['aged.max_abs_diff'], r['full.max_abs_diff']):.4f} "
                f"| {'kernel' if r['auto'] else 'gather'} |", flush=True)
            os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
            with open(a.out, "w") as f:
                json.dump({"device": dev.device_kind, "jax": jax.__version__,
                           "page": PAGE, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
