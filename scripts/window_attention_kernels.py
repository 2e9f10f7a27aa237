#!/usr/bin/env python3
"""The packed step's window attention kernel per call on the device.

The measurement behind `ops/pallas_paged_attention.WINDOW_BQ` and the rows
in docs/kernels.md ("Window rings with rotary"), at Command A+'s published
sizes (128 query / 8 K/V heads of 128, rings of 64 pages of 64 tokens, 32
lanes): `window_attention_ragged_pallas` over (a) one lane's 3840-token
chunk that continues a 4096-token context beside 32 lanes' one-token
slices whose rings are full (the cell's largest dispatch), (b) the 32
one-token slices alone (a decode-only dispatch's packed step), (c) a new
request's 1024-token chunk (no ring to read), each against the larger of
its operations ((query, key) pairs inside the window x 4 x heads x head
size) and its bytes (ring tokens the slice sees and its own, queries in,
outputs out) at the chip's peaks; `--bq` tries other query-block sizes.

Run it on the chip (it refuses any other backend unless --cpu, which only
rehearses the control flow at a small size in interpret mode).  Results go
to stdout and to chiprun_out/window_attention_kernels.json.
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

from kserve_tpu.ops import pallas_paged_attention as pk

HBM, PEAK = 819e9, 197e12
N_LO, N_HI = 2, 6


def per_call(fn, args):
    def timed(n):
        jax.block_until_ready(fn(n, *args))
        out = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(n, *args))
            out.append(time.perf_counter() - t0)
        return statistics.median(out)

    return (timed(N_HI) - timed(N_LO)) / (N_HI - N_LO)


def case(slices, T, lanes, sizes, seed=0):
    """Arrays for `slices`: [(lane, start in the buffer, length, kv_start)]."""
    nq, nkv, d, ps, Wr = sizes
    key = jax.random.split(jax.random.PRNGKey(seed), 4)
    bf = jnp.bfloat16
    q = jax.random.normal(key[0], (T, nq, d), bf)
    k = jax.random.normal(key[1], (T, nkv, d), bf)
    v = jax.random.normal(key[2], (T, nkv, d), bf)
    ring = jax.random.normal(key[3], (1 + lanes * Wr, 2, nkv, ps, d), bf)
    table = 1 + np.arange(lanes)[:, None] * Wr + np.arange(Wr)[None, :]
    q_start, q_len, kv_start = (np.zeros(lanes, np.int32) for _ in range(3))
    pairs = keys = queries = 0
    R = Wr * ps
    for lane, start, n, kv0 in slices:
        q_start[lane], q_len[lane], kv_start[lane] = start, n, kv0
        under = int(np.clip(R - kv0, 0, n))
        pairs += under * kv0 + under * (under + 1) // 2 + (n - under) * R
        keys += min(kv0, R - 1) + n
        queries += n
    least = max(pairs * nq * d * 4 / PEAK,
                (keys * 2 * nkv * d * 2 + queries * 2 * nq * d * 2) / HBM)
    arrays = (q, k, v, ring, jnp.asarray(table, jnp.int32),
              jnp.asarray(q_start), jnp.asarray(q_len), jnp.asarray(kv_start))
    return arrays, least, {"pairs": pairs, "keys": keys, "queries": queries}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--bq", default="32", type=lambda s: [int(x) for x in s.split(",")])
    args = ap.parse_args()
    if jax.default_backend() != "tpu" and not args.cpu:
        print("not on a TPU (use --cpu to rehearse)", file=sys.stderr)
        return 2
    if args.cpu:
        sizes, lanes, window, chunk = (8, 2, 16, 4, 4), 4, 16, 24
    else:
        sizes, lanes, window, chunk = (128, 8, 128, 64, 64), 32, 4096, 3840
    decode = [(b, 8 * b, 1, window + 17 * b) for b in range(lanes)]
    cases = {
        "chunk beside decode lanes": (
            decode[:-1] + [(lanes - 1, 8 * (lanes - 1), chunk, window)],
            8 * (lanes - 1) + chunk),
        "decode lanes alone": (decode, 8 * lanes),
        "a new request's chunk": ([(0, 0, chunk // 4 + 64, 0)], chunk // 4 + 64),
    }
    rows = []
    for bq in args.bq:
        pk.WINDOW_BQ = bq
        # the entry point is jitted and read WINDOW_BQ when it was traced
        pk.window_attention_ragged_pallas.clear_cache()
        for name, (slices, T) in cases.items():
            T = -(-T // 32) * 32
            arrays, least, work = case(slices, T, lanes, sizes)

            def loop(n, *a):
                def body(_, acc):
                    out = pk.window_attention_ragged_pallas(
                        a[0] + acc[:1, :1, :1].astype(a[0].dtype) * 0, *a[1:],
                        sizes[2] ** -0.5, interpret=args.cpu)
                    return out.astype(jnp.float32)
                return jax.lax.fori_loop(
                    0, n, body, jnp.zeros(a[0].shape, jnp.float32))

            fn = jax.jit(loop, static_argnums=0)
            s = per_call(fn, arrays)
            rows.append({"bq": bq, "case": name, "T": T, "us": s * 1e6,
                         "least_us": least * 1e6,
                         "roofline_pct": 100 * least / s, **work})
            print(json.dumps(rows[-1]), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/window_attention_kernels.json", "w") as f:
        json.dump({"device": str(jax.devices()[0]), "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
