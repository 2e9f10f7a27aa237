#!/usr/bin/env python3
"""The latent-attention kernels by page size, and the grouped experts by
rows an expert, per call on the device.

The measurement behind `page_size` in benchmark/configs/glm47-flash.json
and the tables in docs/kernels.md ("Latent pages", "Grouped experts"), at
GLM-4.7-Flash's published widths (20 heads over a row of 576 values stored
in 640 columns; 64 experts of 2048 x 1536, 4 a token):

- `latent_attention_decode`: 48 lanes at contexts spread over 1024-3200
  tokens, by page size: us a call and the share of the chip's bandwidth
  its rows' bytes come to; its result against the XLA reference's;
- `latent_attention_ragged`: one 1664-token chunk behind a 896-token
  prefix beside 47 decode lanes (T = 2048), by page size;
- `routed_experts` (models/moe.py: the counting sort, three grouped
  matmuls, the way back) at 48 tokens (3 rows an expert) and at 512, 1024
  and 2048 tokens (32-128 rows an expert): us a call against the larger of
  its bytes and its FLOPs at the chip's peaks.

Run it on the chip (it refuses any other backend).  A call runs `n` times
inside ONE jitted loop, its output feeding the next call's input, and the
time a call is the slope between two `n` (scripts/decode_attention_crossover.py
has the reasoning).  Results go to stdout as markdown tables and to
chiprun_out/latent_expert_kernels.json.
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

from kserve_tpu.models.moe import MoEConfig, init_moe_params, route, routed_experts
from kserve_tpu.ops.attention import latent_paged_attention, latent_ragged_attention

LANES, HEADS, ROW, RANK = 48, 20, 640, 512
HBM, PEAK = 819e9, 197e12
N_LO, N_HI = 24, 72
SCALE = 1.0 / 16.0
USE_PALLAS = True  # False only to rehearse the script's control flow on a CPU


def per_call(fn, args):
    """Seconds a call, from the slope between N_LO and N_HI calls a jit."""
    def timed(n):
        fn(n, *args).block_until_ready()
        out = []
        for _ in range(7):
            t0 = time.perf_counter()
            fn(n, *args).block_until_ready()
            out.append(time.perf_counter() - t0)
        return statistics.median(out)

    return (timed(N_HI) - timed(N_LO)) / (N_HI - N_LO)


def decode_rows(page_sizes, pool_tokens):
    rows = []
    rng = np.random.RandomState(0)
    lens = rng.permutation(np.linspace(1024, 3200, LANES).astype(np.int32))
    for ps in page_sizes:
        pages_n, width = pool_tokens // ps, -(-3200 // ps)
        pages = jax.random.normal(
            jax.random.PRNGKey(ps), (pages_n, 1, 1, ps, ROW), jnp.bfloat16)
        q = jax.random.normal(
            jax.random.PRNGKey(1), (LANES, HEADS, ROW), jnp.bfloat16)
        table = jnp.asarray(rng.permutation(np.arange(1, pages_n))[
            :LANES * width].reshape(LANES, width), jnp.int32)
        seq = jnp.asarray(lens)
        row = {"page_size": ps, "width": width, "context_mean": float(lens.mean())}

        def run(n, q, pages, table, seq):
            def body(i, q):
                t = 1 + (table - 1 + i) % (pages_n - 1)
                o = latent_paged_attention(
                    q, pages, t, seq, SCALE, RANK, use_pallas=USE_PALLAS)
                return q.at[..., :RANK].set(o)
            return jax.lax.fori_loop(0, n, body, q)

        # against the XLA reference on the same pages, once (a correctness
        # path: it copies the pool to make its K/V view)
        kernel, gather = (jax.jit(
            lambda q, p, t, s, pallas=pallas: latent_paged_attention(
                q, p, t, s, SCALE, RANK, use_pallas=pallas))(
                    q, pages, table, seq) for pallas in (USE_PALLAS, False))
        row["kernel_us"] = 1e6 * per_call(jax.jit(run), (q, pages, table, seq))
        row["max_abs_diff"] = float(jnp.max(jnp.abs(
            kernel.astype(jnp.float32) - gather.astype(jnp.float32))))
        read = float(lens.sum()) * ROW * 2
        row["roofline_pct"] = 100.0 * read / HBM / (row["kernel_us"] * 1e-6)
        rows.append(row)
        print(f"| {ps} | {width} | {row['kernel_us']:.1f} "
              f"| {row['roofline_pct']:.1f} | {row['max_abs_diff']:.4f} |", flush=True)
    return rows


def ragged_rows(page_sizes, pool_tokens):
    rows = []
    rng = np.random.RandomState(1)
    T, chunk, prefix = 2048, 1664, 896
    for ps in page_sizes:
        pages_n, width = pool_tokens // ps, -(-3200 // ps)
        pages = jax.random.normal(
            jax.random.PRNGKey(ps), (pages_n, 1, 1, ps, ROW), jnp.bfloat16)
        q = jax.random.normal(jax.random.PRNGKey(2), (T, HEADS, ROW), jnp.bfloat16)
        table = jnp.asarray(rng.permutation(np.arange(1, pages_n))[
            :LANES * width].reshape(LANES, width), jnp.int32)
        lens = np.linspace(1024, 3100, LANES).astype(np.int32)
        q_start = np.arange(LANES, dtype=np.int32) * 8
        q_len = np.ones(LANES, np.int32)
        kv_start = lens.copy()
        q_start[-1], q_len[-1], kv_start[-1] = (LANES - 1) * 8, chunk, prefix
        args = (q, pages, table, jnp.asarray(q_start), jnp.asarray(q_len),
                jnp.asarray(kv_start))

        def run(n, q, pages, table, qs, ql, ks):
            def body(i, q):
                t = 1 + (table - 1 + i) % (pages_n - 1)
                o = latent_ragged_attention(
                    q, pages, t, qs, ql, ks, SCALE, RANK, use_pallas=USE_PALLAS)
                return q.at[..., :RANK].set(o)
            return jax.lax.fori_loop(0, n, body, q)

        us = 1e6 * per_call(jax.jit(run), args)
        # causal: query j of the chunk sees prefix + j + 1 rows
        pairs = chunk * prefix + chunk * (chunk + 1) / 2 + float(lens[:-1].sum())
        flops = pairs * HEADS * (ROW + RANK) * 2
        rows.append({"page_size": ps, "us": us, "tflops": flops / us / 1e6})
        print(f"| {ps} | {us:.0f} | {flops / 1e9:.1f} | {flops / us / 1e6:.1f} |",
              flush=True)
    return rows


def expert_rows(token_counts):
    cfg = MoEConfig(n_experts=64, top_k=4, hidden_size=2048,
                    intermediate_size=1536, router="sigmoid", scale=1.8)
    params = init_moe_params(cfg, jax.random.PRNGKey(3), dtype=jnp.bfloat16)
    rows = []
    for tokens in token_counts:
        x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, 2048), jnp.bfloat16)

        def one(params, x):
            w, sel = route(params, x, cfg)
            out, counts = routed_experts(params, x, w, sel, cfg.n_experts)
            return out.astype(x.dtype), counts

        def run(n, params, x):
            def body(i, x):
                # the next call's tokens differ: keep their scale, add the output
                return (x + 0.01 * one(params, x)[0]).astype(x.dtype)
            return jax.lax.fori_loop(0, n, body, x)

        us = 1e6 * per_call(jax.jit(run), (params, x))
        counts = np.asarray(jax.jit(one)(params, x)[1])
        hits = int((counts > 0).sum())
        least = max(hits * 3 * 2048 * 1536 * 2 / HBM,
                    tokens * 4 * 6 * 2048 * 1536 / PEAK)
        rows.append({"tokens": tokens, "us": us, "experts_hit": hits,
                     "rows_max": int(counts.max()),
                     "roofline_pct": 100.0 * least / (us * 1e-6)})
        print(f"| {tokens} | {tokens * 4 / 64:.0f} | {hits} | {int(counts.max())} "
              f"| {us:.0f} | {least * 1e6:.0f} | {rows[-1]['roofline_pct']:.1f} |",
              flush=True)
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--page_sizes", default="16,32,64,128")
    ap.add_argument("--pool_tokens", type=int, default=294400)
    ap.add_argument("--out", default="chiprun_out/latent_expert_kernels.json")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"this measures the device; found {dev.platform}")
    page_sizes = [int(p) for p in a.page_sizes.split(",")]
    print(f"device: {dev.device_kind}, jax {jax.__version__}", flush=True)
    result = {"device": dev.device_kind, "jax": jax.__version__}
    print("\n| page size | W | latent_attention_decode us "
          "| share of 819 GB/s % | max abs diff to XLA |\n|---|---|---|---|---|")
    result["decode"] = decode_rows(page_sizes, a.pool_tokens)
    print("\n| page size | latent_attention_ragged us (T = 2048) | GFLOP "
          "| TFLOP/s |\n|---|---|---|---|")
    result["ragged"] = ragged_rows(page_sizes, a.pool_tokens)
    print("\n| tokens | rows an expert | experts hit | fullest | routed_experts us "
          "| least us at the peaks | share of roofline % |\n|---|---|---|---|---|---|---|")
    result["experts"] = expert_rows([48, 512, 1024, 2048])
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
