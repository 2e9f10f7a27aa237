#!/usr/bin/env python3
"""The delta rule's two forms by chunk and sub-block size, and a chip's
share of gated experts by how their width is stored, per call on the device.

The measurement behind `ops/delta.KDA_CHUNK` / `KDA_SUB`, behind what
`models/moe.stored_width` does to a width of 1280, and the tables in
docs/kernels.md ("Delta rule, a decay per channel"), at Solar-Open2's
published widths (64 heads of 128, a convolution of 4 taps over 24576
columns; hidden 4096, 40 of 320 gated experts of width 1280 held, 8 a
token), 48 lanes:

- `kda_step`: one decode step of one layer, 48 live lanes: us a call and
  the share of the chip's bandwidth that what it must move (state read and
  written once, inputs, output) comes to;
- `kda_ragged`: one layer's packed step at T = 512 (48 decode lanes at
  8-token alignment: the one-step form over them, no piece) and at T = 4096
  (47 decode lanes and one 3712-token chunk: 58 pieces), by chunk and
  sub-block: us a call against the larger of its bytes and its operations
  at the chip's peaks, and its result against the one-step form run token
  by token over the chunk's first 96 tokens;
- `causal_conv_ragged`: one layer's packed convolution at `[4096, 24576]`
  (47 decode lanes and one 3712-token chunk: this model's) and at
  `[2048, 6144]` (Nemotron-3-Nano's: 47 lanes and a 1664-token chunk): us a
  call against the bytes it must move (the buffer read once in bf16, the
  result written once in float32, the tails read and written once).  To
  put a parent beside a change, run `--only conv` in both trees in one call;
- `routed_experts`: the counting sort, three grouped matmuls and the way
  back at 48 tokens (a decode step: 384 pairs, an eighth of them on held
  experts) and at 4096 (a packed step), the width stored in 1280 and in
  1536 columns, against the larger of its bytes and its operations.

Run it on the chip (it refuses any other backend unless --cpu, which only
rehearses the control flow at a small size).  A call runs `n` times inside
ONE jitted loop, its result feeding the next call, and the time a call is
the slope between two `n` (scripts/decode_attention_crossover.py has the
reasoning); the convolution's calls are launched one by one instead, each
taking the tail the last one left (inside one loop the compiler would fold
whatever reads the result into the pass that makes it, and the result
would never be written).  Results go to stdout and to
chiprun_out/kda_kernels.json.
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

from kserve_tpu.models.moe import MoEConfig, route, routed_experts
from kserve_tpu.ops import delta, ssm

HBM, PEAK = 819e9, 197e12
N_LO, N_HI = 2, 6


def per_call(fn, args):
    def timed(n):
        jax.block_until_ready(fn(n, *args))
        out = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(n, *args))
            out.append(time.perf_counter() - t0)
        return statistics.median(out)

    return (timed(N_HI) - timed(N_LO)) / (N_HI - N_LO)


def inputs(T, lanes, H, d, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(key, (T, H, d), jnp.float32) for key in keys[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    return dict(
        q=q, k=k, v=jax.random.normal(keys[2], (T, H, d), jnp.float32),
        g=-jnp.exp(jax.random.uniform(keys[3], (T, H, d), jnp.float32,
                                      np.log(1e-3), np.log(0.1))),
        beta=2 * jax.nn.sigmoid(jax.random.normal(keys[4], (T, H))),
        state=jax.random.normal(keys[5], (lanes, H, d, d), jnp.float32))


def slices(T, lanes, chunk_tokens):
    """`lanes - 1` decode lanes at 8-token alignment, then one chunk."""
    q_start, q_len = np.zeros(lanes, np.int32), np.zeros(lanes, np.int32)
    decoders = lanes - 1 if chunk_tokens else lanes
    for lane in range(decoders):
        q_start[lane], q_len[lane] = 8 * lane, 1
    if chunk_tokens:
        q_start[-1], q_len[-1] = 8 * decoders, chunk_tokens
    assert 8 * decoders + chunk_tokens <= T
    return jnp.asarray(q_start), jnp.asarray(q_len), jnp.zeros(lanes, bool)


def delta_rows(args) -> list:
    if args.cpu:
        lanes, H, d, cases = 4, 2, 8, ((32, 0), (64, 40))
        forms = ((16, 4), (16, 16))
    else:
        lanes, H, d, cases = 48, 64, 128, ((512, 0), (4096, 3712))
        forms = ((64, 16), (64, 64), (32, 16), (128, 16))
    rows = []
    a = inputs(lanes, lanes, H, d)

    @jax.jit
    def step_loop(n, q, k, v, g, beta, state):
        def body(_, carry):
            state, acc = carry
            o, state = delta.kda_step(
                q, k, v, g, beta, state, jnp.ones((lanes,), bool))
            return state, acc + o.sum()
        return jax.lax.fori_loop(0, n, body, (state, jnp.float32(0)))

    s = per_call(step_loop, tuple(a[name] for name in
                                  ("q", "k", "v", "g", "beta", "state")))
    bytes_ = lanes * (2 * H * d * d * 4 + 5 * H * d * 4)
    rows.append({"form": "kda_step", "lanes": lanes, "us_per_call": 1e6 * s,
                 "least_us": 1e6 * bytes_ / HBM,
                 "share_pct": 100 * bytes_ / HBM / s})
    print(json.dumps(rows[-1]), flush=True)
    for T, chunk_tokens in cases:
        a = inputs(T, lanes, H, d, seed=T)
        q_start, q_len, fresh = slices(T, lanes, chunk_tokens)
        tokens = int(q_len.sum())
        for chunk, sub in (forms if chunk_tokens else forms[:1]):
            @jax.jit
            def loop(n, q, k, v, g, beta, state, chunk=chunk, sub=sub):
                def body(_, carry):
                    state, acc = carry
                    o, state = delta.kda_ragged(
                        q, k, v, g, beta, state, q_start, q_len, fresh,
                        chunk=chunk, sub=sub)
                    return state, acc + o.sum()
                return jax.lax.fori_loop(0, n, body, (state, jnp.float32(0)))

            s = per_call(loop, tuple(a[name] for name in
                                     ("q", "k", "v", "g", "beta", "state")))
            bytes_ = tokens * 5 * H * d * 4 + 2 * lanes * H * d * d * 4
            flops = tokens * 6 * H * d * d
            o, _ = jax.jit(delta.kda_ragged, static_argnames=("chunk", "sub"))(
                a["q"], a["k"], a["v"], a["g"], a["beta"], a["state"], q_start,
                q_len, fresh, chunk=chunk, sub=sub)
            err = None
            if chunk_tokens:
                lane, at = lanes - 1, int(q_start[-1])
                st = a["state"][lane:lane + 1]
                step = jax.jit(delta.kda_step)
                err = 0.0
                for t in range(at, at + min(96, chunk_tokens)):
                    o_t, st = step(*(a[name][t:t + 1] for name in
                                     ("q", "k", "v", "g", "beta")), st,
                                   jnp.ones((1,), bool))
                    err = max(err, float(jnp.abs(o[t] - o_t[0]).max()))
            rows.append({
                "form": "kda_ragged", "T": T, "chunk_tokens": chunk_tokens,
                "chunk": chunk, "sub": sub, "us_per_call": 1e6 * s,
                "least_us": 1e6 * max(bytes_ / HBM, flops / PEAK),
                "share_pct": 100 * max(bytes_ / HBM, flops / PEAK) / s,
                "max_abs_err_vs_steps": err})
            print(json.dumps(rows[-1]), flush=True)
    return rows


def conv_rows(args) -> list:
    if args.cpu:
        lanes, K, cases = 4, 4, ((64, 96, 40),)
    else:
        lanes, K, cases = 48, 4, ((4096, 24576, 3712), (2048, 6144, 1664))
    conv = jax.jit(ssm.causal_conv_ragged)
    rows = []
    for T, D, chunk_tokens in cases:
        x, tail, w = (
            jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)
            for key, shape in zip(
                jax.random.split(jax.random.PRNGKey(T), 3),
                ((T, D), (lanes, K - 1, D), (K, D))))
        q_start, q_len, fresh = slices(T, lanes, chunk_tokens)
        token_seq = np.full(T, -1, np.int32)
        token_off = np.zeros(T, np.int32)
        for lane, at in enumerate(np.asarray(q_start)):
            n = int(q_len[lane])
            token_seq[at:at + n], token_off[at:at + n] = lane, np.arange(n)
        rest = (w, jnp.zeros((), jnp.float32), jnp.asarray(token_seq),
                jnp.asarray(token_off), q_start, q_len, fresh)

        def timed(n):
            out = []
            for _ in range(3):
                t = tail
                t0 = time.perf_counter()
                for _ in range(n):
                    y, t = conv(x, t, *rest)
                jax.block_until_ready((y, t))
                out.append(time.perf_counter() - t0)
            return statistics.median(out)

        timed(2)
        s = (timed(12) - timed(4)) / 8
        bytes_ = T * D * (2 + 4) + 2 * lanes * (K - 1) * D * 2
        rows.append({"form": "causal_conv_ragged", "T": T, "D": D,
                     "chunk_tokens": chunk_tokens, "us_per_call": 1e6 * s,
                     "least_us": 1e6 * bytes_ / HBM,
                     "share_pct": 100 * bytes_ / HBM / s})
        print(json.dumps(rows[-1]), flush=True)
    return rows


def expert_rows(args) -> list:
    if args.cpu:
        hidden, width, stored, scored, held, k = 64, 48, (48, 128), 16, 2, 2
        token_counts, dtype = (8, 32), jnp.float32
    else:
        hidden, width, stored, scored, held, k = 4096, 1280, (1280, 1536), 320, 40, 8
        token_counts, dtype = (48, 4096), jnp.bfloat16
    cfg = MoEConfig(n_experts=scored, top_k=k, hidden_size=hidden,
                    intermediate_size=width, router="sigmoid", held=held)
    rows = []
    for columns in stored:
        keys = jax.random.split(jax.random.PRNGKey(3), 4)
        pad = columns - width

        def normal(key, shape):
            return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)

        params = {
            "router": normal(keys[0], (hidden, scored)),
            "router_bias": jnp.zeros((scored,), jnp.float32),
            "w_gate": jnp.pad(normal(keys[1], (held, hidden, width)),
                              ((0, 0), (0, 0), (0, pad))),
            "w_up": jnp.pad(normal(keys[2], (held, hidden, width)),
                            ((0, 0), (0, 0), (0, pad))),
            "w_down": jnp.pad(normal(keys[3], (held, width, hidden)),
                              ((0, 0), (0, pad), (0, 0)))}
        for tokens in token_counts:
            x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, hidden), dtype)
            w, sel = route(params, x, cfg)

            def fn(x):
                return routed_experts(
                    params, x, w, sel, scored, None, (0, held), "gated")

            counts = np.asarray(fn(x)[1])

            @jax.jit
            def loop(n, x):
                def body(_, carry):
                    x, acc = carry
                    y = fn(x)[0]
                    return x + (y * 1e-6).astype(x.dtype), acc + y.sum()
                return jax.lax.fori_loop(0, n, body, (x, jnp.float32(0)))

            s = per_call(loop, (x,))
            hit = int((counts > 0).sum())
            bytes_ = hit * 3 * hidden * width * 2
            flops = int(counts.sum()) * 6 * hidden * width
            rows.append({
                "form": "routed_experts", "stored": columns, "tokens": tokens,
                "pairs_here": int(counts.sum()), "experts_hit": hit,
                "fullest": int(counts.max()), "us_per_call": 1e6 * s,
                "least_us": 1e6 * max(bytes_ / HBM, flops / PEAK),
                "share_pct": 100 * max(bytes_ / HBM, flops / PEAK) / s})
            print(json.dumps(rows[-1]), flush=True)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--only", choices=("delta", "conv", "experts"))
    args = ap.parse_args()
    if jax.default_backend() != "tpu" and not args.cpu:
        print("this measures the chip", file=sys.stderr)
        return 1
    rows = []
    if args.only in (None, "delta"):
        rows += delta_rows(args)
    if args.only in (None, "conv"):
        rows += conv_rows(args)
    if args.only in (None, "experts"):
        rows += expert_rows(args)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kda_kernels.json", "w") as f:
        json.dump({"device": str(jax.devices()[0].device_kind), "rows": rows},
                  f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
