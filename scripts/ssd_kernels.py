#!/usr/bin/env python3
"""The Mamba-2 operations by chunk size, per call on the device.

The measurement behind `ops/ssm.SSD_CHUNK` and the table in docs/kernels.md
("Mamba-2"), at Nemotron-3-Nano's published widths (64 heads of 64 in 8
groups, state 128, a convolution of 4 taps over 6144 columns), 48 lanes:

- `ssd_step` and `causal_conv_step`: one decode step of one layer, 48 live
  lanes: us a call and the share of the chip's bandwidth that what it must
  move (state and tail read and written once, inputs, output) comes to;
- `ssd_ragged` and `causal_conv_ragged`: one layer's packed scan at T = 512
  (48 decode lanes at 8-token alignment) and at T = 2048 (47 decode lanes
  and one 1664-token chunk), by chunk size and by the matrix products'
  precision: us a call, against the larger of its bytes and its operations
  at the chip's peaks, and its result against the one-step form run token
  by token over the chunk's lane.

Run it on the chip (it refuses any other backend unless --cpu, which only
rehearses the control flow at a small size).  A call runs `n` times inside
ONE jitted loop, its state feeding the next call, and the time a call is
the slope between two `n` (scripts/decode_attention_crossover.py has the
reasoning).  Results go to stdout and to chiprun_out/ssd_kernels.json.
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

from kserve_tpu.ops import ssm

HBM, PEAK = 819e9, 197e12
N_LO, N_HI = 4, 12


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


def inputs(T, lanes, H, P, G, N, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    f32 = jnp.float32
    return dict(
        x=jax.random.normal(k[0], (T, H, P), f32),
        dt=jax.nn.softplus(jax.random.normal(k[1], (T, H), f32) - 3.0),
        A=-jnp.linspace(1.0, 16.0, H, dtype=f32),
        Bm=jax.random.normal(k[2], (T, G, N), f32),
        Cm=jax.random.normal(k[3], (T, G, N), f32),
        D=jnp.ones((H,), f32),
        state=jax.random.normal(k[4], (lanes, H, P, N), f32))


def packing(T, lanes, chunk_tokens, align=8):
    """Decode lanes at `align`, then one chunk of `chunk_tokens` (0: none)."""
    seq = -np.ones(T, np.int32)
    q_start, q_len, last = (np.zeros(lanes, np.int32) for _ in range(3))
    at = 0
    decode = lanes - (1 if chunk_tokens else 0)
    for lane in range(decode):
        seq[at] = lane
        q_start[lane], q_len[lane], last[lane] = at, 1, at
        at += align
    if chunk_tokens:
        lane = lanes - 1
        seq[at:at + chunk_tokens] = lane
        q_start[lane], q_len[lane] = at, chunk_tokens
        last[lane] = at + chunk_tokens - 1
    return tuple(jnp.asarray(a) for a in (seq, q_start, q_len, last))


def step_row(lanes, H, P, G, N, K):
    a = inputs(lanes, lanes, H, P, G, N)
    conv = H * P + 2 * G * N
    tail = jnp.zeros((lanes, K - 1, conv), jnp.bfloat16)
    xbc = jax.random.normal(jax.random.PRNGKey(9), (lanes, conv), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(8), (K, conv), jnp.bfloat16)
    b = jnp.zeros((conv,), jnp.bfloat16)
    live = jnp.ones((lanes,), bool)

    @jax.jit
    def loop(n, state, tail):
        def body(_, carry):
            state, tail, acc = carry
            y_conv, tail = ssm.causal_conv_step(xbc, tail, w, b)
            xs = a["x"] + y_conv[:, :H * P].reshape(lanes, H, P) * 1e-3
            y, state = ssm.ssd_step(
                xs, a["dt"], a["A"], a["Bm"], a["Cm"], a["D"], state, live)
            return state, tail, acc + y.sum()
        return jax.lax.fori_loop(0, n, body, (state, tail, jnp.float32(0)))

    s = per_call(lambda n, st, tl: loop(n, st, tl), (a["state"], tail))
    must = lanes * (2 * H * P * N * 4 + 2 * (K - 1) * conv * 2
                    + (conv + H) * 2 + H * P * 4)
    return {"op": "ssd_step + causal_conv_step", "lanes": lanes,
            "us_per_call": 1e6 * s, "must_move_MB": must / 1e6,
            "hbm_share_pct": 100.0 * must / s / HBM}


def ragged_rows(T, lanes, chunk_tokens, chunks, H, P, G, N):
    rows = []
    a = inputs(T, lanes, H, P, G, N)
    seq, q_start, q_len, last = packing(T, lanes, chunk_tokens)
    fresh = jnp.zeros((lanes,), bool)
    tokens = int((np.asarray(seq) >= 0).sum())
    slices = int((np.asarray(q_len) > 0).sum())
    # what one lane's chunk must come to: the one-step form over it
    lane = lanes - 1
    want = None
    if chunk_tokens:
        first = int(q_start[lane])

        @jax.jit
        def sequential(state):
            def body(s, t):
                y, s = ssm.ssd_step(
                    a["x"][t][None], a["dt"][t][None], a["A"], a["Bm"][t][None],
                    a["Cm"][t][None], a["D"], s, jnp.ones((1,), bool))
                return s, y[0]
            s, ys = jax.lax.scan(
                body, state, first + jnp.arange(chunk_tokens))
            return ys, s
        want = sequential(a["state"][lane:lane + 1])
    for precision in ("highest", "default"):
        ssm._HP = (jax.lax.Precision.HIGHEST if precision == "highest"
                   else jax.lax.Precision.DEFAULT)
        for chunk in chunks:
            @jax.jit
            def loop(n, state):
                def body(_, carry):
                    state, acc = carry
                    y, state = ssm.ssd_ragged(
                        a["x"], a["dt"], a["A"], a["Bm"], a["Cm"], a["D"],
                        state, seq, q_start, q_len, last, fresh, chunk)
                    return state * 0.5, acc + y.sum()
                return jax.lax.fori_loop(0, n, body, (state, jnp.float32(0)))

            row = {"op": "ssd_ragged", "T": T, "tokens": tokens,
                   "slices": slices, "chunk": chunk, "precision": precision}
            try:
                s = per_call(loop, (a["state"],))
            except Exception as e:  # the compiler refuses a size: say so
                row["refused"] = str(e)[:200]
                rows.append(row)
                print(json.dumps(row), flush=True)
                continue
            bytes_ = (tokens * ((H * P + 2 * G * N + H) * 2 + H * P * 4)
                      + slices * 2 * H * P * N * 4)
            flops = tokens * 4 * H * P * N
            row.update(
                us_per_call=1e6 * s, must_move_MB=bytes_ / 1e6,
                roofline_pct=100.0 * max(bytes_ / HBM, flops / PEAK) / s)
            if want is not None:
                y, state = jax.jit(
                    lambda st: ssm.ssd_ragged(
                        a["x"], a["dt"], a["A"], a["Bm"], a["Cm"], a["D"], st,
                        seq, q_start, q_len, last, fresh, chunk))(a["state"])
                ys = y[first:first + chunk_tokens]
                row["max_err_y"] = float(jnp.abs(ys - want[0]).max())
                row["max_err_state"] = float(
                    jnp.abs(state[lane] - want[1][0]).max())
                row["y_scale"] = float(jnp.abs(want[0]).max())
            rows.append(row)
            print(json.dumps(row), flush=True)
    ssm._HP = jax.lax.Precision.HIGHEST
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--chunks", default="32,64,128")
    args = ap.parse_args()
    backend = jax.default_backend()
    if backend != "tpu" and not args.cpu:
        print(f"this measures the chip; the backend is {backend}", file=sys.stderr)
        return 1
    chunks = [int(c) for c in args.chunks.split(",")]
    if args.cpu:
        lanes, H, P, G, N, K = 4, 4, 8, 2, 16, 4
        shapes = [(64, 0), (128, 64)]
    else:
        lanes, H, P, G, N, K = 48, 64, 64, 8, 128, 4
        shapes = [(512, 0), (2048, 1664)]
    out = {"device": str(jax.devices()[0].device_kind), "rows": []}
    row = step_row(lanes, H, P, G, N, K)
    print(json.dumps(row), flush=True)
    out["rows"].append(row)
    for T, chunk_tokens in shapes:
        out["rows"] += ragged_rows(T, lanes, chunk_tokens, chunks, H, P, G, N)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ssd_kernels.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
