#!/usr/bin/env python3
"""A chip's share of ungated experts per call on the device, by how the
experts' width is stored.

The measurement behind `models/moe.stored_width` and the
table in docs/kernels.md ("A chip's share of the experts"), at
Nemotron-3-Nano's published widths (hidden 2688, 64 of 128 experts of
width 1856 held, 6 a token): `models/moe.routed_experts` (the counting
sort, the two grouped matmuls, the way back) at 48 tokens (a decode step:
288 pairs, about half of them on held experts) and at 2048 tokens (a packed
step), with the width stored in 1920 and in 2048 columns, against the
larger of its bytes (the experts hit x two matrices of the PUBLISHED width)
and its operations at the chip's peaks; and the same 48 tokens through
every held expert with the weights masked (`dense_masked`: one batched
matmul, no grouping), which is what the grouped matmul has to beat there.

At the width the program stores (the last of `stored`) the same call three
ways along `hidden` (PR 55): the tensors whole (`routed_experts`: 2688 = 21
x 128, one of the grouped matmul's k and n on 128-wide tiles in every
call), as `models/moe.device_layout` lays them out (`body_rest`: 2560 of
whole 512-tiles and the 128 left over, the same bytes), and with `hidden`
padded to the next multiple of 512 with zeros, the tokens' columns too
(`hidden_padded`: 3072, 14.3 % more bytes).  `gap_to_whole` is the largest
difference of a form's result from `routed_experts`' on the same tokens.

Run it on the chip (it refuses any other backend unless --cpu, which only
rehearses the control flow at a small size).  Results go to stdout and to
chiprun_out/expert_share_kernels.json.
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

from kserve_tpu.models.moe import (
    STORED_WIDTH_TILE,
    MoEConfig,
    device_layout,
    route,
    routed_experts,
)

HBM, PEAK = 819e9, 197e12
N_LO, N_HI = 8, 24


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


def dense_masked(params, x, weights, selected, cfg):
    """Every held expert over every token, the weights zero where the pair
    was not routed: [held, tokens, width] exists."""
    local = selected - cfg.first_expert  # [N, k]
    held = jnp.arange(cfg.n_held, dtype=jnp.int32)
    w = jnp.sum(jnp.where(local[:, :, None] == held[None, None, :],
                          weights[:, :, None], 0.0), axis=1)  # [N, held]
    up = jnp.einsum("nh,ehf->enf", x, params["w_up"])
    act = jnp.square(jax.nn.relu(up)).astype(x.dtype)
    down = jnp.einsum("enf,efh->enh", act, params["w_down"],
                      preferred_element_type=jnp.float32)
    return jnp.einsum("enh,ne->nh", down, w)


def stored_in(columns: int, cfg: MoEConfig, dtype) -> dict:
    """An expert layer's routed tensors with the width stored in `columns`
    columns, zeros behind it: what `moe.moe_param_shapes` makes at its own
    choice of columns, here at the ones compared."""
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    h, f, held = cfg.hidden_size, cfg.intermediate_size, cfg.n_held

    def normal(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)

    pad = columns - f
    return {
        "router": normal(keys[0], (h, cfg.n_experts)),
        "router_bias": jnp.zeros((cfg.n_experts,), jnp.float32),
        "w_up": jnp.pad(normal(keys[1], (held, h, f)), ((0, 0), (0, 0), (0, pad))),
        "w_down": jnp.pad(normal(keys[2], (held, f, h)), ((0, 0), (0, pad), (0, 0))),
    }


def hidden_padded(params: dict, hidden: int) -> dict:
    """The routed tensors with `hidden` padded to the next multiple of 512,
    zero rows (w_up) and zero columns (w_down) behind it."""
    pad = -hidden % STORED_WIDTH_TILE
    return dict(
        params,
        w_up=jnp.pad(params["w_up"], ((0, 0), (0, pad), (0, 0))),
        w_down=jnp.pad(params["w_down"], ((0, 0), (0, 0), (0, pad))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if jax.default_backend() != "tpu" and not args.cpu:
        print("this measures the chip", file=sys.stderr)
        return 1
    if args.cpu:
        hidden, width, stored, scored, held, k = 640, 48, (128,), 8, 4, 2
        token_counts = (8, 32)
        dtype = jnp.float32  # the CPU has no bf16 x bf16 = f32 product
    else:
        dtype = jnp.bfloat16
        hidden, width, stored, scored, held, k = 2688, 1856, (1920, 2048), 128, 64, 6
        token_counts = (48, 2048)
    rows = []
    cfg = MoEConfig(
        n_experts=scored, top_k=k, hidden_size=hidden, intermediate_size=width,
        router="sigmoid", scale=2.5, form="relu2", held=held)
    for columns in stored:
        params = stored_in(columns, cfg, dtype)
        for tokens in token_counts:
            x = jax.random.normal(
                jax.random.PRNGKey(tokens), (tokens, hidden), dtype)
            w, sel = route(params, x, cfg)
            _, counts = routed_experts(
                params, x, w, sel, cfg.n_experts, None, (0, held), "relu2")
            counts = np.asarray(counts)
            def whole(params, x):
                return routed_experts(
                    params, x, w, sel, cfg.n_experts, None, (0, held), "relu2")[0]

            # name -> (the call, the tensors it is given as ARGUMENTS: closed
            # over, each program would carry its 1.4 GB as constants)
            forms = {"routed_experts": (whole, params)}
            if tokens <= 64:
                forms["dense_masked"] = (
                    lambda params, x: dense_masked(params, x, w, sel, cfg),
                    params)
            if columns == stored[-1]:
                forms["body_rest"] = (whole, device_layout(params))
                forms["hidden_padded"] = (
                    lambda wide, x: whole(wide, jnp.pad(x, (
                        (0, 0), (0, wide["w_up"].shape[1] - hidden))))[:, :hidden],
                    hidden_padded(params, hidden))
            expected = jax.jit(whole)(params, x)
            for name, (fn, tensors) in forms.items():
                @jax.jit
                def loop(n, x, tensors, fn=fn):
                    def body(_, carry):
                        x, acc = carry
                        y = fn(tensors, x)
                        return x + (y * 1e-6).astype(x.dtype), acc + y.sum()
                    return jax.lax.fori_loop(0, n, body, (x, jnp.float32(0)))

                s = per_call(loop, (x, tensors))
                hit = int((counts > 0).sum())
                bytes_ = hit * 2 * hidden * width * 2
                flops = int(counts.sum()) * 4 * hidden * width
                row = {"form": name, "stored": columns, "tokens": tokens,
                       "pairs_here": int(counts.sum()), "experts_hit": hit,
                       "fullest": int(counts.max()), "us_per_call": 1e6 * s,
                       "least_us": 1e6 * max(bytes_ / HBM, flops / PEAK),
                       "bound": "bytes" if bytes_ / HBM > flops / PEAK else "flops"}
                row["share_pct"] = 100.0 * row["least_us"] / row["us_per_call"]
                row["gap_to_whole"] = float(
                    jnp.max(jnp.abs(jax.jit(fn)(tensors, x) - expected)))
                rows.append(row)
                print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/expert_share_kernels.json", "w") as f:
        json.dump({"device": str(jax.devices()[0].device_kind), "rows": rows},
                  f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
