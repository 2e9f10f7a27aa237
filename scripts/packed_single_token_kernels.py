#!/usr/bin/env python3
"""The packed step's attention per call on the device, by form.

The measurement behind `ops/attention.ragged_attention_path`'s
`pallas_ragged+decode` and the rows in docs/kernels.md ("The packed step's
single-token lanes"), at two cells' shapes:

- `qwen3-4b.decode-sat`: T = 512, 48 lanes, 32 query / 8 K/V heads of 128,
  16-token pages, 40-page tables, contexts of 250-450 tokens;
- `ouro-2.6b.eval-sat`: T = 128, 12 lanes, 16 / 16 heads of 128, 24-page
  tables, contexts of 100-300 tokens;

each as a decode-only packed step (every lane one token) and with one lane
bringing a 96-token prompt chunk instead.  Timed per call, in a loop inside
one program, less the loop's own cost: the ragged kernel over every slice
(`ragged`, the packed step before PR 46), the decode kernel over the lanes
(`decode`), the ragged kernel with the single-token lanes' `q_len` zeroed
(`ragged_chunks`), the split as the program traces it (`split`), and the
split with its rows put back two other ways (`split_onehot`: a one-hot
matmul added to the ragged rows; `split_select`: a gather by token and a
select).

Run it on the chip (it refuses any other backend unless --cpu, which only
rehearses the control flow at a small size in interpret mode).  Results go
to stdout and to chiprun_out/packed_single_token_kernels.json.
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

from kserve_tpu.ops import attention as att
from kserve_tpu.ops import pallas_paged_attention as pk


def per_call(fn, args, n_lo, n_hi):
    def timed(n):
        jax.block_until_ready(fn(n, *args))
        out = []
        for _ in range(7):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(n, *args))
            out.append(time.perf_counter() - t0)
        return statistics.median(out)

    return (timed(n_hi) - timed(n_lo)) / (n_hi - n_lo)


def case(T, lanes, nq, nkv, d, ps, W, contexts, chunk, seed=0):
    """Lane b's slice at 8 * b (one token at `contexts[b]`); `chunk` > 0:
    the last lane brings that many tokens of a new prompt instead."""
    key = jax.random.split(jax.random.PRNGKey(seed), 2)
    q = jax.random.normal(key[0], (T, nq, d), jnp.bfloat16)
    pages = jax.random.normal(
        key[1], (1 + lanes * W, 2, nkv, ps, d), jnp.bfloat16)
    q_start = 8 * np.arange(lanes, dtype=np.int32)
    q_len = np.ones(lanes, np.int32)
    kv_start = np.asarray(contexts, np.int32).copy()
    if chunk:
        q_len[-1], kv_start[-1] = chunk, 0
    held = -(-(kv_start + q_len) // ps)
    table = 1 + np.arange(lanes)[:, None] * W + np.arange(W)[None, :]
    table = np.where(np.arange(W)[None, :] < held[:, None], table, 0)
    return (q, pages, jnp.asarray(table, jnp.int32), jnp.asarray(q_start),
            jnp.asarray(q_len), jnp.asarray(kv_start))


def forms(interpret):
    def ragged(q, kv, pt, qs, ql, ks):
        return pk.ragged_paged_attention_pallas(
            q, kv, pt, qs, ql, ks, interpret=interpret)

    def decoded(q, kv, pt, qs, ql, ks):
        single = ql == 1
        return single, pk.paged_attention_pallas(
            q[jnp.where(single, qs, 0)], kv, pt,
            jnp.where(single, ks + 1, 0), interpret=interpret)

    def decode(q, *a):
        _, out = decoded(q, *a)
        return q.at[:out.shape[0]].set(out)

    def ragged_chunks(q, kv, pt, qs, ql, ks):
        return ragged(q, kv, pt, qs, jnp.where(ql == 1, 0, ql), ks)

    def split(q, kv, pt, qs, ql, ks):
        return pk.ragged_single_token_split_pallas(
            q, kv, pt, qs, ql, ks, interpret=interpret)

    def parts(*a):
        return *decoded(*a), ragged_chunks(*a)

    def split_onehot(q, kv, pt, qs, ql, ks):
        single, decoded, chunks = parts(q, kv, pt, qs, ql, ks)
        T = q.shape[0]
        onehot = (single[None, :] & (
            qs[None, :] == jnp.arange(T)[:, None])).astype(q.dtype)
        back = jnp.einsum("tb,bnd->tnd", onehot, decoded,
                          preferred_element_type=jnp.float32)
        return chunks + back.astype(q.dtype)

    def split_select(q, kv, pt, qs, ql, ks):
        single, decoded, chunks = parts(q, kv, pt, qs, ql, ks)
        token_seq, _, valid = att.ragged_token_metadata(qs, ql, q.shape[0])
        seq = jnp.maximum(token_seq, 0)
        return jnp.where((valid & single[seq])[:, None, None],
                         decoded[seq], chunks)

    def nothing(q, kv, pt, qs, ql, ks):
        return q

    return {"nothing": nothing, "ragged": ragged, "decode": decode,
            "ragged_chunks": ragged_chunks, "split": split,
            "split_onehot": split_onehot, "split_select": split_select}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if jax.default_backend() != "tpu" and not args.cpu:
        print("not on a TPU (use --cpu to rehearse)", file=sys.stderr)
        return 2
    rng = np.random.RandomState(0)
    if args.cpu:
        cells = {"tiny": (32, 4, 4, 2, 16, 8, 8, rng.randint(5, 40, 4), 9)}
        n_lo, n_hi = 1, 2
    else:
        cells = {
            "qwen3-4b.decode-sat": (
                512, 48, 32, 8, 128, 16, 40, rng.randint(250, 450, 48), 96),
            "ouro-2.6b.eval-sat": (
                128, 12, 16, 16, 128, 16, 24, rng.randint(100, 300, 12), 96),
        }
        n_lo, n_hi = 40, 440
    rows = []
    for cell, (*sizes, contexts, chunk) in cells.items():
        for traffic, c in (("decode_only", 0), ("with_chunk", chunk)):
            arrays = case(*sizes, contexts, c)
            want = None
            base = None
            for name, form in forms(args.cpu).items():
                def loop(n, q, *a, form=form):
                    # a call's rows are the next call's queries: every
                    # row of every form is used, nothing can be cut away
                    return jax.lax.fori_loop(
                        0, n, lambda _, q: form(q, *a), q)

                fn = jax.jit(loop, static_argnums=0)
                s = per_call(fn, arrays, n_lo, n_hi)
                if name == "nothing":
                    base = s
                    continue
                got = np.asarray(jax.jit(form)(*arrays).astype(jnp.float32))
                gap = None
                if name == "ragged":
                    want = got
                elif name not in ("decode", "ragged_chunks"):
                    gap = float(np.abs(got - want).max())
                rows.append({"cell": cell, "traffic": traffic, "form": name,
                             "us": (s - base) * 1e6, "loop_us": base * 1e6,
                             "max_gap_to_ragged": gap})
                print(json.dumps(rows[-1]), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/packed_single_token_kernels.json", "w") as f:
        json.dump({"device": str(jax.devices()[0]), "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
