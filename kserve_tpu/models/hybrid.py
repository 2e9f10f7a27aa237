"""Decoders whose layers are not all the same layer, run from the per-layer
table of `LlamaConfig.layer_table()` (models/llama.LayerSpec).

First family: `model_type: phi4flash`, the SambaY decoder-hybrid-decoder
(arXiv:2507.06607) with differential attention (arXiv:2410.05258) and no
positional encoding.  Every layer is `h += Mixer(LN_a(h)); h +=
MLP(LN_b(h))` (LayerNorm with bias, SwiGLU MLP); the mixer is one of

- `mamba`: a Mamba-1 mixer; writes `recurrent` state (ops/ssm.py);
- `window_attention`: attention over the last `sliding_window` tokens;
  writes `window_kv`, a per-lane ring;
- `attention`: full causal attention; writes `paged_kv`, pages of the pool;
- `cross_attention`: queries only, over the K/V of the layer it `reads`;
- `gmu`: a gated memory unit over the scan output `m` of the Mamba layer
  it `reads`; holds no state.

State travels as the pytree of engine/kvcache.StateLayout: {"paged",
"window", "ssm", "conv"}, one array per writing layer.  The two entry
points mirror models/llama's: `forward_ragged` (the mixed program's packed
buffer) and `decode_step` (one token per lane), called through
`llama.forward_ragged` / `llama.decode_step`.

Differential attention with the kernels the repo has: a pair's two K heads
lie side by side in one cache row of 2 x head_dim (and its two V heads
likewise), which is a reshape of the projection's output; query head 2j is
padded to `[q, 0]` and 2j+1 to `[0, q]`, so plain grouped-query attention
over rows of twice the width gives `softmax(q_1 k_1^T) [v_1, v_2]` and
`softmax(q_2 k_2^T) [v_1, v_2]`: the pair's two terms, exactly.  The cache
holds the same bytes per token, at the width the decode kernel streams
without a re-layout.  The same rule serves any plain grouped-query row whose
heads are 64 wide (`LlamaConfig.pairs_kv_heads`: the sixth family's): K/V
heads 2r and 2r+1 share cache row r, a query head goes into the half where
ITS K/V head lies (`_pair_queries`) and keeps that half of the output
(`_own_half`): twice the score and value products, and both kernels and the
page write where 64-wide rows would take the gather.

Second family: `model_type: glm4_moe_lite`: every layer `h += Mixer(
RMSNorm(h)); h += FFN(RMSNorm(h))` with the `latent_attention` mixer
(models/latent.py: a compressed row a token in `state["latent"]`, read in
the absorbed form by both programs) and a feed-forward that is `dense` or
`experts` by the row's `ffn` (models/moe.py: routed pairs only, plus a
shared expert).  Its norms carry no bias and its head is untied: `_ln`,
`_ffn` and `_logits` choose by `config.norm_type` and by the row.

Third family: `model_type: nemotron_h`: ONE sublayer a layer, `h +=
Mixer(RMSNorm(h))` with one norm and one residual, the mixer by the row:
`mamba2` (a Mamba-2 mixer: one convolution over x, B and C, a matrix-valued
state a head in `state["ssm"]`, a gated group-wise RMSNorm behind the scan;
ops/ssm.ssd_*), `gqa_attention` (plain grouped-query attention with no
positional encoding, over the pool's pages through the kernels the Llama
path uses) or `ffn` (the row's mixer IS its
feed-forward: routed experts of the ungated `relu2` form, of which this
chip may hold a share, beside a shared one).  A row's `ffn` is `none`
where its mixer is not the feed-forward; `_close` adds what the row has.

Fourth family: `model_type: cohere2_moe` (Command A+): a PARALLEL block, `u
= LN(h); h += Mixer(u) + FFN(u)`: one bias-free LayerNorm, one residual
(`config.parallel_block`; `_close` takes the norm's output along).  The
mixer is plain grouped-query attention, `gqa_window_attention` over the last
`sliding_window` tokens with interleaved rotary (keys are turned BEFORE they
enter the lane's ring, so the ring's slot order stays free for the softmax
and the mask needs the slots' positions only) or `gqa_attention` over the
pool's pages with no positions (`config.layer_ropes`); the feed-forward is
routed experts, of which this chip may hold a share, beside several shared
ones fused into one MLP (models/moe.py).

Fifth family: `model_type: solar_open2` (Solar-Open2): the second family's
layer of two residuals with the mixer by the row: `kda`, a Kimi-delta
linear-attention mixer (one convolution over q, k and v together, a matrix a
head in `state["ssm"]` updated by the gated delta rule with a decay per
channel, a gated RMSNorm a head behind it; ops/delta.kda_*), or
`gqa_attention` with no positions whose output a sigmoid gate multiplies
(`config.attention_gate`); every row's feed-forward is routed experts, of
which this chip may hold a share, beside a shared one.

Sixth family: `model_type: lfm2_moe` (LFM2-24B-A2B): the second family's
layer of two residuals with the mixer by the row: `short_conv`, a gated
short convolution (`[B | C | x] = u W_in`; a depthwise causal convolution
of a few taps over `B * x` with no bias and no activation behind it; `(C *
conv) W_out`) whose only state is the convolution's tail in `state["conv"]`
(`state["ssm"]` holds nothing), or `gqa_attention` with an RMSNorm a head on
q and k before the rotary, heads of 64 on cache rows of 128 as above; the
first `first_k_dense` feed-forwards dense, the others routed experts with no
shared one; the head is the embedding, transposed.

Layers behind the last layer that writes state only feed the logits, so the
packed forward runs them (and the last writer's own attention output) on
the rows that are sampled, one per lane: exact, and it makes every read of
the shared cache a one-query-per-lane read.
"""

from __future__ import annotations

import math
import os
import re
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.kv_write import append_token_kv, slice_runs, write_ragged_kv
from ..ops import delta, ssm
from ..ops.attention import (
    latent_paged_attention,
    latent_ragged_attention,
    paged_attention,
    paged_attention_scaled,
    ragged_paged_attention,
    window_attention_ragged,
)
from ..ops.norms import layer_norm, rms_norm
from ..ops.rotary import apply_rope, apply_rope_interleaved
from . import latent, llama
from .moe import moe_config_of, moe_mlp, moe_param_shapes, zero_stored_padding
from .quant import dense, tied_head_matmul

Params = Dict[str, Any]


# ---------------- parameters ----------------


def layer_param_shapes(config, spec) -> Dict[str, tuple]:
    """{name: (shape, init)} of one layer, from its row of the table.
    `init`: "normal" (N(0, scale)), "ones", "bias" (N(0, scale)),
    "lambda" (N(0, 0.1), arXiv:2410.05258), "A_log", "dt_bias", "D" (the
    Mamba-1 defaults), "A_log_heads" (Mamba-2: one A a head, spread over
    [1, 16]), "A_log_kda" / "dt_bias_kda" (a Kimi-delta mixer's decays:
    `make`), "zeros" (a router's choice-only bias); float32 for those;
    "taps" (a short convolution's: 1 / taps + N(0, scale): `make`);
    "routed_out" (N(0, scale x ROUTED_OUT_GAIN): a routed expert's
    down-projection).  A row has the norm of each sublayer it has."""
    h, f = config.hidden_size, config.intermediate_size
    nq, nkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    di, n, k, r = (config.mamba_d_inner, config.mamba_d_state,
                   config.mamba_d_conv, config.mamba_dt_rank)
    norms = (["attn_norm"] if spec.kind != "ffn" else []) + (
        ["mlp_norm"] if spec.ffn != "none" and not config.parallel_block
        else [])
    shapes = {name: ((h,), "ones") for name in norms}
    if config.norm_type == "layernorm" and config.norm_bias:
        shapes.update({name + "_b": ((h,), "bias") for name in norms})
    if spec.ffn == "experts":
        inits = {"router_bias": "zeros", "w_down": "routed_out"}
        shapes.update({
            name: (shape, inits.get(name, "normal"))
            for name, shape in moe_param_shapes(moe_config_of(config)).items()})
    elif spec.ffn == "dense":
        shapes.update({"w_gate": ((h, f), "normal"), "w_up": ((h, f), "normal"),
                       "w_down": ((f, h), "normal")})
    if spec.kind == "latent_attention":
        shapes.update(latent.param_shapes(config))
    elif spec.kind in ("gqa_attention", "gqa_window_attention"):
        shapes.update({
            "wq": ((h, nq * hd), "normal"), "wk": ((h, nkv * hd), "normal"),
            "wv": ((h, nkv * hd), "normal"), "wo": ((nq * hd, h), "normal")})
        if config.attention_gate and spec.kind == "gqa_attention":
            shapes["wg"] = ((h, nq * hd), "normal")
        if config.qk_norm:
            shapes.update({"q_norm": ((hd,), "ones"), "k_norm": ((hd,), "ones")})
    elif spec.kind == "short_conv":
        shapes.update({
            "in_proj": ((h, 3 * h), "normal"),
            "conv_w": ((config.conv_taps, h), "taps"),
            "out_proj": ((h, h), "normal")})
    elif spec.kind == "kda":
        heads, d, rank = config.kda_n_heads, config.kda_head_dim, config.kda_rank
        shapes.update({
            "wqkv": ((h, config.kda_conv_dim), "normal"),
            "conv_w": ((config.kda_d_conv, config.kda_conv_dim), "normal"),
            "wf_a": ((h, rank), "normal"), "wf_b": ((rank, heads * d), "normal"),
            "A_log": ((heads,), "A_log_kda"),
            "dt_bias": ((heads * d,), "dt_bias_kda"),
            "w_beta": ((h, heads), "normal"),
            "wg_a": ((h, rank), "normal"), "wg_b": ((rank, heads * d), "normal"),
            "o_norm": ((d,), "ones"), "wo": ((heads * d, h), "normal"),
        })
    elif spec.kind == "mamba2":
        heads, conv = config.mamba_n_heads, config.mamba2_conv_dim
        shapes.update({
            "in_proj": ((h, di + conv + heads), "normal"),
            "conv_w": ((k, conv), "normal"), "conv_b": ((conv,), "bias"),
            "dt_bias": ((heads,), "dt_bias"),
            "A_log": ((heads,), "A_log_heads"), "D": ((heads,), "D"),
            "ssm_norm": ((di,), "ones"),
            "out_proj": ((di, h), "normal"),
        })
    elif spec.kind in ("attention", "window_attention", "cross_attention"):
        shapes.update({
            "wq": ((h, nq * hd), "normal"), "wo": ((nq * hd, h), "normal"),
            "lambda_q1": ((hd,), "lambda"), "lambda_k1": ((hd,), "lambda"),
            "lambda_q2": ((hd,), "lambda"), "lambda_k2": ((hd,), "lambda"),
            "subln": ((2 * hd,), "ones"),
        })
        if config.attention_bias:
            shapes["bq"] = ((nq * hd,), "bias")
        if config.attention_out_bias:
            shapes["bo"] = ((h,), "bias")
        if spec.kind != "cross_attention":
            shapes.update({"wk": ((h, nkv * hd), "normal"),
                           "wv": ((h, nkv * hd), "normal")})
            if config.attention_bias:
                shapes.update({"bk": ((nkv * hd,), "bias"),
                               "bv": ((nkv * hd,), "bias")})
    elif spec.kind == "mamba":
        shapes.update({
            "in_proj": ((h, 2 * di), "normal"),
            "conv_w": ((k, di), "normal"), "conv_b": ((di,), "bias"),
            "x_proj": ((di, r + 2 * n), "normal"),
            "dt_proj": ((r, di), "normal"), "dt_bias": ((di,), "dt_bias"),
            "A_log": ((di, n), "A_log"), "D": ((di,), "D"),
            "out_proj": ((di, h), "normal"),
        })
    elif spec.kind == "gmu":
        shapes.update({"gmu_in": ((h, di), "normal"),
                       "gmu_out": ((di, h), "normal")})
    return shapes


_F32_INITS = ("A_log", "dt_bias", "D", "router_bias")

#: Random weights only (a checkpoint's values load as they are): a routed
#: expert's down-projection starts at a tenth of the other projections'
#: scale.  A top-k router is discontinuous: in bf16 the program's router and
#: a float32 reference's choose different experts at 5-20 % of (token, expert
#: layer) decisions, whatever the scale.  With every projection at one scale
#: a single such decision moves the stream by ~10 % and the later layers of
#: random weights amplify it, so that bf16 left the float32 reference by as
#: much as int8 weights did (a served token up to 1.4 under the reference's
#: best logit at logits of deviation 0.9, against 0.01 where no decision
#: differed: PERF.md section 6, PR 37) and no tolerance told a precision
#: from a fault.  At a tenth a differing decision costs no more than the
#: rounding around it, and a routed path that computed nothing would still
#: read over the tolerance.
ROUTED_OUT_GAIN = 0.1


def init_params(config, rng, scale: float = 0.02, weight_quant: str = "none",
                shardings=None) -> Params:
    """Seeded random parameters, each layer made under jit ON its sharding
    (models/llama.init_params).  Biases and the lambda vectors are random
    too, so that a comparison with the reference exercises them."""
    if weight_quant != "none":
        raise NotImplementedError("weight_quant over a hybrid model")
    dtype = jnp.dtype(config.dtype)
    table = config.layer_table()
    keys = jax.random.split(rng, config.n_layers + 1)

    def make(shape, init, key):
        if init == "ones":
            return jnp.ones(shape, dtype)
        if init == "zeros":
            return jnp.zeros(shape, jnp.float32)
        if init == "routed_out":
            return (jax.random.normal(key, shape, jnp.float32)
                    * scale * ROUTED_OUT_GAIN).astype(dtype)
        if init == "lambda":
            return (jax.random.normal(key, shape, jnp.float32) * 0.1).astype(dtype)
        if init == "A_log":
            return jnp.log(jnp.broadcast_to(
                jnp.arange(1, shape[1] + 1, dtype=jnp.float32), shape))
        if init == "A_log_heads":
            return jnp.log(jnp.linspace(1.0, 16.0, shape[0], dtype=jnp.float32))
        if init == "D":
            return jnp.ones(shape, jnp.float32)
        if init in ("dt_bias", "dt_bias_kda"):
            # softplus(dt_bias) log-uniform in [1e-3, 1e-1]; a Kimi-delta
            # mixer's in [1e-3, 5e-2]: with A below in [0.5, 1.5] and the
            # projection's own term of deviation ~0.3 a token keeps 0.9 to
            # 0.999 of a channel, so that what a lane carries over hundreds
            # of tokens shows in its logits (a state that forgets in a few
            # tokens would hide an error in what is carried)
            top = 0.1 if init == "dt_bias" else 0.05
            dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                         * (math.log(top) - math.log(0.001)) + math.log(0.001))
            return dt + jnp.log(-jnp.expm1(-dt))
        if init == "A_log_kda":
            return jnp.log(jax.random.uniform(
                key, shape, jnp.float32, 0.5, 1.5))
        if init == "taps":
            # a short convolution's taps around 1 / taps each: at N(0, scale)
            # alone the rows a lane carries (the tail) would reach the
            # logits at a size bf16 rounding hides, and a wrong tail with it
            return (1.0 / shape[0] + jax.random.normal(key, shape, jnp.float32)
                    * scale).astype(dtype)
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)

    def make_layer(spec, key):
        shapes = layer_param_shapes(config, spec)
        ks = jax.random.split(key, len(shapes))
        layer = {name: make(shape, init, k)
                 for (name, (shape, init)), k in zip(sorted(shapes.items()), ks)}
        if spec.ffn == "experts":
            layer = zero_stored_padding(layer, moe_config_of(config))
        return layer

    def make_top(key):
        k = jax.random.split(key, 2)
        h = config.hidden_size
        top = {"embed": make((config.vocab_size, h), "normal", k[0]),
               "final_norm": jnp.ones((h,), dtype)}
        if config.norm_type == "layernorm" and config.norm_bias:
            top["final_norm_b"] = make((h,), "bias", k[1])
        if not config.tie_word_embeddings:
            top["lm_head"] = make((h, config.vocab_size), "normal", k[1])
        return top

    layer_fn = jax.jit(make_layer, static_argnums=0)
    layers = []
    for i, spec in enumerate(table):
        fn = layer_fn if shardings is None else jax.jit(
            make_layer, static_argnums=0, out_shardings=shardings["layers"][i])
        layers.append(fn(spec, keys[i]))
    top_sharding = None if shardings is None else {
        k: v for k, v in shardings.items() if k != "layers"}
    params = jax.jit(make_top, out_shardings=top_sharding)(keys[-1])
    params["layers"] = layers
    return params


# ---------------- pieces of a layer ----------------


def _ln(x, layer, name, config):
    if config.norm_type == "layernorm":
        return layer_norm(x, layer[name], layer.get(name + "_b"),
                          config.rms_norm_eps)
    return rms_norm(x, layer[name], config.rms_norm_eps)


def _ffn(layer, spec, x, valid, state, config):
    """The row's feed-forward over x [N, h].  An `experts` row multiplies
    only the pairs routed among the `valid` rows to the experts held here
    and adds its sums to `state["stats"]`: experts that got at least one
    row, the fullest expert's rows (engine_moe_expert_hits_total /
    _peak_load_total) and, where the host cannot know them
    (`config.counts_routed_pairs`), the pairs it multiplied and the pairs
    this layer routed, over the rows it SAW (a layer behind the last
    writer sees the sampled rows only): engine_moe_assignments_total and,
    by their difference, engine_moe_pairs_elsewhere_total."""
    if spec.ffn != "experts":
        return llama._mlp(layer, x, config)
    out, rows = moe_mlp(layer, x, moe_config_of(config), valid, with_rows=True)
    sums = [jnp.sum(rows > 0, dtype=jnp.int32), jnp.max(rows)]
    if config.counts_routed_pairs:
        sums += [jnp.sum(rows), jnp.sum(valid, dtype=jnp.int32)
                 * config.n_experts_per_tok]
    state["stats"][0] = state["stats"][0] + jnp.stack(sums)
    return out


def _close(layer, spec, x, mixed, valid, state, config, u=None):
    """Residual around the mixer's output, then the feed-forward's: each
    where the row has it (`mixed` None: the row's mixer is its
    feed-forward).  A parallel block's feed-forward reads `u`, the norm the
    mixer read, and both meet in the one residual."""
    if config.parallel_block:
        return x + mixed + _ffn(layer, spec, u, valid, state, config)
    if mixed is not None:
        x = x + mixed
    if spec.ffn == "none":
        return x
    return x + _ffn(layer, spec, _ln(x, layer, "mlp_norm", config), valid,
                    state, config)


def _lambda_init(layer_index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer_index)


def _queries(layer, u, config):
    """[N, h] -> queries over rows of twice the head size: head 2j is
    [q, 0], head 2j+1 is [0, q]."""
    q = dense(u, layer["wq"])
    if config.attention_bias:
        q = q + layer["bq"]
    # [N, pairs, 2, 1, d] x eye(2)[2, 2, 1]: the pair's first head lands in
    # the row's first half, its second in the second
    q = q.reshape(u.shape[0], config.n_heads // 2, 2, 1, config.head_dim)
    return (q * jnp.eye(2, dtype=q.dtype)[:, :, None]).reshape(
        u.shape[0], config.n_heads, config.cache_head_dim)


def _keys_values(layer, u, config):
    """[N, h] -> K, V [N, K/V heads, head_dim]; differential attention:
    [N, pairs, 2 x head_dim], a pair's heads side by side."""
    k, v = dense(u, layer["wk"]), dense(u, layer["wv"])
    if config.attention_bias:
        k, v = k + layer["bk"], v + layer["bv"]
    shape = (u.shape[0], config.cache_kv_heads, config.cache_head_dim)
    return k.reshape(shape), v.reshape(shape)


def _differential_out(layer, attn, config, layer_index: int):
    """attn [N, heads, 2 x head_dim], heads (2j, 2j+1) the pair's two terms
    -> the layer's output [N, h]."""
    N = attn.shape[0]
    f32 = jnp.float32
    init = _lambda_init(layer_index)
    lam = (jnp.exp(jnp.sum(layer["lambda_q1"].astype(f32)
                           * layer["lambda_k1"].astype(f32)))
           - jnp.exp(jnp.sum(layer["lambda_q2"].astype(f32)
                             * layer["lambda_k2"].astype(f32))) + init)
    pair = attn.astype(f32).reshape(
        N, config.n_heads // 2, 2, config.cache_head_dim)
    diff = pair[:, :, 0] - lam * pair[:, :, 1]
    out = rms_norm(diff, layer["subln"], config.rms_norm_eps) * (1.0 - init)
    out = dense(out.reshape(N, -1).astype(attn.dtype), layer["wo"])
    if config.attention_out_bias:
        out = out + layer["bo"]
    return out


def _rope(x, pos, i, config):
    """x [N, heads, head_dim] of row i at positions pos [N], turned where
    the row carries positions (`config.layer_ropes`)."""
    if not config.layer_ropes(i):
        return x
    if config.rope_interleaved:
        return apply_rope_interleaved(x, pos, config.rope_theta)
    return apply_rope(x[None], pos[None], config.rope_theta,
                      config.rope_scaling)[0]


def _head_norm(x, layer, name, config):
    """x [N, heads, head_dim] under the row's RMSNorm a head (`config.qk_norm`:
    "q_norm" / "k_norm"), where it has one."""
    if name not in layer:
        return x
    with jax.named_scope("qk_norm"):
        return rms_norm(x, layer[name], config.rms_norm_eps)


def _pair_queries(q, config):
    """q [N, heads, head_dim] of a plain grouped-query row whose K/V heads lie
    two a cache row (`config.pairs_kv_heads`) -> [N, heads, 2 x head_dim]:
    `[q, 0]` where the head's K/V head is the row's first, `[0, q]` where it
    is the second.  The kernels scale scores by the ROW's width^-1/2, so the
    queries carry the sqrt(2) that is short of head_dim^-1/2, multiplied in
    float32 and rounded with the cast."""
    N, group = q.shape[0], config.n_heads // config.n_kv_heads
    half = jnp.eye(2, dtype=jnp.float32) * math.sqrt(2.0)
    padded = q.astype(jnp.float32).reshape(
        N, config.cache_kv_heads, 2, group, 1, config.head_dim
    ) * half[:, None, :, None]
    return padded.astype(q.dtype).reshape(
        N, config.n_heads, config.cache_head_dim)


def _own_half(attn, config):
    """attn [N, heads, 2 x head_dim] over paired cache rows -> [N, heads,
    head_dim]: of `softmax(q k^T) [v_even, v_odd]` the half that is the
    head's own V head."""
    N, group = attn.shape[0], config.n_heads // config.n_kv_heads
    pair = attn.reshape(N, config.cache_kv_heads, 2, group, 2, config.head_dim)
    return jnp.stack([pair[:, :, 0, :, 0], pair[:, :, 1, :, 1]], axis=2
                     ).reshape(N, config.n_heads, config.head_dim)


def _gqa_queries(layer, u, config, pos, i):
    """A plain grouped-query row's queries: [N, h] -> [N, heads, the cache
    row's width], normed a head where the row is, turned by position where
    row i is."""
    q = dense(u, layer["wq"]).reshape(
        u.shape[0], config.n_heads, config.head_dim)
    q = _rope(_head_norm(q, layer, "q_norm", config), pos, i, config)
    return _pair_queries(q, config) if config.pairs_kv_heads else q


def _gqa_keys_values(layer, u, config, pos, i):
    """A plain grouped-query row's K (normed and turned as its queries are)
    and V, as the cache stores them."""
    heads = (u.shape[0], config.n_kv_heads, config.head_dim)
    rows = (u.shape[0], config.cache_kv_heads, config.cache_head_dim)
    k, v = dense(u, layer["wk"]), dense(u, layer["wv"])
    k, v = k.reshape(heads), v.reshape(rows)
    k = _rope(_head_norm(k, layer, "k_norm", config), pos, i, config)
    return k.reshape(rows), v


def _gqa_out(layer, attn, config, u=None):
    """A `gqa_attention` row's output: attn [N, heads, the cache row's
    width] -> [N, h]; where the row has a gate (`config.attention_gate`:
    "wg"), times sigmoid(u W_g) first, u [N, h] what the row's projections
    read."""
    if config.pairs_kv_heads:
        attn = _own_half(attn, config)
    attn = attn.reshape(attn.shape[0], -1)
    if "wg" in layer:
        with jax.named_scope("attention_gate"):
            gate = jax.nn.sigmoid(dense(u, layer["wg"]).astype(jnp.float32))
            attn = (attn.astype(jnp.float32) * gate).astype(attn.dtype)
    return dense(attn, layer["wo"])


def _scale(config) -> float:
    return float(config.head_dim) ** -0.5


def _mamba_project(layer, u, config):
    xz = dense(u, layer["in_proj"])
    return xz[:, :config.mamba_d_inner], xz[:, config.mamba_d_inner:]


def _mamba_scan_inputs(layer, conv_out, config):
    """The convolution's output (float32, before its activation) -> what
    the scan takes: x, dt [N, Di] float32, A [Di, N], B, C [N, N_state]."""
    f32 = jnp.float32
    x = jax.nn.silu(conv_out)
    r, n = config.mamba_dt_rank, config.mamba_d_state
    dbc = dense(x.astype(layer["x_proj"].dtype), layer["x_proj"])
    dt = jax.nn.softplus(
        dense(dbc[:, :r], layer["dt_proj"]).astype(f32) + layer["dt_bias"])
    A = -jnp.exp(layer["A_log"].astype(f32))
    return (x, dt, A, dbc[:, r:r + n].astype(f32), dbc[:, r + n:].astype(f32))


def _mamba_out(layer, y, z):
    """The scan's output y [N, Di] float32 -> (the mixer's output, m: what
    a gated memory unit reuses, y before the gate)."""
    gated = y * jax.nn.silu(z.astype(jnp.float32))
    return dense(gated.astype(z.dtype), layer["out_proj"]), y.astype(z.dtype)


def _mamba2_project(layer, u, config):
    """[N, h] -> (z [N, Di], xBC [N, Di + 2 G N], dt [N, H]) of `in_proj`."""
    di, conv = config.mamba_d_inner, config.mamba2_conv_dim
    zxbcdt = dense(u, layer["in_proj"])
    return zxbcdt[:, :di], zxbcdt[:, di:di + conv], zxbcdt[:, di + conv:]


def _mamba2_scan_inputs(layer, conv_out, dt, config):
    """The convolution's output (float32, before its activation) and the
    projected dt -> what the scan takes: x [N, H, P], dt [N, H] float32,
    A [H], B, C [N, G, N_state]."""
    f32 = jnp.float32
    N = conv_out.shape[0]
    H, P = config.mamba_n_heads, config.mamba_head_dim
    G, n = config.mamba_n_groups, config.mamba_d_state
    xbc = jax.nn.silu(conv_out)
    x = xbc[:, :H * P].reshape(N, H, P)
    Bm = xbc[:, H * P:H * P + G * n].reshape(N, G, n)
    Cm = xbc[:, H * P + G * n:].reshape(N, G, n)
    dt = jax.nn.softplus(dt.astype(f32) + layer["dt_bias"])
    return x, dt, -jnp.exp(layer["A_log"].astype(f32)), Bm, Cm


def _mamba2_out(layer, y, z, config):
    """The scan's output y [N, H, P] float32 -> the mixer's output: the
    gate first, then an RMSNorm over each of the G groups of columns by
    itself, then `out_proj`."""
    with jax.named_scope("ssd_gated_norm"):
        N, G = y.shape[0], config.mamba_n_groups
        gated = (y.reshape(N, -1) * jax.nn.silu(z.astype(jnp.float32))
                 ).reshape(N, G, -1)
        var = jnp.mean(gated * gated, axis=-1, keepdims=True)
        normed = (gated * jax.lax.rsqrt(var + config.rms_norm_eps)
                  ).reshape(N, -1) * layer["ssm_norm"].astype(jnp.float32)
    return dense(normed.astype(z.dtype), layer["out_proj"])


def _kda_project(layer, u, config):
    """[N, h] -> (qkv [N, 3 H d] before the convolution, g [N, H, d] float32:
    the log decay a head and channel, <= 0, beta [N, H] float32, the output
    gate's argument [N, H d])."""
    N, H, d = u.shape[0], config.kda_n_heads, config.kda_head_dim
    f32 = jnp.float32
    f = dense(dense(u, layer["wf_a"]), layer["wf_b"]).astype(f32)
    g = -jnp.exp(layer["A_log"].astype(f32))[None, :, None] * jax.nn.softplus(
        (f + layer["dt_bias"].astype(f32)).reshape(N, H, d))
    beta = jax.nn.sigmoid(dense(u, layer["w_beta"]).astype(f32))
    if config.kda_neg_eigval:
        beta = 2.0 * beta
    return (dense(u, layer["wqkv"]), g, beta,
            dense(dense(u, layer["wg_a"]), layer["wg_b"]))


def _kda_scan_inputs(conv_out, config):
    """The convolution's output (float32, before its activation) -> q, k, v
    [N, H, d] float32 as the recurrence takes them: q and k of unit length a
    head, q scaled by d^-1/2."""
    N, H, d = conv_out.shape[0], config.kda_n_heads, config.kda_head_dim
    q, k, v = (x.reshape(N, H, d)
               for x in jnp.split(jax.nn.silu(conv_out), 3, axis=-1))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    return q * float(d) ** -0.5, k, v


def _kda_out(layer, o, gate, config):
    """The recurrence's output o [N, H, d] float32 -> the mixer's output:
    an RMSNorm over each head by itself, times sigmoid(gate), then `wo`."""
    with jax.named_scope("kda_gated_norm"):
        var = jnp.mean(o * o, axis=-1, keepdims=True)
        normed = (o * jax.lax.rsqrt(var + config.rms_norm_eps)
                  * layer["o_norm"].astype(jnp.float32)).reshape(o.shape[0], -1)
        gated = normed * jax.nn.sigmoid(gate.astype(jnp.float32))
    return dense(gated.astype(gate.dtype), layer["wo"])


def _short_conv(layer, u, tail, conv, *packing):
    """A gated short convolution over u [N, h] from the lanes' `tail`, around
    `conv`, the form's convolution (ops/ssm.causal_conv_step, or
    causal_conv_ragged with the buffer's `packing`): -> (the mixer's output,
    the new tail).  The products in float32; what the convolution reads,
    and with it the tail, in the model's dtype."""
    f32 = jnp.float32
    with jax.named_scope("short_conv_in"):
        b, c, x = jnp.split(dense(u, layer["in_proj"]), 3, axis=-1)
    with jax.named_scope("short_conv_taps"):
        z = (b.astype(f32) * x.astype(f32)).astype(u.dtype)
        conv_out, tail = conv(
            z, tail, layer["conv_w"], jnp.zeros((), f32), *packing)
        y = (c.astype(f32) * conv_out).astype(u.dtype)
    with jax.named_scope("short_conv_out"):
        return dense(y, layer["out_proj"]), tail


def _gmu(layer, u, m):
    with jax.named_scope("gmu"):
        gate = jax.nn.silu(dense(u, layer["gmu_in"]).astype(jnp.float32))
        return dense((m.astype(jnp.float32) * gate).astype(u.dtype),
                     layer["gmu_out"])


def _logits(params, x, config):
    if config.norm_type != "layernorm":
        return llama._logits(params, x, config)
    with jax.named_scope("lm_head"):
        x = layer_norm(x, params["final_norm"], params.get("final_norm_b"),
                       config.rms_norm_eps)
        return tied_head_matmul(x, params["embed"]).astype(jnp.float32)


def _slots(table) -> Dict[int, int]:
    """layer index -> index into its kind's list of state arrays."""
    count: Dict[str, int] = {}
    out = {}
    for i, spec in enumerate(table):
        if spec.writes != "none":
            out[i] = count.get(spec.writes, 0)
            count[spec.writes] = out[i] + 1
    return out


def _ring_table(state, lanes: int):
    if not state["window"]:
        return None
    ring = state["window"][0]
    width = (ring.shape[0] - 1) // lanes
    return (1 + jnp.arange(lanes, dtype=jnp.int32)[:, None] * width
            + jnp.arange(width, dtype=jnp.int32)[None, :])


# ---------------- one token per lane ----------------


def _rows_layer(layer, spec, i, x, pos, live, state, slots, page_table,
                page_size, ring_table, handed, config, use_pallas,
                write: bool = True):
    """One layer over one row per lane (x [B, h] at positions pos [B]).
    `write` False: the row's own K/V are already in the cache (the packed
    forward wrote them for every token)."""
    if spec.kind == "ffn":
        return _close(layer, spec, x, None, live, state, config)
    u = _ln(x, layer, "attn_norm", config)
    seq_lens = jnp.where(live, pos + 1, 0)
    if spec.kind == "mamba2":
        with jax.named_scope("ssd"):
            j = slots[i]
            z, xbc, dt = _mamba2_project(layer, u, config)
            with jax.named_scope("ssd_conv"):
                conv_out, tail = ssm.causal_conv_step(
                    xbc, state["conv"][j], layer["conv_w"], layer["conv_b"])
            xs, dt, A, Bm, Cm = _mamba2_scan_inputs(layer, conv_out, dt, config)
            with jax.named_scope("ssd_update"):
                y, s = ssm.ssd_step(
                    xs, dt, A, Bm, Cm, layer["D"], state["ssm"][j], live)
            state["ssm"][j] = s
            state["conv"][j] = jnp.where(
                live[:, None, None], tail, state["conv"][j])
            mixed = _mamba2_out(layer, y, z, config)
    elif spec.kind == "kda":
        with jax.named_scope("kda"):
            j = slots[i]
            qkv, g, beta, gate = _kda_project(layer, u, config)
            with jax.named_scope("kda_conv"):
                conv_out, tail = ssm.causal_conv_step(
                    qkv, state["conv"][j], layer["conv_w"],
                    jnp.zeros((), jnp.float32))
            q, k, v = _kda_scan_inputs(conv_out, config)
            with jax.named_scope("kda_update"):
                o, s = delta.kda_step(q, k, v, g, beta, state["ssm"][j], live)
            state["ssm"][j] = s
            state["conv"][j] = jnp.where(
                live[:, None, None], tail, state["conv"][j])
            mixed = _kda_out(layer, o, gate, config)
    elif spec.kind == "short_conv":
        with jax.named_scope("short_conv"):
            j = slots[i]
            mixed, tail = _short_conv(
                layer, u, state["conv"][j], ssm.causal_conv_step)
            state["conv"][j] = jnp.where(
                live[:, None, None], tail, state["conv"][j])
    elif spec.kind == "gqa_attention":
        with jax.named_scope("gqa_attention"):
            j = slots[i]
            if write:
                k, v = _gqa_keys_values(layer, u, config, pos, i)
                state["paged"][j] = append_token_kv(
                    state["paged"][j], k, v, page_table, pos, live, page_size)
            attn = paged_attention(
                _gqa_queries(layer, u, config, pos, i), state["paged"][j],
                page_table, seq_lens, use_pallas=use_pallas)
            mixed = _gqa_out(layer, attn, config, u)
    elif spec.kind == "gqa_window_attention":
        with jax.named_scope("window_attention"):
            j = slots[i]
            ring = state["window"][j]
            R = ring_table.shape[1] * ring.shape[3]
            k, v = _gqa_keys_values(layer, u, config, pos, i)
            ring = append_token_kv(
                ring, k, v, ring_table, pos % R, live, ring.shape[3])
            state["window"][j] = ring
            attn = paged_attention_scaled(
                _gqa_queries(layer, u, config, pos, i), ring, ring_table,
                jnp.minimum(seq_lens, R), _scale(config),
                "window_attention_decode", use_pallas)
            mixed = _gqa_out(layer, attn, config)
    elif spec.kind == "mamba":
        with jax.named_scope("ssm"):
            j = slots[i]
            xin, z = _mamba_project(layer, u, config)
            conv_out, tail = ssm.causal_conv_step(
                xin, state["conv"][j], layer["conv_w"], layer["conv_b"])
            xs, dt, A, Bm, Cm = _mamba_scan_inputs(layer, conv_out, config)
            y, s = ssm.selective_scan_step(
                xs, dt, A, Bm, Cm, layer["D"], state["ssm"][j], live)
            state["ssm"][j] = s
            state["conv"][j] = jnp.where(
                live[:, None, None], tail, state["conv"][j])
            mixed, handed[i] = _mamba_out(layer, y, z)
    elif spec.kind == "gmu":
        mixed = _gmu(layer, u, handed[spec.reads])
    elif spec.kind == "latent_attention":
        with jax.named_scope("latent_attention"):
            j = slots[i]
            pages = state["latent"][j]
            queries, rows = latent.project(layer, u, pos, config,
                                           pages.shape[-1])
            if write:
                pages = state["latent"][j] = append_token_kv(
                    pages, rows[:, None], None, page_table, pos, live,
                    page_size)
            attn = latent_paged_attention(
                queries, pages, page_table, seq_lens, latent.scale(config),
                config.kv_lora_rank, use_pallas)
            mixed = latent.output(layer, attn, config)
    elif spec.kind == "window_attention":
        with jax.named_scope("window_attention"):
            j = slots[i]
            ring = state["window"][j]
            R = ring_table.shape[1] * ring.shape[3]
            k, v = _keys_values(layer, u, config)
            ring = append_token_kv(
                ring, k, v, ring_table, pos % R, live, ring.shape[3])
            state["window"][j] = ring
            attn = paged_attention_scaled(
                _queries(layer, u, config), ring, ring_table,
                jnp.minimum(seq_lens, R), _scale(config),
                "window_attention_decode", use_pallas)
            mixed = _differential_out(layer, attn, config, i)
    else:  # attention over the shared cache: its own layer's, or another's
        with jax.named_scope("shared_kv_attention"):
            j = slots[spec.reads]
            if spec.kind == "attention" and write:
                k, v = _keys_values(layer, u, config)
                state["paged"][j] = append_token_kv(
                    state["paged"][j], k, v, page_table, pos, live, page_size)
            attn = paged_attention_scaled(
                _queries(layer, u, config), state["paged"][j], page_table,
                seq_lens, _scale(config), "shared_kv_attention_decode",
                use_pallas)
            mixed = _differential_out(layer, attn, config, i)
    return _close(layer, spec, x, mixed, live, state, config, u)


def _copy_state(state) -> dict:
    return {kind: list(arrays) for kind, arrays in state.items()}


def decode_step(params, config, tokens, pos, state, page_table, active,
                page_size: int, use_pallas: Optional[bool] = None):
    """One token per lane through every layer; returns ([B, vocab] logits,
    the new state).  A lane that is not `active` writes to the null page
    and keeps its recurrent state."""
    table = config.layer_table()
    slots = _slots(table)
    state = _copy_state(state)
    ring_table = _ring_table(state, tokens.shape[0])
    with jax.named_scope("embedding"):
        x = params["embed"][tokens].astype(jnp.dtype(config.dtype))
    handed: Dict[int, Any] = {}
    for i, (layer, spec) in enumerate(zip(params["layers"], table)):
        x = _rows_layer(layer, spec, i, x, pos, active, state, slots,
                        page_table, page_size, ring_table, handed, config,
                        use_pallas)
    return _logits(params, x, config), state


# ---------------- the packed buffer ----------------


def _ring_runs(q_start, q_len, kv_start, R: int):
    """A window layer's packed write as the page write's runs
    (ops/kv_write.write_ragged_kv): of lane b's slice the newest R tokens
    are kept; those up to the ring's end are one run, those that wrap to
    its start another.  Two sets, written one after the other, because the
    two runs of one lane may meet on a page."""
    lanes = jnp.arange(q_start.shape[0], dtype=jnp.int32)
    skipped = jnp.maximum(q_len - R, 0)
    first = (kv_start + skipped) % R
    n = q_len - skipped
    to_end = jnp.minimum(n, R - first)
    return [(lanes, q_start + skipped, to_end, first),
            (lanes, q_start + skipped + to_end, n - to_end,
             jnp.zeros_like(first))]


def _ring_kept(token_pos, lane, q_len, kv_start, R: int):
    """[T]: of a slice longer than the ring only the newest R tokens are
    kept (the others would collide with them)."""
    return token_pos >= (kv_start + q_len)[lane] - R


def forward_ragged(params, config, tokens, token_seq, token_pos, q_start,
                   q_len, kv_start, state, page_table, page_size: int,
                   last_idx, use_pallas: Optional[bool] = None,
                   block: int = 1):
    """The mixed program's forward over the packed [T] buffer
    (models/llama.forward_ragged's contract): every lane's slice starts
    from the lane's stored state, or from zero where it starts at position
    0; returns ([B, vocab] logits at each lane's last token, new state)."""
    table = config.layer_table()
    slots = _slots(table)
    state = _copy_state(state)
    B = q_start.shape[0]
    ring_table = _ring_table(state, B)
    lane = jnp.maximum(token_seq, 0)
    token_off = token_pos - kv_start[lane]
    fresh = kv_start == 0
    has_slice = q_len > 0
    last_writer = max(i for i, spec in enumerate(table) if spec.writes != "none")
    with jax.named_scope("embedding"):
        x = params["embed"][tokens].astype(jnp.dtype(config.dtype))
    handed: Dict[int, Any] = {}
    pos_rows = token_pos[last_idx]
    for i, (layer, spec) in enumerate(zip(params["layers"], table)):
        if i > last_writer:
            x = _rows_layer(layer, spec, i, x, pos_rows, has_slice, state,
                            slots, page_table, page_size, ring_table, handed,
                            config, use_pallas)
            continue
        if spec.kind == "ffn":  # the row's mixer is its feed-forward
            x = _close(layer, spec, x, None, token_seq >= 0, state, config)
            continue
        u = _ln(x, layer, "attn_norm", config)
        if spec.kind == "mamba2":
            with jax.named_scope("ssd"):
                j = slots[i]
                z, xbc, dt = _mamba2_project(layer, u, config)
                with jax.named_scope("ssd_conv"):
                    conv_out, tail = ssm.causal_conv_ragged(
                        xbc, state["conv"][j], layer["conv_w"],
                        layer["conv_b"], token_seq, token_off, q_start, q_len,
                        fresh)
                xs, dt, A, Bm, Cm = _mamba2_scan_inputs(
                    layer, conv_out, dt, config)
                with jax.named_scope("ssd_chunk_scan"):
                    y, s = ssm.ssd_ragged(
                        xs, dt, A, Bm, Cm, layer["D"], state["ssm"][j],
                        token_seq, q_start, q_len, last_idx, fresh)
                state["ssm"][j] = s
                state["conv"][j] = jnp.where(
                    has_slice[:, None, None], tail, state["conv"][j])
                mixed = _mamba2_out(layer, y, z, config)
        elif spec.kind == "kda":
            with jax.named_scope("kda"):
                j = slots[i]
                qkv, g, beta, gate = _kda_project(layer, u, config)
                with jax.named_scope("kda_conv"):
                    conv_out, tail = ssm.causal_conv_ragged(
                        qkv, state["conv"][j], layer["conv_w"],
                        jnp.zeros((), jnp.float32), token_seq, token_off,
                        q_start, q_len, fresh)
                q, k, v = _kda_scan_inputs(conv_out, config)
                with jax.named_scope("kda_chunk_scan"):
                    o, s = delta.kda_ragged(
                        q, k, v, g, beta, state["ssm"][j], q_start, q_len,
                        fresh)
                state["ssm"][j] = s
                state["conv"][j] = jnp.where(
                    has_slice[:, None, None], tail, state["conv"][j])
                mixed = _kda_out(layer, o, gate, config)
        elif spec.kind == "short_conv":
            with jax.named_scope("short_conv"):
                j = slots[i]
                mixed, tail = _short_conv(
                    layer, u, state["conv"][j], ssm.causal_conv_ragged,
                    token_seq, token_off, q_start, q_len, fresh)
                state["conv"][j] = jnp.where(
                    has_slice[:, None, None], tail, state["conv"][j])
        elif spec.kind == "gqa_attention" and i != last_writer:
            with jax.named_scope("gqa_attention"):
                j = slots[i]
                k, v = _gqa_keys_values(layer, u, config, token_pos, i)
                state["paged"][j] = write_ragged_kv(
                    state["paged"][j], k, v, page_table, token_seq, token_pos,
                    page_size, runs=slice_runs(q_start, q_len, kv_start))
                attn = ragged_paged_attention(
                    _gqa_queries(layer, u, config, token_pos, i),
                    state["paged"][j], page_table, q_start, q_len, kv_start,
                    use_pallas=use_pallas)
                mixed = _gqa_out(layer, attn, config, u)
        elif spec.kind == "gqa_window_attention":
            with jax.named_scope("window_attention"):
                j = slots[i]
                ring = state["window"][j]
                ps = ring.shape[3]
                R = ring_table.shape[1] * ps
                k, v = _gqa_keys_values(layer, u, config, token_pos, i)
                attn = window_attention_ragged(
                    _gqa_queries(layer, u, config, token_pos, i), k, v, ring,
                    ring_table, token_seq, token_pos, q_start, q_len,
                    kv_start, _scale(config), block, use_pallas)
                state["window"][j] = write_ragged_kv(
                    ring, k, v, ring_table,
                    jnp.where(_ring_kept(token_pos, lane, q_len, kv_start, R),
                              token_seq, -1),
                    token_pos % R, ps,
                    runs=_ring_runs(q_start, q_len, kv_start, R))
                mixed = _gqa_out(layer, attn, config)
        elif spec.kind == "mamba":
            with jax.named_scope("ssm"):
                j = slots[i]
                xin, z = _mamba_project(layer, u, config)
                conv_out, tail = ssm.causal_conv_ragged(
                    xin, state["conv"][j], layer["conv_w"], layer["conv_b"],
                    token_seq, token_off, q_start, q_len, fresh)
                xs, dt, A, Bm, Cm = _mamba_scan_inputs(layer, conv_out, config)
                y, s = ssm.selective_scan_ragged(
                    xs, dt, A, Bm, Cm, layer["D"], state["ssm"][j], token_seq,
                    q_start, q_len, last_idx, fresh, block)
                state["ssm"][j] = s
                state["conv"][j] = jnp.where(
                    has_slice[:, None, None], tail, state["conv"][j])
                mixed, handed[i] = _mamba_out(layer, y, z)
        elif spec.kind == "latent_attention":
            with jax.named_scope("latent_attention"):
                j = slots[i]
                pages = state["latent"][j]
                queries, rows = latent.project(layer, u, token_pos, config,
                                               pages.shape[-1])
                pages = state["latent"][j] = write_ragged_kv(
                    pages, rows[:, None], None, page_table, token_seq,
                    token_pos, page_size)
                attn = latent_ragged_attention(
                    queries, pages, page_table, q_start, q_len, kv_start,
                    latent.scale(config), config.kv_lora_rank, use_pallas)
                mixed = latent.output(layer, attn, config)
        elif spec.kind == "window_attention":
            with jax.named_scope("window_attention"):
                j = slots[i]
                ring = state["window"][j]
                ps = ring.shape[3]
                R = ring_table.shape[1] * ps
                k, v = _keys_values(layer, u, config)
                attn = window_attention_ragged(
                    _queries(layer, u, config), k, v, ring, ring_table,
                    token_seq, token_pos, q_start, q_len, kv_start,
                    _scale(config), block, use_pallas)
                state["window"][j] = write_ragged_kv(
                    ring, k, v, ring_table,
                    jnp.where(_ring_kept(token_pos, lane, q_len, kv_start, R),
                              token_seq, -1),
                    token_pos % R, ps,
                    runs=_ring_runs(q_start, q_len, kv_start, R))
                mixed = _differential_out(layer, attn, config, i)
        elif spec.kind in ("attention", "gqa_attention") and i == last_writer:
            # K/V of every token go to the pool; the attention's own output
            # feeds only layers behind it, so it is taken at the sampled rows
            with jax.named_scope("shared_kv_attention"):
                if spec.kind == "gqa_attention":
                    k, v = _gqa_keys_values(layer, u, config, token_pos, i)
                else:
                    k, v = _keys_values(layer, u, config)
                j = slots[i]
                state["paged"][j] = write_ragged_kv(
                    state["paged"][j], k, v, page_table, token_seq, token_pos,
                    page_size, runs=slice_runs(q_start, q_len, kv_start))
            x = x[last_idx]
            handed = {key: m[last_idx] for key, m in handed.items()}
            x = _rows_layer(layer, spec, i, x, pos_rows, has_slice, state,
                            slots, page_table, page_size, ring_table, handed,
                            config, use_pallas, write=False)
            continue
        else:
            raise NotImplementedError(
                f"layer {i} ({spec.kind}) in the packed forward before the "
                "last layer that writes state")
        x = _close(layer, spec, x, mixed, token_seq >= 0, state, config, u)
        if i == last_writer:
            x = x[last_idx]
            handed = {key: m[last_idx] for key, m in handed.items()}
    return _logits(params, x, config), state


# ---------------- checkpoints ----------------

#: checkpoint tensor (under `model.layers.<i>.`) -> (our name, transposed).
#: Names as the builder knows them from the published modeling file, no
#: network here: listed under `assumed` in the benchmark's configuration.
_HF_COMMON = {
    "input_layernorm.weight": ("attn_norm", False),
    "input_layernorm.bias": ("attn_norm_b", False),
    "post_attention_layernorm.weight": ("mlp_norm", False),
    "post_attention_layernorm.bias": ("mlp_norm_b", False),
    "mlp.fc2.weight": ("w_down", True),
}
_HF_BY_KIND = {
    "attention": {
        "attn.out_proj.weight": ("wo", True), "attn.out_proj.bias": ("bo", False),
        "attn.lambda_q1": ("lambda_q1", False), "attn.lambda_k1": ("lambda_k1", False),
        "attn.lambda_q2": ("lambda_q2", False), "attn.lambda_k2": ("lambda_k2", False),
        "attn.subln.weight": ("subln", False),
    },
    "mamba": {
        "attn.in_proj.weight": ("in_proj", True),
        "attn.conv1d.bias": ("conv_b", False),
        "attn.x_proj.weight": ("x_proj", True),
        "attn.dt_proj.weight": ("dt_proj", True),
        "attn.dt_proj.bias": ("dt_bias", False),
        "attn.A_log": ("A_log", False), "attn.D": ("D", False),
        "attn.out_proj.weight": ("out_proj", True),
    },
    "gmu": {
        "attn.in_proj.weight": ("gmu_in", True),
        "attn.out_proj.weight": ("gmu_out", True),
    },
}
_HF_BY_KIND["window_attention"] = _HF_BY_KIND["attention"]
_HF_BY_KIND["cross_attention"] = _HF_BY_KIND["attention"]


def hf_layer_tensors(config, spec, layer: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """The inverse of the loader for one layer: our arrays (numpy) -> the
    checkpoint's names under `model.layers.<i>.`; tests write a synthetic
    checkpoint with it."""
    out = {"mlp.fc1.weight": np.concatenate(
        [layer["w_gate"].T, layer["w_up"].T], axis=0)}
    for hf, (ours, transposed) in {**_HF_COMMON, **_HF_BY_KIND[spec.kind]}.items():
        if ours in layer:
            out[hf] = layer[ours].T if transposed else layer[ours]
    if spec.kind == "mamba":
        out["attn.conv1d.weight"] = layer["conv_w"].T[:, None, :]
    if "wq" in layer:
        parts = [("wq", "bq")] + (
            [("wk", "bk"), ("wv", "bv")] if "wk" in layer else [])
        out["attn.Wqkv.weight"] = np.concatenate(
            [layer[w].T for w, _ in parts], axis=0)
        if config.attention_bias:
            out["attn.Wqkv.bias"] = np.concatenate([layer[b] for _, b in parts])
    return out


def load_hf_weights_streamed(model_dir: str, config, weight_quant: str = "none",
                             stats: Optional[dict] = None) -> Params:
    """models/llama.load_hf_weights_streamed for a table of several kinds:
    one tensor at a time, routed by the layer's row."""
    from safetensors import safe_open

    if weight_quant != "none":
        raise NotImplementedError("weight_quant over a hybrid model")
    dtype = jnp.dtype(config.dtype)
    table = config.layer_table()
    files = sorted(os.path.join(model_dir, f) for f in os.listdir(model_dir)
                   if f.endswith(".safetensors"))
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {model_dir}")
    acct = {"peak_host_bytes": 0, "read_bytes": 0, "n_tensors": 0}
    params: Params = {"layers": [dict() for _ in table]}
    layer_re = re.compile(r"^model\.layers\.(\d+)\.(.+)$")
    top = {"model.embed_tokens.weight": "embed",
           "model.final_layernorm.weight": "final_norm",
           "model.final_layernorm.bias": "final_norm_b"}

    def put(layer, name, arr, transposed=False):
        want = jnp.float32 if name in _F32_INITS else dtype
        layer[name] = jnp.asarray(arr.T if transposed else arr).astype(want)

    def place(name: str, arr: np.ndarray) -> None:
        if name in top:
            put(params, top[name], arr)
            return
        m = layer_re.match(name)
        if m is None or int(m.group(1)) >= len(table):
            return
        i, suffix = int(m.group(1)), m.group(2)
        layer, spec = params["layers"][i], table[i]
        nq = config.n_heads * config.head_dim
        nkv = config.n_kv_heads * config.head_dim
        if suffix == "mlp.fc1.weight":  # [gate; up] x h
            f = config.intermediate_size
            put(layer, "w_gate", arr[:f], True)
            put(layer, "w_up", arr[f:], True)
        elif suffix == "attn.conv1d.weight":  # [Di, 1, K]
            put(layer, "conv_w", arr[:, 0, :], True)
        elif suffix in ("attn.Wqkv.weight", "attn.Wqkv.bias"):
            w = suffix.endswith("weight")
            names = ("wq", "wk", "wv") if w else ("bq", "bk", "bv")
            put(layer, names[0], arr[:nq], w)
            if spec.kind != "cross_attention":
                put(layer, names[1], arr[nq:nq + nkv], w)
                put(layer, names[2], arr[nq + nkv:], w)
        else:
            hit = {**_HF_COMMON, **_HF_BY_KIND[spec.kind]}.get(suffix)
            if hit is not None:
                put(layer, hit[0], arr, hit[1])

    for path in files:
        with safe_open(path, framework="numpy") as f:
            for name in f.keys():
                arr = f.get_tensor(name)
                acct["read_bytes"] += arr.nbytes
                acct["n_tensors"] += 1
                acct["peak_host_bytes"] = max(acct["peak_host_bytes"], arr.nbytes)
                place(name, arr)
    for i, (layer, spec) in enumerate(zip(params["layers"], table)):
        missing = sorted(set(layer_param_shapes(config, spec)) - set(layer))
        if missing:
            raise ValueError(f"checkpoint lacks {missing} of layer {i} ({spec.kind})")
    if stats is not None:
        stats.update(acct)
    return params
