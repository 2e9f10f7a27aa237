"""Plain reference forward of `model_type: lfm2_moe` (LFM2-24B-A2B).

The layer equations in straightforward `jax.numpy` and float32: one
sequence, all positions at once for the projections, the convolution an
explicit sum over shifted copies of the whole sequence, the attention a
block of queries at a time over heads as published (64 wide, each K/V head
by itself: no pairs), a Python loop over the layers and over the experts.
No cache, no kernel, no batching.  It shares no code with `kserve_tpu/`; it
reads the program's parameter pytree as data (weights stored [in, out]):

    embed, final_norm, layers[l]:
      every row:  attn_norm (the published `operator_norm`), mlp_norm
                  (`ffn_norm`)
      conv rows:  in_proj [hidden, 3 hidden] (B | C | x), conv_w [taps,
                  hidden], out_proj [hidden, hidden]
      attention:  wq, wk, wv, wo, q_norm [head_dim], k_norm [head_dim]
      dense rows: w_gate, w_up [hidden, intermediate], w_down
      experts:    router [hidden, experts], router_bias [experts], w_gate /
                  w_up [experts, hidden, width], w_down [experts, width,
                  hidden]

Pre-norm, two residuals a layer (RMSNorm: weight, no +1, eps `norm_eps`):

    h <- h + Mixer_l(RMSNorm(h));   h <- h + FFN_l(RMSNorm(h))

a final RMSNorm, and the head is the embedding, transposed.  With u the
mixer's normed input:

Short-convolution rows (`layer_types[l] == "conv"`), the LFM2 family's
`Lfm2ShortConv`:

    [B | C | x] = u W_in                     (three slices of `hidden_size`)
    z = B * x
    c_t = sum_{j=0..taps-1} w[j] * z_{t-(taps-1)+j}   (depthwise, causal,
          `conv_L_cache` taps, w[taps-1] on the current token, zeros before
          the first token, no bias, NO activation)
    out = (C * c) W_out

Attention rows (`"full_attention"`): q = u W_q (heads x head_dim), k, v =
u W_k, u W_v (K/V heads x head_dim); q <- RMSNorm_d(q) q_norm, k <-
RMSNorm_d(k) k_norm a head (eps `norm_eps`) BEFORE the rotary; rotary of
the half-split kind (columns j and j + d/2 turn by pos x theta^(-2j/d)),
`rope_theta`, on every attention row; causal over the whole context, scale
1 / sqrt(d); out = Attention(q, k, v) W_o.

Feed-forward: layers under `num_dense_layers` are down(silu(gate x) * up
x) of `intermediate_size`.  The others: s = sigmoid(x W_r) in float32; idx =
top_k(s + b) (`use_expert_bias`: b chooses only); w = s[idx] / (sum s[idx] +
1e-20) (`norm_topk_prob`) x `routed_scaling_factor`; out = sum_j w_j
Expert_{idx_j}(x), an expert the same gated MLP of `moe_intermediate_size`;
no shared expert.

Departures from the published description.  (1) The normaliser's epsilon:
the public `transformers` layer adds 1e-6 to the sum of the chosen scores,
this file and the program add 1e-20; four sigmoids sum to over 1e-3 for any
finite logits, so the two agree to float32 rounding.  (2) Storage, not
mathematics: a routed expert's width is multiplied as stored (1536 is
stored as it is: `models/moe.stored_width`).  What the published
config.json leaves to the modeling file (head_dim, the tied head, the order
of `in_proj`'s slices and of the taps, the norm before the rotary) is in
the configuration's file under `assumed`.  Weights are upcast a layer (an
expert) at a time.
"""

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512


def check_supported(cfg: dict) -> None:
    unsupported = []
    if cfg.get("model_type") != "lfm2_moe":
        unsupported.append(f"model_type={cfg.get('model_type')}")
    if cfg.get("conv_bias"):
        unsupported.append("conv_bias")
    if set(cfg.get("layer_types") or ()) - {"conv", "full_attention"}:
        unsupported.append(f"layer_types={sorted(set(cfg['layer_types']))}")
    rope = cfg.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        unsupported.append(f"rope_type={rope.get('rope_type')}")
    if not cfg.get("tie_word_embeddings", True):
        unsupported.append("tie_word_embeddings false")
    if unsupported:
        raise NotImplementedError(
            "reference/lfm2_moe.py does not compute: " + ", ".join(unsupported))


def f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * f32(weight)


def short_conv(layer: dict, u):
    """u [T, hidden] float32 -> the mixer's output [T, hidden]."""
    t, hidden = u.shape
    b, c, x = jnp.split(u @ f32(layer["in_proj"]), 3, axis=-1)
    w = f32(layer["conv_w"])  # [taps, hidden], w[taps - 1] the current token's
    taps = w.shape[0]
    z = jnp.concatenate([jnp.zeros((taps - 1, hidden)), b * x], axis=0)
    conv = sum(z[j:j + t] * w[j] for j in range(taps))
    return (c * conv) @ f32(layer["out_proj"])


def rope(x, theta: float):
    """x [T, heads, d]: columns j and j + d/2 turned by t x theta^(-2j/d)."""
    t, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def head_dim_of(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def attention(layer: dict, u, cfg: dict):
    t = u.shape[0]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = head_dim_of(cfg), cfg.get("norm_eps", 1e-5)
    theta = float((cfg.get("rope_parameters") or {}).get("rope_theta", 10000.0))
    q = (u @ f32(layer["wq"])).reshape(t, heads, d)
    k = (u @ f32(layer["wk"])).reshape(t, kv_heads, d)
    v = (u @ f32(layer["wv"])).reshape(t, kv_heads, d)
    q = rope(rms_norm(q, layer["q_norm"], eps), theta)
    k = rope(rms_norm(k, layer["k_norm"], eps), theta)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    key_pos = jnp.arange(t)
    blocks = []
    for start in range(0, t, QUERY_BLOCK):  # a block of queries at a time
        query_pos = jnp.arange(start, min(start + QUERY_BLOCK, t))
        scores = jnp.einsum("qhd,khd->hqk", q[start:start + QUERY_BLOCK], k
                            ) / jnp.sqrt(jnp.float32(d))
        scores = jnp.where((query_pos[:, None] >= key_pos[None, :])[None],
                           scores, -jnp.inf)
        blocks.append(jnp.einsum(
            "hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(blocks).reshape(t, heads * d) @ f32(layer["wo"])


def gated(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ f32(w_gate)) * (x @ f32(w_up))) @ f32(w_down)


def route(layer: dict, x, cfg: dict):
    """x [T, hidden] -> (weights [T, k], experts [T, k])."""
    scores = jax.nn.sigmoid(x @ f32(layer["router"]))
    chooser = scores
    if cfg.get("use_expert_bias", True):
        chooser = scores + f32(layer["router_bias"])
    _, idx = jax.lax.top_k(chooser, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return w * cfg.get("routed_scaling_factor", 1.0), idx


def experts(layer: dict, x, cfg: dict):
    w, idx = route(layer, x, cfg)
    w, idx = np.asarray(w), np.asarray(idx)
    out = jnp.zeros_like(x)
    for e in range(cfg["num_experts"]):  # dense over the experts
        rows = np.nonzero((idx == e).any(axis=-1))[0]
        if rows.size == 0:
            continue
        weight = (w * (idx == e)).sum(axis=-1)[rows]
        y = gated(x[rows], layer["w_gate"][e], layer["w_up"][e],
                  layer["w_down"][e])
        out = out.at[rows].add(y * weight[:, None])
    return out


def layer_forward(layer: dict, h, cfg: dict, index: int):
    eps = cfg.get("norm_eps", 1e-5)
    u = rms_norm(h, layer["attn_norm"], eps)
    if cfg["layer_types"][index] == "conv":
        h = h + short_conv(layer, u)
    else:
        h = h + attention(layer, u, cfg)
    x = rms_norm(h, layer["mlp_norm"], eps)
    if index < cfg.get("num_dense_layers", 0):
        return h + gated(x, layer["w_gate"], layer["w_up"], layer["w_down"])
    return h + experts(layer, x, cfg)


def forward(params: dict, cfg: dict, tokens) -> jnp.ndarray:
    """Logits [T, vocab] in float32 for one sequence of token ids."""
    check_supported(cfg)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        embed = jnp.asarray(params["embed"])
        h = f32(embed[tokens])
        for index, layer in enumerate(params["layers"]):
            h = layer_forward(layer, h, cfg, index)
        h = rms_norm(h, params["final_norm"], cfg.get("norm_eps", 1e-5))
        return h @ f32(embed).T
