"""Plain reference forward of `model_type: solar_open2` (Solar-Open2-250B).

The layer equations in straightforward `jax.numpy` and float32: one
sequence, all positions at once for the projections, the attention a block
of queries at a time, the delta rule TOKEN BY TOKEN (a `lax.scan` over the
positions of the recurrence as it is written below: no chunking), a Python
loop over the layers and over the experts held.  No cache, no kernel, no
batching.  It shares no code with `kserve_tpu/`; it reads the program's
parameter pytree as data (weights stored [in, out]):

    embed, final_norm, lm_head, layers[l]:
      every row: attn_norm, mlp_norm, router, router_bias,
                 w_gate / w_up [held, hidden, stored], w_down [held, stored,
                 hidden], shared_gate, shared_up, shared_down
      KDA rows:  wqkv [hidden, 3 H d] (q | k | v), conv_w [taps, 3 H d],
                 wf_a, wf_b, A_log [H], dt_bias [H d], w_beta [hidden, H],
                 wg_a, wg_b, o_norm [d], wo
      GQA rows:  wq, wk, wv, wg, wo

Pre-norm, two residuals a layer (RMSNorm: weight, no +1, eps rms_norm_eps):

    h <- h + Mixer_l(RMSNorm(h));   h <- h + Experts_l(RMSNorm(h))

and NO positional encoding anywhere (`use_rope` false: order comes from the
recurrent layers).  With u the mixer's normed input, H heads of d columns:

KDA rows (every layer that is not in `gqa_layers`), Kimi Delta Attention
(arXiv:2510.26692):

    q, k, v = silu(conv4(u W_q)), silu(conv4(u W_k)), silu(conv4(u W_v))
              (depthwise causal convolutions of `short_conv_kernel_size` taps)
    q <- q / sqrt(|q|^2 + 1e-6) / sqrt(d),   k <- k / sqrt(|k|^2 + 1e-6)   (per head)
    g = -exp(A_log[h]) softplus((u W_fa) W_fb + dt_bias)      [H, d], <= 0
    beta = sigmoid(u W_beta) x 2                (`kda_allow_neg_eigval`)
    S' = Diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t                              (S [H, d, d] float32)
    out = (RMSNorm_d(o_t) o_norm * sigmoid((u W_ga) W_gb)) W_o

GQA rows (`gqa_layers`): q = u W_q (heads x head_dim), k, v = u W_k, u W_v
(K/V heads x head_dim), causal over the whole context, scale 1 / sqrt(d),
`use_gqa_gate`: out = (a * sigmoid(u W_g)) W_o (arXiv:2505.06708's
elementwise gate on the attention's output).

Experts (every layer): s = sigmoid(x W_r) in float32 over the
`router_n_experts` the router scores; idx = top_k(s + b); w = s[idx] / (sum
s[idx] + 1e-20) x routed_scaling_factor; a routed expert and the shared one
are W_down (silu(W_gate x) * W_up x):

    out = sum_{j: idx_j held here} w_j Expert_{idx_j}(x) + Expert_shared(x)

Departures from the published description.  (1) The share: this chip holds
experts `first_expert .. first_expert + n_routed_experts - 1` of the
`router_n_experts` the router scores (the benchmark's configuration: 40 of
320).  A pair routed to an expert that is not held adds NOTHING here, in
the program and in this reference alike: it is the other chips' part of the
sum, and the weights are normalised over all the experts chosen, held or
not.  With every expert held (`router_n_experts` absent) this is the
published layer.  (2) Storage, not mathematics: the program keeps q, k and
v's projections and convolutions side by side in one tensor each, and
stores a routed expert's width of 1280 in 1536 columns with zeros behind
it; silu(0) x 0 = 0 times a zero row adds nothing, so the tensors are
multiplied as they come.  What the published config.json leaves to the
modeling file (the projections' rank, the activations and norms of the KDA
layer, the gate's form, the router) is in the configuration's file under
`assumed`.  Weights are upcast a layer (an expert) at a time.
"""

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512


def check_supported(cfg: dict) -> None:
    unsupported = []
    if cfg.get("model_type") != "solar_open2":
        unsupported.append(f"model_type={cfg.get('model_type')}")
    if cfg.get("first_k_dense_replace", 0):
        unsupported.append("first_k_dense_replace (leading dense layers)")
    if cfg.get("kda_use_full_proj"):
        unsupported.append("kda_use_full_proj")
    if cfg.get("use_rope"):
        unsupported.append("use_rope")
    if cfg.get("n_group", 1) != 1:
        unsupported.append("group-limited routing (n_group > 1)")
    linear = cfg.get("linear_attn_config") or {}
    if linear.get("num_kv_heads") is not None:
        unsupported.append("linear_attn_config.num_kv_heads")
    if cfg.get("n_shared_experts", 1) != 1:
        unsupported.append(f"n_shared_experts={cfg.get('n_shared_experts')}")
    if cfg.get("tie_word_embeddings"):
        unsupported.append("tie_word_embeddings")
    if unsupported:
        raise NotImplementedError(
            "reference/solar_open2.py does not compute: " + ", ".join(unsupported))


def f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * f32(weight)


def conv_silu(x, w):
    """x [T, C]; w [taps, C], w[taps - 1] the current token's tap."""
    t, taps = x.shape[0], w.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x], axis=0)
    return jax.nn.silu(sum(padded[k:k + t] * w[k] for k in range(taps)))


def delta_rule(q, k, v, g, beta):
    """The recurrence token by token: q, k, v, g [T, H, d], beta [T, H] ->
    o [T, H, d]."""
    heads, d = q.shape[1:]

    def step(s, inputs):
        q_t, k_t, v_t, g_t, b_t = inputs
        s = jnp.exp(g_t)[:, :, None] * s
        seen = jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + b_t[:, None, None] * k_t[:, :, None] * (v_t - seen)[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((heads, d, d)), (q, k, v, g, beta))
    return o


def kda(layer: dict, u, cfg: dict):
    """u [T, hidden] float32 -> the mixer's output [T, hidden]."""
    t = u.shape[0]
    linear = cfg["linear_attn_config"]
    heads, d = linear["num_heads"], linear["head_dim"]
    qkv = conv_silu(u @ f32(layer["wqkv"]), f32(layer["conv_w"]))
    q, k, v = (x.reshape(t, heads, d) for x in jnp.split(qkv, 3, axis=-1))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * d ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    f = (u @ f32(layer["wf_a"])) @ f32(layer["wf_b"]) + f32(layer["dt_bias"])
    g = -jnp.exp(f32(layer["A_log"]))[None, :, None] * jax.nn.softplus(
        f.reshape(t, heads, d))
    beta = jax.nn.sigmoid(u @ f32(layer["w_beta"]))
    if cfg.get("kda_allow_neg_eigval"):
        beta = 2.0 * beta
    o = delta_rule(q, k, v, g, beta)
    gate = jax.nn.sigmoid((u @ f32(layer["wg_a"])) @ f32(layer["wg_b"]))
    normed = rms_norm(o, layer["o_norm"], cfg.get("rms_norm_eps", 1e-5))
    return (normed.reshape(t, heads * d) * gate) @ f32(layer["wo"])


def attention(layer: dict, u, cfg: dict):
    t = u.shape[0]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    q = (u @ f32(layer["wq"])).reshape(t, heads, d)
    k = jnp.repeat((u @ f32(layer["wk"])).reshape(t, kv_heads, d),
                   heads // kv_heads, axis=1)
    v = jnp.repeat((u @ f32(layer["wv"])).reshape(t, kv_heads, d),
                   heads // kv_heads, axis=1)
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
    out = jnp.concatenate(blocks).reshape(t, heads * d)
    if cfg.get("use_gqa_gate"):
        out = out * jax.nn.sigmoid(u @ f32(layer["wg"]))
    return out @ f32(layer["wo"])


def gated(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ f32(w_gate)) * (x @ f32(w_up))) @ f32(w_down)


def route(layer: dict, x, cfg: dict):
    """x [T, hidden] -> (weights [T, k], experts [T, k]) over every expert
    the router scores."""
    scores = jax.nn.sigmoid(x @ f32(layer["router"]))
    _, idx = jax.lax.top_k(scores + f32(layer["router_bias"]),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return w * cfg.get("routed_scaling_factor", 1.0), idx


def shared_expert(layer: dict, x):
    return gated(x, layer["shared_gate"], layer["shared_up"],
                 layer["shared_down"])


def experts(layer: dict, x, cfg: dict):
    """The shared expert and the held experts' part of the routed sum."""
    w, idx = route(layer, x, cfg)
    w, idx = np.asarray(w), np.asarray(idx)
    out = shared_expert(layer, x)
    first = cfg.get("first_expert", 0)
    for local in range(cfg["n_routed_experts"]):  # dense over those held
        e = first + local
        rows = np.nonzero((idx == e).any(axis=-1))[0]
        if rows.size == 0:
            continue
        weight = (w * (idx == e)).sum(axis=-1)[rows]
        y = gated(x[rows], layer["w_gate"][local], layer["w_up"][local],
                  layer["w_down"][local])
        out = out.at[rows].add(y * weight[:, None])
    return out


def layer_forward(layer: dict, h, cfg: dict, index: int):
    eps = cfg.get("rms_norm_eps", 1e-5)
    mixer = attention if index in cfg["gqa_layers"] else kda
    h = h + mixer(layer, rms_norm(h, layer["attn_norm"], eps), cfg)
    return h + experts(layer, rms_norm(h, layer["mlp_norm"], eps), cfg)


def forward(params: dict, cfg: dict, tokens) -> jnp.ndarray:
    """Logits [T, vocab] in float32 for one sequence of token ids."""
    check_supported(cfg)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        h = f32(jnp.asarray(params["embed"])[tokens])
        for index, layer in enumerate(params["layers"]):
            h = layer_forward(layer, h, cfg, index)
        h = rms_norm(h, params["final_norm"], cfg.get("rms_norm_eps", 1e-5))
        return h @ f32(params["lm_head"])
