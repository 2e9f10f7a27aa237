"""Plain reference forward of `model_type: cohere2_moe` (Command A+,
`command-a-plus-05-2026`), the language model.

The published layer equations in straightforward `jax.numpy` and float32:
one sequence, all positions at once, a Python loop over the layers, over
the experts held and over the shared experts.  No cache, no kernel, no
batching.  It shares no code with `kserve_tpu/`; it reads the program's
parameter pytree as data (weights stored [in, out]):

    embed [vocab, hidden], final_norm, layers[l]: attn_norm, wq, wk, wv, wo,
      router [hidden, scored], w_gate / w_up [held, hidden, width],
      w_down [held, width, hidden], shared_gate / shared_up
      [hidden, shared x width], shared_down [shared x width, hidden]

Every layer is ONE block (`use_parallel_block`), with h [T, hidden]:

    u = LN(h) = (h - mean) / sqrt(var + layer_norm_eps) * w       (no bias)
    q = u W_q [T, heads, d];  k = u W_k, v = u W_v [T, K/V heads, d]
    `sliding_attention` layers: q, k turned by rotary over the whole head in
      the INTERLEAVED pairing (`rope_gptj`): columns (2j, 2j+1) turn by
      pos x theta^(-2j/d); key j visible to query i iff 0 <= i - j < window
    `full_attention` layers: NO positional encoding; causal
    A = concat_heads(softmax(q k^T / sqrt(d)) v) W_o
    s = sigmoid(u W_r) in float32 over every expert the router scores
    idx = top_k(s);  g = s[idx] / sum(s[idx])                     (norm_topk_prob)
    routed = sum_k g_k down_e(silu(gate_e u) * up_e u),  e = idx_k
    shared = 1 / n_shared x sum_s down_s(silu(gate_s u) * up_s u) (average)
    h' = h + A + routed + shared                                  (one residual)

and logits = logit_scale x LN_f(h) E^T on the tied embedding.

Departures from the published description.  (1) The share: this chip holds
experts `first_expert .. first_expert + num_experts - 1` of the
`router_n_experts` the router scores (the benchmark's configuration: 16 of
128).  A pair routed to an expert that is not held adds NOTHING here, in
the program and in this reference alike: it is the other chips' part of
the sum, and the weights are normalised over all the experts chosen, held
or not.  With every expert held (`router_n_experts` absent) this is the
published layer.  (2) A sliced vocabulary is a smaller vocabulary: the
embedding has `vocab_size` rows and the logits are over them.  (3) Storage,
not mathematics: the program keeps the shared experts' matrices side by
side (expert s is columns [s w, (s + 1) w) of `shared_gate` / `shared_up`
and the same rows of `shared_down`); they are cut apart here and each
expert is computed by itself, then the four are averaged.  (4) The vision
tower is not part of the language model's config and is left out.  What the
published config.json leaves to the modeling file (the bias-free LayerNorm,
no positions on full layers, "average" = the mean over the shared experts)
is in the configuration's file under `assumed`.  Attention is computed a
block of queries at a time and weights are upcast an expert at a time, so
that 5000 tokens at 128 heads fit a host's memory: the arithmetic of each
row is the same.
"""

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256


def check_supported(cfg: dict) -> None:
    unsupported = []
    if cfg.get("model_type") != "cohere2_moe":
        unsupported.append(f"model_type={cfg.get('model_type')}")
    kinds = cfg.get("layer_types") or []
    if (len(kinds) != cfg["num_hidden_layers"]
            or set(kinds) - {"sliding_attention", "full_attention"}):
        unsupported.append(f"layer_types={kinds!r}")
    if not cfg.get("use_parallel_block", True):
        unsupported.append("use_parallel_block false")
    if cfg.get("first_k_dense_replace", 0):
        unsupported.append("first_k_dense_replace (prefix dense layers)")
    if cfg.get("position_embedding_type", "rope_gptj") != "rope_gptj":
        unsupported.append(
            f"position_embedding_type={cfg.get('position_embedding_type')}")
    if cfg.get("rotary_pct", 1) != 1:
        unsupported.append(f"rotary_pct={cfg.get('rotary_pct')}")
    if (cfg.get("rope_parameters") or {}).get("rope_type", "default") != "default":
        unsupported.append("rope scaling")
    if cfg.get("expert_selection_fn", "sigmoid") != "sigmoid":
        unsupported.append(f"expert_selection_fn={cfg.get('expert_selection_fn')}")
    if cfg.get("shared_expert_combination_strategy", "average") != "average":
        unsupported.append("shared_expert_combination_strategy="
                           f"{cfg.get('shared_expert_combination_strategy')}")
    for key in ("attention_bias", "use_qk_norm", "use_parallel_embedding"):
        if cfg.get(key):
            unsupported.append(key)
    for key in ("use_gated_activation", "tie_word_embeddings", "norm_topk_prob"):
        if not cfg.get(key, True):
            unsupported.append(f"{key} false")
    if cfg.get("hidden_act", "silu") != "silu":
        unsupported.append(f"hidden_act={cfg.get('hidden_act')}")
    if unsupported:
        raise NotImplementedError(
            "reference/cohere2_moe.py does not compute: " + ", ".join(unsupported))


def f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def layer_norm(x, weight, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * f32(weight)


def rotary_interleaved(x, theta: float):
    """x [T, heads, d] at positions 0..T-1: columns (2j, 2j+1) are a complex
    number turned by pos x theta^(-2j/d)."""
    t, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)  # [d/2]
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs  # [T, d/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(
        x.shape)


def attention(layer: dict, u, cfg: dict, kind: str):
    t = u.shape[0]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    q = (u @ f32(layer["wq"])).reshape(t, heads, d)
    k = (u @ f32(layer["wk"])).reshape(t, kv_heads, d)
    v = (u @ f32(layer["wv"])).reshape(t, kv_heads, d)
    sliding = kind == "sliding_attention"
    if sliding:
        theta = float((cfg.get("rope_parameters") or {}).get(
            "rope_theta", cfg.get("rope_theta", 10000.0)))
        q, k = rotary_interleaved(q, theta), rotary_interleaved(k, theta)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    key_pos = jnp.arange(t)
    blocks = []
    for start in range(0, t, QUERY_BLOCK):  # a block of queries at a time
        query_pos = jnp.arange(start, min(start + QUERY_BLOCK, t))
        dist = query_pos[:, None] - key_pos[None, :]
        seen = dist >= 0
        if sliding:
            seen = seen & (dist < cfg["sliding_window"])
        scores = jnp.einsum("qhd,khd->hqk", q[start:start + QUERY_BLOCK], k
                            ) / jnp.sqrt(jnp.float32(d))
        scores = jnp.where(seen[None], scores, -jnp.inf)
        blocks.append(jnp.einsum(
            "hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(blocks).reshape(t, heads * d) @ f32(layer["wo"])


def gated(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ f32(w_gate)) * (x @ f32(w_up))) @ f32(w_down)


def route(layer: dict, u, cfg: dict):
    """u [T, hidden] -> (weights [T, k], experts [T, k]) over every expert
    the router scores."""
    scores = jax.nn.sigmoid(u @ f32(layer["router"]))
    _, idx = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return w / w.sum(axis=-1, keepdims=True), idx


def routed_experts(layer: dict, u, cfg: dict):
    """The held experts' part of the routed sum."""
    w, idx = route(layer, u, cfg)
    w, idx = np.asarray(w), np.asarray(idx)
    out = jnp.zeros_like(u)
    first = cfg.get("first_expert", 0)
    for local in range(cfg["num_experts"]):  # dense over those held
        e = first + local
        rows = np.nonzero((idx == e).any(axis=-1))[0]
        if rows.size == 0:
            continue
        weight = (w * (idx == e)).sum(axis=-1)[rows]
        y = gated(u[rows], layer["w_gate"][local], layer["w_up"][local],
                  layer["w_down"][local])
        out = out.at[rows].add(y * weight[:, None])
    return out


def shared_experts(layer: dict, u, cfg: dict):
    """The shared experts, each by itself, AVERAGED."""
    n, width = cfg["num_shared_experts"], cfg["intermediate_size"]
    total = jnp.zeros_like(u)
    for s in range(n):
        cols = slice(s * width, (s + 1) * width)
        total = total + gated(u, layer["shared_gate"][:, cols],
                              layer["shared_up"][:, cols],
                              layer["shared_down"][cols, :])
    return total / n


def layer_forward(layer: dict, h, cfg: dict, kind: str):
    u = layer_norm(h, layer["attn_norm"], cfg.get("layer_norm_eps", 1e-5))
    out = h + attention(layer, u, cfg, kind) + routed_experts(layer, u, cfg)
    if cfg.get("num_shared_experts", 0):
        out = out + shared_experts(layer, u, cfg)
    return out


def forward(params: dict, cfg: dict, tokens) -> jnp.ndarray:
    """Logits [T, vocab] in float32 for one sequence of token ids."""
    check_supported(cfg)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        h = f32(jnp.asarray(params["embed"])[tokens])
        for layer, kind in zip(params["layers"], cfg["layer_types"]):
            h = layer_forward(layer, h, cfg, kind)
        h = layer_norm(h, params["final_norm"], cfg.get("layer_norm_eps", 1e-5))
        return cfg.get("logit_scale", 1) * (h @ f32(params["embed"]).T)
