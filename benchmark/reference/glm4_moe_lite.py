"""Plain reference forward of `model_type: glm4_moe_lite` (GLM-4.7-Flash).

The published layer equations in their FIRST form, in straightforward
`jax.numpy` and float32: one sequence, all positions at once, K and V
materialised per head, full causal attention, a Python loop over the layers
and over the experts that were chosen.  No cache, no kernel, no batching, no
absorbed weights.  It shares no code with `kserve_tpu/`; it reads the
program's parameter pytree as data (weights stored [in, out]):

    embed, final_norm, lm_head, layers[l]{attn_norm, mlp_norm,
      wq_a, q_a_norm, wq_b, wkv_a, kv_a_norm, wkv_b, wo,
      dense rows:  w_gate, w_up, w_down
      expert rows: router, router_bias, w_gate / w_up / w_down [E, ...],
                   shared_gate, shared_up, shared_down}

With H heads, for tokens at positions p = 0..T-1, every layer is

    n   = RMSNorm(h)
    cq  = RMSNorm(n W_dq);  q = cq W_uq -> H x (nope | rope);  q_rope = rope(q_rope, p)
    ckv | kr = n W_dkv;     c = RMSNorm(ckv);  k_rope = rope(kr, p)   (one for all heads)
    k_nope_i | v_i = c W_ukv,i
    s_i[t,u] = (q_nope_i[t] k_nope_i[u] + q_rope_i[t] k_rope[u]) / sqrt(nope + rope),  u <= t
    h  += concat_i(softmax(s_i) v_i) W_o
    n'  = RMSNorm(h)
    l < first_k_dense_replace:  h += W_down(silu(W_gate n') * W_up n')
    else:  s = sigmoid(n' W_r) (float32);  idx = top_k(s + b);
           w = s[idx] / (sum s[idx] + 1e-20) * routed_scaling_factor
           h += sum_j w_j Expert_idx_j(n') + Expert_shared(n')

and logits = RMSNorm(h) W_head.

Departures from the published description: the next-token-prediction module
(`num_nextn_predict_layers`: one more layer behind the stack, a draft head
for self-speculation) is not run: the published causal-LM forward does not
evaluate it and drops its tensors at load, so these ARE the served logits.
What the published config.json leaves to the modeling file (the rope's
pairing of columns, the softmax scale, biases, the router's precision) is in
the configuration's file under `assumed`.  Weights are upcast a layer (an
expert) at a time: the caller holds 10 GB of bf16 parameters.
"""

import jax
import jax.numpy as jnp
import numpy as np


def check_supported(cfg: dict) -> None:
    unsupported = []
    if cfg.get("model_type") != "glm4_moe_lite":
        unsupported.append(f"model_type={cfg.get('model_type')}")
    if cfg.get("rope_scaling"):
        unsupported.append("rope_scaling")
    if cfg.get("attention_bias"):
        unsupported.append("attention_bias")
    if cfg.get("hidden_act", "silu") != "silu":
        unsupported.append(f"hidden_act={cfg.get('hidden_act')}")
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        unsupported.append("group-limited routing (n_group / topk_group > 1)")
    if cfg.get("topk_method", "noaux_tc") != "noaux_tc":
        unsupported.append(f"topk_method={cfg.get('topk_method')}")
    if cfg.get("partial_rotary_factor", 1) != 1:
        unsupported.append("partial_rotary_factor")
    if cfg.get("n_shared_experts", 1) != 1:
        unsupported.append(f"n_shared_experts={cfg.get('n_shared_experts')}")
    if cfg.get("tie_word_embeddings"):
        unsupported.append("tied head")
    if not cfg.get("q_lora_rank"):
        unsupported.append("queries without q_lora_rank")
    if unsupported:
        raise NotImplementedError(
            "reference/glm4_moe_lite.py does not compute: "
            + ", ".join(unsupported))


def f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * f32(weight)


def rope(x, theta):
    """x: [T, heads, d]; rotate-half pairing, position t = row t."""
    t, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(layer: dict, x, cfg: dict):
    """x [T, hidden] float32 -> the mixer's output [T, hidden]."""
    t = x.shape[0]
    heads = cfg["num_attention_heads"]
    nope, rp = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
    eps, theta = cfg.get("rms_norm_eps", 1e-5), cfg.get("rope_theta", 10000.0)
    cq = rms_norm(x @ f32(layer["wq_a"]), layer["q_a_norm"], eps)
    q = (cq @ f32(layer["wq_b"])).reshape(t, heads, nope + rp)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], theta)
    ckv = x @ f32(layer["wkv_a"])
    c = rms_norm(ckv[:, :rank], layer["kv_a_norm"], eps)
    k_rope = rope(ckv[:, None, rank:], theta)  # [T, 1, rope]
    kv = (c @ f32(layer["wkv_b"])).reshape(t, heads, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (t, heads, rp))], axis=-1)
    v = kv[..., nope:]
    scores = jnp.einsum(
        "qhd,khd->hqk", jnp.concatenate([q_nope, q_rope], axis=-1), k)
    scores = scores / jnp.sqrt(jnp.float32(nope + rp))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, heads * vd) @ f32(layer["wo"])


def gated(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ f32(w_gate)) * (x @ f32(w_up))) @ f32(w_down)


def route(layer: dict, x, cfg: dict):
    """x [T, hidden] -> (weights [T, k], experts [T, k])."""
    scores = jax.nn.sigmoid(x @ f32(layer["router"]))
    k = cfg["num_experts_per_tok"]
    _, idx = jax.lax.top_k(scores + f32(layer["router_bias"]), k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return w * cfg.get("routed_scaling_factor", 1.0), idx


def experts(layer: dict, x, cfg: dict):
    w, idx = route(layer, x, cfg)
    w, idx = np.asarray(w), np.asarray(idx)
    out = gated(x, layer["shared_gate"], layer["shared_up"],
                layer["shared_down"])
    for e in np.unique(idx):  # the experts that were chosen, one at a time
        rows = np.nonzero((idx == e).any(axis=-1))[0]
        weight = (w * (idx == e)).sum(axis=-1)[rows]
        y = gated(x[rows], layer["w_gate"][e], layer["w_up"][e],
                  layer["w_down"][e])
        out = out.at[rows].add(y * weight[:, None])
    return out


def layer_forward(layer: dict, x, cfg: dict, index: int):
    eps = cfg.get("rms_norm_eps", 1e-5)
    x = x + attention(layer, rms_norm(x, layer["attn_norm"], eps), cfg)
    n = rms_norm(x, layer["mlp_norm"], eps)
    if index < cfg.get("first_k_dense_replace", 0):
        return x + gated(n, layer["w_gate"], layer["w_up"], layer["w_down"])
    return x + experts(layer, n, cfg)


def forward(params: dict, cfg: dict, tokens) -> jnp.ndarray:
    """Logits [T, vocab] in float32 for one sequence of token ids."""
    check_supported(cfg)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = f32(jnp.asarray(params["embed"])[tokens])
        for index, layer in enumerate(params["layers"]):
            x = layer_forward(layer, x, cfg, index)
        x = rms_norm(x, params["final_norm"], cfg.get("rms_norm_eps", 1e-5))
        return x @ f32(params["lm_head"])
