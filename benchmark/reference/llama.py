"""Plain reference forward of the Llama family (Llama, Mistral, Qwen3).

The published layer equations in straightforward `jax.numpy` and float32:
no cache, no kernels, no batching, no sharding — one sequence, all positions
at once, full causal attention.  It shares no code with
`kserve_tpu/models/llama.py`; it reads the same parameter pytree as data
(`embed`, `layers[i]{attn_norm, wq, wk, wv, wo, mlp_norm, w_gate, w_up,
w_down[, q_norm, k_norm]}`, `final_norm`[, `lm_head`]; weights stored
[in, out]).

Departures from the published description: none for Mistral-7B-v0.3 and
Qwen3-4B as configured here (no sliding window in either config; rope
without scaling).  Configs that need what this file does not compute
(rope scaling, attention bias, sliding windows, softcaps, experts) raise.
"""

import jax
import jax.numpy as jnp


def check_supported(cfg: dict) -> None:
    unsupported = []
    if cfg.get("rope_scaling"):
        unsupported.append("rope_scaling")
    if cfg.get("attention_bias"):
        unsupported.append("attention_bias")
    if cfg.get("sliding_window") and cfg.get("use_sliding_window", True) \
            and cfg.get("model_type") not in ("qwen3", "mistral"):
        unsupported.append("sliding_window")
    if cfg.get("num_local_experts") or cfg.get("num_experts"):
        unsupported.append("experts")
    if cfg.get("hidden_act", "silu") != "silu":
        unsupported.append(f"hidden_act={cfg.get('hidden_act')}")
    if unsupported:
        raise NotImplementedError(
            "reference/llama.py does not compute: " + ", ".join(unsupported))


def rms_norm(x, weight, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def rope(x, theta):
    """x: [T, heads, d]; rotate-half layout, position t = row t."""
    t, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer_forward(layer: dict, x, cfg: dict):
    """One decoder layer on x: [T, hidden] float32."""
    f32 = lambda a: jnp.asarray(a).astype(jnp.float32)  # noqa: E731
    t = x.shape[0]
    nq = cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads", nq)
    d = cfg.get("head_dim") or cfg["hidden_size"] // nq
    eps = cfg.get("rms_norm_eps", 1e-5)
    h = rms_norm(x, layer["attn_norm"], eps)
    q = (h @ f32(layer["wq"])).reshape(t, nq, d)
    k = (h @ f32(layer["wk"])).reshape(t, nkv, d)
    v = (h @ f32(layer["wv"])).reshape(t, nkv, d)
    if "q_norm" in layer:  # Qwen3: per-head RMSNorm before rope
        q = rms_norm(q, layer["q_norm"], eps)
        k = rms_norm(k, layer["k_norm"], eps)
    theta = cfg.get("rope_theta", 10000.0)
    q, k = rope(q, theta), rope(k, theta)
    group = nq // nkv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(t, nq * d) @ f32(layer["wo"])
    h = rms_norm(x, layer["mlp_norm"], eps)
    gate = jax.nn.silu(h @ f32(layer["w_gate"]))
    return x + (gate * (h @ f32(layer["w_up"]))) @ f32(layer["w_down"])


def forward(params: dict, cfg: dict, tokens) -> jnp.ndarray:
    """Logits [T, vocab] in float32 for one sequence of token ids."""
    check_supported(cfg)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = jnp.asarray(params["embed"])[tokens].astype(jnp.float32)
        step = jax.jit(lambda layer, x: layer_forward(layer, x, cfg))
        for layer in params["layers"]:
            x = step(layer, x)
        x = rms_norm(x, params["final_norm"], cfg.get("rms_norm_eps", 1e-5))
        head = params.get("lm_head")
        if head is None:  # tied embeddings
            return x @ jnp.asarray(params["embed"]).astype(jnp.float32).T
        return x @ jnp.asarray(head).astype(jnp.float32)
