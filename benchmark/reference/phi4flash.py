"""Plain reference forward of `model_type: phi4flash` (Phi-4-mini-flash-reasoning).

The SambaY decoder-hybrid-decoder (arXiv:2507.06607) with differential
attention (arXiv:2410.05258), written down from its equations in
straightforward `jax.numpy` and float32: one sequence, all positions at
once, a sequential scan, no cache, no kernels, no packing.  It shares no
code with `kserve_tpu/`; it reads the program's parameter pytree as data
(`embed`, `final_norm`, `final_norm_b`, `layers[i]{...}`, weights stored
[in, out]).

With d = hidden, L layers, half = L / 2, every layer l is

    h <- h + Mixer_l(LN_a(h));   h <- h + W_down(silu(W_gate v) * (W_up v)),  v = LN_b(h)

LN LayerNorm with weight and bias; after the last layer a LayerNorm and
logits = h E^T with the tied embedding.  No positional term anywhere.
Mixer by index: l < half even: Mamba; l < half odd: window attention;
l = half: Mamba, whose scan output m is kept; l = half + 1: full attention,
whose K, V are kept; beyond, l even: gated memory unit on m; l odd:
cross-attention onto the kept K, V.

Departures from the published description: none known.  What the published
config.json does not give (the Mamba sizes, biases, the pairing of heads,
the layer rule above) is in the configuration's file under `assumed`.
"""

import math

import jax
import jax.numpy as jnp

_KNOWN_KEYS = {
    "model_type", "vocab_size", "hidden_size", "intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "layer_norm_eps", "max_position_embeddings", "mb_per_layer",
    "sliding_window", "tie_word_embeddings", "mlp_bias", "lm_head_bias",
    "hidden_act", "embd_pdrop", "resid_pdrop", "attention_bias",
    "attention_out_bias", "mamba_d_inner", "mamba_d_state", "mamba_d_conv",
    "mamba_dt_rank", "torch_dtype", "architectures", "diff_attention_pairing",
}


def check_supported(cfg: dict) -> None:
    unsupported = sorted(set(cfg) - _KNOWN_KEYS)
    if cfg.get("model_type") != "phi4flash":
        unsupported.append(f"model_type={cfg.get('model_type')}")
    if cfg.get("hidden_act", "silu") != "silu":
        unsupported.append(f"hidden_act={cfg.get('hidden_act')}")
    if cfg.get("mb_per_layer", 2) != 2 or cfg["num_hidden_layers"] % 4:
        unsupported.append("a layer pattern other than mb_per_layer 2 over 4k layers")
    for key in ("mlp_bias", "lm_head_bias", "embd_pdrop", "resid_pdrop"):
        if cfg.get(key):
            unsupported.append(key)
    if not cfg.get("tie_word_embeddings", True):
        unsupported.append("untied head")
    if cfg.get("diff_attention_pairing", "adjacent") != "adjacent":
        unsupported.append("diff_attention_pairing")
    if unsupported:
        raise NotImplementedError(
            "reference/phi4flash.py does not compute: " + ", ".join(unsupported))


def f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def layer_norm(x, weight, bias, eps):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * f32(weight) + f32(bias)


def mixer_kind(l: int, n_layers: int) -> str:
    half = n_layers // 2
    if l < half:
        return "mamba" if l % 2 == 0 else "window"
    if l == half:
        return "mamba"
    if l == half + 1:
        return "full"
    return "gmu" if l % 2 == 0 else "cross"


def mamba(layer, u, cfg):
    """u [T, d] -> (output [T, d], the scan's output before the gate)."""
    t = u.shape[0]
    di = cfg.get("mamba_d_inner", 2 * cfg["hidden_size"])
    n = cfg.get("mamba_d_state", 16)
    taps = cfg.get("mamba_d_conv", 4)
    rank = cfg.get("mamba_dt_rank", -(-cfg["hidden_size"] // 16))
    xz = u @ f32(layer["in_proj"])
    x, z = xz[:, :di], xz[:, di:]
    # depthwise causal convolution: y_t = sum_k w_k x_{t - (taps-1) + k}
    padded = jnp.concatenate([jnp.zeros((taps - 1, di), jnp.float32), x])
    w = f32(layer["conv_w"])
    x = sum(padded[k:k + t] * w[k] for k in range(taps)) + f32(layer["conv_b"])
    x = jax.nn.silu(x)
    dbc = x @ f32(layer["x_proj"])
    dt = jax.nn.softplus(dbc[:, :rank] @ f32(layer["dt_proj"]) + f32(layer["dt_bias"]))
    b, c = dbc[:, rank:rank + n], dbc[:, rank + n:]
    a = -jnp.exp(f32(layer["A_log"]))

    def step(s, inputs):
        x_t, dt_t, b_t, c_t = inputs
        s = jnp.exp(dt_t[:, None] * a) * s + (dt_t * x_t)[:, None] * b_t[None, :]
        return s, s @ c_t

    _, y = jax.lax.scan(step, jnp.zeros((di, n), jnp.float32), (x, dt, b, c))
    y = y + f32(layer["D"]) * x
    return (y * jax.nn.silu(z)) @ f32(layer["out_proj"]), y


def differential_attention(layer, u, k, v, l, cfg, window):
    """u [T, d] queries' input; k, v [T, kv heads, 64] -> [T, d]."""
    t = u.shape[0]
    nq = cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads", nq)
    d = cfg.get("head_dim") or cfg["hidden_size"] // nq
    eps = cfg.get("layer_norm_eps", 1e-5)
    q = u @ f32(layer["wq"])
    if "bq" in layer:
        q = q + f32(layer["bq"])
    q = q.reshape(t, nq // 2, 2, d)  # query pairs (2j, 2j+1)
    k = k.reshape(t, nkv // 2, 2, d)  # K/V pairs (2m, 2m+1)
    v = v.reshape(t, nkv // 2, 2 * d)  # the pair's two value heads side by side
    per_kv = (nq // 2) // (nkv // 2)
    k = jnp.repeat(k, per_kv, axis=1)  # query pair j reads K/V pair j // per_kv
    v = jnp.repeat(v, per_kv, axis=1)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = j <= i
    if window:
        mask = mask & (i - j < window)

    def attend(q_half, k_half):
        scores = jnp.einsum("qhd,khd->hqk", q_half, k_half) / math.sqrt(d)
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khe->qhe", jax.nn.softmax(scores, axis=-1), v)

    lambda_init = 0.8 - 0.6 * jnp.exp(-0.3 * l)
    lam = (jnp.exp(jnp.sum(f32(layer["lambda_q1"]) * f32(layer["lambda_k1"])))
           - jnp.exp(jnp.sum(f32(layer["lambda_q2"]) * f32(layer["lambda_k2"])))
           + lambda_init)
    o = attend(q[:, :, 0], k[:, :, 0]) - lam * attend(q[:, :, 1], k[:, :, 1])
    o = o / jnp.sqrt((o * o).mean(axis=-1, keepdims=True) + eps) * f32(layer["subln"])
    out = (o * (1.0 - lambda_init)).reshape(t, -1) @ f32(layer["wo"])
    return out + f32(layer["bo"]) if "bo" in layer else out


def keys_values(layer, u, cfg):
    t = u.shape[0]
    nkv = cfg.get("num_key_value_heads", cfg["num_attention_heads"])
    k, v = u @ f32(layer["wk"]), u @ f32(layer["wv"])
    if "bk" in layer:
        k, v = k + f32(layer["bk"]), v + f32(layer["bv"])
    return k.reshape(t, nkv, -1), v.reshape(t, nkv, -1)


def layer_forward(layer, x, kept, l, kind, cfg):
    """One layer (index l, a float32 scalar; mixer `kind`) on x [T, d];
    `kept` carries m and the full layer's K, V."""
    eps = cfg.get("layer_norm_eps", 1e-5)
    u = layer_norm(x, layer["attn_norm"], layer["attn_norm_b"], eps)
    kept = dict(kept)
    if kind == "mamba":
        out, kept["m"] = mamba(layer, u, cfg)
    elif kind == "gmu":
        out = (kept["m"] * jax.nn.silu(u @ f32(layer["gmu_in"]))) @ f32(layer["gmu_out"])
    elif kind == "cross":
        out = differential_attention(layer, u, kept["k"], kept["v"], l, cfg, 0)
    else:
        k, v = keys_values(layer, u, cfg)
        if kind == "full":
            kept["k"], kept["v"] = k, v
        out = differential_attention(
            layer, u, k, v, l, cfg, cfg["sliding_window"] if kind == "window" else 0)
    x = x + out
    v = layer_norm(x, layer["mlp_norm"], layer["mlp_norm_b"], eps)
    x = x + (jax.nn.silu(v @ f32(layer["w_gate"])) * (v @ f32(layer["w_up"]))) @ f32(layer["w_down"])
    return x, kept


def forward(params: dict, cfg: dict, tokens) -> jnp.ndarray:
    """Logits [T, vocab] in float32 for one sequence of token ids."""
    check_supported(cfg)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = jnp.asarray(params["embed"])[tokens].astype(jnp.float32)
        kept = {}
        steps = {}  # one compiled layer per mixer kind
        for l, layer in enumerate(params["layers"]):
            kind = mixer_kind(l, cfg["num_hidden_layers"])
            if kind not in steps:
                steps[kind] = jax.jit(
                    lambda layer, x, kept, l, kind=kind: layer_forward(
                        layer, x, kept, l, kind, cfg))
            x, kept = steps[kind](layer, x, kept, jnp.float32(l))
        x = layer_norm(x, params["final_norm"], params["final_norm_b"],
                       cfg.get("layer_norm_eps", 1e-5))
        return x @ jnp.asarray(params["embed"]).astype(jnp.float32).T
