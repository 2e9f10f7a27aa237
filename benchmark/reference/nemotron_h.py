"""Plain reference forward of `model_type: nemotron_h` (Nemotron-3-Nano-30B-A3B).

The published layer equations in straightforward `jax.numpy` and float32:
one sequence, all positions at once for the projections and the attention,
a SEQUENTIAL scan over the tokens for the state-space recurrence (no
chunking), a Python loop over the layers and over the experts held.  No
cache, no kernel, no batching.  It shares no code with `kserve_tpu/`; it
reads the program's parameter pytree as data (weights stored [in, out]):

    embed, final_norm, lm_head, layers[l]:
      M rows: attn_norm, in_proj, conv_w [K, C], conv_b, dt_bias, A_log, D,
              ssm_norm, out_proj
      * rows: attn_norm, wq, wk, wv, wo
      E rows: mlp_norm, router, router_bias, w_up [held, hidden, stored],
              w_down [held, stored, hidden], shared_up, shared_down

ONE sublayer a layer, by the letter p[l] of `hybrid_override_pattern`:

    h <- h + Mixer_l(RMSNorm(h))        (weight, no +1, eps layer_norm_epsilon)

`M`, a Mamba-2 mixer with H heads of P columns, G groups, N state columns,
K taps (d_inner = H P; C = d_inner + 2 G N convolution columns):

    [z | xBC | dt] = u W_in
    xBC = silu(conv1d_causal_depthwise(xBC) + b)        (all C columns)
    x [H, P], B [G, N], C [G, N] = split(xBC);  head h reads group h // (H / G)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)        (per head)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t         (S [H, P, N])
    y_t = S_t C_t + D x_t
    y = RMSNorm_groups(y * silu(z))      (the gate first; the norm over each
                                          of the G groups of columns by itself)
    out = y W_out

`*`, attention: q = u W_q (heads x head_dim), k, v = u W_k, u W_v (K/V heads
x head_dim), NO positional encoding, causal, scale 1 / sqrt(head_dim),
out = a W_o.

`E`, experts: s = sigmoid(u W_r) in float32 over the `router_n_experts`
experts the router scores; idx = top_k(s + b); w = s[idx] / (sum s[idx] +
1e-20) * routed_scaling_factor; a routed expert is W_down relu(W_up u)^2,
the shared expert the same at its own width, for every token.

    out = sum_{j: idx_j held here} w_j Expert_{idx_j}(u) + Expert_shared(u)

and logits = RMSNorm(h) W_head.

Departures from the published description.  (1) The share: this chip holds
experts `first_expert .. first_expert + n_routed_experts - 1` of the
`router_n_experts` the router scores (the benchmark's configuration: 64 of
128).  A pair routed to an expert that is not held adds NOTHING here, in
the program and in this reference alike: it is the other chip's part of the
sum, and the weights are normalised over all the experts chosen, held or
not.  With every expert held (`router_n_experts` absent) this is the
published layer.  (2) `time_step_limit` is taken as (0, inf): dt is not
clamped.  (3) Storage, not mathematics: the program stores a routed
expert's width of 1856 in 2048 columns (the next multiple of 512: what the
chip's grouped matmul tiles best) with zeros behind the width; `relu(0)^2 = 0` times a zero row adds
nothing, so the tensors are multiplied as they come.  What the published
config.json leaves to the modeling file is in the configuration's file under `assumed`.  Weights are upcast a layer (an
expert) at a time: the caller holds 11 GB of bf16 parameters.
"""

import jax
import jax.numpy as jnp
import numpy as np

LETTERS = "ME*"


def check_supported(cfg: dict) -> None:
    unsupported = []
    if cfg.get("model_type") != "nemotron_h":
        unsupported.append(f"model_type={cfg.get('model_type')}")
    pattern = cfg.get("hybrid_override_pattern", "")
    if set(pattern) - set(LETTERS) or len(pattern) != cfg["num_hidden_layers"]:
        unsupported.append(f"hybrid_override_pattern={pattern!r}")
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        unsupported.append("group-limited routing (n_group / topk_group > 1)")
    for key in ("mamba_proj_bias", "mlp_bias", "attention_bias", "use_bias",
                "sliding_window", "residual_in_fp32", "tie_word_embeddings"):
        if cfg.get(key):
            unsupported.append(key)
    if cfg.get("mlp_hidden_act", "relu2") != "relu2":
        unsupported.append(f"mlp_hidden_act={cfg.get('mlp_hidden_act')}")
    if cfg.get("mamba_hidden_act", "silu") != "silu":
        unsupported.append(f"mamba_hidden_act={cfg.get('mamba_hidden_act')}")
    if cfg.get("n_shared_experts", 1) != 1:
        unsupported.append(f"n_shared_experts={cfg.get('n_shared_experts')}")
    if not cfg.get("use_conv_bias", True):
        unsupported.append("use_conv_bias false")
    if unsupported:
        raise NotImplementedError(
            "reference/nemotron_h.py does not compute: " + ", ".join(unsupported))


def f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * f32(weight)


def mamba2(layer: dict, u, cfg: dict):
    """u [T, hidden] float32 -> the mixer's output [T, hidden]."""
    t = u.shape[0]
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n, taps = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    di = heads * p
    conv_dim = di + 2 * groups * n
    zxbcdt = u @ f32(layer["in_proj"])
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:di + conv_dim],
                  zxbcdt[:, di + conv_dim:])
    # depthwise causal convolution: tap k multiplies the token k back
    w = f32(layer["conv_w"])  # [taps, C]; w[taps - 1] is the current token's
    padded = jnp.concatenate([jnp.zeros((taps - 1, conv_dim)), xbc], axis=0)
    xbc = sum(padded[k:k + t] * w[k] for k in range(taps)) + f32(layer["conv_b"])
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :di].reshape(t, heads, p)
    b = jnp.repeat(xbc[:, di:di + groups * n].reshape(t, groups, n),
                   heads // groups, axis=1)  # [T, H, N]
    c = jnp.repeat(xbc[:, di + groups * n:].reshape(t, groups, n),
                   heads // groups, axis=1)
    dt = jax.nn.softplus(dt + f32(layer["dt_bias"]))  # [T, H]
    a = -jnp.exp(f32(layer["A_log"]))  # [H]

    def step(s, inputs):
        x_t, b_t, c_t, dt_t = inputs
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, n)), (x, b, c, dt))
    y = y + f32(layer["D"])[None, :, None] * x
    gated = (y.reshape(t, di) * jax.nn.silu(z)).reshape(t, groups, di // groups)
    var = jnp.mean(gated * gated, axis=-1, keepdims=True)
    normed = (gated * jax.lax.rsqrt(var + cfg.get("layer_norm_epsilon", 1e-5))
              ).reshape(t, di) * f32(layer["ssm_norm"])
    return normed @ f32(layer["out_proj"])


def attention(layer: dict, u, cfg: dict):
    t = u.shape[0]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    q = (u @ f32(layer["wq"])).reshape(t, heads, d)
    k = jnp.repeat((u @ f32(layer["wk"])).reshape(t, kv_heads, d),
                   heads // kv_heads, axis=1)
    v = jnp.repeat((u @ f32(layer["wv"])).reshape(t, kv_heads, d),
                   heads // kv_heads, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, heads * d) @ f32(layer["wo"])


def relu2(x, w_up, w_down):
    return jnp.square(jax.nn.relu(x @ f32(w_up))) @ f32(w_down)


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


def experts(layer: dict, x, cfg: dict):
    """The shared expert and the held experts' part of the routed sum."""
    w, idx = route(layer, x, cfg)
    w, idx = np.asarray(w), np.asarray(idx)
    out = relu2(x, layer["shared_up"], layer["shared_down"])
    first = cfg.get("first_expert", 0)
    for local in range(cfg["n_routed_experts"]):  # dense over those held
        e = first + local
        rows = np.nonzero((idx == e).any(axis=-1))[0]
        if rows.size == 0:
            continue
        weight = (w * (idx == e)).sum(axis=-1)[rows]
        y = relu2(x[rows], layer["w_up"][local], layer["w_down"][local])
        out = out.at[rows].add(y * weight[:, None])
    return out


def layer_forward(layer: dict, x, cfg: dict, letter: str):
    eps = cfg.get("layer_norm_epsilon", 1e-5)
    if letter == "E":
        return x + experts(layer, rms_norm(x, layer["mlp_norm"], eps), cfg)
    u = rms_norm(x, layer["attn_norm"], eps)
    mixer = mamba2 if letter == "M" else attention
    return x + mixer(layer, u, cfg)


def forward(params: dict, cfg: dict, tokens) -> jnp.ndarray:
    """Logits [T, vocab] in float32 for one sequence of token ids."""
    check_supported(cfg)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = f32(jnp.asarray(params["embed"])[tokens])
        for layer, letter in zip(params["layers"],
                                 cfg["hybrid_override_pattern"]):
            x = layer_forward(layer, x, cfg, letter)
        x = rms_norm(x, params["final_norm"],
                     cfg.get("layer_norm_epsilon", 1e-5))
        return x @ f32(params["lm_head"])
