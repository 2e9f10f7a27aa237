"""Plain reference forward of the Ouro family (looped language models,
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741;
`model_type: ouro`).

The published equations in straightforward `jax.numpy` and float32: one
sequence, all positions at once, full causal attention over the whole
sequence, Python loops over the passes and over the layers; no cache, no
kernels, no batching, no sharding.  It shares no code with `kserve_tpu/`;
it reads the same parameter pytree as data (`embed`, `layers[i]{attn_norm,
wq, wk, wv, wo, post_attn_norm, mlp_norm, w_gate, w_up, w_down,
post_mlp_norm}`, `final_norm`, `lm_head`, `exit_gate_w`, `exit_gate_b`;
weights stored [in, out]).

    h = E[x]
    for u in 0 .. total_ut_steps - 1:          # ONE set of weights
      for l in 0 .. layers - 1:
        a = Wo . softmax_causal(rope(Wq n) rope(Wk n)^T / sqrt(d)) (Wv n),  n = RMSNorm(h; g1)
        h = h + RMSNorm(a; g2)
        m = Wdown (silu(Wgate n') * Wup n'),                               n' = RMSNorm(h; g3)
        h = h + RMSNorm(m; g4)
      h = RMSNorm(h; g_final)                  # closes EVERY pass, feeds the next
      lambda_u = sigmoid(w_exit . h + b_exit)  # the exit gate
    logits = W_head . h                        # of the pass the exit rule picks

Departures from the published description, each with its reason:

- The exit gate is computed (`exit_gates`) but does not choose the pass: at
  the published `early_exit_threshold` 1 the cumulative exit distribution
  reaches 1 only at the last pass (the last pass takes all the remaining
  mass), so the served logits are the last pass's, exactly.  A threshold
  under 1 raises in `check_supported`.
- Every pass attends over its OWN keys and values, recomputed here from the
  whole sequence: that is what a cache indexed by (pass, layer) holds.  The
  paper's reuse of the last pass's cache at decode is an approximation the
  published config does not turn on; it is not computed here.
- Rope over the whole head in the rotate-half layout, no scaling
  (`rope_scaling: null`); no bias on any projection; no sliding window
  (`use_sliding_window: false`).  Configs that ask for any of these raise.
"""

import jax
import jax.numpy as jnp


def check_supported(cfg: dict) -> None:
    unsupported = []
    if cfg.get("model_type") != "ouro":
        unsupported.append(f"model_type={cfg.get('model_type')}")
    if float(cfg.get("early_exit_threshold", 1.0)) < 1.0:
        unsupported.append(
            f"early_exit_threshold={cfg.get('early_exit_threshold')} < 1")
    if cfg.get("rope_scaling"):
        unsupported.append("rope_scaling")
    if cfg.get("attention_bias"):
        unsupported.append("attention_bias")
    if cfg.get("sliding_window") and cfg.get("use_sliding_window"):
        unsupported.append("sliding_window")
    if cfg.get("tie_word_embeddings"):
        unsupported.append("tie_word_embeddings")
    if cfg.get("hidden_act", "silu") != "silu":
        unsupported.append(f"hidden_act={cfg.get('hidden_act')}")
    if unsupported:
        raise NotImplementedError(
            "reference/ouro.py does not compute: " + ", ".join(unsupported))


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
    """One decoder layer, four norms, on x: [T, hidden] float32."""
    f32 = lambda a: jnp.asarray(a).astype(jnp.float32)  # noqa: E731
    t = x.shape[0]
    nq = cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads", nq)
    d = cfg.get("head_dim") or cfg["hidden_size"] // nq
    eps = cfg.get("rms_norm_eps", 1e-6)
    theta = cfg.get("rope_theta", 10000.0)
    n = rms_norm(x, layer["attn_norm"], eps)
    q = rope((n @ f32(layer["wq"])).reshape(t, nq, d), theta)
    k = rope((n @ f32(layer["wk"])).reshape(t, nkv, d), theta)
    v = (n @ f32(layer["wv"])).reshape(t, nkv, d)
    k = jnp.repeat(k, nq // nkv, axis=1)
    v = jnp.repeat(v, nq // nkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    a = attn.reshape(t, nq * d) @ f32(layer["wo"])
    x = x + rms_norm(a, layer["post_attn_norm"], eps)
    n = rms_norm(x, layer["mlp_norm"], eps)
    m = (jax.nn.silu(n @ f32(layer["w_gate"])) * (n @ f32(layer["w_up"]))) \
        @ f32(layer["w_down"])
    return x + rms_norm(m, layer["post_mlp_norm"], eps)


def hidden_states(params: dict, cfg: dict, tokens):
    """The normed hidden state [T, hidden] after every pass, first to last."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.asarray(params["embed"])[tokens].astype(jnp.float32)
    step = jax.jit(lambda layer, x: layer_forward(layer, x, cfg))
    eps = cfg.get("rms_norm_eps", 1e-6)
    after = []
    for _ in range(int(cfg["total_ut_steps"])):
        for layer in params["layers"]:
            x = step(layer, x)
        x = rms_norm(x, params["final_norm"], eps)
        after.append(x)
    return after


def exit_gates(params: dict, cfg: dict, tokens) -> jnp.ndarray:
    """lambda_u = sigmoid(w_exit . h_u + b_exit), [passes, T]: what the exit
    rule would weigh; at threshold 1 it changes nothing that is served."""
    with jax.default_matmul_precision("highest"):
        w = jnp.asarray(params["exit_gate_w"]).astype(jnp.float32)
        b = jnp.asarray(params["exit_gate_b"]).astype(jnp.float32)
        return jnp.stack([jax.nn.sigmoid((h @ w)[:, 0] + b[0])
                          for h in hidden_states(params, cfg, tokens)])


def forward(params: dict, cfg: dict, tokens) -> jnp.ndarray:
    """Logits [T, vocab] in float32 for one sequence of token ids."""
    check_supported(cfg)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, cfg, tokens)[-1]
        return x @ jnp.asarray(params["lm_head"]).astype(jnp.float32)
