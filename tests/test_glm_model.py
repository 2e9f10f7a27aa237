"""`model_type: glm4_moe_lite` (GLM-4.7-Flash): latent attention in every
layer (models/latent.py, the absorbed form over one compressed row a token)
and routed experts behind a leading dense layer (models/moe.py), held to
the plain reference benchmark/reference/glm4_moe_lite.py (K and V
materialised per head) at tiny sizes, float32, seeded random weights.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_tpu.engine import kvcache
from kserve_tpu.models import latent, llama

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: 1 dense + 2 expert layers, 8 experts 2 a token and a shared one, 4 heads,
#: the five latent widths small and unequal
CFG = {
    "model_type": "glm4_moe_lite", "vocab_size": 320, "hidden_size": 64,
    "intermediate_size": 160, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 8,
    "q_lora_rank": 24, "kv_lora_rank": 40, "qk_nope_head_dim": 12,
    "qk_rope_head_dim": 8, "v_head_dim": 20, "n_routed_experts": 8,
    "n_shared_experts": 1, "num_experts_per_tok": 2,
    "moe_intermediate_size": 48, "first_k_dense_replace": 1,
    "routed_scaling_factor": 1.8, "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc", "rms_norm_eps": 1e-5,
    "rope_theta": 1000000, "rope_scaling": None, "partial_rotary_factor": 1,
    "tie_word_embeddings": False, "hidden_act": "silu",
    "attention_bias": False, "num_nextn_predict_layers": 1,
    "max_position_embeddings": 4096}
PAGE = 4


def _reference():
    path = os.path.join(ROOT, "benchmark", "reference", "glm4_moe_lite.py")
    spec = importlib.util.spec_from_file_location("reference_glm", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


CONFIG = dataclasses.replace(llama.LlamaConfig.from_hf_config(CFG), dtype="float32")
#: scale 0.1: logits of magnitude ~1; a random router bias so that the
#: comparison exercises it (init leaves it zero)
PARAMS = llama.init_params(CONFIG, jax.random.PRNGKey(1), scale=0.1)
for _i, _layer in enumerate(PARAMS["layers"]):
    if "router_bias" in _layer:
        _layer["router_bias"] = 0.05 * jax.random.normal(
            jax.random.PRNGKey(100 + _i), (8,), jnp.float32)
TOL = dict(rtol=2e-4, atol=2e-5)


def _layout(lanes=4, pages=64):
    return kvcache.StateLayout.of(CONFIG, PAGE, pages, lanes, "float32")


def _packed(slices, lanes=4, width=16, T=None):
    """The mixed program's arguments for `slices`: {lane: (tokens, start)}."""
    n = sum(-(-len(t) // 1) for t, _ in slices.values())
    T = T or n
    toks = np.zeros(T, np.int32)
    seq, pos = -np.ones(T, np.int32), np.zeros(T, np.int32)
    q_start, q_len, kv_start, last = (np.zeros(lanes, np.int32) for _ in range(4))
    at = 0
    for lane, (tokens, start) in sorted(slices.items()):
        k = len(tokens)
        toks[at:at + k], seq[at:at + k] = tokens, lane
        pos[at:at + k] = start + np.arange(k)
        q_start[lane], q_len[lane], kv_start[lane] = at, k, start
        last[lane] = at + k - 1
        at += k
    table = np.zeros((lanes, width), np.int32)
    for lane in range(lanes):
        table[lane] = 1 + lane * width + np.arange(width)
    return (jnp.asarray(toks), jnp.asarray(seq), jnp.asarray(pos),
            jnp.asarray(q_start), jnp.asarray(q_len), jnp.asarray(kv_start)), \
        jnp.asarray(table), jnp.asarray(last)


def _forward(state, slices, params=PARAMS, config=CONFIG, T=None):
    args, table, last = _packed(slices, T=T)
    return llama.forward_ragged(
        params, config, *args, state, table, PAGE, last) + (table,)


PROMPT = np.random.RandomState(0).randint(0, 320, 23).tolist()
OTHER = np.random.RandomState(1).randint(0, 320, 6).tolist()


def test_config_table_and_parameters():
    assert CONFIG.is_hybrid and CONFIG.is_latent and CONFIG.latent_width == 48
    table = CONFIG.layer_table()
    assert [r.kind for r in table] == ["latent_attention"] * 3
    assert [r.writes for r in table] == ["latent_kv"] * 3
    assert [r.ffn for r in table] == ["dense", "experts", "experts"]
    assert CONFIG.n_expert_layers == 2 and CONFIG.moe_router == "sigmoid"
    dense, experts = PARAMS["layers"][0], PARAMS["layers"][1]
    assert dense["w_gate"].shape == (64, 160) and "router" not in dense
    assert experts["w_gate"].shape == (8, 64, 48)
    assert experts["shared_down"].shape == (48, 64)
    assert experts["router_bias"].dtype == jnp.float32
    assert dense["wkv_a"].shape == (64, 48) and dense["wkv_b"].shape == (40, 4 * 32)
    assert dense["wq_b"].shape == (24, 4 * 20) and dense["wo"].shape == (80, 64)
    assert PARAMS["lm_head"].shape == (64, 320) and "final_norm_b" not in PARAMS
    # a Llama-family model's table says `dense` (Mixtral's: `experts`)
    assert {r.ffn for r in llama.LlamaConfig.tiny().layer_table()} == {"dense"}
    assert {r.ffn for r in llama.LlamaConfig.tiny(n_experts=4).layer_table()} == {
        "experts"}


def test_the_cache_holds_one_latent_row_a_token_and_layer():
    """The published model cut to 8 layers: 576 values a token and layer,
    stored in 640 columns; no K/V planes, no heads."""
    published = dict(CFG, hidden_size=2048, num_hidden_layers=8,
                     num_attention_heads=20, q_lora_rank=768, kv_lora_rank=512,
                     qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
                     n_routed_experts=64, num_experts_per_tok=4)
    mc = llama.LlamaConfig.from_hf_config(published)
    layout = kvcache.StateLayout.of(mc, 64, 100, 48)
    assert mc.latent_width == 576 and layout.latent_row == 640
    assert layout.paged_layers == () and len(layout.latent_layers) == 8
    assert layout.token_bytes() == 8 * 640 * 2 <= 8 * 1280
    assert layout.bytes_per_token() == {"shared_kv": 0, "latent_kv": 10240}
    assert layout.page_bytes() == 64 * 10240
    assert layout.bytes_in_use(3, 7)["latent_kv"] == 7 * 64 * 10240
    assert layout.expert_layers == 7
    state = jax.eval_shape(layout.init_state)
    assert [a.shape for a in state["latent"]] == [(100, 1, 1, 64, 640)] * 8
    assert state["paged"] == [] and state["stats"][0].shape == (2,)
    # the tiny one: 48 values in 128 columns, float32
    assert _layout().token_bytes() == 3 * 128 * 4


def test_whole_prompt_chunks_packed_dispatch_and_decode_agree_with_the_reference():
    """A 23-token prompt prefilled whole, and in two chunks (the second
    reads the first's latent pages) packed beside another lane's whole
    prompt; then decode steps through the cache over several 4-token pages."""
    ref = _reference()
    want = np.asarray(ref.forward(PARAMS, CFG, PROMPT))
    other = np.asarray(ref.forward(PARAMS, CFG, OTHER))
    whole, state_whole, _ = _forward(_layout().init_state(), {0: (PROMPT, 0)})
    np.testing.assert_allclose(np.asarray(whole[0]), want[-1], **TOL)
    first, state, _ = _forward(_layout().init_state(), {0: (PROMPT[:15], 0)})
    np.testing.assert_allclose(np.asarray(first[0]), want[14], **TOL)
    second, state, table = _forward(
        state, {0: (PROMPT[15:], 15), 2: (OTHER, 0)}, T=24)
    np.testing.assert_allclose(np.asarray(second[0]), want[-1], **TOL)
    np.testing.assert_allclose(np.asarray(second[2]), other[-1], **TOL)
    # the two ways leave the same rows in lane 0's pages
    for a, b in zip(state_whole["latent"], state["latent"]):
        np.testing.assert_allclose(np.asarray(a[1:7]), np.asarray(b[1:7]), **TOL)
    # decode: both lanes, 6 steps, teacher-forced on the reference's argmax
    seqs = {0: list(PROMPT), 2: list(OTHER)}
    tokens = {0: int(want[-1].argmax()), 2: int(other[-1].argmax())}
    for _ in range(6):
        pos = jnp.asarray([len(seqs[0]), 0, len(seqs[2]), 0], jnp.int32)
        step = jnp.asarray([tokens[0], 0, tokens[2], 0], jnp.int32)
        logits, state = llama.decode_step(
            PARAMS, CONFIG, step, pos, state, table,
            jnp.asarray([True, False, True, False]), PAGE)
        for lane in (0, 2):
            seqs[lane].append(tokens[lane])
            row = np.asarray(ref.forward(PARAMS, CFG, seqs[lane]))[-1]
            np.testing.assert_allclose(np.asarray(logits[lane]), row, **TOL)
            tokens[lane] = int(row.argmax())
    assert len(seqs[0]) == 29  # lane 0 decoded across pages 6 and 7


def test_the_absorbed_form_equals_the_materialised_one():
    """One layer's mixer: queries over the compressed row (absorbed, what
    both programs run) against K and V built per head from it."""
    layer = PARAMS["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(3), (9, 64), jnp.float32)
    pos = jnp.arange(9, dtype=jnp.int32)
    queries, rows = latent.project(layer, u, pos, CONFIG, 128)
    assert queries.shape == (9, 4, 128) and rows.shape == (9, 128)
    assert not np.asarray(rows[:, 48:]).any() and not np.asarray(queries[..., 48:]).any()
    scores = jnp.einsum("qhr,kr->hqk", queries, rows) * latent.scale(CONFIG)
    scores = jnp.where(jnp.tril(jnp.ones((9, 9), bool))[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,kc->qhc", jax.nn.softmax(scores, -1), rows[:, :40])
    absorbed = latent.output(layer, attn, CONFIG)
    materialised = _reference().attention(layer, u, CFG)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(materialised), **TOL)


def _broken_references():
    """The reference with one published detail changed: each must leave
    the program's logits by far more than TOL."""
    def scale_by_nope(ref):
        attention = ref.attention

        def wrong(layer, x, cfg):  # scores / sqrt(nope) instead of sqrt(nope + rope)
            return attention(
                dict(layer, wq_b=layer["wq_b"] * (20.0 / 12.0) ** 0.5), x, cfg)
        ref.attention = wrong

    def rope_on_nope(ref):
        attention = ref.attention

        def wrong(layer, x, cfg):  # the query's head columns in reverse:
            # its rope falls on columns the key holds as nope
            wq_b = layer["wq_b"].reshape(24, 4, 20)[..., ::-1]
            return attention(dict(layer, wq_b=wq_b.reshape(24, 80)), x, cfg)
        ref.attention = wrong

    def bias_in_weights(ref):
        def wrong(layer, x, cfg):
            scores = jax.nn.sigmoid(x @ ref.f32(layer["router"])) + ref.f32(
                layer["router_bias"])
            _, idx = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
            w = jnp.take_along_axis(scores, idx, axis=-1)
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
            return w * cfg["routed_scaling_factor"], idx
        ref.route = wrong

    def no_renormalisation(ref):
        route = ref.route
        ref.route = lambda layer, x, cfg: route(
            layer, x, dict(cfg, norm_topk_prob=False))

    def no_scaling(ref):
        route = ref.route
        ref.route = lambda layer, x, cfg: route(
            layer, x, dict(cfg, routed_scaling_factor=1.0))

    def no_shared_expert(ref):
        experts = ref.experts

        def wrong(layer, x, cfg):
            zero = jnp.zeros_like(layer["shared_down"])
            return experts(dict(layer, shared_down=zero), x, cfg)
        ref.experts = wrong

    return {"scale_by_nope": scale_by_nope, "rope_on_nope": rope_on_nope,
            "bias_in_weights": bias_in_weights,
            "no_renormalisation": no_renormalisation, "no_scaling": no_scaling,
            "no_shared_expert": no_shared_expert}


@pytest.mark.parametrize("fault", sorted(_broken_references()))
def test_a_forward_that_changes_one_published_detail_fails_the_comparison(fault):
    ref = _reference()
    _broken_references()[fault](ref)
    wrong = np.asarray(ref.forward(PARAMS, CFG, PROMPT))[-1]
    served, _, _ = _forward(_layout().init_state(), {0: (PROMPT, 0)})
    right = np.asarray(_reference().forward(PARAMS, CFG, PROMPT))[-1]
    np.testing.assert_allclose(np.asarray(served[0]), right, **TOL)
    assert np.abs(wrong - right).max() > 100 * TOL["atol"], fault


def test_the_expert_sums_ride_the_state():
    """Hits (experts that got a row) and the fullest expert's rows, summed
    over the expert layers of every forward step that ran on the state."""
    state = _layout().init_state()
    _, state, table = _forward(state, {0: (PROMPT, 0)}, T=32)
    hits, peak = (int(v) for v in state["stats"][0])
    # 23 tokens x 2 experts over 8 experts, 2 expert layers; padding rows
    # (9 of the 32) reach no expert
    assert 2 <= hits <= 16 and 2 * -(-46 // 8) <= peak <= 2 * 23
    _, state = llama.decode_step(
        PARAMS, CONFIG, jnp.asarray([1, 0, 0, 0], jnp.int32),
        jnp.asarray([23, 0, 0, 0], jnp.int32), state, table,
        jnp.asarray([True, False, False, False]), PAGE)
    hits2, peak2 = (int(v) for v in state["stats"][0])
    # one live lane: 2 experts a layer, each one row
    assert hits2 == hits + 4 and peak2 == peak + 2


def test_foreign_keys_stay_refused_for_other_model_types():
    for model_type in ("deepseek_v3", "kimi_k2", "qwen3_moe"):
        with pytest.raises(ValueError, match="not supported"):
            llama.LlamaConfig.from_hf_config(dict(CFG, model_type=model_type))
    for extra, named in (({"n_group": 4, "topk_group": 2}, "group-limited"),
                         ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
                         ({"q_lora_rank": None}, "q_lora_rank"),
                         ({"topk_method": "greedy"}, "topk_method"),
                         ({"attention_bias": True}, "attention_bias")):
        with pytest.raises(ValueError, match=named):
            llama.LlamaConfig.from_hf_config(dict(CFG, **extra))
