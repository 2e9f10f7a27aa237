"""`model_type: solar_open2` (Solar-Open2): Kimi-delta linear-attention
mixers (ops/delta.kda_*: the gated delta rule with a decay per channel, a
matrix a head) three to every gated grouped-query layer without positions,
routed experts of which this chip holds a share in every layer
(models/moe.py), held to the plain reference
benchmark/reference/solar_open2.py (the recurrence token by token, a dense
loop over the held experts) at tiny sizes, float32, seeded random weights.
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_tpu.engine import kvcache
from kserve_tpu.models import hybrid, llama, moe
from kserve_tpu.ops import delta

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the catalog's row (model-configs guide, Solar-Open2-250B), as published
CATALOG = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 8}

#: one period: a gated GQA row and three KDA rows of 4 heads of 16; 8
#: experts scored, 4 held, 2 a token, a shared one
CFG = dict(
    CATALOG, vocab_size=320, hidden_size=64, intermediate_size=96,
    num_hidden_layers=4, gqa_layers=[0], num_attention_heads=4,
    num_key_value_heads=2, head_dim=16,
    linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                        "num_heads": 4, "num_kv_heads": None},
    moe_intermediate_size=48, n_routed_experts=4, router_n_experts=8,
    first_expert=0, num_experts_per_tok=2, max_position_embeddings=4096)
PAGE = 4


def _reference():
    path = os.path.join(ROOT, "benchmark", "reference", "solar_open2.py")
    spec = importlib.util.spec_from_file_location("reference_solar_open2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _randomised(config, seed=1):
    """scale 0.1: logits of magnitude ~1; the tensors the initialiser leaves
    at a constant (the router's bias, the head norm's weight) random, so
    that the comparison exercises them."""
    params = llama.init_params(config, jax.random.PRNGKey(seed), scale=0.1)
    for i, layer in enumerate(params["layers"]):
        key = jax.random.PRNGKey(100 + i)
        layer["router_bias"] = 0.05 * jax.random.normal(
            key, layer["router_bias"].shape, jnp.float32)
        if "o_norm" in layer:
            layer["o_norm"] = 1.0 + 0.2 * jax.random.normal(
                key, layer["o_norm"].shape, jnp.float32)
    return params


def _config(cfg):
    return dataclasses.replace(
        llama.LlamaConfig.from_hf_config(cfg), dtype="float32")


CONFIG = _config(CFG)
PARAMS = _randomised(CONFIG)
#: float32 on both sides; the chunked form re-associates the recurrence and
#: the packed experts sum in another order.  The same comparison with int8
#: weights reads ~1e-2 (test below)
TOL = dict(rtol=2e-4, atol=3e-5)


def _layout(lanes=4, pages=64, config=CONFIG):
    return kvcache.StateLayout.of(config, PAGE, pages, lanes, "float32")


def _packed(slices, lanes=4, width=16, T=None, align=1):
    """The mixed program's arguments for `slices`: {lane: (tokens, start)},
    each slice at a multiple of `align` (padding between)."""
    T = T or sum(-(-len(t) // align) * align for t, _ in slices.values())
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
        at += -(-k // align) * align
    table = np.zeros((lanes, width), np.int32)
    for lane in range(lanes):
        table[lane] = 1 + lane * width + np.arange(width)
    return (jnp.asarray(toks), jnp.asarray(seq), jnp.asarray(pos),
            jnp.asarray(q_start), jnp.asarray(q_len), jnp.asarray(kv_start)), \
        jnp.asarray(table), jnp.asarray(last)


def _forward(state, slices, params=PARAMS, config=CONFIG, T=None, align=1):
    args, table, last = _packed(slices, T=T, align=align)
    return llama.forward_ragged(
        params, config, *args, state, table, PAGE, last,
        ragged_block=align) + (table,)


PROMPT = np.random.RandomState(0).randint(0, 320, 23).tolist()
OTHER = np.random.RandomState(1).randint(0, 320, 6).tolist()


def test_config_table_and_parameters():
    assert CONFIG.is_hybrid and not CONFIG.is_latent and not CONFIG.one_sublayer
    table = CONFIG.layer_table()
    assert [r.kind for r in table] == ["gqa_attention", "kda", "kda", "kda"]
    assert [r.writes for r in table] == ["paged_kv"] + ["recurrent"] * 3
    assert {r.ffn for r in table} == {"experts"}
    assert CONFIG.n_expert_layers == 4 and CONFIG.has_expert_sums
    assert (CONFIG.n_experts, CONFIG.n_experts_held, CONFIG.first_expert) == (8, 4, 0)
    assert not CONFIG.use_rope and CONFIG.attention_gate and CONFIG.kda_neg_eigval
    assert (CONFIG.kda_n_heads, CONFIG.kda_head_dim, CONFIG.kda_d_conv,
            CONFIG.kda_rank, CONFIG.kda_conv_dim) == (4, 16, 4, 16, 192)
    gqa, kda = PARAMS["layers"][0], PARAMS["layers"][1]
    experts = ["mlp_norm", "router", "router_bias", "shared_down",
               "shared_gate", "shared_up", "w_down", "w_gate", "w_up"]
    assert sorted(gqa) == sorted(
        experts + ["attn_norm", "wg", "wk", "wo", "wq", "wv"])
    assert sorted(kda) == sorted(experts + [
        "A_log", "attn_norm", "conv_w", "dt_bias", "o_norm", "w_beta",
        "wf_a", "wf_b", "wg_a", "wg_b", "wo", "wqkv"])
    assert gqa["wg"].shape == gqa["wq"].shape == (64, 64)
    assert kda["wqkv"].shape == (64, 192) and kda["conv_w"].shape == (4, 192)
    assert kda["wf_a"].shape == (64, 16) and kda["wf_b"].shape == (16, 64)
    assert kda["A_log"].shape == (4,) and kda["dt_bias"].shape == (64,)
    assert {kda[k].dtype for k in ("A_log", "dt_bias")} == {jnp.dtype("float32")}
    # the router keeps its width; the stacked tensors hold the 4 held
    assert kda["router"].shape == (64, 8) and kda["w_gate"].shape == (4, 64, 48)
    assert PARAMS["lm_head"].shape == (64, 320)
    # the draw of the decays: a token keeps 0.9 to 0.999 of a channel
    big = hybrid.init_params(_config(dict(CFG, hidden_size=128)),
                             jax.random.PRNGKey(0))["layers"][1]
    u = jax.random.normal(jax.random.PRNGKey(1), (256, 128), jnp.float32)
    _, g, beta, _ = hybrid._kda_project(big, u, CONFIG)
    kept = np.exp(np.asarray(g))
    assert 0.85 < np.quantile(kept, 0.02) and np.quantile(kept, 0.98) < 0.9996
    assert 0 < float(beta.min()) and float(beta.max()) < 2


def test_the_catalog_row_and_the_cut_file_both_build():
    whole = llama.LlamaConfig.from_hf_config(CATALOG)
    kinds = [r.kind for r in whole.layer_table()]
    assert len(kinds) == 48 and kinds.count("gqa_attention") == 12
    assert [i for i, k in enumerate(kinds) if k == "gqa_attention"] == CATALOG["gqa_layers"]
    assert (whole.n_experts, whole.n_experts_held) == (320, 0)
    with open(os.path.join(ROOT, "benchmark", "configs", "solar-open2.json")) as f:
        cut = json.load(f)
    changed = {k for k in CATALOG if cut[k] != CATALOG[k]}
    assert changed == set(cut["reduced"]) == {
        "num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"}
    assert cut["deployment"]["published"] == {k: CATALOG[k] for k in changed}
    mc = llama.LlamaConfig.from_hf_config(
        {k: v for k, v in cut.items()
         if k not in ("deployment", "assumed", "source", "reduced", "rehearsal")})
    assert [r.kind for r in mc.layer_table()] == ["gqa_attention"] + ["kda"] * 3
    assert (mc.n_experts, mc.n_experts_held, mc.vocab_size) == (320, 40, 24576)


def test_the_recurrent_slot_takes_its_shapes_from_the_mixer():
    """The published model cut to one period: a [128, 128] matrix a head,
    the tail over q, k and v together; K/V of the one attention layer."""
    mc = llama.LlamaConfig.from_hf_config(dict(
        CATALOG, num_hidden_layers=4, gqa_layers=[0], n_routed_experts=40,
        router_n_experts=320, vocab_size=24576))
    layout = kvcache.StateLayout.of(mc, 64, 100, 48)
    assert layout.paged_layers == (0,) and layout.recurrent_layers == (1, 2, 3)
    assert layout.token_bytes() == 4096 == 8 * 128 * 2 * 2
    lane = layout.lane_bytes()
    assert lane["ssm"] == 3 * 64 * 128 * 128 * 4
    assert lane["conv"] == 3 * 3 * 24576 * 2
    assert lane["ssm"] + lane["conv"] == 3 * 4341760  # 4.34 MB a lane and layer
    assert layout.bytes_in_use(10, 7)["ssm"] == 10 * lane["ssm"]
    assert layout.expert_layers == 4 and layout.expert_sums == 4
    state = jax.eval_shape(layout.init_state)
    assert [a.shape for a in state["ssm"]] == [(48, 64, 128, 128)] * 3
    assert [a.shape for a in state["conv"]] == [(48, 3, 24576)] * 3
    assert {a.dtype for a in state["ssm"]} == {jnp.dtype("float32")}
    assert {a.dtype for a in state["conv"]} == {jnp.dtype("bfloat16")}
    assert [a.shape for a in state["paged"]] == [(100, 2, 8, 64, 128)]
    shapes = moe.moe_param_shapes(moe.moe_config_of(mc))
    assert shapes["w_up"] == (40, 4096, 1536) and shapes["router"] == (4096, 320)
    assert shapes["shared_up"] == (4096, 1280)


def test_what_is_not_built_is_refused_by_name():
    linear = CFG["linear_attn_config"]
    for extra, named in (
            ({"first_k_dense_replace": 1}, "first_k_dense_replace"),
            ({"kda_use_full_proj": True}, "kda_use_full_proj"),
            ({"use_rope": True}, "use_rope"),
            ({"n_group": 4}, "group-limited"),
            ({"linear_attn_config": dict(linear, num_kv_heads=2)}, "num_kv_heads"),
            ({"gqa_layers": [1]}, "gqa_layers"),
            ({"gqa_layers": [0, 2]}, "gqa_layers"),
            ({"num_hidden_layers": 8}, "gqa_layers"),
            ({"n_shared_experts": 2}, "n_shared_experts"),
            ({"tie_word_embeddings": True}, "tie_word_embeddings")):
        with pytest.raises(ValueError, match=named):
            llama.LlamaConfig.from_hf_config(dict(CFG, **extra))
        if "gqa_layers" not in named:
            with pytest.raises(NotImplementedError):
                _reference().check_supported(dict(CFG, **extra))
    # two periods, the second's attention row where the interval puts it
    assert llama.LlamaConfig.from_hf_config(
        dict(CFG, num_hidden_layers=8, gqa_layers=[0, 4])).n_layers == 8


def test_whole_prompt_chunks_packed_dispatch_and_decode_agree_with_the_reference():
    """A 23-token prompt prefilled whole, and in two chunks (the second
    starts from the first's stored state, tails and pages) packed beside
    another lane's whole prompt at 8-token alignment; then decode steps
    through the recurrent slots and the pages."""
    ref = _reference()
    want = np.asarray(ref.forward(PARAMS, CFG, PROMPT))
    other = np.asarray(ref.forward(PARAMS, CFG, OTHER))
    whole, state_whole, _ = _forward(_layout().init_state(), {0: (PROMPT, 0)})
    np.testing.assert_allclose(np.asarray(whole[0]), want[-1], **TOL)
    first, state, _ = _forward(_layout().init_state(), {0: (PROMPT[:15], 0)})
    np.testing.assert_allclose(np.asarray(first[0]), want[14], **TOL)
    second, state, table = _forward(
        state, {0: (PROMPT[15:], 15), 2: (OTHER, 0)}, T=32, align=8)
    np.testing.assert_allclose(np.asarray(second[0]), want[-1], **TOL)
    np.testing.assert_allclose(np.asarray(second[2]), other[-1], **TOL)
    # the two ways leave lane 0 the same state, tails and pages
    for kind in ("ssm", "conv"):
        for a, b in zip(state_whole[kind], state[kind]):
            np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]), **TOL)
    for a, b in zip(state_whole["paged"], state["paged"]):
        np.testing.assert_allclose(np.asarray(a[1:7]), np.asarray(b[1:7]), **TOL)
    # lane 1 took no slice: its slots are as they were
    assert not np.asarray(state["ssm"][0][1]).any()
    # decode: both lanes, 6 steps on the program's own argmax; the reference
    # then runs ONCE over each lane's whole sequence
    seqs = {0: list(PROMPT), 2: list(OTHER)}
    tokens = {0: int(np.asarray(second[0]).argmax()),
              2: int(np.asarray(second[2]).argmax())}
    served = {0: [], 2: []}
    for _ in range(6):
        pos = jnp.asarray([len(seqs[0]), 0, len(seqs[2]), 0], jnp.int32)
        step = jnp.asarray([tokens[0], 0, tokens[2], 0], jnp.int32)
        logits, state = llama.decode_step(
            PARAMS, CONFIG, step, pos, state, table,
            jnp.asarray([True, False, True, False]), PAGE)
        for lane in (0, 2):
            seqs[lane].append(tokens[lane])
            served[lane].append(np.asarray(logits[lane]))
            tokens[lane] = int(served[lane][-1].argmax())
    for lane in (0, 2):
        rows = np.asarray(ref.forward(PARAMS, CFG, seqs[lane]))[-6:]
        np.testing.assert_allclose(np.stack(served[lane]), rows, **TOL)
    assert len(seqs[0]) == 29


def test_a_decode_token_in_the_packed_buffer_is_a_decode_step():
    """The mixed program's step 0 carries decode lanes as one-token slices:
    the same logits and the same state as the one-step form."""
    _, state, table = _forward(
        _layout().init_state(), {0: (PROMPT, 0), 2: (OTHER, 0)}, align=8)
    step = jnp.asarray([7, 0, 9, 0], jnp.int32)
    pos = jnp.asarray([23, 0, 6, 0], jnp.int32)
    by_step, state_a = llama.decode_step(
        PARAMS, CONFIG, step, pos, state, table,
        jnp.asarray([True, False, True, False]), PAGE)
    packed, state_b, _ = _forward(state, {0: ([7], 23), 2: ([9], 6)}, align=8)
    for lane in (0, 2):
        np.testing.assert_allclose(
            np.asarray(packed[lane]), np.asarray(by_step[lane]), **TOL)
    for kind in ("ssm", "conv"):
        for a, b in zip(state_a[kind], state_b[kind]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)


def test_an_attention_row_that_closes_the_table_is_gated_at_the_sampled_rows():
    """Two periods cut after the second's attention row: the packed forward
    writes that row's K/V for every token and runs its attention, gate and
    experts on one row a lane."""
    cfg = dict(CFG, num_hidden_layers=5, gqa_layers=[0, 4])
    config = _config(cfg)
    params = _randomised(config, seed=4)
    assert "wg" in params["layers"][4]
    ref = _reference()
    want = np.asarray(ref.forward(params, cfg, PROMPT))
    state = _layout(config=config).init_state()
    first, state, _ = _forward(state, {0: (PROMPT[:9], 0)}, params, config)
    np.testing.assert_allclose(np.asarray(first[0]), want[8], **TOL)
    second, state, table = _forward(
        state, {0: (PROMPT[9:], 9)}, params, config, T=16)
    np.testing.assert_allclose(np.asarray(second[0]), want[-1], **TOL)
    logits, _ = llama.decode_step(
        params, config, jnp.asarray([5, 0, 0, 0]), jnp.asarray([23, 0, 0, 0]),
        state, table, jnp.asarray([True, False, False, False]), PAGE)
    rows = np.asarray(ref.forward(params, cfg, PROMPT + [5]))
    np.testing.assert_allclose(np.asarray(logits[0]), rows[-1], **TOL)
    # the gate is in the sum: without it the logits leave by far more
    ungated = np.asarray(ref.forward(params, dict(cfg, use_gqa_gate=False), PROMPT))
    assert np.abs(ungated[-1] - want[-1]).max() > 100 * TOL["atol"]


def _delta_case(T, B, slices, fresh=(), seed=0, H=3, d=8, fastest=0.3):
    rng = np.random.default_rng(seed)
    q, k = rng.normal(size=(2, T, H, d))
    arrays = dict(
        q=q / np.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5,
        k=k / np.linalg.norm(k, axis=-1, keepdims=True),
        v=rng.normal(size=(T, H, d)),
        g=-rng.uniform(0.001, fastest, size=(T, H, d)),
        beta=rng.uniform(0.0, 2.0, size=(T, H)),
        state=rng.normal(size=(B, H, d, d)))
    arrays = {name: jnp.asarray(a, jnp.float32) for name, a in arrays.items()}
    q_start, q_len = np.zeros(B, np.int32), np.zeros(B, np.int32)
    for lane, start, n in slices:
        q_start[lane], q_len[lane] = start, n
    is_fresh = np.zeros(B, bool)
    is_fresh[list(fresh)] = True
    return arrays, q_start, q_len, is_fresh


def _token_by_token(a, slices, is_fresh):
    """`kda_step` over every slice: {token: o}, the states the lanes keep."""
    outs, states = {}, np.array(a["state"])
    for lane, start, n in slices:
        s = a["state"][lane:lane + 1] * (0.0 if is_fresh[lane] else 1.0)
        for t in range(start, start + n):
            o, s = delta.kda_step(
                a["q"][t:t + 1], a["k"][t:t + 1], a["v"][t:t + 1],
                a["g"][t:t + 1], a["beta"][t:t + 1], s, jnp.ones((1,), bool))
            outs[t] = np.asarray(o[0])
        states[lane] = np.asarray(s[0])
    return outs, states


def test_the_one_step_form_is_the_reference_recurrence():
    a, *_ = _delta_case(12, 1, [])
    want = np.asarray(_reference().delta_rule(
        a["q"], a["k"], a["v"], a["g"], a["beta"]))
    s = jnp.zeros_like(a["state"])
    for t in range(12):
        o, s = delta.kda_step(
            a["q"][t:t + 1], a["k"][t:t + 1], a["v"][t:t + 1], a["g"][t:t + 1],
            a["beta"][t:t + 1], s, jnp.ones((1,), bool))
        np.testing.assert_allclose(np.asarray(o[0]), want[t], rtol=1e-5, atol=1e-6)


#: (T, lanes, [(lane, start, length)], lanes that open a request, chunk, sub)
DELTA_CASES = {
    "decode lanes and a slice over three pieces": (
        64, 6, [(0, 0, 1), (1, 8, 1), (2, 16, 3), (3, 24, 37)], (2,), 16, 4),
    "one piece holds every slice": (
        64, 6, [(0, 0, 1), (1, 8, 1), (2, 16, 3), (3, 24, 37)], (), 64, 16),
    "slices that cross piece boundaries, continued from stored states": (
        64, 6, [(5, 3, 29), (1, 32, 32)], (1,), 16, 8),
    "one lane, every piece a continuation": (64, 6, [(5, 0, 64)], (), 8, 8),
    "unaligned slices, all new requests, lanes out of buffer order": (
        32, 3, [(2, 1, 5), (0, 6, 7), (1, 13, 19)], (0, 1, 2), 8, 4),
    "pieces smaller than a slice, no sub-blocks": (
        32, 3, [(2, 1, 5), (0, 6, 7), (1, 13, 19)], (), 4, 4),
    "padding at both ends, a slice that ends at the buffer's end": (
        32, 3, [(1, 8, 9), (0, 29, 3)], (), 8, 2),
    # what gathered windows, scattered rows and the piece table can get wrong
    "seven live pieces of two lanes, the last one partial, a lane between": (
        64, 4, [(0, 2, 30), (2, 34, 20)], (2,), 8, 4),
    "three lanes' pieces back to back, each slice's last piece partial": (
        64, 4, [(1, 0, 20), (3, 21, 24), (0, 50, 9)], (0,), 8, 4),
    "every piece full, the buffer full, as many dead pieces as live ones": (
        32, 3, [(0, 0, 16), (2, 16, 16)], (0,), 8, 8),
    "short slices of several lanes, new and continued, and a one-token lane": (
        128, 6, [(0, 0, 2), (4, 2, 63), (1, 70, 5), (5, 80, 17), (3, 100, 1)],
        (4, 5), 64, 16),
    "unaligned slices, lanes out of buffer order, one a new request": (
        32, 3, [(2, 1, 5), (0, 6, 7), (1, 13, 19)], (1,), 8, 4),
    "a last piece whose window would pass the buffer's last row": (
        64, 3, [(0, 3, 2), (1, 41, 23)], (0,), 16, 4),
    "single-token lanes and padding only: no piece at all": (
        32, 4, [(0, 0, 1), (2, 9, 1), (3, 31, 1)], (2,), 8, 4),
    "an empty buffer: every lane keeps its state": (32, 3, [], (), 8, 4),
}


@pytest.mark.parametrize("case", sorted(DELTA_CASES))
def test_the_packed_form_is_the_one_step_form_token_by_token(case):
    """`kda_ragged` (pieces of a chunk's tokens as matrix products, one
    state a piece) against `kda_step` run token by token: slices of unequal
    length in one buffer, slices that open a request (zero state) or
    continue one (the lane's stored state), padding between and around; a
    lane without a slice keeps its state and a row of no slice reads zero."""
    T, B, slices, fresh, chunk, sub = DELTA_CASES[case]
    a, q_start, q_len, is_fresh = _delta_case(T, B, slices, fresh)
    o, new = jax.jit(delta.kda_ragged, static_argnames=("chunk", "sub"))(
        a["q"], a["k"], a["v"], a["g"], a["beta"], a["state"],
        jnp.asarray(q_start), jnp.asarray(q_len), jnp.asarray(is_fresh),
        chunk=chunk, sub=sub)
    want, want_state = _token_by_token(a, slices, is_fresh)
    for t, row in want.items():
        np.testing.assert_allclose(np.asarray(o[t]), row, rtol=1e-4, atol=1e-5)
    assert not np.asarray(o)[sorted(set(range(T)) - set(want))].any()
    np.testing.assert_allclose(np.asarray(new), want_state, rtol=1e-4, atol=1e-5)
    untouched = sorted(set(range(B)) - {lane for lane, _, _ in slices})
    assert np.array_equal(np.asarray(new)[untouched],
                          np.asarray(a["state"])[untouched])


@pytest.mark.parametrize("sub", [16, 64])
def test_a_channel_that_decays_by_e5_a_token_stays_finite_and_exact(sub):
    """THE trap of a decay per channel: g down to -5 a token over a whole
    piece of 64 tokens is a running sum of -320, whose negation no float32
    exponent holds.  Every exponent formed is a difference that is <= 0:
    finite, and the reference's recurrence token by token."""
    slices = [(0, 0, 64), (1, 64, 40)]
    a, q_start, q_len, is_fresh = _delta_case(
        128, 2, slices, fresh=(0,), fastest=5.0)
    # some channels at the fastest decay on every token, some hardly decaying
    g = np.array(a["g"])
    g[:, :, 0], g[:, :, 1] = -5.0, -1e-4
    a["g"] = jnp.asarray(g)
    o, new = delta.kda_ragged(
        a["q"], a["k"], a["v"], a["g"], a["beta"], a["state"],
        jnp.asarray(q_start), jnp.asarray(q_len), jnp.asarray(is_fresh),
        chunk=64, sub=sub)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(new)).all()
    want = np.asarray(_reference().delta_rule(
        a["q"][:64], a["k"][:64], a["v"][:64], a["g"][:64], a["beta"][:64]))
    np.testing.assert_allclose(np.asarray(o[:64]), want, rtol=1e-4, atol=1e-5)
    by_step, want_state = _token_by_token(a, slices, is_fresh)
    np.testing.assert_allclose(np.asarray(o[103]), by_step[103], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new), want_state, rtol=1e-4, atol=1e-5)
    # the factored form this guards against: exp(-G) alone overflows
    with np.errstate(over="ignore"):
        assert np.isinf(np.exp(-np.cumsum(g[:64], axis=0), dtype=np.float32)).any()


@pytest.mark.parametrize("seed", [0, 1])
def test_the_packed_form_is_as_near_a_float64_recurrence_as_the_one_step_form(seed):
    """At the published 64 heads of 128, a 200-token slice continued from a
    stored state (four pieces, the state carried between them) and a new
    40-token one: the outputs and the states the lanes keep against the
    recurrence in float64 on the same float32 inputs.  On the CPU the
    one-step form in float32 reads 5.8e-8 and 4.5e-7 there and the packed
    form 1.3e-7 and 4.6e-7; with its inverse as matrix products (before PR
    53) it read 1.3e-7 and 4.6e-7 to 6.1e-7: sums in another order, not a
    loss.  The limits are three times the readings."""
    H, d, slices = 64, 128, [(1, 3, 200), (0, 210, 40)]
    a, q_start, q_len, is_fresh = _delta_case(
        256, 2, slices, fresh=(0,), seed=seed, H=H, d=d, fastest=0.1)
    o, new = jax.jit(delta.kda_ragged)(
        a["q"], a["k"], a["v"], a["g"], a["beta"], a["state"],
        jnp.asarray(q_start), jnp.asarray(q_len), jnp.asarray(is_fresh))
    o, new = np.asarray(o), np.asarray(new)
    q, k, v, g, beta, state = (
        np.asarray(a[name], np.float64)
        for name in ("q", "k", "v", "g", "beta", "state"))
    for lane, start, n in slices:
        S = state[lane] * (0.0 if is_fresh[lane] else 1.0)
        for t in range(start, start + n):
            S = np.exp(g[t])[..., None] * S
            u = beta[t][:, None] * (v[t] - np.einsum("hc,hce->he", k[t], S))
            S = S + k[t][..., None] * u[:, None, :]
            want = np.einsum("hc,hce->he", q[t], S)
            assert np.abs(o[t] - want).max() < 4e-7, t
        assert np.abs(new[lane] - S).max() < 1.5e-6, lane


def test_a_lane_that_is_not_live_keeps_its_state():
    a, *_ = _delta_case(8, 3, [])
    _, new = delta.kda_step(
        a["q"][:3], a["k"][:3], a["v"][:3], a["g"][:3], a["beta"][:3],
        a["state"], jnp.asarray([True, False, True]))
    assert np.array_equal(np.asarray(new[1]), np.asarray(a["state"][1]))
    assert not np.array_equal(np.asarray(new[0]), np.asarray(a["state"][0]))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """THE share test: the routed parts that eight chips' shares of the
    experts compute, with the mixer and the shared expert counted once, are
    the uncut reference's layer; the program's eight shares likewise."""
    ref = _reference()
    whole_cfg = dict(CFG, n_routed_experts=16, router_n_experts=16)
    whole_mc = _config(whole_cfg)
    assert whole_mc.n_experts_held == 0
    layer = _randomised(whole_mc, seed=3)["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(5), (19, 64), jnp.float32)
    want = np.asarray(ref.layer_forward(layer, h, whole_cfg, 1))
    # what every chip computes alike: the mixer's residual and the shared expert
    mixed = h + ref.kda(layer, ref.rms_norm(h, layer["attn_norm"], 1e-5), whole_cfg)
    x = ref.rms_norm(mixed, layer["mlp_norm"], 1e-5)
    alike = np.asarray(mixed + ref.shared_expert(layer, x))
    shared = np.asarray(ref.shared_expert(layer, x))
    parts_ref, parts_program, multiplied = [], [], 0
    for first in range(0, 16, 2):
        share = dict(layer, **{name: layer[name][first:first + 2]
                               for name in ("w_gate", "w_up", "w_down")})
        cfg = dict(whole_cfg, n_routed_experts=2, first_expert=first)
        parts_ref.append(np.asarray(ref.experts(share, x, cfg)) - shared)
        mc = moe.moe_config_of(llama.LlamaConfig.from_hf_config(cfg))
        assert (mc.first_expert, mc.n_held, mc.holds_all) == (first, 2, False)
        out, rows = moe.moe_mlp(share, x, mc, with_rows=True)
        parts_program.append(np.asarray(out) - shared)
        multiplied += int(rows.sum())
    np.testing.assert_allclose(sum(parts_ref) + alike, want, **TOL)
    np.testing.assert_allclose(sum(parts_program) + alike, want, **TOL)
    # every routed pair was multiplied by exactly one of the eight chips
    assert multiplied == 19 * 2
    # and one share alone is not the layer
    assert np.abs(parts_program[0] + alike - want).max() > 100 * TOL["atol"]


def test_the_expert_sums_ride_the_state():
    """Hits, the fullest expert's rows, the pairs this chip multiplied and
    the pairs routed, summed over the expert layers of a forward step; the
    last row lies on the last writer, so every expert layer sees every
    token."""
    ref = _reference()
    state = _layout().init_state()
    _, state, _ = _forward(state, {0: (PROMPT, 0)}, T=32)
    hits, peak, here, routed = (int(v) for v in state["stats"][0])
    assert routed == 23 * 2 * 4  # tokens x experts a token x expert layers
    held, h = 0, ref.f32(PARAMS["embed"])[jnp.asarray(PROMPT)]
    for i, layer in enumerate(PARAMS["layers"]):
        mixer = ref.attention if i == 0 else ref.kda
        mid = h + mixer(layer, ref.rms_norm(h, layer["attn_norm"], 1e-5), CFG)
        _, idx = ref.route(layer, ref.rms_norm(mid, layer["mlp_norm"], 1e-5), CFG)
        held += int((np.asarray(idx) < 4).sum())
        h = ref.layer_forward(layer, h, CFG, i)
    assert here == held and 0 < here < routed
    assert 4 <= hits <= 16 and peak <= here


def _int8(w):
    """Per-output-channel symmetric int8, dequantised: the nearest precision
    below the configuration's that the program has."""
    w = np.asarray(w, np.float32)
    scale = np.abs(w).max(axis=-2, keepdims=True) / 127.0 + 1e-12
    return jnp.asarray(np.round(w / scale) * scale)


def test_int8_weights_and_a_bf16_state_fail_the_tolerance():
    """The same comparison with every matrix at int8, and with the carried
    state rounded to bf16 after every token: both by far outside TOL, so
    TOL tells a lower precision from the configuration's."""
    ref = _reference()
    want = np.asarray(ref.forward(PARAMS, CFG, PROMPT))[-1]
    served, _, _ = _forward(_layout().init_state(), {0: (PROMPT, 0)})
    np.testing.assert_allclose(np.asarray(served[0]), want, **TOL)
    limit = 30 * TOL["atol"] + 30 * TOL["rtol"] * np.abs(want).max()
    quantised = jax.tree.map(
        lambda a: _int8(a) if a.ndim >= 2 and a.shape[-1] > 8 else a, PARAMS)
    low = np.asarray(ref.forward(quantised, CFG, PROMPT))[-1]
    assert np.abs(low - want).max() > limit
    # the program token by token, its state kept in bf16 between the tokens
    state, table = _layout().init_state(), _packed({0: (PROMPT, 0)})[1]
    for t, token in enumerate(PROMPT):
        logits, state = llama.decode_step(
            PARAMS, CONFIG, jnp.asarray([token, 0, 0, 0]),
            jnp.asarray([t, 0, 0, 0]), state, table,
            jnp.asarray([True, False, False, False]), PAGE)
        state["ssm"] = [s.astype(jnp.bfloat16).astype(jnp.float32)
                        for s in state["ssm"]]
    assert np.abs(np.asarray(logits[0]) - want).max() > limit


def _broken_references():
    """The reference with one assumed detail changed: each must leave the
    program's logits by far more than TOL."""
    def without(name):
        def change(ref):
            kda = ref.kda

            def wrong(layer, u, cfg):
                return kda(dict(layer, **{name: jnp.zeros_like(layer[name])}), u, cfg)
            ref.kda = wrong
        return change

    def beta_not_doubled(ref):
        kda = ref.kda
        ref.kda = lambda layer, u, cfg: kda(
            layer, u, dict(cfg, kda_allow_neg_eigval=False))

    def one_decay_a_head(ref):
        kda = ref.kda

        def wrong(layer, u, cfg):  # the first channel's decay for all of a head
            bias = jnp.asarray(layer["dt_bias"]).reshape(4, 16)
            wf_b = jnp.asarray(layer["wf_b"]).reshape(16, 4, 16)
            return kda(dict(
                layer, dt_bias=jnp.repeat(bias[:, :1], 16, axis=1).reshape(-1),
                wf_b=jnp.repeat(wf_b[:, :, :1], 16, axis=2).reshape(16, 64)),
                u, cfg)
        ref.kda = wrong

    def no_convolution(ref):
        kda = ref.kda

        def wrong(layer, u, cfg):  # only the current token's tap
            w = jnp.asarray(layer["conv_w"])
            return kda(dict(layer, conv_w=w.at[:-1].set(0.0)), u, cfg)
        ref.kda = wrong

    def rope_on_attention(ref):
        attention = ref.attention

        def wrong(layer, u, cfg):  # a positional term where the family has none
            ramp = 1.0 + 0.05 * jnp.arange(u.shape[0], dtype=jnp.float32)[:, None]
            return attention(layer, u * ramp, cfg)
        ref.attention = wrong

    def no_attention_gate(ref):
        attention = ref.attention
        ref.attention = lambda layer, u, cfg: attention(
            layer, u, dict(cfg, use_gqa_gate=False))

    def every_expert_held(ref):
        experts = ref.experts

        def wrong(layer, x, cfg):  # pairs to absent experts land on held ones
            return experts(layer, x, dict(cfg, first_expert=4))
        ref.experts = wrong

    def no_shared_expert(ref):
        ref.shared_expert = lambda layer, x: jnp.zeros_like(x)

    return {"no_decay": without("A_log"),  # A = 1 for every head
            "no_output_gate": without("wg_b"),
            "beta_not_doubled": beta_not_doubled,
            "one_decay_a_head": one_decay_a_head,
            "no_convolution": no_convolution,
            "rope_on_attention": rope_on_attention,
            "no_attention_gate": no_attention_gate,
            "every_expert_held": every_expert_held,
            "no_shared_expert": no_shared_expert}


@pytest.mark.parametrize("detail", sorted(_broken_references()))
def test_each_assumed_detail_is_held_by_the_comparison(detail):
    ref = _reference()
    want = np.asarray(ref.forward(PARAMS, CFG, PROMPT))[-1]
    _broken_references()[detail](ref)
    wrong = np.asarray(ref.forward(PARAMS, CFG, PROMPT))[-1]
    assert np.abs(wrong - want).max() > 30 * TOL["atol"] + 30 * TOL["rtol"] * np.abs(want).max()


@pytest.mark.parametrize("rows", [1, 5, 16, 23, 64])
def test_the_triangular_system_is_inverted_by_halves(rows):
    """`(I + A)^-1` by matrix products from blocks of one row up, at sizes
    that are no power of two as well; exact where keys repeat and beta is 2
    (A = 2 x the strictly lower ones: a Neumann series' terms pass 1e30)."""
    rng = np.random.default_rng(rows)
    A = np.tril(rng.normal(size=(3, rows, rows)), -1).astype(np.float32)
    A[0] = 2.0 * np.tril(np.ones((rows, rows), np.float32), -1)
    got = np.asarray(delta._unit_lower_inverse(jnp.asarray(A)))
    want = np.linalg.inv(np.eye(rows) + A.astype(np.float64))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5 * np.abs(want).max())
    assert np.array_equal(got[0], want[0].astype(np.float32))
