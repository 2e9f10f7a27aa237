"""`model_type: cohere2_moe` (Command A+): a parallel attention + experts
block (one bias-free LayerNorm, one residual), roped window rows that keep a
ring a lane beside full rows without positions on the pool's pages,
interleaved rotary, several shared experts fused into one MLP beside a
chip's share of the routed ones; held to the plain reference
benchmark/reference/cohere2_moe.py (no cache, a loop over the held and over
the shared experts) at tiny sizes, float32, seeded random weights.  The
packed step's window attention kernel against its XLA oracle, in interpret
mode.
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
from kserve_tpu.ops import attention as attn_ops
from kserve_tpu.ops.pallas_paged_attention import window_attention_ragged_pallas
from kserve_tpu.ops.rotary import apply_rope_interleaved

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: one period (three window rows, one full row); a window of 16 tokens = 4
#: ring pages of 4; 8 experts scored, 4 held, 4 a token; 2 shared experts
CFG = {
    "model_type": "cohere2_moe", "vocab_size": 320, "hidden_size": 64,
    "intermediate_size": 48, "num_hidden_layers": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": 16, "num_experts": 4, "router_n_experts": 8,
    "first_expert": 0, "num_experts_per_tok": 4, "num_shared_experts": 2,
    "shared_expert_combination_strategy": "average",
    "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
    "layer_norm_eps": 1e-5, "rope_theta": 50000,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "position_embedding_type": "rope_gptj", "rotary_pct": 1,
    "use_parallel_block": True, "use_qk_norm": False, "attention_bias": False,
    "tie_word_embeddings": True, "logit_scale": 1, "first_k_dense_replace": 0,
    "hidden_act": "silu", "use_gated_activation": True,
    "max_position_embeddings": 4096}
PAGE = 4
WINDOW = 16


def _reference():
    path = os.path.join(ROOT, "benchmark", "reference", "cohere2_moe.py")
    spec = importlib.util.spec_from_file_location("reference_cohere2_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _randomised(config, seed=1):
    """scale 0.1: logits of magnitude ~1; the norms' weights random, so
    that the comparison exercises them."""
    params = llama.init_params(config, jax.random.PRNGKey(seed), scale=0.1)
    for i, layer in enumerate(params["layers"]):
        layer["attn_norm"] = 1.0 + 0.2 * jax.random.normal(
            jax.random.PRNGKey(100 + i), layer["attn_norm"].shape, jnp.float32)
    params["final_norm"] = 1.0 + 0.2 * jax.random.normal(
        jax.random.PRNGKey(99), params["final_norm"].shape, jnp.float32)
    return params


CONFIG = dataclasses.replace(llama.LlamaConfig.from_hf_config(CFG), dtype="float32")
PARAMS = _randomised(CONFIG)
#: float32 on both sides; the online softmax over (ring, slice) and the
#: packed experts sum in another order than the reference.  The same
#: comparison with the half-rotation pairing, a window one token wider or
#: the shared experts summed reads 1e-2 and more (tests below)
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


#: longer than the window, so that a ring wraps inside a prompt
PROMPT = np.random.RandomState(0).randint(0, 320, 41).tolist()
OTHER = np.random.RandomState(1).randint(0, 320, 6).tolist()


def test_config_table_and_parameters():
    assert CONFIG.is_hybrid and not CONFIG.is_latent and CONFIG.parallel_block
    table = CONFIG.layer_table()
    assert [r.kind for r in table] == ["gqa_window_attention"] * 3 + ["gqa_attention"]
    assert [r.writes for r in table] == ["window_kv"] * 3 + ["paged_kv"]
    assert [r.ffn for r in table] == ["experts"] * 4
    assert [CONFIG.layer_ropes(i) for i in range(4)] == [True, True, True, False]
    assert CONFIG.rope_interleaved and CONFIG.rope_theta == 50000
    assert (CONFIG.n_experts, CONFIG.n_experts_held, CONFIG.first_expert) == (8, 4, 0)
    assert CONFIG.n_shared_experts == 2 and CONFIG.moe_shared_average
    assert CONFIG.has_expert_sums and CONFIG.counts_routed_pairs
    assert CONFIG.norm_type == "layernorm" and not CONFIG.norm_bias
    layer = PARAMS["layers"][0]
    # ONE norm a layer and no bias; no router bias; the shared experts fused
    assert sorted(layer) == [
        "attn_norm", "router", "shared_down", "shared_gate", "shared_up",
        "w_down", "w_gate", "w_up", "wk", "wo", "wq", "wv"]
    assert layer["router"].shape == (64, 8) and layer["w_gate"].shape == (4, 64, 48)
    assert layer["shared_up"].shape == (64, 96) and layer["shared_down"].shape == (96, 64)
    assert layer["wq"].shape == (64, 128) and layer["wk"].shape == (64, 32)
    assert sorted(k for k in PARAMS if k != "layers") == ["embed", "final_norm"]
    layout = _layout()
    assert layout.window_layers == (0, 1, 2) and layout.paged_layers == (3,)
    assert (layout.ring_page_size, layout.ring_width) == (4, 4)


def test_the_published_config_and_the_cut_file_are_accepted():
    """The catalog row's `config` (32 layers, 128 experts, every one held)
    and the benchmark's cut of it: the sizes the cell's arithmetic rests on."""
    with open(os.path.join(ROOT, "benchmark", "configs", "command-a-plus.json")) as f:
        cut = json.load(f)
    published = dict(cut, **cut["deployment"]["published"])
    published["layer_types"] = cut["layer_types"] * 8
    for key in ("router_n_experts", "first_expert"):
        published.pop(key)
    whole = llama.LlamaConfig.from_hf_config(published)
    assert (whole.n_layers, whole.n_experts, whole.n_experts_held) == (32, 128, 0)
    assert whole.vocab_size == 262144 and not whole.counts_routed_pairs
    assert [r.kind for r in whole.layer_table()].count("gqa_attention") == 8
    mc = llama.LlamaConfig.from_hf_config(cut)
    assert (mc.n_layers, mc.n_experts, mc.n_experts_held, mc.first_expert) == (4, 128, 16, 0)
    assert (mc.hidden_size, mc.n_heads, mc.n_kv_heads, mc.head_dim) == (4096, 128, 8, 128)
    assert (mc.sliding_window, mc.rope_theta, mc.n_experts_per_tok) == (4096, 50000, 8)
    shapes = moe.moe_param_shapes(moe.moe_config_of(mc))
    assert shapes["router"] == (4096, 128) and "router_bias" not in shapes
    assert shapes["w_gate"] == shapes["w_up"] == (16, 4096, 4096)
    assert shapes["shared_gate"] == (4096, 16384) and shapes["shared_down"] == (16384, 4096)
    layout = kvcache.StateLayout.of(mc, 64, 4352, 32)
    assert (layout.ring_page_size, layout.ring_width) == (64, 64)
    # a lane: 3 window layers x 4096 tokens x 4096 B of rings whatever its
    # context; a token: 4096 B of the one full layer's pages
    assert layout.lane_bytes()["window_kv"] == 3 * 4096 * 4096 == 50331648
    assert layout.token_bytes() == 4096
    state = jax.eval_shape(layout.init_state)
    assert [a.shape for a in state["window"]] == [(1 + 32 * 64, 2, 8, 64, 128)] * 3
    assert [a.shape for a in state["paged"]] == [(4352, 2, 8, 64, 128)]
    weights = sum(
        int(np.prod(shape)) for spec in mc.layer_table()
        for shape, _ in hybrid.layer_param_shapes(mc, spec).values())
    assert weights + 32768 * 4096 + 4096 == 4733292544  # 9.47 GB in bf16


def test_what_is_not_built_is_refused_by_name():
    for extra, named in (
            ({"use_parallel_block": False}, "use_parallel_block"),
            ({"first_k_dense_replace": 2}, "first_k_dense_replace"),
            ({"position_embedding_type": "rope"}, "position_embedding_type"),
            ({"rotary_pct": 0.5}, "rotary_pct"),
            ({"expert_selection_fn": "softmax"}, "expert_selection_fn"),
            ({"use_qk_norm": True}, "use_qk_norm"),
            ({"attention_bias": True}, "attention_bias"),
            ({"logit_scale": 0.25}, "logit_scale"),
            ({"layer_types": ["sliding_attention"] * 3}, "layer_types"),
            ({"sliding_window": None}, "sliding_window")):
        with pytest.raises(ValueError, match=named):
            llama.LlamaConfig.from_hf_config(dict(CFG, **extra))
    for extra in ({"use_parallel_block": False}, {"use_qk_norm": True},
                  {"shared_expert_combination_strategy": "sum"},
                  {"model_type": "cohere2"}):
        with pytest.raises(NotImplementedError):
            _reference().check_supported(dict(CFG, **extra))


def test_interleaved_rotary_is_the_complex_pair_formula():
    """Columns (2j, 2j+1) as one complex number turned by pos x
    theta^(-2j/d); NOT the half-rotation pairing (j, j + d/2)."""
    x = np.random.RandomState(3).normal(size=(7, 3, 16)).astype(np.float32)
    pos = np.asarray([0, 1, 2, 17, 100, 4095, 5000], np.int32)
    got = np.asarray(apply_rope_interleaved(jnp.asarray(x), jnp.asarray(pos), 50000.0))
    z = x[..., 0::2] + 1j * x[..., 1::2]
    turn = np.exp(1j * pos[:, None, None].astype(np.float64)
                  * 50000.0 ** (-np.arange(0, 16, 2) / 16))
    want = np.stack([(z * turn).real, (z * turn).imag], axis=-1).reshape(x.shape)
    # float32 angles: a position of 5000 is known to ~3e-4 of a radian
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
    half = np.asarray(llama.apply_rope(
        jnp.asarray(x)[None], jnp.asarray(pos)[None], 50000.0)[0])
    assert np.abs(half - want).max() > 0.1
    # the reference's own form agrees at positions 0..T-1
    ref = _reference()
    np.testing.assert_allclose(
        np.asarray(ref.rotary_interleaved(jnp.asarray(x), 50000.0)),
        np.asarray(apply_rope_interleaved(
            jnp.asarray(x), jnp.arange(7, dtype=jnp.int32), 50000.0)),
        rtol=1e-5, atol=1e-5)


def test_whole_prompt_chunks_packed_dispatch_and_decode_agree_with_the_reference():
    """A 41-token prompt (window 16: the ring wraps twice) prefilled whole,
    and in two chunks (the second reads a wrapped ring and overwrites it)
    packed beside another lane's short prompt at 8-token alignment; then
    decode steps with the window binding on one lane and not on the other."""
    ref = _reference()
    want = np.asarray(ref.forward(PARAMS, CFG, PROMPT))
    other = np.asarray(ref.forward(PARAMS, CFG, OTHER))
    whole, state_whole, _ = _forward(_layout().init_state(), {0: (PROMPT, 0)})
    np.testing.assert_allclose(np.asarray(whole[0]), want[-1], **TOL)
    first, state, _ = _forward(_layout().init_state(), {0: (PROMPT[:27], 0)})
    np.testing.assert_allclose(np.asarray(first[0]), want[26], **TOL)
    second, state, table = _forward(
        state, {0: (PROMPT[27:], 27), 2: (OTHER, 0)}, T=32, align=8)
    np.testing.assert_allclose(np.asarray(second[0]), want[-1], **TOL)
    np.testing.assert_allclose(np.asarray(second[2]), other[-1], **TOL)
    # the two ways leave lane 0 the same rings (pages 1..4) and pages
    for a, b in zip(state_whole["window"], state["window"]):
        np.testing.assert_allclose(np.asarray(a[1:5]), np.asarray(b[1:5]), **TOL)
    for a, b in zip(state_whole["paged"], state["paged"]):
        np.testing.assert_allclose(np.asarray(a[1:11]), np.asarray(b[1:11]), **TOL)
    # lane 1 took no slice: its ring is as it was
    assert not np.asarray(state["window"][0][5:9]).any()
    seqs = {0: list(PROMPT), 2: list(OTHER)}
    tokens = {0: int(np.asarray(second[0]).argmax()),
              2: int(np.asarray(second[2]).argmax())}
    served = {0: [], 2: []}
    for _ in range(14):  # lane 2 passes the window (6 + 14 > 16) on the way
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
        rows = np.asarray(ref.forward(PARAMS, CFG, seqs[lane]))[-14:]
        np.testing.assert_allclose(np.stack(served[lane]), rows, **TOL)
    assert len(seqs[0]) == 55 and len(seqs[2]) == 20


def test_a_decode_token_in_the_packed_buffer_is_a_decode_step():
    """The mixed program's step 0 carries decode lanes as one-token slices:
    the same logits and the same rings as the one-step form, with the
    window binding (lane 0 at 41) and not (lane 2 at 6)."""
    _, state, table = _forward(
        _layout().init_state(), {0: (PROMPT, 0), 2: (OTHER, 0)}, align=8)
    step = jnp.asarray([7, 0, 9, 0], jnp.int32)
    pos = jnp.asarray([41, 0, 6, 0], jnp.int32)
    by_step, state_a = llama.decode_step(
        PARAMS, CONFIG, step, pos, state, table,
        jnp.asarray([True, False, True, False]), PAGE)
    packed, state_b, _ = _forward(state, {0: ([7], 41), 2: ([9], 6)}, align=8)
    for lane in (0, 2):
        np.testing.assert_allclose(
            np.asarray(packed[lane]), np.asarray(by_step[lane]), **TOL)
    for kind in ("window", "paged"):  # page 0 is the null page
        for a, b in zip(state_a[kind], state_b[kind]):
            np.testing.assert_allclose(np.asarray(a[1:]), np.asarray(b[1:]), **TOL)


def _variant(**changes):
    """The reference with one thing changed: what the tolerance must tell."""
    ref = _reference()
    for name, value in changes.items():
        setattr(ref, name, value)
    return np.asarray(ref.forward(PARAMS, CFG, PROMPT))[-1]


def test_the_tolerance_tells_each_mechanism():
    """The program agrees with the reference; a reference that pairs the
    rotary by halves, turns the full layer too, widens the window by one,
    sums the shared experts, or norms the feed-forward's input apart, does
    not, by far more than the tolerance."""
    ref = _reference()
    got, _, _ = _forward(_layout().init_state(), {0: (PROMPT, 0)})
    got = np.asarray(got[0])

    def half_rotation(x, theta):
        return llama.apply_rope(
            x[None], jnp.arange(x.shape[0], dtype=jnp.int32)[None], theta)[0]

    def summed(layer, u, cfg):
        return ref.shared_experts(layer, u, cfg) * cfg["num_shared_experts"]

    real_attention = ref.attention
    variants = {
        "half-rotation pairing": dict(rotary_interleaved=half_rotation),
        "shared experts summed": dict(
            layer_forward=lambda layer, h, cfg, kind: (
                ref.layer_forward(layer, h, cfg, kind)
                + ref.shared_experts(layer, ref.layer_norm(
                    h, layer["attn_norm"], 1e-5), cfg))),
        "positions on the full layer": dict(
            attention=lambda layer, u, cfg, kind: real_attention(
                layer, u, dict(cfg, sliding_window=10 ** 6), "sliding_attention")),
        "a window one token wider": dict(
            attention=lambda layer, u, cfg, kind: real_attention(
                layer, u, dict(cfg, sliding_window=WINDOW + 1), kind)),
    }
    del summed
    for name, changes in variants.items():
        wrong = _variant(**changes)
        assert np.abs(wrong - got).max() > 100 * TOL["atol"], name
    np.testing.assert_allclose(got, _variant(), **TOL)


def test_the_shares_add_up_to_the_uncut_layer():
    """THE share test: eight chips share a layer, one expert each at this
    size.  The routed parts the eight compute, with the attention and the
    shared experts (what every chip computes alike) counted once, add up to
    the uncut reference's layer output; the program's eight shares likewise."""
    ref = _reference()
    whole_cfg = {k: v for k, v in CFG.items()
                 if k not in ("router_n_experts", "first_expert")}
    whole_cfg["num_experts"] = 8
    whole_mc = dataclasses.replace(
        llama.LlamaConfig.from_hf_config(whole_cfg), dtype="float32")
    assert whole_mc.n_experts_held == 0 and not whole_mc.counts_routed_pairs
    layer = _randomised(whole_mc, seed=3)["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(5), (19, 64), jnp.float32)
    want = np.asarray(ref.layer_forward(layer, h, whole_cfg, "sliding_attention"))
    u = ref.layer_norm(h, layer["attn_norm"], 1e-5)
    alike = np.asarray(h + ref.attention(layer, u, whole_cfg, "sliding_attention")
                       + ref.shared_experts(layer, u, whole_cfg))
    parts_ref, parts_program, multiplied = [], [], 0
    for first in range(8):
        share = dict(layer, **{
            name: layer[name][first:first + 1]
            for name in ("w_gate", "w_up", "w_down")})
        cfg = dict(CFG, num_experts=1, first_expert=first)
        parts_ref.append(np.asarray(
            ref.layer_forward(share, h, cfg, "sliding_attention")) - alike)
        mc = moe.moe_config_of(llama.LlamaConfig.from_hf_config(cfg))
        assert (mc.first_expert, mc.n_held, mc.holds_all) == (first, 1, False)
        out, rows = moe.moe_mlp(share, u, mc, with_rows=True)
        parts_program.append(
            np.asarray(out) - np.asarray(ref.shared_experts(layer, u, cfg)))
        multiplied += int(rows.sum())
    np.testing.assert_allclose(sum(parts_ref) + alike, want, **TOL)
    np.testing.assert_allclose(sum(parts_program) + alike, want, **TOL)
    # every routed pair was multiplied by exactly one of the eight chips
    assert multiplied == 19 * 4
    # and one share alone is not the layer
    assert np.abs(parts_program[0] + alike - want).max() > 100 * TOL["atol"]


def test_four_shared_experts_fused_are_the_four_averaged():
    """One MLP over the experts' columns side by side and a factor of 1/n is
    the mean of the n experts, each by itself."""
    mc = moe.MoEConfig(
        n_experts=8, top_k=2, hidden_size=32, intermediate_size=24,
        router="sigmoid", router_bias=False, shared=True, n_shared=4,
        shared_average=True)
    params = moe.init_moe_params(mc, jax.random.PRNGKey(2), scale=0.2)
    assert params["shared_gate"].shape == (32, 96) and "router_bias" not in params
    x = jax.random.normal(jax.random.PRNGKey(4), (11, 32), jnp.float32)
    each = [
        (jax.nn.silu(x @ params["shared_gate"][:, s * 24:(s + 1) * 24])
         * (x @ params["shared_up"][:, s * 24:(s + 1) * 24]))
        @ params["shared_down"][s * 24:(s + 1) * 24] for s in range(4)]
    fused = moe.shared_expert(params, x) / 4
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(sum(each) / 4), rtol=1e-5, atol=1e-6)
    routed, _ = moe.routed_experts(
        params, x, *moe.route(params, x, mc), mc.n_experts)
    np.testing.assert_allclose(
        np.asarray(moe.moe_mlp(params, x, mc)), np.asarray(routed + fused),
        rtol=1e-5, atol=1e-6)
    summed = dataclasses.replace(mc, shared_average=False)
    np.testing.assert_allclose(
        np.asarray(moe.moe_mlp(params, x, summed)),
        np.asarray(routed + 4 * fused), rtol=1e-5, atol=1e-6)


def test_the_expert_sums_ride_the_state():
    """Hits, the fullest expert's rows, the pairs this chip multiplied and
    the pairs routed, summed over the expert layers of every forward step:
    the last layer (the full row, the last writer) sees the sampled row
    only."""
    ref = _reference()
    state = _layout().init_state()
    _, state, _ = _forward(state, {0: (PROMPT, 0)}, T=48)
    hits, peak, here, routed = (int(v) for v in state["stats"][0])
    # 41 tokens x 4 experts a token x 3 layers + one row x 4 in the last
    assert routed == 41 * 4 * 3 + 4
    held = 0
    h = ref.f32(PARAMS["embed"])[jnp.asarray(PROMPT)]
    for i, (layer, kind) in enumerate(zip(PARAMS["layers"], CFG["layer_types"])):
        u = ref.layer_norm(h, layer["attn_norm"], 1e-5)
        _, idx = ref.route(layer, u, CFG)
        held += int((np.asarray(idx)[slice(None) if i < 3 else slice(-1, None)] < 4).sum())
        h = ref.layer_forward(layer, h, CFG, kind)
    assert here == held and 0 < here < routed and peak <= here and 4 <= hits <= 16


# ---------------- the packed step's window kernel ----------------


def _window_case(T, lanes, slices, seed=0, nq=8, nkv=2, d=16, ps=4, Wr=4,
                 block=8):
    """Random queries, buffer K/V and rings for `slices`: [(lane, start in
    the buffer, length, kv_start)]."""
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(T, nq, d))
    k, v = rng.normal(size=(2, T, nkv, d))
    ring = rng.normal(size=(1 + lanes * Wr, 2, nkv, ps, d))
    table = 1 + np.arange(lanes)[:, None] * Wr + np.arange(Wr)[None, :]
    seq, pos = -np.ones(T, np.int32), np.zeros(T, np.int32)
    q_start, q_len, kv_start = (np.zeros(lanes, np.int32) for _ in range(3))
    for lane, start, n, kv0 in slices:
        assert start % block == 0
        seq[start:start + n], pos[start:start + n] = lane, kv0 + np.arange(n)
        q_start[lane], q_len[lane], kv_start[lane] = start, n, kv0
    f = lambda a: jnp.asarray(a, jnp.float32)
    i = lambda a: jnp.asarray(a, jnp.int32)
    return dict(q=f(q), k_new=f(k), v_new=f(v), ring_pages=f(ring),
                ring_table=i(table), token_seq=i(seq), token_pos=i(pos),
                q_start=i(q_start), q_len=i(q_len), kv_start=i(kv_start))


#: (T, lanes, [(lane, start, length, kv_start)]); window = 16 = 4 pages of 4
WINDOW_CASES = {
    "new requests, one longer than the window": (
        64, 4, [(0, 0, 29, 0), (2, 32, 7, 0)]),
    "decode lanes beside a chunk over a wrapped ring": (
        64, 6, [(0, 0, 1, 41), (1, 8, 1, 6), (3, 16, 1, 16), (4, 24, 37, 27)]),
    "a chunk that starts mid-ring and wraps it": (
        32, 2, [(1, 0, 30, 9)]),
    "one step holds one lane, the next two": (
        64, 3, [(2, 0, 32, 100), (0, 32, 9, 3), (1, 48, 16, 15)]),
    "padding only": (32, 2, []),
    "a ring exactly full, then one token": (32, 2, [(0, 8, 1, 16), (1, 16, 2, 15)]),
}


@pytest.mark.parametrize("case", sorted(WINDOW_CASES))
def test_the_window_kernel_is_its_xla_oracle(case):
    """`window_attention_ragged_pallas` in interpret mode against
    `ring_window_attention_ragged`: the ring's part by slot position, the
    buffer's part causal within the lane, both inside the window."""
    T, lanes, slices = WINDOW_CASES[case]
    a = _window_case(T, lanes, slices)
    want = attn_ops.ring_window_attention_ragged(
        a["q"], a["k_new"], a["v_new"], a["ring_pages"], a["ring_table"],
        a["token_seq"], a["token_pos"], a["kv_start"], 0.25, 8)
    got = window_attention_ragged_pallas(
        a["q"], a["k_new"], a["v_new"], a["ring_pages"], a["ring_table"],
        a["q_start"], a["q_len"], a["kv_start"], 0.25, 8, interpret=True)
    valid = np.asarray(a["token_seq"]) >= 0
    np.testing.assert_allclose(
        np.asarray(got)[valid], np.asarray(want)[valid], rtol=2e-5, atol=2e-5)
    assert not np.asarray(got)[~valid].any()


def test_which_window_path_is_a_rule_on_sizes():
    """The kernel where the XLA form's per-block arrays pass the bound (the
    published 4096 x 128 heads), the XLA form for the first hybrid family's
    window of 512, for the CPU and for rows that are no whole tiles."""
    rule = attn_ops._should_use_window_pallas
    assert rule(128, 128, 8, 4096, 8, "tpu")
    assert not rule(128, 40, 10, 512, 8, "tpu")  # phi4-mini-flash
    assert not rule(128, 128, 8, 4096, 8, "cpu")
    assert not rule(64, 128, 8, 4096, 8, "tpu")
    assert not rule(128, 128, 8, 4096, 1, "tpu")
    a = _window_case(32, 2, [(1, 0, 30, 9)])
    auto = attn_ops.window_attention_ragged(
        a["q"], a["k_new"], a["v_new"], a["ring_pages"], a["ring_table"],
        a["token_seq"], a["token_pos"], a["q_start"], a["q_len"],
        a["kv_start"], 0.25, 8)
    want = attn_ops.ring_window_attention_ragged(
        a["q"], a["k_new"], a["v_new"], a["ring_pages"], a["ring_table"],
        a["token_seq"], a["token_pos"], a["kv_start"], 0.25, 8)
    assert np.array_equal(np.asarray(auto), np.asarray(want))
