"""`model_type: lfm2_moe` (LFM2-24B-A2B): gated short-convolution mixers
whose only per-lane state is the convolution's tail, three to every
grouped-query row with an RMSNorm a head before a half-split rotary, heads
of 64 on cache rows of 128 (two K/V heads a row), two dense feed-forwards
then routed experts; held to the plain reference
benchmark/reference/lfm2_moe.py (an explicit sum over shifted copies,
unpaired heads, a dense loop over the experts) at tiny sizes, float32,
seeded random weights.
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
from kserve_tpu.models import hybrid, llama
from kserve_tpu.ops import ssm
from kserve_tpu.ops.kv_write import write_ragged_kv

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the catalog's row (model-configs guide, LFM2-24B-A2B), as published
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv", "full_attention", "conv"] * 10,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}

#: the first 8 rows of the published table at tiny widths; heads of 64 (4
#: query / 2 K/V), so the attention rows' K/V heads lie two a cache row
CFG = dict(
    CATALOG, vocab_size=320, hidden_size=64, intermediate_size=96,
    num_hidden_layers=8, layer_types=CATALOG["layer_types"][:8],
    num_attention_heads=4, num_key_value_heads=2, head_dim=64,
    moe_intermediate_size=48, num_experts=8, num_experts_per_tok=2,
    max_position_embeddings=4096)
PAGE = 4


def _reference():
    path = os.path.join(ROOT, "benchmark", "reference", "lfm2_moe.py")
    spec = importlib.util.spec_from_file_location("reference_lfm2_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _randomised(config, seed=1):
    """scale 0.1: logits of magnitude ~1; the tensors the initialiser leaves
    at a constant (the router's bias, the head norms' weights) random, so
    that the comparison exercises them."""
    params = llama.init_params(config, jax.random.PRNGKey(seed), scale=0.1)
    for i, layer in enumerate(params["layers"]):
        key = jax.random.PRNGKey(100 + i)
        if "router_bias" in layer:
            layer["router_bias"] = 0.05 * jax.random.normal(
                key, layer["router_bias"].shape, jnp.float32)
        for k, name in enumerate(("q_norm", "k_norm")):
            if name in layer:
                layer[name] = 1.0 + 0.2 * jax.random.normal(
                    jax.random.fold_in(key, k), layer[name].shape, jnp.float32)
    return params


def _config(cfg):
    return dataclasses.replace(
        llama.LlamaConfig.from_hf_config(cfg), dtype="float32")


CONFIG = _config(CFG)
PARAMS = _randomised(CONFIG)
#: float32 on both sides; the packed experts sum in another order.  The same
#: comparison with int8 weights or a zeroed tail reads 1e-2 and more
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
    assert [r.kind for r in table] == [
        "short_conv", "short_conv", "gqa_attention", "short_conv"] * 2
    assert [r.writes for r in table] == [
        "recurrent", "recurrent", "paged_kv", "recurrent"] * 2
    assert [r.ffn for r in table] == ["dense"] * 2 + ["experts"] * 6
    assert CONFIG.n_expert_layers == 6 and CONFIG.has_expert_sums
    # every expert held, every expert layer before the last writer (row 7):
    # the host's count of routed pairs serves
    assert not CONFIG.counts_routed_pairs and CONFIG.n_experts_held == 0
    assert CONFIG.tie_word_embeddings and CONFIG.qk_norm and CONFIG.use_rope
    assert (CONFIG.conv_taps, CONFIG.head_dim, CONFIG.rope_theta) == (3, 64, 1e6)
    assert CONFIG.recurrent_slot() == (llama.NO_SCAN_STATE, 64, 3)
    conv, attn = PARAMS["layers"][0], PARAMS["layers"][2]
    assert sorted(conv) == sorted([
        "attn_norm", "mlp_norm", "in_proj", "conv_w", "out_proj",
        "w_gate", "w_up", "w_down"])
    assert sorted(attn) == sorted([
        "attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
        "router", "router_bias", "w_gate", "w_up", "w_down"])
    assert conv["in_proj"].shape == (64, 192) and conv["conv_w"].shape == (3, 64)
    assert conv["out_proj"].shape == (64, 64) and conv["w_gate"].shape == (64, 96)
    assert attn["wq"].shape == (64, 256) and attn["wk"].shape == (64, 128)
    assert attn["q_norm"].shape == attn["k_norm"].shape == (64,)
    assert attn["router"].shape == (64, 8) and attn["w_gate"].shape == (8, 64, 48)
    assert "lm_head" not in PARAMS and "shared_up" not in attn
    # the taps' draw: around 1 / taps each, so that the tail's rows weigh
    # what the current token's does
    taps = np.asarray(hybrid.init_params(CONFIG, jax.random.PRNGKey(0))
                      ["layers"][0]["conv_w"])
    assert np.abs(taps - 1 / 3).max() < 0.1 and taps.std() > 0.01


def test_the_catalog_row_and_the_cut_file_both_build():
    whole = llama.LlamaConfig.from_hf_config(CATALOG)
    table = whole.layer_table()
    assert len(table) == 40
    assert [i for i, r in enumerate(table) if r.kind == "gqa_attention"] == list(
        range(2, 40, 4))
    assert [i for i, r in enumerate(table) if r.ffn == "dense"] == [0, 1]
    assert (whole.head_dim, whole.cache_kv_heads, whole.cache_head_dim) == (64, 4, 128)
    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2-24b-a2b.json")) as f:
        cut = json.load(f)
    changed = {k for k in CATALOG if cut[k] != CATALOG[k]}
    assert changed == set(cut["reduced"]) == {"num_hidden_layers", "layer_types"}
    assert cut["head_dim"] == 64  # the file's own key, under `assumed`
    assert cut["deployment"]["published"] == {k: CATALOG[k] for k in changed}
    mc = llama.LlamaConfig.from_hf_config(
        {k: v for k, v in cut.items()
         if k not in ("deployment", "assumed", "source", "reduced", "rehearsal")})
    assert mc.layer_table() == table[:8]
    assert (mc.n_experts, mc.n_experts_held, mc.vocab_size) == (64, 0, 65536)


def test_a_tail_only_slot_allocates_no_float32_state():
    """The published model's first 8 layers: 6 tails of [2, 2048] bf16 a
    lane, 48 KB whatever its context, and NO scan state: `ssm` costs 0 bytes
    and holds no array; K/V of the two attention rows at 64-wide heads two a
    128-wide row, the same 2048 B a token and layer."""
    mc = llama.LlamaConfig.from_hf_config(dict(
        CATALOG, num_hidden_layers=8, layer_types=CATALOG["layer_types"][:8]))
    layout = kvcache.StateLayout.of(mc, 64, 100, 48)
    assert layout.paged_layers == (2, 6)
    assert layout.recurrent_layers == (0, 1, 3, 4, 5, 7)
    assert (layout.kv_heads, layout.head_dim) == (4, 128)
    assert layout.token_bytes() == 4096 == 2 * 8 * 64 * 2 * 2
    lane = layout.lane_bytes()
    assert lane == {"window_kv": 0, "ssm": 0, "conv": 6 * 2 * 2048 * 2}
    assert lane["conv"] == 49152
    in_use = layout.bytes_in_use(10, 7)
    assert in_use["ssm"] == 0 and in_use["conv"] == 10 * 49152
    assert in_use["shared_kv"] == 7 * 64 * 4096
    state = jax.eval_shape(layout.init_state)
    assert state["ssm"] == []
    assert [a.shape for a in state["conv"]] == [(48, 2, 2048)] * 6
    assert {a.dtype for a in state["conv"]} == {jnp.dtype("bfloat16")}
    assert [a.shape for a in state["paged"]] == [(100, 2, 4, 64, 128)] * 2
    leaves = jax.tree.leaves(state)
    assert not [a for a in leaves if a.dtype == jnp.float32]
    assert layout.expert_layers == 6 and layout.expert_sums == 2


REFUSALS = {
    "conv_bias": {"conv_bias": True},
    "conv_L_cache": {"conv_L_cache": 1},
    "layer_types": {"layer_types": ["conv", "sliding_attention"] * 4},
    "layer_types of 7": {"layer_types": CFG["layer_types"][:7]},
    "num_dense_layers": {"num_dense_layers": 9},
    "rope_type": {"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}},
    "tie_word_embeddings": {"tie_word_embeddings": False},
}


@pytest.mark.parametrize("named", sorted(REFUSALS))
def test_what_is_not_built_is_refused_by_name(named):
    with pytest.raises(ValueError, match="lfm2_moe: not implemented.*" + named):
        llama.LlamaConfig.from_hf_config(dict(CFG, **REFUSALS[named]))


def test_the_reference_refuses_what_it_does_not_compute():
    for extra in ({"conv_bias": True}, {"model_type": "lfm2"},
                  {"layer_types": ["conv", "sliding_attention"] * 4},
                  {"tie_word_embeddings": False}):
        with pytest.raises(NotImplementedError):
            _reference().check_supported(dict(CFG, **extra))


def _conv_layer(seed=0, hidden=64):
    key = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {
        "in_proj": 0.1 * jax.random.normal(key[0], (hidden, 3 * hidden)),
        "conv_w": 1 / 3 + 0.1 * jax.random.normal(key[1], (3, hidden)),
        "out_proj": 0.1 * jax.random.normal(key[2], (hidden, hidden))}


def _step(layer, u, tail):
    return hybrid._short_conv(layer, u, tail, ssm.causal_conv_step)


def test_the_one_step_form_is_the_reference_token_by_token():
    """`[B | C | x]`, the product, three taps over the carried tail, the
    product, `out_proj`: one token a call from a zero tail is the
    reference's sum over three shifted copies of the whole sequence."""
    layer = _conv_layer()
    u = jax.random.normal(jax.random.PRNGKey(7), (12, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(_reference().short_conv(layer, u))
        tail = jnp.zeros((1, 2, 64), jnp.float32)
        for t in range(12):
            y, tail = _step(layer, u[t:t + 1], tail)
            np.testing.assert_allclose(
                np.asarray(y[0]), want[t], rtol=1e-5, atol=1e-6)
    # the tail is the lane's last two rows of z = B * x, oldest first
    b, _, x = np.split(np.asarray(u @ layer["in_proj"]), 3, axis=-1)
    np.testing.assert_allclose(
        np.asarray(tail[0]), (b * x)[-2:], rtol=1e-5, atol=1e-6)


#: (T, lanes, [(lane, start, length)], lanes that open a request)
PACKED_CASES = {
    "slices of unequal length in one buffer": (
        32, 4, [(0, 0, 9), (2, 9, 4), (3, 13, 17)], (0, 2, 3)),
    "a slice of one token and of two: shorter than the taps": (
        16, 4, [(1, 0, 1), (0, 8, 2), (3, 10, 5)], (0,)),
    "every slice continued from its lane's stored tail": (
        32, 4, [(2, 3, 11), (0, 16, 1), (1, 24, 2)], ()),
    "aligned slices with padding between, a lane with no slice": (
        32, 4, [(0, 0, 5), (3, 8, 8), (1, 24, 3)], (3,)),
    "one lane fills the buffer": (16, 2, [(1, 0, 16)], ()),
    "an empty buffer: every lane keeps its tail": (16, 3, [], ()),
}


@pytest.mark.parametrize("case", sorted(PACKED_CASES))
def test_the_packed_form_is_the_one_step_form(case):
    """`_short_conv` over the packed buffer (`causal_conv_ragged`: static
    shifts, a slice's first two rows from the lane's stored tail) against
    the one-step form run token by token: a slice that opens a request
    starts from zeros, one that continues from the stored tail; a lane
    without a slice (a dead lane) keeps its tail."""
    T, B, slices, fresh = PACKED_CASES[case]
    layer = _conv_layer(seed=3)
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(T, 64)), jnp.float32)
    stored = jnp.asarray(rng.normal(size=(B, 2, 64)), jnp.float32)
    q_start, q_len = np.zeros(B, np.int32), np.zeros(B, np.int32)
    seq, off = -np.ones(T, np.int32), np.zeros(T, np.int32)
    for lane, start, n in slices:
        q_start[lane], q_len[lane] = start, n
        seq[start:start + n], off[start:start + n] = lane, np.arange(n)
    is_fresh = np.zeros(B, bool)
    is_fresh[list(fresh)] = True
    with jax.default_matmul_precision("highest"):
        y, tails = hybrid._short_conv(
            layer, u, stored, ssm.causal_conv_ragged, jnp.asarray(seq),
            jnp.asarray(off), jnp.asarray(q_start), jnp.asarray(q_len),
            jnp.asarray(is_fresh))
        for lane, start, n in slices:
            tail = stored[lane:lane + 1] * (0.0 if is_fresh[lane] else 1.0)
            for t in range(start, start + n):
                want, tail = _step(layer, u[t:t + 1], tail)
                np.testing.assert_allclose(
                    np.asarray(y[t]), np.asarray(want[0]), rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(
                np.asarray(tails[lane]), np.asarray(tail[0]), rtol=1e-6, atol=1e-7)


def test_whole_prompt_chunks_packed_dispatch_and_decode_agree_with_the_reference():
    """A 23-token prompt prefilled whole, and in two chunks (the second
    starts from the first's stored tails and reads its pages) packed beside
    another lane's whole prompt at 8-token alignment; then decode steps
    through the tails and the pages: logits against the reference's full
    forward."""
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
    # a prompt chunked over two dispatches equals one chunk: the same logits
    # and the same tails and pages left to lane 0
    np.testing.assert_allclose(np.asarray(second[0]), np.asarray(whole[0]), **TOL)
    assert state["ssm"] == state_whole["ssm"] == []
    for a, b in zip(state_whole["conv"], state["conv"]):
        np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]), **TOL)
    for a, b in zip(state_whole["paged"], state["paged"]):
        np.testing.assert_allclose(np.asarray(a[1:7]), np.asarray(b[1:7]), **TOL)
    # lane 1 took no slice: its tails are as they were
    assert not np.asarray(state["conv"][0][1]).any()
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
    the same logits and the same tails as the one-step form."""
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
    for a, b in zip(state_a["conv"], state_b["conv"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)


def test_a_zeroed_tail_fails_the_comparison():
    """The stored tails set to zero at a chunk boundary, and before a decode
    step: the logits leave the reference by far more than TOL, so the
    comparison sees what a lane carries (taps of 1 / 3: with taps of
    deviation `scale` alone the carried rows would weigh a hundredth)."""
    ref = _reference()
    want = np.asarray(ref.forward(PARAMS, CFG, PROMPT + [5]))
    limit = 30 * TOL["atol"] + 30 * TOL["rtol"] * np.abs(want).max()
    _, state, _ = _forward(_layout().init_state(), {0: (PROMPT[:15], 0)})
    right, kept, table = _forward(state, {0: (PROMPT[15:], 15)}, T=8)
    np.testing.assert_allclose(np.asarray(right[0]), want[22], **TOL)
    wiped = dict(state, conv=[jnp.zeros_like(a) for a in state["conv"]])
    wrong, _, _ = _forward(wiped, {0: (PROMPT[15:], 15)}, T=8)
    assert np.abs(np.asarray(wrong[0]) - want[22]).max() > limit
    args = (jnp.asarray([5, 0, 0, 0]), jnp.asarray([23, 0, 0, 0]))
    live = jnp.asarray([True, False, False, False])
    logits, _ = llama.decode_step(PARAMS, CONFIG, *args, kept, table, live, PAGE)
    np.testing.assert_allclose(np.asarray(logits[0]), want[23], **TOL)
    wiped = dict(kept, conv=[jnp.zeros_like(a) for a in kept["conv"]])
    logits, _ = llama.decode_step(PARAMS, CONFIG, *args, wiped, table, live, PAGE)
    assert np.abs(np.asarray(logits[0]) - want[23]).max() > limit


def _int8(w):
    """Per-output-channel symmetric int8, dequantised: the nearest precision
    below the configuration's that the program has."""
    w = np.asarray(w, np.float32)
    scale = np.abs(w).max(axis=-2, keepdims=True) / 127.0 + 1e-12
    return jnp.asarray(np.round(w / scale) * scale)


def test_int8_weights_fail_the_tolerance():
    ref = _reference()
    want = np.asarray(ref.forward(PARAMS, CFG, PROMPT))[-1]
    limit = 30 * TOL["atol"] + 30 * TOL["rtol"] * np.abs(want).max()
    quantised = jax.tree.map(
        lambda a: _int8(a) if a.ndim >= 2 and a.shape[-1] > 8 else a, PARAMS)
    low = np.asarray(ref.forward(quantised, CFG, PROMPT))[-1]
    assert np.abs(low - want).max() > limit


def _attention_case():
    """An attention row of 4 query / 2 K/V heads of 64 over two lanes: lane
    0 a 21-token slice continued from 11 cached tokens, lane 1 a whole
    13-token prompt; what the reference gives for each sequence."""
    layer = PARAMS["layers"][2]
    rng = np.random.default_rng(3)
    u = {0: rng.normal(size=(32, 64)), 1: rng.normal(size=(13, 64))}
    u = {lane: jnp.asarray(x, jnp.float32) for lane, x in u.items()}
    ref = _reference()
    want = {lane: np.asarray(ref.attention(layer, x, CFG)) for lane, x in u.items()}
    return layer, u, want


def _write(layer, pages, table, u, seq, pos):
    k, v = hybrid._gqa_keys_values(layer, u, CONFIG, pos, 2)
    return write_ragged_kv(pages, k, v, table, seq, pos, PAGE)


def test_heads_of_64_lie_two_a_cache_row_and_equal_unpaired_attention():
    """The pair rule on the kernels: K/V heads 2r and 2r+1 side by side in a
    128-wide cache row, a query padded into the half of its own K/V head
    and carrying the sqrt(2) the row's width^-1/2 is short of, the matching
    half of the output kept: the ragged kernel and the decode kernel (both
    in interpret mode) and the XLA gather each equal the reference's
    unpaired 64-wide attention, the norm a head and the rotary included."""
    from kserve_tpu.ops import attention as att
    from kserve_tpu.ops.pallas_paged_attention import (
        paged_attention_pallas,
        ragged_paged_attention_pallas,
    )

    assert CONFIG.pairs_kv_heads
    assert (CONFIG.cache_kv_heads, CONFIG.cache_head_dim) == (1, 128)
    layer, u, want = _attention_case()
    table = jnp.asarray(1 + np.arange(2 * 16).reshape(2, 16), jnp.int32)
    pages = jnp.zeros((40, 2, 1, PAGE, 128), jnp.float32)
    with jax.default_matmul_precision("highest"):
        # lane 0's first 11 tokens are in the cache already
        pages = _write(layer, pages, table, u[0][:11],
                       jnp.zeros(11, jnp.int32), jnp.arange(11, dtype=jnp.int32))
        # the packed buffer: lane 0's next 21 tokens at 0, lane 1's 13 at 24
        T = 40
        buf = jnp.zeros((T, 64), jnp.float32).at[:21].set(u[0][11:]).at[24:37].set(u[1])
        seq = np.full(T, -1, np.int32)
        seq[:21], seq[24:37] = 0, 1
        pos = np.zeros(T, np.int32)
        pos[:21], pos[24:37] = 11 + np.arange(21), np.arange(13)
        seq, pos = jnp.asarray(seq), jnp.asarray(pos)
        pages = _write(layer, pages, table, buf, seq, pos)
        q = hybrid._gqa_queries(layer, buf, CONFIG, pos, 2)
        assert q.shape == (T, 4, 128)
        # heads 0, 1 read K/V head 0 (the row's first half), heads 2, 3 the second
        assert not np.asarray(q[:, :2, 64:]).any() and not np.asarray(q[:, 2:, :64]).any()
        q_start = jnp.asarray([0, 24], jnp.int32)
        q_len = jnp.asarray([21, 13], jnp.int32)
        kv_start = jnp.asarray([11, 0], jnp.int32)
        for attend in (
                lambda: ragged_paged_attention_pallas(
                    q, pages, table, q_start, q_len, kv_start, interpret=True),
                lambda: att.ragged_paged_attention_xla(
                    q, pages, table, q_start, q_len, kv_start)):
            out = np.asarray(hybrid._gqa_out(layer, attend(), CONFIG))
            np.testing.assert_allclose(out[:21], want[0][11:], **TOL)
            np.testing.assert_allclose(out[24:37], want[1], **TOL)
        # one query a lane: each lane's last token over its whole context
        rows = jnp.stack([u[0][31], u[1][12]])
        q1 = hybrid._gqa_queries(layer, rows, CONFIG, jnp.asarray([31, 12]), 2)
        seq_lens = jnp.asarray([32, 13], jnp.int32)
        for attend in (
                lambda: paged_attention_pallas(
                    q1, pages, table, seq_lens, interpret=True),
                lambda: att.paged_attention_xla(q1, pages, table, seq_lens)):
            out = np.asarray(hybrid._gqa_out(layer, attend(), CONFIG))
            np.testing.assert_allclose(out[0], want[0][31], **TOL)
            np.testing.assert_allclose(out[1], want[1][12], **TOL)


def test_the_pair_rule_is_for_heads_of_64_alone():
    """A width that is neither 64 nor a multiple of 128, the Llama path and
    a table with window rows keep their rows as they are."""
    for head_dim, paired in ((64, True), (16, False), (128, False), (32, False)):
        mc = llama.LlamaConfig.from_hf_config(dict(CFG, head_dim=head_dim))
        assert mc.pairs_kv_heads == paired
        assert mc.cache_head_dim == (128 if paired else head_dim)
        assert mc.cache_kv_heads == (1 if paired else 2)
    assert not llama.LlamaConfig.llama3_1b().pairs_kv_heads
    assert not llama.LlamaConfig.from_hf_config(
        dict(CFG, num_key_value_heads=1)).pairs_kv_heads
    ringed = dataclasses.replace(
        CONFIG, sliding_window=8, mixer_kinds=("gqa_window_attention",) + (
            CONFIG.mixer_kinds[1:]))
    assert not ringed.pairs_kv_heads


def test_unpaired_heads_of_16_agree_with_the_reference_too():
    """The benchmark's rehearsal runs heads of 16: no pairs, the same
    forward."""
    cfg = dict(CFG, head_dim=16)
    config = _config(cfg)
    params = _randomised(config, seed=2)
    want = np.asarray(_reference().forward(params, cfg, PROMPT))
    logits, _, _ = _forward(
        _layout(config=config).init_state(), {0: (PROMPT, 0)}, params, config)
    np.testing.assert_allclose(np.asarray(logits[0]), want[-1], **TOL)


def _broken_references():
    """The reference with one assumed detail changed: each must leave the
    program's logits by far more than TOL."""
    def slices_in_another_order(ref):
        conv = ref.short_conv

        def wrong(layer, u):  # [C | B | x]
            b, c, x = np.split(np.asarray(layer["in_proj"]), 3, axis=-1)
            return conv(dict(layer, in_proj=np.concatenate([c, b, x], -1)), u)
        ref.short_conv = wrong

    def taps_reversed(ref):
        conv = ref.short_conv
        ref.short_conv = lambda layer, u: conv(
            dict(layer, conv_w=jnp.asarray(layer["conv_w"])[::-1]), u)

    def an_activation_behind_the_convolution(ref):
        conv = ref.short_conv

        def wrong(layer, u):  # silu on what the taps read, as a Mamba's
            b, c, x = jnp.split(u @ ref.f32(layer["in_proj"]), 3, axis=-1)
            w, t = ref.f32(layer["conv_w"]), u.shape[0]
            z = jnp.concatenate([jnp.zeros((2, u.shape[1])), b * x])
            out = jax.nn.silu(sum(z[j:j + t] * w[j] for j in range(3)))
            return (c * out) @ ref.f32(layer["out_proj"])
        ref.short_conv = wrong

    def no_norm_a_head(ref):
        attention = ref.attention
        ref.attention = lambda layer, u, cfg: attention(
            dict(layer, q_norm=jnp.ones(64), k_norm=jnp.ones(64)), u, cfg)

    def rotary_of_the_interleaved_kind(ref):
        def wrong(x, theta):  # columns (2j, 2j+1)
            t, _, d = x.shape
            inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
            ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
            cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
            x1, x2 = x[..., 0::2], x[..., 1::2]
            return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                             axis=-1).reshape(x.shape)
        ref.rope = wrong

    def an_untied_head(ref):
        forward = ref.forward

        def wrong(params, cfg, tokens):
            logits = forward(params, cfg, tokens)
            return logits * 0.5
        ref.forward = wrong

    return {f.__name__: f for f in (
        slices_in_another_order, taps_reversed,
        an_activation_behind_the_convolution, no_norm_a_head,
        rotary_of_the_interleaved_kind, an_untied_head)}


@pytest.mark.parametrize("name", sorted(_broken_references()))
def test_each_assumed_detail_is_seen_by_the_comparison(name):
    ref = _reference()
    want = np.asarray(ref.forward(PARAMS, CFG, PROMPT))[-1]
    _broken_references()[name](ref)
    wrong = np.asarray(ref.forward(PARAMS, CFG, PROMPT))[-1]
    limit = 30 * TOL["atol"] + 30 * TOL["rtol"] * np.abs(want).max()
    assert np.abs(wrong - want).max() > limit


def test_the_expert_sums_ride_the_state_and_the_host_counts_the_pairs():
    """Hits and the fullest expert's rows, summed over the six expert layers
    of a forward step; the pairs are the host's to count (every expert held,
    every expert layer sees every token)."""
    state = _layout().init_state()
    _, state, _ = _forward(state, {0: (PROMPT, 0)}, T=32)
    assert state["stats"][0].shape == (2,)
    hits, peak = (int(v) for v in state["stats"][0])
    assert 6 <= hits <= 6 * 8 and 6 * 23 * 2 / 8 <= peak <= 6 * 23
