"""`model_type: nemotron_h` (Nemotron-3-Nano): ONE sublayer a layer, by the
letter of `hybrid_override_pattern`: Mamba-2 mixers (ops/ssm.ssd_*: a
matrix-valued state a head), ungated relu^2 experts of which this chip
holds a share (models/moe.py) and plain grouped-query attention without a
positional encoding, held to the plain reference
benchmark/reference/nemotron_h.py (a sequential scan, a dense loop over the
held experts) at tiny sizes, float32, seeded random weights.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_tpu.engine import kvcache
from kserve_tpu.models import llama, moe
from kserve_tpu.ops import ssm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: all three letters, the attention layer in the middle and one Mamba-2
#: layer behind the last expert layer; 8 experts scored, 4 held, 2 a token;
#: 8 heads of 8 in 2 groups, state 16
CFG = {
    "model_type": "nemotron_h", "vocab_size": 320, "hidden_size": 64,
    "intermediate_size": 48, "num_hidden_layers": 6,
    "hybrid_override_pattern": "ME*MEM", "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "mamba_num_heads": 8,
    "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "chunk_size": 128, "expand": 2,
    "n_routed_experts": 4, "router_n_experts": 8, "first_expert": 0,
    "num_experts_per_tok": 2, "moe_intermediate_size": 48,
    "moe_shared_expert_intermediate_size": 96, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "layer_norm_epsilon": 1e-5, "tie_word_embeddings": False,
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "attention_bias": False, "mamba_proj_bias": False, "mlp_bias": False,
    "use_bias": False, "use_conv_bias": True, "sliding_window": None,
    "residual_in_fp32": False, "rope_theta": 10000,
    "max_position_embeddings": 4096}
PAGE = 4


def _reference():
    path = os.path.join(ROOT, "benchmark", "reference", "nemotron_h.py")
    spec = importlib.util.spec_from_file_location("reference_nemotron", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _randomised(config, seed=1):
    """scale 0.1: logits of magnitude ~1; the tensors the initialiser
    leaves at a constant (the router's bias, D, the gated norm's weight)
    random, so that the comparison exercises them."""
    params = llama.init_params(config, jax.random.PRNGKey(seed), scale=0.1)
    for i, layer in enumerate(params["layers"]):
        key = jax.random.PRNGKey(100 + i)
        if "router_bias" in layer:
            layer["router_bias"] = 0.05 * jax.random.normal(
                key, layer["router_bias"].shape, jnp.float32)
        if "ssm_norm" in layer:
            layer["ssm_norm"] = 1.0 + 0.2 * jax.random.normal(
                key, layer["ssm_norm"].shape, jnp.float32)
            layer["D"] = 1.0 + 0.3 * jax.random.normal(
                jax.random.fold_in(key, 1), layer["D"].shape, jnp.float32)
    return params


CONFIG = dataclasses.replace(llama.LlamaConfig.from_hf_config(CFG), dtype="float32")
PARAMS = _randomised(CONFIG)
#: float32 on both sides; the chunked form re-associates the recurrence
#: and the packed experts sum in another order.  The same comparison with
#: int8 weights reads ~1e-2 (test below)
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
    assert CONFIG.is_hybrid and not CONFIG.is_latent and CONFIG.one_sublayer
    table = CONFIG.layer_table()
    assert [r.kind for r in table] == [
        "mamba2", "ffn", "gqa_attention", "mamba2", "ffn", "mamba2"]
    assert [r.writes for r in table] == [
        "recurrent", "none", "paged_kv", "recurrent", "none", "recurrent"]
    assert [r.ffn for r in table] == [
        "none", "experts", "none", "none", "experts", "none"]
    assert CONFIG.n_expert_layers == 2 and CONFIG.has_expert_sums
    assert (CONFIG.n_experts, CONFIG.n_experts_held, CONFIG.first_expert) == (8, 4, 0)
    assert not CONFIG.use_rope and not CONFIG.diff_attention
    assert CONFIG.mamba_d_inner == 64 and CONFIG.mamba2_conv_dim == 64 + 2 * 2 * 16
    mamba, experts, attention = (PARAMS["layers"][i] for i in (0, 1, 2))
    # one norm a layer, and only the tensors of the layer's one sublayer
    assert sorted(mamba) == ["A_log", "D", "attn_norm", "conv_b", "conv_w",
                             "dt_bias", "in_proj", "out_proj", "ssm_norm"]
    assert mamba["in_proj"].shape == (64, 64 + 128 + 8)
    assert mamba["conv_w"].shape == (4, 128) and mamba["A_log"].shape == (8,)
    assert {mamba[k].dtype for k in ("A_log", "D", "dt_bias")} == {jnp.dtype("float32")}
    assert sorted(experts) == ["mlp_norm", "router", "router_bias", "shared_down",
                               "shared_up", "w_down", "w_up"]
    # the router keeps its width; the stacked tensors hold the 4 held, ungated
    assert experts["router"].shape == (64, 8) and experts["router_bias"].shape == (8,)
    # ... at a width under one 512-column tile, which is stored as it is
    assert experts["w_up"].shape == (4, 64, 48) and experts["w_down"].shape == (4, 48, 64)
    assert experts["shared_up"].shape == (64, 96)
    assert sorted(attention) == ["attn_norm", "wk", "wo", "wq", "wv"]
    assert attention["wk"].shape == (64, 32)
    assert PARAMS["lm_head"].shape == (64, 320)


def test_the_recurrent_slot_takes_its_shapes_from_the_mixer():
    """The published model cut to 16 layers: a matrix a head, the tail over
    x, B and C; K/V of the two attention layers only."""
    published = dict(
        CFG, hidden_size=2688, num_hidden_layers=16,
        hybrid_override_pattern="MEMEM*EMEMEM*EME", num_attention_heads=32,
        head_dim=128, mamba_num_heads=64, mamba_head_dim=64, n_groups=8,
        ssm_state_size=128, n_routed_experts=64, router_n_experts=128,
        num_experts_per_tok=6, moe_intermediate_size=1856,
        moe_shared_expert_intermediate_size=3712, vocab_size=131072)
    mc = llama.LlamaConfig.from_hf_config(published)
    layout = kvcache.StateLayout.of(mc, 64, 100, 48)
    assert layout.paged_layers == (5, 12) and len(layout.recurrent_layers) == 7
    assert layout.token_bytes() == 2048 == 2 * 2 * 2 * 128 * 2
    lane = layout.lane_bytes()
    assert lane["ssm"] == 7 * 64 * 64 * 128 * 4 and lane["conv"] == 7 * 3 * 6144 * 2
    assert layout.expert_layers == 7 and layout.expert_sums == 4
    state = jax.eval_shape(layout.init_state)
    assert [a.shape for a in state["ssm"]] == [(48, 64, 64, 128)] * 7
    assert [a.shape for a in state["conv"]] == [(48, 3, 6144)] * 7
    assert {a.dtype for a in state["ssm"]} == {jnp.dtype("float32")}
    assert [a.shape for a in state["paged"]] == [(100, 2, 2, 64, 128)] * 2
    assert state["stats"][0].shape == (4,)
    shapes = moe.moe_param_shapes(moe.moe_config_of(mc))
    assert shapes["w_up"] == (64, 2688, 2048) and shapes["router"] == (2688, 128)
    assert shapes["w_down"] == (64, 2048, 2688) and 1856 % 128
    assert shapes["shared_up"] == (2688, 3712) and "w_gate" not in shapes
    # the first hybrid family's slot is what it was
    phi = kvcache.StateLayout(
        paged_layers=(), window_layers=(), recurrent_layers=(0,), kv_heads=1,
        head_dim=8, page_size=4, num_pages=8, lanes=2, window=0, d_inner=12,
        d_state=5, d_conv=4)
    assert phi.ssm_shape == (12, 5) and phi.conv_width == 12
    assert phi.lane_bytes()["ssm"] == 12 * 5 * 4


def test_what_is_not_built_is_refused_by_name():
    for extra, named in (
            ({"n_group": 4, "topk_group": 2}, "group-limited"),
            ({"hybrid_override_pattern": "ME-MEM"}, "letters"),
            ({"hybrid_override_pattern": "ME*M"}, "4 letters"),
            ({"mamba_proj_bias": True}, "mamba_proj_bias"),
            ({"mlp_bias": True}, "mlp_bias"),
            ({"attention_bias": True}, "attention_bias"),
            ({"sliding_window": 4096}, "sliding_window"),
            ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
            ({"n_groups": 3}, "n_groups")):
        with pytest.raises(ValueError, match=named):
            llama.LlamaConfig.from_hf_config(dict(CFG, **extra))
        if "n_groups" not in extra and "letters" not in named:
            with pytest.raises(NotImplementedError):
                _reference().check_supported(dict(CFG, **extra))
    with pytest.raises(ValueError, match="held of"):
        moe.MoEConfig(n_experts=8, first_expert=6, held=4)
    # a plain row beside cache rows that hold differential pairs
    with pytest.raises(ValueError, match="gqa_attention rows"):
        dataclasses.replace(CONFIG, diff_attention=True)


def test_whole_prompt_chunks_packed_dispatch_and_decode_agree_with_the_reference():
    """A 23-token prompt prefilled whole, and in two chunks (the second
    starts from the first's stored state, tail and pages) packed beside
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
    # the two ways leave lane 0 the same state, tail and pages
    for kind in ("ssm", "conv"):
        for a, b in zip(state_whole[kind], state[kind]):
            np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]), **TOL)
    for a, b in zip(state_whole["paged"], state["paged"]):
        np.testing.assert_allclose(np.asarray(a[1:7]), np.asarray(b[1:7]), **TOL)
    # lane 1 took no slice: its slots are as they were
    assert not np.asarray(state["ssm"][0][1]).any()
    # decode: both lanes, 6 steps on the program's own argmax; the reference
    # then runs ONCE over each lane's whole sequence (a row depends on the
    # tokens before it only)
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


def _scan_case(T, B, slices, fresh=(), seed=0, H=4, P=8, G=2, N=16):
    rng = np.random.default_rng(seed)
    arrays = dict(
        x=rng.normal(size=(T, H, P)), dt=rng.uniform(0.001, 0.5, size=(T, H)),
        A=-rng.uniform(1, 16, size=(H,)), Bm=rng.normal(size=(T, G, N)),
        Cm=rng.normal(size=(T, G, N)), D=rng.normal(size=(H,)),
        state=rng.normal(size=(B, H, P, N)))
    arrays = {k: jnp.asarray(v, jnp.float32) for k, v in arrays.items()}
    seq = -np.ones(T, np.int32)
    q_start, q_len, last = (np.zeros(B, np.int32) for _ in range(3))
    for lane, start, n in slices:
        seq[start:start + n] = lane
        q_start[lane], q_len[lane], last[lane] = start, n, start + n - 1
    is_fresh = np.zeros(B, bool)
    is_fresh[list(fresh)] = True
    return arrays, seq, q_start, q_len, last, is_fresh


#: (T, lanes, [(lane, start, length)], lanes that open a request, chunk)
SCAN_CASES = {
    "decode lanes and a chunk over three chunks": (
        64, 6, [(0, 0, 1), (1, 8, 1), (2, 16, 3), (3, 24, 37)], (2,), 16),
    "one chunk holds everything": (
        64, 6, [(0, 0, 1), (1, 8, 1), (2, 16, 3), (3, 24, 37)], (), 64),
    "a slice that opens mid-chunk and runs over its end": (
        64, 6, [(5, 3, 29), (1, 32, 32)], (1,), 16),
    "one lane, every chunk a continuation": (64, 6, [(5, 0, 64)], (), 8),
    "unaligned slices, all new requests": (
        32, 3, [(2, 1, 5), (0, 6, 7), (1, 13, 19)], (0, 1, 2), 8),
    "chunks smaller than a slice's head": (
        32, 3, [(2, 1, 5), (0, 6, 7), (1, 13, 19)], (), 4),
    "padding at both ends": (32, 3, [(1, 8, 9)], (), 8),
}


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_the_packed_scan_is_the_one_step_form_token_by_token(case):
    """`ssd_ragged` (chunked matrix products, one state a chunk) against
    `ssd_step` run token by token: slices that open a request (zero state),
    slices that continue one (the lane's stored state), padding between and
    around; a lane without a slice keeps its state."""
    T, B, slices, fresh, chunk = SCAN_CASES[case]
    a, seq, q_start, q_len, last, is_fresh = _scan_case(T, B, slices, fresh)
    y, new = ssm.ssd_ragged(
        a["x"], a["dt"], a["A"], a["Bm"], a["Cm"], a["D"], a["state"],
        jnp.asarray(seq), jnp.asarray(q_start), jnp.asarray(q_len),
        jnp.asarray(last), jnp.asarray(is_fresh), chunk)
    want_state = np.array(a["state"])
    for lane, start, n in slices:
        s = jnp.zeros_like(a["state"][:1]) if is_fresh[lane] else a["state"][lane:lane + 1]
        for t in range(start, start + n):
            y_t, s = ssm.ssd_step(
                a["x"][t:t + 1], a["dt"][t:t + 1], a["A"], a["Bm"][t:t + 1],
                a["Cm"][t:t + 1], a["D"], s, jnp.ones((1,), bool))
            np.testing.assert_allclose(
                np.asarray(y[t]), np.asarray(y_t[0]), rtol=1e-4, atol=1e-4)
        want_state[lane] = np.asarray(s[0])
    np.testing.assert_allclose(np.asarray(new), want_state, rtol=1e-4, atol=1e-5)


def test_a_lane_that_is_not_live_keeps_its_state():
    a, *_ = _scan_case(8, 3, [])
    _, new = ssm.ssd_step(
        a["x"][:3], a["dt"][:3], a["A"], a["Bm"][:3], a["Cm"][:3], a["D"],
        a["state"], jnp.asarray([True, False, True]))
    assert np.array_equal(np.asarray(new[1]), np.asarray(a["state"][1]))
    assert not np.array_equal(np.asarray(new[0]), np.asarray(a["state"][0]))


def test_the_shares_add_up_to_the_uncut_layer():
    """THE share test: the routed parts that the two halves of the experts
    compute, plus the shared expert counted once, are the uncut reference's
    expert layer; the program's two shares likewise."""
    ref = _reference()
    whole_cfg = dict(CFG, n_routed_experts=8)
    whole_mc = dataclasses.replace(
        llama.LlamaConfig.from_hf_config(whole_cfg), dtype="float32")
    assert whole_mc.n_experts_held == 0
    layer = _randomised(whole_mc, seed=3)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(5), (19, 64), jnp.float32)
    want = np.asarray(ref.experts(layer, x, whole_cfg))
    shared = np.asarray(ref.relu2(x, layer["shared_up"], layer["shared_down"]))
    parts_ref, parts_program, multiplied = [], [], 0
    for first in (0, 4):
        half = dict(layer, w_up=layer["w_up"][first:first + 4],
                    w_down=layer["w_down"][first:first + 4])
        cfg = dict(CFG, first_expert=first)
        parts_ref.append(np.asarray(ref.experts(half, x, cfg)) - shared)
        mc = moe.moe_config_of(llama.LlamaConfig.from_hf_config(cfg))
        assert (mc.first_expert, mc.n_held, mc.holds_all) == (first, 4, False)
        out, rows = moe.moe_mlp(half, x, mc, with_rows=True)
        parts_program.append(np.asarray(out) - shared)
        multiplied += int(rows.sum())
    np.testing.assert_allclose(sum(parts_ref) + shared, want, **TOL)
    np.testing.assert_allclose(sum(parts_program) + shared, want, **TOL)
    # every routed pair was multiplied by exactly one of the two chips
    assert multiplied == 19 * 2
    # and one half alone is not the layer
    assert np.abs(parts_program[0] + shared - want).max() > 100 * TOL["atol"]


def _held_pairs(params, cfg, tokens, rows_behind):
    """Pairs that fall on the experts held (the first four), by the
    reference's router: every token in an expert layer up to the last
    Mamba-2 layer, the rows `rows_behind` in one behind it."""
    ref = _reference()
    pattern = cfg["hybrid_override_pattern"]
    held = 0
    x = np.asarray(ref.f32(params["embed"])[jnp.asarray(tokens)])
    for i, (layer, letter) in enumerate(zip(params["layers"], pattern)):
        if letter == "E":
            _, idx = ref.route(layer, ref.rms_norm(x, layer["mlp_norm"], 1e-5), cfg)
            seen = slice(None) if i < pattern.rindex("M") else rows_behind
            held += int((np.asarray(idx)[seen] < 4).sum())
        x = np.asarray(ref.layer_forward(layer, jnp.asarray(x), cfg, letter))
    return held


def test_the_expert_sums_ride_the_state():
    """Hits, the fullest expert's rows and, with a share, the pairs this
    chip multiplied and the pairs routed, summed over the expert layers of
    every forward step."""
    state = _layout().init_state()
    _, state, table = _forward(state, {0: (PROMPT, 0)}, T=32)
    hits, peak, here, routed = (int(v) for v in state["stats"][0])
    # 23 tokens x 2 experts a token x 2 expert layers = 92 pairs routed,
    # about half of them to the 4 experts held; padding reaches no expert
    assert routed == 92
    assert 2 <= hits <= 8 and 0 < here < routed and peak <= here
    assert here == _held_pairs(PARAMS, CFG, PROMPT, slice(None))


def test_an_expert_layer_behind_the_last_writer_counts_the_rows_it_saw():
    """`...ME`: the packed forward runs the closing expert layer on one row
    a lane with a slice, not on every token, so the pairs routed are NOT
    tokens x experts a token x expert layers; a decode step runs every
    layer on every live lane.  The published cut ends so."""
    cfg = dict(CFG, num_hidden_layers=5, hybrid_override_pattern="ME*ME")
    config = dataclasses.replace(
        llama.LlamaConfig.from_hf_config(cfg), dtype="float32")
    assert config.counts_routed_pairs and config.n_expert_layers == 2
    params = _randomised(config, seed=2)
    state = _layout(config=config).init_state()
    _, state, table = _forward(
        state, {0: (PROMPT, 0), 2: (OTHER, 0)}, params, config, T=40, align=8)
    hits, peak, here, routed = (int(v) for v in state["stats"][0])
    # the first expert layer: 23 + 6 tokens; the closing one: 2 rows
    assert routed == 2 * (23 + 6) + 2 * 2
    want = (_held_pairs(params, cfg, PROMPT, slice(-1, None))
            + _held_pairs(params, cfg, OTHER, slice(-1, None)))
    assert here == want and 0 < here < routed
    # a decode step of the two lanes: 2 rows in both expert layers
    _, state = llama.decode_step(
        params, config, jnp.asarray([5, 0, 7, 0]), jnp.asarray([23, 0, 6, 0]),
        state, table, jnp.asarray([True, False, True, False]), PAGE)
    assert int(state["stats"][0][3]) == routed + 2 * 2 * 2
    # where every expert is held the host can still not know the closing
    # layer's rows; with every expert layer in front of the last writer and
    # every expert held it can, and the program counts no pairs
    whole = llama.LlamaConfig.from_hf_config(dict(cfg, n_routed_experts=8))
    assert whole.n_experts_held == 0 and whole.counts_routed_pairs
    assert not llama.LlamaConfig.from_hf_config(
        dict(CFG, n_routed_experts=8)).counts_routed_pairs


def _int8(w):
    """Per-output-channel symmetric int8, dequantised: the nearest precision
    below the configuration's that the program has."""
    w = np.asarray(w, np.float32)
    scale = np.abs(w).max(axis=-2, keepdims=True) / 127.0 + 1e-12
    return jnp.asarray(np.round(w / scale) * scale)


def test_int8_weights_fail_the_tolerance():
    """The same comparison with every matrix at int8: by far outside TOL,
    so TOL tells a lower precision from the configuration's."""
    ref = _reference()
    quantised = jax.tree.map(
        lambda a: _int8(a) if a.ndim >= 2 and a.shape[-1] > 8 else a, PARAMS)
    want = np.asarray(ref.forward(PARAMS, CFG, PROMPT))[-1]
    low = np.asarray(ref.forward(quantised, CFG, PROMPT))[-1]
    served, _, _ = _forward(_layout().init_state(), {0: (PROMPT, 0)})
    np.testing.assert_allclose(np.asarray(served[0]), want, **TOL)
    assert np.abs(low - want).max() > 30 * TOL["atol"] + 30 * TOL["rtol"] * np.abs(want).max()


def _broken_references():
    """The reference with one published detail changed: each must leave
    the program's logits by far more than TOL."""
    def rope_on_attention(ref):
        attention = ref.attention

        def wrong(layer, u, cfg):  # a positional term where the family has none
            t = u.shape[0]
            ramp = 1.0 + 0.05 * jnp.arange(t, dtype=jnp.float32)[:, None]
            return attention(layer, u * ramp, cfg)
        ref.attention = wrong

    def norm_before_gate(ref):
        mamba2 = ref.mamba2

        def wrong(layer, u, cfg):  # silu(z) taken as 1: the gate left out
            di = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
            in_proj = jnp.asarray(layer["in_proj"]).at[:, :di].set(0.0)
            return mamba2(dict(layer, in_proj=in_proj), u, cfg)
        ref.mamba2 = wrong

    def norm_over_all_columns(ref):
        mamba2 = ref.mamba2
        ref.mamba2 = lambda layer, u, cfg: mamba2(layer, u, dict(cfg, n_groups=1))

    def gated_experts(ref):
        ref.relu2 = lambda x, up, down: jax.nn.silu(x @ ref.f32(up)) @ ref.f32(down)

    def no_conv_bias(ref):
        mamba2 = ref.mamba2
        ref.mamba2 = lambda layer, u, cfg: mamba2(
            dict(layer, conv_b=jnp.zeros_like(layer["conv_b"])), u, cfg)

    def no_skip(ref):
        mamba2 = ref.mamba2
        ref.mamba2 = lambda layer, u, cfg: mamba2(
            dict(layer, D=jnp.zeros_like(layer["D"])), u, cfg)

    def every_expert_held(ref):
        experts = ref.experts

        def wrong(layer, x, cfg):  # pairs to absent experts land on held ones
            return experts(layer, x, dict(cfg, first_expert=4))
        ref.experts = wrong

    def no_scaling(ref):
        route = ref.route
        ref.route = lambda layer, x, cfg: route(
            layer, x, dict(cfg, routed_scaling_factor=1.0))

    return {"rope_on_attention": rope_on_attention,
            "norm_before_gate": norm_before_gate,
            "gated_experts": gated_experts, "no_conv_bias": no_conv_bias,
            "no_skip": no_skip, "every_expert_held": every_expert_held,
            "no_scaling": no_scaling}


@pytest.mark.parametrize("fault", sorted(_broken_references()))
def test_a_forward_that_changes_one_published_detail_fails_the_comparison(fault):
    ref = _reference()
    _broken_references()[fault](ref)
    wrong = np.asarray(ref.forward(PARAMS, CFG, PROMPT))[-1]
    right = np.asarray(_reference().forward(PARAMS, CFG, PROMPT))[-1]
    assert np.abs(wrong - right).max() > 100 * TOL["atol"], fault


def test_a_routed_width_is_stored_in_whole_tiles():
    """models/moe.stored_width: the next multiple of 512 columns, zeros
    behind the width, and nothing that is computed changes."""
    assert [moe.stored_width(w) for w in (48, 511, 512, 520, 1536, 1856, 1920)] == [
        48, 511, 512, 1024, 1536, 2048, 2048]
    mc = moe.MoEConfig(n_experts=8, top_k=2, hidden_size=32, intermediate_size=520,
                       router="sigmoid", form="relu2", held=4, first_expert=4)
    params = moe.init_moe_params(mc, jax.random.PRNGKey(7), scale=0.1)
    assert params["w_up"].shape == (4, 32, 1024) and params["w_down"].shape == (4, 1024, 32)
    assert np.asarray(params["w_up"][:, :, :520]).any()
    assert not np.asarray(params["w_up"][:, :, 520:]).any()
    assert not np.asarray(params["w_down"][:, 520:]).any()
    x = jax.random.normal(jax.random.PRNGKey(8), (11, 32), jnp.float32)
    cut = dict(params, w_up=params["w_up"][:, :, :520], w_down=params["w_down"][:, :520])
    np.testing.assert_allclose(
        np.asarray(moe.moe_mlp(params, x, mc)), np.asarray(moe.moe_mlp(cut, x, mc)), **TOL)


def test_the_first_two_hybrid_families_trace_what_they_traced():
    """A model that holds every expert in the gated form has no share in
    its program: `routed_experts` takes the path it took."""
    glm = moe.MoEConfig(n_experts=8, router="sigmoid", shared=True)
    assert glm.holds_all and glm.form == "gated" and glm.n_held == 8
    assert sorted(moe.moe_param_shapes(glm)) == [
        "router", "router_bias", "shared_down", "shared_gate", "shared_up",
        "w_down", "w_gate", "w_up"]
    assert sorted(moe.moe_param_pspecs()) == ["router", "w_down", "w_gate", "w_up"]
    assert set(moe.moe_param_pspecs(glm)) == set(moe.moe_param_shapes(glm))
