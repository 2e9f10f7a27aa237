"""models/hybrid.py (the per-layer table's forward) against the plain
reference of its first family, `benchmark/reference/phi4flash.py`, at a
tiny size on the CPU with seeded random weights.

Logits are compared, in float32 under `highest` matmul precision, so every
comparison is about the mathematics: the two implementations share no code
and agree to ~5e-6 on logits of magnitude ~2.  The tolerance is rtol 2e-4 /
atol 5e-5 (float32 rounding through eight layers and a scan re-associated
in blocks); computing in bfloat16 where float32 is stated misses it by two
orders (`test_bfloat16_where_float32_is_stated_fails_the_tolerance`).
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_tpu.engine.kvcache import StateLayout
from kserve_tpu.models import hybrid, llama

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = {
    "model_type": "phi4flash", "vocab_size": 320, "hidden_size": 64,
    "intermediate_size": 160, "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "layer_norm_eps": 1e-5,
    "sliding_window": 8, "mb_per_layer": 2, "tie_word_embeddings": True,
    "mamba_d_state": 4}
LANES, PAGE, WIDTH = 3, 4, 16
RTOL, ATOL = 2e-4, 5e-5


def _reference():
    path = os.path.join(ROOT, "benchmark", "reference", "phi4flash.py")
    spec = importlib.util.spec_from_file_location("reference_phi4flash", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def model():
    config = dataclasses.replace(
        llama.LlamaConfig.from_hf_config(CFG), dtype="float32")
    params = llama.init_params(config, jax.random.PRNGKey(1), scale=0.1)
    layout = StateLayout.of(config, PAGE, 1 + LANES * WIDTH, LANES, "float32")
    return config, params, layout


@pytest.fixture(scope="module")
def sequences(model):
    """Three token sequences and the reference's logits at every position."""
    _, params, _ = model
    ref = _reference()
    rs = np.random.RandomState(0)
    out = []
    for n in (37, 23, 30):
        tokens = rs.randint(0, CFG["vocab_size"], size=n)
        out.append((tokens, np.asarray(ref.forward(params, CFG, tokens.tolist()))))
    assert float(np.abs(out[0][1]).max()) > 0.5  # not vacuous
    return out


PAGE_TABLE = np.stack(
    [1 + lane * WIDTH + np.arange(WIDTH) for lane in range(LANES)]).astype(np.int32)


_JITTED = {}


def _jitted(config, name, **static):
    """One compiled program per (config, entry point, static arguments)."""
    key = (id(config), name, tuple(sorted(static.items())))
    if key not in _JITTED:
        fn = getattr(llama, name)
        _JITTED[key] = jax.jit(
            lambda params, *args: fn(params, config, *args, **static))
    return _JITTED[key]


def packed_forward(model, state, slices, block=1, bucket=48):
    """One call of the mixed program's forward: `slices` is a list of
    (lane, tokens, start), packed into a buffer of `bucket` tokens.
    Returns (logits [LANES, V], new state)."""
    config, params, _ = model
    q_start = np.zeros(LANES, np.int32)
    q_len = np.zeros(LANES, np.int32)
    kv_start = np.zeros(LANES, np.int32)
    last = np.zeros(LANES, np.int32)
    tok, seq, pos = [], [], []
    for lane, tokens, start in slices:
        n = len(tokens)
        pad = -n % block
        q_start[lane], q_len[lane], kv_start[lane] = len(tok), n, start
        last[lane] = len(tok) + n - 1
        tok += list(tokens) + [0] * pad
        seq += [lane] * n + [-1] * pad
        pos += list(range(start, start + n)) + [0] * pad
    fill = bucket - len(tok)
    assert fill >= 0
    tok, seq, pos = tok + [0] * fill, seq + [-1] * fill, pos + [0] * fill
    with jax.default_matmul_precision("highest"):
        return _jitted(config, "forward_ragged", ragged_block=block)(
            params, jnp.asarray(tok, jnp.int32),
            jnp.asarray(seq, jnp.int32), jnp.asarray(pos, jnp.int32),
            jnp.asarray(q_start), jnp.asarray(q_len), jnp.asarray(kv_start),
            state, jnp.asarray(PAGE_TABLE), PAGE, jnp.asarray(last))


def decode(model, state, lane_tokens):
    """One decode step: `lane_tokens` is {lane: (token, position)}."""
    config, params, _ = model
    tok = np.zeros(LANES, np.int32)
    pos = np.zeros(LANES, np.int32)
    active = np.zeros(LANES, bool)
    for lane, (t, p) in lane_tokens.items():
        tok[lane], pos[lane], active[lane] = t, p, True
    with jax.default_matmul_precision("highest"):
        return _jitted(config, "decode_step")(
            params, jnp.asarray(tok), jnp.asarray(pos), state,
            jnp.asarray(PAGE_TABLE), jnp.asarray(active), PAGE)


def close(got, want):
    np.testing.assert_allclose(np.asarray(got), want, rtol=RTOL, atol=ATOL)


def test_the_table_says_what_every_layer_writes_and_reads(model):
    config, _, layout = model
    rows = [(r.kind, r.writes, r.reads) for r in config.layer_table()]
    assert rows == [
        ("mamba", "recurrent", 0), ("window_attention", "window_kv", 1),
        ("mamba", "recurrent", 2), ("window_attention", "window_kv", 3),
        ("mamba", "recurrent", 4), ("attention", "paged_kv", 5),
        ("gmu", "none", 4), ("cross_attention", "none", 5)]
    assert (layout.paged_layers, layout.window_layers,
            layout.recurrent_layers) == ((5,), (1, 3), (0, 2, 4))
    # a Llama-family model is the table with n equal rows
    rows = llama.LlamaConfig.tiny(n_layers=3).layer_table()
    assert [(r.kind, r.writes, r.reads) for r in rows] == [
        ("attention", "paged_kv", i) for i in range(3)]


def test_forward_of_one_sequence_matches_the_reference(model, sequences):
    tokens, want = sequences[0]
    _, _, layout = model
    got, _ = packed_forward(model, layout.init_state(), [(1, tokens, 0)])
    close(got[1], want[-1])


def test_chunked_prefill_then_decode_past_the_window(model, sequences):
    """Chunks of 7, 9 and 4 tokens (edges inside the window of 8, inside a
    page of 4), then one token at a time to position 36: four windows deep.
    The logits after every chunk and at every decoded position are the
    reference's full forward's."""
    tokens, want = sequences[0]
    _, _, layout = model
    state = layout.init_state()
    start = 0
    for n in (7, 9, 4):
        got, state = packed_forward(
            model, state, [(2, tokens[start:start + n], start)])
        start += n
        close(got[2], want[start - 1])
    for p in range(start, len(tokens)):
        got, state = decode(model, state, {2: (tokens[p], p)})
        close(got[2], want[p])


@pytest.mark.parametrize("block", (1, 8), ids=("packed-dense", "packed-by-8"))
def test_packed_batch_of_unequal_slices_with_decode_slices_among_them(
        model, sequences, block):
    """Three lanes at once, as the mixed program packs them: lane 0 decodes
    (one-token slices), lane 1 prefills in chunks, lane 2 joins late with a
    chunk longer than the window; slices start at `block` multiples with
    padding between, and the buffer is padded to its bucket."""
    _, _, layout = model
    (t0, w0), (t1, w1), (t2, w2) = sequences
    state = layout.init_state()
    _, state = packed_forward(model, state, [(0, t0[:20], 0)], block)
    got, state = packed_forward(
        model, state, [(0, t0[20:21], 20), (1, t1[:10], 0)], block)
    close(got[0], w0[20])
    close(got[1], w1[9])
    got, state = packed_forward(
        model, state,
        [(0, t0[21:22], 21), (1, t1[10:23], 10), (2, t2[:17], 0)], block)
    close(got[0], w0[21])
    close(got[1], w1[22])
    close(got[2], w2[16])
    got, state = decode(
        model, state, {0: (t0[22], 22), 2: (t2[17], 17)})
    close(got[0], w0[22])
    close(got[2], w2[17])


def test_a_lane_used_again_gives_the_logits_it_gives_alone(model, sequences):
    """State is reset where a lane's slice starts at position 0: a second
    request on a lane that another has just left (ring full, recurrent
    state far from zero) reads the same logits as on a fresh engine."""
    (t0, _), (t1, w1), _ = sequences
    _, _, layout = model
    _, used = packed_forward(model, layout.init_state(), [(1, t0, 0)])
    _, used = decode(model, used, {1: (5, len(t0))})
    got, used = packed_forward(model, used, [(1, t1[:12], 0)])
    close(got[1], w1[11])
    got, _ = packed_forward(model, used, [(1, t1[12:], 12)])
    close(got[1], w1[-1])


def test_a_lane_that_is_not_live_keeps_its_state(model, sequences):
    tokens, want = sequences[0]
    _, _, layout = model
    _, state = packed_forward(model, layout.init_state(), [(0, tokens[:20], 0)])
    # another lane steps; lane 0 sits the step out (as a lane past its
    # capacity or mid-prompt does in the device loop)
    _, state = decode(model, state, {1: (7, 0)})
    got, _ = decode(model, state, {0: (tokens[20], 20)})
    close(got[0], want[20])


def test_bfloat16_where_float32_is_stated_fails_the_tolerance(model, sequences):
    """The comparison is tight enough to tell the precisions apart."""
    config, params, layout = model
    tokens, want = sequences[0]
    low = dataclasses.replace(config, dtype="bfloat16")
    cast = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32
        and a.ndim != 0 else a, params)
    state = StateLayout.of(low, PAGE, 1 + LANES * WIDTH, LANES).init_state()
    got, _ = packed_forward((low, cast, None), state, [(1, tokens, 0)])
    err = np.abs(np.asarray(got[1], np.float32) - want[-1])
    assert float((err - (ATOL + RTOL * np.abs(want[-1]))).max()) > 100 * ATOL


def test_state_bytes_at_the_published_sizes():
    """Shapes only, nothing allocated: what the arithmetic of the
    architecture says a token and a lane cost."""
    with open(os.path.join(ROOT, "benchmark", "configs", "phi4-mini-flash.json")) as f:
        import json

        published = {k: v for k, v in json.load(f).items()
                     if k not in ("deployment", "assumed", "source", "reduced",
                                  "rehearsal")}
    config = llama.LlamaConfig.from_hf_config(published)
    layout = StateLayout.of(config, 16, 50000, 48)
    assert layout.token_bytes() == 5120  # one layer of 20 K/V heads of 64, bf16
    assert layout.lane_bytes() == {
        "window_kv": 8 * 512 * 5120,  # 21.0 MB
        "ssm": 9 * 5120 * 16 * 4,  # 2.95 MB
        "conv": 9 * 3 * 5120 * 2}  # 0.28 MB
    assert sum(layout.lane_bytes().values()) == 24_197_120
    assert (len(layout.paged_layers), len(layout.window_layers),
            len(layout.recurrent_layers)) == (1, 8, 9)
    assert (layout.ring_page_size, layout.ring_width) == (16, 32)
    shapes = jax.eval_shape(layout.init_state)
    assert shapes["paged"][0].shape == (50000, 2, 10, 16, 128)
    assert shapes["window"][0].shape == (1 + 48 * 32, 2, 10, 16, 128)
    assert shapes["ssm"][0].shape == (48, 5120, 16)
    assert shapes["ssm"][0].dtype == jnp.float32
    assert shapes["conv"][0].shape == (48, 3, 5120)
    # under one shape for every layer the same lane would hold 32 x 5120 B a token
    assert 32 * 5120 * 1024 == 167_772_160
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(
        jax.eval_shape(lambda k: llama.init_params(config, k), jax.random.PRNGKey(0))))
    assert 3852.0 <= n / 1e6 < 3853.0  # 7.70 GB in bf16
    # qwen3-4b: the table with 36 equal rows, 147 KB a token
    qwen = llama.LlamaConfig(
        vocab_size=151936, hidden_size=2560, intermediate_size=9728,
        n_layers=36, n_heads=32, n_kv_heads=8, head_dim=128)
    assert StateLayout.of(qwen, 16, 2300, 48).token_bytes() == 36 * 4096
    assert StateLayout.of(qwen, 16, 2300, 48).lane_bytes() == {
        "window_kv": 0, "ssm": 0, "conv": 0}


def test_config_json_is_read_by_model_type_and_an_unknown_one_is_refused():
    config = llama.LlamaConfig.from_hf_config(CFG)
    assert config.is_hybrid and not config.use_rope and config.diff_attention
    assert (config.mamba_d_inner, config.mamba_d_conv, config.mamba_dt_rank) == (
        128, 4, 4)
    assert (config.cache_kv_heads, config.cache_head_dim) == (1, 32)
    with pytest.raises(ValueError, match="jamba.*attn_layer_indices"):
        llama.LlamaConfig.from_hf_config(
            {**CFG, "model_type": "jamba", "attn_layer_indices": [1]})
    with pytest.raises(ValueError, match="multiple of 4"):
        llama.LlamaConfig.from_hf_config({**CFG, "num_hidden_layers": 6})
    with pytest.raises(ValueError, match="diff_attention_pairing"):
        llama.LlamaConfig.from_hf_config(
            {**CFG, "diff_attention_pairing": "halves"})
    # a Llama-shaped config with no model_type is still a Llama
    plain = {k: CFG[k] for k in (
        "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads")}
    assert not llama.LlamaConfig.from_hf_config(plain).is_hybrid


def test_checkpoint_round_trip(tmp_path, model):
    """A tiny synthetic checkpoint under the family's tensor names, written
    from seeded parameters and loaded back by the streamed loader."""
    from safetensors.numpy import save_file

    config, params, _ = model
    tensors = {"model.embed_tokens.weight": np.asarray(params["embed"]),
               "model.final_layernorm.weight": np.asarray(params["final_norm"]),
               "model.final_layernorm.bias": np.asarray(params["final_norm_b"])}
    for i, (layer, spec) in enumerate(zip(params["layers"], config.layer_table())):
        host = {k: np.asarray(v) for k, v in layer.items()}
        for name, arr in hybrid.hf_layer_tensors(config, spec, host).items():
            tensors[f"model.layers.{i}.{name}"] = np.ascontiguousarray(arr)
    assert "model.layers.0.attn.conv1d.weight" in tensors
    assert tensors["model.layers.1.attn.Wqkv.weight"].shape == (64 + 32 + 32, 64)
    assert tensors["model.layers.7.attn.Wqkv.weight"].shape == (64, 64)  # cross
    save_file(tensors, str(tmp_path / "model.safetensors"))
    stats = {}
    loaded = llama.load_hf_weights_streamed(str(tmp_path), config, stats=stats)
    assert stats["n_tensors"] == len(tensors)
    flat_a, tree_a = jax.tree.flatten(params)
    flat_b, tree_b = jax.tree.flatten(loaded)
    assert tree_a == tree_b
    for a, b in zip(flat_a, flat_b):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    del tensors["model.layers.2.attn.A_log"]
    save_file(tensors, str(tmp_path / "model.safetensors"))
    with pytest.raises(ValueError, match="lacks.*A_log.*layer 2"):
        llama.load_hf_weights_streamed(str(tmp_path), config)
