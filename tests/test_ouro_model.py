"""A looped model (`model_type: ouro`: the layer stack run several times a
token over one set of weights, K/V rows of its own for every (pass, layer))
in models/llama.py's forwards, against the plain reference
benchmark/reference/ouro.py.  Tiny sizes, float32, seeded random weights,
on the CPU: 3 passes over 2 layers.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_tpu.engine.kvcache import KVCacheConfig, StateLayout, init_kv_pages
from kserve_tpu.models import llama
from kserve_tpu.parallel import sharding as shd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = {
    "model_type": "ouro", "vocab_size": 320, "hidden_size": 64,
    "intermediate_size": 160, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "hidden_act": "silu", "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "rope_scaling": None, "tie_word_embeddings": False,
    "total_ut_steps": 3, "early_exit_threshold": 1,
    "max_position_embeddings": 256, "torch_dtype": "float32"}
PAGE, POOL = 8, 16


def _reference():
    path = os.path.join(ROOT, "benchmark", "reference", "ouro.py")
    spec = importlib.util.spec_from_file_location("reference_ouro", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config_of(**over) -> llama.LlamaConfig:
    return dataclasses.replace(
        llama.LlamaConfig.from_hf_config({**CFG, **over}), dtype="float32")


CONFIG = config_of()
#: scale 0.1: logits of magnitude ~1, so the comparison is not vacuous
PARAMS = llama.init_params(CONFIG, jax.random.PRNGKey(1), scale=0.1)
TOKENS = np.random.RandomState(0).randint(0, 320, size=21)
i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731


def cache(config=CONFIG):
    return init_kv_pages(KVCacheConfig(
        n_layers=config.n_layers, n_kv_heads=config.n_kv_heads,
        head_dim=config.head_dim, page_size=PAGE, num_pages=POOL,
        max_pages_per_seq=4, dtype="float32", n_passes=config.n_passes))


def want(tokens, cfg=CFG, params=PARAMS):
    return np.asarray(_reference().forward(params, cfg, list(map(int, tokens))))


def close(got, ref):
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-4, atol=5e-5)


def test_config_json_maps_to_the_looped_llama_layer():
    c = CONFIG
    assert (c.n_passes, c.early_exit_threshold, c.is_looped) == (3, 1.0, True)
    assert c.sandwich_norms and not c.norm_plus_one and not c.attention_bias
    assert not c.tie_word_embeddings and not c.qk_norm and not c.is_hybrid
    assert c.rope_theta == 1000000 and c.head_dim == 16
    one = config_of(total_ut_steps=1)
    assert one.n_passes == 1 and not one.is_looped
    assert llama.LlamaConfig.tiny().n_passes == 1


@pytest.mark.parametrize("model_type", ["looped-llama", "universal"])
def test_an_unknown_family_with_several_passes_is_never_served_as_one(model_type):
    cfg = {**CFG, "model_type": model_type}
    with pytest.raises(ValueError, match="total_ut_steps"):
        llama.LlamaConfig.from_hf_config(cfg)
    # one pass is an ordinary stack: nothing to refuse on that account
    assert llama.LlamaConfig.from_hf_config(
        {**cfg, "total_ut_steps": 1}).n_passes == 1


def test_parameters_are_one_set_whatever_the_passes():
    def count(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))

    h = 64
    layer = 4 * h * h + 3 * h * 160 + 4 * h
    gate = h + 1
    assert count(PARAMS) == 2 * layer + 2 * 320 * h + h + gate
    six = llama.init_params(config_of(total_ut_steps=6),
                            jax.random.PRNGKey(1), scale=0.1)
    assert count(six) == count(PARAMS)
    one = llama.init_params(config_of(total_ut_steps=1),
                            jax.random.PRNGKey(1), scale=0.1)
    assert count(one) == count(PARAMS) - gate  # no gate without a loop
    assert PARAMS["exit_gate_w"].shape == (h, 1)
    assert PARAMS["exit_gate_b"].shape == (1,)
    # plain RMSNorm, not Gemma's zero: the norms before a branch start at
    # one, those after it at 1 / sqrt(2 L) (a looped stack's conditioning)
    assert float(PARAMS["layers"][0]["mlp_norm"][0]) == 1.0
    assert float(PARAMS["layers"][0]["post_mlp_norm"][0]) == 0.5
    assert float(one["layers"][0]["post_attn_norm"][0]) == 1.0  # no loop


def test_sharding_specs_cover_the_gate_and_weights_are_made_on_the_mesh():
    specs = shd.param_pspecs(CONFIG)
    assert jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
    ) == jax.tree.structure(PARAMS)
    mesh = shd.create_mesh(tp=1, devices=jax.devices()[:1])
    made = shd.init_params_on_mesh(CONFIG, jax.random.PRNGKey(1), mesh)
    assert sorted(made) == sorted(PARAMS)


def test_the_cache_holds_a_row_for_every_pass_and_layer():
    cc = KVCacheConfig(n_layers=2, n_kv_heads=4, head_dim=16, page_size=PAGE,
                       num_pages=POOL, dtype="float32", n_passes=3)
    assert cc.cache_rows == 6
    assert cc.bytes_per_page() == 2 * 4 * PAGE * 16 * 4  # one row
    assert cc.page_bytes() == 6 * cc.bytes_per_page()
    pages = init_kv_pages(cc)
    assert len(pages) == 2 and pages[0].shape == (3 * POOL, 2, 4, PAGE, 16)
    layout = StateLayout.of(CONFIG, PAGE, POOL, 2, "float32")
    assert layout.cache_rows == 6
    assert layout.token_bytes() == 6 * 2 * 4 * 16 * 4
    assert layout.bytes_in_use(1, 3)["shared_kv"] == 3 * PAGE * layout.token_bytes()
    one = StateLayout.of(config_of(total_ut_steps=1), PAGE, POOL, 2, "float32")
    assert one.token_bytes() * 3 == layout.token_bytes()
    assert KVCacheConfig(n_layers=2, n_kv_heads=4, head_dim=16).cache_rows == 2


def test_whole_prompt_prefill_agrees_with_the_reference():
    with jax.default_matmul_precision("highest"):
        logits, pages = llama.prefill(
            PARAMS, CONFIG, i32(np.pad(TOKENS, (0, 3)))[None], i32([21]),
            cache(), i32([[1, 2, 3, 0]]), PAGE)
    close(logits[0], want(TOKENS)[-1])
    # K and V of two passes differ, and every pass wrote its own pages
    rows = [np.asarray(pages[0][u * POOL + 1]) for u in range(3)]
    assert all(np.abs(r).max() > 0 for r in rows)
    assert np.abs(rows[0] - rows[1]).max() > 1e-3
    assert np.abs(rows[1] - rows[2]).max() > 1e-3
    assert float(np.abs(np.asarray(pages[0][4])).max()) == 0.0  # not this lane's


def test_chunked_prefill_agrees_with_the_reference():
    pages = cache()
    with jax.default_matmul_precision("highest"):
        for start, n in ((0, 8), (8, 8), (16, 5)):
            chunk = np.zeros((8,), np.int64)
            chunk[:n] = TOKENS[start:start + n]
            logits, pages = llama.prefill_chunk(
                PARAMS, CONFIG, i32(chunk)[None], i32([start]), i32([n]),
                pages, i32([[1, 2, 3, 0]]), PAGE)
    close(logits[0], want(TOKENS)[-1])


def _packed(pages, a, b, a_start=0):
    """Two lanes in one packed buffer: lane 0 holds `a` from a_start on,
    lane 1 the whole of `b`; slices start at multiples of 8."""
    la, lb = len(a), len(b)
    off = -(-la // 8) * 8
    buf = np.zeros((off + -(-lb // 8) * 8,), np.int64)
    seq = np.full(buf.shape, -1, np.int64)
    pos = np.zeros(buf.shape, np.int64)
    buf[:la], seq[:la], pos[:la] = a, 0, np.arange(a_start, a_start + la)
    buf[off:off + lb], seq[off:off + lb], pos[off:off + lb] = b, 1, np.arange(lb)
    with jax.default_matmul_precision("highest"):
        return llama.forward_ragged(
            PARAMS, CONFIG, i32(buf), i32(seq), i32(pos), i32([0, off]),
            i32([la, lb]), i32([a_start, 0]), pages,
            i32([[1, 2, 3, 0], [4, 5, 0, 0]]), PAGE,
            i32([la - 1, off + lb - 1]))


def test_a_packed_mixed_dispatch_agrees_with_the_reference():
    """One lane's second chunk (after a first chunk of 8 through the cache)
    and another lane's whole prompt in one packed buffer."""
    other = np.random.RandomState(3).randint(0, 320, size=13)
    _, pages = _packed(cache(), TOKENS[:8], other[:1])
    logits, _ = _packed(pages, TOKENS[8:], other, a_start=8)
    close(logits[0], want(TOKENS)[-1])
    close(logits[1], want(other)[-1])


def test_decode_through_the_cache_agrees_with_the_reference():
    """Prefill 13 tokens, then 8 decode steps teacher-forced on TOKENS, two
    lanes of different lengths side by side; positions cross a page."""
    other = np.random.RandomState(4).randint(0, 320, size=16)
    _, pages = _packed(cache(), TOKENS[:13], other[:5])
    table = i32([[1, 2, 3, 0], [4, 5, 0, 0]])
    step = jax.jit(lambda tok, pos, pages: llama.decode_step(
        PARAMS, CONFIG, tok, pos, pages, table, jnp.asarray([True, True]),
        PAGE, use_pallas=False))
    ref_a, ref_b = want(TOKENS), want(other)
    with jax.default_matmul_precision("highest"):
        for s in range(8):
            logits, pages = step(
                i32([TOKENS[13 + s], other[5 + s]]), i32([13 + s, 5 + s]), pages)
            close(logits[0], ref_a[13 + s])
            close(logits[1], ref_b[5 + s])
    assert float(np.abs(ref_a[20]).max()) > 0.5  # not vacuous


def test_a_forward_that_reads_another_passs_rows_fails_the_comparison():
    """The same decode step over a cache whose rows of pass 1 and pass 2
    have changed places: the logits leave the reference by far more than
    the comparison allows."""
    _, pages = _packed(cache(), TOKENS[:13], TOKENS[:1])
    swapped = []
    for layer in pages:
        p1 = layer[POOL:2 * POOL]
        swapped.append(layer.at[POOL:2 * POOL].set(layer[2 * POOL:])
                       .at[2 * POOL:].set(p1))
    table = i32([[1, 2, 3, 0], [4, 5, 0, 0]])
    args = (i32([TOKENS[13], 0]), i32([13, 1]))
    with jax.default_matmul_precision("highest"):
        good, _ = llama.decode_step(
            PARAMS, CONFIG, *args, pages, table, jnp.asarray([True, False]),
            PAGE, use_pallas=False)
        bad, _ = llama.decode_step(
            PARAMS, CONFIG, *args, swapped, table, jnp.asarray([True, False]),
            PAGE, use_pallas=False)
    ref = want(TOKENS)[13]
    close(good[0], ref)
    assert np.abs(np.asarray(bad[0]) - ref).max() > 1e-2


def test_one_pass_is_todays_sandwich_norm_stack():
    """`total_ut_steps` 1: one pass, one final norm.  The logits are those
    of the same layers configured as a sandwich-norm Llama directly, and
    the reference's with one pass."""
    cfg1 = {**CFG, "total_ut_steps": 1}
    one = config_of(total_ut_steps=1)
    direct = llama.LlamaConfig(
        vocab_size=320, hidden_size=64, intermediate_size=160, n_layers=2,
        n_heads=4, n_kv_heads=4, head_dim=16, rope_theta=1000000,
        rms_norm_eps=1e-6, max_position_embeddings=256, sandwich_norms=True,
        dtype="float32")
    assert one == direct
    params = {k: v for k, v in PARAMS.items() if not k.startswith("exit_gate")}
    with jax.default_matmul_precision("highest"):
        logits, _ = llama.prefill(
            params, one, i32(np.pad(TOKENS, (0, 3)))[None], i32([21]),
            cache(one), i32([[1, 2, 3, 0]]), PAGE)
    ref1 = want(TOKENS, cfg1)
    close(logits[0], ref1[-1])
    assert np.abs(ref1[-1] - want(TOKENS)[-1]).max() > 1e-2  # passes matter


def _lowered(config, params):
    pages = cache(config)
    return jax.jit(lambda tok, pos, pages: llama.decode_step(
        params, config, tok, pos, pages, i32([[1, 2, 3, 0]]),
        jnp.asarray([True]), PAGE, use_pallas=False)).lower(
            i32([5]), i32([3]), pages).as_text(debug_info=True)


def test_the_loop_is_in_the_program_and_a_one_pass_model_has_none():
    looped = _lowered(CONFIG, PARAMS)
    assert "loop_pass" in looped and "stablehlo.while" in looped
    # one traced stack, not three: a layer's projections appear once
    assert looped.count("stablehlo.dot_general") < 2 * (2 * 7 + 1) + 4
    qwen = dataclasses.replace(llama.LlamaConfig.tiny(
        vocab_size=320, qk_norm=True, tie_word_embeddings=True, head_dim=16,
        n_kv_heads=4), dtype="float32")
    plain = _lowered(qwen, llama.init_params(qwen, jax.random.PRNGKey(1)))
    assert "loop_pass" not in plain and "stablehlo.while" not in plain


def test_the_reference_gate_is_computed_and_changes_nothing_served():
    ref = _reference()
    gates = np.asarray(ref.exit_gates(PARAMS, CFG, TOKENS.tolist()))
    assert gates.shape == (3, 21) and (0 < gates).all() and (gates < 1).all()
    with pytest.raises(NotImplementedError, match="early_exit_threshold"):
        ref.check_supported({**CFG, "early_exit_threshold": 0.5})
    with pytest.raises(NotImplementedError, match="model_type"):
        ref.check_supported({**CFG, "model_type": "qwen3"})
