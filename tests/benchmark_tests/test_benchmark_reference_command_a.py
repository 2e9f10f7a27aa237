"""benchmark/reference/cohere2_moe.py: what it computes against a
hand-written loop, what it refuses, the published configuration's file and
the cell's files, and the check child with `--family cohere2_moe`.  (The
program's forwards are held to it in tests/test_command_a_model.py and
tests/test_command_a_engine.py.)"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
from bench_paths import BENCH, ROOT
from test_benchmark_reference import load_reference

from kbench import cohere_math, manifest

TINY = {
    "model_type": "cohere2_moe", "vocab_size": 320, "hidden_size": 64,
    "intermediate_size": 48, "num_hidden_layers": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": 5, "num_experts": 4, "router_n_experts": 8,
    "first_expert": 0, "num_experts_per_tok": 4, "num_shared_experts": 2,
    "shared_expert_combination_strategy": "average",
    "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
    "layer_norm_eps": 1e-5, "rope_theta": 50000,
    "position_embedding_type": "rope_gptj", "rotary_pct": 1,
    "use_parallel_block": True, "tie_word_embeddings": True, "logit_scale": 1}


def _params(cfg, scale=0.1):
    import jax

    sys.path.insert(0, ROOT)
    from kserve_tpu.models import llama

    return llama.init_params(
        llama.LlamaConfig.from_hf_config(cfg), jax.random.PRNGKey(1), scale=scale)


def test_the_block_the_window_the_rotary_and_the_held_experts():
    """`forward` against the equations written out once more with numpy,
    token by token: the bias-free LayerNorm, attention with the interleaved
    rotary and the window on a sliding layer and neither on a full one, the
    experts as a loop over tokens and their choices with the absent experts
    adding nothing, the shared experts averaged, ONE residual."""
    import jax

    ref, params = load_reference("cohere2_moe"), _params(TINY)
    tokens = np.random.RandomState(0).randint(0, 320, size=9).tolist()
    logits = np.asarray(ref.forward(params, TINY, tokens))
    assert logits.shape == (9, 320) and logits.dtype == np.float32
    moved = np.asarray(ref.forward(params, TINY, tokens[:-1] + [7]))
    np.testing.assert_allclose(moved[:-1], logits[:-1], rtol=1e-5, atol=1e-6)
    layer = {k: np.asarray(v, np.float32) for k, v in params["layers"][0].items()}
    h = np.random.RandomState(1).randn(9, 64).astype(np.float32)
    u = (h - h.mean(-1, keepdims=True)) / np.sqrt(h.var(-1, keepdims=True) + 1e-5)
    u = u * layer["attn_norm"]

    def gated(v, gate, up, down):
        a = v @ gate
        return (a / (1 + np.exp(-a)) * (v @ up)) @ down

    def turned(x, pos):  # [heads, 16] at one position
        out = np.empty_like(x)
        for j in range(8):
            angle = pos * 50000.0 ** (-2 * j / 16)
            a, b = x[:, 2 * j], x[:, 2 * j + 1]
            out[:, 2 * j] = a * np.cos(angle) - b * np.sin(angle)
            out[:, 2 * j + 1] = a * np.sin(angle) + b * np.cos(angle)
        return out

    def attention(sliding):
        q = (u @ layer["wq"]).reshape(9, 8, 16)
        k = (u @ layer["wk"]).reshape(9, 2, 16)
        v = (u @ layer["wv"]).reshape(9, 2, 16)
        if sliding:
            q = np.stack([turned(q[t], t) for t in range(9)])
            k = np.stack([turned(k[t], t) for t in range(9)])
        out = np.zeros((9, 8, 16), np.float32)
        for t in range(9):
            first = max(0, t - 4) if sliding else 0  # a window of 5
            for head in range(8):
                kv = head // 4
                s = k[first:t + 1, kv] @ q[t, head] / 4.0
                p = np.exp(s - s.max())
                out[t, head] = (p / p.sum()) @ v[first:t + 1, kv]
        return out.reshape(9, 128) @ layer["wo"]

    routed, absent = np.zeros_like(h), 0
    for t in range(9):
        s = 1 / (1 + np.exp(-(u[t] @ layer["router"])))
        idx = np.argsort(-s, kind="stable")[:4]
        w = s[idx] / s[idx].sum()
        for wj, e in zip(w, idx):
            if e < 4:
                routed[t] += wj * gated(u[t], layer["w_gate"][e],
                                        layer["w_up"][e], layer["w_down"][e])
            else:
                absent += 1
    assert 0 < absent < 36  # the comparison exercises both branches
    shared = sum(
        gated(u, layer["shared_gate"][:, c], layer["shared_up"][:, c],
              layer["shared_down"][c]) for c in (slice(0, 48), slice(48, 96))) / 2
    for kind in ("sliding_attention", "full_attention"):
        want = h + attention(kind == "sliding_attention") + routed + shared
        with jax.default_matmul_precision("highest"):
            got = np.asarray(ref.layer_forward(
                params["layers"][0], ref.f32(h), TINY, kind))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # the two kinds differ: the window and the positions are real
    with jax.default_matmul_precision("highest"):
        a, b = (np.asarray(ref.attention(params["layers"][0], ref.f32(u), TINY, kind))
                for kind in ("sliding_attention", "full_attention"))
    assert np.abs(a - b).max() > 1e-3
    # attention a block of queries at a time is attention
    ref.QUERY_BLOCK = 4
    with jax.default_matmul_precision("highest"):
        blocked = np.asarray(ref.attention(
            params["layers"][0], ref.f32(u), TINY, "sliding_attention"))
    np.testing.assert_allclose(blocked, a, rtol=1e-5, atol=1e-6)


def test_reference_refuses_what_it_does_not_compute():
    ref = load_reference("cohere2_moe")
    ref.check_supported(TINY)
    for extra in ({"layer_types": ["sliding_attention"] * 3},
                  {"layer_types": ["chunked_attention"] * 4},
                  {"use_parallel_block": False}, {"first_k_dense_replace": 1},
                  {"position_embedding_type": "rope"}, {"rotary_pct": 0.5},
                  {"rope_parameters": {"rope_type": "yarn"}},
                  {"expert_selection_fn": "softmax"},
                  {"shared_expert_combination_strategy": "sum"},
                  {"attention_bias": True}, {"use_qk_norm": True},
                  {"tie_word_embeddings": False}, {"norm_topk_prob": False},
                  {"hidden_act": "gelu"}, {"model_type": "cohere2"}):
        with pytest.raises(NotImplementedError):
            ref.check_supported({**TINY, **extra})


def test_the_configurations_file_is_the_catalogs_but_for_depth_share_and_vocabulary():
    with open(os.path.join(BENCH, "configs", "command-a-plus.json")) as f:
        cfg = json.load(f)
    hf = {k: v for k, v in cfg.items() if k not in manifest.OWN_KEYS}
    load_reference("cohere2_moe").check_supported(hf)
    published = {
        "attention_bias": False, "expert_selection_fn": "sigmoid",
        "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 4096,
        "layer_norm_eps": 1e-05, "layer_switch": 4, "logit_scale": 1,
        "max_position_embeddings": 200000, "model_type": "cohere2_moe",
        "norm_topk_prob": True, "num_attention_heads": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 8,
        "num_shared_experts": 4,
        "order_of_interleaved_layers": "local_attn_first",
        "position_embedding_type": "rope_gptj",
        "prefix_dense_intermediate_size": 16384,
        "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
        "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
        "rope_theta": 50000, "rotary_pct": 1,
        "shared_expert_combination_strategy": "average",
        "sliding_window": 4096, "tf_legacy_loss": False,
        "tie_word_embeddings": True, "use_embedding_sharing": True,
        "use_gated_activation": True, "use_parallel_block": True,
        "use_parallel_embedding": False, "use_qk_norm": False}
    assert {k: hf[k] for k in published} == published
    assert set(hf) == set(published) | {
        "num_hidden_layers", "layer_types", "num_experts", "vocab_size",
        "router_n_experts", "first_expert"}
    # one whole period, the published pattern's first four entries
    assert hf["num_hidden_layers"] == 4 == len(hf["layer_types"])
    assert hf["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]
    assert (hf["num_experts"], hf["router_n_experts"], hf["first_expert"]) == (16, 128, 0)
    assert hf["vocab_size"] == 32768
    assert cfg["reduced"] == [
        "num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
    dep = cfg["deployment"]
    assert dep["published"] == {
        "num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144}
    assert dep["family"] == "cohere2_moe" and dep["chips"] == 1
    assert "8 chips" in dep["stands_for"] and "8 x their share" in dep["stands_for"]
    flags = dep["server_flags"]
    assert (flags["max_batch_size"], flags["page_size"], flags["kv_pages"],
            flags["tp"]) == (32, 64, 4352, 1)
    # the pool of the ONE full layer: 4096 B a token; the rings beside it
    token = cohere_math.kv_token_bytes(hf)
    assert token == 2 * 8 * 128 * 2 == 4096
    assert 1.1e9 < flags["kv_pages"] * flags["page_size"] * token < 1.2e9
    assert cohere_math.window_layers(hf) == 3
    assert cohere_math.ring_bytes_per_lane(hf) == 3 * 4096 * 4096
    assert 1.6e9 < 32 * cohere_math.ring_bytes_per_lane(hf) < 1.62e9
    assert cohere_math.held_expert_bytes(hf) == 3 * 4096 * 4096 * 2
    assert cohere_math.held_pair_flops(hf) == 6 * 4096 * 4096
    assert any("router_n_experts 128" in a for a in cfg["assumed"])
    assert any("NO positional encoding" in a for a in cfg["assumed"])
    assert any("MEAN over the four" in a for a in cfg["assumed"])
    assert any("NO bias" in a for a in cfg["assumed"])
    tiny = cfg["rehearsal"]["hf_overrides"]
    assert (tiny["num_experts"], tiny["router_n_experts"],
            tiny["num_shared_experts"], tiny["sliding_window"]) == (4, 8, 2, 32)
    cell = manifest.resolve_cell("command-a-plus.mixed-len-sat")
    assert cell.chips == 1 and cell.pair["clients"] == 32
    assert cell.pair["server_flags"] == {
        "max_model_len": 8192, "max_prefill_len": 4096}
    mix = cell.traffic
    assert mix["loop"] == "closed" and mix["sampling"] == {"temperature": 0.0}
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 3072,
                                 "sigma": 0.8, "min": 256, "max": 7168}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert (mix["stratum"], mix["per_client"]) == (16, 8)
    assert mix["limits"] == {"ttft_ms": 20000.0, "tpot_ms": 100.0}
    # the worst case fits the pool: no request waits for a page
    assert 32 * -(-8192 // flags["page_size"]) <= flags["kv_pages"] - 1
    assert {m["name"] for m in cell.end_to_end} == {
        "tpot_p50_ms", "itl_p99_ms", "output_tok_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"attention.window_ragged_share", "attention.window_ragged_roofline",
            "attention.window_bound_share", "moe.held_gated_roofline",
            "attention.window_share", "moe.held_pair_share",
            "moe.rows_per_expert", "cache.state_hbm_share",
            "cache.pool_fill_share", "dispatch.padded_share",
            "dispatch.deliver_overlap_share",
            "attention.decode_null_fetch_share"} <= names
    # readers that look for another family's keys stay off this cell
    assert not {"moe.experts_roofline", "moe.experts_share",
                "moe.held_experts_roofline", "ssd.share"} & names


def test_check_child_reports_gaps_and_catches_a_wrong_token(tmp_path):
    """benchmark/reference/check.py, unedited, with `--family cohere2_moe`."""
    cfg = dict(TINY, torch_dtype="bfloat16")
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    prompt = np.random.RandomState(1).randint(0, 320, size=12).tolist()

    def run(probes):
        (tmp_path / "probes.json").write_text(json.dumps(probes))
        proc = subprocess.run(
            [sys.executable, os.path.join(BENCH, "reference", "check.py"),
             "--config", str(tmp_path / "config.json"),
             "--family", "cohere2_moe",
             "--probes", str(tmp_path / "probes.json"),
             "--out", str(tmp_path / "out.json")],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
            text=True, timeout=300, cwd=ROOT)
        assert proc.returncode == 0, proc.stderr[-2000:]
        with open(tmp_path / "out.json") as f:
            return json.load(f)

    ref = load_reference("cohere2_moe")
    params = _params(cfg, scale=0.02)
    served = []
    for _ in range(4):
        logits = ref.forward(params, cfg, prompt + served)
        served.append(int(np.asarray(logits[-1]).argmax()))
    good = run([{"prompt": prompt, "served": served}])
    assert good["max_gap"] == 0.0 and good["argmax_match_share"] == 1.0
    wrong = list(served)
    wrong[2] = (wrong[2] + 1) % 320
    bad = run([{"prompt": prompt, "served": wrong}])
    assert bad["gaps"][0][2] > 0.0 and bad["argmax_match_share"] <= 0.75
    assert bad["gaps"][0][:2] == [0.0, 0.0]
