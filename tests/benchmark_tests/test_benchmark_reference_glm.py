"""benchmark/reference/glm4_moe_lite.py: what it computes against a
hand-written loop, what it refuses, the published configuration's file and
the cell's files, and the check child with `--family glm4_moe_lite`.  (The
program's forwards are held to it in tests/test_glm_model.py and
tests/test_glm_engine.py.)"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
from bench_paths import BENCH, ROOT
from test_benchmark_reference import load_reference

from kbench import manifest

TINY = {
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
    "attention_bias": False, "num_nextn_predict_layers": 1}


def _params(cfg, scale=0.1):
    import jax

    sys.path.insert(0, ROOT)
    from kserve_tpu.models import llama

    return llama.init_params(
        llama.LlamaConfig.from_hf_config(cfg), jax.random.PRNGKey(1), scale=scale)


def test_keys_and_values_per_head_the_chosen_experts_and_causality():
    """`forward` against the equations written out once more with numpy:
    one head's scores from its own K (nope from the compressed row, rope
    shared), the expert layer as a loop over tokens and their choices."""
    import jax

    ref, params = load_reference("glm4_moe_lite"), _params(TINY)
    tokens = np.random.RandomState(0).randint(0, 320, size=9).tolist()
    logits = np.asarray(ref.forward(params, TINY, tokens))
    assert logits.shape == (9, 320) and logits.dtype == np.float32
    # causal: a later token does not move an earlier position
    moved = np.asarray(ref.forward(params, TINY, tokens[:-1] + [7]))
    np.testing.assert_allclose(moved[:-1], logits[:-1], rtol=1e-5, atol=1e-6)
    # the expert layer, token by token
    layer = {k: np.asarray(v, np.float32) for k, v in params["layers"][1].items()}
    x = np.random.RandomState(1).randn(5, 64).astype(np.float32)

    def gated(v, e=None):
        pick = (lambda w: w[e]) if e is not None else (lambda w: w)
        names = ("w_gate", "w_up", "w_down") if e is not None else (
            "shared_gate", "shared_up", "shared_down")
        g, u, d = (pick(layer[n]) for n in names)
        a = v @ g
        return ((a / (1 + np.exp(-a))) * (v @ u)) @ d

    want = np.zeros_like(x)
    for t in range(5):
        s = 1 / (1 + np.exp(-(x[t] @ layer["router"])))
        idx = np.argsort(-(s + layer["router_bias"]), kind="stable")[:2]
        w = s[idx] / (s[idx].sum() + 1e-20) * 1.8
        want[t] = gated(x[t]) + sum(wj * gated(x[t], e) for wj, e in zip(w, idx))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.experts(params["layers"][1], ref.f32(x), TINY))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # one head's scores: q_nope . k_nope_i + q_rope . k_rope, over sqrt(20)
    with jax.default_matmul_precision("highest"):
        out = np.asarray(ref.attention(params["layers"][0], ref.f32(x), TINY))
    assert out.shape == (5, 64) and np.isfinite(out).all()
    # the dense layer comes first, the expert layers behind it
    assert "router" not in params["layers"][0] and "router" in params["layers"][2]


def test_reference_refuses_what_it_does_not_compute():
    ref = load_reference("glm4_moe_lite")
    ref.check_supported(TINY)
    for extra in ({"rope_scaling": {"type": "yarn", "factor": 4}},
                  {"attention_bias": True}, {"hidden_act": "gelu"},
                  {"n_group": 8, "topk_group": 4}, {"topk_method": "greedy"},
                  {"partial_rotary_factor": 0.5}, {"n_shared_experts": 2},
                  {"tie_word_embeddings": True}, {"q_lora_rank": None},
                  {"model_type": "deepseek_v3"}):
        with pytest.raises(NotImplementedError):
            ref.check_supported({**TINY, **extra})


def test_the_configurations_file_is_the_catalogs_but_for_its_depth():
    with open(os.path.join(BENCH, "configs", "glm47-flash.json")) as f:
        cfg = json.load(f)
    hf = {k: v for k, v in cfg.items() if k not in manifest.OWN_KEYS}
    load_reference("glm4_moe_lite").check_supported(hf)
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_key_value_heads": 20,
        "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768, "kv_lora_rank": 512,
        "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
        "vocab_size": 154880}
    assert {k: hf[k] for k in published} == published
    assert set(hf) == set(published) | {"num_hidden_layers", "head_dim"}
    assert hf["num_hidden_layers"] == 8 and hf["head_dim"] == 64
    assert cfg["reduced"] == ["num_hidden_layers"]
    dep = cfg["deployment"]
    assert dep["published"] == {"num_hidden_layers": 47}
    assert dep["family"] == "glm4_moe_lite" and dep["chips"] == 1
    flags = dep["server_flags"]
    assert flags["max_batch_size"] == 48 and flags["tp"] == 1
    # the pool: ~3 GB of latent rows as stored (640 columns, 8 layers, bf16)
    pool = flags["kv_pages"] * flags["page_size"] * 8 * 640 * 2
    assert 2.8e9 < pool < 3.2e9
    assert any("next-token-prediction" in a and "NOT" in a for a in cfg["assumed"])
    tiny = cfg["rehearsal"]["hf_overrides"]
    assert tiny["num_hidden_layers"] == 3 and tiny["n_routed_experts"] == 8
    assert len({tiny[k] for k in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                                  "qk_rope_head_dim", "v_head_dim")}) == 5
    cell = manifest.resolve_cell("glm47-flash.agent-sat")
    assert cell.chips == 1 and cell.pair["clients"] == 48
    assert cell.pair["server_flags"] == {
        "max_model_len": 3200, "max_prefill_len": 2048}
    mix = cell.traffic
    assert mix["loop"] == "closed" and mix["sampling"] == {"temperature": 0.0}
    assert (mix["prompt_len"]["min"], mix["prompt_len"]["max"]) == (1024, 2560)
    assert (mix["output_len"]["min"], mix["output_len"]["max"]) == (384, 640)
    assert mix["per_client"] == 8 and mix["stratum"] == 16
    # the worst case fits the pool: no request waits for a page
    assert 48 * -(-3200 // flags["page_size"]) <= flags["kv_pages"] - 1
    assert {m["name"] for m in cell.end_to_end} == {
        "tpot_p50_ms", "itl_p99_ms", "output_tok_s", "setup_s"}
    assert {"attention.latent_share", "attention.latent_decode_roofline",
            "moe.experts_share", "moe.experts_roofline", "moe.rows_per_expert",
            "dispatch.padded_share", "dispatch.deliver_overlap_share",
            "cache.pool_fill_share"} <= {m["name"] for m in cell.per_layer}


def test_check_child_reports_gaps_and_catches_a_wrong_token(tmp_path):
    """benchmark/reference/check.py, unedited, with `--family
    glm4_moe_lite`: it makes the weights through LlamaConfig.from_hf_config
    and sharding.init_params_on_mesh, as for any family."""
    cfg = dict(TINY, torch_dtype="bfloat16")
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    prompt = np.random.RandomState(1).randint(0, 320, size=12).tolist()

    def run(probes):
        (tmp_path / "probes.json").write_text(json.dumps(probes))
        proc = subprocess.run(
            [sys.executable, os.path.join(BENCH, "reference", "check.py"),
             "--config", str(tmp_path / "config.json"),
             "--family", "glm4_moe_lite",
             "--probes", str(tmp_path / "probes.json"),
             "--out", str(tmp_path / "out.json")],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
            text=True, timeout=300, cwd=ROOT)
        assert proc.returncode == 0, proc.stderr[-2000:]
        with open(tmp_path / "out.json") as f:
            return json.load(f)

    ref = load_reference("glm4_moe_lite")
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
