"""benchmark/reference/nemotron_h.py: what it computes against a
hand-written loop, what it refuses, the published configuration's file and
the cell's files, and the check child with `--family nemotron_h`.  (The
program's forwards are held to it in tests/test_nemotron_model.py and
tests/test_nemotron_engine.py.)"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
from bench_paths import BENCH, ROOT
from test_benchmark_reference import load_reference

from kbench import manifest, nemotron_math

TINY = {
    "model_type": "nemotron_h", "vocab_size": 320, "hidden_size": 64,
    "intermediate_size": 48, "num_hidden_layers": 6,
    "hybrid_override_pattern": "ME*MEM", "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "mamba_num_heads": 8,
    "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "n_routed_experts": 4, "router_n_experts": 8,
    "first_expert": 0, "num_experts_per_tok": 2, "moe_intermediate_size": 48,
    "moe_shared_expert_intermediate_size": 96, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "layer_norm_epsilon": 1e-5, "tie_word_embeddings": False,
    "mlp_hidden_act": "relu2", "use_conv_bias": True}


def _params(cfg, scale=0.1):
    import jax

    sys.path.insert(0, ROOT)
    from kserve_tpu.models import llama

    return llama.init_params(
        llama.LlamaConfig.from_hf_config(cfg), jax.random.PRNGKey(1), scale=scale)


def test_the_recurrence_the_held_experts_and_causality():
    """`forward` against the equations written out once more with numpy:
    the Mamba-2 mixer token by token (convolution taps, a state a head, the
    gate before the group-wise norm), the expert layer as a loop over tokens
    and their choices with the absent experts adding nothing."""
    import jax

    ref, params = load_reference("nemotron_h"), _params(TINY)
    tokens = np.random.RandomState(0).randint(0, 320, size=9).tolist()
    logits = np.asarray(ref.forward(params, TINY, tokens))
    assert logits.shape == (9, 320) and logits.dtype == np.float32
    moved = np.asarray(ref.forward(params, TINY, tokens[:-1] + [7]))
    np.testing.assert_allclose(moved[:-1], logits[:-1], rtol=1e-5, atol=1e-6)
    # the expert layer, token by token: 4 of 8 experts are held
    layer = {k: np.asarray(v, np.float32) for k, v in params["layers"][1].items()}
    x = np.random.RandomState(1).randn(7, 64).astype(np.float32)

    def relu2(v, up, down):
        return np.square(np.maximum(v @ up, 0.0)) @ down

    want, absent = np.zeros_like(x), 0
    for t in range(7):
        s = 1 / (1 + np.exp(-(x[t] @ layer["router"])))
        idx = np.argsort(-(s + layer["router_bias"]), kind="stable")[:2]
        w = s[idx] / (s[idx].sum() + 1e-20) * 2.5
        want[t] = relu2(x[t], layer["shared_up"], layer["shared_down"])
        for wj, e in zip(w, idx):
            if e < 4:
                want[t] += wj * relu2(x[t], layer["w_up"][e], layer["w_down"][e])
            else:
                absent += 1
    assert 0 < absent < 14  # the comparison exercises both branches
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.experts(params["layers"][1], ref.f32(x), TINY))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # the Mamba-2 mixer, token by token
    m = {k: np.asarray(v, np.float32) for k, v in params["layers"][0].items()}
    H, P, G, N, K = 8, 8, 2, 16, 4
    di, conv = H * P, H * P + 2 * G * N
    zxbcdt = x @ m["in_proj"]
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:di + conv], zxbcdt[:, di + conv:]
    state = np.zeros((H, P, N), np.float32)
    out = np.zeros((7, di), np.float32)
    for t in range(7):
        taps = sum(m["conv_w"][K - 1 - k] * xbc[t - k]
                   for k in range(K) if t - k >= 0) + m["conv_b"]
        act = taps / (1 + np.exp(-taps))
        xt = act[:di].reshape(H, P)
        b = act[di:di + G * N].reshape(G, N)
        c = act[di + G * N:].reshape(G, N)
        step = np.log1p(np.exp(dt[t] + m["dt_bias"]))
        a = -np.exp(m["A_log"])
        for h in range(H):
            g = h // (H // G)
            state[h] = np.exp(step[h] * a[h]) * state[h] + step[h] * np.outer(xt[h], b[g])
            out[t, h * P:(h + 1) * P] = state[h] @ c[g] + m["D"][h] * xt[h]
    gated = (out * (z / (1 + np.exp(-z)))).reshape(7, G, di // G)
    normed = (gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
              ).reshape(7, di) * m["ssm_norm"]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.mamba2(params["layers"][0], ref.f32(x), TINY))
    np.testing.assert_allclose(got, normed @ m["out_proj"], rtol=2e-4, atol=2e-5)
    # attention has no positional term: with ONE token nothing else could
    with jax.default_matmul_precision("highest"):
        one = np.asarray(ref.attention(params["layers"][2], ref.f32(x[:1]), TINY))
    a = {k: np.asarray(v, np.float32) for k, v in params["layers"][2].items()}
    v = np.repeat((x[:1] @ a["wv"]).reshape(1, 2, 16), 2, axis=1).reshape(1, 64)
    np.testing.assert_allclose(one, v @ a["wo"], rtol=1e-4, atol=1e-5)


def test_reference_refuses_what_it_does_not_compute():
    ref = load_reference("nemotron_h")
    ref.check_supported(TINY)
    for extra in ({"hybrid_override_pattern": "ME-MEM"},
                  {"hybrid_override_pattern": "ME*M"},
                  {"n_group": 8, "topk_group": 4}, {"mamba_proj_bias": True},
                  {"mlp_bias": True}, {"attention_bias": True},
                  {"sliding_window": 4096}, {"mlp_hidden_act": "silu"},
                  {"n_shared_experts": 2}, {"tie_word_embeddings": True},
                  {"use_conv_bias": False}, {"model_type": "mamba2"}):
        with pytest.raises(NotImplementedError):
            ref.check_supported({**TINY, **extra})


def test_the_configurations_file_is_the_catalogs_but_for_depth_pattern_and_share():
    with open(os.path.join(BENCH, "configs", "nemotron3-nano.json")) as f:
        cfg = json.load(f)
    hf = {k: v for k, v in cfg.items() if k not in manifest.OWN_KEYS}
    load_reference("nemotron_h").check_supported(hf)
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072}
    assert {k: hf[k] for k in published} == published
    assert set(hf) == set(published) | {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "router_n_experts", "first_expert"}
    assert hf["num_hidden_layers"] == 16 == len(hf["hybrid_override_pattern"])
    assert hf["hybrid_override_pattern"] == "MEMEM*EMEMEM*EME"
    # 7 : 7 : 2 of the published 23 : 23 : 6, and the pattern's first 16 letters
    dep = cfg["deployment"]
    whole = dep["published"]["hybrid_override_pattern"]
    assert whole.startswith(hf["hybrid_override_pattern"]) and len(whole) == 52
    assert [whole.count(c) for c in "ME*"] == [23, 23, 6]
    assert (hf["n_routed_experts"], hf["router_n_experts"], hf["first_expert"]) == (
        64, 128, 0)
    assert cfg["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts"]
    assert dep["published"]["n_routed_experts"] == 128
    assert dep["published"]["num_hidden_layers"] == 52
    assert dep["family"] == "nemotron_h" and dep["chips"] == 1
    assert "2 chips" in dep["stands_for"] and "first 16 layers" in dep["stands_for"]
    flags = dep["server_flags"]
    assert flags["max_batch_size"] == 48 and flags["tp"] == 1
    # K/V of the 2 attention layers: 2048 B a token
    token = 2 * 2 * 2 * 128 * 2
    pool = flags["kv_pages"] * flags["page_size"] * token
    assert 0.6e9 < pool < 1.1e9
    assert any("router_n_experts 128" in a for a in cfg["assumed"])
    assert any("NO rotary" in a for a in cfg["assumed"])
    assert any("d_inner" in a and "4096" in a for a in cfg["assumed"])
    tiny = cfg["rehearsal"]["hf_overrides"]
    assert set(tiny["hybrid_override_pattern"]) == set("ME*")
    assert (tiny["n_routed_experts"], tiny["router_n_experts"],
            tiny["num_experts_per_tok"]) == (4, 8, 2)
    cell = manifest.resolve_cell("nemotron3-nano.agent-long-sat")
    assert cell.chips == 1 and cell.pair["clients"] == 48
    assert cell.pair["server_flags"] == {
        "max_model_len": 5120, "max_prefill_len": 2048}
    mix = cell.traffic
    assert mix["loop"] == "closed" and mix["sampling"] == {"temperature": 0.0}
    assert (mix["prompt_len"]["min"], mix["prompt_len"]["max"]) == (2048, 4096)
    assert (mix["output_len"]["min"], mix["output_len"]["max"]) == (768, 1024)
    # the worst case fits the pool: no request waits for a page
    assert 48 * -(-5120 // flags["page_size"]) <= flags["kv_pages"] - 1
    assert {m["name"] for m in cell.end_to_end} == {
        "tpot_p50_ms", "itl_p99_ms", "output_tok_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"ssd.share", "ssd.update_roofline", "ssd.chunk_roofline",
            "moe.held_experts_roofline", "moe.held_pair_share",
            "moe.rows_per_expert", "moe.experts_share", "cache.state_hbm_share",
            "cache.pool_fill_share", "dispatch.padded_share",
            "dispatch.deliver_overlap_share"} <= names
    # gated experts' and Mamba-1's readers stay off this cell
    assert not {"moe.experts_roofline", "ssm.update_share",
                "ssm.update_roofline"} & names
    # the sizes' arithmetic, from the file alone
    assert nemotron_math.sizes(hf)["conv"] == 6144
    assert nemotron_math.state_bytes(hf) == 64 * 64 * 128 * 4 + 3 * 6144 * 2
    assert (nemotron_math.mamba_layers(hf), nemotron_math.expert_layers(hf)) == (7, 7)
    assert nemotron_math.held_expert_bytes(hf) == 2 * 2688 * 1856 * 2


def test_check_child_reports_gaps_and_catches_a_wrong_token(tmp_path):
    """benchmark/reference/check.py, unedited, with `--family nemotron_h`."""
    cfg = dict(TINY, torch_dtype="bfloat16")
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    prompt = np.random.RandomState(1).randint(0, 320, size=12).tolist()

    def run(probes):
        (tmp_path / "probes.json").write_text(json.dumps(probes))
        proc = subprocess.run(
            [sys.executable, os.path.join(BENCH, "reference", "check.py"),
             "--config", str(tmp_path / "config.json"),
             "--family", "nemotron_h",
             "--probes", str(tmp_path / "probes.json"),
             "--out", str(tmp_path / "out.json")],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
            text=True, timeout=300, cwd=ROOT)
        assert proc.returncode == 0, proc.stderr[-2000:]
        with open(tmp_path / "out.json") as f:
            return json.load(f)

    ref = load_reference("nemotron_h")
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
