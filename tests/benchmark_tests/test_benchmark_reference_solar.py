"""benchmark/reference/solar_open2.py: what it computes against a
hand-written loop, what it refuses, the published configuration's file and
the cell's files, and the check child with `--family solar_open2`.  (The
program's forwards are held to it in tests/test_solar_open2_model.py and
tests/test_solar_open2_engine.py.)"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
from bench_paths import BENCH, ROOT
from test_benchmark_reference import load_reference

from kbench import delta_math, expert_math, manifest

TINY = {
    "model_type": "solar_open2", "vocab_size": 320, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 4, "gqa_interval": 3,
    "gqa_layers": [0], "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "use_rope": False, "use_gqa_gate": True,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                           "num_heads": 4, "num_kv_heads": None},
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "moe_intermediate_size": 48, "n_routed_experts": 4, "router_n_experts": 8,
    "first_expert": 0, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 1, "norm_topk_prob": True, "rms_norm_eps": 1e-5,
    "first_k_dense_replace": 0, "tie_word_embeddings": False}


def _params(cfg, scale=0.1):
    import jax

    sys.path.insert(0, ROOT)
    from kserve_tpu.models import llama

    return llama.init_params(
        llama.LlamaConfig.from_hf_config(cfg), jax.random.PRNGKey(1), scale=scale)


def _sigmoid(x):
    return 1 / (1 + np.exp(-x))


def _silu(x):
    return x * _sigmoid(x)


def test_the_recurrence_the_gate_the_held_experts_and_causality():
    """`forward` against the equations written out once more with numpy:
    the KDA mixer token by token (three convolutions' taps, the norms of q
    and k, a decay a channel, the delta rule on a matrix a head, the gated
    norm a head), the attention row's gate, the expert layer as a loop over
    tokens and their choices with the absent experts adding nothing."""
    import jax

    ref, params = load_reference("solar_open2"), _params(TINY)
    tokens = np.random.RandomState(0).randint(0, 320, size=9).tolist()
    logits = np.asarray(ref.forward(params, TINY, tokens))
    assert logits.shape == (9, 320) and logits.dtype == np.float32
    moved = np.asarray(ref.forward(params, TINY, tokens[:-1] + [7]))
    np.testing.assert_allclose(moved[:-1], logits[:-1], rtol=1e-5, atol=1e-6)
    x = np.random.RandomState(1).randn(7, 64).astype(np.float32)
    # the expert layer, token by token: 4 of 8 experts are held
    layer = {k: np.asarray(v, np.float32) for k, v in params["layers"][1].items()}

    def gated(v, gate, up, down):
        return (_silu(v @ gate) * (v @ up)) @ down

    want, absent = np.zeros_like(x), 0
    for t in range(7):
        s = _sigmoid(x[t] @ layer["router"])
        idx = np.argsort(-(s + layer["router_bias"]), kind="stable")[:2]
        w = s[idx] / (s[idx].sum() + 1e-20)
        want[t] = gated(x[t], layer["shared_gate"], layer["shared_up"],
                        layer["shared_down"])
        for wj, e in zip(w, idx):
            if e < 4:
                want[t] += wj * gated(x[t], layer["w_gate"][e],
                                      layer["w_up"][e], layer["w_down"][e])
            else:
                absent += 1
    assert 0 < absent < 14  # the comparison exercises both branches
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.experts(params["layers"][1], ref.f32(x), TINY))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # the KDA mixer, token by token
    H, d, K = 4, 16, 4
    qkv = x @ layer["wqkv"]
    f = (x @ layer["wf_a"]) @ layer["wf_b"] + layer["dt_bias"]
    g = -np.exp(layer["A_log"])[None, :, None] * np.log1p(np.exp(f)).reshape(7, H, d)
    beta = 2 * _sigmoid(x @ layer["w_beta"])
    gate = _sigmoid((x @ layer["wg_a"]) @ layer["wg_b"])
    state = np.zeros((H, d, d), np.float32)
    out = np.zeros((7, H * d), np.float32)
    for t in range(7):
        taps = _silu(sum(layer["conv_w"][K - 1 - k] * qkv[t - k]
                         for k in range(K) if t - k >= 0))
        q, k, v = (taps[i * H * d:(i + 1) * H * d].reshape(H, d) for i in range(3))
        q = q / np.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) / np.sqrt(d)
        k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
        for h in range(H):
            decayed = np.exp(g[t, h])[:, None] * state[h]
            state[h] = decayed + beta[t, h] * np.outer(k[h], v[h] - decayed.T @ k[h])
            o = state[h].T @ q[h]
            o = o / np.sqrt((o * o).mean() + 1e-5) * layer["o_norm"]
            out[t, h * d:(h + 1) * d] = o
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.kda(params["layers"][1], ref.f32(x), TINY))
    np.testing.assert_allclose(got, (out * gate) @ layer["wo"], rtol=2e-4, atol=2e-5)
    # attention has no positional term and its output is gated: with ONE
    # token nothing but the value, the gate and the output projection
    with jax.default_matmul_precision("highest"):
        one = np.asarray(ref.attention(params["layers"][0], ref.f32(x[:1]), TINY))
    a = {k: np.asarray(v, np.float32) for k, v in params["layers"][0].items()}
    v = np.repeat((x[:1] @ a["wv"]).reshape(1, 2, 16), 2, axis=1).reshape(1, 64)
    np.testing.assert_allclose(
        one, (v * _sigmoid(x[:1] @ a["wg"])) @ a["wo"], rtol=1e-4, atol=1e-5)


def test_reference_refuses_what_it_does_not_compute():
    ref = load_reference("solar_open2")
    ref.check_supported(TINY)
    linear = TINY["linear_attn_config"]
    for extra in ({"first_k_dense_replace": 1}, {"kda_use_full_proj": True},
                  {"use_rope": True}, {"n_group": 8},
                  {"linear_attn_config": dict(linear, num_kv_heads=2)},
                  {"n_shared_experts": 2}, {"tie_word_embeddings": True},
                  {"model_type": "kimi_linear"}):
        with pytest.raises(NotImplementedError):
            ref.check_supported({**TINY, **extra})


def test_the_configurations_file_is_the_catalogs_but_for_depth_share_and_vocabulary():
    with open(os.path.join(BENCH, "configs", "solar-open2.json")) as f:
        cfg = json.load(f)
    hf = {k: v for k, v in cfg.items() if k not in manifest.OWN_KEYS}
    load_reference("solar_open2").check_supported(hf)
    published = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                               "num_heads": 64, "num_kv_heads": None},
        "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "intermediate_size": 10240,
        "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "tie_word_embeddings": False,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
        "use_rope": False, "gqa_interval": 3, "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    assert {k: hf[k] for k in published} == published
    assert set(hf) == set(published) | {
        "num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size",
        "router_n_experts", "first_expert"}
    assert (hf["num_hidden_layers"], hf["gqa_layers"]) == (4, [0])
    assert (hf["n_routed_experts"], hf["router_n_experts"], hf["first_expert"],
            hf["vocab_size"]) == (40, 320, 0, 24576)
    assert cfg["reduced"] == [
        "num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"]
    dep = cfg["deployment"]
    assert dep["published"] == {
        "num_hidden_layers": 48,
        "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
        "n_routed_experts": 320, "vocab_size": 196608}
    # the floors: a whole period and 4 layers, 8 experts, 1/8 of the vocabulary
    assert hf["num_hidden_layers"] % (hf["gqa_interval"] + 1) == 0
    assert hf["n_routed_experts"] >= 8 and hf["vocab_size"] * 8 >= 196608
    assert dep["family"] == "solar_open2" and dep["chips"] == 1
    assert "8 chips" in dep["stands_for"] and "4-layer stage of 12" in dep["stands_for"]
    flags = dep["server_flags"]
    assert flags["max_batch_size"] == 48 and flags["tp"] == 1
    # K/V of the one GQA layer: 4096 B a token; the worst case fits the pool
    pool = flags["kv_pages"] * flags["page_size"] * 8 * 128 * 2 * 2
    assert 1.6e9 < pool < 1.8e9
    for said in ("router_n_experts 320", "NO positional encoding",
                 "rank 128", "arXiv:2505.06708", "arXiv:2510.26692"):
        assert any(said in a for a in cfg["assumed"]), said
    tiny = cfg["rehearsal"]["hf_overrides"]
    assert (tiny["num_hidden_layers"], tiny["gqa_layers"]) == (4, [0])
    assert (tiny["n_routed_experts"], tiny["router_n_experts"],
            tiny["num_experts_per_tok"]) == (4, 8, 2)
    # between the readings of the harness's own 48 + 8 probes: the largest
    # in bf16 on a probe of that shape and the reference on int8 weights;
    # each written into the file with its origin, the longer probes' too
    assert 0.0619 < dep["logit_tolerance"] < 0.1068
    assert all(said in dep["logit_tolerance_why"] for said in (
        "0.0009", "0.0619", "0.1068", "0.1318", "0.3982", "NOT separated"))
    cell = manifest.resolve_cell("solar-open2.long-doc-sat")
    assert cell.chips == 1 and cell.pair["clients"] == 48
    assert cell.pair["server_flags"] == {
        "max_model_len": 8192, "max_prefill_len": 4096}
    mix = cell.traffic
    assert mix["loop"] == "closed" and mix["sampling"] == {"temperature": 0.0}
    assert (mix["prompt_len"]["dist"], mix["prompt_len"]["min"],
            mix["prompt_len"]["max"]) == ("uniform", 3072, 7168)
    assert (mix["output_len"]["dist"], mix["output_len"]["min"],
            mix["output_len"]["max"]) == ("uniform", 512, 768)
    assert 48 * -(-8192 // flags["page_size"]) <= flags["kv_pages"] - 1
    assert {m["name"] for m in cell.end_to_end} == {
        "tpot_p50_ms", "itl_p99_ms", "output_tok_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"kda.share", "kda.update_roofline", "kda.chunk_roofline",
            "moe.experts_share", "moe.experts_roofline", "moe.held_pair_share",
            "moe.rows_per_expert", "cache.state_hbm_share",
            "cache.pool_fill_share", "dispatch.padded_share",
            "dispatch.deliver_overlap_share"} <= names
    # other families' readers stay off this cell
    assert not {"ssd.share", "ssm.update_share", "moe.held_experts_roofline",
                "moe.held_gated_roofline", "attention.window_share"} & names
    # the three new metrics are this cell's alone, at the list's end
    whole = manifest.load_manifest()
    assert [m["name"] for m in whole["per_layer"][-3:]] == [
        "kda.share", "kda.update_roofline", "kda.chunk_roofline"]
    assert all(m["workloads"] == ["solar-open2.long-doc-sat"]
               for m in whole["per_layer"][-3:])
    # the sizes' arithmetic, from the file alone
    assert delta_math.sizes(hf) == {
        "H": 64, "d": 128, "K": 4, "inner": 8192, "conv": 24576}
    assert delta_math.state_bytes(hf) == 64 * 128 * 128 * 4 + 3 * 24576 * 2 == 4_341_760
    assert delta_math.kda_layers(hf) == 3
    assert expert_math.expert_bytes(hf) == 3 * 4096 * 1280 * 2
    assert expert_math.expert_layers(hf) == 4


def test_check_child_reports_gaps_and_catches_a_wrong_token(tmp_path):
    """benchmark/reference/check.py, unedited, with `--family solar_open2`."""
    cfg = dict(TINY, torch_dtype="bfloat16")
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    prompt = np.random.RandomState(1).randint(0, 320, size=12).tolist()

    def run(probes):
        (tmp_path / "probes.json").write_text(json.dumps(probes))
        proc = subprocess.run(
            [sys.executable, os.path.join(BENCH, "reference", "check.py"),
             "--config", str(tmp_path / "config.json"),
             "--family", "solar_open2",
             "--probes", str(tmp_path / "probes.json"),
             "--out", str(tmp_path / "out.json")],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
            text=True, timeout=300, cwd=ROOT)
        assert proc.returncode == 0, proc.stderr[-2000:]
        with open(tmp_path / "out.json") as f:
            return json.load(f)

    ref = load_reference("solar_open2")
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
