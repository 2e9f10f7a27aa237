"""benchmark/reference/lfm2_moe.py: what it computes against a hand-written
loop, what it refuses, the published configuration's file and the cell's
files, and the check child with `--family lfm2_moe`.  (The program's
forwards are held to it in tests/test_lfm2_model.py and
tests/test_lfm2_engine.py.)"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
from bench_paths import BENCH, ROOT
from test_benchmark_reference import load_reference

from kbench import conv_math, manifest

TINY = {
    "model_type": "lfm2_moe", "vocab_size": 320, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 4,
    "layer_types": ["conv", "conv", "full_attention", "conv"],
    "conv_L_cache": 3, "conv_bias": False, "num_attention_heads": 4,
    "num_key_value_heads": 2, "norm_eps": 1e-5, "num_dense_layers": 2,
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 48,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}


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


def _rms(x, w, eps=1e-5):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def test_the_convolution_the_normed_roped_heads_the_experts_and_causality():
    """`forward` against the equations written out once more with numpy:
    the short convolution token by token (the two products around three
    taps over the last rows of z), one attention head at a time with its
    norm and its rotary on columns (j, j + d/2), the expert layer as a loop
    over tokens and their choices."""
    import jax

    ref, params = load_reference("lfm2_moe"), _params(TINY)
    tokens = np.random.RandomState(0).randint(0, 320, size=9).tolist()
    logits = np.asarray(ref.forward(params, TINY, tokens))
    assert logits.shape == (9, 320) and logits.dtype == np.float32
    moved = np.asarray(ref.forward(params, TINY, tokens[:-1] + [7]))
    np.testing.assert_allclose(moved[:-1], logits[:-1], rtol=1e-5, atol=1e-6)
    x = np.random.RandomState(1).randn(7, 64).astype(np.float32)
    # the short convolution, token by token
    layer = {k: np.asarray(v, np.float32) for k, v in params["layers"][0].items()}
    bcx = x @ layer["in_proj"]
    b, c, xin = bcx[:, :64], bcx[:, 64:128], bcx[:, 128:]
    z = b * xin
    want = np.zeros((7, 64), np.float32)
    for t in range(7):
        conv = sum(layer["conv_w"][2 - k] * z[t - k] for k in range(3) if t - k >= 0)
        want[t] = (c[t] * conv) @ layer["out_proj"]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.short_conv(params["layers"][0], ref.f32(x)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # the dense rows' feed-forward
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.gated(ref.f32(x), layer["w_gate"], layer["w_up"],
                                   layer["w_down"]))
    np.testing.assert_allclose(
        got, (_silu(x @ layer["w_gate"]) * (x @ layer["w_up"])) @ layer["w_down"],
        rtol=1e-4, atol=1e-5)
    # the attention row, a head and a query at a time: 4 query heads of 16
    # over 2 K/V heads, the norm a head, then the rotary
    a = {k: np.asarray(v, np.float32) for k, v in params["layers"][2].items()}
    a["q_norm"] = 1 + 0.2 * np.random.RandomState(2).randn(16).astype(np.float32)
    a["k_norm"] = 1 + 0.2 * np.random.RandomState(3).randn(16).astype(np.float32)
    d, inv = 16, 1e6 ** (-np.arange(0, 16, 2) / 16)

    def turned(v, t):
        ang = t * inv
        v1, v2 = v[:8], v[8:]
        return np.concatenate([v1 * np.cos(ang) - v2 * np.sin(ang),
                               v2 * np.cos(ang) + v1 * np.sin(ang)])

    q = (x @ a["wq"]).reshape(7, 4, d)
    k = (x @ a["wk"]).reshape(7, 2, d)
    v = (x @ a["wv"]).reshape(7, 2, d)
    out = np.zeros((7, 4, d), np.float32)
    for h in range(4):
        kv = h // 2
        keys = np.stack([turned(_rms(k[s, kv], a["k_norm"]), s) for s in range(7)])
        for t in range(7):
            qt = turned(_rms(q[t, h], a["q_norm"]), t)
            scores = keys[:t + 1] @ qt / np.sqrt(d)
            p = np.exp(scores - scores.max())
            out[t, h] = (p / p.sum()) @ v[:t + 1, kv]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.attention(a, ref.f32(x), TINY))
    np.testing.assert_allclose(
        got, out.reshape(7, 64) @ a["wo"], rtol=2e-4, atol=2e-5)
    # the expert layer, token by token: the bias chooses and weighs nothing
    a["router_bias"] = 0.05 * np.random.RandomState(4).randn(8).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(7):
        s = _sigmoid(x[t] @ a["router"])
        idx = np.argsort(-(s + a["router_bias"]), kind="stable")[:2]
        w = s[idx] / s[idx].sum()
        for wj, e in zip(w, idx):
            want[t] += wj * (_silu(x[t] @ a["w_gate"][e]) * (
                x[t] @ a["w_up"][e])) @ a["w_down"][e]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.experts(a, ref.f32(x), TINY))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_reference_refuses_what_it_does_not_compute():
    ref = load_reference("lfm2_moe")
    ref.check_supported(TINY)
    for extra in ({"conv_bias": True}, {"model_type": "lfm2"},
                  {"layer_types": ["conv", "sliding_attention"] * 2},
                  {"rope_parameters": {"rope_type": "yarn"}},
                  {"tie_word_embeddings": False}):
        with pytest.raises(NotImplementedError):
            ref.check_supported({**TINY, **extra})


def test_the_configurations_file_is_the_catalogs_but_for_depth():
    with open(os.path.join(BENCH, "configs", "lfm2-24b-a2b.json")) as f:
        cfg = json.load(f)
    hf = {k: v for k, v in cfg.items() if k not in manifest.OWN_KEYS}
    load_reference("lfm2_moe").check_supported(hf)
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
        "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    assert {k: hf[k] for k in published} == published
    # one key the published file lacks, head_dim (under `assumed`: an accepted
    # reader asks for it); no alias of `num_experts` for a reader's sake
    assert set(hf) == set(published) | {
        "num_hidden_layers", "layer_types", "head_dim"}
    assert hf["head_dim"] == 64 == hf["hidden_size"] // hf["num_attention_heads"]
    whole = ["conv", "conv", "full_attention", "conv"] * 10
    assert (hf["num_hidden_layers"], hf["layer_types"]) == (8, whole[:8])
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    dep = cfg["deployment"]
    assert dep["published"] == {"num_hidden_layers": 40, "layer_types": whole}
    # the floors: whole periods, 4 layers behind the dense ones, every expert
    assert hf["num_hidden_layers"] % 4 == 0
    assert hf["num_hidden_layers"] - hf["num_dense_layers"] >= 4
    assert dep["family"] == "lfm2_moe" and dep["chips"] == 1
    assert "first of five 8-layer pipeline stages" in dep["stands_for"]
    assert "chips that share a layer: 1" in dep["stands_for"]
    flags = dep["server_flags"]
    assert flags["max_batch_size"] == 48 and flags["tp"] == 1
    # K/V of the two attention rows: 4096 B a token; the worst case fits
    pool = flags["kv_pages"] * flags["page_size"] * 2 * 8 * 64 * 2 * 2
    assert 1.6e9 < pool < 1.8e9
    for said in ("head_dim 64", "tied head", "[B | C | x]", "NO activation",
                 "BEFORE the rotary", "1e-20", "1/3 + N(0, 0.02)"):
        assert any(said in a for a in cfg["assumed"]), said
    tiny = cfg["rehearsal"]["hf_overrides"]
    assert (tiny["num_experts"], tiny["num_experts_per_tok"]) == (8, 2)
    assert tiny["hidden_size"] // tiny["num_attention_heads"] == 16 == tiny["head_dim"]
    assert "num_hidden_layers" not in tiny and "layer_types" not in tiny
    cell = manifest.resolve_cell("lfm2-24b-a2b.extract-sat")
    assert cell.chips == 1 and cell.pair["clients"] == 48
    assert cell.pair["server_flags"] == {
        "max_model_len": 8192, "max_prefill_len": 4096}
    mix = cell.traffic
    assert mix["loop"] == "closed" and mix["sampling"] == {"temperature": 0.0}
    assert (mix["prompt_len"]["dist"], mix["prompt_len"]["min"],
            mix["prompt_len"]["max"]) == ("uniform", 2048, 6144)
    assert (mix["output_len"]["dist"], mix["output_len"]["min"],
            mix["output_len"]["max"]) == ("uniform", 64, 192)
    assert 48 * -(-8192 // flags["page_size"]) <= flags["kv_pages"] - 1
    assert {m["name"] for m in cell.end_to_end} == {
        "tpot_p50_ms", "itl_p99_ms", "output_tok_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"conv.share", "conv.packed_roofline", "model.mfu",
            "kernel.attention_share", "attention.xla_gather_share"} <= names
    # other families' readers stay off this cell; `expert_math` keys on
    # `n_routed_experts`, which this published file does not have
    assert not {"ssd.share", "ssm.update_share", "kda.share",
                "moe.experts_share", "moe.experts_roofline",
                "moe.held_pair_share", "attention.window_share"} & names
    # the two new metrics are this cell's alone, at the list's end
    whole = manifest.load_manifest()
    assert [m["name"] for m in whole["per_layer"][-2:]] == [
        "conv.share", "conv.packed_roofline"]
    assert all(m["workloads"] == ["lfm2-24b-a2b.extract-sat"]
               and m["moves"] == "output_tok_s"
               for m in whole["per_layer"][-2:])
    assert [w["name"] for w in whole["workloads"]][-1] == "lfm2-24b-a2b.extract-sat"
    entry = whole["configs"][-1]
    assert entry["name"] == "lfm2-24b-a2b" and entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    # the sizes' arithmetic, from the file alone
    assert conv_math.sizes(hf) == {"hidden": 2048, "in": 6144, "K": 3}
    assert conv_math.conv_layers(hf) == 6
    assert conv_math.tail_bytes(hf) == 2 * 2048 * 2 == 8192


def test_check_child_reports_gaps_and_catches_a_wrong_token(tmp_path):
    """benchmark/reference/check.py, unedited, with `--family lfm2_moe`."""
    cfg = dict(TINY, torch_dtype="bfloat16")
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    prompt = np.random.RandomState(1).randint(0, 320, size=12).tolist()

    def run(probes):
        (tmp_path / "probes.json").write_text(json.dumps(probes))
        proc = subprocess.run(
            [sys.executable, os.path.join(BENCH, "reference", "check.py"),
             "--config", str(tmp_path / "config.json"),
             "--family", "lfm2_moe",
             "--probes", str(tmp_path / "probes.json"),
             "--out", str(tmp_path / "out.json")],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
            text=True, timeout=300, cwd=ROOT)
        assert proc.returncode == 0, proc.stderr[-2000:]
        with open(tmp_path / "out.json") as f:
            return json.load(f)

    ref = load_reference("lfm2_moe")
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
