"""benchmark/reference/phi4flash.py against the program's logits at a tiny
size, what it refuses, and the check child with `--family phi4flash`."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from bench_paths import BENCH, ROOT
from test_benchmark_reference import load_reference

TINY = {
    "model_type": "phi4flash", "vocab_size": 320, "hidden_size": 64,
    "intermediate_size": 160, "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "layer_norm_eps": 1e-5,
    "sliding_window": 8, "mb_per_layer": 2, "tie_word_embeddings": True,
    "mamba_d_inner": 128, "mamba_d_state": 4, "mamba_d_conv": 4,
    "mamba_dt_rank": 4, "attention_bias": True, "attention_out_bias": True,
    "diff_attention_pairing": "adjacent", "torch_dtype": "float32"}


def test_reference_matches_the_programs_prefill_logits():
    """The program's packed forward over one 37-token prompt (float32, so
    the comparison is about the mathematics) against the plain forward: the
    logits at the last position agree to float32 accuracy.  37 tokens under
    a window of 8: the window layers' mask is in what is compared."""
    import jax
    import jax.numpy as jnp

    from kserve_tpu.engine.kvcache import StateLayout
    from kserve_tpu.models import llama

    config = dataclasses.replace(
        llama.LlamaConfig.from_hf_config(TINY), dtype="float32")
    params = llama.init_params(config, jax.random.PRNGKey(1), scale=0.1)
    tokens = np.random.RandomState(0).randint(0, 320, size=37)
    state = StateLayout.of(config, 16, 8, 1, "float32").init_state()
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got, _ = llama.forward_ragged(
            params, config, i32(np.pad(tokens, (0, 3))),
            i32([0] * 37 + [-1] * 3), i32(list(range(37)) + [0] * 3),
            i32([0]), i32([37]), i32([0]), state, i32([[1, 2, 3, 0]]), 16,
            i32([36]))
    want = load_reference("phi4flash").forward(params, TINY, tokens.tolist())
    assert want.shape == (37, 320)
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(want[-1]), rtol=2e-4, atol=5e-5)
    assert float(np.abs(np.asarray(want[-1])).max()) > 0.5  # not vacuous


def test_reference_refuses_what_it_does_not_compute():
    ref = load_reference("phi4flash")
    ref.check_supported(TINY)
    for extra in ({"rope_theta": 10000.0}, {"partial_rotary_factor": 0.5},
                  {"mlp_bias": True}, {"lm_head_bias": True},
                  {"hidden_act": "gelu_tanh"}, {"mb_per_layer": 4},
                  {"num_hidden_layers": 6}, {"tie_word_embeddings": False},
                  {"diff_attention_pairing": "halves"},
                  {"model_type": "qwen3"}, {"some_new_key": 1}):
        with pytest.raises(NotImplementedError):
            ref.check_supported({**TINY, **extra})
    # the published configuration, with what the benchmark's file assumes
    with open(os.path.join(BENCH, "configs", "phi4-mini-flash.json")) as f:
        cfg = json.load(f)
    ref.check_supported({k: v for k, v in cfg.items() if k not in (
        "deployment", "assumed", "source", "reduced", "rehearsal")})


def test_check_child_reports_gaps_and_catches_a_wrong_token(tmp_path):
    """benchmark/reference/check.py, unedited, with `--family phi4flash`:
    it makes the weights through LlamaConfig.from_hf_config and
    sharding.init_params_on_mesh, as for any family."""
    cfg = dict(TINY, torch_dtype="bfloat16")
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    prompt = np.random.RandomState(1).randint(0, 320, size=12).tolist()

    def run(probes):
        (tmp_path / "probes.json").write_text(json.dumps(probes))
        proc = subprocess.run(
            [sys.executable, os.path.join(BENCH, "reference", "check.py"),
             "--config", str(tmp_path / "config.json"), "--family", "phi4flash",
             "--probes", str(tmp_path / "probes.json"),
             "--out", str(tmp_path / "out.json")],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
            text=True, timeout=300, cwd=ROOT)
        assert proc.returncode == 0, proc.stderr[-2000:]
        with open(tmp_path / "out.json") as f:
            return json.load(f)

    import jax

    sys.path.insert(0, ROOT)
    from kserve_tpu.models import llama

    ref = load_reference("phi4flash")
    params = llama.init_params(
        llama.LlamaConfig.from_hf_config(cfg), jax.random.PRNGKey(1))
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
