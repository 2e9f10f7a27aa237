"""Each reference forward against the program's logits at a tiny size, and
the logit-gap comparison that decides agreement."""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from bench_paths import BENCH, ROOT

TINY = {
    "qwen3-like": {  # QK-norm, tied embeddings, head_dim not hidden/heads
        "model_type": "qwen3", "vocab_size": 320, "hidden_size": 64,
        "intermediate_size": 160, "num_hidden_layers": 3,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "rms_norm_eps": 1e-6, "rope_theta": 1000000.0,
        "tie_word_embeddings": True, "torch_dtype": "float32"},
    "mistral-like": {  # untied head, no QK-norm
        "model_type": "mistral", "vocab_size": 288, "hidden_size": 64,
        "intermediate_size": 192, "num_hidden_layers": 2,
        "num_attention_heads": 8, "num_key_value_heads": 2,
        "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
        "tie_word_embeddings": False, "torch_dtype": "float32"},
}


def load_reference(family="llama"):
    path = os.path.join(BENCH, "reference", family + ".py")
    spec = importlib.util.spec_from_file_location("reference_" + family, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("name", sorted(TINY))
def test_reference_matches_the_programs_prefill_logits(name):
    """The program's own prefill (float32 here, so the comparison is about
    the mathematics, not rounding) against the plain forward: the logits at
    the last position agree to float32 accuracy."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from kserve_tpu.engine.kvcache import KVCacheConfig, init_kv_pages
    from kserve_tpu.models import llama

    cfg = TINY[name]
    config = dataclasses.replace(
        llama.LlamaConfig.from_hf_config(cfg), dtype="float32")
    params = llama.init_params(config, jax.random.PRNGKey(1), scale=0.1)
    tokens = np.random.RandomState(0).randint(0, cfg["vocab_size"], size=37)
    cache = KVCacheConfig(
        n_layers=config.n_layers, n_kv_heads=config.n_kv_heads,
        head_dim=config.head_dim, page_size=16, num_pages=8,
        max_pages_per_seq=4, dtype="float32")
    with jax.default_matmul_precision("highest"):
        got, _ = llama.prefill(
            params, config, jnp.asarray(tokens)[None], jnp.asarray([37]),
            init_kv_pages(cache), jnp.asarray([[1, 2, 3, 0]]), 16)
    want = load_reference().forward(params, cfg, tokens.tolist())
    assert want.shape == (37, cfg["vocab_size"])
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(want[-1]), rtol=2e-4, atol=2e-5)
    assert float(np.abs(np.asarray(want[-1])).max()) > 0.05  # not vacuous


def test_reference_refuses_what_it_does_not_compute():
    ref = load_reference()
    for extra in ({"rope_scaling": {"rope_type": "llama3"}},
                  {"attention_bias": True}, {"num_local_experts": 8},
                  {"hidden_act": "gelu_tanh"}):
        with pytest.raises(NotImplementedError):
            ref.check_supported({**TINY["mistral-like"], **extra})


def test_check_child_reports_gaps_and_catches_a_wrong_token(tmp_path):
    """benchmark/reference/check.py as run.py starts it: served tokens that
    ARE the reference's greedy continuation have gap 0; a token swapped for
    another has a gap far beyond any tolerance."""
    cfg = dict(TINY["qwen3-like"], torch_dtype="bfloat16")
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    prompt = np.random.RandomState(1).randint(0, 320, size=12).tolist()

    def run(probes):
        (tmp_path / "probes.json").write_text(json.dumps(probes))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, os.path.join(BENCH, "reference", "check.py"),
             "--config", str(tmp_path / "config.json"), "--family", "llama",
             "--probes", str(tmp_path / "probes.json"),
             "--out", str(tmp_path / "out.json")],
            env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
        assert proc.returncode == 0, proc.stderr[-2000:]
        with open(tmp_path / "out.json") as f:
            return json.load(f)

    # greedy continuation by the reference itself, teacher-forced
    import jax

    sys.path.insert(0, ROOT)
    from kserve_tpu.models import llama

    ref = load_reference()
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
    # (the token after the swap is judged against a changed context)
    assert bad["gaps"][0][2] > 0.0 and bad["argmax_match_share"] <= 0.75
    assert bad["gaps"][0][:2] == [0.0, 0.0]
