"""benchmark/reference/ouro.py: what it computes against a hand-written
loop, what it refuses, the published configuration's file, and the check
child with `--family ouro`.  (The program's forwards are held to it in
tests/test_ouro_model.py and tests/test_ouro_engine.py.)"""

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
    "model_type": "ouro", "vocab_size": 320, "hidden_size": 64,
    "intermediate_size": 160, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "hidden_act": "silu", "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "rope_scaling": None, "tie_word_embeddings": False, "total_ut_steps": 3,
    "early_exit_threshold": 1, "torch_dtype": "float32"}


def _params(cfg):
    import jax

    sys.path.insert(0, ROOT)
    from kserve_tpu.models import llama

    return llama.init_params(
        llama.LlamaConfig.from_hf_config(cfg), jax.random.PRNGKey(1), scale=0.1)


def test_the_final_norm_closes_every_pass_and_the_weights_are_shared():
    """`forward` is the last of `hidden_states` through the head; a pass is
    the same layers again on the normed output of the one before: three
    passes are one pass applied three times."""
    import jax
    import jax.numpy as jnp

    ref, params = load_reference("ouro"), _params(TINY)
    tokens = np.random.RandomState(0).randint(0, 320, size=11).tolist()
    with jax.default_matmul_precision("highest"):
        after = ref.hidden_states(params, TINY, tokens)
        x = jnp.asarray(params["embed"])[jnp.asarray(tokens)].astype(jnp.float32)
        for want in after:
            for layer in params["layers"]:
                x = ref.layer_forward(layer, x, TINY)
            x = ref.rms_norm(x, params["final_norm"], 1e-6)
            np.testing.assert_allclose(np.asarray(x), np.asarray(want),
                                       rtol=1e-5, atol=1e-6)
    logits = np.asarray(ref.forward(params, TINY, tokens))
    assert logits.shape == (11, 320) and logits.dtype == np.float32
    np.testing.assert_allclose(
        logits, np.asarray(after[-1]) @ np.asarray(params["lm_head"], np.float32),
        rtol=1e-4, atol=1e-5)
    # every pass changes the state: none is a fixed point at these weights
    assert np.abs(np.asarray(after[1]) - np.asarray(after[2])).max() > 1e-2
    # causal: a later token does not move an earlier position
    moved = np.asarray(ref.forward(params, TINY, tokens[:-1] + [7]))
    np.testing.assert_allclose(moved[:-1], logits[:-1], rtol=1e-5, atol=1e-6)


def test_reference_refuses_what_it_does_not_compute():
    ref = load_reference("ouro")
    ref.check_supported(TINY)
    for extra in ({"early_exit_threshold": 0.9}, {"attention_bias": True},
                  {"rope_scaling": {"type": "linear", "factor": 2}},
                  {"tie_word_embeddings": True}, {"hidden_act": "gelu"},
                  {"sliding_window": 128, "use_sliding_window": True},
                  {"model_type": "llama"}):
        with pytest.raises(NotImplementedError):
            ref.check_supported({**TINY, **extra})


def test_the_configurations_file_is_the_catalogs_and_is_supported():
    with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as f:
        cfg = json.load(f)
    hf = {k: v for k, v in cfg.items() if k not in manifest.OWN_KEYS}
    load_reference("ouro").check_supported(hf)
    assert cfg["reduced"] == [] and cfg["deployment"]["family"] == "ouro"
    published = {
        "head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
        "num_hidden_layers": 48, "num_attention_heads": 16,
        "num_key_value_heads": 16, "vocab_size": 49152, "total_ut_steps": 4,
        "early_exit_threshold": 1, "rope_theta": 1000000,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "max_position_embeddings": 65536, "model_type": "ouro"}
    assert {k: hf[k] for k in published} == published
    assert hf["layer_types"] == ["full_attention"] * 48
    flags = cfg["deployment"]["server_flags"]
    assert (flags["max_batch_size"], flags["kv_pages"], flags["page_size"]) == (
        12, 300, 16)
    tiny = cfg["rehearsal"]["hf_overrides"]
    assert tiny["total_ut_steps"] == 3 and tiny["num_hidden_layers"] >= 2
    cell = manifest.resolve_cell("ouro-2.6b.eval-sat")
    assert cell.chips == 1 and cell.pair["clients"] == 12
    assert cell.pair["server_flags"]["max_model_len"] == 384
    mix = cell.traffic
    assert mix["loop"] == "closed" and mix["sampling"] == {"temperature": 0.0}
    assert (mix["prompt_len"]["min"], mix["prompt_len"]["max"]) == (48, 128)
    assert (mix["output_len"]["min"], mix["output_len"]["max"]) == (128, 256)
    # the worst case fits the pool: no request waits for a page
    assert 12 * (384 // 16) <= flags["kv_pages"] - 1
    assert {m["name"] for m in cell.end_to_end} == {
        "tpot_p50_ms", "itl_p99_ms", "output_tok_s", "setup_s"}


def test_check_child_reports_gaps_and_catches_a_wrong_token(tmp_path):
    """benchmark/reference/check.py, unedited, with `--family ouro`: it
    makes the weights (gate included) through LlamaConfig.from_hf_config and
    sharding.init_params_on_mesh, as for any family."""
    cfg = dict(TINY, torch_dtype="bfloat16")
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    prompt = np.random.RandomState(1).randint(0, 320, size=12).tolist()

    def run(probes):
        (tmp_path / "probes.json").write_text(json.dumps(probes))
        proc = subprocess.run(
            [sys.executable, os.path.join(BENCH, "reference", "check.py"),
             "--config", str(tmp_path / "config.json"), "--family", "ouro",
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

    ref = load_reference("ouro")
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
