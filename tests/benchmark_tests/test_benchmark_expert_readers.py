"""The per-layer readers PR 37 adds, on hand-made runs: each finds what the
program publishes, and each returns nothing (and does not raise) for a
program without its counter or kernel, as the parent commit is, for another
family's configuration, and for an untraced or chipless run where it needs
the trace or the peaks."""

import json
import os

import pytest
from bench_paths import BENCH

from kbench import expert_math, latent_math, manifest

with open(os.path.join(BENCH, "configs", "glm47-flash.json")) as _f:
    GLM = {k: v for k, v in json.load(_f).items() if k not in manifest.OWN_KEYS}
with open(os.path.join(BENCH, "configs", "qwen3-4b.json")) as _f:
    QWEN = {k: v for k, v in json.load(_f).items() if k not in manifest.OWN_KEYS}
PEAKS = manifest.load_peaks("TPU v5 lite")
NEW = ("attention.latent_share", "attention.latent_decode_roofline",
       "moe.experts_share", "moe.experts_roofline", "moe.rows_per_expert")
FLAGS = {"max_batch_size": 48, "page_size": 64, "kv_pages": 4600, "tp": 1,
         "max_prefill_len": 2048, "max_model_len": 3200}


def key(name, **labels):
    return (name, frozenset(labels.items()))


def glm_run():
    """A window of 51 s that held 170 dispatches of 8 forward steps: a packed
    step of ~1700 tokens and 7 decode steps over 48 lanes at ~2100 cached
    tokens; a 4 s capture."""
    dispatches = 170.0
    tokens = dispatches * (1700 + 7 * 48)
    context = dispatches * 7 * 48 * 2100.0
    hits = dispatches * 7 * (64 + 7 * 61)  # the packed step reaches all 64
    before = {
        key("engine_dispatches_total", model_name="bench", program="mixed"): 60.0,
        key("engine_kv_context_tokens_total", model_name="bench"): 5.0e7,
        key("engine_moe_assignments_total", model_name="bench"): 1.0e6,
        key("engine_moe_expert_hits_total", model_name="bench"): 1.0e4,
        key("engine_moe_peak_load_total", model_name="bench"): 2.0e4,
    }
    after = {
        key("engine_dispatches_total", model_name="bench", program="mixed"):
            60.0 + dispatches,
        key("engine_kv_context_tokens_total", model_name="bench"): 5.0e7 + context,
        key("engine_moe_assignments_total", model_name="bench"):
            1.0e6 + tokens * 4 * 7,
        key("engine_moe_expert_hits_total", model_name="bench"): 1.0e4 + hits,
        key("engine_moe_peak_load_total", model_name="bench"): 2.0e4 + 9.0e5,
    }
    op_s = {
        "latent_attention_decode_bf16_48_20_512_": 0.40,
        "latent_attention_ragged_bf16_2048_20_512_": 0.30,
        "ragged-dot-none_bf16_192_1536_": 0.90,
        "ragged-dot-none_f32_192_2048_": 0.50,
        "ragged-dot-none_bf16_8192_1536_": 0.20,
        "ragged-dot-metadata_s32_65_": 0.01,
        "fusion_s32_8192_65_": 0.02,  # the counting sort
        "fusion_bf16_192_2048_": 0.03,  # pairs' rows gathered
        "fusion_bf16_2048_4_2048_": 0.02,  # and weighed back
        "fusion_bf16_2048_2048_": 0.60,  # a dense part: not the experts'
        "fusion_bf16_48_10240_": 0.20,
    }
    return {
        "cell": "glm47-flash.agent-sat", "chips": 1, "seconds": 51.0,
        "hf_config": GLM, "flags": FLAGS,
        "trace": {"busy_s": 3.8, "window_s": 4.0, "op_s": op_s,
                  "opcode_s": {"fusion": 0.87, "custom-call": 2.31}},
        "peaks": PEAKS, "before": before, "after": after,
    }


def read(name, run):
    return manifest.load_reader(name).read(run)


def test_bytes_and_operations_from_the_configurations_sizes():
    assert latent_math.is_latent(GLM) and not latent_math.is_latent(QWEN)
    assert latent_math.row_values(GLM) == 576
    assert latent_math.row_bytes_stored(GLM) == 1280
    assert latent_math.token_bytes_stored(GLM) == 8 * 1280
    assert latent_math.context_read_bytes(GLM, 10) == 10 * 10240
    assert expert_math.has_experts(GLM) and not expert_math.has_experts(QWEN)
    assert expert_math.expert_layers(GLM) == 7
    assert expert_math.expert_bytes(GLM) == 3 * 2048 * 1536 * 2 == 18_874_368
    assert expert_math.pair_flops(GLM) == 6 * 2048 * 1536
    policy = manifest.resolve_cell("glm47-flash.agent-sat").deployment["engine_policy"]
    rows = expert_math.pair_rows(FLAGS, policy, GLM)
    # 4 x 512 = 2048 is a buffer's own length and cannot be told apart
    assert {192, 4096, 8192} <= rows and 2048 not in rows
    assert expert_math.is_routing_op("fusion_s32_8192_65_", rows, GLM)
    assert expert_math.is_routing_op("fusion_bf16_2048_4_2048_", rows, GLM)
    assert expert_math.is_routing_op("fusion_s32_192_", rows, GLM)
    assert not expert_math.is_routing_op("fusion_bf16_2048_2048_", rows, GLM)
    assert not expert_math.is_routing_op("fusion_bf16_48_154880_", rows, GLM)
    assert expert_math.is_grouped_matmul("ragged-dot-none_bf16_192_1536_")
    assert not expert_math.is_grouped_matmul("fusion_bf16_192_1536_")


def test_each_reader_on_a_recorded_run():
    run = glm_run()
    assert read("attention.latent_share", run) == pytest.approx(100 * 0.40 / 3.8)
    must_read = 170 * 7 * 48 * 2100.0 * 10240 / 51.0
    assert read("attention.latent_decode_roofline", run) == pytest.approx(
        100 * must_read / (0.40 / 4.0) / 819e9)
    assert read("moe.experts_share", run) == pytest.approx(
        100 * (0.90 + 0.50 + 0.20 + 0.01 + 0.02 + 0.03 + 0.02) / 3.8)
    hits = 170 * 7 * (64 + 7 * 61)
    pairs = 170 * (1700 + 7 * 48) * 4 * 7
    least = max(hits * 18_874_368 / 819e9, pairs * 6 * 2048 * 1536 / 197e12)
    assert least == hits * 18_874_368 / 819e9  # bytes bind over the window
    share = read("moe.experts_roofline", run)
    assert share == pytest.approx(100 * least / 51.0 / (1.61 / 4.0))
    assert 0 < share < 100
    assert read("moe.rows_per_expert", run) == pytest.approx(pairs / hits)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_and_does_not_raise(name):
    """The parent's program: no expert counters, no latent kernel in its
    trace; another family's configuration; an untraced run; no peaks."""
    run = glm_run()
    parent = dict(run, before={k: v for k, v in run["before"].items()
                               if "moe" not in k[0]},
                  after={k: v for k, v in run["after"].items()
                         if "moe" not in k[0]},
                  trace=dict(run["trace"], op_s={
                      "fusion_bf16_2048_2048_": 0.6,
                      "paged_attention_decode_bf16_48_32_128_": 0.4}))
    assert read(name, parent) is None
    if name == "moe.rows_per_expert":  # counters alone: a dense model's stay 0
        assert read(name, dict(run, after=run["before"])) is None
    else:
        assert read(name, dict(run, hf_config=QWEN, cell="qwen3-4b.decode-sat")) is None
        assert read(name, dict(run, trace=None)) is None
    if "roofline" in name:
        assert read(name, dict(run, peaks=None)) is None
