"""The per-layer readers PR 29 adds, on hand-made runs: each finds what the
program publishes, and each returns nothing (and does not raise) for a
program or a configuration that has no such series, operation or size, as
the parent commit and the qwen3-4b cells do not."""

import json
import os

import pytest
from bench_paths import BENCH

from kbench import manifest, state_math

with open(os.path.join(BENCH, "configs", "phi4-mini-flash.json")) as _f:
    PHI = {k: v for k, v in json.load(_f).items() if k not in manifest.OWN_KEYS}
with open(os.path.join(BENCH, "configs", "qwen3-4b.json")) as _f:
    QWEN = {k: v for k, v in json.load(_f).items() if k not in manifest.OWN_KEYS}
PEAKS = manifest.load_peaks("TPU v5 lite")
NEW = ("ssm.update_share", "ssm.update_roofline", "attention.window_share",
       "attention.shared_kv_share", "cache.state_hbm_share")


def key(name, **labels):
    return (name, frozenset(labels.items()))


def phi_run():
    """A 4 s capture in which the state update took 0.090 s, beside a window
    of 51 s that held 100 dispatches with 48 lanes seated."""
    op_s = {
        "fusion_f32_48_5120_16_": 0.060,  # the decode step's scan
        "fusion_bf16_48_3_5120_": 0.030,  # its convolution tail
        "fusion_f32_64_5120_16_": 0.050,  # the packed step's blocked scan
        "window_attention_decode_bf16_48_40_128_": 0.200,
        "shared_kv_attention_decode_bf16_48_40_128_": 0.300,
        "sort_f32_48_200064_": 1.500,
        "fusion_bf16_48_5120_": 0.040,  # a projection's epilogue: not counted
    }
    before = {key("engine_decode_step_seconds_count", model_name="bench"): 10.0}
    after = {
        key("engine_decode_step_seconds_count", model_name="bench"): 110.0,
        key("engine_state_slots_in_use", model_name="bench"): 48.0,
        key("engine_state_bytes", model_name="bench", kind="shared_kv"): 150e6,
        key("engine_state_bytes", model_name="bench", kind="window_kv"): 1006e6,
        key("engine_state_bytes", model_name="bench", kind="ssm"): 141e6,
        key("engine_state_bytes", model_name="bench", kind="conv"): 13e6,
    }
    return {
        "cell": "phi4-mini-flash.reason-sat", "chips": 1, "seconds": 51.0,
        "hf_config": PHI, "flags": {"max_batch_size": 48, "page_size": 16,
                                    "kv_pages": 50000, "tp": 1},
        "trace": {"busy_s": 3.5, "window_s": 4.0, "op_s": op_s,
                  "opcode_s": {"fusion": 0.18, "custom-call": 0.5, "sort": 1.5}},
        "peaks": PEAKS, "before": before, "after": after,
    }


def read(name, run):
    return manifest.load_reader(name).read(run)


def test_bytes_a_state_update_must_move():
    # state 2 x 5120 x 16 x 4 + tail 2 x 3 x 5120 x 2; x, dt, B, C in; y out
    per_lane = 655360 + 61440 + (10240 + 20480 + 128) + 20480
    assert state_math.update_bytes_per_call(PHI, 1) == per_lane == 768128
    assert state_math.update_bytes_per_call(PHI, 48) == 48 * per_lane
    assert state_math.mamba_layers(PHI) == 9 and state_math.window_layers(PHI) == 8
    assert state_math.label_dims("fusion_f32_48_5120_16_") == [48, 5120, 16]
    assert state_math.label_dims("sort") is None
    assert state_math.is_state_update([48, 5120, 16], PHI, 48)
    assert not state_math.is_state_update([64, 5120, 16], PHI, 48)
    assert state_math.is_ssm([64, 5120, 16], PHI)
    assert state_math.is_ssm([48, 4, 5120], PHI)
    assert not state_math.is_ssm([48, 5120], PHI)


def test_readers_on_a_run_of_the_new_cell():
    run = phi_run()
    assert read("ssm.update_share", run) == pytest.approx(100 * 0.140 / 3.5)
    assert read("attention.window_share", run) == pytest.approx(100 * 0.2 / 3.5)
    assert read("attention.shared_kv_share", run) == pytest.approx(100 * 0.3 / 3.5)
    assert read("cache.state_hbm_share", run) == pytest.approx(
        100 * 1310e6 / 17179869184)
    # 100 dispatches in 51 s x 7 decode steps x 9 layers = 123.5 calls/s of
    # 36.9 MB over 0.0225 device seconds a second, against 819 GB/s
    calls = 100 / 51.0 * 7 * 9
    want = 100 * 48 * 768128 * calls / (0.090 / 4.0) / 819e9
    assert read("ssm.update_roofline", run) == pytest.approx(want)
    assert 20 < want < 30


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_returns_nothing(name):
    """The parent commit's program under the new cell's files, a qwen3-4b
    cell, and an untraced run: no such series, kernel or size."""
    parent = phi_run()
    parent["after"] = {k: v for k, v in parent["after"].items()
                       if not k[0].startswith("engine_state")}
    parent["trace"]["op_s"] = {"paged_attention_decode_bf16_48_40_64_": 0.3,
                               "sort_f32_48_200064_": 1.5}
    assert read(name, parent) is None
    qwen = phi_run()
    qwen.update(cell="qwen3-4b.decode-sat", hf_config=QWEN)
    qwen["trace"]["op_s"] = {"paged_attention_decode_bf16_48_32_128_": 0.3}
    qwen["after"] = {}
    assert read(name, qwen) is None
    untraced = dict(phi_run(), trace=None, peaks=None)
    assert read(name, untraced) is None
