"""The per-layer readers PR 35 adds, on hand-made runs: each finds what the
program publishes, and each returns nothing (and does not raise) for a
program without its counter, as the parent commit is, and for an untraced
or chipless run where it needs the trace or the peaks."""

import json
import os

import pytest
from bench_paths import BENCH

from kbench import loop_math, manifest

with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as _f:
    OURO = {k: v for k, v in json.load(_f).items() if k not in manifest.OWN_KEYS}
with open(os.path.join(BENCH, "configs", "qwen3-4b.json")) as _f:
    QWEN = {k: v for k, v in json.load(_f).items() if k not in manifest.OWN_KEYS}
PEAKS = manifest.load_peaks("TPU v5 lite")
NEW = ("loop.passes_per_step", "model.weight_stream_roofline",
       "attention.decode_roofline", "cache.pool_fill_share")


def key(name, **labels):
    return (name, frozenset(labels.items()))


def ouro_run():
    """A window of 51 s that held 100 dispatches of 8 forward steps, each of
    4 passes, 7 of them decode steps over 12 lanes at ~200 cached tokens;
    a 4 s capture in which the decode kernel took 0.8 s."""
    before = {
        key("engine_dispatches_total", model_name="bench", program="mixed"): 40.0,
        key("engine_layer_passes_total", model_name="bench"): 1280.0,
        key("engine_kv_context_tokens_total", model_name="bench"): 1.0e6,
        key("engine_kv_pages_total", model_name="bench"): 299.0,
        key("engine_kv_pages_free", model_name="bench"): 59.8,
    }
    after = {
        key("engine_dispatches_total", model_name="bench", program="mixed"): 140.0,
        key("engine_layer_passes_total", model_name="bench"): 1280.0 + 3200.0,
        key("engine_kv_context_tokens_total", model_name="bench"): 1.0e6 + 1.68e6,
        key("engine_kv_pages_total", model_name="bench"): 299.0,
        key("engine_kv_pages_free", model_name="bench"): 0.0,
    }
    op_s = {"paged_attention_decode_bf16_12_16_128_": 0.8,
            "ragged_paged_attention_bf16_128_16_128_": 0.1,
            "fusion_bf16_12_5632_": 1.5}
    return {
        "cell": "ouro-2.6b.eval-sat", "chips": 1, "seconds": 51.0,
        "hf_config": OURO, "flags": {"max_batch_size": 12, "page_size": 16,
                                     "kv_pages": 300, "tp": 1},
        "trace": {"busy_s": 3.6, "window_s": 4.0, "op_s": op_s,
                  "opcode_s": {"fusion": 2.0, "custom-call": 0.9}},
        "peaks": PEAKS, "before": before, "after": after,
    }


def read(name, run):
    return manifest.load_reader(name).read(run)


def test_bytes_a_looped_step_must_stream_and_read():
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert loop_math.layer_params(OURO) == layer == 51_388_416
    assert loop_math.stack_bytes(OURO) == 48 * layer * 2
    assert loop_math.head_bytes(OURO) == 49152 * 2048 * 2
    assert loop_math.passes(OURO) == 4 and loop_math.passes(QWEN) == 1
    assert loop_math.step_stream_bytes(OURO) == (
        4 * 48 * layer * 2 + 49152 * 2048 * 2)
    assert 19.9e9 < loop_math.step_stream_bytes(OURO) < 20.0e9
    assert loop_math.cache_rows(OURO) == 192 and loop_math.cache_rows(QWEN) == 36
    assert loop_math.row_token_bytes(OURO) == 8192  # K and V, 16 heads x 128, bf16
    assert loop_math.row_token_bytes(OURO) * loop_math.cache_rows(OURO) == 1572864
    assert loop_math.context_read_bytes(OURO, 10) == 10 * 1572864
    # a one-pass model with two norms a layer: Qwen3-4B's 36 layers
    assert loop_math.layer_params(QWEN) == (
        2560 * (32 + 16) * 128 + 32 * 128 * 2560 + 3 * 2560 * 9728 + 2 * 2560)
    assert loop_math.has_series({key("a_total", m="x"): 0.0}, "a_total")
    assert not loop_math.has_series({key("a_total_created"): 1.0}, "a_total")


def test_readers_on_a_run_of_the_new_cell():
    run = ouro_run()
    assert read("loop.passes_per_step", run) == pytest.approx(4.0)
    # 800 forward steps in 51 s, each 19.94 GB, against 819 GB/s
    want = 100 * 800 * loop_math.step_stream_bytes(OURO) / 51.0 / 819e9
    assert read("model.weight_stream_roofline", run) == pytest.approx(want)
    assert 35 < want < 40
    # 1.68 M row-tokens x 1.5 MiB in 51 s, over 0.2 device seconds a second
    want = 100 * (1.68e6 * 1572864 / 51.0) / (0.8 / 4.0) / 819e9
    assert read("attention.decode_roofline", run) == pytest.approx(want)
    assert 25 < want < 40
    assert read("cache.pool_fill_share", run) == pytest.approx(
        100 * (0.8 + 1.0) / 2)


def test_a_skipped_pass_shows():
    run = ouro_run()
    run["after"][key("engine_layer_passes_total", model_name="bench")] -= 800.0
    assert read("loop.passes_per_step", run) == pytest.approx(3.0)


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_returns_nothing(name):
    """The parent commit's program under the new cell's files: it has
    `engine_dispatches_total` and `engine_kv_pages_free`, and none of this
    PR's series."""
    parent = ouro_run()
    for side in ("before", "after"):
        parent[side] = {
            k: v for k, v in parent[side].items()
            if k[0] in ("engine_dispatches_total", "engine_kv_pages_free")}
    assert read(name, parent) is None
    empty = dict(ouro_run(), before={}, after={})
    assert read(name, empty) is None


@pytest.mark.parametrize("name", [
    "model.weight_stream_roofline", "attention.decode_roofline"])
def test_no_chip_no_share_of_a_peak(name):
    assert read(name, dict(ouro_run(), trace=None, peaks=None)) is None


def test_decode_roofline_needs_its_kernel_in_the_trace():
    run = ouro_run()
    run["trace"]["op_s"] = {"ragged_paged_attention_bf16_128_16_128_": 0.1}
    assert read("attention.decode_roofline", run) is None
