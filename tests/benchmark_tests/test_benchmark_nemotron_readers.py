"""The per-layer readers PR 41 adds, on the operation table of a recorded
trace of the cell (recorded_nemotron_ops.json: the first run on the chip)
and hand-made counters: each finds what the program publishes, and each
returns nothing (and does not raise) for a program without its counter or
operations, as the parent commit is, for another family's configuration,
and for an untraced or chipless run where it needs the trace or the peaks."""

import json
import os

import pytest
from bench_paths import BENCH

from kbench import manifest, nemotron_math, state_math

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(BENCH, "configs", "nemotron3-nano.json")) as _f:
    NEMOTRON = {k: v for k, v in json.load(_f).items() if k not in manifest.OWN_KEYS}
with open(os.path.join(BENCH, "configs", "glm47-flash.json")) as _f:
    GLM = {k: v for k, v in json.load(_f).items() if k not in manifest.OWN_KEYS}
with open(os.path.join(HERE, "recorded_nemotron_ops.json")) as _f:
    RECORDED = json.load(_f)
PEAKS = manifest.load_peaks("TPU v5 lite")
NEW = ("ssd.share", "ssd.update_roofline", "ssd.chunk_roofline",
       "moe.held_experts_roofline", "moe.held_pair_share")
FLAGS = {"max_batch_size": 48, "page_size": 64, "kv_pages": 5600, "tp": 1,
         "max_prefill_len": 2048, "max_model_len": 5120}
CELL = "nemotron3-nano.agent-long-sat"
#: pairs routed in 75 dispatches: 6 a token, 6 expert layers over every
#: token and the closing one over the sampled rows
ROUTED = 75.0 * 6 * (6 * (1700 + 7 * 48) + (48 + 7 * 48))


def key(name, **labels):
    return (name, frozenset(labels.items()))


def nemotron_run():
    """A window of 51 s that held 75 dispatches of 8 forward steps: a packed
    step of ~1700 tokens and 7 decode steps over 48 lanes, 7 Mamba-2 and 7
    expert layers, about half the routed pairs on the 64 experts held.  The
    closing expert layer lies behind the last Mamba-2 layer and sees one
    row a lane in the packed step."""
    dispatches = 75.0
    routed = ROUTED
    here = 0.52 * routed
    hits = dispatches * 7 * (64 + 7 * 57)
    names = {
        "engine_dispatches_total": (60.0, dispatches, dict(program="mixed")),
        "engine_moe_assignments_total": (1.0e6, here, {}),
        "engine_moe_pairs_elsewhere_total": (1.1e6, routed - here, {}),
        "engine_moe_expert_hits_total": (1.0e4, hits, {}),
        "engine_ssd_scan_tokens_total": (5.0e5, dispatches * 1700 * 7, {}),
        "engine_ssd_update_calls_total": (3.0e3, dispatches * 49, {}),
        "engine_ssd_update_lane_steps_total": (1.0e5, dispatches * 49 * 48, {}),
    }
    before = {key(n, model_name="bench", **ls): v for n, (v, _, ls) in names.items()}
    after = {key(n, model_name="bench", **ls): v + d for n, (v, d, ls) in names.items()}
    after[key("engine_state_slots_in_use", model_name="bench")] = 48.0
    return {
        "cell": CELL, "chips": 1, "seconds": 51.0, "hf_config": NEMOTRON,
        "flags": FLAGS, "peaks": PEAKS, "before": before, "after": after,
        "trace": {"busy_s": RECORDED["busy_s"], "window_s": RECORDED["window_s"],
                  "op_s": dict(RECORDED["op_s"])},
    }


def read(name, run):
    return manifest.load_reader(name).read(run)


def seconds(pick):
    return sum(s for label, s in RECORDED["op_s"].items()
               if pick(state_math.label_dims(label) or []))


def test_bytes_and_operations_from_the_configurations_sizes():
    assert nemotron_math.is_nemotron_h(NEMOTRON) and not nemotron_math.is_nemotron_h(GLM)
    assert nemotron_math.sizes(NEMOTRON) == {
        "H": 64, "P": 64, "G": 8, "N": 128, "K": 4, "inner": 4096, "conv": 6144}
    assert nemotron_math.state_bytes(NEMOTRON) == 2_097_152 + 36_864
    # 2 x (state + tail) + xBC and dt in + y out: 4.30 MB a lane and layer
    assert nemotron_math.update_bytes_per_lane(NEMOTRON) == (
        2 * 2_134_016 + (6144 + 64) * 2 + 4096 * 4) == 4_296_832
    assert nemotron_math.scan_bytes_per_token(NEMOTRON) == (6144 + 64) * 2 + 4096 * 4
    assert nemotron_math.scan_flops_per_token(NEMOTRON) == 4 * 64 * 64 * 128
    # two matrices an expert, not three (kbench/expert_math.py counts gated ones)
    assert nemotron_math.held_expert_bytes(NEMOTRON) == 2 * 2688 * 1856 * 2 == 19_955_712
    assert nemotron_math.held_pair_flops(NEMOTRON) == 4 * 2688 * 1856


@pytest.mark.parametrize("label, update, scan, conv", [
    ("fusion_f32_48_64_64_128_", True, False, False),  # the decode step's state
    ("fusion_bf16_48_3_6144_", True, False, True),  # its tail
    ("pad_f32_32_64_64_128_", False, True, False),  # chunk-end states
    ("slice_add_fusion_f32_31_64_64_128_", False, True, False),
    ("fusion_f32_48_8_8_64_128_", False, True, False),  # a lane's window sum
    ("fusion_f32_32_8_8_64_64_", False, True, False),  # decay masks
    ("copy_f32_48_64_8_8_64_", False, True, False),  # a lane's window
    ("fusion_f32_2048_64_64_", False, True, False),  # the scan's output
    ("divide_multiply_fusion_f32_2048_6144_", False, False, True),
    ("fusion_f32_6144_48_", False, False, True),
    ("ragged-dot-none_bf16_288_1920_", False, False, False),
    ("fusion_bf16_2048_10304_", False, False, False),  # in_proj: a dense part
    ("paged_attention_decode_bf16_48_32_128_", False, False, False),
    ("convert_divide_fusion_f32_48_131072_", False, False, False),
])
def test_the_mixers_operations_are_told_by_what_they_produce(label, update, scan, conv):
    dims = state_math.label_dims(label)
    assert nemotron_math.is_update(dims, NEMOTRON, 48) is update
    assert nemotron_math.is_chunk_scan(dims, NEMOTRON, 48) is scan
    assert nemotron_math.is_conv(dims, NEMOTRON) is conv
    assert nemotron_math.is_ssd(dims, NEMOTRON, 48) is (update or scan or conv)
    assert label in RECORDED["op_s"]


def test_each_reader_on_the_recorded_trace():
    run = nemotron_run()
    ssd = seconds(lambda d: nemotron_math.is_ssd(d, NEMOTRON, 48))
    assert read("ssd.share", run) == pytest.approx(100 * ssd / RECORDED["busy_s"])
    assert 5 < read("ssd.share", run) < 20
    update = seconds(lambda d: nemotron_math.is_update(d, NEMOTRON, 48))
    # the decode updates' bytes and the packed steps' one write of what the
    # 48 lanes keep: the seconds are both steps' operations of that shape
    kept = 75 * 7 * 48 * 2_134_016
    assert nemotron_math.packed_state_pass_bytes(NEMOTRON, 75, 48) == kept
    must = (75 * 49 * 48 * 4_296_832 + kept) / 51.0
    share = read("ssd.update_roofline", run)
    assert share == pytest.approx(
        100 * must / (update / RECORDED["window_s"]) / 819e9)
    assert 0 < share < 100
    scan = seconds(lambda d: nemotron_math.is_chunk_scan(d, NEMOTRON, 48))
    tokens = 75 * 1700 * 7
    bytes_ = tokens * 28_800 + kept  # and their one read of it
    assert bytes_ / 819e9 > tokens * 4 * 64 * 64 * 128 / 197e12  # bytes bind
    share = read("ssd.chunk_roofline", run)
    assert share == pytest.approx(
        100 * bytes_ / 819e9 / 51.0 / (scan / RECORDED["window_s"]))
    assert 0 < share < 100
    matmul = sum(s for label, s in RECORDED["op_s"].items()
                 if label.startswith("ragged-dot"))
    hits = 75 * 7 * (64 + 7 * 57)
    pairs = 0.52 * ROUTED
    least = max(hits * 19_955_712 / 819e9, pairs * 4 * 2688 * 1856 / 197e12)
    share = read("moe.held_experts_roofline", run)
    assert share == pytest.approx(
        100 * least / 51.0 / (matmul / RECORDED["window_s"]))
    assert 0 < share < 100
    assert read("moe.held_pair_share", run) == pytest.approx(52.0)
    # the accepted readers the cell is appended to find their operations too
    assert 50 < read("moe.experts_share", run) < 100
    assert read("moe.rows_per_expert", run) == pytest.approx(pairs / hits)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_and_does_not_raise(name):
    """The parent's program: no share or Mamba-2 counters, none of the
    mixers' operations in its trace; another family's configuration; an
    untraced run; no peaks."""
    run = nemotron_run()
    gone = ("engine_ssd", "engine_moe")
    parent = dict(
        run,
        before={k: v for k, v in run["before"].items() if not k[0].startswith(gone)},
        after={k: v for k, v in run["after"].items() if not k[0].startswith(gone)},
        trace=dict(run["trace"], op_s={
            "fusion_bf16_2048_2048_": 0.6,
            "paged_attention_decode_bf16_48_32_128_": 0.4}))
    assert read(name, parent) is None
    if name == "moe.held_pair_share":  # counters alone
        assert read(name, dict(run, after=run["before"])) is None
    else:
        assert read(name, dict(run, hf_config=GLM, cell="glm47-flash.agent-sat")) is None
        assert read(name, dict(run, trace=None)) is None
    if "roofline" in name:
        assert read(name, dict(run, peaks=None)) is None
