"""The per-layer readers PR 49 adds, on the operation table of a recorded
trace of the cell (recorded_solar_ops.json: the final tree's traced run on
the chip) and hand-made counters: each finds what the program publishes, and
each returns nothing (and does not raise) for a program without its counter
or operations, as the parent commit is, for another family's configuration,
and for an untraced or chipless run where it needs the trace or the peaks.
The accepted expert readers the cell is appended to read this family as
they stand."""

import json
import os

import pytest
from bench_paths import BENCH

from kbench import delta_math, manifest

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(BENCH, "configs", "solar-open2.json")) as _f:
    SOLAR = {k: v for k, v in json.load(_f).items() if k not in manifest.OWN_KEYS}
with open(os.path.join(BENCH, "configs", "nemotron3-nano.json")) as _f:
    NEMOTRON = {k: v for k, v in json.load(_f).items() if k not in manifest.OWN_KEYS}
with open(os.path.join(HERE, "recorded_solar_ops.json")) as _f:
    RECORDED = json.load(_f)
with open(os.path.join(HERE, "recorded_nemotron_ops.json")) as _f:
    OTHER_FAMILY = json.load(_f)
PEAKS = manifest.load_peaks("TPU v5 lite")
NEW = ("kda.share", "kda.update_roofline", "kda.chunk_roofline")
FLAGS = {"max_batch_size": 48, "page_size": 64, "kv_pages": 6528, "tp": 1,
         "max_prefill_len": 4096, "max_model_len": 8192}
CELL = "solar-open2.long-doc-sat"
DISPATCHES, PACKED = 190.0, 3383
#: pairs routed in 190 dispatches: 8 a token in 4 expert layers over every
#: packed token and every decode lane-step; an eighth of the experts held
ROUTED = DISPATCHES * 8 * 4 * (PACKED + 7 * 48)


def key(name, **labels):
    return (name, frozenset(labels.items()))


def solar_run():
    """A window of 51 s that held 190 dispatches of 8 forward steps: a
    packed step of ~3383 tokens and 7 decode steps over 48 lanes, 3 KDA
    layers, an eighth of the routed pairs on the 40 experts held."""
    here = 0.125 * ROUTED
    hits = DISPATCHES * 4 * (40 + 7 * 28)
    names = {
        "engine_dispatches_total": (60.0, DISPATCHES, dict(program="mixed")),
        "engine_moe_assignments_total": (1.0e6, here, {}),
        "engine_moe_pairs_elsewhere_total": (7.0e6, ROUTED - here, {}),
        "engine_moe_expert_hits_total": (1.0e4, hits, {}),
        "engine_kda_chunk_tokens_total": (5.0e5, DISPATCHES * PACKED * 3, {}),
        "engine_kda_update_lane_steps_total": (1.0e5, DISPATCHES * 7 * 48 * 3, {}),
    }
    before = {key(n, model_name="bench", **ls): v for n, (v, _, ls) in names.items()}
    after = {key(n, model_name="bench", **ls): v + d for n, (v, d, ls) in names.items()}
    after[key("engine_state_slots_in_use", model_name="bench")] = 48.0
    return {
        "cell": CELL, "chips": 1, "seconds": 51.0, "hf_config": SOLAR,
        "flags": FLAGS, "peaks": PEAKS, "before": before, "after": after,
        "trace": {"busy_s": RECORDED["busy_s"], "window_s": RECORDED["window_s"],
                  "op_s": dict(RECORDED["op_s"])},
    }


def read(name, run):
    return manifest.load_reader(name).read(run)


def seconds(pick, table=RECORDED):
    return delta_math.seconds_of(
        table, lambda dims, dtype: pick(dims, SOLAR, 48, dtype))


def test_bytes_and_operations_from_the_configurations_sizes():
    assert delta_math.is_solar_open2(SOLAR) and not delta_math.is_solar_open2(NEMOTRON)
    assert delta_math.sizes(SOLAR) == {
        "H": 64, "d": 128, "K": 4, "inner": 8192, "conv": 24576}
    assert delta_math.kda_layers(SOLAR) == 3
    assert delta_math.state_bytes(SOLAR) == 4_194_304 + 147_456
    # q, k, v, the decay and beta in (bf16), the output out (float32)
    assert delta_math.token_bytes(SOLAR) == (24576 + 8192 + 64) * 2 + 8192 * 4 == 98_432
    # 2 x (state + tail) + a token's inputs and output: 8.78 MB a lane and layer
    assert delta_math.update_bytes_per_lane(SOLAR) == 2 * 4_341_760 + 98_432 == 8_781_952
    assert delta_math.chunk_flops_per_token(SOLAR) == 6 * 64 * 128 * 128
    assert delta_math.packed_state_pass_bytes(SOLAR, 10, 48) == 10 * 3 * 48 * 4_341_760
    assert delta_math.label_shape("multiply_reduce_fusion_f32_48_64_128_") == (
        "f32", [48, 64, 128])
    assert delta_math.label_shape("while") == (None, [])


@pytest.mark.parametrize("label, update, chunk, conv", [
    ("fusion_f32_48_64_128_128_", True, False, False),  # the decode step's state
    ("broadcast_select_fusion_f32_48_64_128_128_", True, False, False),
    ("multiply_reduce_fusion_f32_48_64_128_", True, False, False),  # S^T k, S^T q
    ("fusion_bf16_48_3_24576_", True, False, True),  # the tail
    ("convert_divide_fusion_f32_48_24576_", False, False, True),
    ("fusion_f32_4096_24576_", False, False, True),  # the packed convolution
    ("fusion_f32_64_2_32_2_32_", False, True, False),  # a level of the inverse
    ("convolution_negate_fusion_f32_64_8_4_4_", False, True, False),
    ("multiply_reduce_fusion_f32_2_4_16_16_64_", False, True, False),  # differences
    ("exponential_multiply_fusion_f32_4_16_16_64_128_", False, True, False),
    ("fusion_f32_64_64_256_", False, True, False),  # [U | W] of a piece
    ("constant_dynamic-slice_fusion_f32_1_64_128_128_", False, True, False),  # S_in
    ("constant_dynamic-slice_fusion_f32_64_64_128_", False, True, False),  # a window
    ("dynamic_update_slice_f32_4096_64_128_", False, True, False),  # its output
    ("multiply_multiply_fusion_f32_4096_64_128_", False, True, False),
    ("ragged_paged_attention_bf16_4096_64_128_", False, False, False),
    ("paged_attention_decode_bf16_48_64_128_", False, False, False),
    ("ragged-dot-none_bf16_384_1536_", False, False, False),
    ("fusion_bf16_4096_8192_", False, False, False),  # a projection
    ("copy_f32_512_8_64_128_", False, False, False),  # the attention row's blocks
    ("sort_f32_4096_320_", False, False, False),
])
def test_the_mixers_operations_are_told_by_what_they_produce(label, update, chunk, conv):
    dtype, dims = delta_math.label_shape(label)
    assert delta_math.is_update(dims, SOLAR, 48, dtype) is update
    assert delta_math.is_chunk(dims, SOLAR, 48, dtype) is chunk
    assert delta_math.is_conv(dims, SOLAR) is conv
    assert delta_math.is_kda(dims, SOLAR, 48, dtype) is (update or chunk or conv)
    assert label in RECORDED["op_s"]


def test_each_reader_on_the_recorded_trace():
    run = solar_run()
    kda = seconds(delta_math.is_kda)
    assert read("kda.share", run) == pytest.approx(100 * kda / RECORDED["busy_s"])
    assert 35 < read("kda.share", run) < 60
    update = seconds(delta_math.is_update)
    # the decode updates' bytes and the packed steps' one write of what the
    # 48 lanes keep: the seconds are both steps' operations of those shapes
    kept = 190 * 3 * 48 * 4_341_760
    must = (190 * 7 * 48 * 3 * 8_781_952 + kept) / 51.0
    share = read("kda.update_roofline", run)
    assert share == pytest.approx(
        100 * must / (update / RECORDED["window_s"]) / 819e9)
    assert 20 < share < 100
    chunk = seconds(delta_math.is_chunk)
    tokens = 190 * 3383 * 3
    bytes_ = tokens * 98_432 + kept  # and their one read of it
    assert bytes_ / 819e9 > tokens * 6 * 64 * 128 * 128 / 197e12  # bytes bind
    share = read("kda.chunk_roofline", run)
    assert share == pytest.approx(
        100 * bytes_ / 819e9 / 51.0 / (chunk / RECORDED["window_s"]))
    assert 0 < share < 100
    # no second is counted twice between the two rooflines
    both = delta_math.seconds_of(
        RECORDED, lambda d, t: delta_math.is_update(d, SOLAR, 48, t)
        and delta_math.is_chunk(d, SOLAR, 48, t))
    assert both == 0.0


def test_the_accepted_expert_readers_read_this_family_as_they_stand():
    """`moe.experts_roofline` keys on `n_routed_experts` (the 40 HELD) and
    `moe_intermediate_size` (the published 1280, not the 1536 stored), and
    its operations bound takes `engine_moe_assignments_total`, which under
    a share is the program's own count of the pairs it multiplied: pairs
    routed to experts held elsewhere are not in it."""
    run = solar_run()
    matmul = sum(s for label, s in RECORDED["op_s"].items()
                 if label.startswith("ragged-dot"))
    hits = 190 * 4 * (40 + 7 * 28)
    pairs = 0.125 * ROUTED
    least = max(hits * 3 * 4096 * 1280 * 2 / 819e9,
                pairs * 6 * 4096 * 1280 / 197e12)
    share = read("moe.experts_roofline", run)
    assert share == pytest.approx(
        100 * least / 51.0 / (matmul / RECORDED["window_s"]))
    assert 0 < share < 100
    # where operations bind (few experts hit, each by many rows) the bound
    # takes the pairs THIS chip multiplied, an eighth of those routed
    few = dict(run, after=dict(run["after"]))
    hit_key = key("engine_moe_expert_hits_total", model_name="bench")
    few["after"][hit_key] = run["before"][hit_key] + 100.0
    assert read("moe.experts_roofline", few) == pytest.approx(
        100 * (ROUTED / 8) * 6 * 4096 * 1280 / 197e12 / 51.0
        / (matmul / RECORDED["window_s"]))
    assert read("moe.held_pair_share", run) == pytest.approx(12.5)
    assert read("moe.rows_per_expert", run) == pytest.approx(pairs / hits)
    assert 20 < read("moe.experts_share", run) < 60


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_and_does_not_raise(name):
    """The parent's program: no Kimi-delta counters, none of the mixers'
    operations in its trace; another family's configuration under its own
    recorded trace; an untraced run; no peaks."""
    run = solar_run()
    parent = dict(
        run,
        before={k: v for k, v in run["before"].items() if "kda" not in k[0]},
        after={k: v for k, v in run["after"].items() if "kda" not in k[0]},
        trace=dict(run["trace"], op_s={
            "fusion_bf16_4096_4096_": 0.6,
            "paged_attention_decode_bf16_48_64_128_": 0.4}))
    assert read(name, parent) is None
    other = dict(run, hf_config=NEMOTRON, cell="nemotron3-nano.agent-long-sat",
                 trace=dict(OTHER_FAMILY))
    assert read(name, other) is None
    assert read(name, dict(run, trace=None)) is None
    assert read(name, dict(run, trace={"busy_s": 0.0, "window_s": 0.0, "op_s": {}})) is None
    if "roofline" in name:
        assert read(name, dict(run, peaks=None)) is None
        # the counters there and the operations not: nothing to divide by
        assert read(name, dict(run, trace=dict(run["trace"], op_s={
            "fusion_bf16_4096_4096_": 1.0}))) is None
