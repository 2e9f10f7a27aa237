"""The per-layer readers PR 54 adds, on the operation table of a recorded
trace of the cell (recorded_lfm2_ops.json: the traced run of the cell's
first chip run, PR 54) and hand-made counters: each finds what the program
publishes, and each returns nothing (and does not raise) for a program
without its counters or operations, as the parent commit is, for another
family's configuration, and for an untraced or chipless run where it needs
the trace or the peaks."""

import json
import os

import pytest
from bench_paths import BENCH

from kbench import conv_math, manifest

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(BENCH, "configs", "lfm2-24b-a2b.json")) as _f:
    LFM2 = {k: v for k, v in json.load(_f).items() if k not in manifest.OWN_KEYS}
with open(os.path.join(BENCH, "configs", "solar-open2.json")) as _f:
    SOLAR = {k: v for k, v in json.load(_f).items() if k not in manifest.OWN_KEYS}
with open(os.path.join(HERE, "recorded_lfm2_ops.json")) as _f:
    RECORDED = json.load(_f)
with open(os.path.join(HERE, "recorded_solar_ops.json")) as _f:
    OTHER_FAMILY = json.load(_f)
PEAKS = manifest.load_peaks("TPU v5 lite")
NEW = ("conv.share", "conv.packed_roofline")
FLAGS = {"max_batch_size": 48, "page_size": 64, "kv_pages": 6528, "tp": 1,
         "max_prefill_len": 4096, "max_model_len": 8192}
CELL = "lfm2-24b-a2b.extract-sat"
DISPATCHES, PACKED, SLICES = 362.0, 4098, 17


def key(name, **labels):
    return (name, frozenset(labels.items()))


def lfm2_run():
    """A window of 51 s that held 362 dispatches of 8 forward steps: a
    packed step of ~4098 tokens in 17 slices (15 decode lanes and two prompt
    chunks) and 7 decode steps over ~15 live lanes, 6 short-conv layers."""
    names = {
        "engine_dispatches_total": (60.0, DISPATCHES, dict(program="mixed")),
        "engine_conv_packed_tokens_total": (5.0e5, DISPATCHES * PACKED * 6, {}),
        "engine_conv_update_lane_steps_total": (1.0e5, DISPATCHES * 7 * 15 * 6, {}),
        "engine_packed_lanes_total": (
            900.0, DISPATCHES * 15, dict(attention_path="decode_kernel")),
    }
    before = {key(n, model_name="bench", **ls): v for n, (v, _, ls) in names.items()}
    after = {key(n, model_name="bench", **ls): v + d for n, (v, d, ls) in names.items()}
    ragged = key("engine_packed_lanes_total", model_name="bench",
                 attention_path="ragged")
    before[ragged], after[ragged] = 100.0, 100.0 + DISPATCHES * 2
    return {
        "cell": CELL, "chips": 1, "seconds": 51.0, "hf_config": LFM2,
        "flags": FLAGS, "peaks": PEAKS, "before": before, "after": after,
        "trace": {"busy_s": RECORDED["busy_s"], "window_s": RECORDED["window_s"],
                  "op_s": dict(RECORDED["op_s"])},
    }


def read(name, run):
    return manifest.load_reader(name).read(run)


def seconds(pick, table=RECORDED):
    return conv_math.seconds_of(table, pick)


def taps(dims, dtype):
    return conv_math.is_taps(dims, dtype, LFM2, 48, 4096)


def packed_taps(dims, dtype):
    return conv_math.is_packed_taps(dims, dtype, LFM2, 48, 4096)


def test_bytes_from_the_configurations_sizes():
    assert conv_math.is_lfm2_moe(LFM2) and not conv_math.is_lfm2_moe(SOLAR)
    assert conv_math.sizes(LFM2) == {"hidden": 2048, "in": 6144, "K": 3}
    assert conv_math.conv_layers(LFM2) == 6
    assert conv_math.tail_bytes(LFM2) == 2 * 2048 * 2
    # B and x in, the convolution's row out, 2 B a value
    assert conv_math.taps_token_bytes(LFM2) == 3 * 2048 * 2 == 12_288
    assert conv_math.packed_bytes(LFM2, 1000, 10) == (
        1000 * 12_288 + 10 * 6 * 2 * 8192)
    assert conv_math.label_shape("multiply_add_fusion_f32_4096_2048_") == (
        "f32", [4096, 2048])
    assert conv_math.label_shape("while") == (None, [])


@pytest.mark.parametrize("label, in_proj, own, packed", [
    ("fusion_bf16_4096_6144_", True, False, False),  # the packed step's in_proj
    ("fusion_bf16_48_6144_", True, False, False),  # a decode step's
    ("copy-done_bf16_4096_6144_", True, False, False),
    ("multiply_add_fusion_f32_4096_2048_", False, True, True),  # the shifted sum
    ("fusion_f32_4096_2048_", False, True, True),  # the opening rows put in
    ("fusion_f32_2048_48_", False, True, False),  # a decode step's taps
    ("broadcast_select_fusion_bf16_48_2_2048_", False, True, True),  # the tails
    ("copy_bf16_48_2_2048_", False, True, True),
    ("fusion_bf16_96_2048_", False, True, True),  # two rows a lane, gathered
    ("fusion_f32_48_2_2048_", False, True, True),
    ("fusion_bf16_48_1_2048_", False, True, True),
    # the experts' float32 rows: tokens x experts a token, past the ladder
    ("ragged-dot-none_f32_16384_2048_", False, False, False),
    ("fusion_f32_16384_2048_", False, False, False),
    ("ragged-dot-none_f32_192_2048_", False, False, False),
    ("fusion_f32_192_2048_", False, False, False),
    ("reshape_f32_4096_4_2048_", False, False, False),
    ("fusion_f32_48_2048_", False, False, False),  # a decode step's row a lane
    # bf16 [tokens, hidden]: norms, residuals, projections, the experts' sum
    ("multiply_reduce_fusion_bf16_4096_2048_", False, False, False),
    ("multiply_convert_fusion_bf16_4096_2048_", False, False, False),
    ("fusion_bf16_4096_2048_", False, False, False),
    ("copy_bf16_4096_2048_", False, False, False),
    ("fusion_bf16_4096_11776_", False, False, False),  # the dense rows' MLP
    ("ragged_paged_attention_bf16_4096_32_128_", False, False, False),
    ("paged_attention_decode_bf16_48_32_128_", False, False, False),
    ("kv_page_write_bf16_6528_8_64_128_", False, False, False),
    ("convert_divide_fusion_f32_48_65536_", False, False, False),
])
def test_the_mixers_operations_are_told_by_what_they_produce(
        label, in_proj, own, packed):
    dtype, dims = conv_math.label_shape(label)
    assert conv_math.is_in_proj(dims, LFM2) is in_proj
    assert taps(dims, dtype) is own
    assert packed_taps(dims, dtype) is packed
    assert label in RECORDED["op_s"]


def test_a_smaller_dispatchs_expert_rows_are_the_one_ambiguity():
    """[1024 x 4 experts a token, hidden] in float32 is [a rung, hidden] too:
    such a dispatch's gather reads as the convolution's (the share high,
    the roofline low, never over: conv_math.is_taps)."""
    assert taps([4096, 2048], "f32") and taps([512, 2048], "f32")
    assert not taps([8192, 2048], "f32")  # past max_prefill_len
    assert not taps([4096, 2048], "bf16")
    assert taps([96, 2048], "f32")  # the lanes' two rows, of any type


def test_each_reader_on_the_recorded_trace():
    run = lfm2_run()
    proj = seconds(lambda dims, dtype: conv_math.is_in_proj(dims, LFM2))
    own = seconds(taps)
    share = read("conv.share", run)
    assert share == pytest.approx(100 * (proj + own) / RECORDED["busy_s"])
    assert 2 < share < 8 and proj > 3 * own
    packed = seconds(packed_taps)
    tokens = DISPATCHES * PACKED * 6
    must = tokens * 12_288 + DISPATCHES * SLICES * 6 * 2 * 8192
    roofline = read("conv.packed_roofline", run)
    assert roofline == pytest.approx(
        100 * must / 819e9 / 51.0 / (packed / RECORDED["window_s"]))
    assert 30 < roofline < 100
    # the decode steps' rows are in the share and not in the roofline
    assert own > packed > 0.7 * own


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_and_does_not_raise(name):
    """The parent's program: no counters of the convolution, none of its
    operations in a trace; another family's configuration under its own
    recorded trace; an untraced run; no peaks."""
    run = lfm2_run()
    parent = dict(
        run,
        before={k: v for k, v in run["before"].items() if "conv" not in k[0]},
        after={k: v for k, v in run["after"].items() if "conv" not in k[0]},
        trace=dict(run["trace"], op_s={
            "fusion_bf16_4096_4096_": 0.6,
            "paged_attention_decode_bf16_48_64_128_": 0.4}))
    assert read(name, parent) is None
    other = dict(run, hf_config=SOLAR, cell="solar-open2.long-doc-sat",
                 trace=dict(OTHER_FAMILY))
    assert read(name, other) is None
    assert read(name, dict(run, trace=None)) is None
    assert read(name, dict(run, trace={"busy_s": 0.0, "window_s": 0.0, "op_s": {}})) is None
    if "roofline" in name:
        assert read(name, dict(run, peaks=None)) is None
        # the counters there and the operations not (XLA fused them all into
        # the projections): nothing of the convolution's own to divide by
        assert read(name, dict(run, trace=dict(run["trace"], op_s={
            "fusion_bf16_4096_6144_": 1.0, "fusion_bf16_4096_2048_": 0.5}))) is None


def test_the_accepted_readers_the_cell_joins_read_it_as_they_stand():
    """Counters only: the routed pairs over the experts hit, the lanes'
    tails over the chip's memory."""
    run = lfm2_run()
    hits, pairs = 362 * 6 * (64 + 7 * 40), 362 * 6 * 4 * (4098 + 7 * 15)
    for name, value in (("engine_moe_expert_hits_total", hits),
                        ("engine_moe_assignments_total", pairs)):
        run["before"][key(name, model_name="bench")] = 10.0
        run["after"][key(name, model_name="bench")] = 10.0 + value
    assert read("moe.rows_per_expert", run) == pytest.approx(pairs / hits)
    for kind, held in (("ssm", 0.0), ("conv", 48 * 49152.0), ("window_kv", 0.0),
                       ("shared_kv", 5000 * 64 * 4096.0)):
        run["after"][key("engine_state_bytes", model_name="bench", kind=kind)] = held
    assert read("cache.state_hbm_share", run) == pytest.approx(
        100 * (48 * 49152 + 5000 * 64 * 4096) / PEAKS["hbm_bytes"])
    # `expert_math` keys on `n_routed_experts`, which this file lacks
    assert read("moe.experts_share", run) is None
    assert read("moe.experts_roofline", run) is None
