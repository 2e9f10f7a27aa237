"""The per-layer readers PR 43 adds, on the operation table of a recorded
trace of the cell (recorded_command_a_ops.json: a `--trace 1` run on the
chip) and hand-made counters: each finds what the program publishes, and
each returns nothing (and does not raise) for a program without its counter
or operations, as the parent commit is, for another family's configuration,
and for an untraced or chipless run where it needs the trace or the peaks."""

import json
import os

import pytest
from bench_paths import BENCH

from kbench import cohere_math, manifest, state_math

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(BENCH, "configs", "command-a-plus.json")) as _f:
    COMMAND_A = {k: v for k, v in json.load(_f).items() if k not in manifest.OWN_KEYS}
with open(os.path.join(BENCH, "configs", "nemotron3-nano.json")) as _f:
    NEMOTRON = {k: v for k, v in json.load(_f).items() if k not in manifest.OWN_KEYS}
with open(os.path.join(HERE, "recorded_command_a_ops.json")) as _f:
    RECORDED = json.load(_f)
PEAKS = manifest.load_peaks("TPU v5 lite")
NEW = ("attention.window_ragged_share", "attention.window_ragged_roofline",
       "attention.window_bound_share", "moe.held_gated_roofline")
FLAGS = {"max_batch_size": 32, "page_size": 64, "kv_pages": 4352, "tp": 1,
         "max_prefill_len": 4096, "max_model_len": 8192}
CELL = "command-a-plus.mixed-len-sat"
#: a window of 51 s that held 225 dispatches: a packed step of 32 one-token
#: slices of lanes at ~4500 tokens and ~1350 prompt tokens whose queries see
#: ~2000 keys each, then 7 decode steps over 32 lanes; 3 window layers
DISPATCHES = 225.0
QUERIES = DISPATCHES * 3 * (32 + 1350)
PAIRS = DISPATCHES * 3 * (32 * 4096 + 1350 * 2000)
KEYS = DISPATCHES * 3 * (32 * 4095 + 1350 + 2000)


def key(name, **labels):
    return (name, frozenset(labels.items()))


def command_a_run():
    lane_steps = DISPATCHES * 7 * 32
    routed = DISPATCHES * 8 * (3 * (1382 + 7 * 32) + (32 + 7 * 32))
    here = 0.125 * routed
    hits = DISPATCHES * 4 * (16 + 7 * 14)
    names = {
        "engine_dispatches_total": (60.0, DISPATCHES, dict(program="mixed")),
        "engine_moe_assignments_total": (1.0e6, here, {}),
        "engine_moe_pairs_elsewhere_total": (7.0e6, routed - here, {}),
        "engine_moe_expert_hits_total": (1.0e4, hits, {}),
    }
    before = {key(n, model_name="bench", **ls): v for n, (v, _, ls) in names.items()}
    after = {key(n, model_name="bench", **ls): v + d for n, (v, d, ls) in names.items()}
    for bound, share in (("yes", 0.44), ("no", 0.56)):
        k = key("engine_window_lane_steps_total", model_name="bench", bound=bound)
        before[k], after[k] = 5.0e4, 5.0e4 + share * lane_steps
    for unit, n in (("queries", QUERIES), ("pairs", PAIRS), ("keys", KEYS)):
        k = key("engine_window_ragged_work_total", model_name="bench", unit=unit)
        before[k], after[k] = 1.0e6, 1.0e6 + n
    return {
        "cell": CELL, "chips": 1, "seconds": 51.0, "hf_config": COMMAND_A,
        "flags": FLAGS, "peaks": PEAKS, "before": before, "after": after,
        "trace": {"busy_s": RECORDED["busy_s"], "window_s": RECORDED["window_s"],
                  "op_s": dict(RECORDED["op_s"])},
    }


def read(name, run):
    return manifest.load_reader(name).read(run)


def labelled(prefix):
    return sum(s for label, s in RECORDED["op_s"].items() if label.startswith(prefix))


def test_bytes_and_operations_from_the_configurations_sizes():
    assert cohere_math.is_cohere2_moe(COMMAND_A)
    assert not cohere_math.is_cohere2_moe(NEMOTRON)
    assert cohere_math.window_layers(COMMAND_A) == 3
    assert cohere_math.kv_token_bytes(COMMAND_A) == 4096
    assert cohere_math.ring_bytes_per_lane(COMMAND_A) == 50_331_648
    # a pair: q.k and p.v for 128 heads of 128; a key: K and V of 8 heads;
    # a query: 128 heads of 128 in and out
    assert cohere_math.window_ragged_flops(COMMAND_A, 1) == 4 * 128 * 128
    assert cohere_math.window_ragged_bytes(COMMAND_A, 1, 0) == 4096
    assert cohere_math.window_ragged_bytes(COMMAND_A, 0, 1) == 2 * 128 * 128 * 2
    # three matrices an expert (kbench/nemotron_math.py counts ungated ones)
    assert cohere_math.held_expert_bytes(COMMAND_A) == 3 * 4096 * 4096 * 2 == 100_663_296
    assert cohere_math.held_pair_flops(COMMAND_A) == 6 * 4096 * 4096


def test_the_recorded_trace_holds_the_kernels_by_name():
    labels = set(RECORDED["op_s"])
    # the packed step's window kernel by buffer length (rows = T x 16), the
    # decode kernel over a ring, the full layer's, the page write
    assert {"window_attention_ragged_bf16_8_65536_128_",
            "window_attention_ragged_bf16_8_4096_128_",
            "window_attention_decode_bf16_32_128_128_",
            "paged_attention_decode_bf16_32_128_128_"} <= labels
    assert any(label.startswith("kv_page_write") for label in labels)
    assert any(label.startswith("ragged-dot") for label in labels)
    # no gathered ring a block of queries, [blocks, 64, 2, 8, 64, 128] (the
    # buffer's own K/V as 64 pages, [64, 2, 8, 64, 128], is the kernel's
    # second source), and no scores over a ring
    dims = [state_math.label_dims(label) or [] for label in labels]
    assert [64, 2, 8, 64, 128] in dims
    assert not [d for d in dims if len(d) == 6 and d[1:] == [64, 2, 8, 64, 128]]
    assert not [d for d in dims if len(d) >= 4 and d[-1] == 4096 and 8 in d[-3:-1]]
    assert cohere_math.kernel_seconds(RECORDED) == pytest.approx(
        labelled("window_attention_ragged"))
    assert 0.3 < cohere_math.kernel_seconds(RECORDED) < 0.5


def test_each_reader_on_the_recorded_trace():
    run = command_a_run()
    kernel = labelled("window_attention_ragged")
    share = read("attention.window_ragged_share", run)
    assert share == pytest.approx(100 * kernel / RECORDED["busy_s"])
    assert 5 < share < 15
    flops_s = PAIRS * 4 * 128 * 128 / 197e12
    bytes_s = (KEYS * 4096 + QUERIES * 65536) / 819e9
    assert flops_s > bytes_s  # operations bind in this mix
    roofline = read("attention.window_ragged_roofline", run)
    assert roofline == pytest.approx(
        100 * flops_s / 51.0 / (kernel / RECORDED["window_s"]))
    assert 0 < roofline < 100
    assert read("attention.window_bound_share", run) == pytest.approx(44.0)
    matmul = labelled("ragged-dot")
    hits = DISPATCHES * 4 * (16 + 7 * 14)
    pairs = 0.125 * DISPATCHES * 8 * (3 * (1382 + 7 * 32) + (32 + 7 * 32))
    least = max(hits * 100_663_296 / 819e9, pairs * 6 * 4096 * 4096 / 197e12)
    assert least == hits * 100_663_296 / 819e9  # bytes bind: ~11 rows an expert
    share = read("moe.held_gated_roofline", run)
    assert share == pytest.approx(
        100 * least / 51.0 / (matmul / RECORDED["window_s"]))
    assert 0 < share < 100
    # the accepted readers the cell is appended to find their counters and
    # kernels too
    assert read("moe.held_pair_share", run) == pytest.approx(12.5)
    assert read("moe.rows_per_expert", run) == pytest.approx(pairs / hits)
    assert read("attention.window_share", run) == pytest.approx(
        100 * labelled("window_attention_decode") / RECORDED["busy_s"])
    # those that look for another family's keys find nothing here
    for other in ("moe.experts_share", "moe.experts_roofline",
                  "moe.held_experts_roofline", "ssd.share"):
        assert read(other, run) is None


def test_bytes_bind_where_every_slice_is_one_token():
    """A decode-only dispatch's packed step: each lane reads its ring for
    one query."""
    run = command_a_run()
    for unit, n in (("queries", 32.0), ("pairs", 32 * 4096.0), ("keys", 32 * 4096.0)):
        k = key("engine_window_ragged_work_total", model_name="bench", unit=unit)
        run["after"][k] = run["before"][k] + DISPATCHES * 3 * n
    kernel = labelled("window_attention_ragged")
    bytes_s = DISPATCHES * 3 * (32 * 4096 * 4096 + 32 * 65536) / 819e9
    assert bytes_s > DISPATCHES * 3 * 32 * 4096 * 65536 / 197e12
    assert read("attention.window_ragged_roofline", run) == pytest.approx(
        100 * bytes_s / 51.0 / (kernel / RECORDED["window_s"]))


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_and_does_not_raise(name):
    """The parent's program: no window counters, none of the kernel's calls
    in its trace; another family's configuration; an untraced run; no
    peaks."""
    run = command_a_run()
    gone = ("engine_window", "engine_moe")
    parent = dict(
        run,
        before={k: v for k, v in run["before"].items() if not k[0].startswith(gone)},
        after={k: v for k, v in run["after"].items() if not k[0].startswith(gone)},
        trace=dict(run["trace"], op_s={
            "fusion_bf16_2048_2048_": 0.6,
            "window_attention_decode_bf16_48_40_128_": 0.4}))
    assert read(name, parent) is None
    if name == "attention.window_bound_share":  # counters alone
        assert read(name, dict(run, after=run["before"])) is None
    elif name == "attention.window_ragged_share":  # the trace alone
        assert read(name, dict(run, trace=None)) is None
    else:
        assert read(name, dict(run, hf_config=NEMOTRON,
                               cell="nemotron3-nano.agent-long-sat")) is None
        assert read(name, dict(run, trace=None)) is None
        assert read(name, dict(run, peaks=None)) is None
