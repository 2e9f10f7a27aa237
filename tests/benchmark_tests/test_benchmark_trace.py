"""The reduction from a profiler trace to busy time, per-operation time and
idle gaps: on hand-made events whose answers are known, on a small trace
recorded on the chip, and through the real xplane reader on a CPU trace."""

import json
import os

import pytest
from bench_paths import BENCH  # noqa: F401

from kbench import xplane_reduce as xr

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6  # ns


@pytest.mark.parametrize("name,label,opcode", [
    ("rev.17 = f32[48,151936]{1,0:T(8,128)S(1)} reverse(f32[48,151936]{1,0} %x), dimensions={1}",
     "rev_f32_48_151936_", "reverse"),
    ("%sort.12 = (f32[48,151936]{1,0}, s32[48,151936]{1,0}) sort(f32[48,151936] %a, s32[] %b)",
     "sort_f32_48_151936_", "sort"),
    ("custom-call.33 = bf16[48,32,128]{2,1,0:T(8,128)(2,1)} custom-call(bf16[48,32,128] %x), custom_call_target=\"tpu_custom_call\"",
     "custom-call_bf16_48_32_128_", "custom-call"),
    ("all-reduce-start.5 = bf16[48,4096]{1,0} all-reduce-start(bf16[48,4096] %x)",
     "all-reduce-start_bf16_48_4096_", "all-reduce-start"),
    ("while.4 = (s32[]{:T(128)}, s32[48]{0:T(128)}) while((s32[]) %tuple.1), condition=%c",
     "while_s32__", "while"),
    ("jit_step", "jit_step", ""),
])
def test_parse_op(name, label, opcode):
    assert xr.parse_op(name) == (label, opcode)


def synthetic():
    """Device 0: a 100 ms `while` that holds a 40 ms sort and a 20 ms kernel;
    a gap of 50 ms; a 30 ms all-reduce.  Device 1: busy 90 ms."""
    dev0 = [["while_s32__", 0, 100 * MS, "while"],
            ["sort_f32_48_151936_", 10 * MS, 40 * MS, "sort"],
            ["custom-call_bf16_48_32_128_", 60 * MS, 20 * MS, "custom-call"],
            ["all-reduce_bf16_48_4096_", 150 * MS, 30 * MS, "all-reduce"]]
    dev1 = [["fusion_bf16_48_9728_", 5 * MS, 90 * MS, "fusion"]]
    host = [["$engine.py:1929 _run_loop", 0, 200 * MS],
            ["$engine.py:3144 _step_mixed", 95 * MS, 60 * MS],
            ["$sampling.py:68 from_params", 120 * MS, 10 * MS]]
    return {"devices": {"0": dev0, "1": dev1}, "host": host, "lines": {}}


def test_reduce_on_known_events():
    r = xr.reduce(synthetic())
    assert r["n_devices"] == 2
    assert r["window_s"] == pytest.approx(0.200)
    # union per device, averaged over the devices: (130 + 90) / 2 ms
    assert r["busy_s"] == pytest.approx(0.110)
    # self times: the while keeps 100 - 40 - 20 = 40 ms; per chip = / 2
    assert r["op_s"]["while_s32__"] == pytest.approx(0.020)
    assert r["opcode_s"]["sort"] == pytest.approx(0.020)
    assert sum(r["opcode_s"].values()) == pytest.approx(r["busy_s"])
    assert xr.share_of_busy(r, lambda op: op == "sort") == pytest.approx(100 * 20 / 110)
    assert xr.share_of_busy(r, xr.is_collective) == pytest.approx(100 * 15 / 110)
    # device 0's gaps: 100-150 ms (its middle, 125 ms, lies in from_params)
    # and 180-200 ms (only the run loop is under way)
    gaps = dict(r["idle_gaps"])
    assert gaps["sampling.py:68 from_params"] == pytest.approx(0.050)
    assert gaps["engine.py:1929 _run_loop"] == pytest.approx(0.020)
    assert r["device_ops"][0][0] == "fusion_bf16_48_9728_"


def test_reduce_of_an_empty_trace_reports_no_busy_time():
    r = xr.reduce({"devices": {}, "host": [], "lines": {}})
    assert r["busy_s"] == 0.0 and r["device_ops"] == []
    assert xr.share_of_busy(r, lambda op: True) is None


def test_reduce_on_a_trace_recorded_on_the_chip():
    """A trimmed extract of a `qwen3-4b.chat` capture on a TPU v5e (the
    first operations of device 0 and the longest host events): the numbers
    below were read off it once and must not move."""
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        recorded = json.load(f)
    assert recorded["lines"] == {
        "/device:TPU:0": ["XLA Modules", "XLA Ops", "Async XLA Ops", "TC Overlay"]}
    r = xr.reduce(recorded)
    with open(os.path.join(HERE, "recorded_trace.expected.json")) as f:
        expected = json.load(f)
    assert r["n_devices"] == 1
    assert r["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(expected["window_s"], rel=1e-9)
    assert [name for name, _ in r["device_ops"]] == expected["top_ops"]
    assert sum(r["opcode_s"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert 0.0 < r["busy_s"] <= r["window_s"]
    assert all(seconds > 0 for _, seconds in r["idle_gaps"])


def test_extract_reads_a_real_xplane(tmp_path):
    """`extract()` through jax.profiler.ProfileData on a trace made here: no
    device plane on the CPU, the host's python events are there."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.sort(jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    path = xr.newest_xplane(str(tmp_path))
    assert path and path.endswith(".xplane.pb")
    extracted = xr.extract(path)
    assert extracted["devices"] == {}
    assert any("stop_trace" in name or "sort" in name.lower()
               for name, _, _ in extracted["host"])
    assert xr.reduce(extracted)["busy_s"] == 0.0
