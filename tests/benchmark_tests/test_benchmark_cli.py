"""The command as the driver runs it: it measures only on a TPU, and its
CPU rehearsal prints counts only, under other names."""

import json
import os
import subprocess
import sys
import time

import pytest
from bench_paths import BENCH, ROOT

sys.path.insert(0, os.path.join(ROOT, "tests"))
from conftest import HAS_TPU  # noqa: E402

RUN = [sys.executable, os.path.join(BENCH, "run.py")]


@pytest.mark.skipif(HAS_TPU, reason="this host has a TPU")
def test_no_chip_no_result():
    """Without an accelerator the measurement path exits non-zero within
    seconds and prints no result line: it never falls back to the CPU."""
    t0 = time.monotonic()
    proc = subprocess.run(
        RUN + ["--workload", "qwen3-4b.decode-sat", "--seed", "1",
               "--seconds", "2", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "before turning ready" in proc.stderr
    assert time.monotonic() - t0 < 120


def test_unknown_workload_exits_non_zero_with_no_result():
    proc = subprocess.run(
        RUN + ["--workload", "nope.chat", "--seed", "1", "--seconds", "2"],
        cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no workload" in proc.stderr


def test_cpu_rehearsal_prints_counts_only():
    """The whole flow at a tiny size on the CPU: server child, synthetic
    tokenizer, probes, reference child, shape grid, ramp, window, probes
    again.  Its one line carries counts and `correct`, and neither
    `metrics` nor `device`: nothing a CPU measured appears under the name
    of a device metric."""
    proc = subprocess.run(
        RUN + ["--workload", "qwen3-4b.chat", "--seed", str(2**31 + 7),
               "--seconds", "4", "--trace", "0", "--mode", "rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["platform"] == "cpu"
    assert "metrics" not in line and "device" not in line
    assert line["correct"] is True and line["reasons"] == []
    assert line["requests_attempted"] == 12 and line["requests_failed"] == 0
    assert line["compiles_in_window"] == 0
    # the grid reached every pair of the rehearsal's buckets (none is
    # compiled where an earlier run left them in the checkout's AOT cache)
    assert line["shapes_compiled"] in (0, 6)
    assert line["reference_max_gap"] <= 0.05
    assert "service.ttft_p50_ms" in line["per_layer_readers_ok"]
