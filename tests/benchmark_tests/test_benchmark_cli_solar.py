"""The Kimi-delta and expert-share cell's whole flow at its rehearsal size
on the CPU."""

import json
import subprocess

from bench_paths import ROOT
from test_benchmark_cli import RUN


def test_cpu_rehearsal_of_the_kimi_delta_expert_share_cell():
    """`solar-open2.long-doc-sat`: one gated GQA row and three KDA rows, 4
    of 8 experts held in every layer, through the server child, the probes,
    the reference child (`--family solar_open2`), the shape grid, ramp and
    window; the readers of the cell that need no chip run on its counters."""
    proc = subprocess.run(
        RUN + ["--workload", "solar-open2.long-doc-sat", "--seed",
               str(2**31 + 49), "--seconds", "4", "--trace", "0",
               "--mode", "rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["platform"] == "cpu"
    assert "metrics" not in line and "device" not in line
    assert line["correct"] is True and line["reasons"] == []
    # six clients, each at least one request: the tiny model's loop over
    # pieces is slow on a CPU that five other test workers share
    assert line["requests_attempted"] >= 6 and line["requests_failed"] == 0
    assert line["compiles_in_window"] == 0
    assert line["shapes_compiled"] in (0, 6)
    assert line["reference_max_gap"] <= 0.05
    readers = line["per_layer_readers_ok"]
    assert {"moe.rows_per_expert", "moe.held_pair_share",
            "cache.pool_fill_share", "dispatch.padded_share",
            "dispatch.step_ms"} <= set(readers)
