"""The short-convolution cell's whole flow at its rehearsal size on the
CPU."""

import json
import subprocess

from bench_paths import ROOT
from test_benchmark_cli import RUN


def test_cpu_rehearsal_of_the_short_convolution_cell():
    """`lfm2-24b-a2b.extract-sat`: six gated short-convolution rows and two
    roped, QK-normed attention rows, two dense and six expert layers,
    through the server child, the probes, the reference child (`--family
    lfm2_moe`), the shape grid, ramp and window; the readers of the cell that
    need no chip run on its counters."""
    proc = subprocess.run(
        RUN + ["--workload", "lfm2-24b-a2b.extract-sat", "--seed",
               str(2**31 + 54), "--seconds", "4", "--trace", "0",
               "--mode", "rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["platform"] == "cpu"
    assert "metrics" not in line and "device" not in line
    assert line["correct"] is True and line["reasons"] == []
    assert line["requests_attempted"] >= 6 and line["requests_failed"] == 0
    assert line["compiles_in_window"] == 0
    assert line["shapes_compiled"] in (0, 6)
    assert line["reference_max_gap"] <= 0.05
    readers = line["per_layer_readers_ok"]
    assert {"moe.rows_per_expert", "cache.pool_fill_share",
            "dispatch.padded_share", "dispatch.step_ms"} <= set(readers)
