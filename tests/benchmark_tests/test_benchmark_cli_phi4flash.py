"""The new cell's whole flow at its rehearsal size on the CPU."""

import json
import subprocess

from bench_paths import ROOT
from test_benchmark_cli import RUN


def test_cpu_rehearsal_of_the_hybrid_cell():
    """`phi4-mini-flash.reason-sat`: an 8-layer model with every mixer kind
    and a window of 8 that every request outruns, through the server child,
    the probes, the reference child (`--family phi4flash`), the shape grid,
    ramp and window; every reader of the cell runs on its counters."""
    proc = subprocess.run(
        RUN + ["--workload", "phi4-mini-flash.reason-sat", "--seed",
               str(2**31 + 29), "--seconds", "4", "--trace", "0",
               "--mode", "rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["platform"] == "cpu"
    assert "metrics" not in line and "device" not in line
    assert line["correct"] is True and line["reasons"] == []
    assert line["requests_attempted"] > 20 and line["requests_failed"] == 0
    assert line["compiles_in_window"] == 0
    assert line["shapes_compiled"] in (0, 6)
    assert line["reference_max_gap"] <= 0.05
    assert "dispatch.step_ms" in line["per_layer_readers_ok"]
