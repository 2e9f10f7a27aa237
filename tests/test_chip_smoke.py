"""chip_smoke.py off the chip: the CLI must refuse to pass, and its request
and validation logic must keep working against the server it drives (here a
tiny CPU server, through `run_leg` — `main()` only ever builds TPU legs)."""

import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from conftest import HAS_TPU  # noqa: E402


@pytest.mark.skipif(HAS_TPU, reason="this host has a TPU")
def test_no_chip_no_result():
    """The driver's contract: without an accelerator chip_smoke.py exits
    non-zero within seconds and prints no result line."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no tpu device" in proc.stderr
    assert time.monotonic() - t0 < 60


def _tiny_leg(**kw) -> chip_smoke.Leg:
    return chip_smoke.Leg(
        name="tiny on the CPU",
        server_args=["--model_config=tiny", "--kv_pages=512"],
        n_devices=1,
        concurrent=(20, 60, 300),  # 300 > max_prefill_len: chunked
        sequential=(24, 300),
        stream_len=24,
        logprobs_len=300,
        **kw)


def test_leg_logic_against_a_cpu_server(tmp_path):
    """Every phase of a leg — state checks, concurrent / streamed /
    repeated / logprobs requests, the zero-compile warm repeat, SIGTERM —
    against the real server entry point."""
    chip_smoke.run_leg(
        _tiny_leg(expect_mixed_attention="xla_ragged_gather"),
        "cpu", str(tmp_path))


def test_leg_fails_when_mixed_is_not_the_pallas_kernel(tmp_path):
    """The gate that keeps an `*_xla` path from reporting success: a leg
    with the default expectation (the Pallas ragged kernel) fails against
    a server that built `mixed` on the XLA gather."""
    with pytest.raises(chip_smoke.SmokeFailure, match="xla_ragged_gather"):
        chip_smoke.run_leg(_tiny_leg(), "cpu", str(tmp_path))
