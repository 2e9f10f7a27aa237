"""The server child does not outlive the `run.py` that started it, however
that is ended (the ledger's `process_left_running`, PR 44: a run killed at
its limit left a server holding the chip).  A child process starts a
sleeping stand-in through `kbench.server.Server` and is killed."""

import os
import signal
import subprocess
import sys
import time

import pytest
from bench_paths import BENCH

STARTS_A_SERVER = """
import sys, time
sys.path.insert(0, sys.argv[1])
from kbench import server
server.Server.ENTRY = ("-c", "import time; time.sleep(600)")
child = server.Server({}, "cpu", sys.argv[2], "lifetime")
print(child.proc.pid, flush=True)
time.sleep(600)
"""


def alive(pid: int) -> bool:
    """A process that runs: not gone, and not a zombie nobody has reaped."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rpartition(")")[2].split()[0] != "Z"
    except FileNotFoundError:
        return False


@pytest.mark.parametrize("how", [signal.SIGTERM, signal.SIGKILL])
def test_the_server_dies_with_the_run_that_started_it(tmp_path, how):
    run = subprocess.Popen(
        [sys.executable, "-c", STARTS_A_SERVER, BENCH, str(tmp_path)],
        stdout=subprocess.PIPE, text=True)
    server_pid = None
    try:
        server_pid = int(run.stdout.readline())
        assert alive(server_pid)
        run.send_signal(how)
        code = run.wait(10.0)
        # SIGTERM: the handler's exit, through the callers' `finally`
        assert code == (128 + how if how == signal.SIGTERM else -how)
        deadline = time.monotonic() + 5.0
        while alive(server_pid) and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not alive(server_pid)
    finally:
        run.kill()
        run.wait(10.0)
        run.stdout.close()
        if server_pid is not None and alive(server_pid):
            os.kill(server_pid, signal.SIGKILL)
